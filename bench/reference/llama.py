"""Plain reference of a Llama-style decoder (SmolLM-135M and kin).

Straightforward ``jax.numpy``: token embedding, ``num_hidden_layers``
blocks of pre-norm grouped-query attention with rotary positions and a
SwiGLU MLP, a final RMS norm and a head tied to the embedding.  No
kernels, no cache, no batching tricks.  It imports nothing of the program
under test.

Weights are laid out the way the benchmark hands them to the program
(``make_params``): a dict with ``embed`` (V, d), ``final_ln`` (d,) and
``blocks`` = a 1-tuple of dicts of layer-stacked matrices, each stored
``(d_in, d_out)``.  RMS-norm scales are stored as offsets from one
(``y * (1 + scale)``), initialised to zero: the published
``(weight = ones)`` convention, reparametrised.

The K-FAC reference (``bench/lib/kfac_ref.py``) drives this model layer
by layer through :class:`Model`, which exposes the pieces it needs: the
loss and gradient, the Kronecker factor statistics of every tagged map
under targets sampled from the model, and exact-Fisher quadratic forms.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

LAYER_MATS = {  # K-FAC block name -> stacked weight key
    "q": "wq", "k": "wk", "v": "wv", "o": "wo",
    "gate": "wg", "up": "wu", "down": "wd",
}
# the head's sequence chunk: the program draws one key per chunk of this
# many positions for its sampled targets, and the reference draws the same
HEAD_CHUNK = 128


def sizes(cfg):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim", d // h)
    return dict(d=d, h=h, hkv=cfg["num_key_value_heads"], hd=hd,
                f=cfg["intermediate_size"], v=cfg["vocab_size"],
                n=cfg["num_hidden_layers"], eps=cfg["rms_norm_eps"],
                theta=cfg["rope_theta"])


def param_shapes(cfg):
    s = sizes(cfg)
    d, f, n = s["d"], s["f"], s["n"]
    qd, kvd = s["h"] * s["hd"], s["hkv"] * s["hd"]
    blk = {"ln1": (n, d), "ln2": (n, d),
           "wq": (n, d, qd), "wk": (n, d, kvd), "wv": (n, d, kvd),
           "wo": (n, qd, d), "wg": (n, d, f), "wu": (n, d, f),
           "wd": (n, f, d)}
    return {"embed": (s["v"], d), "final_ln": (d,), "blocks": (blk,)}


def make_params(cfg, key, dtype=jnp.float32):
    """Seeded weights in one jitted call: matrices N(0, 1/fan_in), the
    embedding N(0, 0.02²), norm offsets zero."""
    shapes = param_shapes(cfg)

    is_shape = lambda x: (isinstance(x, tuple)
                          and all(isinstance(i, int) for i in x))

    @jax.jit
    def make(key):
        flat, tree = jax.tree_util.tree_flatten_with_path(shapes,
                                                          is_leaf=is_shape)
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, shp) in zip(keys, flat):
            path = jax.tree_util.keystr(path)
            if "ln" in path:
                out.append(jnp.zeros(shp, dtype))
            elif "embed" in path:
                out.append((jax.random.normal(k, shp) * 0.02).astype(dtype))
            else:
                out.append((jax.random.normal(k, shp)
                            / math.sqrt(shp[-2])).astype(dtype))
        return jax.tree.unflatten(tree, out)

    return make(key)


def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, theta):
    """x: (B, T, H, hd); rotate the two halves of each head."""
    t, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def layer(s, p, h, probes=None):
    """One decoder block.  ``p``: this layer's weights; ``probes``: zero
    arrays added to each tagged map's output (their cotangents are the
    per-token pre-activation gradients).  Returns the new residual stream
    and, per tagged map, the sum of outer products of its inputs."""
    b, t, _ = h.shape
    aa = {}

    def lin(name, x):
        aa[name] = jnp.einsum("btd,bte->de", jax.lax.stop_gradient(x),
                              jax.lax.stop_gradient(x),
                              preferred_element_type=jnp.float32)
        y = x @ p[LAYER_MATS[name]].astype(x.dtype)
        return y if probes is None else y + probes[name].astype(y.dtype)

    a = rms(h, p["ln1"].astype(h.dtype), s["eps"])
    q = lin("q", a).reshape(b, t, s["h"], s["hd"])
    k = lin("k", a).reshape(b, t, s["hkv"], s["hd"])
    v = lin("v", a).reshape(b, t, s["hkv"], s["hd"])
    q, k = rope(q, s["theta"]), rope(k, s["theta"])
    g = s["h"] // s["hkv"]
    qg = q.reshape(b, t, s["hkv"], g, s["hd"])
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) / math.sqrt(s["hd"])
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    sc = jnp.where(causal, sc, jnp.asarray(-1e30, sc.dtype))
    pr = jax.nn.softmax(sc.astype(jnp.float32), axis=-1).astype(v.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", pr, v).reshape(b, t, -1)
    h = h + lin("o", o)
    a = rms(h, p["ln2"].astype(h.dtype), s["eps"])
    m = jax.nn.silu(lin("gate", a)) * lin("up", a)
    return h + lin("down", m), aa


def _layer_params(params, i):
    return {k: v[i] for k, v in params["blocks"][0].items()}


class Model:
    """What the K-FAC reference asks of a model, for this decoder.

    ``dtype`` is the compute type: the weights are cast to it on entry, as
    a program computing in that type would.  Tagged blocks: per map, a
    layer-stacked dense Kronecker pair; the embedding has a diagonal A
    (token frequencies) and a dense G on the model width.  The RMS-norm
    scales are untagged (diagonal curvature)."""

    def __init__(self, cfg, dtype=jnp.float32):
        self.cfg = cfg
        self.s = sizes(cfg)
        self.dtype = dtype
        s = self.s
        qd, kvd = s["h"] * s["hd"], s["hkv"] * s["hd"]
        dims = {"q": (s["d"], qd), "k": (s["d"], kvd), "v": (s["d"], kvd),
                "o": (qd, s["d"]), "gate": (s["d"], s["f"]),
                "up": (s["d"], s["f"]), "down": (s["f"], s["d"])}
        self.blocks = {name: dict(path=("blocks", 0, LAYER_MATS[name]),
                                  a_kind="full", g_kind="full",
                                  a_dim=di, g_dim=do)
                       for name, (di, do) in dims.items()}
        self.blocks["embed"] = dict(path=("embed",), a_kind="diag",
                                    g_kind="full", a_dim=s["v"],
                                    g_dim=s["d"])
        sf = partial(layer, s)
        self._fwd = jax.jit(lambda p, h: sf(p, h))
        self._bwd = jax.jit(self._layer_bwd)
        self._jvp = jax.jit(self._layer_jvp)
        self._head = jax.jit(self._head_chunk)
        self._ce = jax.jit(self._ce_chunk)
        self._quad = jax.jit(self._quad_chunk)

    # -- pieces --------------------------------------------------------
    def _cast(self, tree):
        return jax.tree.map(lambda x: x.astype(self.dtype), tree)

    def _layer_bwd(self, p, h, dh_true, dh_samp):
        p = self._cast(p)
        zeros = {n: jnp.zeros(h.shape[:2] + (self.blocks[n]["g_dim"],),
                              self.dtype) for n in LAYER_MATS}
        out, vjp = jax.vjp(lambda p_, h_, z_: layer(self.s, p_, h_, z_)[0],
                           p, h, zeros)
        dp, dh_in, _ = vjp(dh_true.astype(out.dtype))
        _, dh_in_s, dz = vjp(dh_samp.astype(out.dtype))
        g = {n: jnp.einsum("btd,bte->de", dz[n], dz[n],
                           preferred_element_type=jnp.float32)
             for n in LAYER_MATS}
        return (jax.tree.map(lambda x: x.astype(jnp.float32), dp),
                dh_in.astype(jnp.float32), dh_in_s.astype(jnp.float32), g)

    def _layer_jvp(self, p, h, tp, th):
        p, tp = self._cast(p), self._cast(tp)
        f = lambda p_, h_: layer(self.s, p_, h_)[0]
        return jax.jvp(f, (p, h), (tp, th.astype(h.dtype)))

    def _final(self, params, h):
        return rms(h, params["final_ln"].astype(h.dtype), self.s["eps"])

    def _head_chunk(self, final_ln, embed, h, labels, key):
        """One sequence chunk of the head: CE against the true labels and
        against labels sampled from the model, with the gradients of the
        true loss (w.r.t. hidden, final norm, embedding) and of the
        sampled loss (w.r.t. hidden).  Sums, not means."""
        final_ln, embed = self._cast(final_ln), self._cast(embed)

        def ce(fl, e, hh, y):
            z = (rms(hh, fl, self.s["eps"]) @ e.T).astype(jnp.float32)
            lp = jax.nn.log_softmax(z, axis=-1)
            return -jnp.sum(jnp.take_along_axis(lp, y[..., None], -1)), z

        (lt, z), gt = jax.value_and_grad(ce, argnums=(0, 1, 2),
                                         has_aux=True)(final_ln, embed,
                                                       h.astype(self.dtype),
                                                       labels)
        ys = jax.random.categorical(key, z, axis=-1)
        (ls, _), gs = jax.value_and_grad(ce, argnums=2, has_aux=True)(
            final_ln, embed, h.astype(self.dtype), ys)
        f32 = lambda x: x.astype(jnp.float32)
        return lt, f32(gt[0]), f32(gt[1]), f32(gt[2]), f32(gs)

    def _ce_chunk(self, final_ln, embed, h, labels):
        """Summed true-label CE of one sequence chunk of the head."""
        final_ln, embed = self._cast(final_ln), self._cast(embed)
        z = (rms(h.astype(self.dtype), final_ln, self.s["eps"])
             @ embed.T).astype(jnp.float32)
        lp = jax.nn.log_softmax(z, axis=-1)
        return -jnp.sum(jnp.take_along_axis(lp, labels[..., None], -1))

    def _quad_chunk(self, e, te, hf, thf):
        """Σ over this chunk's tokens of δᵢᵀ J ᵀ F_R J δⱼ for a softmax
        output: Σ_c p_c żᵢ żⱼ − (Σ p żᵢ)(Σ p żⱼ)."""
        e, te = self._cast(e), self._cast(te)
        z = (hf @ e.T).astype(jnp.float32)
        zd = (jnp.einsum("mbtd,vd->mbtv", thf.astype(hf.dtype), e)
              + jnp.einsum("btd,mvd->mbtv", hf, te)).astype(jnp.float32)
        p = jax.nn.softmax(z, axis=-1)
        pz = jnp.einsum("btv,mbtv->mbt", p, zd)
        pzz = jnp.einsum("btv,mbtv,kbtv->mk", p, zd, zd)
        return pzz - jnp.einsum("mbt,kbt->mk", pz, pz)

    # -- what the K-FAC reference calls ---------------------------------
    def loss_grad_stats(self, params, batch, key):
        """Mean true-label loss, its gradient, and each tagged block's
        factor contributions (1/N-normalised) under sampled targets drawn
        from ``key`` the way the program's head draws them."""
        s = self.s
        tokens, labels = batch["tokens"], batch["labels"]
        b, t = tokens.shape
        n = b * t
        emb = params["embed"].astype(self.dtype)
        h = emb[tokens]
        hs, aas = [], []
        for i in range(s["n"]):
            hs.append(h)
            h, aa = self._fwd(self._cast(_layer_params(params, i)), h)
            aas.append(aa)
        nc = t // HEAD_CHUNK
        keys = jax.random.split(key, nc)
        lt = 0.0
        g_fl = jnp.zeros_like(params["final_ln"])
        g_emb = jnp.zeros(params["embed"].shape, jnp.float32)
        dh_t, dh_s = [], []
        for c in range(nc):
            sl = slice(c * HEAD_CHUNK, (c + 1) * HEAD_CHUNK)
            l_c, gfl, gemb, dht, dhs = self._head(
                params["final_ln"], params["embed"], h[:, sl], labels[:, sl],
                keys[c])
            lt = lt + l_c
            g_fl, g_emb = g_fl + gfl, g_emb + gemb
            dh_t.append(dht)
            dh_s.append(dhs)
        dh_t = jnp.concatenate(dh_t, axis=1) / n
        dh_s = jnp.concatenate(dh_s, axis=1) / n
        grads_blk = {k: [None] * s["n"] for k in params["blocks"][0]}
        gfac = {nm: [None] * s["n"] for nm in LAYER_MATS}
        for i in reversed(range(s["n"])):
            dp, dh_t, dh_s, g = self._bwd(_layer_params(params, i), hs[i],
                                          dh_t, dh_s)
            for k, v in dp.items():
                grads_blk[k][i] = v
            for nm, v in g.items():
                gfac[nm][i] = v
        g_emb = g_emb / n + jnp.zeros_like(g_emb).at[tokens].add(dh_t)
        grads = {"embed": g_emb, "final_ln": g_fl / n,
                 "blocks": ({k: jnp.stack(v) for k, v in grads_blk.items()},)}
        contrib = {nm: {"a": jnp.stack([aa[nm] for aa in aas]) / n,
                        "g": jnp.stack(gfac[nm]) * n}
                   for nm in LAYER_MATS}
        counts = jnp.zeros((s["v"],), jnp.float32).at[tokens.reshape(-1)].add(1.0)
        contrib["embed"] = {"a": counts / n,
                            "g": jnp.einsum("btd,bte->de", dh_s, dh_s) * n}
        return lt / n, grads, contrib

    def loss(self, params, batch):
        """Mean true-label loss alone (the forward pass)."""
        tokens, labels = batch["tokens"], batch["labels"]
        b, t = tokens.shape
        h = params["embed"].astype(self.dtype)[tokens]
        for i in range(self.s["n"]):
            h, _ = self._fwd(self._cast(_layer_params(params, i)), h)
        lt = 0.0
        for c in range(t // HEAD_CHUNK):
            sl = slice(c * HEAD_CHUNK, (c + 1) * HEAD_CHUNK)
            lt = lt + self._ce(params["final_ln"], params["embed"],
                               h[:, sl], labels[:, sl])
        return lt / (b * t)

    def fisher_quad(self, params, batch, tangents):
        """(m, m) matrix of δᵢᵀ F δⱼ with the exact Fisher of the
        predictive distribution, normalised like the mean loss."""
        s = self.s
        tokens = batch["tokens"]
        b, t = tokens.shape
        m = len(tangents)
        emb = params["embed"].astype(self.dtype)
        h = emb[tokens]
        th = jnp.stack([tg["embed"].astype(self.dtype)[tokens]
                        for tg in tangents])
        for i in range(s["n"]):
            p = _layer_params(params, i)
            outs = [self._jvp(p, h, _layer_params(tg, i), th[j])
                    for j, tg in enumerate(tangents)]
            h = outs[0][0]
            th = jnp.stack([o[1] for o in outs])
        fl = params["final_ln"].astype(self.dtype)
        hf, thf = [], []
        for j, tg in enumerate(tangents):
            y, ty = jax.jvp(lambda fl_, h_: rms(h_, fl_, s["eps"]),
                            (fl, h), (tg["final_ln"].astype(self.dtype),
                                      th[j]))
            hf = y
            thf.append(ty)
        thf = jnp.stack(thf)
        te = jnp.stack([tg["embed"] for tg in tangents])
        q = jnp.zeros((m, m), jnp.float32)
        for c in range(t // HEAD_CHUNK):
            sl = slice(c * HEAD_CHUNK, (c + 1) * HEAD_CHUNK)
            q = q + self._quad(params["embed"], te, hf[:, sl], thf[:, :, sl])
        return q / (b * t)

