"""Unified LM builder: one code path for all 10 assigned architectures.

Layers are organized as a repeating *pattern* of block positions (period =
lcm of the arch's alternation features: local/global attention, MoE
interleave, Mamba:attention ratio).  Parameters for each pattern position are
stacked over ``n_groups = n_layers / period`` and the forward is a
``lax.scan`` over groups — fast compiles, and K-FAC factors come out
naturally stacked (vmapped inverses).

Three execution paths share the block code:
  * train/eval forward  (optionally K-FAC-tagged, builds no cache)
  * prefill             (plain forward that also emits the decode cache)
  * decode_step         (one token against a full cache)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, KFACConfig
from repro.core import factors as F
from repro.kernels import ops
from repro.core.tags import LayerMeta, Tagger, merge_records
from repro.models import params as PM
from repro.models.conv import conv, conv_meta
from repro.models.head import head_logits, lm_head_loss
from repro.models.layers import attention, apply_rope, dense, rms_norm
from repro.models.moe import moe_ffn
from repro.models.rwkv import rwkv_channel_mix, rwkv_time_mix
from repro.models.ssm import dt_rank, mamba_block
from repro.utils.sharding import axis_size, batch_axes, constrain, pick_shard

AUX_LOSS_WEIGHT = 0.01


@dataclass(frozen=True)
class BlockSpec:
    pos: int
    attn: str            # global | local | mamba | rwkv
    mlp: str             # dense | moe | rwkv_cm
    cross: bool = False  # enc-dec decoder cross-attention


def build_pattern(cfg: ModelConfig) -> List[BlockSpec]:
    if cfg.attn_free:
        return [BlockSpec(0, "rwkv", "rwkv_cm")]
    period = 1
    if cfg.alt_local_global:
        period = 2
    if cfg.n_experts and cfg.moe_every > 1:
        period = math.lcm(period, cfg.moe_every)
    if cfg.attn_every > 1:
        period = math.lcm(period, cfg.attn_every)
    assert cfg.n_layers % period == 0, (cfg.name, cfg.n_layers, period)
    out = []
    for i in range(period):
        if cfg.attn_every > 1:
            attn = "global" if cfg.is_attn_layer(i) else "mamba"
        elif cfg.alt_local_global:
            attn = "local" if i % 2 == 0 else "global"
        else:
            attn = "global"
        mlp = "moe" if cfg.is_moe_layer(i) else "dense"
        out.append(BlockSpec(i, attn, mlp, cross=cfg.encoder_layers > 0))
    return out


def sinusoid_posemb(t: int, d: int, offset=0):
    pos = jnp.arange(t, dtype=jnp.float32) + offset
    half = d // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None] * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


class LM:
    def __init__(self, cfg: ModelConfig, kfac: Optional[KFACConfig] = None,
                 mesh=None, compute_dtype=jnp.float32, fsdp: bool = True):
        self.cfg = cfg
        self.kfac = kfac or KFACConfig()
        self.mesh = mesh
        self.cdtype = compute_dtype
        self.fsdp = fsdp
        self.pattern = build_pattern(cfg)
        self.period = len(self.pattern)
        self.n_groups = cfg.n_layers // self.period
        self.defs = self._param_defs()
        self.metas = self._layer_metas()
        self.contract_map = self._contract_map()
        self.gcontract_map = {}   # fused_stats G-side hooks (core/fused)

    # ------------------------------------------------------------------
    # parameter definitions
    # ------------------------------------------------------------------
    def _fs(self, dim):
        from repro.utils.sharding import pick_shard as _ps
        return _ps(dim, self.mesh, "data") if self.fsdp else None

    def _pd(self, shape, axes, lead=(), **kw):
        spec = P(*((None,) * len(lead)), *axes)
        return PM.ParamDef(shape=tuple(lead) + tuple(shape), spec=spec, **kw)

    def _block_defs(self, spec: BlockSpec, lead):
        cfg, m = self.cfg, self.mesh
        d, f = cfg.d_model, cfg.d_ff
        qd, kvd = cfg.q_dim, cfg.kv_dim
        fs = self._fs(d)                   # fsdp axis for d_model dims
        tp_q = pick_shard(qd, m, "model")
        tp_kv = pick_shard(kvd, m, "model")
        tp_f = pick_shard(f, m, "model")
        p: Dict[str, Any] = {"ln1": self._pd((d,), (None,), lead, init="zeros")}
        if spec.attn in ("global", "local"):
            p["attn"] = {
                "wq": self._pd((d, qd), (fs, tp_q), lead),
                "wk": self._pd((d, kvd), (fs, tp_kv), lead),
                "wv": self._pd((d, kvd), (fs, tp_kv), lead),
                "wo": self._pd((qd, d), (tp_q, fs), lead),
            }
        elif spec.attn == "mamba":
            di = cfg.ssm_expand * d
            r = dt_rank(d)
            n = cfg.ssm_state_dim
            tp_di = pick_shard(di, m, "model")
            p["mamba"] = {
                "in_proj": self._pd((d, 2 * di), (fs, tp_di), lead),
                "conv_w": self._pd((cfg.ssm_conv_dim, di), (None, tp_di), lead,
                                   init="normal", scale=0.5),
                "x_proj": self._pd((di, r + 2 * n), (tp_di, None), lead),
                "dt_proj": self._pd((r, di), (None, tp_di), lead),
                "dt_bias": self._pd((di,), (tp_di,), lead, init="zeros"),
                "A_log": self._pd((di, n), (tp_di, None), lead, init="zeros"),
                "D": self._pd((di,), (tp_di,), lead, init="ones"),
                "out_proj": self._pd((di, d), (tp_di, fs), lead),
            }
        elif spec.attn == "rwkv":
            hd = cfg.rwkv_head_dim
            h = d // hd
            tp_d = pick_shard(d, m, "model")
            lora = 64 if d >= 64 else 16
            p["ln2"] = self._pd((d,), (None,), lead, init="zeros")
            vec = lambda init="normal": self._pd((d,), (None,), lead, init=init,
                                                 scale=0.02)
            p["rwkv"] = {
                "mu_r": vec(), "mu_k": vec(), "mu_v": vec(), "mu_g": vec(),
                "mu_w": vec(), "mu_cr": vec(), "mu_ck": vec(),
                "wr": self._pd((d, d), (fs, tp_d), lead),
                "wk": self._pd((d, d), (fs, tp_d), lead),
                "wv": self._pd((d, d), (fs, tp_d), lead),
                "wg": self._pd((d, d), (fs, tp_d), lead),
                "wo": self._pd((d, d), (tp_d, fs), lead),
                "w_lora_a": self._pd((d, lora), (fs, None), lead),
                "w_lora_b": self._pd((lora, d), (None, tp_d), lead,
                                     init="zeros"),
                "w0": self._pd((d,), (tp_d,), lead, init="ones"),
                "u": self._pd((d,), (tp_d,), lead, init="zeros"),
                "ln_x": self._pd((h, hd), (None, None), lead, init="zeros"),
                "cm_wr": self._pd((d, d), (fs, tp_d), lead),
                "cm_wk": self._pd((d, f), (fs, tp_f), lead),
                "cm_wv": self._pd((f, d), (tp_f, fs), lead),
            }
        if spec.cross:
            p["ln_cross"] = self._pd((d,), (None,), lead, init="zeros")
            p["cross"] = {
                "wq": self._pd((d, qd), (fs, tp_q), lead),
                "wk": self._pd((d, kvd), (fs, tp_kv), lead),
                "wv": self._pd((d, kvd), (fs, tp_kv), lead),
                "wo": self._pd((qd, d), (tp_q, fs), lead),
            }
        if spec.mlp == "dense":
            p["ln2"] = self._pd((d,), (None,), lead, init="zeros")
            p["mlp"] = {
                "wg": self._pd((d, f), (fs, tp_f), lead),
                "wu": self._pd((d, f), (fs, tp_f), lead),
                "wd": self._pd((f, d), (tp_f, fs), lead),
            }
        elif spec.mlp == "moe":
            e = cfg.n_experts
            ep = pick_shard(e, m, "model")
            p["ln2"] = self._pd((d,), (None,), lead, init="zeros")
            p["moe"] = {
                "router": self._pd((d, e), (fs, None), lead),
                "gate": self._pd((e, d, f), (ep, fs, None), lead),
                "up": self._pd((e, d, f), (ep, fs, None), lead),
                "down": self._pd((e, f, d), (ep, None, fs), lead),
            }
            if cfg.moe_shared_expert:
                p["moe_shared"] = {
                    "wg": self._pd((d, f), (fs, tp_f), lead),
                    "wu": self._pd((d, f), (fs, tp_f), lead),
                    "wd": self._pd((f, d), (tp_f, fs), lead),
                }
        # rwkv_cm handled inside the rwkv dict
        return p

    def _param_defs(self):
        cfg, m = self.cfg, self.mesh
        d, v = cfg.d_model, cfg.vocab_size
        lead = (self.n_groups,)
        defs: Dict[str, Any] = {
            "embed": self._pd((v, d), (pick_shard(v, m, "model"),
                                       self._fs(d)), init="embed"),
            "final_ln": self._pd((d,), (None,), init="zeros"),
            "blocks": tuple(self._block_defs(s, lead) for s in self.pattern),
        }
        if not cfg.tie_embeddings:
            defs["head"] = self._pd((d, v), (self._fs(d),
                                             pick_shard(v, m, "model")))
        if cfg.encoder_layers:
            defs["enc_blocks"] = self._enc_block_defs((cfg.encoder_layers,))
            defs["enc_final_ln"] = self._pd((d,), (None,), init="zeros")
        if cfg.frontend == "audio":
            # whisper Conv1D stem: mels -> d (k=3 s=1), d -> d (k=3 s=2);
            # weights stored as tap-major patch matrices, bias = last row
            defs["enc_conv1"] = self._pd((3 * cfg.n_mels + 1, d),
                                         (None, self._fs(d)))
            defs["enc_conv2"] = self._pd((3 * d + 1, d),
                                         (None, self._fs(d)))
        if cfg.frontend == "patch":
            p, ic = cfg.patch_size, cfg.image_channels
            defs["vis_patch"] = self._pd((p * p * ic + 1, d),
                                         (None, self._fs(d)))
        return defs

    def _enc_block_defs(self, lead):
        cfg, m = self.cfg, self.mesh
        d, f, qd, kvd = cfg.d_model, cfg.d_ff, cfg.q_dim, cfg.kv_dim
        fs = self._fs(d)
        return {
            "ln1": self._pd((d,), (None,), lead, init="zeros"),
            "attn": {
                "wq": self._pd((d, qd), (fs, pick_shard(qd, m, "model")), lead),
                "wk": self._pd((d, kvd), (fs, pick_shard(kvd, m, "model")), lead),
                "wv": self._pd((d, kvd), (fs, pick_shard(kvd, m, "model")), lead),
                "wo": self._pd((qd, d), (pick_shard(qd, m, "model"), fs), lead),
            },
            "ln2": self._pd((d,), (None,), init="zeros", lead=lead),
            "mlp": {
                "wg": self._pd((d, f), (fs, pick_shard(f, m, "model")), lead),
                "wu": self._pd((d, f), (fs, pick_shard(f, m, "model")), lead),
                "wd": self._pd((f, d), (pick_shard(f, m, "model"), fs), lead),
            },
        }

    # ------------------------------------------------------------------
    # K-FAC layer metadata
    # ------------------------------------------------------------------
    def _dense_meta(self, name, path, pdef: PM.ParamDef, n_stack, n_expert=0,
                    probe_tshard=False):
        kf = self.kfac
        tp = 1 if self.mesh is None else int(self.mesh.shape.get("model", 1))
        # feature axes are the last two spec entries / shape dims
        d_in, d_out = pdef.shape[-2], pdef.shape[-1]
        sp = pdef.spec
        in_ax, out_ax = sp[-2] if len(sp) >= 2 else None, sp[-1] if len(sp) >= 1 else None
        a_kind, a_blocks = F.factor_layout(d_in, in_ax == "model", tp,
                                           kf.max_factor_dim)
        g_kind, g_blocks = F.factor_layout(d_out, out_ax == "model", tp,
                                           kf.max_factor_dim)
        return LayerMeta(name=name, param_path=path, d_in=d_in, d_out=d_out,
                         kind="expert" if n_expert else "dense",
                         n_stack=n_stack, n_expert=n_expert,
                         a_kind=a_kind, g_kind=g_kind,
                         a_blocks=a_blocks, g_blocks=g_blocks,
                         probe_tshard=probe_tshard)

    def _layer_metas(self) -> Dict[str, LayerMeta]:
        cfg = self.cfg
        ng = self.n_groups
        metas: Dict[str, LayerMeta] = {}

        def add(name, path, n_expert=0, n_stack=ng, probe_tshard=False):
            pdef = self.defs
            for k in path:
                pdef = pdef[k]
            metas[name] = self._dense_meta(name, path, pdef, n_stack, n_expert,
                                           probe_tshard)

        for pos, spec in enumerate(self.pattern):
            b = f"blk{pos}"
            bp = ("blocks", pos)
            if spec.attn in ("global", "local"):
                for w in ("q", "k", "v", "o"):
                    # context-parallel attention: q/k/v outputs live
                    # sequence-sharded, so their probes follow suit
                    add(f"{b}.attn.{w}", bp + ("attn", f"w{w}"),
                        probe_tshard=w in ("q", "k", "v"))
            elif spec.attn == "mamba":
                for w in ("in_proj", "x_proj", "dt_proj", "out_proj"):
                    add(f"{b}.mamba.{w}", bp + ("mamba", w))
            elif spec.attn == "rwkv":
                for w in ("r", "k", "v", "g", "o", "w_lora_a", "w_lora_b"):
                    key = {"r": "wr", "k": "wk", "v": "wv", "g": "wg",
                           "o": "wo"}.get(w, w)
                    add(f"{b}.rwkv.{w}", bp + ("rwkv", key))
                for w, key in (("cm_r", "cm_wr"), ("cm_k", "cm_wk"),
                               ("cm_v", "cm_wv")):
                    add(f"{b}.rwkv.{w}", bp + ("rwkv", key))
            if spec.cross:
                for w in ("q", "k", "v", "o"):
                    add(f"{b}.cross.{w}", bp + ("cross", f"w{w}"))
            if spec.mlp == "dense":
                for w, key in (("gate", "wg"), ("up", "wu"), ("down", "wd")):
                    add(f"{b}.mlp.{w}", bp + ("mlp", key))
            elif spec.mlp == "moe":
                add(f"{b}.moe.router", bp + ("moe", "router"))
                for w in ("gate", "up", "down"):
                    add(f"{b}.moe.{w}", bp + ("moe", w), n_expert=cfg.n_experts)
                if cfg.moe_shared_expert:
                    for w, key in (("gate", "wg"), ("up", "wu"), ("down", "wd")):
                        add(f"{b}.moe_shared.{w}", bp + ("moe_shared", key))
        if cfg.encoder_layers:
            for w in ("q", "k", "v", "o"):
                add(f"enc.attn.{w}", ("enc_blocks", "attn", f"w{w}"),
                    n_stack=cfg.encoder_layers)
            for w, key in (("gate", "wg"), ("up", "wu"), ("down", "wd")):
                add(f"enc.mlp.{w}", ("enc_blocks", "mlp", key),
                    n_stack=cfg.encoder_layers)
        # modality frontends: KFC conv blocks (Grosse & Martens 1602.01407)
        if cfg.frontend == "audio":
            metas["enc.conv1"] = conv_meta(
                "enc.conv1", ("enc_conv1",), spatial=(3,), stride=(1,),
                c_in=cfg.n_mels, d_out=cfg.d_model, padding="SAME",
                max_factor_dim=self.kfac.max_factor_dim)
            metas["enc.conv2"] = conv_meta(
                "enc.conv2", ("enc_conv2",), spatial=(3,), stride=(2,),
                c_in=cfg.d_model, d_out=cfg.d_model, padding="SAME",
                max_factor_dim=self.kfac.max_factor_dim)
        if cfg.frontend == "patch":
            metas["vis.patch"] = conv_meta(
                "vis.patch", ("vis_patch",), spatial=(cfg.patch_size,) * 2,
                stride=(cfg.patch_size,) * 2, c_in=cfg.image_channels,
                d_out=cfg.d_model, padding="VALID",
                max_factor_dim=self.kfac.max_factor_dim)
        # embedding: diagonal A (token frequencies), full G on d_model
        metas["embed"] = LayerMeta(
            name="embed", param_path=("embed",), d_in=cfg.vocab_size,
            d_out=cfg.d_model, kind="embed", n_stack=0,
            a_kind="diag", g_kind="full")
        if not cfg.tie_embeddings:
            metas["lm_head"] = LayerMeta(
                name="lm_head", param_path=("head",), d_in=cfg.d_model,
                d_out=cfg.vocab_size, kind="head", n_stack=0,
                a_kind="full", g_kind="diag")
        return metas

    def _contract_map(self):
        cm = {}
        for name, meta in self.metas.items():
            if meta.kind in ("dense", "expert", "head"):
                cm[name] = partial(F.outer_sum, kind=meta.a_kind,
                                   blocks=meta.a_blocks,
                                   expert=meta.kind == "expert")
        return cm

    # ------------------------------------------------------------------
    # initialization / abstraction
    # ------------------------------------------------------------------
    def init_params(self, key, dtype=jnp.float32):
        params = PM.materialize(key, self.defs, dtype)
        # conv stems: zero the homogeneous bias rows (MLP/ConvNet convention;
        # materialize draws the full matrix like a weight)
        for name in ("enc_conv1", "enc_conv2", "vis_patch"):
            if name in params:
                params[name] = params[name].at[-1].set(0.0)
        return params

    def abstract_params(self, dtype=jnp.float32):
        return PM.abstract(self.defs, dtype, self.mesh)

    def param_shardings(self):
        return PM.shardings(self.defs, self.mesh)

    def n_params(self) -> int:
        return PM.count(self.defs)

    # ------------------------------------------------------------------
    # block application (shared by train / prefill / decode)
    # ------------------------------------------------------------------
    def _attn(self, tg, name, p, x, positions, *, window, cache=None,
              decode_pos=None, build_cache=False, causal=True, kv_x=None,
              page_table=None):
        cfg = self.cfg
        bsz, t, _ = x.shape
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        q = dense(tg, f"{name}.q", p["wq"], x).reshape(bsz, t, hq, hd)
        xk = x if kv_x is None else kv_x
        tk = xk.shape[1]
        k = dense(tg, f"{name}.k", p["wk"], xk).reshape(bsz, tk, hkv, hd)
        v = dense(tg, f"{name}.v", p["wv"], xk).reshape(bsz, tk, hkv, hd)
        # context-parallel attention (train/prefill): queries stay
        # sequence-sharded over `model` (head counts need not divide the
        # mesh); the small GQA K/V are gathered across it.  The attention is
        # then a single unscanned block so GSPMD slices the score tensor
        # along the sharded T_q dim (a q-chunk scan would sequentialize).
        # Constraints sit on the bf16 projections, *before* the f32 RoPE
        # internals, so the collectives move bf16.
        cp = (cache is None and self.mesh is not None
              and pick_shard(t, self.mesh, "model") is not None
              and bsz % axis_size(self.mesh, batch_axes(self.mesh)) == 0)
        q_chunk = t if cp else None
        if cp:
            ba = batch_axes(self.mesh)
            q = constrain(q, self.mesh, P(ba, "model", None, None))
            k = constrain(k, self.mesh, P(ba, None, None, None))
            v = constrain(v, self.mesh, P(ba, None, None, None))
        use_rope = cfg.family not in ("audio",) and kv_x is None
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            kpos = positions if decode_pos is None else positions
            k = apply_rope(k, kpos, cfg.rope_theta)
        new_cache = None
        kv_valid = None
        q_offset = None
        if cache is not None and page_table is not None:
            # block-indexed paged decode: scatter this token's K/V straight
            # into its physical page (page = table[b, pos//P], offset =
            # pos%P; idle rows land on the allocator's null page), then
            # attend the page pool in place through the page table — the
            # dense (B, S_view) gather view is never materialized.
            assert t == 1, "paged decode is one token per row"
            page_size = cache["k"].shape[2]        # (pages, hkv, P, hd)
            page = jnp.take_along_axis(
                page_table, (decode_pos // page_size)[:, None], axis=1)[:, 0]
            off = decode_pos % page_size
            ck = cache["k"].at[page, :, off].set(
                k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[page, :, off].set(
                v[:, 0].astype(cache["v"].dtype))
            o = ops.flash_decode_paged(q[:, 0], ck, cv, decode_pos + 1,
                                       page_table, window=window,
                                       cap=cfg.attn_softcap)
            o = o[:, None].astype(x.dtype)
            o = dense(tg, f"{name}.o", p["wo"], o.reshape(bsz, t, hq * hd))
            return o, {"k": ck, "v": cv}
        if cache is not None:          # decode: splice into cache, per row
            # decode_pos is a (B,) vector — continuous-batching slots sit at
            # *different* positions, so each row splices at its own offset
            bidx = jnp.arange(bsz)
            tidx = decode_pos[:, None] + jnp.arange(t)[None, :]
            ck = cache["k"].at[bidx[:, None], tidx].set(
                k.astype(cache["k"].dtype))
            cv = cache["v"].at[bidx[:, None], tidx].set(
                v.astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv}
            if t == 1:
                # serve path: one token per row against the full cache —
                # route through the flash-decode kernel (einsum fallback
                # masks per-row; Pallas gets the lengths via scalar
                # prefetch).  Row b attends exactly [0, decode_pos[b]].
                o = ops.flash_decode(
                    q[:, 0], ck.transpose(0, 2, 1, 3), cv.transpose(0, 2, 1, 3),
                    decode_pos + 1, window=window, cap=cfg.attn_softcap)
                o = o[:, None].astype(x.dtype)
                o = dense(tg, f"{name}.o", p["wo"], o.reshape(bsz, t, hq * hd))
                return o, new_cache
            k, v = ck, cv
            kv_valid = (jnp.arange(k.shape[1])[None, :]
                        <= decode_pos[:, None] + t - 1)
            q_offset = decode_pos
        elif build_cache and kv_x is None:
            new_cache = {"k": k.astype(self.cdtype), "v": v.astype(self.cdtype)}
        o = attention(q, k, v, causal=causal, window=window,
                      cap=cfg.attn_softcap, q_offset=q_offset,
                      kv_valid=kv_valid,
                      **({"q_chunk": q_chunk} if q_chunk else {}))
        o = dense(tg, f"{name}.o", p["wo"], o.reshape(bsz, t, hq * hd))
        return o, new_cache

    def _seq_shard(self, x):
        """Constrain a block output back to the T-sharded residual layout —
        GSPMD then emits a reduce-scatter instead of an all-reduce."""
        if self.mesh is None:
            return x
        ba = batch_axes(self.mesh)
        if (x.shape[0] % axis_size(self.mesh, ba)
                or x.shape[1] % axis_size(self.mesh, "model")):
            return x
        return constrain(x, self.mesh,
                         P(ba, "model", *((None,) * (x.ndim - 2))))

    def _full_t(self, x):
        """Constrain to full-T (batch-sharded only) — pins GSPMD's reshard
        point onto this bf16 tensor instead of some f32 internal."""
        if self.mesh is None:
            return x
        ba = batch_axes(self.mesh)
        if x.shape[0] % axis_size(self.mesh, ba):
            return x
        return constrain(x, self.mesh, P(ba, *((None,) * (x.ndim - 1))))

    def _mlp(self, tg, name, p, x):
        g = dense(tg, f"{name}.gate", p["wg"], x)
        u = dense(tg, f"{name}.up", p["wu"], x)
        return dense(tg, f"{name}.down", p["wd"], jax.nn.silu(g) * u)

    def _apply_block(self, spec: BlockSpec, p, tg: Tagger, h, positions,
                     enc_out=None, cache=None, decode_pos=None,
                     build_cache=False, page_table=None):
        cfg = self.cfg
        name = f"blk{spec.pos}"
        aux = jnp.float32(0.0)
        new_cache: Dict[str, Any] = {}
        eps = cfg.norm_eps

        if spec.attn == "rwkv":
            st_tm = None if cache is None else cache
            y, st = rwkv_time_mix(tg, f"{name}.rwkv", p["rwkv"],
                                  rms_norm(h, p["ln1"], eps), st_tm,
                                  head_dim=cfg.rwkv_head_dim)
            h = h + y
            y2, st2 = rwkv_channel_mix(tg, f"{name}.rwkv", p["rwkv"],
                                       rms_norm(h, p["ln2"], eps), st_tm)
            h = h + y2
            if cache is not None or build_cache:
                new_cache.update(st)
                new_cache.update(st2)
            return h, aux, new_cache

        if spec.attn == "mamba":
            y, st = mamba_block(tg, f"{name}.mamba", p["mamba"],
                                rms_norm(h, p["ln1"], eps),
                                cache if cache is not None else None,
                                ssm_state_dim=cfg.ssm_state_dim,
                                conv_dim=cfg.ssm_conv_dim, mesh=self.mesh)
            h = h + y
            if cache is not None or build_cache:
                new_cache.update(st)
        else:
            window = cfg.sliding_window if spec.attn == "local" else 0
            o, kvc = self._attn(tg, f"{name}.attn", p["attn"],
                                rms_norm(h, p["ln1"], eps), positions,
                                window=window,
                                cache=None if cache is None else
                                {"k": cache["k"], "v": cache["v"]},
                                decode_pos=decode_pos, build_cache=build_cache,
                                page_table=page_table)
            h = h + o
            if kvc is not None:
                new_cache.update(kvc)

        if spec.cross:
            o, xc = self._cross_attn(tg, f"{name}.cross", p["cross"],
                                     rms_norm(h, p["ln_cross"], eps),
                                     enc_out, cache)
            h = h + o
            if cache is not None:   # decode: carry the cross cache forward
                new_cache["xk"] = cache["xk"]
                new_cache["xv"] = cache["xv"]
            elif build_cache:
                new_cache.update(xc)

        if spec.mlp == "dense":
            h = h + self._mlp(tg, f"{name}.mlp", p["mlp"],
                              rms_norm(h, p["ln2"], eps))
        elif spec.mlp == "moe":
            x = rms_norm(h, p["ln2"], eps)
            y, a = moe_ffn(tg, f"{name}.moe", p["moe"], x,
                           n_experts=cfg.n_experts, top_k=cfg.top_k)
            if cfg.moe_shared_expert:
                y = y + self._mlp(tg, f"{name}.moe_shared", p["moe_shared"], x)
            h = h + y
            aux = aux + a
        return h, aux, new_cache

    def _cross_attn(self, tg, name, p, x, enc_out, cache):
        """Decoder cross-attention. At decode time k/v come from the cache."""
        cfg = self.cfg
        bsz, t, _ = x.shape
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        q = dense(tg, f"{name}.q", p["wq"], x).reshape(bsz, t, hq, hd)
        if cache is not None and "xk" in cache:
            k, v = cache["xk"], cache["xv"]
        else:
            tk = enc_out.shape[1]
            k = dense(tg, f"{name}.k", p["wk"], enc_out).reshape(bsz, tk, hkv, hd)
            v = dense(tg, f"{name}.v", p["wv"], enc_out).reshape(bsz, tk, hkv, hd)
        o = attention(q, k, v, causal=False)
        o = dense(tg, f"{name}.o", p["wo"], o.reshape(bsz, t, hq * hd))
        xk = {} if cache is not None else {"xk": k.astype(self.cdtype),
                                           "xv": v.astype(self.cdtype)}
        return o, xk

    # ------------------------------------------------------------------
    # encoder (whisper)
    # ------------------------------------------------------------------
    def _encoder(self, params, mels, tg: Tagger, tg_mode, probes):
        """Whisper encoder: Conv1D stem (k=3 s=1, then k=3 s=2, GELU after
        each — both KFC-tagged on the OUTER tagger) + full-attention stack.
        mels: (B, 2*encoder_seq, n_mels) raw log-mel frames."""
        cfg = self.cfg
        x = conv(tg, "enc.conv1", params["enc_conv1"],
                 mels.astype(self.cdtype), spatial=(3,), stride=(1,),
                 padding="SAME")
        x = jax.nn.gelu(x)
        x = conv(tg, "enc.conv2", params["enc_conv2"], x, spatial=(3,),
                 stride=(2,), padding="SAME")
        x = jax.nn.gelu(x)
        x = x + sinusoid_posemb(x.shape[1], cfg.d_model).astype(x.dtype)[None]
        pr = {k: v for k, v in (probes or {}).items()
              if k.startswith("enc.") and ".conv" not in k}

        def body(h, xs):
            p, prs = xs
            tg = Tagger(tg_mode, prs, self.contract_map, self.gcontract_map)
            o, _ = self._attn(tg, "enc.attn", p["attn"],
                              rms_norm(h, p["ln1"], cfg.norm_eps),
                              jnp.arange(h.shape[1]), window=0, causal=False)
            h = h + o
            h = h + self._mlp(tg, "enc.mlp", p["mlp"],
                              rms_norm(h, p["ln2"], cfg.norm_eps))
            return h, tg.out()

        h, recs = jax.lax.scan(jax.checkpoint(body), x,
                               (params["enc_blocks"], pr))
        return rms_norm(h, params["enc_final_ln"], cfg.norm_eps), recs

    # ------------------------------------------------------------------
    # full forwards
    # ------------------------------------------------------------------
    def _embed(self, params, tokens, tg: Tagger):
        x = jnp.take(params["embed"], tokens, axis=0).astype(self.cdtype)
        return tg.tag_embed("embed", tokens, x)

    def _backbone(self, params, x, positions, tg_mode, probes, enc_out=None):
        pr = {k: v for k, v in (probes or {}).items() if k.startswith("blk")}

        ba = batch_axes(self.mesh)
        b_ok = (self.mesh is not None
                and x.shape[0] % axis_size(self.mesh, ba) == 0)
        # sequence parallelism: the residual stream (and hence the per-layer
        # remat buffers) is sharded over `model` along T; blocks all-gather /
        # reduce-scatter at their boundaries (Megatron-SP pattern via GSPMD)
        t_ok = (self.mesh is not None
                and x.shape[1] % axis_size(self.mesh, "model") == 0)
        sp = P(ba if b_ok else None, "model" if t_ok else None, None)

        def body(carry, xs):
            h, auxl = carry
            bp, prs = xs
            if b_ok or t_ok:
                h = constrain(h, self.mesh, sp)
            tg = Tagger(tg_mode, prs, self.contract_map, self.gcontract_map)
            for pos, spec in enumerate(self.pattern):
                h, a, _ = self._apply_block(spec, bp[pos], tg, h, positions,
                                            enc_out=enc_out)
                auxl = auxl + a
            return (h, auxl), tg.out()

        (h, auxl), recs = jax.lax.scan(jax.checkpoint(body),
                                       (x, jnp.float32(0.0)),
                                       (params["blocks"], pr))
        return h, auxl, recs

    def _prepare_inputs(self, params, batch, tg: Tagger, probes, tg_mode):
        """Embed tokens + modality frontends. Returns (x, positions, labels,
        mask, enc_out, extra_recs)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        bsz, t = tokens.shape
        x = self._embed(params, tokens, tg)
        labels = batch["labels"]
        mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
        enc_out = None
        extra = {}
        if cfg.frontend == "patch":
            # Conv2D patchifier (KFC-tagged): raw images -> patch embeddings
            p = conv(tg, "vis.patch", params["vis_patch"],
                     batch["images"].astype(self.cdtype),
                     spatial=(cfg.patch_size,) * 2,
                     stride=(cfg.patch_size,) * 2, padding="VALID")
            p = p + sinusoid_posemb(p.shape[1], cfg.d_model
                                    ).astype(p.dtype)[None]
            x = jnp.concatenate([p, x], axis=1)
            pfx = jnp.zeros((bsz, p.shape[1]), labels.dtype)
            labels = jnp.concatenate([pfx, labels], axis=1)
            mask = jnp.concatenate([jnp.zeros_like(pfx, jnp.float32), mask],
                                   axis=1)
        elif cfg.frontend == "audio":
            enc_out, enc_recs = self._encoder(params, batch["mels"], tg,
                                              tg_mode, probes)
            extra.update(enc_recs)
            x = x + sinusoid_posemb(t, cfg.d_model).astype(x.dtype)[None]
        positions = jnp.arange(x.shape[1])
        return x, positions, labels, mask, enc_out, extra

    def head_weight(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["head"]

    def _cast_params(self, params):
        """One conversion at entry: everything downstream (activations,
        tangents, FSDP gathers) then lives in the compute dtype."""
        if self.cdtype == jnp.float32:
            return params
        return jax.tree.map(
            lambda x: x.astype(self.cdtype)
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
            else x, params)

    def loss(self, params, probes, batch, rng, mode: str = "plain"):
        """Returns ((loss_true, loss_sampled), aux)."""
        cfg = self.cfg
        params = self._cast_params(params)
        tg = Tagger(mode, probes, self.contract_map, self.gcontract_map)
        x, positions, labels, mask, enc_out, extra = self._prepare_inputs(
            params, batch, tg, probes, mode)
        h, auxl, recs = self._backbone(params, x, positions, mode, probes,
                                       enc_out)
        if self.mesh is not None:   # gather T for the (B, c)-tiled head
            ba = batch_axes(self.mesh)
            b_ok = x.shape[0] % axis_size(self.mesh, ba) == 0
            h = constrain(h, self.mesh, P(ba if b_ok else None, None, None))
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        lt, ls, metrics = lm_head_loss(
            tg, h, self.head_weight(params), labels, mask, rng,
            logit_cap=cfg.logit_softcap)
        loss_t = lt + AUX_LOSS_WEIGHT * auxl
        all_recs = merge_records(tg.out(), recs, extra)
        metrics["aux_loss"] = auxl
        return (loss_t, ls), {"recs": all_recs, "metrics": metrics}

    def loss_only(self, params, batch, rng):
        (lt, _), aux = self.loss(params, None, batch, rng, mode="plain")
        return lt, aux["metrics"]

    def hidden(self, params, batch):
        """Final normed hidden states (for exact-Fisher J-products, App C)."""
        params = self._cast_params(params)
        tg = Tagger("plain")
        x, positions, labels, mask, enc_out, _ = self._prepare_inputs(
            params, batch, tg, None, "plain")
        h, _, _ = self._backbone(params, x, positions, "plain", None, enc_out)
        if self.mesh is not None:
            ba = batch_axes(self.mesh)
            b_ok = x.shape[0] % axis_size(self.mesh, ba) == 0
            h = constrain(h, self.mesh, P(ba if b_ok else None, None, None))
        h = rms_norm(h, params["final_ln"], self.cfg.norm_eps)
        return h, labels, mask

    # ------------------------------------------------------------------
    # probes
    # ------------------------------------------------------------------
    def probe_shapes(self, batch_abs, params_abs=None):
        params_abs = params_abs or self.abstract_params()

        def f(p, b):
            (lt, ls), aux = self.loss(p, None, b, jax.random.PRNGKey(0),
                                      mode="shapes")
            return aux["recs"]

        return jax.eval_shape(f, params_abs, batch_abs)

    def _probe_spec(self, name: str, shape) -> P:
        """Sharding for a probe (and hence its g cotangent): batch over
        (pod, data), expert/model dims over model."""
        m = self.mesh
        meta = self.metas.get(name)
        axes = [None] * len(shape)
        i0 = 1 if (meta is not None and meta.n_stack) else 0
        ba = batch_axes(m)
        if m is not None and shape[i0] % axis_size(m, ba) == 0:
            axes[i0] = ba
        if meta is not None and meta.kind == "expert":
            axes[i0 + 1] = pick_shard(shape[i0 + 1], m, "model")
        elif meta is not None and meta.probe_tshard and len(shape) >= i0 + 3:
            # context-parallel outputs (attention q/k/v): sequence-sharded
            axes[-2] = pick_shard(shape[-2], m, "model")
        elif meta is not None and meta.g_kind == "block" and not (
                meta.probe_tshard):
            # model-shard the feature dim only when the G factor is blocked
            # along it (otherwise the full-G contraction would re-gather)
            axes[-1] = pick_shard(shape[-1], m, "model")
        elif len(shape) >= i0 + 3:
            # full-G layers: their outputs are model-replicated, so the
            # probe (and its cotangent) sequence-shards over model for free
            axes[-2] = pick_shard(shape[-2], m, "model")
        return P(*axes)

    def make_probes(self, shapes):
        out = {}
        for k, v in shapes.items():
            z = jnp.zeros(v.shape, self.cdtype)
            if self.mesh is not None:
                z = jax.lax.with_sharding_constraint(
                    z, jax.sharding.NamedSharding(self.mesh,
                                                  self._probe_spec(k, v.shape)))
            out[k] = z
        return out

    # ------------------------------------------------------------------
    # serving: prefill + decode
    # ------------------------------------------------------------------
    def prefill(self, params, batch, return_hidden: bool = False):
        """Full forward; returns (last-token logits, cache) — or
        (logits, cache, last-token hidden state) with ``return_hidden``
        (the post-final-norm ``(B, d_model)`` features the Laplace
        uncertainty head consumes; the default path is untouched so
        compiled serving graphs stay bitwise-identical)."""
        cfg = self.cfg
        params = self._cast_params(params)
        tg = Tagger("plain")
        x, positions, _, _, enc_out, _ = self._prepare_inputs(
            params, {"tokens": batch["tokens"],
                     "labels": jnp.zeros_like(batch["tokens"]),
                     **{k: v for k, v in batch.items()
                        if k in ("images", "mels")}}, tg, None, "plain")

        def body(h, bp):
            caches = {}
            for pos, spec in enumerate(self.pattern):
                h, _, c = self._apply_block(spec, bp[pos], tg, h, positions,
                                            enc_out=enc_out, build_cache=True)
                caches[f"pos{pos}"] = c
            return h, caches

        h, cache = jax.lax.scan(body, x, params["blocks"])
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        logits = head_logits(h[:, -1:, :], self.head_weight(params),
                             cfg.logit_softcap)
        if enc_out is not None:
            cache["enc_out"] = enc_out
        if return_hidden:
            return logits, cache, h[:, -1, :]
        return logits, cache

    def decode_step(self, params, cache, tokens, pos, page_table=None,
                    return_hidden: bool = False):
        """One decode step. tokens: (B, 1); pos: scalar int32 position, or a
        ``(B,)`` vector of *per-slot* positions (continuous batching: each
        slot splices and attends at its own offset).

        With ``page_table`` (a ``(B, max_blocks)`` int32 block table) the
        cache leaves are *page pools* ``(ng, num_pages, hkv, page_size,
        hd)`` shared by all rows: each attention layer scatters its one new
        KV row into the slot's physical page and attends block-indexed
        through the table (``ops.flash_decode_paged``) — no dense per-row
        cache view is built.  Without it the leaves are the dense
        ``(ng, B, S, hkv, hd)`` caches, spliced and attended as before.

        ``return_hidden`` additionally returns the post-final-norm
        ``(B, d_model)`` hidden state (Laplace uncertainty input)."""
        cfg = self.cfg
        params = self._cast_params(params)
        tg = Tagger("plain")
        x = self._embed(params, tokens, tg)
        pos = jnp.asarray(pos, jnp.int32)
        pos_vec = jnp.broadcast_to(pos.reshape(-1), (tokens.shape[0],))
        if cfg.frontend == "audio":
            half = cfg.d_model // 2
            freq = jnp.exp(-math.log(10000.0)
                           * jnp.arange(half, dtype=jnp.float32) / half)
            ang = pos_vec.astype(jnp.float32)[:, None] * freq[None, :]
            pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
            x = x + pe[:, None, :].astype(x.dtype)
        positions = pos_vec[:, None]
        enc_out = cache.get("enc_out") if isinstance(cache, dict) else None

        def body(h, xs):
            bp, cs = xs
            new_cs = {}
            for pos_i, spec in enumerate(self.pattern):
                h, _, c = self._apply_block(spec, bp[pos_i], tg, h, positions,
                                            enc_out=enc_out,
                                            cache=cs[f"pos{pos_i}"],
                                            decode_pos=pos_vec,
                                            page_table=page_table)
                new_cs[f"pos{pos_i}"] = c
            return h, new_cs

        layer_cache = {k: v for k, v in cache.items() if k.startswith("pos")}
        h, new_cache = jax.lax.scan(body, x, (params["blocks"], layer_cache))
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        logits = head_logits(h, self.head_weight(params), cfg.logit_softcap)
        if enc_out is not None:
            new_cache["enc_out"] = enc_out
        if return_hidden:
            return logits, new_cache, h[:, -1, :]
        return logits, new_cache

    # ------------------------------------------------------------------
    # cache construction (decode dry-run entry: a *full* cache of length S)
    # ------------------------------------------------------------------
    def cache_defs(self, batch_size: int, cache_len: int):
        """ParamDef tree for a decode cache (zeros init, proper shardings).

        Sharding: batch over (pod?, data) when it divides; otherwise the
        sequence dim is data-sharded (long-context decode with batch=1).
        """
        cfg, m = self.cfg, self.mesh
        ba = batch_axes(m) if m is not None else ("data",)
        bs_ok = m is not None and batch_size % axis_size(m, ba) == 0
        b_ax = ba if bs_ok else None
        # flash-decode layout: the cache sequence dim shards over `model`
        # (and over `data` too when the batch can't use it) — each shard
        # scores its local KV slice; softmax partials all-reduce tiny scalars
        s_axes = []
        if not bs_ok and pick_shard(cache_len, m, "data"):
            s_axes.append("data")
        if pick_shard(cache_len, m, "model"):
            s_axes.append("model")
        s_ax = tuple(s_axes) if s_axes else None
        hd_ax = None
        ng = self.n_groups

        def kv():
            return {
                "k": PM.ParamDef((ng, batch_size, cache_len, cfg.n_kv_heads,
                                  cfg.hd), P(None, b_ax, s_ax, None, hd_ax),
                                 init="zeros", dtype="bfloat16"),
                "v": PM.ParamDef((ng, batch_size, cache_len, cfg.n_kv_heads,
                                  cfg.hd), P(None, b_ax, s_ax, None, hd_ax),
                                 init="zeros", dtype="bfloat16"),
            }

        d = cfg.d_model
        di = cfg.ssm_expand * d
        tp_di = pick_shard(di, m, "model")
        tp_d = pick_shard(d, m, "model")
        caches = {}
        for pos, spec in enumerate(self.pattern):
            c = {}
            if spec.attn in ("global", "local"):
                c = kv()
            elif spec.attn == "mamba":
                c = {
                    "conv": PM.ParamDef((ng, batch_size, cfg.ssm_conv_dim - 1,
                                         di), P(None, b_ax, None, tp_di),
                                        init="zeros", dtype="bfloat16"),
                    "ssm": PM.ParamDef((ng, batch_size, di, cfg.ssm_state_dim),
                                       P(None, b_ax, tp_di, None),
                                       init="zeros"),
                }
            elif spec.attn == "rwkv":
                hd = cfg.rwkv_head_dim
                nh = d // hd
                c = {
                    "shift_tm": PM.ParamDef((ng, batch_size, d),
                                            P(None, b_ax, tp_d), init="zeros",
                                            dtype="bfloat16"),
                    "shift_cm": PM.ParamDef((ng, batch_size, d),
                                            P(None, b_ax, tp_d), init="zeros",
                                            dtype="bfloat16"),
                    "wkv": PM.ParamDef((ng, batch_size, nh, hd, hd),
                                       P(None, b_ax, None, None, None),
                                       init="zeros"),
                }
            if spec.cross:
                c["xk"] = PM.ParamDef((ng, batch_size, cfg.encoder_seq,
                                       cfg.n_kv_heads, cfg.hd),
                                      P(None, b_ax, None, None, hd_ax),
                                      init="zeros", dtype="bfloat16")
                c["xv"] = PM.ParamDef((ng, batch_size, cfg.encoder_seq,
                                       cfg.n_kv_heads, cfg.hd),
                                      P(None, b_ax, None, None, hd_ax),
                                      init="zeros", dtype="bfloat16")
            caches[f"pos{pos}"] = c
        if cfg.encoder_layers:
            caches["enc_out"] = PM.ParamDef(
                (batch_size, cfg.encoder_seq, d), P(b_ax, None, None),
                init="zeros", dtype="bfloat16")
        return caches
