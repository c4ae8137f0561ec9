"""The system under test, built from a configuration and a traffic file.

This is the one place the benchmark touches the program: it builds the
model, the optimizer and the trainer the way the repository's training
launcher does, and moves weights between the reference's layout and the
program's.  Inputs and weights come from the benchmark (``bench/lib/
data.py``, ``bench/reference/*.py``), never from the program.
"""
from __future__ import annotations

import os
import sys
from types import SimpleNamespace

from bench.lib.harness import ROOT

SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


# -- weight layouts -----------------------------------------------------

_ATTN = ("wq", "wk", "wv", "wo")
_MLP = ("wg", "wu", "wd")


def llama_to_program(p):
    b = p["blocks"][0]
    blk = {"ln1": b["ln1"], "ln2": b["ln2"],
           "attn": {k: b[k] for k in _ATTN}, "mlp": {k: b[k] for k in _MLP}}
    return {"embed": p["embed"], "final_ln": p["final_ln"], "blocks": (blk,)}


def llama_from_program(p):
    b = p["blocks"][0]
    blk = {"ln1": b["ln1"], "ln2": b["ln2"], **b["attn"], **b["mlp"]}
    return {"embed": p["embed"], "final_ln": p["final_ln"], "blocks": (blk,)}


LAYOUT = {
    "llama": (llama_to_program, llama_from_program),
    "mlp_autoencoder": (lambda p: p, lambda p: p),
}


# -- the model ------------------------------------------------------------

def _llama_model(cfg, kcfg, compute_dtype):
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig
    from repro.models.lm import LM
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    mc = ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=d, n_heads=h, n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", d // h), d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        max_seq=cfg["max_position_embeddings"])
    return LM(mc, kcfg, None, compute_dtype=getattr(jnp, compute_dtype))


def _mlp_model(cfg, kcfg, compute_dtype):
    from repro.models.mlp import MLP
    enc = list(cfg["encoder"])
    return MLP(enc + enc[-2::-1], nonlin=cfg["nonlin"], loss=cfg["loss"])


MODELS = {"llama": _llama_model, "mlp_autoencoder": _mlp_model}


def kfac_config(opt: dict, obs_cfg):
    """KFACConfig from the traffic's optimizer entry: the keys it names,
    the program's defaults for the rest."""
    from repro.configs.base import KFACConfig
    kw = {k: v for k, v in opt.items() if k not in ("name", "family")}
    return KFACConfig(obs=obs_cfg, **kw)


def build(cfg: dict, traffic: dict, seed: int, *, trace: bool = False):
    """Model, optimizer and trainer, sharing one telemetry object; spans
    (with profiler annotations) only when ``trace``."""
    from repro import obs as obs_mod
    from repro import optimizers
    from repro.configs.base import ObsConfig, TrainConfig
    from repro.training.trainer import Trainer

    ocfg = ObsConfig(enabled=trace, trace_annotations=trace)
    obs = obs_mod.Obs(ocfg)
    opt_spec = traffic["optimizer"]
    kcfg = kfac_config(opt_spec, ocfg)
    model = MODELS[cfg["family"]](cfg, kcfg,
                                  cfg["precision"]["compute"])
    if opt_spec["name"] != "kfac":
        raise ValueError(f"unknown optimizer {opt_spec['name']!r}")
    opt = optimizers.kfac(model, kcfg, None,
                          family=opt_spec.get("family", "categorical"),
                          obs=obs)
    tcfg = TrainConfig(steps=0, seed=seed, log_every=1 << 30, obs=ocfg)
    trainer = Trainer(model, opt, tcfg, None, None, obs=obs)
    to_prog, from_prog = LAYOUT[cfg["family"]]
    return SimpleNamespace(model=model, opt=opt, trainer=trainer, obs=obs,
                           to_program=to_prog, from_program=from_prog)

