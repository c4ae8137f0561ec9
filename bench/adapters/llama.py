"""The ``llama`` family (``LlamaForCausalLM``): the program's ``LM`` at the
configuration's sizes, and its weights moved between the reference's
layout (``bench/reference/llama.py``: one flat dict of layer-stacked
matrices a block) and the program's (``attn`` and ``mlp`` groups)."""
from bench.lib import flops

_ATTN = ("wq", "wk", "wv", "wo")
_MLP = ("wg", "wu", "wd")


def model(cfg, kcfg, compute_dtype, mesh=None):
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
    return LM(mc, kcfg, mesh, compute_dtype=getattr(jnp, compute_dtype))


def to_program(p):
    b = p["blocks"][0]
    blk = {"ln1": b["ln1"], "ln2": b["ln2"],
           "attn": {k: b[k] for k in _ATTN}, "mlp": {k: b[k] for k in _MLP}}
    return {"embed": p["embed"], "final_ln": p["final_ln"], "blocks": (blk,)}


def from_program(p):
    b = p["blocks"][0]
    blk = {"ln1": b["ln1"], "ln2": b["ln2"], **b["attn"], **b["mlp"]}
    return {"embed": p["embed"], "final_ln": p["final_ln"], "blocks": (blk,)}


def train_flops(cfg, data):
    return flops.llama_train_flops(cfg, data["batch"], data["seq"])
