"""Operations and bytes computed from shapes: the yardstick's arithmetic.

Model FLOPs count what the model's forward and backward passes require
(a matmul of ``m×k`` by ``k×n`` is ``2mkn``; backward is twice forward),
never what a program recomputes or what the optimizer adds.  Kernel
counts follow each kernel's definition.  Copied from the shape
arithmetic of the program's CPU suites (``benchmarks/roofline.py``
``active_params``, ``benchmarks/bench_kernels.py`` ``factor_update``) so
that later changes to the program cannot move it.
"""
from __future__ import annotations


def tile_ok(*dims: int) -> bool:
    """Whether every dim tiles into 128-blocks (d % 128 == 0, or d ≤ 128
    and d % 8 == 0): the shapes on which the program's Pallas kernels run."""
    return all(d % 128 == 0 or (0 < d <= 128 and d % 8 == 0) for d in dims)


def llama_train_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward + backward FLOPs of one training step of a Llama-style
    decoder: 6 × (matmul parameters, head included) × tokens, plus causal
    attention (QKᵀ and PV over the lower triangle, ×3 for backward)."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim", d // h)
    kv = cfg["num_key_value_heads"] * hd
    f, v, n = cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]
    per_layer = d * h * hd + 2 * d * kv + h * hd * d + 3 * d * f
    matmul_params = n * per_layer + d * v          # the tied head
    tokens = batch * seq
    attn_fwd = n * 2 * 2 * batch * h * hd * seq * (seq + 1) / 2
    return 6.0 * matmul_params * tokens + 3.0 * attn_fwd


def mlp_train_flops(dims, batch: int) -> float:
    """Forward + backward FLOPs of an MLP with homogeneous inputs."""
    return 6.0 * batch * sum((dims[i] + 1) * dims[i + 1]
                             for i in range(len(dims) - 1))


def factor_update(n: int, d: int, stack: int = 1, itemsize: int = 4):
    """(flops, bytes) of the fused factor update C ← βC + αXᵀX with X
    (n, d), over ``stack`` stacked layers: 2nd² for XᵀX plus 3d² for the
    blend; X read once, C read and written once."""
    flops = stack * (2.0 * n * d * d + 3.0 * d * d)
    byts = stack * itemsize * (n * d + 2.0 * d * d)
    return flops, byts

