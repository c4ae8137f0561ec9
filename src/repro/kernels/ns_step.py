"""Newton–Schulz inverse iteration as two fused Pallas matmuls:

    X' = X (2I − M X)  =  2 X − X (M X)

The identity never materializes: step 1 computes Z = M @ X; step 2 uses the
matmul kernel's epilogue (alpha=-1, beta=2, C=X) to fuse the subtraction.
This is the paper's S8 suggestion (Pan & Schreiber) made MXU-native — the
whole d³ inversion pipeline is plain matmul work.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.matmul import matmul


def ns_step(m, x, *, block: int = 128, interpret=None):
    """One Newton–Schulz iteration for M⁻¹. m, x: (d, d)."""
    z = matmul(m, x, bm=block, bn=block, bk=block, interpret=interpret)
    return matmul(x, z, c=x, alpha=-1.0, beta=2.0, bm=block, bn=block,
                  bk=block, interpret=interpret)


def ns_inverse(m, iters: int, *, block: int = 128, interpret=None):
    """Full inversion: cold start X0 = I/‖M‖_inf, then `iters` steps."""
    d = m.shape[-1]
    lam = jnp.max(jnp.sum(jnp.abs(m), axis=-1))
    x = jnp.eye(d, dtype=jnp.float32) / lam
    for _ in range(iters):
        x = ns_step(m, x, block=block, interpret=interpret)
    return 0.5 * (x + x.T)
