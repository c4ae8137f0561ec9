"""Two-sided K-FAC preconditioning (paper S4.2):

    U = Ā⁻¹ V G⁻¹

as a pair of tiled Pallas matmuls (the (d_in, d_out) grad matrix stays in
HBM; tiles stream through VMEM)."""
from __future__ import annotations

from repro.kernels.matmul import matmul


def precondition(a_inv, v, g_inv, *, block: int = 128,
                 interpret=None):
    """a_inv: (d_in, d_in); v: (d_in, d_out); g_inv: (d_out, d_out)."""
    t = matmul(v, g_inv, bm=block, bn=block, bk=block, interpret=interpret)
    return matmul(a_inv, t, bm=block, bn=block, bk=block, interpret=interpret)
