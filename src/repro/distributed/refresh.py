"""Block-parallel inverse refresh: shard_map over a flat device mesh.

The serial refresh computes every block's damped inverse (or EKFAC eigen
state) on every device — Σd³ work replicated P times.  Here each device
computes only the blocks a :class:`~repro.distributed.plan.RefreshPlan`
assigns it (``lax.cond`` keeps the unowned branches out of the device's
runtime work) and an all-gather — spelled as a ``psum`` of
owner-computed-else-zero trees — replicates the finished inverses back to
everyone.  Per-device work drops to ~Σd³/P (the plan's critical path).

The refresh runs on its *own* flat 1-axis mesh over the same devices as
the training mesh: it is dispatched as a separate jitted computation
anyway (serially on T3 steps in ``refresh_mode="sharded"``, asynchronously
in ``"overlap"``), so jit reshards the factor inputs in (they are small
next to the weights) and the output inverses land replicated, exactly like
the serial refresh produced them.

Numerics contract: each block's inverse is computed by exactly one device
with the same per-block math the serial path uses (``blk.damped_inverse``
/ ``blk.eigen_state``), and the combining psum only ever adds exact zeros
— so the sharded refresh is bitwise-identical to the serial one (pinned by
``tests/test_refresh_service.py`` on 1 device and
``tests/test_distributed_numerics.py`` on a forced 8-device CPU mesh).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.plan import CHAIN, RefreshPlan, build_plan
from repro.launch.mesh import make_mesh

AXIS = "shard"


def flat_refresh_mesh(mesh: Optional[Mesh] = None) -> Mesh:
    """1-axis ("shard",) mesh over the training mesh's devices (or all
    local devices when training runs meshless, e.g. CPU tests)."""
    devs = (np.asarray(mesh.devices).reshape(-1) if mesh is not None
            else np.asarray(jax.devices()))
    return make_mesh((devs.size,), (AXIS,), devices=devs)


def _zeros_like_shape(shapes):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def _owned(owner: int, compute, operand):
    """Run ``compute(operand)`` only on the owning shard; zeros elsewhere.

    ``lax.cond`` on the runtime ``axis_index`` keeps the d³ work off the
    7/8ths of devices that don't own the block — a ``where`` would compute
    everywhere and only *select* per device.
    """
    idx = jax.lax.axis_index(AXIS)
    shapes = jax.eval_shape(compute, operand)
    return jax.lax.cond(idx == jnp.uint32(owner), compute,
                        lambda _: _zeros_like_shape(shapes), operand)


def build_sharded_refresh(engine, mesh: Optional[Mesh] = None,
                          plan: Optional[RefreshPlan] = None):
    """Compile the block-parallel refresh for ``engine``.

    Returns a jitted ``refresh(factors, gamma, prev=None) -> inv`` whose
    output pytree matches ``KFACState.inv`` for the engine's ``inv_mode``
    (damped inverses, eigen states, plus the tridiagonal Ψ/Σ cache when
    the model has a chain).  ``prev`` is the previous inverse tree and is
    only consulted for Newton–Schulz hot starts (``inverse_method="ns"``),
    mirroring ``KFACEngine.refresh_inverses(hot=True)``.

    Attributes on the returned callable: ``.plan`` (the
    :class:`RefreshPlan`), ``.mesh`` (the flat shard mesh) and
    ``.lower(...)`` (for dry-run cost accounting).
    """
    cfg = engine.cfg
    blocks = engine.blocks
    chain = engine.chain
    eigen = engine.eigen
    use_prev = (not eigen) and cfg.inverse_method == "ns"
    fmesh = flat_refresh_mesh(mesh if mesh is not None else engine.mesh)
    if plan is None:
        plan = build_plan(blocks, fmesh.devices.size, chain=chain is not None)

    def _one_block(blk, fac, gamma, prev_blk):
        if eigen:
            return blk.eigen_state(fac, gamma)
        return blk.damped_inverse(fac, gamma, method=cfg.inverse_method,
                                  iters=cfg.ns_iters, prev=prev_blk)

    def _sharded(factors, gamma, prev):
        out = {}
        for name, blk in blocks.items():
            prev_blk = None if prev is None else prev.get(name)
            out[name] = _owned(
                plan.owners[name],
                lambda op, blk=blk: _one_block(blk, op[0], op[1], op[2]),
                (factors[name], gamma, prev_blk))
        if chain is not None:
            out[chain.TRI] = _owned(
                plan.owners[CHAIN],
                lambda op: chain.damped_inverse(op[0], op[1]),
                (factors, gamma))
        return jax.lax.psum(out, AXIS)

    mapped = jax.shard_map(_sharded, mesh=fmesh, in_specs=(P(), P(), P()),
                           out_specs=P(), check_vma=False)
    jitted = jax.jit(mapped)

    def refresh(factors, gamma, prev=None):
        return jitted(factors, gamma, prev if use_prev else None)

    refresh.plan = plan
    refresh.mesh = fmesh
    refresh.lower = lambda factors, gamma, prev=None: jitted.lower(
        factors, gamma, prev if use_prev else None)
    return refresh
