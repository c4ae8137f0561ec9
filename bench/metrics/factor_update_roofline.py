"""The ``factor_update`` Pallas kernel's share of its roofline in the
traced training window: for every call of the kernel in the trace, the
least time the chip could take — max(FLOPs / peak FLOP/s, bytes / peak
HBM bytes/s), with FLOPs and bytes from the call's own shapes
(``bench/lib/flops.factor_update``) — summed, over the summed device
time of those calls.

The kernel has no name of its own in the trace: its op reads
``%vmap__.N = f32[L,d,d]{...} custom-call(...), custom_call_target=
"tpu_custom_call", operand_layout_constraints={f32[2]{0}, f32[L,n,d]{...},
f32[L,n,d]{...}, f32[L,d,d]{...}}, ...``: a Pallas call whose result is a
square float32 factor (stacked over L layers or not), whose first operand
is the two prefetched scalars and whose second is the (n, d) input it
contracts.  Other custom calls with square float32 results (the
compiler's ``ConcatBitcast`` and ``AllocateBuffer``) are not it.  Where
the trace holds no such call, the reader returns nothing."""
import re

from bench.lib import flops, trace

RESULT = re.compile(r"= f32\[(?:(\d+),)?(\d+),(\d+)\]\{[^}]*\} custom-call\(")
OPERANDS = re.compile(r"operand_layout_constraints=\{f32\[2\]\{[^}]*\}, "
                      r"f32\[(?:\d+,)?(\d+),(\d+)\]")


def _shapes(name: str):
    """(layers, n, d) of a ``factor_update`` call, or None for any other
    op."""
    r, o = RESULT.search(name), OPERANDS.search(name)
    if r is None or o is None or 'custom_call_target="tpu_custom_call"' \
            not in name:
        return None
    (layers, d, d2), (n, dx) = r.groups(), o.groups()
    if not d == d2 == dx:
        return None
    return int(layers or 1), int(n), int(d)


def read(ctx):
    dev = sorted(ctx.trace["devices"])[0]
    least = secs = 0.0
    for name, s, e in trace.clip(ctx.trace["devices"][dev], ctx.lo, ctx.hi):
        shapes = _shapes(name)
        if shapes is None:
            continue
        layers, n, d = shapes
        fl, by = flops.factor_update(n, d, stack=layers)
        least += max(fl / ctx.peaks["flops_per_s"],
                     by / ctx.peaks["hbm_bytes_per_s"])
        secs += (e - s) * 1e-9
    if secs <= 0:
        return None
    return 100.0 * least / secs
