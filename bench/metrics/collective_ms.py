"""Milliseconds per step of device time in cross-chip collectives: every
op of the traced window whose HLO opcode is an all-reduce, all-gather,
reduce-scatter, all-to-all or collective-permute (or the start or done of
an asynchronous one), its device time clipped to the window and summed
on each chip, averaged over the chips, and divided by the steps in the
window.  Where the window holds no such op the reader returns nothing."""
import re

from bench.lib import trace

# the opcode of an instruction ``%name = <shape> <opcode>(...)``; an
# operand named after a collective (``add(%all-reduce.3, ...)``) follows a
# ``%`` and is never followed by ``(``
OPCODE = re.compile(r"(?<![%\w.-])(all-reduce|all-gather|reduce-scatter|"
                    r"all-to-all|collective-permute)(?:-start|-done)?\(")


def is_collective(name: str) -> bool:
    return OPCODE.search(name) is not None


def read(ctx):
    devs = ctx.trace["devices"]
    if not devs or not ctx.steps:
        return None
    per_chip = [sum(e - s for name, s, e in trace.clip(ops, ctx.lo, ctx.hi)
                    if is_collective(name))
                for ops in devs.values()]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) * 1e-6 / ctx.steps
