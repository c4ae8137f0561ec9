"""Share of the traced training window in which the device runs no
operation while the host is at one of the training loop's sync sites
(``host_sync_ms``'s spans) or making the step's inputs
(``train/step_inputs``): 100 × the part of the device's idle gaps
(``bench/lib/trace.py``) that those spans cover, over the window's
length, averaged over the devices.  Every gap counts, not only the
longest.  The instrumentation's own span (``train/emit``, present only in
a traced run) is not charged.  Where the window holds none of the spans
(a program without them) the reader returns nothing."""
from bench.lib import harness as H
from bench.lib import trace

SITES = H.load_module("metrics", "host_sync_ms").SITES + (
    "train/step_inputs",)


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    (start, end) intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx):
    lo, hi = ctx.lo, ctx.hi
    host = trace.union([h for h in trace.clip(ctx.trace["host"], lo, hi)
                        if h[0] in SITES])
    devs = ctx.trace["devices"]
    if not host or not devs or hi <= lo:
        return None
    idle = sum(overlap_ns(trace.gaps(ops, lo, hi), host)
               for ops in devs.values()) / len(devs)
    return 100.0 * idle / (hi - lo)
