"""The whole step's share of the chip's peak, in %: the least time of one
step's work over the device's busy time per step in the traced window.

The least time is the larger of the step's operations over the compute peak
and its bytes over the HBM bandwidth (``bench/work.py``: ``step``).  Its
bytes cover every stage, the external burst's evaluations among them; its
operations count only the four kernels', a floor, and at the cells' sizes
the bytes bound the step 28 to 34 times over.  The time is the union of the
device's op intervals (``trace.busy_s``), so host gaps do not lower it:
``device.idle_share`` reads those.

On several chips the busy time is one chip's (averaged over the chips) and
the work one chip's share of the whole mesh's, ``step(conf)`` over the
chips: the ghost triangles a partition computes again are not work the step
needs."""
from bench import work


def read(trace, ctx):
    if not trace.ops or ctx.steps <= 0 or trace.busy_s <= 0:
        return None
    flops, nbytes = work.step(ctx.conf)
    lt, bound = work.least_seconds(flops, nbytes, ctx.peaks())
    busy_per_step = trace.busy_s / ctx.steps
    ctx.notes.append({"step_least_s": lt, "step_bound": bound,
                      "step_flops": flops, "step_bytes": nbytes,
                      "busy_s_per_step": busy_per_step})
    return 100.0 * lt / (ctx.chips * busy_per_step)
