"""Column kernels under ``shard_map`` (``kernels/``,
``distributed/ocean.py``): device ms per step of the compiled Pallas
kernels' events (TPU custom calls) under any ``kops.<op>.pallas`` scope,
averaged over the chips.  ``None`` where no kernel event carries such a
scope: a program that runs the column solves on the ``ref`` path has none."""


def _compiled_kernel_scope(component: str) -> bool:
    return component.startswith("kops.") and component.endswith(".pallas")


def read(trace, ctx):
    t = trace.op_seconds(lambda o: o.kernel and any(
        _compiled_kernel_scope(c) for c in o.scope.split("/")))
    if t <= 0 or ctx.steps <= 0:
        return None
    return 1e3 * t / ctx.steps
