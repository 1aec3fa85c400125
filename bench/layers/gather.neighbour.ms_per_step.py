"""Mesh-connectivity gathers (``core/geometry.py``, ``core/dg3d.py``):
device ms per step of the ops under ``gather.neighbour``, the values of the
neighbour across each edge, in every stage of the step.  ``None`` where no
op carries a ``stage.*`` scope; ``0.0`` where the stages are there and the
scope is not (a program that predates it)."""
SCOPES = ("gather.neighbour",)


def read(trace, ctx):
    staged = trace.op_seconds(lambda o: any(
        c.startswith("stage.") for c in o.scope.split("/")))
    if staged <= 0 or ctx.steps <= 0:
        return None          # no op carries a stage scope
    return 1e3 * trace.under(SCOPES) / ctx.steps
