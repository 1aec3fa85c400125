"""Edge-node selections (``core/geometry.py``, ``core/dg3d.py``): device ms
per step of the ops under ``gather.edge_local``, each triangle's own values
at the two nodes of each edge, in every stage of the step.  ``None`` where
no op carries a ``stage.*`` scope; ``0.0`` where the stages are there and
the scope is not (a program that predates it)."""
SCOPES = ("gather.edge_local",)


def read(trace, ctx):
    staged = trace.op_seconds(lambda o: any(
        c.startswith("stage.") for c in o.scope.split("/")))
    if staged <= 0 or ctx.steps <= 0:
        return None          # no op carries a stage scope
    return 1e3 * trace.under(SCOPES) / ctx.steps
