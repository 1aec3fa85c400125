"""External-mode burst, edge states (``core/dg2d.py``): device ms per step
of the ops under ``burst.edge_states``, the interior and neighbour edge
values of each right-hand side and their ghost states.  ``None`` where no
op carries ``stage.external_burst``; ``0.0`` where the burst is there and
the sub-scope is not (a program that predates it)."""
ENCLOSING = ("stage.external_burst",)
SCOPES = ("burst.edge_states",)


def read(trace, ctx):
    if trace.under(ENCLOSING) <= 0 or ctx.steps <= 0:
        return None          # no op carries the burst's scope
    return 1e3 * trace.under(SCOPES) / ctx.steps
