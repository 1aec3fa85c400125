"""External-mode burst, sub-step assembly (``core/dg2d.py``): device ms per
step of the ops under ``burst.assembly``, the volume terms, numerical
fluxes, scatters, inverse mass and the SSPRK3 combinations.  ``None`` where
no op carries ``stage.external_burst``; ``0.0`` where the burst is there
and the sub-scope is not (a program that predates it)."""
ENCLOSING = ("stage.external_burst",)
SCOPES = ("burst.assembly",)


def read(trace, ctx):
    if trace.under(ENCLOSING) <= 0 or ctx.steps <= 0:
        return None          # no op carries the burst's scope
    return 1e3 * trace.under(SCOPES) / ctx.steps
