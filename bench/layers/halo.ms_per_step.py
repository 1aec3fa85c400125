"""Halo exchange (``distributed/halo.py``): device ms per step of the ops
under ``halo.exchange`` (the pack gathers, the ``ppermute`` collectives and
the unpack scatters of every exchange), averaged over the chips.  ``None``
where no op carries the scope: a one-chip program has no exchange."""
SCOPES = ("halo.exchange",)


def read(trace, ctx):
    t = trace.under(SCOPES)
    if t <= 0 or ctx.steps <= 0:
        return None
    return 1e3 * t / ctx.steps
