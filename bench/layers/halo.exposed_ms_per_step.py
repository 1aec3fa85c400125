"""Exposed halo time: device ms per step, averaged over the chips, of the
collective ops' intervals (``collective-permute*``, the exchange's
``ppermute``) during which no other op runs on the same device: the part of
the exchange that nothing hides.  Only ops with nothing nested in them count
as running, so a loop around a collective does not hide it.  ``None`` where
the trace has no collective op."""
PREFIX = "collective-permute"


def _length(intervals):
    """Total length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for s, e in sorted(intervals):
        if reach is None or s > reach:
            total += e - s
            reach = e
        elif e > reach:
            total += e - reach
            reach = e
    return total


def exposed_ns(ops) -> float:
    """Summed over devices: the length of the collective ops' intervals
    that no other leaf op on that device covers."""
    total = 0.0
    for dev in {o.device for o in ops}:
        mine = [o for o in ops if o.device == dev]
        # a leaf op: its own time is its whole interval (nothing nested)
        leaves = [o for o in mine if o.dur_ns >= (o.end_ns - o.start_ns) - 1e-6]
        coll = [(o.start_ns, o.end_ns) for o in leaves
                if o.name.startswith(PREFIX)]
        other = [(o.start_ns, o.end_ns) for o in leaves
                 if not o.name.startswith(PREFIX)]
        # |C \ O| = |C u O| - |O|
        total += _length(coll + other) - _length(other)
    return total


def read(trace, ctx):
    if ctx.steps <= 0 or not any(o.name.startswith(PREFIX)
                                 for o in trace.ops):
        return None
    return 1e-6 * exposed_ns(trace.ops) / max(trace.n_devices, 1) / ctx.steps
