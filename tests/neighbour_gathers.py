"""Test helpers for the neighbour gathers.

The point-gather formulas that the record gathers replaced, as oracles: each
fetches neighbour values by (node, triangle) index pairs, `f[..., ext_na,
ext_tri]`, one gather per node picked.  And a reader of the neighbour
gathers in a compiled program's text.
"""
import re

import jax.numpy as jnp


def edge_ext_point(geom, f):
    """Neighbour nodal values facing my edge nodes a and b: two (..., 3, nt)."""
    return (f[..., geom.ext_na, geom.ext_tri],
            f[..., geom.ext_nb, geom.ext_tri])


def edge_ext_nodal6_point(geom, f):
    """(..., 6, nt) -> (..., 3edge, 2[a|b], 2[top|bot], nt), four gathers."""
    ta, tb = edge_ext_point(geom, f[..., 0:3, :])
    ba, bb = edge_ext_point(geom, f[..., 3:6, :])
    return jnp.stack([jnp.stack([ta, ba], axis=-2),
                      jnp.stack([tb, bb], axis=-2)], axis=-3)


def gather_ext_grad_point(geom, gradf):
    """(k, nl, 2qz, 2comp, nt) -> n . neighbour gradient (k, nl, 2qz, 3, nt),
    one gather per component."""
    ge_x = gradf[..., 0, :][..., geom.ext_tri]
    ge_y = gradf[..., 1, :][..., geom.ext_tri]
    return ge_x * geom.edge_nx + ge_y * geom.edge_ny


def hlo_neighbour_gathers(hlo):
    """The gathers of a compiled program's text that sit under a
    `gather.neighbour` scope, each as a dict: `operand` and `indices`
    (their dims), `layout` (the operand's minor-to-major order, or None),
    `start_index_map` and `slice_sizes`."""
    defs = {name: (dims, layout) for name, dims, layout in re.findall(
        r"(%[\w.\-]+) = \w+\[([\d,]*)\](?:\{([\d,]*)[^}]*\})?", hlo)}
    dims = lambda d: tuple(int(x) for x in d.split(",") if x)
    found = []
    for ln in hlo.splitlines():
        if not re.search(r"\sgather\(", ln) or "gather.neighbour" not in ln:
            continue
        operand = re.search(r"gather\((%[\w.\-]+), ", ln).group(1)
        found.append(dict(
            operand=dims(defs[operand][0]),
            layout=dims(defs[operand][1]) if defs[operand][1] else None,
            start_index_map=dims(re.search(r"start_index_map=\{([\d,]*)\}",
                                           ln).group(1)),
            slice_sizes=dims(re.search(r"slice_sizes=\{([\d,]*)\}",
                                       ln).group(1))))
    return found


def is_record_gather(g):
    """One index a record, by the triangle axis alone: a rank-1 or rank-2
    operand with the triangle axis last, each index (one number, on that
    axis) fetching all of the leading axis."""
    rank = len(g["operand"])
    return (rank <= 2 and g["start_index_map"] == (rank - 1,)
            and g["slice_sizes"] == g["operand"][:-1] + (1,))
