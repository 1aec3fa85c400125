"""DG P1 geometry + quadrature machinery (2D triangles, extruded prisms).

Everything here is JAX-traceable; static mesh data lives in `mesh2d.Mesh2D`
(numpy) and is baked into a `Geom2D` pytree once at setup.

Layout conventions (TPU-minded: triangle index is always the minor axis — it
is the long, contiguous, lane-friendly dimension; see DESIGN.md §2):
  2D scalar field      f     : (3, nt)            [node, tri]
  2D vector field      v     : (2, 3, nt)         [comp, node, tri]
  3D scalar field      T     : (nl, 6, nt)        [layer, node, tri]
  3D vector field      u     : (2, nl, 6, nt)
  edge-quad values           : (3, 2, nt)         [edge, qp, tri]
  neighbour record           : (K, 3, nt)         [record, edge, tri]:
                               f.reshape(K, nt)[:, ext_tri], f's values on
                               the triangle across each edge; K is the size
                               of all f's axes but the last: 9 for the
                               burst's (3 fields, 3 nodes), k*nl*6 for 3D
                               fields (k, nl, 6, nt), k*nl*2qz*2 for their
                               gradients (k, nl, 2qz, 2, nt)

Quadrature (used uniformly for ALL terms so that discrete consistency —
free-surface vs continuity, tracer constancy — holds exactly):
  * triangle volume: 3 edge-midpoint points, weight A/3 (exact to degree 2)
  * edge: 2-point Gauss (exact to degree 3)
  * vertical: 2-point Gauss on [-1, 1]
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import mesh2d
from .mesh2d import EDGE_NODES, INTERIOR, OPEN, WALL

G_GRAV = 9.81

# local node ids of each local edge
EDGE_A = np.array([0, 1, 2])
EDGE_B = np.array([1, 2, 0])

# 2-point Gauss on s in [0,1]
S_GAUSS = np.array([0.5 - np.sqrt(3) / 6, 0.5 + np.sqrt(3) / 6])
W_GAUSS = np.array([0.5, 0.5])  # times edge length

# 2-point Gauss on zeta in [-1,1] (for vertical integration; weight 1 each)
Z_GAUSS = np.array([-1 / np.sqrt(3), 1 / np.sqrt(3)])
W_ZGAUSS = np.array([1.0, 1.0])

# triangle volume quadrature: edge midpoints, weights A/3
#   PHI_VQ[q, i] = phi_i(x_q)
PHI_VQ = np.array([[0.5, 0.5, 0.0],
                   [0.0, 0.5, 0.5],
                   [0.5, 0.0, 0.5]])
W_VQ = 1.0 / 3.0  # times area

# vertical P1 basis at the 2 Gauss points: row=qp, col=(top, bot)
PHI_ZQ = np.stack([(1 + Z_GAUSS) / 2, (1 - Z_GAUSS) / 2], axis=1)  # (2,2)
DPHI_ZQ = np.array([0.5, -0.5])  # d/dzeta of (top,bot) basis — constant


def _f(x, dtype):
    return jnp.asarray(np.asarray(x), dtype=dtype)


def _i(x):
    return jnp.asarray(np.asarray(x), dtype=jnp.int32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Geom2D:
    """Static per-triangle geometry + DG connectivity gathers (pytree)."""

    area: jax.Array       # (nt,)
    jh: jax.Array         # (nt,)  = 2*area
    dphi: jax.Array       # (3, 2, nt) physical gradients of P1 basis
    node_x: jax.Array     # (3, nt)
    node_y: jax.Array     # (3, nt)
    edge_len: jax.Array   # (3, nt)
    edge_nx: jax.Array    # (3, nt) outward unit normal
    edge_ny: jax.Array    # (3, nt)
    ext_tri: jax.Array    # (3, nt) int32 — neighbour triangle (self at boundary)
    ext_na: jax.Array     # (3, nt) int32 — neighbour-local node facing my node a
    ext_nb: jax.Array     # (3, nt) int32 — neighbour-local node facing my node b
    wall: jax.Array       # (3, nt) 1.0 on WALL edges
    openb: jax.Array      # (3, nt) 1.0 on OPEN edges

    @property
    def nt(self) -> int:
        return self.area.shape[-1]

    @property
    def interior(self) -> jax.Array:
        return 1.0 - self.wall - self.openb


def geom2d_from_mesh(mesh: mesh2d.Mesh2D, dtype=jnp.float32) -> Geom2D:
    p = mesh.node_xy()                      # (nt, 3, 2)
    area = mesh.areas()                     # (nt,)
    d1 = p[:, 1] - p[:, 0]
    d2 = p[:, 2] - p[:, 0]
    # physical gradients: inverse-transpose of [d1 d2] applied to ref grads
    # ref grads: phi0=(-1,-1), phi1=(1,0), phi2=(0,1)
    det = d1[:, 0] * d2[:, 1] - d1[:, 1] * d2[:, 0]  # = 2A > 0
    # J = [[d1x, d2x],[d1y, d2y]]; J^{-1} = adj(J)/det = [[d2y,-d2x],[-d1y,d1x]]/det
    inv_j = np.stack([
        np.stack([d2[:, 1], -d2[:, 0]], axis=-1),
        np.stack([-d1[:, 1], d1[:, 0]], axis=-1),
    ], axis=1) / det[:, None, None]          # (nt, 2, 2): J^{-1}
    gref = np.array([[-1.0, -1.0], [1.0, 0.0], [0.0, 1.0]])  # (3, 2)
    # physical grad: J^{-T} @ gref_n, i.e. dphi[n,d] = sum_c inv_j[c,d]*gref[n,c]
    dphi = np.einsum("tcd,nc->ndt", inv_j, gref)  # (3, 2, nt)

    # edges
    pa = p[:, EDGE_A]                       # (nt, 3, 2)
    pb = p[:, EDGE_B]
    ev = pb - pa
    elen = np.linalg.norm(ev, axis=-1)      # (nt, 3)
    # outward normal for CCW triangles: rotate edge vector by -90deg
    nx = ev[:, :, 1] / elen
    ny = -ev[:, :, 0] / elen

    # neighbour node matching: my edge (a,b) faces neighbour edge (a',b') with
    # a<->b' and b<->a' (opposite traversal).
    ne = mesh.neigh_edge                    # (nt, 3)
    ext_na = EDGE_NODES[ne, 1]              # b'
    ext_nb = EDGE_NODES[ne, 0]              # a'
    bnd = mesh.edge_type != INTERIOR
    # boundary: ext node = own node (ghost state mirrors interior)
    ext_na = np.where(bnd, EDGE_NODES[np.arange(3)[None, :], 0], ext_na)
    ext_nb = np.where(bnd, EDGE_NODES[np.arange(3)[None, :], 1], ext_nb)

    return Geom2D(
        area=_f(area, dtype),
        jh=_f(2 * area, dtype),
        dphi=_f(dphi, dtype),
        node_x=_f(p[:, :, 0].T, dtype),
        node_y=_f(p[:, :, 1].T, dtype),
        edge_len=_f(elen.T, dtype),
        edge_nx=_f(nx.T, dtype),
        edge_ny=_f(ny.T, dtype),
        ext_tri=_i(mesh.neigh_tri.T),
        ext_na=_i(ext_na.T),
        ext_nb=_i(ext_nb.T),
        wall=_f((mesh.edge_type == WALL).T, dtype),
        openb=_f((mesh.edge_type == OPEN).T, dtype),
    )


# ---------------------------------------------------------------------------
# Elementwise DG operations (2D). All support leading batch dims via vmap-free
# broadcasting: fields may have extra leading axes before (3, nt).
# ---------------------------------------------------------------------------
def grad2d(geom: Geom2D, f: jax.Array) -> jax.Array:
    """Constant per-triangle gradient of a P1 field: (..., 3, nt) -> (..., 2, nt)."""
    return jnp.einsum("...nt,ndt->...dt", f, geom.dphi)


def mass_apply(geom: Geom2D, f: jax.Array) -> jax.Array:
    """M f with M = (A/12)(I + ones): (..., 3, nt)."""
    s = f.sum(axis=-2, keepdims=True)
    return (geom.area / 12.0) * (f + s)


def minv_apply(geom: Geom2D, r: jax.Array) -> jax.Array:
    """M^{-1} r = (12/A)(r - sum(r)/4): (..., 3, nt)."""
    s = r.sum(axis=-2, keepdims=True)
    return (12.0 / geom.area) * (r - 0.25 * s)


def lumped_mass(geom: Geom2D) -> jax.Array:
    """Row-sum lumped mass (A/3 per node): (1, nt) broadcastable."""
    return (geom.area / 3.0)[None, :]


# --- edge quadrature ---------------------------------------------------------
_SQ = jnp.asarray(S_GAUSS)          # (2,)
_PHIA = 1.0 - _SQ                   # basis of node a at qps
_PHIB = _SQ


def edge_blend(fa: jax.Array, fb: jax.Array) -> jax.Array:
    """Values at the 2 Gauss points of each edge from the nodal values at its
    ends a and b: two (..., 3, nt) -> (..., 3, 2, nt) [edge, qp]."""
    return (fa[..., :, None, :] * _PHIA[:, None]
            + fb[..., :, None, :] * _PHIB[:, None])


def edge_interp(f: jax.Array) -> jax.Array:
    """Interior values at the 2 Gauss points of the 3 edges.

    f: (..., 3, nt) nodal -> (..., 3, 2, nt) [edge, qp].
    """
    with jax.named_scope("gather.edge_local"):
        fa = f[..., EDGE_A, :]      # (..., 3, nt)
        fb = f[..., EDGE_B, :]
    return edge_blend(fa, fb)


def neighbour_record(geom: Geom2D, f: jax.Array) -> jax.Array:
    """f's values on the triangle across each edge: (..., nt) -> (..., 3 edge,
    nt), one gather by ext_tri alone of f's flat (K, nt) record.

    A TPU gather costs per index, not per value, so the leading axes are
    gathered as one flat axis: on a TPU v5e at 52,480 triangles,
    (9, nt)[:, ext_tri] takes 0.29 ms, (3, 3, nt)[..., :, ext_tri] 1.16 ms
    and one point gather of a (3, nt) field 1.61 ms.  The rank-2 operand
    also fixes the layout: XLA reads each index as one contiguous record
    whatever nt is, where a point gather of a 3D field gets a triangle-minor
    (strided) operand at some sizes (nt = 51,842 on a v5e, PERF.md section 5).
    """
    with jax.named_scope("gather.neighbour"):
        rec = f.reshape(-1, f.shape[-1])[:, geom.ext_tri]
        return rec.reshape(f.shape[:-1] + rec.shape[-2:])


def edge_ext_record(geom: Geom2D, f: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Neighbour nodal values facing my edge nodes a and b: two (..., 3, nt).

    Fetches the neighbour's whole nodal record, (..., 3 node, 3 edge, nt),
    then picks the node facing my node a (ext_na) and the one facing b
    (ext_nb) by an exact 3-way select: the values of the point gathers
    f[..., ext_na, ext_tri] and f[..., ext_nb, ext_tri], bit for bit, from
    one gather for a whole stack of fields.  The burst's (eta, qx, qy) and
    the 3D stages' fields (`edge_interp_ext`, `dg3d.edge_ext_nodal6`) take
    their neighbour values here: the record's rank-2 operand keeps one
    contiguous record an index at every nt (`neighbour_record`).
    """
    rec = neighbour_record(geom, f)
    with jax.named_scope("gather.edge_local"):
        n0, n1, n2 = rec[..., 0, :, :], rec[..., 1, :, :], rec[..., 2, :, :]
        fa = jnp.where(geom.ext_na == 0, n0,
                       jnp.where(geom.ext_na == 1, n1, n2))
        fb = jnp.where(geom.ext_nb == 0, n0,
                       jnp.where(geom.ext_nb == 1, n1, n2))
    return fa, fb


def edge_interp_ext(geom: Geom2D, f: jax.Array) -> jax.Array:
    """Exterior (neighbour) values at my edge Gauss points: (..., 3, 2, nt)."""
    return edge_blend(*edge_ext_record(geom, f))


# scatter tensor: _EDGE_SCATTER[e, q, n] = w_q * phi_n(s_q) on edge e
# (node EDGE_A[e] carries _PHIA, node EDGE_B[e] carries _PHIB, third node 0);
# kept as numpy — its 12 nonzero entries are baked in as trace-time scalars
_EDGE_SCATTER = np.zeros((3, 2, 3))
for _e in range(3):
    _EDGE_SCATTER[_e, :, EDGE_A[_e]] += W_GAUSS * (1.0 - S_GAUSS)
    _EDGE_SCATTER[_e, :, EDGE_B[_e]] += W_GAUSS * S_GAUSS


def edge_scatter(geom: Geom2D, g: jax.Array) -> jax.Array:
    """Assemble edge integrals back onto nodes.

    g: (..., 3, 2, nt) integrand at edge Gauss points (WITHOUT the length
    jacobian). Returns (..., 3, nt): sum_e sum_q w_q * l_e/1 * phi_node(s_q) * g.
    Note: weights W_GAUSS already include the 1/2 of the [0,1]->[s] map, so the
    jacobian factor is just edge_len.

    The (edge, qp) -> node accumulation contracts against the precomputed
    scatter tensor _EDGE_SCATTER, unrolled over its 12 nonzero entries as
    trace-time scalars: this sits inside every lateral term, and both the
    seed per-edge .at[].add chain and a jnp.einsum contraction are ~8-14x
    slower on CPU XLA (the einsum lowers to transpose-heavy HLO; the
    unrolled form fuses into one elementwise pass over the qp array).
    """
    gw = g * geom.edge_len[:, None, :]
    cols = []
    for n in range(3):
        acc = None
        for e in range(3):
            for q in range(2):
                c = float(_EDGE_SCATTER[e, q, n])
                if c != 0.0:
                    term = c * gw[..., e, q, :]
                    acc = term if acc is None else acc + term
        cols.append(acc)
    return jnp.stack(cols, axis=-2)


# --- volume quadrature -------------------------------------------------------
_PHI_VQ = jnp.asarray(PHI_VQ)       # (q=3, node=3)


def vol_interp(f: jax.Array) -> jax.Array:
    """Nodal (..., 3, nt) -> values at the 3 volume qps (..., 3, nt)."""
    return jnp.einsum("qn,...nt->...qt", _PHI_VQ, f)


def vol_scatter(geom: Geom2D, g: jax.Array) -> jax.Array:
    """∫ phi_i g over each triangle, g given at volume qps.

    g: (..., 3, nt) at qps -> (..., 3, nt) nodal coefficients.
    """
    return jnp.einsum("qn,...qt->...nt", _PHI_VQ, g) * (geom.area / 3.0)
