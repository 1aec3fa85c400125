"""Vertical (column) solvers — the computational heart of the paper.

1. Matrix-free solvers (paper §2.3, Algorithm 1): the systems for the
   hydrostatic pressure gradient r (D_vu r = F) and the vertical velocity w
   (D_vd w = F) have an a-priori-known bidiagonal-of-M_h structure.  After
   applying M_h^{-1} per face they reduce to prefix sums over layers:

     r_b^l = r_surf - sum_{k<=l}(g_t^k + g_b^k),   r_t^l = r_b^l + 2 g_b^l
     w_t^l = w_floor + sum_{k>=l}(g_t^k + g_b^k),  w_b^l = w_t^l - 2 g_t^l

   (derived from the D_vu/D_vd matrices in §2.3; verified against dense
   assembly in tests).  In JAX these are cumsums over the layer axis — the
   TPU analogue of the single-pass CUDA sweep.

2. Fully-assembled column operator (paper §2.4): implicit vertical
   advection + viscosity couples each prism's 6 nodes to the prisms above
   and below -> block-tridiagonal with 6x6 blocks.  We assemble
   (L, D, U) blocks and solve with a block-Thomas elimination scanned over
   layers, batched over all columns (lanes).  The same blocks give the
   explicit matvec F_3D^v(u) for fully-explicit sub-steps.

All 'weighted mass' face integrals use the shared 3-point quadrature of
`geometry` so that discrete consistency holds across every operator.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import geometry as G

# vertical P1 mass on [-1,1]: int phi_a phi_b dzeta
MZ = jnp.array([[2.0 / 3.0, 1.0 / 3.0], [1.0 / 3.0, 2.0 / 3.0]])
# d/dzeta of (top, bottom) vertical basis
SZ = jnp.array([0.5, -0.5])
# vertical basis at the 2 Gauss points (qz, [top,bot])
PHI_Z = jnp.asarray(G.PHI_ZQ)


def _minv_faces(geom: G.Geom2D, F: jax.Array) -> jax.Array:
    """Apply M_h^{-1} to the two 3-node faces of (..., nl, 6, nt)."""
    gt = G.minv_apply(geom, F[..., 0:3, :])
    gb = G.minv_apply(geom, F[..., 3:6, :])
    return jnp.concatenate([gt, gb], axis=-2)


def solve_r(geom: G.Geom2D, F: jax.Array, r_surf: jax.Array) -> jax.Array:
    """Matrix-free top-down solve of D_vu r = F (paper Alg. 1).

    F: (..., nl, 6, nt) assembled RHS (interior terms only);
    r_surf: (..., 3, nt) Dirichlet surface value (paper eq. 8 on Gamma_s).
    """
    g = _minv_faces(geom, F)
    s = jnp.cumsum(g[..., 0:3, :] + g[..., 3:6, :], axis=-3)  # (.., nl, 3, nt)
    r_b = r_surf[..., None, :, :] - s
    r_t = r_b + 2.0 * g[..., 3:6, :]
    return jnp.concatenate([r_t, r_b], axis=-2)


def solve_w(geom: G.Geom2D, F: jax.Array,
            w_floor: Optional[jax.Array] = None) -> jax.Array:
    """Matrix-free bottom-up solve of D_vd w = F.

    w_floor: (..., 3, nt) bottom impermeability value (0 for the mesh-aligned
    w-tilde; u.grad(b) for the physical w on a z-mesh)."""
    g = _minv_faces(geom, F)
    gsum = g[..., 0:3, :] + g[..., 3:6, :]
    # reverse cumsum over layers: sum_{k>=l}
    s = jnp.flip(jnp.cumsum(jnp.flip(gsum, axis=-3), axis=-3), axis=-3)
    w_t = s if w_floor is None else w_floor[..., None, :, :] + s
    w_b = w_t - 2.0 * g[..., 0:3, :]
    return jnp.concatenate([w_t, w_b], axis=-2)


# ---------------------------------------------------------------------------
# Weighted 3x3 horizontal mass blocks:  WM[g]_ij = sum_q (A/3) phi_i phi_j g_q
# ---------------------------------------------------------------------------
def wmass(geom: G.Geom2D, g_qp: jax.Array) -> jax.Array:
    """g at volume qps (..., 3, nt) -> blocks (..., 3, 3, nt)."""
    return jnp.einsum("qi,qj,...qt->...ijt", G._PHI_VQ, G._PHI_VQ,
                      g_qp) * (geom.area / 3.0)


def wmass_apply(geom: G.Geom2D, g_qp: jax.Array, v: jax.Array) -> jax.Array:
    """WM[g] @ v without materialising blocks: v (..., 3, nt)."""
    vq = G.vol_interp(v)
    return jnp.einsum("qi,...qt->...it", G._PHI_VQ, g_qp * vq) * (geom.area / 3.0)


# ---------------------------------------------------------------------------
# Block-tridiagonal column operator
# ---------------------------------------------------------------------------
class Blocks(NamedTuple):
    """Column operator blocks, each (nl, 6, 6, nt).

    lo[l] couples layer l to layer l-1 (above), up[l] to layer l+1 (below).
    lo[0] and up[nl-1] are zero."""
    lo: jax.Array
    dg: jax.Array
    up: jax.Array


def mass_blocks(geom: G.Geom2D, jz: jax.Array, nl: int) -> jax.Array:
    """3D prism mass matrix blocks (block-diagonal): (nl, 6, 6, nt).

    M = MZ (x) WM[jz]; jz (3, nt) is constant over layers (sigma grid).
    """
    wm = wmass(geom, G.vol_interp(jz))              # (3, 3, nt)
    blk = jnp.einsum("ab,ijt->aibjt", MZ, wm)       # (2,3,2,3,nt)
    blk = blk.reshape(6, 6, wm.shape[-1])
    return jnp.broadcast_to(blk[None], (nl, 6, 6, blk.shape[-1]))


def mass_apply3d(geom: G.Geom2D, jz: jax.Array, u: jax.Array) -> jax.Array:
    """M u for 3D fields (..., nl, 6, nt) without materialising blocks."""
    ut, ub = u[..., 0:3, :], u[..., 3:6, :]
    wm_t = wmass_apply(geom, G.vol_interp(jz), MZ[0, 0] * ut + MZ[0, 1] * ub)
    wm_b = wmass_apply(geom, G.vol_interp(jz), MZ[1, 0] * ut + MZ[1, 1] * ub)
    return jnp.concatenate([wm_t, wm_b], axis=-2)


def mass_solve3d(geom: G.Geom2D, jz: jax.Array, r: jax.Array) -> jax.Array:
    """M^{-1} r: MZ^{-1} (x) WM[jz]^{-1}; WM[jz]^{-1} via 3x3 solve."""
    # MZ^{-1} = [[2,-1],[-1,2]]
    rt, rb = r[..., 0:3, :], r[..., 3:6, :]
    st = 2.0 * rt - rb
    sb = -rt + 2.0 * rb
    wm = wmass(geom, G.vol_interp(jz))               # (3,3,nt)
    wmT = jnp.moveaxis(wm, -1, 0)                    # (nt,3,3)
    def solve3(v):
        vT = jnp.moveaxis(v, -1, -2)                 # (..., 3, nt)->(...,nt,3)
        out = jnp.linalg.solve(wmT, vT[..., None])[..., 0]
        return jnp.moveaxis(out, -1, -2)
    return jnp.concatenate([solve3(st), solve3(sb)], axis=-2)


def sigma3_horizontal(geom: G.Geom2D, H: jax.Array, nl: int,
                      N0: float = 5.0, o: int = 1, d: int = 3) -> jax.Array:
    """Interior-penalty coefficient on horizontal faces (paper eq. 19):
    sigma_d = N0(o+1)(o+d) / (2 d L), L = average prism height."""
    L = H / nl                                        # (3, nt)
    return N0 * (o + 1) * (o + d) / (2.0 * d * L)


def assemble_vertical_operator(
        geom: G.Geom2D,
        nl: int,
        jz: jax.Array,           # (3, nt)
        wrel_nodes: jax.Array,   # (nl, 6, nt): w~ - w_m at prism nodes
        wface: jax.Array,        # (nl+1, 3, nt): advective speed at interfaces
                                 #   (w~_t of the layer below the interface - w_m);
                                 #   row 0 = free surface, row nl = floor.
        kappa: jax.Array,        # (nl, 6, nt): implicit vertical visc/diff
        H: jax.Array,            # (3, nt) for the penalty length scale
        drag_coeff: Optional[jax.Array] = None,  # (3, nt) linearised bottom
                                 # drag Cd|u_bot| (momentum only)
        ) -> Blocks:
    """Assemble F_3D^v as block-tridiagonal blocks (paper eq. 18).

    Sign convention: F_3D^v(u) = (lo, dg, up) @ u appears on the RHS of the
    momentum/tracer equations; the implicit system is (M - dt*A) u1 = rhs.
    """
    nt = jz.shape[-1]
    dt_ = jz.dtype
    dg = jnp.zeros((nl, 6, 6, nt), dt_)
    lo = jnp.zeros((nl, 6, 6, nt), dt_)
    up = jnp.zeros((nl, 6, 6, nt), dt_)
    jz_q = G.vol_interp(jz)                         # (3qp, nt)
    area3 = geom.area / 3.0

    def wm(g_qp):                                   # (..., 3qp, nt)->(...,3,3,nt)
        return jnp.einsum("qi,qj,...qt->...ijt", G._PHI_VQ, G._PHI_VQ,
                          g_qp) * area3

    # --- 1. advection volume: + s_a * sum_qz phi_z^b(qz) WM[wrel(qz)] -------
    # wrel at (qz, qh): interp vertical then horizontal
    wt_q = G.vol_interp(wrel_nodes[:, 0:3, :])      # (nl, 3qp, nt)
    wb_q = G.vol_interp(wrel_nodes[:, 3:6, :])
    for iz in range(2):                             # vertical Gauss points
        wq = PHI_Z[iz, 0] * wt_q + PHI_Z[iz, 1] * wb_q   # (nl, 3qp, nt)
        blk = wm(wq)                                # (nl, 3, 3, nt)
        for a in range(2):
            for b_ in range(2):
                coef = SZ[a] * PHI_Z[iz, b_]
                dg = dg.at[:, 3 * a:3 * a + 3, 3 * b_:3 * b_ + 3, :].add(
                    coef * blk)

    # --- 3. viscosity volume: - s_a s_b WM[sum_qz kappa(qz)/jz] -------------
    kt_q = G.vol_interp(kappa[:, 0:3, :])
    kb_q = G.vol_interp(kappa[:, 3:6, :])
    ksum_q = (PHI_Z[0, 0] + PHI_Z[1, 0]) * kt_q + (PHI_Z[0, 1] + PHI_Z[1, 1]) * kb_q
    blk_visc = wm(ksum_q / jz_q)                    # (nl, 3, 3, nt)
    for a in range(2):
        for b_ in range(2):
            dg = dg.at[:, 3 * a:3 * a + 3, 3 * b_:3 * b_ + 3, :].add(
                -SZ[a] * SZ[b_] * blk_visc)

    # --- interface terms (k = 1..nl-1 interior) ------------------------------
    # advective upwind flux, viscosity consistency mean, interior penalty
    Wq = G.vol_interp(wface)                        # (nl+1, 3qp, nt)
    up_mask = (Wq > 0).astype(dt_)                  # upwind = from below
    k_bot_above = G.vol_interp(kappa[:, 3:6, :])    # (nl, 3qp, nt) at own bottom
    k_top_below = G.vol_interp(kappa[:, 0:3, :])    # (nl, 3qp, nt) at own top
    sig = G.vol_interp(sigma3_horizontal(geom, H, nl))  # (3qp, nt)

    # interior interfaces k=1..nl-1: between layer k-1 (above) and k (below)
    Wk = Wq[1:nl]                                   # (nl-1, 3qp, nt)
    upk = up_mask[1:nl]
    blk_below = wm(Wk * upk)                        # coupling to u_{k, top}
    blk_above = wm(Wk * (1 - upk))                  # coupling to u_{k-1, bot}
    # test (k, top) rows [n_z=+1]: -flux
    dg = dg.at[1:, 0:3, 0:3, :].add(-blk_below)
    lo = lo.at[1:, 0:3, 3:6, :].add(-blk_above)
    # test (k-1, bot) rows [n_z=-1]: +flux
    up = up.at[:-1, 3:6, 0:3, :].add(blk_below)
    dg = dg.at[:-1, 3:6, 3:6, :].add(blk_above)

    # surface interface k=0: u^up == interior (layer 0 top) for both signs
    W0 = Wq[0]
    blk0 = wm(W0)
    dg = dg.at[0, 0:3, 0:3, :].add(-blk0)
    # floor interface k=nl: speed is 0 by impermeability (wface[nl] == 0);
    # assemble anyway for generality (upwind = from above = own bottom)
    Wn = Wq[nl]
    blkn = wm(Wn)
    dg = dg.at[nl - 1, 3:6, 3:6, :].add(blkn)

    # viscosity consistency at interior interfaces: mean of kappa d_zeta u / jz
    # from both sides; factor 1/2 (mean) * 1/2 (d_zeta of P1) = 1/4
    kb = wm(k_bot_above[:nl - 1] / jz_q / 4.0)      # (nl-1,3,3,nt) above side
    kt = wm(k_top_below[1:] / jz_q / 4.0)           # below side
    # test (k, top) [+]: + {.}  => +kt*(u_t^k - u_b^k)/.. +kb*(u_t^{k-1}-u_b^{k-1})
    dg = dg.at[1:, 0:3, 0:3, :].add(kt)
    dg = dg.at[1:, 0:3, 3:6, :].add(-kt)
    lo = lo.at[1:, 0:3, 0:3, :].add(kb)
    lo = lo.at[1:, 0:3, 3:6, :].add(-kb)
    # test (k-1, bot) [-]: - {.}
    up = up.at[:-1, 3:6, 0:3, :].add(-kt)
    up = up.at[:-1, 3:6, 3:6, :].add(kt)
    dg = dg.at[:-1, 3:6, 0:3, :].add(-kb)
    dg = dg.at[:-1, 3:6, 3:6, :].add(kb)

    # interior penalty: -sigma {kappa} [[u]] on interface k
    kmean = 0.5 * (k_bot_above[:nl - 1] + k_top_below[1:])  # (nl-1, 3qp, nt)
    pen = wm(sig * kmean) * 0.5                     # the [[.]] carries 1/2
    # test (k, top): -pen*(u_t^k - u_b^{k-1})
    dg = dg.at[1:, 0:3, 0:3, :].add(-pen)
    lo = lo.at[1:, 0:3, 3:6, :].add(pen)
    # test (k-1, bot): -pen*(u_b^{k-1} - u_t^k)
    dg = dg.at[:-1, 3:6, 3:6, :].add(-pen)
    up = up.at[:-1, 3:6, 0:3, :].add(pen)

    # bottom drag (momentum): - WM[Cd|u|] on the floor nodes
    if drag_coeff is not None:
        blk_drag = wm(G.vol_interp(drag_coeff))
        dg = dg.at[nl - 1, 3:6, 3:6, :].add(-blk_drag)

    return Blocks(lo=lo, dg=dg, up=up)


def implicit_system(M_blocks: jax.Array, A: Blocks, dtau: float) -> Blocks:
    """The vertically-implicit system (M - dt A) as Blocks.

    M_blocks: (nl, 6, 6, nt) mass blocks at the end-of-stage geometry;
    A: the assembled F_3D^v operator.  Used by both the SoA reference solve
    and the cell-layout Pallas path (kernels/ops.block_thomas)."""
    return Blocks(lo=-dtau * A.lo, dg=M_blocks - dtau * A.dg,
                  up=-dtau * A.up)


def blocks_matvec(blocks: Blocks, u: jax.Array) -> jax.Array:
    """Apply the block-tridiagonal operator: u (..., nl, 6, nt)."""
    lo, dg, up = blocks
    out = jnp.einsum("lijt,...ljt->...lit", dg, u)
    out = out.at[..., 1:, :, :].add(
        jnp.einsum("lijt,...ljt->...lit", lo[1:], u[..., :-1, :, :]))
    out = out.at[..., :-1, :, :].add(
        jnp.einsum("lijt,...ljt->...lit", up[:-1], u[..., 1:, :, :]))
    return out


def block_thomas_solve(blocks: Blocks, rhs: jax.Array) -> jax.Array:
    """Solve the block-tridiagonal system; rhs (k, nl, 6, nt) for k RHS
    components (momentum solves u,v together; tracers T,S together).

    Scanned forward elimination with batched 6x6 LU solves over columns —
    the JAX reference for the Pallas `column_solve` kernel (paper §2.4).
    """
    lo, dg, up = blocks
    k, nl, _, nt = rhs.shape
    # Arrays stacked over the layers keep the column axis last; only one
    # layer at a time is turned to (nt, 6, 6) for the batched linalg.  (A
    # TPU pads a trailing (6, 6) pair to an (8, 128) tile: stacked, that
    # was 28 times the data, about 7 GB at 52k columns and 32 layers.)
    def cols(a):                          # (r, c, nt) -> (nt, r, c)
        return jnp.moveaxis(a.reshape(6, -1, nt), -1, 0)

    def lanes(a):                         # (nt, r, c) -> (r * c, nt)
        return jnp.moveaxis(a, 0, -1).reshape(-1, nt)

    def fwd(carry, inp):
        C_prev, y_prev = carry                           # (nt,6,6), (nt,6,k)
        L, D, U, b = map(cols, inp)
        S = D - L @ C_prev
        Cy = jnp.linalg.solve(S, jnp.concatenate([U, b - L @ y_prev], axis=-1))
        C = Cy[..., :6]
        y = Cy[..., 6:]
        return (C, y), (lanes(C), lanes(y))

    C0 = jnp.zeros((nt, 6, 6), rhs.dtype)
    y0 = jnp.zeros((nt, 6, k), rhs.dtype)
    _, (Cs, ys) = jax.lax.scan(fwd, (C0, y0),
                               tuple(a.reshape(nl, -1, nt) for a in
                                     (lo, dg, up, jnp.moveaxis(rhs, 0, 2))))

    def bwd(x_next, inp):
        C, y = map(cols, inp)
        x = y - C @ x_next
        return x, lanes(x)

    _, xs = jax.lax.scan(bwd, jnp.zeros((nt, 6, k), rhs.dtype), (Cs, ys),
                         reverse=True)
    return jnp.moveaxis(xs.reshape(nl, 6, k, nt), 2, 0)    # (k, nl, 6, nt)


def blocks_dense(blocks: Blocks) -> jax.Array:
    """Materialise (nt, nl*6, nl*6) dense matrices (tests only)."""
    lo, dg, up = blocks
    nl, _, _, nt = dg.shape
    A = jnp.zeros((nt, nl * 6, nl * 6), dg.dtype)
    for l in range(nl):
        A = A.at[:, l * 6:(l + 1) * 6, l * 6:(l + 1) * 6].set(
            jnp.moveaxis(dg[l], -1, 0))
        if l > 0:
            A = A.at[:, l * 6:(l + 1) * 6, (l - 1) * 6:l * 6].set(
                jnp.moveaxis(lo[l], -1, 0))
        if l < nl - 1:
            A = A.at[:, l * 6:(l + 1) * 6, (l + 1) * 6:(l + 2) * 6].set(
                jnp.moveaxis(up[l], -1, 0))
    return A
