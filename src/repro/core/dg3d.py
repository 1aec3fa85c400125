"""3D DG operators on the prismatic mesh (paper SI §S2–S3).

Provides:
  * prism volume / lateral-face quadrature helpers (tensor-product P1),
  * the RHS of the hydrostatic pressure gradient r (SI eq. 11),
  * the RHS of the modified continuity equation for w-tilde (SI eq. 13),
  * the horizontal momentum / tracer flux F_3D^h (SI eq. 17 / 20),
  * the consistent 3D transport q-bar (paper eq. 18),
  * Smagorinsky / Okubo horizontal mixing coefficients.

Consistency refinement (DESIGN.md §5, `exact_consistency`): the 3D lateral
advective flux is  n.{q} + {Jz/H} * (Fbar_edge - n.{Qbar}),  where Fbar_edge
is the stage-weighted time-average of the *actual* 2D free-surface edge flux
accumulated during the external burst.  Its vertical sum telescopes to
Fbar_edge exactly, making tracer constancy and mass consistency hold to
machine precision (the paper's literal form  n.{q} + {Jz/H} c+ [[eta]]  is
recovered with `exact_consistency=False`).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import geometry as G
from .extrusion import VGrid, VertGeom, vsum_dofs
from .vertical import PHI_Z, SZ
from ..kernels import dispatch as _dispatch

RHO0 = 1025.0


# ---------------------------------------------------------------------------
# Prism quadrature helpers
# ---------------------------------------------------------------------------
def zinterp(f: jax.Array) -> jax.Array:
    """Vertical interp of a prism field to the 2 Gauss-zeta levels.

    (..., nl, 6, nt) -> (..., nl, 2qz, 3, nt), nodal in horizontal."""
    ft = f[..., :, 0:3, :]
    fb = f[..., :, 3:6, :]
    return (ft[..., :, None, :, :] * PHI_Z[:, 0][:, None, None]
            + fb[..., :, None, :, :] * PHI_Z[:, 1][:, None, None])


def vol3d_scatter(geom: G.Geom2D, g: jax.Array) -> jax.Array:
    """Prism volume integral against all 6 test functions.

    g: (..., nl, 2qz, 3qh, nt) integrand (without Jacobians; the A/3 weight
    and unit vertical Gauss weights are applied here) -> (..., nl, 6, nt)."""
    # horizontal scatter for each (qz): (..., nl, 2qz, 3nodes, nt)
    s = jnp.einsum("qn,...zqt->...znt", G._PHI_VQ, g) * (geom.area / 3.0)
    top = jnp.einsum("z,...znt->...nt", PHI_Z[:, 0], s)
    bot = jnp.einsum("z,...znt->...nt", PHI_Z[:, 1], s)
    return jnp.concatenate([top, bot], axis=-2)


def lat_interp(f: jax.Array) -> jax.Array:
    """Interior values at lateral-face qps.

    (..., nl, 6, nt) -> (..., nl, 2qz, 3edge, 2qs, nt)."""
    fz = zinterp(f)                                   # (..., nl, 2qz, 3, nt)
    return G.edge_interp(fz)                          # edge interp on last axes


def lat_interp_ext(geom: G.Geom2D, f: jax.Array) -> jax.Array:
    fz = zinterp(f)
    return G.edge_interp_ext(geom, fz)


def edge_ext_nodal6(geom: G.Geom2D, f: jax.Array) -> jax.Array:
    """Neighbour nodal values per lateral edge — ONE gather at nodal width.

    f: (..., 6, nt) -> (..., 3edge, 2[a|b], 2[top|bot], nt): for edge e the
    neighbour's values at the nodes facing my edge nodes a/b, on the top and
    bottom faces.  The qp-level exterior states (`lat_interp_ext`) are a
    linear map of these (see `lat_ext_from_nodal`), so the fused pipeline
    gathers once here instead of at 12-qp width.  The whole (..., 6, nt)
    field is one record gather by ext_tri (`G.edge_ext_record`, top and
    bottom faces as a leading axis), then an exact pick."""
    fa, fb = G.edge_ext_record(geom, f.reshape(f.shape[:-2] + (2, 3)
                                               + f.shape[-1:]))
    return jnp.moveaxis(jnp.stack([fa, fb], axis=-4), -2, -4)


def own_nodal6(f: jax.Array) -> jax.Array:
    """Own nodal values in the edge_ext_nodal6 layout (identity 'gather');
    used to blend forced open-boundary values at nodal level."""
    ft, fb = f[..., 0:3, :], f[..., 3:6, :]
    with jax.named_scope("gather.edge_local"):
        ta, tb = ft[..., G.EDGE_A, :], ft[..., G.EDGE_B, :]
        ba, bb = fb[..., G.EDGE_A, :], fb[..., G.EDGE_B, :]
    return jnp.stack([jnp.stack([ta, ba], axis=-2),
                      jnp.stack([tb, bb], axis=-2)], axis=-3)


def lat_ext_from_nodal(fx: jax.Array) -> jax.Array:
    """Exterior values at lateral qps from the nodal neighbour gather.

    fx: (..., nl, 3edge, 2[a|b], 2[top|bot], nt)
    -> (..., nl, 2qz, 3edge, 2qs, nt), identical to `lat_interp_ext` of the
    ungathered field (zeta-interp and the gather commute node-wise).
    Written as broadcast arithmetic (axis-insertion style, like
    `edge_interp`) — the einsum form lowers to transpose-heavy HLO."""
    ft, fb = fx[..., 0, :], fx[..., 1, :]           # (..., nl, 3e, 2j, nt)
    fz = (ft[..., None, :, :, :] * PHI_Z[:, 0][:, None, None, None]
          + fb[..., None, :, :, :] * PHI_Z[:, 1][:, None, None, None])
    fa, fb2 = fz[..., 0, :], fz[..., 1, :]          # (..., nl, 2qz, 3e, nt)
    return (fa[..., :, None, :] * G._PHIA[:, None]
            + fb2[..., :, None, :] * G._PHIB[:, None])


def reflect_nodal(geom: G.Geom2D, fx_pair: jax.Array) -> jax.Array:
    """Free-slip wall reflection applied to a velocity pair's nodal
    neighbour gather (2, ..., 3edge, 2, 2, nt).  Normals are constant per
    edge, so reflecting nodally then interpolating equals reflecting the
    interpolated qp states (`reflect_pair`)."""
    nx = geom.edge_nx[:, None, None, :]
    ny = geom.edge_ny[:, None, None, :]
    wall = geom.wall[:, None, None, :]
    un = fx_pair[0] * nx + fx_pair[1] * ny
    return jnp.stack([fx_pair[0] - 2 * wall * un * nx,
                      fx_pair[1] - 2 * wall * un * ny])


def lat_scatter(geom: G.Geom2D, g: jax.Array) -> jax.Array:
    """Lateral-face integral against all 6 test functions.

    g: (..., nl, 2qz, 3edge, 2qs, nt) integrand (Jl edge-length jacobian is
    applied inside; vertical Gauss weights are 1) -> (..., nl, 6, nt)."""
    s = G.edge_scatter(geom, g)                       # (..., nl, 2qz, 3, nt)
    top = jnp.einsum("z,...znt->...nt", PHI_Z[:, 0], s)
    bot = jnp.einsum("z,...znt->...nt", PHI_Z[:, 1], s)
    return jnp.concatenate([top, bot], axis=-2)


def iso_grad(geom: G.Geom2D, f_qz: jax.Array) -> jax.Array:
    """Iso-zeta horizontal gradient from nodal-at-qz values.

    f_qz: (..., nl, 2qz, 3, nt) -> (..., nl, 2qz, 2comp, nt)."""
    return jnp.einsum("...nt,ndt->...dt", f_qz, geom.dphi)


# ---------------------------------------------------------------------------
# Boundary ghosts for 3D lateral faces
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LateralBC:
    """How to build ghost values on WALL / OPEN boundary faces."""
    reflect: bool = False                  # True for velocity components
    open_value: Optional[jax.Array] = None  # (..., nl, 6, nt) forced field


def lat_states(geom: G.Geom2D, f: jax.Array, bc: LateralBC = LateralBC()):
    """(int, ext) values at lateral qps with BCs applied.

    For vector fields pass components separately and use `reflect_pair`."""
    fi = lat_interp(f)
    fe = lat_interp_ext(geom, f)
    if bc.open_value is not None:
        openb = geom.openb[None, :, None, :]
        fo = lat_interp(bc.open_value)
        fe = fe * (1 - openb) + fo * openb
    return fi, fe


def reflect_pair(geom: G.Geom2D, uxe: jax.Array, uye: jax.Array):
    """Apply free-slip wall reflection to exterior velocity values at lateral
    qps (gathered ext == int on boundaries, so reflecting gives the ghost)."""
    nx = geom.edge_nx[:, None, :]
    ny = geom.edge_ny[:, None, :]
    wall = geom.wall[None, :, None, :]
    un = uxe * nx + uye * ny
    return (uxe - 2 * wall * un * nx, uye - 2 * wall * un * ny)


# ---------------------------------------------------------------------------
# Consistent 3D transport (paper eq. 18 + §2.5)
# ---------------------------------------------------------------------------
def transport_from_velocity(vge: VertGeom, ux: jax.Array, uy: jax.Array):
    """q = J_z u projected (nodally) to the linear basis: (2, nl, 6, nt)."""
    jz6 = jnp.concatenate([vge.jz, vge.jz], axis=-2)   # (6, nt)
    return jnp.stack([ux * jz6, uy * jz6])


def consistent_transport(vge: VertGeom, ux, uy, qbar_x2d, qbar_y2d, nl: int):
    """q-bar: nodal J_z u corrected so that the sum over vertical DOFs equals
    the externally-averaged 2D transport Q-bar exactly (paper eq. 18):
    the column-wise defect is distributed uniformly over the 2*nl DOFs."""
    q = transport_from_velocity(vge, ux, uy)
    def fix(qc, Q2d):
        d = (Q2d - vsum_dofs(qc)) / (2.0 * nl)         # (3, nt)
        d6 = jnp.concatenate([d, d], axis=-2)          # (6, nt)
        return qc + d6[None]
    return jnp.stack([fix(q[0], qbar_x2d), fix(q[1], qbar_y2d)])


# ---------------------------------------------------------------------------
# Lateral advective flux speed (per lateral qp)
# ---------------------------------------------------------------------------
class LateralFlux(NamedTuple):
    speed: jax.Array     # (nl, 2qz, 3, 2qs, nt) signed normal flux speed
    upwind: jax.Array    # same shape, 1.0 where interior side is upwind


def lateral_flux_speed(geom: G.Geom2D, vge: VertGeom, vg: VGrid,
                       qx: jax.Array, qy: jax.Array,
                       eta: jax.Array, b2d: jax.Array,
                       fbar_edge: Optional[jax.Array] = None,
                       qbar2d: Optional[tuple] = None,
                       h_min: float = 0.05, cache=None) -> LateralFlux:
    """Normal advective flux speed at lateral qps.

    paper form:   n.{q} + {Jz/H} c+ [[eta]]          (fbar_edge=None)
    exact form:   n.{q} + {Jz/H} (Fbar - n.{Qbar})   (fbar_edge given)
    Wall faces: reflected ghost -> n.{q} = 0, [[eta]]=0 -> speed 0.

    cache: optional per-stage EdgeCache (core/horizontal.py) supplying the
    field-independent {Jz/H} coefficient and eta/H edge states, so only the
    transport itself is gathered here (once per transport per stage).
    """
    nx = geom.edge_nx[:, None, :]
    ny = geom.edge_ny[:, None, :]
    qxi, qxe = lat_interp(qx), lat_interp_ext(geom, qx)
    qyi, qye = lat_interp(qy), lat_interp_ext(geom, qy)
    qxe, qye = reflect_pair(geom, qxe, qye)
    mean_qn = 0.5 * ((qxi + qxe) * nx + (qyi + qye) * ny)

    # {Jz/H} at lateral qps — constant 1/(2 nl) on the uniform sigma grid,
    # computed from fields for generality
    if cache is not None:
        alpha = cache.alpha[None, None]
    else:
        a = vge.jz / jnp.maximum(vge.H, h_min)         # (3, nt)
        ai = G.edge_interp(a)
        ae = G.edge_interp_ext(geom, a)
        alpha = 0.5 * (ai + ae)                        # (3, 2qs, nt)
        alpha = alpha[None, None]                      # bcast (nl, qz)

    if fbar_edge is not None:
        Qbx, Qby = qbar2d
        Qxi, Qxe = G.edge_interp(Qbx), G.edge_interp_ext(geom, Qbx)
        Qyi, Qye = G.edge_interp(Qby), G.edge_interp_ext(geom, Qby)
        # same wall reflection as the 2D mode applied to Q ghosts
        nx2, ny2 = geom.edge_nx[:, None, :], geom.edge_ny[:, None, :]
        wall2 = geom.wall[:, None, :]
        Qn_e = Qxe * nx2 + Qye * ny2
        Qxe = Qxe - 2 * wall2 * Qn_e * nx2
        Qye = Qye - 2 * wall2 * Qn_e * ny2
        mean_Qn = 0.5 * ((Qxi + Qxe) * nx2 + (Qyi + Qye) * ny2)  # (3,2qs,nt)
        corr = fbar_edge - mean_Qn
        speed = mean_qn + alpha * corr[None, None]
    else:
        if cache is not None:
            # vge.H == max(eta + b2d, h_min) (layer_geometry, same h_min)
            Hi, He = cache.H_int, cache.H_ext
            ei, ee = cache.eta_int, cache.eta_ext
        else:
            H2 = jnp.maximum(eta + b2d, h_min)
            Hi, He = G.edge_interp(H2), G.edge_interp_ext(geom, H2)
            ei, ee = G.edge_interp(eta), G.edge_interp_ext(geom, eta)
        c_plus = jnp.sqrt(G.G_GRAV * jnp.maximum(Hi, He))
        jump_eta = 0.5 * (ei - ee) * (1.0 - geom.wall[:, None, :])
        speed = mean_qn + alpha * (c_plus * jump_eta)[None, None]
    return LateralFlux(speed=speed, upwind=(speed > 0).astype(speed.dtype))


# ---------------------------------------------------------------------------
# Generic horizontal advection-diffusion (momentum & tracers share this)
# ---------------------------------------------------------------------------
class FieldStates(NamedTuple):
    """Field-dependent interpolations of one advected field set — everything
    `horizontal_advdiff` needs that depends on neither the flux nor the
    mixing coefficient.  The momentum prediction and momentum update calls
    interpolate the SAME velocity fields, so the stepper builds this once
    per field set per stage and shares it (core/horizontal.py)."""
    fq: jax.Array        # (k, nl, 2qz, 3, nt)      zeta-interp
    fqq: jax.Array       # (k, nl, 2qz, 3qh, nt)    vol-quad values
    fi: jax.Array        # (k, nl, 2qz, 3, 2qs, nt) interior lateral states
    fe: jax.Array        # same, exterior (post-BC)
    fx: Optional[jax.Array]  # (k, nl, 3, 2, 2, nt) nodal ext gather (post-
                             # BC) — nodal path only; feeds the Pallas kernel
    gradf: jax.Array     # (k, nl, 2qz, 2, nt)      iso-zeta gradient
    gno: jax.Array       # (k, nl, 2qz, 3e, nt)     interior normal gradient
    gradf_e: jax.Array   # same, exterior


def field_states(geom: G.Geom2D, f: jax.Array, bc_reflect: bool = False,
                 open_values: Optional[jax.Array] = None,
                 nodal: bool = True) -> FieldStates:
    """Build the FieldStates of (k, nl, 6, nt) fields.

    bc_reflect: the first two components are the horizontal velocity vector
    (free-slip wall reflection of the exterior states).

    nodal=True (fused path) builds the exterior states from ONE neighbour
    gather at nodal width with the BC fixups applied nodally — they are
    linear, so the qp states match the qp-level construction to fp
    reassociation — and keeps the gather (`fx`) for the Pallas lateral-flux
    kernel.  nodal=False reproduces the seed qp-level construction verbatim
    (the equivalence oracle)."""
    k = f.shape[0]
    fq = zinterp(f)                                   # (k, nl, 2qz, 3, nt)
    fqq = G.vol_interp(fq)                            # (k, nl, 2qz, 3qh, nt)
    fi = lat_interp(f)                                # (k, nl, 2qz, 3, 2qs, nt)
    if nodal:
        fx = edge_ext_nodal6(geom, f)                 # (k, nl, 3, 2, 2, nt)
        if bc_reflect:
            assert k >= 2
            fx = jnp.concatenate([reflect_nodal(geom, fx[:2]), fx[2:]])
        if open_values is not None:
            openb = geom.openb[:, None, None, :]
            fx = fx * (1 - openb) + own_nodal6(open_values) * openb
        fe = lat_ext_from_nodal(fx)
    else:
        fx = None
        fe = lat_interp_ext(geom, f)
        if bc_reflect:
            assert k >= 2
            fxe, fye = reflect_pair(geom, fe[0], fe[1])
            fe = jnp.concatenate([jnp.stack([fxe, fye]), fe[2:]])
        if open_values is not None:
            openb = geom.openb[None, :, None, :]
            fo = lat_interp(open_values)
            fe = fe * (1 - openb) + fo * openb
    gradf = iso_grad(geom, fq)                        # (k, nl, 2qz, 2, nt)
    gno = jnp.einsum("...zdt,edt->...zet", gradf,
                     jnp.stack([geom.edge_nx, geom.edge_ny], axis=1))
    gradf_e = _gather_ext_grad(geom, gradf)           # (k, nl, 2qz, 3e, nt)
    return FieldStates(fq=fq, fqq=fqq, fi=fi, fe=fe, fx=fx,
                       gradf=gradf, gno=gno, gradf_e=gradf_e)


def horizontal_advdiff(geom: G.Geom2D, vge: VertGeom, nl: int,
                       f: jax.Array,               # (k, nl, 6, nt) fields
                       qx: jax.Array, qy: jax.Array,  # (nl, 6, nt) transport
                       flux: LateralFlux,
                       nu_h: jax.Array,            # (nl, 6, nt) horiz. mixing
                       bc_reflect: bool = False,   # True for velocity
                       open_values: Optional[jax.Array] = None,
                       cache=None, tcache=None, fcache=None,
                       backend="ref") -> jax.Array:
    """Horizontal advection + along-sigma diffusion terms of F_3D^h / eq. 20.

    Returns (k, nl, 6, nt) RHS contributions (not mass-inverted).

    cache / tcache / fcache (core/horizontal.py) supply the per-stage
    interpolations: field-independent edge/volume states, vol-quad
    transport, and the FieldStates of f.  When fcache is given,
    bc_reflect/open_values are ignored (already baked in).  Without caches
    everything is recomputed per call — the seed path, the equivalence
    oracle.  The lateral advective term runs through the fused Pallas
    kernel (kernels/horizontal_flux.py) when the FieldStates carry the
    nodal gather and ``backend`` resolves to a kernel backend.
    """
    if fcache is None:
        fcache = field_states(geom, f, bc_reflect=bc_reflect,
                              open_values=open_values,
                              nodal=cache is not None)
    adv = horizontal_advection(geom, vge, nl, f, qx, qy, flux,
                               tcache=tcache, fcache=fcache, backend=backend)
    diff = horizontal_diffusion(geom, vge, nl, f, nu_h,
                                cache=cache, fcache=fcache)
    return adv + diff


def horizontal_advection(geom: G.Geom2D, vge: VertGeom, nl: int,
                         f: jax.Array, qx: jax.Array, qy: jax.Array,
                         flux: LateralFlux, bc_reflect: bool = False,
                         open_values: Optional[jax.Array] = None,
                         tcache=None, fcache=None,
                         backend="ref") -> jax.Array:
    """Flux-dependent half of `horizontal_advdiff`: volume advection +
    lateral upwind flux.  This is the part that must run per LateralFlux;
    the diffusion half depends only on (f, nu) and is hoisted by the fused
    stepper to one evaluation per field set per stage.

    bc_reflect/open_values apply only when fcache is not prebuilt (a
    prebuilt FieldStates already carries the BC fixups)."""
    if fcache is None:
        fcache = field_states(geom, f, bc_reflect=bc_reflect,
                              open_values=open_values, nodal=False)

    # --- volume advection: <Jh f (q . phi_z grad(phi_h))> -------------------
    if tcache is not None:
        qxq, qyq = tcache.qxq, tcache.qyq
    else:
        qxq = G.vol_interp(zinterp(qx))               # (nl, 2qz, 3qh, nt)
        qyq = G.vol_interp(zinterp(qy))
    # scatter with gradient test functions: sum_q (A/3) f q . dphi_i phi_z^a
    # (dphi is constant per triangle, so the qh sum factorises)
    gx = (fcache.fqq * qxq).sum(axis=-2)               # (k, nl, 2qz, nt)
    gy = (fcache.fqq * qyq).sum(axis=-2)
    sx = gx[..., None, :] * geom.dphi[:, 0, :]         # (k, nl, 2qz, 3n, nt)
    sy = gy[..., None, :] * geom.dphi[:, 1, :]
    s = (sx + sy) * (geom.area / 3.0)                  # (k, nl, 2qz, 3, nt)
    top = jnp.einsum("z,...znt->...nt", PHI_Z[:, 0], s)
    bot = jnp.einsum("z,...znt->...nt", PHI_Z[:, 1], s)
    out = jnp.concatenate([top, bot], axis=-2)         # (k, nl, 6, nt)

    # --- lateral upwind advective flux --------------------------------------
    bk = _dispatch.resolve(backend)
    if fcache.fx is not None and bk is not _dispatch.Backend.REF:
        # fused Pallas kernel: nodal neighbour gather + zeta/edge interp +
        # upwind select + speed multiply + weighted scatter in one pass
        from ..kernels import ops as kops
        lat_adv = kops.lateral_flux_term(geom, f, fcache.fx, flux.speed,
                                         backend=bk)
    else:
        f_up = jnp.where(flux.upwind > 0.5, fcache.fi, fcache.fe)
        lat_adv = lat_scatter(geom, f_up * flux.speed[None])
    return out - lat_adv


def horizontal_diffusion(geom: G.Geom2D, vge: VertGeom, nl: int,
                         f: jax.Array, nu_h: jax.Array,
                         bc_reflect: bool = False,
                         open_values: Optional[jax.Array] = None,
                         cache=None, fcache=None) -> jax.Array:
    """Along-sigma diffusion half of `horizontal_advdiff` (SIP form).

    Depends only on (f, nu_h, jz) — NOT on the transport or flux — so the
    fused stepper evaluates it once per field set per stage (the seed
    evaluated the momentum diffusion twice: prediction and update).

    bc_reflect/open_values apply only when fcache is not prebuilt (a
    prebuilt FieldStates already carries the BC fixups, which enter the
    penalty jump term here)."""
    jz_q = cache.jz_q if cache is not None else G.vol_interp(vge.jz)
    if fcache is None:
        fcache = field_states(geom, f, bc_reflect=bc_reflect,
                              open_values=open_values, nodal=False)

    # volume: -<Jh Jz nu (grad~ phi_i . grad~ f) phi_z^a>
    nu_q = G.vol_interp(zinterp(nu_h))                 # (nl, 2qz, 3qh, nt)
    gradf = fcache.gradf                               # (k, nl, 2qz, 2, nt)
    # against test gradient dphi_i (per qh the integrand is const in qh except
    # nu and jz):  sum_qh (A/3) jz nu  *  dphi_i . gradf
    coef = (nu_q * jz_q).sum(axis=-2) / 3.0 * geom.area  # (nl, 2qz, nt)
    nu_int = lat_interp(nu_h)                          # (nl, 2qz, 3, 2qs, nt)
    nu_ext = lat_interp_ext(geom, nu_h)
    nu_int_b, nu_ext_b = nu_int[None], nu_ext[None]    # bcast over k
    dvol = jnp.einsum("...zdt,ndt,...zt->...znt", gradf, geom.dphi, coef)
    dtop = jnp.einsum("z,...znt->...nt", PHI_Z[:, 0], dvol)
    dbot = jnp.einsum("z,...znt->...nt", PHI_Z[:, 1], dvol)
    out = -jnp.concatenate([dtop, dbot], axis=-2)

    # lateral consistency: + <<phi {Jz nu n.grad~ f} Jl>> (interior faces
    # only).  gno: interior normal gradient per edge; the exterior side
    # gathers the neighbour's (per-triangle-constant) gradient dotted with
    # *our* outward normal (see field_states / _gather_ext_grad).
    if cache is not None:
        nzjz_int, nzjz_ext = cache.jz_int, cache.jz_ext
    else:
        nzjz_int = G.edge_interp(vge.jz)                # (3, 2qs, nt)
        nzjz_ext = G.edge_interp_ext(geom, vge.jz)
    flux_int = fcache.gno[..., None, :] * nu_int_b * nzjz_int[None, None, None]
    flux_ext = (fcache.gradf_e[..., None, :] * nu_ext_b
                * nzjz_ext[None, None, None])
    interior = geom.interior[None, :, None, :]
    mean_flux = 0.5 * (flux_int + flux_ext)

    # lateral penalty: - <<sigma3 {nu} {Jz} [[f]] Jl>>  (interior faces);
    # assembled together with the consistency term in ONE edge scatter
    sig = cache.sigma3 if cache is not None else sigma3_lateral(geom)
    numean = 0.5 * (nu_int_b + nu_ext_b)
    jzmean = (cache.jz_mean if cache is not None
              else 0.5 * (nzjz_int + nzjz_ext))
    jumpf = 0.5 * (fcache.fi - fcache.fe)
    pen = sig[:, None, :] * numean * jzmean[None, None] * jumpf
    return out + lat_scatter(geom, (mean_flux - pen) * interior)


def _gather_ext_grad(geom: G.Geom2D, gradf: jax.Array) -> jax.Array:
    """Exterior iso-zeta gradient dotted with our outward normal, per edge.

    gradf: (k, nl, 2qz, 2comp, nt) constant-per-triangle gradients, both
    components fetched by one record gather (`G.neighbour_record`).
    Returns (k, nl, 2qz, 3edge, nt): n_ours . grad_ext.
    """
    ge = G.neighbour_record(geom, gradf)              # (k,nl,2qz,2,3,nt)
    return ge[..., 0, :, :] * geom.edge_nx + ge[..., 1, :, :] * geom.edge_ny


def sigma3_lateral(geom: G.Geom2D, N0: float = 5.0, o: int = 1,
                   d: int = 3) -> jax.Array:
    """Interior-penalty coefficient on lateral faces (eq. 19): L = A/l."""
    L_int = geom.area[None, :] / geom.edge_len          # (3, nt)
    # exterior L: neighbour's area over the same (shared) edge length
    with jax.named_scope("gather.neighbour"):
        area_ext = geom.area[geom.ext_tri]
    L_ext = area_ext / geom.edge_len
    return N0 * (o + 1) * (o + d) / (2.0 * d * jnp.minimum(L_int, L_ext))


# ---------------------------------------------------------------------------
# Horizontal mixing coefficients (paper §1.1: Smagorinsky / Okubo)
# ---------------------------------------------------------------------------
def smagorinsky_nu(geom: G.Geom2D, ux: jax.Array, uy: jax.Array,
                   cs: float = 0.1, nu_min: float = 1e-3,
                   nu_max: float = 1e4) -> jax.Array:
    """Smagorinsky horizontal viscosity: nu = (cs)^2 * 2A * |S|.

    |S| from the layer-mean iso-sigma velocity gradients.
    Returns (nl, 6, nt) nodal (constant per element per layer)."""
    um = 0.5 * (ux[:, 0:3, :] + ux[:, 3:6, :])           # (nl, 3, nt)
    vm = 0.5 * (uy[:, 0:3, :] + uy[:, 3:6, :])
    gu = G.grad2d(geom, um)                              # (nl, 2, nt)
    gv = G.grad2d(geom, vm)
    s11, s22 = gu[:, 0], gv[:, 1]
    s12 = 0.5 * (gu[:, 1] + gv[:, 0])
    smag = jnp.sqrt(2.0 * (s11 ** 2 + s22 ** 2 + 2.0 * s12 ** 2))  # (nl, nt)
    nu = jnp.clip(cs ** 2 * (2.0 * geom.area) * smag, nu_min, nu_max)
    return jnp.broadcast_to(nu[:, None, :], (nu.shape[0], 6, nu.shape[1]))


def okubo_kappa(geom: G.Geom2D, nl: int, coef: float = 2.055e-4,
                expo: float = 1.15) -> jax.Array:
    """Okubo (1971) scale-dependent horizontal diffusivity:
    kappa = coef * L^expo with L = sqrt(2A) [m]. Returns (nl, 6, nt)."""
    L = jnp.sqrt(2.0 * geom.area)
    kap = coef * L ** expo
    return jnp.broadcast_to(kap[None, None, :], (nl, 6, kap.shape[0]))


# ---------------------------------------------------------------------------
# Pressure gradient RHS (SI eq. 11) + surface value
# ---------------------------------------------------------------------------
def pressure_gradient_rhs(geom: G.Geom2D, vg: VGrid, vge: VertGeom,
                          rho_p: jax.Array, cache=None) -> tuple:
    """RHS of D_vu r = F and the surface Dirichlet value r_s.

    rho_p: (nl, 6, nt) density anomaly. Returns (F (2, nl, 6, nt), r_s (2,3,nt)).
    cache: optional per-stage EdgeCache supplying the jz interpolations.
    """
    g = G.G_GRAV
    nl = vg.nl
    # volume: +g <phi grad~_h rho' Jh Jz>
    rq = zinterp(rho_p)                                 # (nl, 2qz, 3, nt)
    grho = iso_grad(geom, rq)                           # (nl, 2qz, 2, nt)
    jz_q = cache.jz_q if cache is not None else G.vol_interp(vge.jz)
    # integrand at (qz, qh): g * grho (const per qh) * jz(qh)
    intg = g * grho[:, :, :, None, :] * jz_q[None, None, None]  # (nl,2qz,2,3qh,nt)
    F = vol3d_scatter(geom, jnp.moveaxis(intg, 2, 0))   # (2, nl, 6, nt)

    # interior horizontal interfaces k=1..nl-1:
    # -g <<2 phi n_h [[rho']] |Jh/n_z|>>_top ; n_h|Jh/nz| = -grad(z_k) Jh
    from .extrusion import interface_z
    zi = interface_z(vg, vge)                           # (nl+1, 3, nt)
    gz = G.grad2d(geom, zi)                             # (nl+1, 2, nt)
    rho_top = rho_p[1:, 0:3, :]                         # below iface k=1..nl-1
    rho_bot = rho_p[:-1, 3:6, :]                        # above iface
    jump = 0.5 * (rho_top - rho_bot)                    # (nl-1, 3, nt) [[rho']]
    jq = G.vol_interp(jump)                             # (nl-1, 3qh, nt)
    # face integral: sum_qh (A/3) phi_i * (-2 g [[rho']]) * (-grad z_k)
    term = jnp.einsum("qn,kqt,kdt->dknt", G._PHI_VQ, jq,
                      -gz[1:nl]) * (geom.area / 3.0) * (-2.0 * g)
    # applies to test functions on the top face of layer k (k=1..nl-1)
    F = F.at[:, 1:, 0:3, :].add(term)

    # lateral: -g <<phi n [[rho']] {Jz} Jl>>
    ri = lat_interp(rho_p)
    re = lat_interp_ext(geom, rho_p)
    jumpl = 0.5 * (ri - re) * geom.interior[None, :, None, :]
    if cache is not None:
        jzm = cache.jz_mean                             # (3, 2qs, nt)
    else:
        jzi = G.edge_interp(vge.jz)
        jze = G.edge_interp_ext(geom, vge.jz)
        jzm = 0.5 * (jzi + jze)
    n_ = jnp.stack([geom.edge_nx, geom.edge_ny])        # (2, 3, nt)
    intg_l = (-g) * jumpl[None] * jzm[None, None, None] * n_[:, None, None, :, None, :]
    F = F + lat_scatter(geom, intg_l)

    # surface value: r_s = g rho'(eta) grad_h(eta)
    geta = G.grad2d(geom, vge.eta)                      # (2, nt)
    r_s = g * rho_p[0, 0:3, :][None] * geta[:, None, :]

    # Sign convention: the paper's eq. (8) writes d_z r = +g grad(rho'), but
    # its own eq. (7) derivation gives r(z) = g rho'(eta) grad(eta)
    # + g int_z^eta grad(rho') dz~, i.e. r *grows* with depth for a positive
    # density gradient (deep flow must be pushed from the dense toward the
    # light side by -r/rho0).  The top-down solver D_vu decreases r by
    # Mh^{-1}F per face, so the physically-correct RHS is -F of the form
    # assembled above (validated by test_baroclinic_adjustment).
    return -F, r_s


# ---------------------------------------------------------------------------
# Modified continuity RHS for w-tilde (SI eq. 13)
# ---------------------------------------------------------------------------
def continuity_rhs(geom: G.Geom2D, vge: VertGeom, nl: int,
                   qx: jax.Array, qy: jax.Array,
                   flux: LateralFlux, tcache=None) -> jax.Array:
    """RHS of D_vd w~ = F: volume transport divergence + lateral fluxes.

    Uses the SAME LateralFlux as the tracer/momentum advection so the
    discrete budgets telescope exactly.  tcache reuses the vol-quad
    transport shared with horizontal_advdiff.
    """
    # volume: <q . phi_z grad(phi_h) Jh>
    if tcache is not None:
        qxq, qyq = tcache.qxq, tcache.qyq
    else:
        qxq = G.vol_interp(zinterp(qx))                 # (nl, 2qz, 3qh, nt)
        qyq = G.vol_interp(zinterp(qy))
    sx = jnp.einsum("...zqt,nt->...znt", qxq, geom.dphi[:, 0, :])
    sy = jnp.einsum("...zqt,nt->...znt", qyq, geom.dphi[:, 1, :])
    s = (sx + sy) * (geom.area / 3.0)
    top = jnp.einsum("z,...znt->...nt", PHI_Z[:, 0], s)
    bot = jnp.einsum("z,...znt->...nt", PHI_Z[:, 1], s)
    F = jnp.concatenate([top, bot], axis=-2)            # (nl, 6, nt)
    # lateral: - <<phi speed Jl>>
    F = F - lat_scatter(geom, flux.speed)
    return F
