"""2D barotropic ("external") mode: free surface + depth-averaged momentum.

Discretisation follows the paper's SI §S1 exactly:
  * eq (2):  M d(eta)/dt = <Jh grad(phi).Q> - <<phi (n.{Q} + c+ [[eta]]) Jl>> + <phi s Jh>
  * eq (4):  M dQ/dt = -<g phi H grad(eta) Jh> + <<n phi g {H} [[eta]] Jl>>
                        - <<phi c+ [[Q]] Jl>> - <phi (H/rho0) grad(p_atm) Jh>
                        + F_3D->2D
  with the reverse-integration-by-parts well-balanced form
  [[H^2/2]] = {H}[[eta]] (removes the O(H^2 eps_machine) noise, SI §S1.2) and a
  local Lax-Friedrichs dissipation speed c+ = max(c_int, c_ext), c = sqrt(gH).

Boundary conditions (via ghost states on the edge quadrature points):
  WALL: eta_ext = eta_int, Q_ext = Q_int - 2 (Q.n) n   (weak impermeability)
  OPEN: eta_ext = eta_bc(t), Q_ext = Q_int             (radiative forcing)

The external mode driver `run_external` advances m sub-steps of SSPRK(3,3)
inside a single `lax.scan` — one fused compiled program for the whole
barotropic burst.  This is the TPU answer to the paper's §3.3 launch-latency
wall: the per-kernel launch overhead that dominates SLIM's 2D mode on GPUs is
amortised away entirely by tracing (DESIGN.md §5, beyond-paper opt #1).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import geometry as G

RHO0 = 1025.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class State2D:
    eta: jax.Array  # (3, nt)
    qx: jax.Array   # (3, nt)
    qy: jax.Array   # (3, nt)

    def __add__(self, o):
        return State2D(self.eta + o.eta, self.qx + o.qx, self.qy + o.qy)

    def __mul__(self, a):
        return State2D(self.eta * a, self.qx * a, self.qy * a)

    __rmul__ = __mul__


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Forcing2D:
    """External-mode forcing, all optional (None disables the term)."""
    eta_open: Optional[jax.Array] = None   # (3, nt) open-boundary elevation
    patm: Optional[jax.Array] = None       # (3, nt) atmospheric pressure
    tau_x: Optional[jax.Array] = None      # (3, nt) wind stress / rho0
    tau_y: Optional[jax.Array] = None
    source: Optional[jax.Array] = None     # (3, nt) rain/evaporation s


def _edge_states(geom: G.Geom2D, st: State2D, forcing: Forcing2D):
    """Interior/exterior values of (eta, qx, qy) at the edge Gauss points,
    with WALL / OPEN ghost states applied.  The neighbour values of all three
    fields come from one gather of their stacked nodal record."""
    ei = G.edge_interp(st.eta)
    qxi = G.edge_interp(st.qx)
    qyi = G.edge_interp(st.qy)
    ext_a, ext_b = G.edge_ext_record(geom,
                                     jnp.stack([st.eta, st.qx, st.qy]))
    ee, qxe, qye = G.edge_blend(ext_a, ext_b)

    nx = geom.edge_nx[:, None, :]
    ny = geom.edge_ny[:, None, :]
    wall = geom.wall[:, None, :]
    openb = geom.openb[:, None, :]
    intm = 1.0 - wall - openb

    # WALL ghost: reflect normal transport (gathered ext == int on boundaries)
    qn = qxe * nx + qye * ny
    qx_wall = qxe - 2.0 * qn * nx
    qy_wall = qye - 2.0 * qn * ny
    # OPEN ghost
    if forcing.eta_open is not None:
        eta_open = G.edge_interp(forcing.eta_open)
    else:
        eta_open = ee
    eta_e = intm * ee + wall * ei + openb * eta_open
    qx_e = intm * qxe + wall * qx_wall + openb * qxi
    qy_e = intm * qye + wall * qy_wall + openb * qyi
    return (ei, qxi, qyi), (eta_e, qx_e, qy_e)


def external_rhs(geom: G.Geom2D, b: jax.Array, st: State2D,
                 forcing: Forcing2D = Forcing2D(),
                 f3d2d_x: Optional[jax.Array] = None,
                 f3d2d_y: Optional[jax.Array] = None,
                 h_min: float = 0.05,
                 return_flux: bool = False):
    """Right-hand side d/dt (eta, Q) — already multiplied by M^{-1}.

    With return_flux=True also returns the free-surface edge flux
    (n.{Q} + c+[[eta]]) at the edge Gauss points, (3, 2, nt) — accumulated by
    `run_external` into Fbar_edge for the exact-consistency 3D fluxes."""
    g = G.G_GRAV
    # named for the trace: the edge values (the burst's gathers) apart from
    # the rest of the right-hand side; H stays first, where the compiled
    # loop's carried values expect it
    with jax.named_scope("burst.assembly"):
        H = jnp.maximum(st.eta + b, h_min)
    with jax.named_scope("burst.edge_states"):
        (ei, qxi, qyi), (ee, qxe, qye) = _edge_states(geom, st, forcing)
        Hi = ei + G.edge_interp(b)
        He = ee + G.edge_interp(b)  # bathymetry continuous-ish; ghost: own b
        Hi = jnp.maximum(Hi, h_min)
        He = jnp.maximum(He, h_min)
    with jax.named_scope("burst.assembly"):
        nx = geom.edge_nx[:, None, :]
        ny = geom.edge_ny[:, None, :]

        c_plus = jnp.sqrt(g * jnp.maximum(Hi, He))
        jump_eta = 0.5 * (ei - ee)
        jump_qx = 0.5 * (qxi - qxe)
        jump_qy = 0.5 * (qyi - qye)
        mean_qn = 0.5 * ((qxi + qxe) * nx + (qyi + qye) * ny)
        mean_H = 0.5 * (Hi + He)

        # ----- free surface -------------------------------------------------
        # volume: <grad(phi) . Q>  (Q is P1: mean over qps exact)
        qx_q = G.vol_interp(st.qx)
        qy_q = G.vol_interp(st.qy)
        # sum_q (A/3) * dphi_n . Q(q):
        vol_eta = (geom.area / 3.0) * (
            geom.dphi[:, 0, :] * qx_q.sum(axis=0)
            + geom.dphi[:, 1, :] * qy_q.sum(axis=0))
        eta_edge_flux = mean_qn + c_plus * jump_eta
        edge_eta = G.edge_scatter(geom, eta_edge_flux)
        rhs_eta = vol_eta - edge_eta
        if forcing.source is not None:
            rhs_eta = rhs_eta + G.mass_apply(geom, forcing.source)

        # ----- momentum -----------------------------------------------------
        # volume: -<g phi H grad(eta)>  (grad(eta) const per tri; H at qps)
        deta = G.grad2d(geom, st.eta)                  # (2, nt)
        H_q = G.vol_interp(H)                          # (3, nt) at qps
        vol_qx = -g * G.vol_scatter(geom, H_q * deta[0][None, :])
        vol_qy = -g * G.vol_scatter(geom, H_q * deta[1][None, :])
        # edges: + <<n phi g {H}[[eta]]>> - <<phi c+ [[Q]]>>
        edge_qx = G.edge_scatter(
            geom, nx * g * mean_H * jump_eta - c_plus * jump_qx)
        edge_qy = G.edge_scatter(
            geom, ny * g * mean_H * jump_eta - c_plus * jump_qy)
        rhs_qx = vol_qx + edge_qx
        rhs_qy = vol_qy + edge_qy

        if forcing.patm is not None:
            dp = G.grad2d(geom, forcing.patm)
            rhs_qx = rhs_qx - G.vol_scatter(geom,
                                            H_q * dp[0][None, :] / RHO0)
            rhs_qy = rhs_qy - G.vol_scatter(geom,
                                            H_q * dp[1][None, :] / RHO0)
        if forcing.tau_x is not None:
            rhs_qx = rhs_qx + G.mass_apply(geom, forcing.tau_x)
            rhs_qy = rhs_qy + G.mass_apply(geom, forcing.tau_y)
        if f3d2d_x is not None:
            rhs_qx = rhs_qx + f3d2d_x
            rhs_qy = rhs_qy + f3d2d_y

        out = State2D(G.minv_apply(geom, rhs_eta),
                      G.minv_apply(geom, rhs_qx),
                      G.minv_apply(geom, rhs_qy))
    if return_flux:
        return out, eta_edge_flux
    return out


def standalone_extra_rhs(geom: G.Geom2D, b: jax.Array, st: State2D,
                         coriolis_f: float = 0.0,
                         bottom_cd: float = 0.0,
                         h_min: float = 0.05) -> State2D:
    """Optional standalone-2D terms the coupled model gets from S3 instead:
    Coriolis -f ez x Q and quadratic bottom drag -Cd |Q| Q / H^2."""
    H = jnp.maximum(st.eta + b, h_min)
    rqx = coriolis_f * st.qy
    rqy = -coriolis_f * st.qx
    if bottom_cd > 0:
        qn = jnp.sqrt(st.qx ** 2 + st.qy ** 2)
        rqx = rqx - bottom_cd * qn * st.qx / H ** 2
        rqy = rqy - bottom_cd * qn * st.qy / H ** 2
    return State2D(jnp.zeros_like(st.eta), rqx, rqy)


def ssprk3_step(rhs_fn: Callable[[State2D], State2D], st: State2D,
                dt: float) -> State2D:
    """Shu-Osher SSPRK(3,3) — the paper's 3-stage explicit RK external mode."""
    k1 = st + dt * rhs_fn(st)
    k2 = 0.75 * st + 0.25 * (k1 + dt * rhs_fn(k1))
    return (1.0 / 3.0) * st + (2.0 / 3.0) * (k2 + dt * rhs_fn(k2))


class ExternalResult(NamedTuple):
    state: State2D
    q_bar_x: jax.Array    # (3, nt) effective time-averaged transport
    q_bar_y: jax.Array
    f2d_x: jax.Array      # (3, nt) momentum input from the external mode
    f2d_y: jax.Array
    fbar_edge: jax.Array  # (3, 2, nt) effective time-averaged eta edge flux


# SSPRK(3,3) effective stage weights: u1 = u0 + h(F0/6 + F1/6 + 2 F2/3)
_SSP_W = (1.0 / 6.0, 1.0 / 6.0, 2.0 / 3.0)


def run_external(geom: G.Geom2D, b: jax.Array, st0: State2D, dt: float,
                 m: int, forcing: Forcing2D = Forcing2D(),
                 f3d2d_x: Optional[jax.Array] = None,
                 f3d2d_y: Optional[jax.Array] = None,
                 coriolis_f: float = 0.0, bottom_cd: float = 0.0,
                 h_min: float = 0.05,
                 exchange_fn: Optional[Callable[[State2D], State2D]] = None,
                 exchange_period: int = 0) -> ExternalResult:
    """Advance the external mode by m sub-steps of dt/m (one fused scan).

    Returns the new state, the momentum increment F2D (paper eq. 6)
        F2D = (Q1 - (Q0 + dt*F3D2D)) / dt,
    and the *stage-weighted* time averages of the transport Qbar (paper eq. 5,
    refined: weights follow the SSPRK3 effective fluxes so the eta update is
    EXACTLY dt * div-flux(Qbar, Fbar_edge)) and of the free-surface edge flux
    Fbar_edge.  These make the 3D advection discretely consistent to machine
    precision (DESIGN.md §5).

    Distributed runs pass `exchange_fn` (halo refresh of the 2D state):
      exchange_period = 0: exchange before every RK-stage RHS (paper §3.3 —
        one halo exchange per 2D kernel iteration; needs a 1-deep halo);
      exchange_period = j>0: exchange once per j sub-steps (communication-
        avoiding; needs a 3j-deep halo, beyond-paper opt #2).
    """
    if f3d2d_x is None:
        f3d2d_x = jnp.zeros_like(st0.qx)
        f3d2d_y = jnp.zeros_like(st0.qy)
    dts = dt / m
    ex = exchange_fn if exchange_fn is not None else (lambda s: s)
    per_stage = exchange_fn is not None and exchange_period == 0

    def rhs(s):
        if per_stage:
            s = ex(s)
        r, eflux = external_rhs(geom, b, s, forcing, f3d2d_x, f3d2d_y, h_min,
                                return_flux=True)
        if coriolis_f != 0.0 or bottom_cd > 0.0:
            with jax.named_scope("burst.assembly"):
                r = r + standalone_extra_rhs(geom, b, s, coriolis_f,
                                             bottom_cd, h_min)
        return r, eflux

    def substep(s):
        r0, ef0 = rhs(s)
        with jax.named_scope("burst.assembly"):
            s1 = s + dts * r0
        r1, ef1 = rhs(s1)
        with jax.named_scope("burst.assembly"):
            s2 = 0.75 * s + 0.25 * (s1 + dts * r1)
        r2, ef2 = rhs(s2)
        with jax.named_scope("burst.assembly"):
            s3 = (1.0 / 3.0) * s + (2.0 / 3.0) * (s2 + dts * r2)
            w0, w1, w2 = _SSP_W
            qx_eff = w0 * s.qx + w1 * s1.qx + w2 * s2.qx
            qy_eff = w0 * s.qy + w1 * s1.qy + w2 * s2.qy
            ef_eff = w0 * ef0 + w1 * ef1 + w2 * ef2
        return s3, (qx_eff, qy_eff, ef_eff)

    if exchange_fn is not None and exchange_period > 0:
        assert m % exchange_period == 0, (m, exchange_period)
        def body(s, _):
            s = ex(s)
            accs = []
            for _ in range(exchange_period):   # unrolled burst
                s, acc = substep(s)
                accs.append(acc)
            with jax.named_scope("burst.assembly"):
                mean = tuple(sum(a[i] for a in accs) / exchange_period
                             for i in range(3))
            return s, mean
        st1, (qxs, qys, efs) = jax.lax.scan(
            body, st0, None, length=m // exchange_period)
    else:
        st1, (qxs, qys, efs) = jax.lax.scan(
            lambda s, _: substep(s), st0, None, length=m)
    # paper eq. 6: F2D = (Q1 - (Q0 + dt*F3D2D))/dt.  F3D2D enters the RHS as a
    # raw assembled integral (mass-weighted); F2D is a nodal rate, so the
    # subtraction must use the mass-inverted F3D2D.
    f2d_x = (st1.qx - st0.qx) / dt - G.minv_apply(geom, f3d2d_x)
    f2d_y = (st1.qy - st0.qy) / dt - G.minv_apply(geom, f3d2d_y)
    return ExternalResult(st1, qxs.mean(axis=0), qys.mean(axis=0),
                          f2d_x, f2d_y, efs.mean(axis=0))


def cfl_dt(geom: G.Geom2D, b: jax.Array, cfl: float = 0.25) -> float:
    """Explicit gravity-wave CFL time step estimate (static, numpy-side)."""
    import numpy as np
    h = np.sqrt(np.asarray(geom.area))           # element length scale
    c = np.sqrt(G.G_GRAV * np.maximum(np.asarray(b).max(axis=0), 0.05))
    return float((cfl * h / c).min())
