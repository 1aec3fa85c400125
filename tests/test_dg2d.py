"""External (2D barotropic) mode property tests.

The key physical invariants of the DG discretisation:
  * well-balancedness (lake at rest over varying bathymetry),
  * exact discrete mass conservation in a closed basin,
  * correct gravity-wave dynamics (standing-wave period),
  * energy dissipation (LF fluxes never create energy).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dg2d, geometry, mesh2d
from repro.core.dg2d import Forcing2D, State2D
from neighbour_gathers import edge_ext_point

G_ = geometry.G_GRAV


def make(nx=12, ny=10, lx=1000.0, ly=800.0, jitter=0.2, depth=20.0,
         shelf=False):
    m = mesh2d.rect_mesh(nx, ny, lx, ly, jitter=jitter, seed=2)
    geom = geometry.geom2d_from_mesh(m)
    if shelf:
        bfun = mesh2d.shelf_bathymetry(depth * 0.3, depth, lx)
        b = jnp.asarray(np.stack([bfun(np.stack(
            [np.asarray(geom.node_x[i]), np.asarray(geom.node_y[i])], 1))
            for i in range(3)]), dtype=jnp.float32)
    else:
        b = jnp.full((3, m.nt), depth)
    return m, geom, b


def zeros_state(nt):
    z = jnp.zeros((3, nt))
    return State2D(z, z, z)


def total_mass(geom, eta):
    return float(geometry.mass_apply(geom, eta).sum())


def total_energy(geom, b, st):
    H = st.eta + b
    e = 0.5 * G_ * st.eta ** 2 + 0.5 * (st.qx ** 2 + st.qy ** 2) / H
    # integrate P1-interpolated energy density
    return float(geometry.mass_apply(geom, e).sum())


def test_lake_at_rest_flat():
    m, geom, b = make()
    st = zeros_state(m.nt)
    r = dg2d.external_rhs(geom, b, st)
    for f in (r.eta, r.qx, r.qy):
        np.testing.assert_allclose(np.asarray(f), 0.0, atol=1e-6)


def test_lake_at_rest_shelf():
    """Well-balancedness: varying bathymetry, eta = 0, Q = 0 stays at rest."""
    m, geom, b = make(shelf=True)
    st = zeros_state(m.nt)
    r = dg2d.external_rhs(geom, b, st)
    # scale: g*H*grad(eta) terms would be O(g*20/1000) ~ 0.2 if unbalanced
    for f in (r.eta, r.qx, r.qy):
        np.testing.assert_allclose(np.asarray(f), 0.0, atol=1e-5)


def test_mass_conservation_closed_basin():
    m, geom, b = make(shelf=True)
    key = jax.random.PRNGKey(0)
    eta = 0.1 * jax.random.normal(key, (3, m.nt))
    qx = 0.5 * jax.random.normal(jax.random.fold_in(key, 1), (3, m.nt))
    qy = 0.5 * jax.random.normal(jax.random.fold_in(key, 2), (3, m.nt))
    st = State2D(eta, qx, qy)
    m0 = total_mass(geom, st.eta)
    dt = dg2d.cfl_dt(geom, b)
    step = jax.jit(lambda s: dg2d.ssprk3_step(
        lambda x: dg2d.external_rhs(geom, b, x), s, dt))
    for _ in range(20):
        st = step(st)
    m1 = total_mass(geom, st.eta)
    area = float(geom.area.sum())
    assert abs(m1 - m0) < 1e-7 * area, (m0, m1)


def test_gravity_wave_period():
    """Standing wave in a closed flat basin: eta = eps*cos(pi x/L).
    Exact period T = 2L/c with c = sqrt(gH). After one period the initial
    pattern must reappear (correlation > 0.97)."""
    lx, ly, depth = 1000.0, 400.0, 10.0
    m, geom, b = make(nx=32, ny=8, lx=lx, ly=ly, jitter=0.15, depth=depth)
    eps = 1e-3  # linear regime
    eta0 = eps * jnp.cos(jnp.pi * geom.node_x / lx)
    st = State2D(eta0, jnp.zeros_like(eta0), jnp.zeros_like(eta0))
    c = np.sqrt(G_ * depth)
    T = 2 * lx / c
    n_steps = 400
    dt = T / n_steps
    assert dt < dg2d.cfl_dt(geom, b, cfl=0.8)
    step = jax.jit(lambda s: dg2d.ssprk3_step(
        lambda x: dg2d.external_rhs(geom, b, x), s, dt))
    for _ in range(n_steps):
        st = step(st)
    a = np.asarray(eta0).ravel()
    bb = np.asarray(st.eta).ravel()
    corr = float(np.dot(a, bb) / (np.linalg.norm(a) * np.linalg.norm(bb)))
    assert corr > 0.97, corr


def test_energy_dissipation():
    """LF fluxes + walls must not create energy in a closed basin."""
    m, geom, b = make(shelf=True)
    key = jax.random.PRNGKey(3)
    eta = 0.05 * jax.random.normal(key, (3, m.nt))
    st = State2D(eta, jnp.zeros_like(eta), jnp.zeros_like(eta))
    e0 = total_energy(geom, b, st)
    dt = dg2d.cfl_dt(geom, b)
    step = jax.jit(lambda s: dg2d.ssprk3_step(
        lambda x: dg2d.external_rhs(geom, b, x), s, dt))
    es = [e0]
    for _ in range(50):
        st = step(st)
        es.append(total_energy(geom, b, st))
    assert es[-1] <= es[0] * (1 + 1e-5), es
    assert np.isfinite(es).all()


def test_run_external_f2d_identity():
    """F2D definition (paper eq. 6) must satisfy
    Q1 = Q0 + dt*(F3D2D + F2D) exactly."""
    m, geom, b = make()
    st0 = zeros_state(m.nt)
    f3x = 1e-4 * jnp.ones((3, m.nt))
    f3y = -2e-4 * jnp.ones((3, m.nt))
    dt = 10 * dg2d.cfl_dt(geom, b)
    res = jax.jit(lambda s: dg2d.run_external(
        geom, b, s, dt, m=10, f3d2d_x=f3x, f3d2d_y=f3y))(st0)
    # Q1 = Q0 + dt*(Minv F3D2D + F2D): F3D2D is raw-assembled, F2D nodal
    np.testing.assert_allclose(
        np.asarray(res.state.qx),
        np.asarray(st0.qx + dt * (geometry.minv_apply(geom, f3x) + res.f2d_x)),
        rtol=1e-4, atol=1e-8)
    np.testing.assert_allclose(
        np.asarray(res.state.qy),
        np.asarray(st0.qy + dt * (geometry.minv_apply(geom, f3y) + res.f2d_y)),
        rtol=1e-4, atol=1e-8)
    assert res.q_bar_x.shape == (3, m.nt)
    assert res.fbar_edge.shape == (3, 2, m.nt)


def test_open_boundary_tidal_inflow():
    """Channel with tidal elevation at open ends: flow develops, stays finite,
    and responds in the right direction (high eta at x=0 drives +x flow)."""
    mch = mesh2d.channel_mesh(24, 6, 3000.0, 600.0, jitter=0.1)
    geom = geometry.geom2d_from_mesh(mch)
    b = jnp.full((3, mch.nt), 10.0)
    amp = 0.2
    eta_bc = amp * (1.0 - geom.node_x / 3000.0)  # ~amp at x=0, 0 at x=L
    st = zeros_state(mch.nt)
    dt = dg2d.cfl_dt(geom, b)
    forcing = Forcing2D(eta_open=eta_bc)
    step = jax.jit(lambda s: dg2d.ssprk3_step(
        lambda x: dg2d.external_rhs(geom, b, x, forcing), s, dt))
    for _ in range(100):
        st = step(st)
    qx = np.asarray(st.qx)
    assert np.isfinite(qx).all()
    assert qx.mean() > 1e-4  # net +x transport develops


# ---------------------------------------------------------------------------
# the burst's neighbour gather: one record gather of the stacked state
# ---------------------------------------------------------------------------
def _edge_states_per_field(geom, st, forcing):
    """The per-field formula the record gather replaced: two point gathers
    per field, then the same ghost states."""
    ei = geometry.edge_interp(st.eta)
    qxi = geometry.edge_interp(st.qx)
    qyi = geometry.edge_interp(st.qy)
    ee, qxe, qye = (geometry.edge_blend(*edge_ext_point(geom, f))
                    for f in (st.eta, st.qx, st.qy))
    nx = geom.edge_nx[:, None, :]
    ny = geom.edge_ny[:, None, :]
    wall = geom.wall[:, None, :]
    openb = geom.openb[:, None, :]
    intm = 1.0 - wall - openb
    qn = qxe * nx + qye * ny
    qx_wall = qxe - 2.0 * qn * nx
    qy_wall = qye - 2.0 * qn * ny
    if forcing.eta_open is not None:
        eta_open = geometry.edge_interp(forcing.eta_open)
    else:
        eta_open = ee
    eta_e = intm * ee + wall * ei + openb * eta_open
    qx_e = intm * qxe + wall * qx_wall + openb * qxi
    qy_e = intm * qye + wall * qy_wall + openb * qyi
    return (ei, qxi, qyi), (eta_e, qx_e, qy_e)


def _channel(dtype, seed=5):
    """Jittered channel, WALL sides and OPEN ends, with a random state."""
    mch = mesh2d.channel_mesh(24, 6, 3000.0, 600.0, jitter=0.1)
    geom = geometry.geom2d_from_mesh(mch, dtype=dtype)
    key = jax.random.PRNGKey(seed)
    eta, qx, qy, bc = (jax.random.normal(jax.random.fold_in(key, i),
                                         (3, mch.nt), dtype=dtype)
                       for i in range(4))
    return mch, geom, State2D(0.1 * eta, qx, qy), 0.2 * bc


@pytest.mark.parametrize("open_bc", [False, True],
                         ids=["no_eta_open", "eta_open"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                         ids=["f32", "f64"])
def test_edge_states_match_per_field_gathers(dtype, open_bc):
    """The record gather gives the per-field formula's edge states, bit for
    bit, interior and ghost, on WALL and OPEN edges."""
    with jax.enable_x64(dtype == jnp.float64):     # f32 as on the chip
        mch, geom, st, bc = _channel(dtype)
        assert (mch.edge_type == mesh2d.WALL).any()
        assert (mch.edge_type == mesh2d.OPEN).any()
        forcing = Forcing2D(eta_open=bc if open_bc else None)
        got = jax.jit(dg2d._edge_states)(geom, st, forcing)
        want = jax.jit(_edge_states_per_field)(geom, st, forcing)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("stacked", [False, True], ids=["field", "stacked"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                         ids=["f32", "f64"])
def test_edge_ext_record_matches_nodal(dtype, stacked):
    """`edge_ext_record` returns the two point gathers' arrays bit for bit,
    for one (3, nt) field and for a stacked (3, 3, nt) one."""
    _, geom, st, _ = _channel(dtype, seed=9)
    f = jnp.stack([st.eta, st.qx, st.qy]) if stacked else st.qx
    got = geometry.edge_ext_record(geom, f)
    want = edge_ext_point(geom, f)
    for g, w in zip(got, want):
        assert g.shape == f.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_external_rhs_has_one_neighbour_gather():
    """The burst's right-hand side fetches neighbour values with exactly one
    gather instruction in the compiled program (six before the stacked
    record)."""
    import re
    mch, geom, st, bc = _channel(jnp.float32)
    b = jnp.full((3, mch.nt), 10.0, dtype=jnp.float32)
    hlo = jax.jit(dg2d.external_rhs).lower(
        geom, b, st, Forcing2D(eta_open=bc)).compile().as_text()
    gathers = [ln for ln in hlo.splitlines()
               if re.search(r"\sgather\(", ln)]
    neighbour = [ln for ln in gathers if "gather.neighbour" in ln]
    assert len(neighbour) == 1, neighbour
    assert len(gathers) > 1          # the edge-local selections stay apart
