"""The 3D stages' neighbour fetches are record gathers by ext_tri.

Every neighbour fetch of the 3D stages (`geometry.edge_interp_ext`,
`dg3d.edge_ext_nodal6`, `dg3d._gather_ext_grad`) takes one flat (K, nt)
record of the field by ext_tri and picks the facing nodes with an exact
select.  These tests hold each to the point-gather formula it replaced,
bit for bit, and count the gathers of the compiled step.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dg3d, geometry, mesh2d, stepper
from repro.core.extrusion import VGrid
from repro.launch import sim_campaign
from repro.launch.ocean_dryrun import OceanCell, cell_setup
from neighbour_gathers import (edge_ext_nodal6_point, edge_ext_point,
                               gather_ext_grad_point, hlo_neighbour_gathers,
                               is_record_gather)

NL = 3
DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                                 ids=["f32", "f64"])


def _channel(dtype):
    """Jittered channel, WALL sides and OPEN ends."""
    mch = mesh2d.channel_mesh(16, 5, 3000.0, 600.0, jitter=0.15)
    assert (mch.edge_type == mesh2d.WALL).any()
    assert (mch.edge_type == mesh2d.OPEN).any()
    return geometry.geom2d_from_mesh(mch, dtype=dtype)


def _normal(seed, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype=dtype)


def _same(got, want, dtype):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("lead", [(), (2, NL, 2)],
                         ids=["field2d", "zeta_interp3d"])
@DTYPES
def test_edge_interp_ext_matches_point_gathers(dtype, lead):
    """`edge_interp_ext` of a (3, nt) field and of a (k, nl, 2qz, 3, nt)
    one (the unfused path's `lat_interp_ext`) equals the blend of the two
    point gathers."""
    with jax.enable_x64(dtype == jnp.float64):     # f32 as on the chip
        geom = _channel(dtype)
        f = _normal(1, lead + (3, geom.nt), dtype)
        got = jax.jit(geometry.edge_interp_ext)(geom, f)
        want = jax.jit(lambda g, x: geometry.edge_blend(
            *edge_ext_point(g, x)))(geom, f)
    _same(got, want, dtype)


@DTYPES
def test_edge_ext_nodal6_matches_point_gathers(dtype):
    """One record of the whole (k, nl, 6, nt) field gives the four point
    gathers' (k, nl, 3edge, 2[a|b], 2[top|bot], nt)."""
    with jax.enable_x64(dtype == jnp.float64):
        geom = _channel(dtype)
        f = _normal(2, (4, NL, 6, geom.nt), dtype)
        got = jax.jit(dg3d.edge_ext_nodal6)(geom, f)
        want = jax.jit(edge_ext_nodal6_point)(geom, f)
    assert got.shape == (4, NL, 3, 2, 2, geom.nt)
    _same(got, want, dtype)


@DTYPES
def test_gather_ext_grad_matches_point_gathers(dtype):
    """One flat record of both gradient components gives the two
    per-component gathers' normal gradient."""
    with jax.enable_x64(dtype == jnp.float64):
        geom = _channel(dtype)
        gradf = _normal(3, (2, NL, 2, 2, geom.nt), dtype)
        got = jax.jit(dg3d._gather_ext_grad)(geom, gradf)
        want = jax.jit(gather_ext_grad_point)(geom, gradf)
    _same(got, want, dtype)


@pytest.mark.parametrize("bc", ["none", "bc_reflect", "open_values"])
@DTYPES
def test_field_states_match_point_gathers(monkeypatch, dtype, bc):
    """`field_states` through the record gathers equals `field_states`
    through the point gathers, every leaf bit for bit, with the wall
    reflection and the open-boundary blend."""
    with jax.enable_x64(dtype == jnp.float64):
        geom = _channel(dtype)
        f = _normal(4, (2, NL, 6, geom.nt), dtype)
        opens = _normal(5, (2, NL, 6, geom.nt), dtype)
        kw = {"bc_reflect": dict(bc_reflect=True),
              "open_values": dict(open_values=opens), "none": {}}[bc]
        got = jax.jit(lambda g, x: dg3d.field_states(g, x, **kw))(geom, f)
        monkeypatch.setattr(dg3d, "edge_ext_nodal6", edge_ext_nodal6_point)
        monkeypatch.setattr(dg3d, "_gather_ext_grad", gather_ext_grad_point)
        want = jax.jit(lambda g, x: dg3d.field_states(g, x, **kw))(geom, f)
    _same(got, want, dtype)


def test_step_neighbour_gathers_are_records():
    """The compiled step (GBR physics: reef shelf, Jackett, GLS, Coriolis;
    `ref` backend, the kernels' oracles) fetches neighbour values with 40
    gathers (81 with point gathers), each one index a record by ext_tri
    alone: a rank-2 (K, nt) operand, or the rank-1 triangle areas."""
    cell = OceanCell("tiny", 6, 5, 6000.0, 5000.0, NL, 2, 45.0, 120.0,
                     reef=True)
    with jax.enable_x64(False):
        _, geom, b, cfg = cell_setup(cell, jnp.float32)
        cfg = dataclasses.replace(cfg, backend="ref")
        vg = VGrid(b=jnp.asarray(b), nl=cfg.nl)
        st = stepper.init_state(geom, vg, dtype=jnp.float32)
        hlo = sim_campaign.step_program.lower(geom, vg, cfg,
                                              st).compile().as_text()
    gathers = hlo_neighbour_gathers(hlo)
    assert len(gathers) == 40, len(gathers)
    assert all(is_record_gather(g) for g in gathers), [
        g for g in gathers if not is_record_gather(g)]
    assert all(g["operand"][-1] == geom.nt for g in gathers)
    assert sum(len(g["operand"]) == 1 for g in gathers) == 1   # sigma3's area
