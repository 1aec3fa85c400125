"""Compile rehearsals for a TPU v5e that is described, not attached.

The four ocean kernels are compiled with ``interpret=False`` at the widths of
the one-chip benchmark cell (``chip_smoke.py``): nl=32 and 52,480 columns,
times the number of fields each call folds into its lanes.  A kernel that
Mosaic cannot lower (a scatter, a sublane concatenate, a misaligned slice,
too much VMEM) fails here, at no chip time.  The topology is described only
inside the fixture, never while a module is imported, so that several test
workers each collect the same tests and only one loads the TPU library.
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
NL = 32
NT = 52_480          # chip_share(OCEAN_CELLS["benchmark"], 4)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # what this compiles cannot be read back without a chip: keep it out
    # of any persistent cache the environment names
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


chip_smoke = _load_chip_smoke()


@pytest.mark.parametrize("name", ["block_thomas_cell", "lateral_flux_cell",
                                  "solve_r_cell", "solve_w_cell"])
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, _, shapes = chip_smoke.kernel_cases(NT, NL)[name]
    args = [_f32(one_chip, *s) for s in shapes]
    # the chip path runs with x64 off (Mosaic takes no 64-bit types); the
    # suite turns it on
    with jax.enable_x64(False):
        compiled = jax.jit(
            lambda *a: kernel(*a, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_step_compiles_for_v5e_2x2(one_chip, topo, monkeypatch):
    """The whole shard-mapped step on a described 2x2 host with the
    compiled kernels inside ``shard_map``, at a local size that is not a
    multiple of the 128-lane cell width; the column solve is the kernel,
    with no HLO LU under its scope."""
    import re
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from repro.core import mesh2d, stepper
    from repro.distributed.ocean import DistributedOcean
    # nothing can be placed on a described device: keep the partition data
    # on the host, where only their shapes are read
    monkeypatch.setattr(DistributedOcean, "_place", lambda self, t: t)
    m = mesh2d.rect_mesh(16, 8, 4000.0, 2000.0, jitter=0.2, seed=4)
    mesh = Mesh(np.array(topo.devices), ("x",))
    cfg = stepper.OceanConfig(nl=3, dt=24.0, m_2d=2, use_gls=False,
                              eos_kind="linear", backend="pallas")
    do = DistributedOcean(m, np.full((3, m.nt), 20.0, np.float32), cfg,
                          mesh, ("x",))
    assert do.spec.n_loc % 128
    shd = NamedSharding(mesh, do.pspec)
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=shd),
        do.abstract_args())
    with jax.enable_x64(False):
        hlo = jax.jit(do.make_step_args()).lower(*args).compile().as_text()
    assert "collective-permute" in hlo
    solve = [ln for ln in hlo.splitlines()
             if re.search(r'op_name="[^"]*/kops\.block_thomas\.pallas/', ln)]
    assert any("tpu_custom_call" in ln for ln in solve)
    assert not any(re.search(r'op_name="[^"]*/lu"', ln) for ln in solve)
    kernels = {k for k in ("block_thomas", "solve_r", "solve_w",
                           "lateral_flux")
               if re.search(rf'tpu_custom_call.*op_name="[^"]*'
                            rf'/kops\.{k}\.pallas/', hlo)}
    assert kernels == {"block_thomas", "solve_r", "solve_w", "lateral_flux"}


def test_neighbour_gathers_read_records_for_v5e(one_chip):
    """The one-chip step at 51,842 triangles (161 x 161 squares, the size of
    gbr-4chip's n_loc; GBR physics, nl=20, the compiled kernels): every
    neighbour gather reads a (K, nt) operand laid out triangle-major,
    ``{0,1}``, one contiguous record an index.  The point gathers of 3D
    fields that the records replaced got a triangle-minor operand at this
    size (``f32[2,20,3,51842]{3,0,2,1}``, 40 strided values an index), and a
    slab one at 51,520."""
    import dataclasses
    from repro.core import stepper
    from repro.core.extrusion import VGrid
    from repro.launch import sim_campaign
    from repro.launch.ocean_dryrun import OceanCell, cell_setup
    from neighbour_gathers import hlo_neighbour_gathers, is_record_gather
    cell = OceanCell("gbr-n_loc", 161, 161, 250e3, 250e3, 20, 20, 45.0,
                     120.0, reef=True)
    with jax.enable_x64(False):
        _, geom, b, cfg = cell_setup(cell, jnp.float32)
        cfg = dataclasses.replace(cfg, backend="pallas")
        vg = VGrid(b=jnp.asarray(b), nl=cfg.nl)
        st = stepper.init_state(geom, vg, dtype=jnp.float32)
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            (geom, vg, st))
        hlo = sim_campaign.step_program.lower(
            args[0], args[1], cfg, args[2]).compile().as_text()
    assert geom.nt == 51_842
    gathers = hlo_neighbour_gathers(hlo)
    assert len(gathers) == 40, len(gathers)
    assert all(is_record_gather(g) for g in gathers)
    records = [g for g in gathers if len(g["operand"]) == 2]
    assert len(records) == 39
    assert all(g["layout"] == (0, 1) for g in records), [
        g for g in records if g["layout"] != (0, 1)]


def test_chip_smoke_needs_tpu_and_cache_follows_env(monkeypatch, tmp_path):
    if jax.default_backend() == "tpu":
        pytest.skip("the device check is exercised where there is no TPU")
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.check_device()

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.cache_dir() == str(ROOT / ".jax_cache")
