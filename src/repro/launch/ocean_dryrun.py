"""Ocean-model cells for the multi-pod dry-run (the paper's own workload).

Two configurations:
  * benchmark: the paper's timeline/benchmark mesh class — 210k triangles,
    32 sigma layers (Fig. 2 caption), m=20 external sub-steps;
  * gbr: Great-Barrier-Reef scale — 3.3M triangles (paper §5), 20 layers
    (paper: 10-29 variable; sigma grid uses the mean), reef-belt bathymetry.

Each lowers one full split-IMEX internal step (both stages, both external
bursts, implicit solves, GLS) of the shard_map'd distributed stepper with
ShapeDtypeStruct inputs for the (16,16) and (2,16,16) production meshes.
The shard-local step runs the kernel backend ``OceanConfig.backend``
resolves to, as one device does: the compiled Pallas kernels on a TPU.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..core import geometry, mesh2d, stepper
from ..distributed.ocean import DistributedOcean


@dataclasses.dataclass(frozen=True)
class OceanCell:
    name: str
    nx: int
    ny: int
    lx: float
    ly: float
    nl: int
    m_2d: int
    dt: float
    depth: float
    reef: bool = False
    halo_exchange_period: int = 0


OCEAN_CELLS = {
    # 2*320*328 = 209,920 triangles (divisible by 512), 32 layers
    "benchmark": OceanCell("benchmark", 320, 328, 512e3, 512e3, 32, 20,
                           60.0, 50.0),
    # 2*1280*1290 = 3,302,400 triangles, GBR-scale
    "gbr": OceanCell("gbr", 1280, 1290, 2000e3, 2600e3, 20, 20, 45.0,
                     120.0, reef=True),
    # communication-avoiding variant of the benchmark (beyond-paper opt #2)
    "benchmark-ca2": OceanCell("benchmark-ca2", 320, 328, 512e3, 512e3, 32,
                               20, 60.0, 50.0, halo_exchange_period=2),
}


def chip_share(cell: OceanCell, n_chips: int = 4) -> OceanCell:
    """One chip's share of ``cell`` spread over ``n_chips`` (a square
    number): the domain is cut along both axes at the same element size,
    so dt, m_2d and the CFL numbers stay those of the full cell."""
    k = int(round(n_chips ** 0.5))
    if k * k != n_chips or cell.nx % k or cell.ny % k:
        raise ValueError(f"{cell.name}: cannot cut into {n_chips} squares")
    return dataclasses.replace(cell, name=f"{cell.name}/{n_chips}",
                               nx=cell.nx // k, ny=cell.ny // k,
                               lx=cell.lx / k, ly=cell.ly / k)


def cell_setup(cell: OceanCell, dtype=jnp.float32):
    """Mesh, geometry, nodal bathymetry (3, nt) and OceanConfig of a cell:
    the one description the dry-run, the campaign and ``chip_smoke.py``
    share."""
    m = mesh2d.rect_mesh(cell.nx, cell.ny, cell.lx, cell.ly, jitter=0.2,
                         seed=7)
    if cell.reef:
        bf = mesh2d.reef_bathymetry(0.1 * cell.depth, cell.depth, cell.lx,
                                    cell.ly)
    else:
        bf = mesh2d.shelf_bathymetry(0.3 * cell.depth, cell.depth, cell.lx)
    geom = geometry.geom2d_from_mesh(m, dtype=dtype)
    pts = np.stack([np.asarray(geom.node_x).ravel(),
                    np.asarray(geom.node_y).ravel()], axis=1)
    b = bf(pts).reshape(3, m.nt).astype(dtype)
    cfg = stepper.OceanConfig(
        nl=cell.nl, dt=cell.dt, m_2d=cell.m_2d, coriolis_f=-4e-5,
        eos_kind="jackett", use_gls=True,
        halo_exchange_period=cell.halo_exchange_period)
    return m, geom, b, cfg


def build_cell(cell: OceanCell, device_mesh):
    m, _, b, cfg = cell_setup(cell)
    return DistributedOcean(m, b, cfg, device_mesh,
                            axes=device_mesh.axis_names)


def lower_ocean(config_name: str, device_mesh):
    cell = OCEAN_CELLS[config_name]
    do = build_cell(cell, device_mesh)
    fn = do.make_step_args()
    args = do.abstract_args()
    lowered = jax.jit(fn).lower(*args)
    aux = dict(arch=f"ocean-{cell.name}", shape=f"nl{cell.nl}_m{cell.m_2d}",
               n_triangles=cell.nx * cell.ny * 2, n_layers=cell.nl,
               model_flops=0.0,
               n_params=0, n_params_active=0)
    return lowered, aux
