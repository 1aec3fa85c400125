"""Distributed (shard_map + halo exchange) correctness tests.

The decisive test: the sharded multi-device step must reproduce the
single-device step on the owned cells — for the paper-faithful per-stage
exchange AND the communication-avoiding k-halo variant.  Device-count
spoofing requires a fresh process, so the heavy checks run in subprocesses.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import mesh2d
from repro.distributed import partition

REPO = Path(__file__).resolve().parents[1]

# ---------------------------------------------------------------------------
# partition-building invariants (run in-process, numpy only)
# ---------------------------------------------------------------------------

def test_partition_covers_mesh():
    m = mesh2d.rect_mesh(16, 16, 1.0, 1.0, jitter=0.2, seed=1)  # nt = 512
    spec = partition.build_partition(m, 8, halo_depth=1)
    ids = spec.glob_ids[:, :spec.n_own].ravel()
    assert sorted(ids.tolist()) == list(range(m.nt))


def test_partition_halo_contains_all_neighbours():
    m = mesh2d.rect_mesh(16, 16, 1.0, 1.0, jitter=0.2, seed=1)
    spec = partition.build_partition(m, 8, halo_depth=1)
    for p in range(8):
        own = set(range(p * spec.n_own, (p + 1) * spec.n_own))
        local = set(spec.glob_ids[p].tolist())
        for t in own:
            for n in m.neigh_tri[t]:
                assert int(n) in local, (p, t, n)


def test_partition_exchange_tables_consistent():
    """Sending p's owned slot for triangle t must land in the receiver's halo
    slot for the same global triangle."""
    m = mesh2d.rect_mesh(16, 16, 1.0, 1.0, jitter=0.2, seed=1)
    spec = partition.build_partition(m, 8, halo_depth=2)
    trash = spec.n_loc - 1
    for off, (send, recv) in spec.tables.items():
        for src in range(8):
            dst = (src + off) % 8
            for j in range(send.shape[1]):
                r = recv[dst, j]
                if r == trash:
                    continue
                g_sent = spec.glob_ids[src, send[src, j]]
                g_recv = spec.glob_ids[dst, r]
                assert g_sent == g_recv, (off, src, j)


def test_scatter_gather_roundtrip():
    m = mesh2d.rect_mesh(16, 16, 1.0, 1.0, jitter=0.2, seed=1)
    spec = partition.build_partition(m, 8, halo_depth=1)
    f = np.random.default_rng(0).normal(size=(3, m.nt))
    back = partition.gather_field(spec, partition.scatter_field(spec, f))
    np.testing.assert_array_equal(back, f)


def _tiny_ocean(backend, nt_side=4):
    import jax
    from repro.core import stepper
    from repro.distributed.ocean import DistributedOcean
    m = mesh2d.rect_mesh(nt_side, nt_side, 4000.0, 4000.0, jitter=0.2,
                         seed=1)
    dmesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    cfg = stepper.OceanConfig(nl=2, backend=backend)
    return m, DistributedOcean(m, np.full((3, m.nt), 10.0), cfg, dmesh,
                               ("x",))


def test_kernel_dispatch_under_shard_map():
    """The sharded step traces the four ocean kernels on a kernel backend,
    and none on ``ref``; the run says which path it took."""
    import jax
    from repro.obs import metrics
    seen, gauges = {}, {}
    for backend in ("ref", "pallas_interpret"):
        metrics.reset()
        _, do = _tiny_ocean(backend)
        with jax.enable_x64(False):         # f32, as on the chip
            jax.jit(do.make_step_args()).lower(*do.abstract_args())
        snap = metrics.default().snapshot()
        seen[backend] = {k for k, v in snap["counter"].items()
                         if k.startswith("kernel_dispatch{") and v > 0}
        gauges[backend] = {k for k in snap["gauge"]
                           if k.startswith("distributed.backend")}
    metrics.reset()
    kernels = {"block_thomas", "solve_r", "solve_w", "lateral_flux"}
    assert {f"kernel_dispatch{{backend=pallas_interpret,op={op}}}"
            for op in kernels} <= seen["pallas_interpret"]
    assert not any("backend=ref" in k for k in seen["pallas_interpret"])
    assert not any("backend=pallas" in k for k in seen["ref"])
    assert gauges == {
        "ref": {"distributed.backend{backend=ref}"},
        "pallas_interpret": {"distributed.backend{backend=pallas_interpret}"}}


def test_abstract_args_places_nothing_and_matches_scatter_state(
        monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.core import geometry, stepper
    from repro.core.extrusion import VGrid
    m, do = _tiny_ocean("ref", nt_side=6)
    placed = []
    monkeypatch.setattr(do, "_place", lambda t: placed.append(t) or t)
    live = len(jax.live_arrays())
    args = do.abstract_args()
    assert not placed and len(jax.live_arrays()) == live
    monkeypatch.undo()
    geom = geometry.geom2d_from_mesh(m, dtype=jnp.float32)
    st = stepper.init_state(
        geom, VGrid(b=jnp.full((3, m.nt), 10.0, jnp.float32), nl=2),
        dtype=jnp.float32)
    shape = lambda t: jax.tree_util.tree_map(
        lambda x: (x.shape, jnp.dtype(x.dtype)), t)
    assert shape(args[3]) == shape(do.scatter_state(st))
    assert shape(args[:3]) == shape((do.geom_stk, do.b_stk, do.tables))


# ---------------------------------------------------------------------------
# the kernels under shard_map: 4 spoofed devices, the reef belt, a seeded
# state, against the one-device ref step and the benchmark's plain reference
# ---------------------------------------------------------------------------
_KERNEL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, json
import jax, jax.numpy as jnp
import numpy as np
jax.config.update("jax_enable_x64", True)
from jax.sharding import Mesh
from bench import harness, reference, traffic
from repro.core import dg2d, stepper
from repro.core.extrusion import VGrid
from repro.distributed import halo
from repro.distributed.ocean import DistributedOcean
from repro.kernels import column_solve
from repro.launch.ocean_dryrun import OceanCell, cell_setup

backend = "{backend}"
conf = json.load(open("bench/configs/gbr-share64.json"))
# gbr-share64's element size, dt, depths and physics on an 8 x 6 rectangle
# of 4 layers: 96 triangles, 24 owned a device
conf.update(nx=8, ny=6, lx=8 * 250e3 / 160, ly=6 * 2600e3 / 1290,
            triangles=96, nl=4)
cell = OceanCell("gbr-tiny", conf["nx"], conf["ny"], conf["lx"],
                 conf["ly"], conf["nl"], conf["m_2d"], conf["dt"],
                 conf["depth"], reef=True)
m, geom, b, cfg = cell_setup(cell, jnp.float64)
harness.same_physics(cfg, conf)
plain_case = reference.build(conf, jnp.float64)
assert np.array_equal(np.asarray(b), np.asarray(plain_case.vg.b))
f0 = traffic.initial_fields(traffic.load("wave-front"), conf,
                            plain_case.node_x,
                            np.asarray(plain_case.geom.node_y),
                            np.asarray(b), seed={seed})
vg = VGrid(b=jnp.asarray(b), nl=cfg.nl)
st = stepper.init_state(geom, vg, dtype=jnp.float64)
f = {{k: jnp.asarray(f0[k]) for k in ("eta", "qx", "qy", "ux", "uy", "T",
                                      "S")}}
st = dataclasses.replace(st, ext=dg2d.State2D(f["eta"], f["qx"], f["qy"]),
                         ux=f["ux"], uy=f["uy"], T=f["T"], S=f["S"])
start = harness.fields(st)
ref_cfg = dataclasses.replace(cfg, backend="ref")
one = harness.fields(jax.jit(
    lambda s: stepper.step(geom, vg, ref_cfg, s))(st))
plain = harness.fields(reference.step_fn(plain_case)(
    reference.initial_state(plain_case, f0, jnp.float64)))
dmesh = Mesh(np.array(jax.devices()[:4]), ("x",))


def gaps():
    jax.clear_caches()
    do = DistributedOcean(m, b, dataclasses.replace(cfg, backend=backend),
                          dmesh, ("x",), dtype=jnp.float64)
    got = harness.fields(do.gather_state(
        do.make_step()(do.scatter_state(st))))
    # per field the widest gap over the widest change of the step
    return {{against: max(v for k, v in harness.deviation(
                 got, want, start).items() if not k.endswith("_mean"))
             for against, want in (("one_device_ref", one),
                                   ("plain_reference", plain))}}


out = {{"sound": gaps()}}
exchange = halo.exchange
halo.exchange = lambda x, t: x
out["no_exchange"] = gaps()
halo.exchange = exchange
column_solve.block_thomas_cell = lambda lo, dg, up, rhs, **kw: rhs
out["identity_block_thomas"] = gaps()
print("GAPS", json.dumps(out))
"""

# float64 roundoff through one step, over the step's own change: the
# kernels and the fused horizontal RHS reorder sums, and the GLS limiters
# amplify a 2^-50 nudge of eta to 5.9e-12 of the field
# (tests/bench/test_bench_reference.py); a missing halo exchange or a
# column solve left out moves the step by percents
KERNEL_PATH_TOL = 1e-9


def _run_script(code: str, timeout: int = 1500) -> str:
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.pathsep.join([str(REPO / "src"), str(REPO)])})
    assert res.returncode == 0, res.stdout + res.stderr[-4000:]
    return res.stdout


@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
def test_sharded_step_agrees_on_the_reef_belt(backend):
    """The sharded step on 4 devices, with the column kernels on where the
    backend has them, takes the one-device ref step and the plain
    reference's step; the same comparison fails without the halo exchange
    and, on the kernel backend, with the block-Thomas kernel an identity."""
    out = _run_script(_KERNEL_SCRIPT.format(backend=backend, seed=2 ** 33 + 5))
    gaps = json.loads(out.split("GAPS", 1)[1])
    # a gap past the tolerance, or not finite, fails (as harness.judge)
    fails = lambda g: all(not v <= 1e3 * KERNEL_PATH_TOL  # noqa: E731
                          for v in g.values())
    assert max(gaps["sound"].values()) <= KERNEL_PATH_TOL, gaps
    assert fails(gaps["no_exchange"]), gaps
    broken = gaps["identity_block_thomas"]
    if backend == "ref":        # no kernel on the path: nothing changes
        assert broken == gaps["sound"], gaps
    else:
        assert fails(broken), gaps


# ---------------------------------------------------------------------------
# full equivalence in a subprocess with 8 spoofed devices
# ---------------------------------------------------------------------------
_EQUIV_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from repro.core import geometry, mesh2d, stepper
from repro.core.extrusion import VGrid
from repro.distributed.ocean import DistributedOcean

period = {period}
mesh = mesh2d.rect_mesh(16, 8, 4000.0, 2000.0, jitter=0.2, seed=4)  # nt=256
geom = geometry.geom2d_from_mesh(mesh)
b = np.full((3, mesh.nt), 20.0, np.float32)
cfg = stepper.OceanConfig(nl=3, dt=24.0, m_2d=12, use_gls=True,
                          eos_kind="linear", halo_exchange_period=period)
vg = VGrid(b=jnp.asarray(b), nl=3)
st = stepper.init_state(geom, vg)
eta0 = (0.05 * jnp.cos(jnp.pi * geom.node_x / 4000.0)
        * jnp.cos(jnp.pi * geom.node_y / 2000.0))
Tf = 10.0 + 2.0 * jnp.exp(-((geom.node_x - 1000.0) ** 2
                            + (geom.node_y - 800.0) ** 2) / 5e5)
T0 = jnp.broadcast_to(jnp.concatenate([Tf, Tf])[None], st.T.shape)
st = stepper.OceanState(ext=stepper.State2D(eta0, st.ext.qx, st.ext.qy),
                        ux=st.ux, uy=st.uy, T=T0, S=st.S,
                        turb_k=st.turb_k, turb_eps=st.turb_eps,
                        nu_t=st.nu_t, kappa_t=st.kappa_t, time=st.time)

# single device reference
step1 = jax.jit(lambda s: stepper.step(geom, vg, cfg, s))
ref = st
for _ in range(3):
    ref = step1(ref)

# distributed over 8 devices
dmesh = jax.make_mesh((2, 4), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
do = DistributedOcean(mesh, b, cfg, dmesh, ("data", "model"))
stk = do.scatter_state(st)
stepd = do.make_step()
for _ in range(3):
    stk = stepd(stk)
out = do.gather_state(stk)

for name in ("ux", "uy", "T", "S"):
    a = np.asarray(getattr(ref, name))
    bb = np.asarray(getattr(out, name))
    err = np.abs(a - bb).max()
    scale = np.abs(a).max() + 1e-12
    assert err < 5e-5 * max(scale, 1.0), (name, err, scale)
ea = np.asarray(ref.ext.eta); eb = np.asarray(out.ext.eta)
assert np.abs(ea - eb).max() < 5e-5, np.abs(ea - eb).max()
assert np.abs(np.asarray(ref.T)).max() > 10.0  # blob alive
print("EQUIV_OK period=", period)
'''


def _run_equiv(period):
    assert "EQUIV_OK" in _run_script(_EQUIV_SCRIPT.format(period=period))


@pytest.mark.slow
def test_distributed_equivalence_per_stage():
    """Paper-faithful: halo exchange before every 2D RK stage (1-deep halo)."""
    _run_equiv(0)


@pytest.mark.slow
def test_distributed_equivalence_comm_avoiding():
    """Beyond-paper: 2-substep exchange period with 6-deep halos."""
    _run_equiv(2)
