"""Distributed (shard_map + halo exchange) correctness tests.

The decisive test: the sharded multi-device step must reproduce the
single-device step on the owned cells — for the paper-faithful per-stage
exchange AND the communication-avoiding k-halo variant.  Device-count
spoofing requires a fresh process, so the heavy checks run in subprocesses.
"""
import subprocess
import sys

import numpy as np
import pytest

from repro.core import mesh2d
from repro.distributed import partition

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


@pytest.mark.parametrize("backend", ["pallas", "pallas_interpret"])
def test_distributed_rejects_kernel_backends(backend):
    """Kernels do not run under shard_map yet: asking for them is an error,
    not a silent switch to the reference."""
    import jax
    from repro.core import stepper
    from repro.distributed.ocean import DistributedOcean
    m = mesh2d.rect_mesh(4, 4, 1.0, 1.0, jitter=0.2, seed=1)
    dmesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    cfg = stepper.OceanConfig(nl=2, backend=backend)
    with pytest.raises(ValueError, match="backend 'ref' only"):
        DistributedOcean(m, np.full((3, m.nt), 10.0), cfg, dmesh, ("x",))


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
    res = subprocess.run(
        [sys.executable, "-c", _EQUIV_SCRIPT.format(period=period)],
        capture_output=True, text=True, timeout=1500,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "EQUIV_OK" in res.stdout


@pytest.mark.slow
def test_distributed_equivalence_per_stage():
    """Paper-faithful: halo exchange before every 2D RK stage (1-deep halo)."""
    _run_equiv(0)


@pytest.mark.slow
def test_distributed_equivalence_comm_avoiding():
    """Beyond-paper: 2-substep exchange period with 6-deep halos."""
    _run_equiv(2)
