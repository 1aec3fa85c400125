"""Chaos-hardening tests: deterministic fault injection, verified
checkpoints, runner recovery semantics, and the graceful-degradation dt
ladder.

The heavyweight chaos *matrix* (every recoverable fault class bitwise-equal
to a fault-free run) runs here on a tiny ocean mesh and again in
``scripts/ci.sh --chaos-smoke``."""
import dataclasses
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

from repro.checkpoint.checkpoint import (CheckpointCorruption,
                                         CheckpointError, Checkpointer)
from repro.launch import sim_campaign
from repro.obs import diagnostics as obs_diag
from repro.obs import metrics
from repro.runtime import chaos
from repro.runtime.fault_tolerance import (LadderConfig, RunnerConfig,
                                           SimulationRunner, TrainRunner)

F64 = jnp.float64


def tree_equal(a, b) -> bool:
    la = [np.asarray(x) for x in jax.tree_util.tree_leaves(a)]
    lb = [np.asarray(x) for x in jax.tree_util.tree_leaves(b)]
    return len(la) == len(lb) and all(
        x.shape == y.shape and np.array_equal(x, y, equal_nan=True)
        for x, y in zip(la, lb))


def demo_tree():
    return {"a": jnp.arange(6.0).reshape(2, 3),
            "b": {"c": jnp.asarray(3), "d": (jnp.ones(4), jnp.zeros(2))}}


# ---------------------------------------------------------------------------
# Checkpointer: manifest, verification, fallback
# ---------------------------------------------------------------------------
def test_checkpoint_manifest_written(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(3, demo_tree(), blocking=True)
    meta = ck.manifest(3)
    assert meta["format"] == 2 and meta["step"] == 3
    assert set(meta["leaves"]) == set(meta["keys"])
    info = meta["leaves"]["a"]
    assert info["shape"] == [2, 3] and info["dtype"] == "float64"
    assert isinstance(info["crc32"], int)
    assert ck.verify(3) == []


def test_checkpoint_verify_detects_corruption(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, demo_tree(), blocking=True)
    d = str(tmp_path / "step_000000001")

    # bit flip -> checksum mismatch
    fn = os.path.join(d, "a.npy")
    data = bytearray(open(fn, "rb").read())
    data[-1] ^= 0xFF
    open(fn, "wb").write(bytes(data))
    assert any("checksum" in p for p in ck.verify(1))

    # truncation -> unreadable
    with open(fn, "r+b") as fh:
        fh.truncate(os.path.getsize(fn) // 2)
    assert any("unreadable" in p or "checksum" in p for p in ck.verify(1))

    # missing leaf
    os.remove(fn)
    assert any("missing" in p for p in ck.verify(1))


def test_restore_falls_back_to_newest_intact(tmp_path):
    ck = Checkpointer(str(tmp_path), keep_last=5)
    t1 = demo_tree()
    t2 = jax.tree_util.tree_map(lambda x: x + 1, t1)
    ck.save(1, t1, blocking=True)
    ck.save(2, t2, blocking=True)
    # corrupt the newest step
    fn = tmp_path / "step_000000002" / "a.npy"
    with open(fn, "r+b") as fh:
        fh.truncate(4)
    assert ck.intact_steps() == [1]
    out = ck.restore(demo_tree())           # auto: falls back to step 1
    assert tree_equal(out, t1)
    # explicit request for the corrupt step raises instead of substituting
    with pytest.raises(CheckpointCorruption):
        ck.restore(demo_tree(), step=2)


def test_latest_step_survives_bad_pointer(tmp_path):
    ck = Checkpointer(str(tmp_path), keep_last=5)
    ck.save(1, demo_tree(), blocking=True)
    ck.save(2, demo_tree(), blocking=True)
    latest = tmp_path / "latest"
    latest.write_text("step_000000999")         # dangling
    assert ck.latest_step() == 2
    latest.write_text("step_000000001")         # stale
    assert ck.latest_step() == 2
    latest.unlink()                             # missing
    assert ck.latest_step() == 2


def test_restore_latest_skips_corrupt_and_reports_step(tmp_path):
    ck = Checkpointer(str(tmp_path), keep_last=5)
    t1 = demo_tree()
    ck.save(4, t1, blocking=True)
    ck.save(6, jax.tree_util.tree_map(lambda x: x * 2, t1), blocking=True)
    os.remove(tmp_path / "step_000000006" / "b__c.npy")   # missing leaf
    out, step = ck.restore_latest(demo_tree())
    assert step == 4 and tree_equal(out, t1)
    # nothing on disk -> (None, None), the runner's cold-restore signal
    ck2 = Checkpointer(str(tmp_path / "empty"))
    assert ck2.restore_latest(demo_tree()) == (None, None)


# ---------------------------------------------------------------------------
# Checkpointer: async save failures must be loud (satellite 1)
# ---------------------------------------------------------------------------
def test_async_save_failure_reraised_from_wait(tmp_path, monkeypatch):
    ck = Checkpointer(str(tmp_path))

    def boom(*a, **k):
        raise OSError("disk full (injected)")
    monkeypatch.setattr(np, "save", boom)      # temp-dir leaf write fails
    ck.save(1, demo_tree())                    # async: no error yet
    with pytest.raises(CheckpointError, match="disk full"):
        ck.wait()
    assert ck.latest_step() is None            # nothing pretends to exist


def test_async_save_failure_reraised_from_next_save(tmp_path, monkeypatch):
    ck = Checkpointer(str(tmp_path))
    calls = {"n": 0}
    real_save = np.save

    def flaky(path, arr, *a, **k):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("quota exceeded (injected)")
        return real_save(path, arr, *a, **k)
    monkeypatch.setattr(np, "save", flaky)
    ck.save(1, demo_tree())
    with pytest.raises(CheckpointError, match="quota"):
        ck.save(2, demo_tree(), blocking=True)
    # the error is consumed: a fresh save goes through and verifies
    ck.save(3, demo_tree(), blocking=True)
    assert ck.latest_step() == 3 and ck.verify(3) == []


def test_chaos_io_error_site_in_worker(tmp_path):
    ck = Checkpointer(str(tmp_path))
    plan = chaos.FaultPlan([chaos.Fault("checkpoint.write", "io_error")])
    with chaos.active(plan):
        ck.save(1, demo_tree())
        with pytest.raises(CheckpointError, match="chaos"):
            ck.wait()
    assert plan.log[0]["kind"] == "io_error"
    ck.save(2, demo_tree(), blocking=True)     # disarmed: saves fine
    assert ck.latest_step() == 2


# ---------------------------------------------------------------------------
# chaos harness unit tests
# ---------------------------------------------------------------------------
def test_fault_validation_and_parse():
    with pytest.raises(ValueError, match="site"):
        chaos.Fault("nope", "poison_nan")
    with pytest.raises(ValueError, match="kind"):
        chaos.Fault("sim.state", "nope")
    f = chaos.parse_fault("poison_nan@sim.state:step=5,field=T,count=2")
    assert (f.site, f.kind, f.step, f.field, f.count) == \
        ("sim.state", "poison_nan", 5, "T", 2)
    f2 = chaos.parse_fault("stall@runner.step:seconds=0.01")
    assert f2.args == {"seconds": 0.01}


def test_site_is_identity_without_plan():
    x = {"a": jnp.ones(3)}
    assert chaos.site("sim.state", x, step=0) is x


def test_poison_is_deterministic_and_field_targeted():
    st = {"T": jnp.zeros((4, 5)), "S": jnp.zeros((4, 5)),
          "turb_k": jnp.zeros(3)}

    def poisoned(seed):
        plan = chaos.FaultPlan([chaos.Fault("sim.state", "poison_nan",
                                            step=2, field="T")], seed=seed)
        with chaos.active(plan):
            out = chaos.site("sim.state", st, step=2)
        return out, plan
    o1, p1 = poisoned(0)
    o2, _ = poisoned(0)
    o3, _ = poisoned(1)
    assert tree_equal(o1, o2)                        # same seed, same cell
    assert np.isnan(np.asarray(o1["T"])).sum() == 1  # exactly one element
    assert not np.isnan(np.asarray(o1["S"])).any()   # exact-name match:
    assert not np.isnan(np.asarray(o1["turb_k"])).any()   # T != turb_k
    i1 = np.flatnonzero(np.isnan(np.asarray(o1["T"]).ravel()))
    i3 = np.flatnonzero(np.isnan(np.asarray(o3["T"]).ravel()))
    assert p1.log and "T" in p1.log[0]["detail"]
    # step gating: nothing fires off-step
    plan = chaos.FaultPlan([chaos.Fault("sim.state", "poison_nan",
                                        step=2, field="T")])
    with chaos.active(plan):
        out = chaos.site("sim.state", st, step=1)
    assert tree_equal(out, st) and plan.log == []
    del i1, i3   # (different seeds may or may not collide; determinism is
    #              what matters and is asserted above)


def test_corrupt_leaf_and_latest_injectors(tmp_path):
    ck = Checkpointer(str(tmp_path), keep_last=5)
    ck.save(1, demo_tree(), blocking=True)
    plan = chaos.FaultPlan([
        chaos.Fault("checkpoint.saved", "truncate", step=2, field="a"),
        chaos.Fault("checkpoint.saved", "stale_latest", step=3)])
    with chaos.active(plan):
        ck.save(2, demo_tree(), blocking=True)
        ck.save(3, demo_tree(), blocking=True)
    assert any("checksum" in p or "unreadable" in p for p in ck.verify(2))
    assert open(tmp_path / "latest").read().strip() == "step_000000001"
    # hardened latest_step ignores the stale pointer; restore skips step 2
    assert ck.latest_step() == 3
    assert 2 not in ck.intact_steps()


# ---------------------------------------------------------------------------
# halo-exchange payload corruption (trace-time site)
# ---------------------------------------------------------------------------
def test_halo_payload_chaos_site():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.distributed import halo

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    t = halo.HaloTables(send=(jnp.arange(2, dtype=jnp.int32),),
                        recv=(jnp.asarray([2, 3], jnp.int32),),
                        offsets=(0,), n_devices=1, axes=("x",))
    x = jnp.arange(1.0, 5.0)[None, :]           # (1 device, 4 slots)

    def f(xs):
        return halo.exchange(xs[0], t)[None]
    run = lambda: jax.jit(shard_map(f, mesh=mesh, in_specs=P("x"),
                                    out_specs=P("x"), check_vma=False))(x)
    clean = np.asarray(run())
    np.testing.assert_array_equal(clean[0], [1.0, 2.0, 1.0, 2.0])

    plan = chaos.FaultPlan([chaos.Fault("halo.payload", "halo_nan")])
    with chaos.active(plan):                    # armed during TRACING
        poisoned = np.asarray(jax.jit(shard_map(
            lambda xs: halo.exchange(xs[0] * 1.0, t)[None], mesh=mesh,
            in_specs=P("x"), out_specs=P("x"), check_vma=False))(x))
    assert np.isnan(poisoned[0, 2:]).all()      # halo slots poisoned
    np.testing.assert_array_equal(poisoned[0, :2], [1.0, 2.0])  # owned intact
    assert plan.log[0]["kind"] == "halo_nan"


# ---------------------------------------------------------------------------
# elastic restore (satellite 4)
# ---------------------------------------------------------------------------
_SUBPROC_ENV = {"PYTHONPATH": "src", "HOME": "/root",
                "PATH": "/usr/bin:/bin", "JAX_ENABLE_X64": "1",
                # without this jax probes for TPUs at backend init and hangs
                "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}


def test_elastic_restore_1_to_8_devices(tmp_path):
    """Save on THIS 1-device process; restore onto 8 spoofed devices with a
    sharded layout; global array must be bitwise identical."""
    ck = Checkpointer(str(tmp_path))
    x = jnp.arange(64.0).reshape(8, 8)
    ck.save(7, {"x": x}, blocking=True)
    assert ck.verify(7) == []
    script = f'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.checkpoint.checkpoint import Checkpointer
mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
sh = NamedSharding(mesh, P("data", None))
ck = Checkpointer({str(tmp_path)!r})
out = ck.restore({{"x": jnp.zeros((8, 8))}}, shardings={{"x": sh}})
assert len(out["x"].sharding.device_set) == 8, out["x"].sharding
assert np.array_equal(np.asarray(out["x"]),
                      np.arange(64.0).reshape(8, 8)), "values differ"
print("RESTORED_8DEV")
'''
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=300,
                         env=_SUBPROC_ENV, cwd="/root/repo")
    assert "RESTORED_8DEV" in res.stdout, res.stdout + res.stderr


def test_elastic_restore_8_to_1_devices(tmp_path):
    """Save sharded over 8 spoofed devices; restore in THIS 1-device
    process; the manifest-verified global array is bitwise identical."""
    script = f'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.checkpoint.checkpoint import Checkpointer
mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                   NamedSharding(mesh, P("data", None)))
ck = Checkpointer({str(tmp_path)!r})
ck.save(5, {{"x": x}}, blocking=True)
assert ck.verify(5) == [], ck.verify(5)
print("SAVED_8DEV")
'''
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=300,
                         env=_SUBPROC_ENV, cwd="/root/repo")
    assert "SAVED_8DEV" in res.stdout, res.stdout + res.stderr
    ck = Checkpointer(str(tmp_path))
    meta = ck.manifest(5)
    assert meta["leaves"]["x"]["shape"] == [8, 8]   # GLOBAL shape on disk
    out = ck.restore({"x": jnp.zeros((8, 8))})
    np.testing.assert_array_equal(np.asarray(out["x"]),
                                  np.arange(64.0).reshape(8, 8))


def test_elastic_reshard_chaos_site(tmp_path):
    """The runner.restore_shardings site swaps shardings at recovery time
    (elastic restore onto a different layout), bitwise-preserving."""
    from jax.sharding import SingleDeviceSharding
    ck = Checkpointer(str(tmp_path))
    x = jnp.arange(12.0).reshape(3, 4)
    ck.save(2, {"x": x}, blocking=True)

    cfg = RunnerConfig(checkpoint_dir=str(tmp_path), emit_metrics=False)
    runner = SimulationRunner(lambda c: None, object(), cfg)
    sh = {"x": SingleDeviceSharding(jax.devices()[0])}
    plan = chaos.FaultPlan([chaos.Fault("runner.restore_shardings",
                                        "reshard", args={"shardings": sh})])
    with chaos.active(plan):
        state, step = runner._recover({"x": jnp.zeros((3, 4))}, None, 0)
    assert step == 2 and plan.log[0]["kind"] == "reshard"
    assert state["x"].sharding == sh["x"]
    np.testing.assert_array_equal(np.asarray(state["x"]), np.asarray(x))


# ---------------------------------------------------------------------------
# runner semantics (satellites 2 + 3)
# ---------------------------------------------------------------------------
class _Dataset:
    def batch_at(self, step):
        return {"x": jnp.asarray(float(step))}


def test_train_runner_cold_restore_from_start(tmp_path):
    """A failure BEFORE the first checkpoint must restart from the caller's
    start snapshot (counted as a cold restore), not silently retry the
    in-memory state."""
    seen = []
    failed = {"done": False}

    def step_fn(state, batch):
        s = int(state["step"])
        seen.append(s)
        if s == 2 and not failed["done"]:
            failed["done"] = True
            raise RuntimeError("injected failure before first checkpoint")
        return ({"step": state["step"] + 1,
                 "acc": state["acc"] + batch["x"]}, {"loss": 1.0})

    cfg = RunnerConfig(checkpoint_dir=str(tmp_path), checkpoint_every=100,
                       max_retries=2, emit_metrics=False,
                       backoff_base_s=0.0)
    runner = TrainRunner(step_fn, _Dataset(), cfg)
    out = runner.run({"step": jnp.asarray(0), "acc": jnp.asarray(0.0)},
                     n_steps=4, resume=False)
    assert runner.stats["cold_restores"] == 1
    assert seen == [0, 1, 2, 0, 1, 2, 3]        # restarted from scratch
    assert int(out["step"]) == 4
    assert float(out["acc"]) == sum(range(4))   # deterministic re-run


def test_signal_handlers_restored_after_run(tmp_path):
    """The runner's SIGTERM/SIGINT handlers must not leak past run()
    (previously they leaked into pytest and subsequent code)."""
    sentinel = lambda signum, frame: None
    prev_term = signal.getsignal(signal.SIGTERM)
    prev_int = signal.getsignal(signal.SIGINT)
    try:
        signal.signal(signal.SIGTERM, sentinel)
        cfg = RunnerConfig(checkpoint_dir=str(tmp_path),
                           emit_metrics=False)
        runner = TrainRunner(
            lambda s, b: (s, {"loss": 1.0}), _Dataset(), cfg)
        runner.run({"s": jnp.asarray(0)}, n_steps=2, resume=False)
        assert signal.getsignal(signal.SIGTERM) is sentinel
        assert signal.getsignal(signal.SIGINT) is prev_int
        # ... even when the run dies on an exhausted failure
        bad = TrainRunner(lambda s, b: (_ for _ in ()).throw(
            RuntimeError("boom")), _Dataset(),
            dataclasses.replace(cfg, max_retries=0, backoff_base_s=0.0))
        with pytest.raises(RuntimeError, match="boom"):
            bad.run({"s": jnp.asarray(0)}, n_steps=2, resume=False)
        assert signal.getsignal(signal.SIGTERM) is sentinel
    finally:
        signal.signal(signal.SIGTERM, prev_term)
        signal.signal(signal.SIGINT, prev_int)


def test_runner_save_failure_is_retried_not_silent(tmp_path):
    """An async save failure surfaces at the next save and is retried
    synchronously — the run keeps its checkpoint cadence."""
    cfg = RunnerConfig(checkpoint_dir=str(tmp_path), checkpoint_every=2,
                       max_retries=2, emit_metrics=False, backoff_base_s=0.0)
    runner = TrainRunner(
        lambda s, b: ({"step": s["step"] + 1}, {"loss": 1.0}),
        _Dataset(), cfg)
    plan = chaos.FaultPlan([chaos.Fault("checkpoint.write", "io_error",
                                        step=2)])
    with chaos.active(plan):
        out = runner.run({"step": jnp.asarray(0)}, n_steps=6, resume=False)
    assert int(out["step"]) == 6
    assert runner.stats["ckpt_failures"] == 1
    assert runner.ckpt.latest_step() == 6       # cadence recovered


# ---------------------------------------------------------------------------
# SimulationRunner: synthetic ladder mechanics (fast, no ocean step)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class _ToyCfg:
    dt: float = 10.0


def test_sim_ladder_engages_and_rewidens(tmp_path):
    """Deterministic early-phase failure at full dt: blind retry loops, the
    ladder degrades to dt/2, rides out the rough phase, then re-widens."""
    def factory(cfg):
        def fn(state):
            n = int(state["n"])
            if cfg.dt == 10.0 and n < 2:
                raise RuntimeError(f"synthetic blow-up at n={n}")
            return {"n": state["n"] + 1}, {"nonfinite": False,
                                           "cfl_2d": 0.2}
        return fn

    cfg = RunnerConfig(checkpoint_dir=str(tmp_path), checkpoint_every=2,
                       max_retries=3, emit_metrics=False, backoff_base_s=0.0)
    ladder = LadderConfig(dt_factor=0.5, max_rungs=2, recover_steps=3,
                          cfl_ok=0.8)
    runner = SimulationRunner(factory, _ToyCfg(), cfg, ladder=ladder)
    out = runner.run({"n": jnp.asarray(0)}, n_steps=6, resume=False)
    assert int(out["n"]) == 6
    # retry 1: plain restore at full dt (fails again); retry 2: rung 1
    assert runner.stats["retries"] == 2
    assert runner.stats["cold_restores"] == 2   # no checkpoint existed yet
    assert runner.stats["ladder_engagements"] == 1
    assert runner.stats["ladder_transitions"] == 2   # down once, up once
    assert runner.rung == 0                          # re-widened


def test_sim_ladder_disabled_is_blind_retry(tmp_path):
    def factory(cfg):
        def fn(state):
            if cfg.dt == 10.0:
                raise RuntimeError("deterministic blow-up")
            return {"n": state["n"] + 1}, {"nonfinite": False}
        return fn

    cfg = RunnerConfig(checkpoint_dir=str(tmp_path), max_retries=3,
                       emit_metrics=False, backoff_base_s=0.0)
    runner = SimulationRunner(factory, _ToyCfg(), cfg,
                              ladder=LadderConfig(max_rungs=0))
    with pytest.raises(RuntimeError, match="deterministic blow-up"):
        runner.run({"n": jnp.asarray(0)}, n_steps=4, resume=False)
    assert runner.stats["retries"] == 4          # exhausted, no escalation


# ---------------------------------------------------------------------------
# the ocean chaos matrix (bitwise recovery) + the CFL blow-up ladder
# ---------------------------------------------------------------------------
N_STEPS = 6
_FNS = {}


@pytest.fixture(scope="module")
def ocean_case():
    return sim_campaign.build_case(nx=4, ny=3, nl=4)


def _factory_for(case):
    """step_factory with a per-dt jit cache so the matrix scenarios reuse
    one compiled step."""
    def factory(cfg):
        key = (float(cfg.dt), float(cfg.nu_v_bg))
        if key not in _FNS:
            _FNS[key] = jax.jit(lambda s: obs_diag.step_with_diagnostics(
                case.geom, case.vg, cfg, s))
        return _FNS[key]
    return factory


def _run_ocean(case, tmp_path, name, plan, resume=False, n=N_STEPS):
    cfg = RunnerConfig(checkpoint_dir=str(tmp_path / name),
                       checkpoint_every=2, max_retries=3,
                       emit_metrics=False, backoff_base_s=0.0)
    runner = SimulationRunner(
        _factory_for(case), case.cfg, cfg,
        policy=obs_diag.MonitorPolicy(cfl_max=1.0, on_violation="halt"))
    ctx = chaos.active(plan) if plan is not None else _Null()
    with ctx:
        out = runner.run(case.state, n, resume=resume)
    return out, runner


class _Null:
    def __enter__(self):
        return None

    def __exit__(self, *e):
        return False


@pytest.fixture(scope="module")
def ocean_baseline(ocean_case, tmp_path_factory):
    out, _ = _run_ocean(ocean_case, tmp_path_factory.mktemp("base"),
                        "baseline", plan=None)
    return out


def test_chaos_matrix_nan_poison_bitwise(ocean_case, ocean_baseline,
                                         tmp_path):
    plan = chaos.FaultPlan([chaos.Fault("sim.state", "poison_nan",
                                        step=N_STEPS - 1, field="T")])
    out, runner = _run_ocean(ocean_case, tmp_path, "nan", plan)
    assert len(plan.log) == 1
    assert runner.stats["retries"] == 1
    assert runner.rung == 0                      # transient: no degradation
    assert tree_equal(out, ocean_baseline)


def test_chaos_matrix_corrupt_checkpoint_bitwise(ocean_case, ocean_baseline,
                                                 tmp_path):
    metrics.reset()
    plan = chaos.FaultPlan(
        [chaos.Fault("checkpoint.saved", "truncate", step=4),
         chaos.Fault("sim.state", "poison_inf", step=N_STEPS - 1,
                     field="ux")])
    out, runner = _run_ocean(ocean_case, tmp_path, "corrupt", plan)
    skipped = metrics.default().snapshot()["counter"].get(
        "checkpoint.corrupt_skipped", 0)
    assert skipped >= 1                          # fell back past step 4
    assert tree_equal(out, ocean_baseline)
    metrics.reset()


def test_chaos_matrix_preemption_bitwise(ocean_case, ocean_baseline,
                                         tmp_path):
    plan = chaos.FaultPlan([chaos.Fault("runner.step", "preempt",
                                        step=N_STEPS - 2)])
    out1, runner1 = _run_ocean(ocean_case, tmp_path, "preempt", plan)
    assert runner1.stats["preempted"]
    saved = runner1.ckpt.latest_step()
    assert saved == N_STEPS - 2                  # blocking save on SIGTERM
    out, runner2 = _run_ocean(ocean_case, tmp_path, "preempt", plan=None,
                              resume=True)
    assert runner2.stats["steps"] == 2           # only the preempted tail
    assert tree_equal(out, ocean_baseline)


def test_chaos_matrix_save_thread_failure_bitwise(ocean_case, ocean_baseline,
                                                  tmp_path):
    plan = chaos.FaultPlan([chaos.Fault("checkpoint.write", "io_error",
                                        step=2)])
    out, runner = _run_ocean(ocean_case, tmp_path, "savefail", plan)
    assert runner.stats["ckpt_failures"] == 1
    assert runner.stats["retries"] == 0          # never lost sim progress
    assert tree_equal(out, ocean_baseline)


def test_cfl_blowup_recovers_via_dt_ladder(ocean_case, tmp_path):
    """Forced deterministic CFL blow-up (dt=80 on this mesh diverges in one
    step): blind restore-and-retry provably fails; the dt ladder halves dt,
    finishes the run, and reports the engagement through obs.metrics."""
    metrics.reset()
    blow = sim_campaign.build_case(nx=4, ny=3, nl=4, dt=80.0)
    policy = lambda: obs_diag.MonitorPolicy(cfl_max=1.0, on_violation="halt")
    cfg = lambda d: RunnerConfig(checkpoint_dir=str(tmp_path / d),
                                 checkpoint_every=2, max_retries=3,
                                 backoff_base_s=0.0)

    # the OLD behaviour (no ladder): restores the same state, re-runs the
    # same step, fails identically until retries are exhausted
    blind = SimulationRunner(_factory_for(blow), blow.cfg, cfg("blind"),
                             policy=policy(),
                             ladder=LadderConfig(max_rungs=0))
    with pytest.raises(obs_diag.MonitorHalt):
        blind.run(blow.state, 4, resume=False)
    assert blind.stats["retries"] == 4

    # the ladder: retry 2 drops to dt=40 (CFL ~0.34) and the run finishes
    ladder = LadderConfig(dt_factor=0.5, max_rungs=2, recover_steps=64)
    runner = SimulationRunner(_factory_for(blow), blow.cfg, cfg("ladder"),
                              policy=policy(), ladder=ladder)
    out = runner.run(blow.state, 4, resume=False)
    assert runner.stats["ladder_engagements"] >= 1
    assert runner.rung == 1
    assert runner.stats["steps"] == 4
    assert float(out.time) == pytest.approx(4 * 40.0)    # ran at dt/2
    snap = metrics.default().snapshot()["counter"]
    assert snap.get("sim.ladder.transitions{direction=down}", 0) >= 1
    d = obs_diag.to_dict(obs_diag.compute(blow.geom, blow.vg,
                                          blow.cfg.with_recovery(0.5), out))
    assert not d["nonfinite"] and d["cfl_2d"] < 1.0
    metrics.reset()


def test_with_recovery_scales_dt_and_viscosity():
    from repro.core import stepper
    cfg = stepper.OceanConfig(dt=60.0, m_2d=20, nu_v_bg=1e-4, kappa_v_bg=1e-5)
    r = cfg.with_recovery(dt_factor=0.5, visc_factor=10.0)
    assert r.dt == 30.0 and r.m_2d == 20        # dt_2d halves consistently
    assert r.nu_v_bg == pytest.approx(1e-3)
    assert r.kappa_v_bg == pytest.approx(1e-4)
    assert cfg.dt == 60.0                        # original untouched
