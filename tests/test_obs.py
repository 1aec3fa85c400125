"""Flight-recorder tests: metrics registry + schema, physics diagnostics
(conservation to roundoff, NaN localisation), monitor policy, the
fault-tolerance NaN path, and the bench artifact plumbing."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

from repro.core import dg2d, geometry, mesh2d, stepper
from repro.core.extrusion import VGrid
from repro.obs import diagnostics as obs_diag
from repro.obs import metrics, schema
from repro.runtime.fault_tolerance import RunnerConfig, TrainRunner

F64 = jnp.float64


def build(nx=6, ny=5, lx=2000.0, ly=1500.0, depth=20.0, nl=4):
    m = mesh2d.rect_mesh(nx, ny, lx, ly, jitter=0.2, seed=3)
    geom = geometry.geom2d_from_mesh(m, dtype=F64)
    vg = VGrid(b=jnp.full((3, m.nt), depth, F64), nl=nl)
    return m, geom, vg


def standing_wave_state(geom, vg, lx=2000.0, amp=0.05):
    st = stepper.init_state(geom, vg, dtype=F64)
    eta = (amp * jnp.cos(jnp.pi * geom.node_x / lx)).astype(F64)
    return dataclasses.replace(
        st, ext=dg2d.State2D(eta, st.ext.qx, st.ext.qy))


# ---------------------------------------------------------------------------
# metrics registry + schema
# ---------------------------------------------------------------------------
def test_registry_roundtrip_jsonl(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    reg = metrics.Registry(sink=metrics.JsonlSink(path))
    reg.counter("kernel_dispatch", op="solve_r", backend="ref").inc(3)
    reg.gauge("runner.step_time_ema_s").set(0.125)
    h = reg.histogram("stage_time_us", stage="imex.stage1")
    for v in (10.0, 20.0, 30.0):
        h.observe(v)
    reg.event("monitor.violation", {"rule": "cfl_2d", "value": 1.5}, step=2)
    reg.diagnostics("physics", {"volume": 1.0, "nonfinite": False,
                                "eta_max": float("nan")}, step=2)
    reg.flush(step=3)
    reg.close()

    n_ok, errors = schema.validate_file(path)
    assert errors == [], errors
    assert n_ok == 5  # event + diagnostics + counter + gauge + histogram
    recs = [json.loads(l) for l in open(path)]
    diag = next(r for r in recs if r["kind"] == "diagnostics")
    assert diag["value"]["eta_max"] is None  # NaN sanitised to null
    hist = next(r for r in recs if r["kind"] == "histogram")
    assert hist["value"]["p50"] == 20.0 and hist["value"]["count"] == 3
    snap = reg.snapshot()
    assert snap["counter"][
        "kernel_dispatch{backend=ref,op=solve_r}"] == 3.0


def test_schema_rejects_malformed():
    with pytest.raises(schema.SchemaError):
        schema.validate_record({"ts": 0.0, "kind": "bogus", "name": "x"})
    with pytest.raises(schema.SchemaError):
        schema.validate_record({"ts": 0.0, "kind": "counter", "name": "x",
                                "value": -1})
    with pytest.raises(schema.SchemaError):
        schema.validate_record({"kind": "gauge", "name": "x", "value": 1})
    # strict JSON: bare NaN literals are schema violations, not valid JSON
    n_ok, errors = schema.validate_lines(
        ['{"ts": 1.0, "kind": "gauge", "name": "g", "value": NaN}'])
    assert n_ok == 0 and len(errors) == 1


def test_dispatch_counter_counts_traces():
    metrics.reset()
    from repro.kernels import ops
    a = jnp.ones((4, 128))
    ops.tridiag(a, 4.0 * a, a, a)
    snap = metrics.default().snapshot()["counter"]
    keys = [k for k in snap if k.startswith("kernel_dispatch")]
    assert len(keys) == 1 and "op=tridiag" in keys[0]
    assert snap[keys[0]] >= 1.0
    metrics.reset()


# ---------------------------------------------------------------------------
# benchmarks/common.time_fn
# ---------------------------------------------------------------------------
def test_time_fn_blocks_pytrees_and_reports_percentiles():
    from benchmarks.common import Timing, time_fn

    def fn(x):
        return {"a": x * 2, "b": [x + 1, None, "label"], "t": (x,)}

    t = time_fn(fn, jnp.arange(8.0), warmup=1, iters=5)
    assert isinstance(t, float) and isinstance(t, Timing)
    assert t.min <= t.p50 <= t.p90 and t.n == 5
    assert t * 1e6 > 0.0  # float arithmetic still works
    stats = t.stats()
    assert set(stats) == {"p50", "p90", "min", "mean", "n"}


# ---------------------------------------------------------------------------
# physics diagnostics
# ---------------------------------------------------------------------------
def test_conservation_standing_wave_20_steps():
    """Volume and tracer mass conserved to f64 roundoff over 20 steps."""
    _, geom, vg = build()
    cfg = stepper.OceanConfig(dt=5.0, nl=4, m_2d=6)
    st = standing_wave_state(geom, vg)
    fn = jax.jit(lambda s: obs_diag.step_with_diagnostics(geom, vg, cfg, s))
    st, diag = fn(st)
    d0 = obs_diag.to_dict(diag)
    for _ in range(19):
        st, diag = fn(st)
    d = obs_diag.to_dict(diag)
    assert abs(d["volume"] - d0["volume"]) / d0["volume"] < 1e-12
    assert abs(d["mass_T"] - d0["mass_T"]) / d0["mass_T"] < 1e-12
    assert abs(d["mass_S"] - d0["mass_S"]) / d0["mass_S"] < 1e-12
    assert not d["nonfinite"] and d["bad_cell"] == -1
    assert 0.0 < d["cfl_2d"] < 1.0
    assert 0.0 < d["eta_max"] <= 0.06  # wave oscillates within initial amp


def test_nan_localizer_pinpoints_injected_cell():
    _, geom, vg = build()
    cfg = stepper.OceanConfig(dt=5.0, nl=4, m_2d=6)
    st = standing_wave_state(geom, vg)
    bad_cell = 7
    st = dataclasses.replace(
        st, T=st.T.at[2, 4, bad_cell].set(jnp.nan))
    diag = jax.jit(lambda s: obs_diag.compute(geom, vg, cfg, s))(st)
    d = obs_diag.to_dict(diag)
    assert d["nonfinite"]
    assert d["bad_field_name"] == "T"
    assert d["bad_cell"] == bad_cell
    # priority order: a bad eta in a later cell wins over the bad T
    st2 = dataclasses.replace(
        st, ext=dg2d.State2D(st.ext.eta.at[0, 11].set(jnp.inf),
                             st.ext.qx, st.ext.qy))
    d2 = obs_diag.to_dict(obs_diag.compute(geom, vg, cfg, st2))
    assert d2["bad_field_name"] == "eta" and d2["bad_cell"] == 11


def test_monitor_policy_warn_and_halt(tmp_path):
    _, geom, vg = build()
    cfg = stepper.OceanConfig(dt=5.0, nl=4, m_2d=6)
    st = standing_wave_state(geom, vg)
    diag = obs_diag.compute(geom, vg, cfg, st)

    ok = obs_diag.MonitorPolicy(cfl_max=1.0, on_violation="halt")
    assert ok.check(diag) == []

    path = str(tmp_path / "m.jsonl")
    reg = metrics.Registry(sink=metrics.JsonlSink(path))
    warn = obs_diag.MonitorPolicy(cfl_max=1e-6, eta_max=1e-3,
                                  on_violation="warn")
    with pytest.warns(RuntimeWarning, match="cfl_2d"):
        v = warn.check(diag, step=0, registry=reg)
    assert {x["rule"] for x in v} == {"cfl_2d", "eta_max"}
    reg.close()
    n_ok, errors = schema.validate_file(path)
    assert errors == [] and n_ok == 3  # 1 diagnostics + 2 violation events

    halt = obs_diag.MonitorPolicy(cfl_max=1e-6, on_violation="halt")
    with pytest.raises(obs_diag.MonitorHalt) as ei:
        halt.check(diag)
    assert ei.value.violations[0]["rule"] == "cfl_2d"

    # tracer bounds + drift vs first-check reference
    drift = obs_diag.MonitorPolicy(
        cfl_max=None, tracer_bounds={"T": (9.9, 10.1)},
        volume_drift_max=1e-12, on_violation="silent")
    assert drift.check(diag) == []          # captures reference
    bigger = dataclasses.replace(diag, volume=diag.volume * 1.01,
                                 T_max=jnp.asarray(11.0))
    v = drift.check(bigger)
    assert {x["rule"] for x in v} == {"T_max", "volume_drift"}


# ---------------------------------------------------------------------------
# fault tolerance: NaN diagnostics -> restore-and-retry
# ---------------------------------------------------------------------------
class _CountingDataset:
    def batch_at(self, step):
        return {"x": jnp.asarray(float(step))}


def test_runner_retries_on_nonfinite_diagnostics(tmp_path):
    failed = {"done": False}

    def step_fn(state, batch):
        s = int(state["step"])
        new = {"step": state["step"] + 1,
               "acc": state["acc"] + batch["x"]}
        diag = {"nonfinite": False, "bad_field_name": None, "bad_cell": -1}
        if s == 5 and not failed["done"]:
            failed["done"] = True   # fail exactly once, first time at step 5
            diag = {"nonfinite": True, "bad_field_name": "T", "bad_cell": 7}
        return new, {"loss": 1.0, "diagnostics": diag}

    cfg = RunnerConfig(checkpoint_dir=str(tmp_path), checkpoint_every=2,
                       max_retries=2, emit_metrics=False)
    runner = TrainRunner(step_fn, _CountingDataset(), cfg)
    state = {"step": jnp.asarray(0), "acc": jnp.asarray(0.0)}
    out = runner.run(state, n_steps=8, resume=False)
    assert int(out["step"]) == 8
    assert runner.stats["retries"] == 1
    # restored to the step-4 checkpoint and re-ran deterministically
    assert float(out["acc"]) == sum(range(8))


def test_runner_diag_failure_exhausts_retries(tmp_path):
    def step_fn(state, batch):
        return state, {"loss": 1.0,
                       "diagnostics": {"nonfinite": True,
                                       "bad_field_name": "eta",
                                       "bad_cell": 0}}

    cfg = RunnerConfig(checkpoint_dir=str(tmp_path), checkpoint_every=100,
                       max_retries=1, emit_metrics=False)
    runner = TrainRunner(step_fn, _CountingDataset(), cfg)
    with pytest.raises(FloatingPointError, match="field=eta"):
        runner.run({"step": jnp.asarray(0)}, n_steps=3, resume=False)


def test_runner_accepts_diagnostics_pytree(tmp_path):
    """The duck-typed check also reads the Diagnostics dataclass itself."""
    _, geom, vg = build(nx=4, ny=3)
    cfg3 = stepper.OceanConfig(dt=5.0, nl=4, m_2d=6)
    st = standing_wave_state(geom, vg)
    bad = dataclasses.replace(st, T=st.T.at[0, 0, 3].set(jnp.nan))
    diag = obs_diag.compute(geom, vg, cfg3, bad)

    def step_fn(state, batch):
        return state, {"loss": 1.0, "diagnostics": diag}

    cfg = RunnerConfig(checkpoint_dir=str(tmp_path), max_retries=0,
                       emit_metrics=False)
    runner = TrainRunner(step_fn, _CountingDataset(), cfg)
    with pytest.raises(FloatingPointError, match="field=T"):
        runner.run({"s": jnp.asarray(0)}, n_steps=1, resume=False)


# ---------------------------------------------------------------------------
# SimulationRunner: host spans in a profiler trace, one host copy per step
# ---------------------------------------------------------------------------
class _SpyPolicy:
    def __init__(self):
        self.seen = []

    def check(self, diag, step=None, registry=None):
        self.seen.append(diag)
        return []


def _runner_spans(trace_dir):
    """(start_ns, duration_ns, name, stats) of the ``runner.*`` host spans
    of the one profile under ``trace_dir``, in start order."""
    from jax.profiler import ProfileData
    [path] = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return sorted((ev.start_ns, ev.duration_ns, ev.name, dict(ev.stats))
                  for plane in pd.planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("runner."))


def test_runner_spans_in_profiler_trace(tmp_path):
    from repro.launch import sim_campaign
    from repro.runtime.fault_tolerance import SimulationRunner
    case = sim_campaign.build_case(nx=4, ny=3)
    policy = _SpyPolicy()
    runner = SimulationRunner(
        sim_campaign.make_step_factory(case), case.cfg,
        RunnerConfig(checkpoint_dir=str(tmp_path / "ckpt"),
                     checkpoint_every=100, emit_metrics=False),
        policy=policy)
    # compiled before the trace: the profiler slows XLA's compile severalfold
    jax.block_until_ready(sim_campaign.step_program(
        case.geom, case.vg, case.cfg, case.state))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        runner.run(case.state, 2, resume=False)
    finally:
        jax.profiler.stop_trace()
    spans = _runner_spans(tmp_path / "trace")
    steps = [s for s in spans if s[2] == "runner.step"]
    assert [s[3].get("step_num") for s in steps] == [0, 1]
    for k, (t0, dur, _, _) in enumerate(steps):
        inside = [name for t, d, name, _ in spans
                  if name != "runner.step" and t0 <= t and t + d <= t0 + dur]
        # the step is built once, inside the first step's dispatch
        assert inside == (
            ["runner.dispatch", "runner.build_step", "runner.wait",
             "runner.check"] if k == 0 else
            ["runner.dispatch", "runner.wait", "runner.check"]), inside
    # the policy reads the host copy the runner made, not the device bundle
    assert len(policy.seen) == 2
    assert all(isinstance(d, dict) for d in policy.seen)
    assert runner.stats["step_time_ema"] > 0


def test_sim_runner_names_the_nonfinite_field_and_cell(tmp_path):
    from repro.runtime.fault_tolerance import SimulationRunner
    _, geom, vg = build(nx=4, ny=3)
    cfg3 = stepper.OceanConfig(dt=5.0, nl=4, m_2d=6)
    st = standing_wave_state(geom, vg)
    bad = dataclasses.replace(st, T=st.T.at[0, 0, 3].set(jnp.nan))
    diag = obs_diag.compute(geom, vg, cfg3, bad)
    runner = SimulationRunner(
        lambda c: (lambda s: (s, diag)), cfg3,
        RunnerConfig(checkpoint_dir=str(tmp_path), max_retries=0,
                     emit_metrics=False))
    with pytest.raises(FloatingPointError, match="field=T, cell=3"):
        runner.run(st, 1, resume=False)


# ---------------------------------------------------------------------------
# obs_report: bench diff
# ---------------------------------------------------------------------------
def test_obs_report_diff_flags_regression(tmp_path, capsys):
    from benchmarks import obs_report

    old = [dict(name="fused", nl=4, nt=96, us_per_call=100.0),
           dict(name="ref", nl=4, nt=96, us_per_call=200.0),
           dict(kind="breakdown", path="fused", component="continuity",
                nl=16, nt=864, us_per_call=50.0)]
    new = [dict(name="fused", nl=4, nt=96, us_per_call=150.0),   # 1.5x slower
           dict(name="ref", nl=4, nt=96, us_per_call=190.0),
           dict(kind="breakdown", path="fused", component="continuity",
                nl=16, nt=864, us_per_call=49.0)]
    po, pn = tmp_path / "old.json", tmp_path / "new.json"
    po.write_text(json.dumps(old))
    pn.write_text(json.dumps(new))

    rows = obs_report.diff_records(old, new)
    assert len(rows) == 3
    fused = next(r for r in rows if r["key"].startswith("fused"))
    assert fused["ratio"] == pytest.approx(1.5)

    assert obs_report.diff(str(po), str(pn), threshold=0.10, fail=True) == 1
    assert obs_report.diff(str(po), str(pn), threshold=0.60, fail=True) == 0
    capsys.readouterr()
