"""The reduction from a profiler trace to per-layer readings, on a slice of
a recorded TPU v5e trace and on hand-made events.

The slice (``bench/testdata/gbr_step.*``) is one device step of a traced
``gbr-share64.wave-front`` run: of its ``.xplane.pb``, the device plane's op
events and the benchmark's host spans that start inside that step (one event
of the ``XLA Modules`` line), the spans clipped to the step, each event's
metadata named by its HLO instruction alone (``tpu_custom_call`` where it is
one), written with the subset of the XSpace schema the slice needs and the
part of ``scopes.json`` it uses."""
import dataclasses
import gzip
import json
import math

import pytest

from tiny_cell import REPO

from bench import harness, trace_reduce, work

DATA = REPO / "bench" / "testdata"
STAGES = ("stage.external_burst", "stage.edge_cache",
          "stage.pressure_gradient", "stage.flux_prediction",
          "stage.horizontal_rhs", "stage.turbulence", "stage.w_solve",
          "stage.momentum_update", "stage.tracer_update",
          "stage.turbulence_final")


@pytest.fixture(scope="module")
def slice_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("slice")
    prof = d / "plugins" / "profile" / "slice"
    prof.mkdir(parents=True)
    with gzip.open(DATA / "gbr_step.xplane.pb.gz") as f:
        (prof / "slice.xplane.pb").write_bytes(f.read())
    (d / trace_reduce.SCOPES_FILE).write_text(
        (DATA / "gbr_step.scopes.json").read_text())
    return d


@pytest.fixture(scope="module")
def trace(slice_dir):
    return trace_reduce.reduce(slice_dir)


def _ctx(trace):
    conf = json.loads((REPO / "bench/configs/gbr-share64.json").read_text())
    return trace_reduce.Context(conf=conf, steps=1, wall_s=trace.window_s,
                                kind="TPU v5 lite",
                                peaks=lambda: harness.peaks("TPU v5 lite"))


def test_slice_is_one_device_step(trace):
    assert trace.n_devices == 1
    assert 0.5 < trace.window_s < 2.0
    assert 0 < trace.busy_s <= trace.window_s
    assert len(trace.ops) > 10_000
    # own times of nested ops add up to the busy time, nothing counted twice
    own = sum(o.dur_ns for o in trace.ops) * 1e-9
    assert math.isclose(own, trace.busy_s, rel_tol=1e-6)


def test_scopes_reach_the_ops(trace):
    scoped = trace.op_seconds(lambda o: o.scope != "")
    assert scoped / trace.busy_s > 0.95
    for s in STAGES:
        assert trace.under([s]) > 0, s


def test_kernel_events_match_the_counted_calls(trace):
    calls = work.calls(_ctx(trace).conf)
    for name, (_, scope) in work.KERNELS.items():
        evs = [o for o in trace.ops
               if o.kernel and scope in o.scope.split("/")]
        assert len(evs) == len(calls[name]), name


def test_readers_on_the_slice(trace):
    ctx = _ctx(trace)
    got = {m["name"]: harness.layer_reader(m["name"])(trace, ctx)
           for m in json.loads(
               (REPO / "BENCHMARK.json").read_text())["per_layer"]
           if "gbr-share64.wave-front" in m["workloads"]}
    assert all(v is not None for v in got.values()), got
    assert 0 <= got["device.idle_share"] < 100
    assert 0 < got["kernels_roofline"] <= 100
    assert 0 < got["step_mfu"] <= 100
    stages = sum(got[k] for k in ("burst.ms_per_step",
                                  "horizontal.ms_per_step",
                                  "column.ms_per_step"))
    assert stages <= 1e3 * trace.busy_s
    assert len(ctx.notes) == 5          # four kernels and the step
    bd = trace.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][1] >= bd["device_ops"][-1][1]


def test_self_times_take_nested_ops_out():
    evs = [("while", 0.0, 10.0), ("a", 2.0, 3.0), ("b", 6.0, 2.0),
           ("c", 12.0, 1.0)]
    own = {e[0]: t for e, t in trace_reduce._self_times(evs)}
    assert own == {"while": 5.0, "a": 3.0, "b": 2.0, "c": 1.0}


def test_union_and_names():
    assert trace_reduce._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace_reduce.op_name("%fusion.12 = f32[3] fusion(%x)") == \
        "fusion.12"
    hlo = ('  %fusion.12 = f32[3]{0} fusion(%p), kind=kLoop, '
           'metadata={op_name="jit(f)/imex.stage1/stage.w_solve/add"}\n'
           '  ROOT %tuple.3 = (f32[3]{0}) tuple(%fusion.12)\n')
    assert trace_reduce.hlo_scopes(hlo) == {
        "fusion.12": "jit(f)/imex.stage1/stage.w_solve/add"}


def test_step_mfu_counts_one_chips_share(trace):
    """On one chip the reading is the whole step's least time over the busy
    time per step, as before the reader knew chips; on four, where the busy
    time is one chip's, it counts a quarter of the whole mesh's work."""
    ctx = _ctx(trace)
    read = harness.layer_reader("step_mfu")
    one = read(trace, ctx)
    lt, _ = work.least_seconds(*work.step(ctx.conf), ctx.peaks())
    assert one == 100.0 * lt / (trace.busy_s / ctx.steps)
    four = dataclasses.replace(ctx, chips=4, notes=[])
    assert read(trace, four) == pytest.approx(one / 4, rel=1e-15)
