"""The halo exchange's per-layer readers: on hand-made op lists with a known
overlap, and on a slice of a recorded four-chip TPU v5e trace
(``bench/testdata/bench4_step.*``).

The slice is one step of a traced ``benchmark-4chip.wave-front`` run, cut as
``test_bench_trace.py`` says for one chip, on each of the four
``/device:TPU:<n>`` planes: that step's event of the ``XLA Modules`` line
and the ``XLA Ops`` events that start in it; the ``bench.*`` host spans
clipped to the step."""
import gzip
import json

import pytest

from tiny_cell import REPO

from bench import harness, trace_reduce
from bench.trace_reduce import Context, Op, Trace

DATA = REPO / "bench" / "testdata"
EXPOSED = "halo.exposed_ms_per_step"


def _op(name, start_ms, ms, device=0, scope="", own_ms=None):
    """An op from ``start_ms`` lasting ``ms``; ``own_ms`` less where ops are
    nested in it."""
    return Op(name=name, scope=scope, start_ns=start_ms * 1e6,
              dur_ns=(ms if own_ms is None else own_ms) * 1e6, device=device,
              kernel=False, end_ns=(start_ms + ms) * 1e6)


def _read(metric, ops, n_devices=1, steps=1):
    trace = Trace(ops=ops, spans=[], window_s=1.0, busy_s=0.5,
                  n_devices=n_devices, gaps=[])
    ctx = Context(conf={}, steps=steps, wall_s=1.0, kind="TPU v5 lite",
                  peaks=lambda: {}, chips=n_devices)
    return harness.layer_reader(metric)(trace, ctx)


def test_exposed_is_the_collective_time_no_other_op_covers():
    # device 0: a 4 ms collective, 1 ms of it under a fusion; device 1: a
    # 2 ms collective alone, and an op after it
    ops = [_op("collective-permute.1", 0.0, 4.0),
           _op("fusion.3", 3.0, 2.0),
           _op("collective-permute.1", 0.0, 2.0, device=1),
           _op("fusion.3", 2.0, 1.0, device=1)]
    # (3 + 2) ms over 2 devices and 2 steps
    assert _read(EXPOSED, ops, n_devices=2, steps=2) == pytest.approx(1.25)


def test_a_loop_around_the_collective_does_not_hide_it():
    ops = [_op("while.7", 0.0, 10.0, own_ms=4.0),
           _op("collective-permute-start.2", 1.0, 0.5),
           _op("collective-permute-done.2", 4.0, 2.0),
           _op("fusion.9", 1.5, 2.5),
           _op("fusion.10", 5.0, 3.0)]
    # done runs 4-6 ms, fusion.10 covers 5-6: 0.5 + 1 ms exposed
    assert _read(EXPOSED, ops) == pytest.approx(1.5)


def test_halo_readers_read_nothing_without_an_exchange():
    ops = [_op("fusion.1", 0.0, 1.0, scope="jit(f)/stage.w_solve/add")]
    assert _read(EXPOSED, ops) is None
    assert _read("halo.ms_per_step", ops) is None


def test_halo_time_is_the_scope_averaged_over_chips():
    ops = [_op("gather.2", 0.0, 1.0,
               scope="jit(f)/stage.w_solve/halo.exchange/gather"),
           _op("collective-permute.1", 1.0, 3.0,
               scope="jit(f)/stage.w_solve/halo.exchange/ppermute"),
           _op("fusion.1", 4.0, 2.0, scope="jit(f)/stage.w_solve/add"),
           _op("gather.2", 0.0, 2.0, device=1,
               scope="jit(f)/stage.w_solve/halo.exchange/gather")]
    assert _read("halo.ms_per_step", ops, n_devices=2) == pytest.approx(3.0)


# ------------------------------------------------------------ recorded
@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("bench4_slice")
    prof = d / "plugins" / "profile" / "slice"
    prof.mkdir(parents=True)
    (prof / "slice.xplane.pb").write_bytes(gzip.decompress(
        (DATA / "bench4_step.xplane.pb.gz").read_bytes()))
    (d / trace_reduce.SCOPES_FILE).write_text(
        (DATA / "bench4_step.scopes.json").read_text())
    return trace_reduce.reduce(d)


def test_slice_is_one_step_on_four_chips(trace):
    assert trace.n_devices == 4
    assert {o.device for o in trace.ops} == {0, 1, 2, 3}
    assert 0 < trace.busy_s <= trace.window_s
    assert any(o.name.startswith("collective-permute") for o in trace.ops)


def test_cell_readers_on_the_four_chip_slice(trace):
    conf = json.loads(
        (REPO / "bench/configs/benchmark-4chip.json").read_text())
    ctx = Context(conf=conf, steps=1, wall_s=trace.window_s,
                  kind="TPU v5 lite",
                  peaks=lambda: harness.peaks("TPU v5 lite"), chips=4)
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    got = {m["name"]: harness.layer_reader(m["name"])(trace, ctx)
           for m in bm["per_layer"]
           if "benchmark-4chip.wave-front" in m["workloads"]}
    # what the recorded step holds; a metric listed later (a kernel's
    # roofline once kernels run under shard_map) may read nothing here
    held = ("device.idle_share", "burst.ms_per_step",
            "horizontal.ms_per_step", "column.ms_per_step", "step_mfu",
            "halo.ms_per_step", EXPOSED)
    assert all(got[k] is not None for k in held), got
    assert all(v is None or v >= 0 for v in got.values()), got
    halo, exposed = got["halo.ms_per_step"], got[EXPOSED]
    assert 0 < exposed and 0 < halo <= 1e3 * trace.busy_s
    # the exposed part is collective time, which the exchange's scope holds
    coll = 1e3 * trace.op_seconds(
        lambda o: o.name.startswith("collective-permute"))
    assert exposed <= coll * (1 + 1e-9)
    assert 0 < got["step_mfu"] <= 100
    stages = sum(got[k] for k in ("burst.ms_per_step",
                                  "horizontal.ms_per_step",
                                  "column.ms_per_step"))
    assert stages <= 1e3 * trace.busy_s


def test_slice_ops_keep_their_ends(trace):
    # an op's end takes in what is nested in it; its own time does not
    assert all(o.end_ns >= o.start_ns + o.dur_ns - 1e-6 for o in trace.ops)
    assert any(o.end_ns > o.start_ns + o.dur_ns + 1e3 for o in trace.ops)
