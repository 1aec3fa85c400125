"""The scopes that split the burst and name every index gather, and the
per-layer readers of them: in the compiled step of the tiny cell, on
hand-made traces, and on a slice of a recorded TPU v5e trace of the scoped
program (``bench/testdata/gbr_step_scoped.*``).

The slice is one step of a traced ``gbr-share64.wave-front`` run, cut as
``test_bench_trace.py`` says, with two differences: it spans the host's
``runner.step`` span of that step (the device step and the idle gap around
it), and it keeps the ``runner.*`` host spans, each with its stats."""
import gzip
import json

import pytest

from tiny_cell import REPO, TINY

from bench import harness, trace_reduce
from bench.trace_reduce import Context, Op, Trace

BURST = ("burst.edge_states", "burst.assembly")
GATHER = ("gather.neighbour", "gather.edge_local")
READERS = tuple(f"{s}.ms_per_step" for s in BURST + GATHER)


@pytest.fixture(scope="module")
def step_scopes():
    """HLO instruction -> op_name of the tiny cell's compiled step, float32
    as on the chip."""
    import jax
    from bench import reference, traffic
    from repro.launch import sim_campaign
    conf = json.loads(
        (REPO / "bench/configs/benchmark-share4.json").read_text())
    conf.update(TINY)
    mix = traffic.load("wave-front", REPO / "bench")
    with jax.enable_x64(False):
        x, y, b = reference.nodes(conf)
        case, _ = harness.program_case(
            conf, traffic.initial_fields(mix, conf, x, y, b, 11))
        hlo = sim_campaign.step_program.lower(
            case.geom, case.vg, case.cfg, case.state).compile().as_text()
    return trace_reduce.hlo_scopes(hlo)


def test_compiled_step_carries_the_scopes(step_scopes):
    parts = {c for path in step_scopes.values() for c in path.split("/")}
    for s in BURST + GATHER:
        assert s in parts, s


def test_every_burst_gather_is_named(step_scopes):
    gathers = [p for p in step_scopes.values()
               if "stage.external_burst" in p.split("/")
               and p.endswith("/gather")]
    assert gathers
    unnamed = [p for p in gathers
               if not set(GATHER) & set(p.split("/"))]
    assert unnamed == []


# ------------------------------------------------------------ hand-made
def _op(scope, ms):
    return Op(name="fusion.1", scope=scope, start_ns=0.0, dur_ns=ms * 1e6,
              device=0, kernel=False, end_ns=ms * 1e6)


def _read(metric, ops, steps=2):
    busy = sum(o.dur_ns for o in ops) * 1e-9
    trace = Trace(ops=ops, spans=[], window_s=max(busy, 1.0), busy_s=busy,
                  n_devices=1, gaps=[])
    ctx = Context(conf={}, steps=steps, wall_s=1.0, kind="TPU v5 lite",
                  peaks=lambda: {})
    value = harness.layer_reader(metric)(trace, ctx)
    assert ctx.notes == []
    return value


BODY = "jit(f)/imex.stage1/stage.external_burst/while/body/closed_call"
SCOPED = [_op(f"{BODY}/burst.edge_states/gather.neighbour/gather", 3.0),
          _op(f"{BODY}/burst.edge_states/gather.edge_local/gather", 1.0),
          _op(f"{BODY}/burst.edge_states/add", 0.5),
          _op(f"{BODY}/burst.assembly/mul", 2.0),
          _op("jit(f)/imex.stage1/stage.w_solve/gather.edge_local/gather",
              0.5),
          _op("jit(f)/imex.stage1/stage.edge_cache/gather.neighbour/gather",
              1.0),
          _op("jit(f)/obs.diagnostics/reduce", 4.0)]


@pytest.mark.parametrize("metric,want", [
    ("burst.edge_states.ms_per_step", 2.25),
    ("burst.assembly.ms_per_step", 1.0),
    ("gather.neighbour.ms_per_step", 2.0),
    ("gather.edge_local.ms_per_step", 0.75)])
def test_reader_value(metric, want):
    assert _read(metric, SCOPED) == pytest.approx(want)


@pytest.mark.parametrize("metric", READERS)
def test_reader_zero_where_the_layer_has_no_sub_scope(metric):
    # a program that predates the sub-scopes: the stage is there
    ops = [_op(f"{BODY}/gather", 3.0),
           _op("jit(f)/imex.stage1/stage.w_solve/gather", 1.0)]
    assert _read(metric, ops) == 0.0


@pytest.mark.parametrize("metric", READERS)
def test_reader_none_where_the_layer_is_absent(metric):
    # no op carries the enclosing layer: a CPU trace, or a renamed scope
    assert _read(metric, []) is None
    assert _read(metric, [_op("jit(f)/obs.diagnostics/reduce", 1.0),
                          _op("jit(f)/gather.neighbour/gather", 1.0)]) \
        is None


# ------------------------------------------------------------ recorded
DATA = REPO / "bench" / "testdata"


@pytest.fixture(scope="module")
def scoped_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("scoped_slice")
    prof = d / "plugins" / "profile" / "slice"
    prof.mkdir(parents=True)
    (prof / "slice.xplane.pb").write_bytes(gzip.decompress(
        (DATA / "gbr_step_scoped.xplane.pb.gz").read_bytes()))
    (d / trace_reduce.SCOPES_FILE).write_text(
        (DATA / "gbr_step_scoped.scopes.json").read_text())
    return d


def test_burst_split_on_the_scoped_slice(scoped_dir):
    trace = trace_reduce.reduce(scoped_dir)
    conf = json.loads((REPO / "bench/configs/gbr-share64.json").read_text())
    ctx = Context(conf=conf, steps=1, wall_s=trace.window_s,
                  kind="TPU v5 lite",
                  peaks=lambda: harness.peaks("TPU v5 lite"))
    got = {m: harness.layer_reader(m)(trace, ctx)
           for m in READERS + ("burst.ms_per_step",)}
    split = (got["burst.edge_states.ms_per_step"]
             + got["burst.assembly.ms_per_step"])
    assert split >= 0.95 * got["burst.ms_per_step"]
    gathers = (got["gather.neighbour.ms_per_step"],
               got["gather.edge_local.ms_per_step"])
    assert min(gathers) > 0
    assert sum(gathers) <= 1e3 * trace.busy_s


def test_runner_spans_on_the_scoped_slice(scoped_dir):
    """The runner's host spans reach the TPU trace, on the device's clock:
    the step's span carries its number, its children lie inside it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(
        str(scoped_dir / "plugins/profile/slice/slice.xplane.pb"))
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
              dict(ev.stats))
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("runner.")]
    steps = [s for s in spans if s[0] == "runner.step"]
    assert steps and all("step_num" in s[3] for s in steps)
    names = {s[0] for s in spans}
    assert {"runner.dispatch", "runner.wait", "runner.check"} <= names
    for name, a, b, _ in spans:
        assert any(s[1] <= a and b <= s[2] for s in steps), name
