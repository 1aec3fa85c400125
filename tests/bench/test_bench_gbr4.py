"""The GBR deployment on one four-chip host: its configuration is the 2x2
block of ``gbr-share64``'s 8x8 squares of the GBR-scale mesh, at the same
element size and physics, and the reader of the column kernels' device time
under ``shard_map`` (``kernels.ms_per_step``) reads the compiled kernels'
events, on hand-made traces and on slices of recorded TPU v5e traces
(``bench/testdata/``: a four-chip step with the column solves on ``ref``,
and a one-chip step of the kernel path)."""
import gzip
import json
import math

import pytest

from tiny_cell import REPO

from bench import harness, trace_reduce
from bench.trace_reduce import Context, Op, Trace

BM = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "gbr-4chip.wave-front"
METRIC = "kernels.ms_per_step"
DATA = REPO / "bench" / "testdata"


def _conf(name):
    return json.loads((REPO / "bench/configs" / f"{name}.json").read_text())


def test_gbr4_is_a_two_by_two_block_of_gbr_share64():
    from repro.launch.ocean_dryrun import OCEAN_CELLS
    gbr = OCEAN_CELLS["gbr"]
    conf, share = _conf("gbr-4chip"), _conf("gbr-share64")
    assert conf["whole"] == share["whole"] == {
        "nx": gbr.nx, "ny": gbr.ny, "lx": gbr.lx, "ly": gbr.ly,
        "triangles": 2 * gbr.nx * gbr.ny}
    # two of gbr-share64's squares a side: 2 x 2 x 51,520 triangles, the
    # same element size, and as many owned triangles a chip as one square
    assert (conf["nx"], conf["ny"]) == (2 * share["nx"], 2 * share["ny"])
    assert conf["lx"] == 2 * share["lx"]
    assert math.isclose(conf["ly"], 2 * share["ly"], rel_tol=1e-15)
    assert conf["triangles"] == 2 * conf["nx"] * conf["ny"] == 206_080
    assert conf["triangles"] // 4 == share["triangles"]
    for k in harness.PHYSICS + ("dtype", "guarantees", "jitter", "mesh_seed",
                                "depth", "shallow_frac", "reef", "forcing",
                                "reduced", "assumed"):
        assert conf[k] == share[k], k
    assert (conf["nl"], conf["dt"], conf["m_2d"], conf["depth"],
            conf["reef"]) == (gbr.nl, gbr.dt, gbr.m_2d, gbr.depth, gbr.reef)


def test_gbr4_cell_is_on_four_chips_with_its_files():
    cell = harness.load_cell(CELL)
    assert cell.workload["chips"] == 4
    assert cell.workload["traffic"] == "wave-front"
    entry = {c["name"]: c for c in BM["configs"]}["gbr-4chip"]
    assert entry["file"] == "bench/configs/gbr-4chip.json"
    assert entry["reduced"] == cell.conf["reduced"]
    assert set(cell.limits["numbers"]) == {
        "eta", "uy_mean", "drift_volume", "drift_mass_T", "drift_mass_S"}
    assert METRIC in {m["name"] for m in cell.per_layer}


def test_kernel_metrics_list_the_cells_they_can_read():
    # the kernels under shard_map exist only where a cell takes several
    # chips; which of those cells the metric lists is the benchmark's data
    per = {m["name"]: m for m in BM["per_layer"]}
    chips = {w["name"]: w["chips"] for w in BM["workloads"]}
    listed = per[METRIC]["workloads"]
    assert listed and all(chips[w] > 1 for w in listed)


def _op(scope, ms, kernel=True, device=0):
    return Op(name="custom-call.1" if kernel else "fusion.1", scope=scope,
              start_ns=0.0, dur_ns=ms * 1e6, device=device, kernel=kernel,
              end_ns=ms * 1e6)


def _read(ops, n_devices=1, steps=1):
    trace = Trace(ops=ops, spans=[], window_s=1.0, busy_s=0.5,
                  n_devices=n_devices, gaps=[])
    ctx = Context(conf={}, steps=steps, wall_s=1.0, kind="TPU v5 lite",
                  peaks=lambda: {}, chips=n_devices)
    return harness.layer_reader(METRIC)(trace, ctx)


def test_reader_counts_compiled_kernel_events_per_step_and_chip():
    local = "jit(f)/distributed.local_step/stage.w_solve"
    ops = [_op(f"{local}/kops.block_thomas.pallas/pallas_call", 3.0),
           _op(f"{local}/kops.solve_w.pallas/pallas_call", 1.0, device=1),
           # the padding around a kernel is no kernel event
           _op(f"{local}/kops.block_thomas.pallas/pad", 5.0, kernel=False),
           # the ref path's ops under its scope are not kernels
           _op(f"{local}/kops.block_thomas.ref/lu", 7.0, kernel=False),
           _op("jit(f)/stage.w_solve/add", 2.0, kernel=False)]
    assert _read(ops, n_devices=2, steps=2) == pytest.approx(1.0)
    assert _read(ops[2:]) is None
    assert _read([]) is None


def _slice(tmp_path_factory, name):
    d = tmp_path_factory.mktemp(name)
    prof = d / "plugins" / "profile" / "slice"
    prof.mkdir(parents=True)
    (prof / "slice.xplane.pb").write_bytes(gzip.decompress(
        (DATA / f"{name}.xplane.pb.gz").read_bytes()))
    (d / trace_reduce.SCOPES_FILE).write_text(
        (DATA / f"{name}.scopes.json").read_text())
    return trace_reduce.reduce(d)


def _recorded(trace, conf, chips):
    ctx = Context(conf=_conf(conf), steps=1, wall_s=trace.window_s,
                  kind="TPU v5 lite",
                  peaks=lambda: harness.peaks("TPU v5 lite"), chips=chips)
    return harness.layer_reader(METRIC)(trace, ctx)


def test_reader_on_recorded_steps(tmp_path_factory):
    # four chips with the column solves on ref: no kernel ran
    ref4 = _slice(tmp_path_factory, "bench4_step")
    assert _recorded(ref4, "benchmark-4chip", 4) is None
    # one chip's kernel path: the four kernels' events, under a step's time
    kern = _slice(tmp_path_factory, "gbr_step_scoped")
    got = _recorded(kern, "gbr-share64", 1)
    assert got is not None and 0 < got < 1e3 * kern.busy_s
    assert got == pytest.approx(1e3 * kern.op_seconds(lambda o: o.kernel))
