"""The benchmark's files: BENCHMARK.json's shape, the configuration files'
sizes, the operation and byte counts, the peaks table, the device check and
the lookup of cells, mixes and metrics by name."""
import json
import math
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from tiny_cell import REPO, TINY, TINY_CELL, add_cell

from bench import harness, traffic, work

BM = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_keys_and_names():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench", "tests/bench"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    cells = {w["name"] for w in BM["workloads"]}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert (REPO / "bench/layers" / f"{m['name']}.py").is_file()
    for w in BM["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (REPO / "bench/traffic" / f"{w['traffic']}.json").is_file()
        assert (REPO / "bench/limits" / f"{w['name']}.json").is_file()


@pytest.mark.parametrize("entry", BM["configs"], ids=lambda c: c["name"])
def test_config_sizes_and_reduced(entry):
    conf = json.loads((REPO / entry["file"]).read_text())
    assert conf["name"] == entry["name"]
    assert conf["reduced"] == entry["reduced"]
    assert conf["triangles"] == 2 * conf["nx"] * conf["ny"]
    whole = conf["whole"]
    assert whole["triangles"] == 2 * whole["nx"] * whole["ny"]
    # one chip's share at the whole mesh's element size
    assert math.isclose(conf["lx"] / conf["nx"], whole["lx"] / whole["nx"])
    assert math.isclose(conf["ly"] / conf["ny"], whole["ly"] / whole["ny"])
    for k in conf["reduced"]:
        assert k in conf
    for k in harness.PHYSICS:
        assert k in conf


def test_config_triangle_counts():
    c = {e["name"]: json.loads((REPO / e["file"]).read_text())
         for e in BM["configs"]}
    assert c["benchmark-share4"]["triangles"] == 52_480
    assert c["benchmark-share4"]["whole"]["triangles"] == 4 * 52_480
    assert c["gbr-share64"]["triangles"] == 51_520        # 2 rows short
    assert c["gbr-share64"]["whole"]["triangles"] == 3_302_400
    assert c["benchmark-4chip"]["triangles"] == 209_920


def test_four_chip_config_is_the_whole_benchmark_mesh():
    from repro.launch.ocean_dryrun import OCEAN_CELLS
    cell = OCEAN_CELLS["benchmark"]
    conf = json.loads(
        (REPO / "bench/configs/benchmark-4chip.json").read_text())
    whole = {"nx": cell.nx, "ny": cell.ny, "lx": cell.lx, "ly": cell.ly,
             "triangles": 2 * cell.nx * cell.ny}
    assert conf["whole"] == whole
    assert {k: conf[k] for k in whole} == whole
    assert (conf["nl"], conf["m_2d"], conf["dt"], conf["depth"]) == (
        cell.nl, cell.m_2d, cell.dt, cell.depth)
    assert conf["reduced"] == []
    # the one-chip share's physics, precision and guarantees
    share = json.loads(
        (REPO / "bench/configs/benchmark-share4.json").read_text())
    for k in harness.PHYSICS + ("dtype", "guarantees", "jitter",
                                "mesh_seed", "shallow_frac", "reef"):
        assert conf[k] == share[k], k


def test_at_most_half_the_cells_take_four_chips():
    chips = [w["chips"] for w in BM["workloads"]]
    assert chips.count(4) <= max(1, len(chips) // 2)
    multi = {w["name"] for w in BM["workloads"] if w["chips"] > 1}
    for name in multi:
        assert (REPO / "bench/limits" / f"{name}.json").is_file(), name
    # the halo's metrics read an exchange between chips: cells on one chip
    # have none
    for m in BM["per_layer"]:
        if m["name"].startswith("halo."):
            assert set(m["workloads"]) <= multi, m["name"]


def test_work_hand_counts():
    # one layer, one right-hand side, one column: 432 + 36 + 588 + 144
    assert work.block_thomas(1, 1, 1) == (1200, 4 * (108 + 12))
    # two layers: a backward step of 78k more
    assert work.block_thomas(2, 2, 3)[0] == 3 * (2 * (1056 + 288) + 156)
    assert work.column_sweep(2, 3) == (3 * 61, 4 * 3 * 28)
    assert work.lateral_flux(1, 1) == (300, 4 * 42)
    tiny = {"triangles": 10, "nl": 1, "m_2d": 2}
    per = work.kernel_step(tiny)
    assert per["block_thomas"] == (2 * 10 * 1344, 2 * 4 * 10 * 132, 2)
    assert per["solve_r"][2] == 2 and per["solve_w"][2] == 2
    assert per["lateral_flux"] == (2 * 300 * 60, 2 * 4 * 42 * 60, 4)
    flops, nbytes = work.step(tiny)
    assert flops == sum(f for f, _, _ in per.values())
    # 5 passes over 4*6 + 4 values per column, 3*(1+2) burst evaluations
    # of 18 + 3 values per triangle
    assert nbytes == 4 * (5 * 28 * 10) + 4 * 9 * 21 * 10


def test_least_seconds_bound():
    pk = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(1000, 10, pk) == (10.0, "compute")
    assert work.least_seconds(10, 1000, pk) == (100.0, "bytes")


def test_peaks_known_device_with_sources_and_unknown_is_an_error():
    pk = harness.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert pk["ici_bits_per_s"] == 1600e9
    assert all(pk[k] for k in ("flops_source", "hbm_source", "ici_source"))
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks("TPU v99")


def test_harness_refuses_a_cpu():
    with pytest.raises(harness.NoChip, match="no TPU"):
        harness.devices(1)


def test_command_exits_nonzero_without_a_tpu_and_prints_no_result(tmp_path):
    cmd = [sys.executable, "bench/run.py", "--workload",
           BM["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
           "--trace", "0"]
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(tmp_path), "TMPDIR": str(tmp_path),
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    r = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0 and "no TPU" in r.stderr
    assert not r.stdout.strip().startswith("{")
    # a checkout of the benchmark's own files alone has no program to run
    alone = tmp_path / "alone"
    for p in BM["paths"]:
        shutil.copytree(REPO / p, alone / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", alone)
    r = subprocess.run(cmd, cwd=alone, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0 and "{" not in r.stdout


def test_harness_finds_new_files_by_name(tiny_repo, tmp_path):
    root = tmp_path / "copy"
    shutil.copytree(tiny_repo, root)
    mix = traffic.load("wave-front", root / "bench")
    mix["amp_m"] = [0.01, 0.02]
    (root / "bench/traffic/calm.json").write_text(json.dumps(mix))
    (root / "bench/layers/probe.ops.py").write_text(
        "def read(trace, ctx):\n    return float(len(trace.ops))\n")
    conf = json.loads((root / "bench/configs/tiny.json").read_text())
    conf["name"] = "tiny2"
    name = add_cell(root, conf, "calm", {"numbers": {}})
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["per_layer"].append({"name": "probe.ops", "unit": "1",
                            "better": "higher", "source": "device_trace",
                            "layer": "test", "moves": "sim_per_wall",
                            "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = harness.load_cell(name, root)
    assert cell.conf["name"] == "tiny2" and cell.mix["amp_m"] == [0.01, 0.02]
    assert [m["name"] for m in cell.per_layer][-1] == "probe.ops"
    read = harness.layer_reader("probe.ops", root / "bench")
    assert read(type("T", (), {"ops": [1, 2]})(), None) == 2.0
    with pytest.raises(SystemExit, match="no workload"):
        harness.load_cell("nope.calm", root)


def test_traffic_draw_follows_the_seed_not_the_sizes():
    mix = traffic.load("wave-front")
    conf = {"lx": 1000.0, "ly": 800.0, "nl": 4}
    x = np.array([[0.0, 500.0, 1000.0]] * 3)
    y = np.array([[0.0, 400.0, 800.0]] * 3)
    b = np.full((3, 3), 20.0)
    a = traffic.initial_fields(mix, conf, x, y, b, 2 ** 40 + 1)
    again = traffic.initial_fields(mix, conf, x, y, b, 2 ** 40 + 1)
    c = traffic.initial_fields(mix, conf, x, y, b, -5)
    assert a["draw"] == again["draw"] and a["draw"] != c["draw"]
    for k in ("eta", "qx", "qy"):
        assert a[k].shape == (3, 3)
    for k in ("ux", "uy", "T", "S"):
        assert a[k].shape == (4, 6, 3)
    for k, (lo, hi) in ((k, mix[k]) for k in a["draw"]):
        assert lo <= a["draw"][k] <= hi, k
    # the transport is the depth integral of the velocity: the jet's depth
    # mean is nought, the flow across the front depth-uniform
    np.testing.assert_allclose(a["uy"].mean(axis=(0, 1)), 0.0, atol=1e-15)
    np.testing.assert_allclose(a["qx"], (a["eta"] + b) * a["ux"][0, :3])
    assert TINY_CELL and TINY["triangles"] == 2 * TINY["nx"] * TINY["ny"]
