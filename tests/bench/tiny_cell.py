"""Helpers of the benchmark's CPU tests: a tiny cell added to a copy of the
benchmark, and one run of it in the harness's CPU rehearsal mode."""
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY_CELL = "tiny.wave-front"
# the benchmark-share4 physics on an 8 x 6 rectangle of 1 km elements, 4 layers
TINY = {"name": "tiny", "nx": 8, "ny": 6, "lx": 8000.0, "ly": 6000.0,
        "triangles": 96, "nl": 4}


def add_cell(root: Path, conf: dict, traffic: str, limits: dict,
             chips: int = 1) -> str:
    """Add a configuration file and a cell of it on ``chips`` chips to the
    benchmark copy at ``root``; every per-layer metric lists the new
    cell."""
    bm = json.loads((root / "BENCHMARK.json").read_text())
    (root / "bench/configs" / f"{conf['name']}.json").write_text(
        json.dumps(conf))
    bm["configs"].append({"name": conf["name"], "source": "test",
                          "file": f"bench/configs/{conf['name']}.json",
                          "reduced": [], "why": "test"})
    name = f"{conf['name']}.{traffic}"
    bm["workloads"].append({"name": name, "config": conf["name"],
                            "traffic": traffic, "chips": chips,
                            "why": "test"})
    for m in bm["per_layer"]:
        m.setdefault("workloads", []).append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    (root / "bench/limits" / f"{name}.json").write_text(json.dumps(limits))
    return name


def rehearse(root: Path, seed: int = 2 ** 33 + 7, seconds: float = 0.5,
             trace: bool = False, break_step=None,
             workload: str = TINY_CELL) -> dict:
    """One harness run of the tiny cell on the CPU, float32 as on the chip."""
    import jax
    from bench import harness
    with jax.enable_x64(False):
        return harness.run(harness.Options(
            workload=workload, seed=seed, seconds=seconds, trace=trace,
            t_start=time.perf_counter(), require_tpu=False, repo=root,
            run_dir=root / "runs", break_step=break_step))
