"""The whole run of a tiny four-chip cell on the CPU, in the harness's
rehearsal mode, on four virtual devices: set-up through
``DistributedOcean``, the window, the gathered check against the plain
reference split over the same devices, and the result line; and each fault
the cell can have planted underneath the runner or inside the compiled step
comes out not correct; and the readings ``bench/control.py`` takes on that
path.  Device-count spoofing needs a fresh process, so the runs share one
subprocess (``python test_bench_sharded.py <root>``)."""
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import pytest

from tiny_cell import REPO, add_cell

CELL = "tiny4.wave-front"
FAULTS = ("unchanged", "half", "altered", "no_exchange")


@pytest.fixture(scope="module")
def runs(tiny_repo, tmp_path_factory):
    """Result objects of the sound, traced and broken runs, by name."""
    import shutil
    root = tmp_path_factory.mktemp("bench4")
    shutil.copytree(tiny_repo, root, dirs_exist_ok=True)
    conf = json.loads((root / "bench/configs/tiny.json").read_text())
    conf["name"] = "tiny4"
    limits = json.loads(
        (REPO / "bench/limits/benchmark-share4.wave-front.json").read_text())
    assert add_cell(root, conf, "wave-front", limits, chips=4) == CELL
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(REPO / "src"), str(REPO)])}
    r = subprocess.run([sys.executable, __file__, str(root)], env=env,
                       cwd=Path(__file__).parent, capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_tiny_four_chip_cell_runs_end_to_end_and_is_correct(runs):
    r = runs["sound"]
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] >= 3
    assert set(r["metrics"]) == {"sim_per_wall", "setup_s"}
    assert r["device"]["count"] == 4 and r["device"]["platform"] == "cpu"
    assert set(r["check"]) == {"eta", "uy_mean", "drift_volume",
                               "drift_mass_T", "drift_mass_S"}
    for row in r["check"].values():
        assert math.isfinite(row["value"]) and row["value"] <= row["limit"]


def test_tiny_four_chip_traced_run_reports_the_trace(runs):
    r = runs["traced"]
    assert r["correct"] is True
    # a CPU trace has no TPU plane: no device op, so no per-layer reading
    assert r["metrics"] == {}
    assert r["device"]["window_s"] > 0 and "busy_s" in r["device"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_sharded_step_is_not_correct(runs, fault):
    r = runs[fault]
    assert r["correct"] is False
    assert any(not row["value"] <= row["limit"]
               for row in r["check"].values())


def test_control_readings_on_four_devices(runs):
    """``bench/control.py`` on the four-chip path: sound readings with their
    drift, the control's without (its limits are stated)."""
    summary = runs["readings"]
    assert summary["sound_correct"] == [True, True]
    assert set(summary["lower"]) >= {"eta", "uy_mean", "drift_volume",
                                     "drift_mass_T", "drift_mass_S"}
    assert all(math.isfinite(v) for v in summary["lower"].values())
    assert set(summary["upper"]) == {k for k in summary["lower"]
                                     if not k.startswith("drift_")}
    assert len(summary["control_correct"]) == 1


# ------------------------------------------------------------ the subprocess
def _placed(new, like):
    import jax
    return jax.device_put(new, like.sharding)


def _unchanged(factory):
    """A step that returns its state unchanged."""
    def make(cfg):
        fn = factory(cfg)
        return lambda s: (s, fn(s)[1])
    return make


def _half(factory):
    """Half of each partition's triangles left out: they keep their old
    state."""
    import jax
    import jax.numpy as jnp

    def make(cfg):
        fn = factory(cfg)

        def step(s):
            new, diag = fn(s)
            n = s.T.shape[-1]

            def keep(a, o):
                if a.ndim < 2:
                    return a
                return _placed(jnp.where(jnp.arange(n) < n // 2, a, o), a)
            return jax.tree.map(keep, new, s), diag
        return step
    return make


def _altered(factory):
    """The surface elevation off by a centimetre at one owned node of the
    first partition, where the step produces it."""
    def make(cfg):
        fn = factory(cfg)

        def step(s):
            new, diag = fn(s)
            eta = _placed(new.ext.eta.at[0, 0, 0].add(0.01), new.ext.eta)
            ext = type(new.ext)(eta, new.ext.qx, new.ext.qy)
            return type(new)(**{**new.__dict__, "ext": ext}), diag
        return step
    return make


def _main(root: Path):
    import jax
    from repro.distributed import halo
    from tiny_cell import rehearse
    out = {"sound": rehearse(root, workload=CELL),
           "traced": rehearse(root, workload=CELL, trace=True, seconds=0.2)}
    for name, fault in (("unchanged", _unchanged), ("half", _half),
                        ("altered", _altered)):
        out[name] = rehearse(root, workload=CELL, break_step=fault)
    from bench import control
    with jax.enable_x64(False):
        out["readings"] = control.readings(CELL, [5, 2 ** 35], [7],
                                           require_tpu=False, repo=root)
    # the exchange between chips left out, inside the compiled step
    exchange = halo.exchange
    halo.exchange = lambda x, t: x
    jax.clear_caches()
    try:
        out["no_exchange"] = rehearse(root, workload=CELL)
    finally:
        halo.exchange = exchange
    print(json.dumps(out))


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).parent))
    _main(Path(sys.argv[1]))
