"""Paper Figs. 16-18: multi-device scaling and the Amdahl fit.

Strong scaling measured on 8 spoofed host devices (subprocess), plus the
paper's Amdahl decomposition: the 2D external mode is the latency-bound
'serial' fraction, the 3D mode scales.  We report measured times for
1/2/4/8 ways and the fitted serial fraction; the dry-run collective model
extends the curve to 256/512 chips (EXPERIMENTS.md §Dry-run).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from .common import row

ROOT = Path(__file__).resolve().parents[1]

_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time, json
import jax, jax.numpy as jnp
import numpy as np
from repro.core import geometry, mesh2d, stepper
from repro.distributed.ocean import DistributedOcean

results = {}
mesh2d_obj = mesh2d.rect_mesh(32, 16, 40e3, 20e3, jitter=0.15, seed=3)
b = np.full((3, mesh2d_obj.nt), 30.0, np.float32)
cfg = stepper.OceanConfig(nl=8, dt=20.0, m_2d=10, use_gls=True)
for p in (1, 2, 4, 8):
    dmesh = jax.make_mesh((p,), ("data",),
                          axis_types=(jax.sharding.AxisType.Auto,))
    do = DistributedOcean(mesh2d_obj, b, cfg, dmesh, ("data",))
    stk = do.init_state()
    step = do.make_step()
    stk = step(stk); jax.block_until_ready(stk)
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        stk = step(stk)
        jax.block_until_ready(stk)
        ts.append(time.perf_counter() - t0)
    results[p] = float(np.median(ts))
print("RESULTS=" + json.dumps(results))
'''


def run():
    # a virtual-device CPU rehearsal: the parent has imported JAX and may
    # hold the accelerator, so the child must never ask for it
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        timeout=3600, cwd=ROOT, env=env)
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULTS=")]
    if res.returncode != 0 or not line:
        raise RuntimeError(f"fig16_scaling child failed (rc "
                           f"{res.returncode}): {res.stderr[-2000:]}")
    results = {int(k): v for k, v in json.loads(line[0][8:]).items()}
    t1 = results[1]
    # Amdahl fit: t(p) = t1*(s + (1-s)/p) — least squares over measured p
    import numpy as np
    ps = np.array(sorted(results))
    ts = np.array([results[p] for p in ps])
    A = np.stack([np.ones_like(ps, float), 1.0 / ps], axis=1)
    coef, *_ = np.linalg.lstsq(A, ts / t1, rcond=None)
    serial = max(min(coef[0], 1.0), 0.0)
    for p in ps:
        sp = t1 / results[p]
        eff = sp / p
        row(f"fig16_scaling_p{p}", results[p] * 1e6,
            f"speedup={sp:.2f};efficiency={eff:.2f}")
    row("fig16_amdahl_serial_fraction", serial * 1e6,
        f"serial_fraction={serial:.3f}")


if __name__ == "__main__":
    run()
