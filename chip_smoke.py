"""Bring-up smoke of the split-IMEX ocean step on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip phase, and only that

Everything runs in this one process; no child process is started.  The script
exits non-zero, and prints no result line, unless JAX's first device is a TPU
and ``dispatch.resolve("auto")`` is the compiled Pallas backend: there is no
CPU fallback, no interpret mode and no f64 here.  A failed check is printed
as a ``phase=FAIL`` line when it happens; the phases after it still run, and
the script exits non-zero at the end.

Size.  The cell is ``OCEAN_CELLS["benchmark"]``, the paper's benchmark mesh
class: 32 layers, m_2d=20 external sub-steps, dt=60 s, Jackett EOS, GLS and
Coriolis.  Its whole mesh, rect_mesh(320, 328) = 209,920 triangles, needs
about four times the temporaries of a quarter of it, about 25 GB on the
kernel path, so it is a four-chip deployment.  One chip runs one chip's
share of it,
``chip_share(benchmark, 4)`` = rect_mesh(160, 164) = 52,480 triangles at the
same element size.  The v5e compile of that share's kernel-path step takes
6.33 GB of temporaries and 0.20 GB of arguments, within 80% of the 15.75 GB
the chip gives a program; the unfused reference step takes 5.54 GB.  Over
four chips the whole mesh, with the kernels on under ``shard_map``, takes
9.51 GB of temporaries per chip (a v5e:2x2 compile).

One chip, in order:
  0. each of the four ocean kernels, compiled, against its jnp oracle in
     ``kernels/ref.py`` on seeded inputs of the widths the step calls it at;
  1. compile the kernel-path step (``sim_campaign.step_program``, the
     campaign's step with its diagnostics) ahead of time, outside the
     runner; print the compile seconds, ``memory_analysis()`` and the count
     of ``tpu_custom_call`` in the compiled text, which must be > 0;
  2. run N_STEPS steps of it through ``SimulationRunner`` with one
     checkpoint; fail on any retry, ladder transition or cold restore, any
     non-finite diagnostic, or volume or tracer-mass drift past the
     ``MonitorPolicy`` bounds below; print wall ms per step, the
     physical-to-wall ratio, the ``kernel_dispatch{backend=pallas}``
     counters and ``peak_bytes_in_use``;
  3. run the same steps on the plain reference (``backend="ref"``,
     ``fused_horizontal=False``) and on the kernel path, both at the step's
     highest matmul precision, and fail past TOL; print, ungated, the
     deviation of the kernel path at TPU's default precision.

Four chips (``--chips 4``):
  1. the whole 209,920-triangle cell through ``DistributedOcean``: finite,
     volume and tracer mass within the bounds, ``peak_bytes_in_use`` about
     equal on the four devices;
  2. ``DistributedOcean`` at the one-chip share against the single-chip
     ``stepper.step``, field by field.

The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N_STEPS = 5
CKPT_EVERY = 3          # one checkpoint in N_STEPS
# Two f32 paths at highest precision, compared after step 1 and after
# N_STEPS: per field, max |a - b| over the field's scale max |b| (the two
# velocity components share the larger of their scales).  The bounds are
# ten times what the CPU f32 rehearsal of the kernel-vs-reference comparison
# gave on chip_share(benchmark, 64), 3,280 triangles: after step 1 eta
# 4.8e-5, ux 6.2e-3, uy 3.9e-3, T 3.7e-4, S 3.6e-4; after step 5 eta
# 9.3e-4, ux 0.14, uy 1.3e-2, T 4.7e-3, S 4.8e-3.  The velocity amplifies
# roundoff about 5e4-fold in one step, with or without GLS and in f64 alike
# (there the two paths differ by 8.7e-12 after step 1), and more with each
# step, so after step 5 the velocity bound catches only a gross failure:
# step 1 carries the check.  The distributed-vs-single-device rehearsal on
# four virtual CPU devices (800 triangles) stays inside the same bounds
# (after step 1: eta and u bitwise equal, T 8.3e-6, S 1.3e-5).
TOL = {1: {"eta": 5e-4, "ux": 7e-2, "uy": 4e-2, "T": 4e-3, "S": 4e-3},
       N_STEPS: {"eta": 1e-2, "ux": 1.5, "uy": 0.2, "T": 5e-2, "S": 5e-2}}
# a compiled kernel against its oracle: max |k - o| over max |o|.  The CPU
# interpret-mode rehearsal gave at most 3.0e-7 (nl=32, 1,280 columns); a
# bf16 pass would give about 1e-3 and a miscompile O(1)
KERNEL_TOL = 1e-5
# MonitorPolicy bounds on relative drift over N_STEPS, ten times the CPU f32
# rehearsal (volume 0, tracer masses 5.6e-7, the f32 rounding of the sums).
VOLUME_DRIFT_MAX = 1e-5
MASS_DRIFT_MAX = 1e-5
# largest allowed spread of peak_bytes_in_use over the four devices
PEAK_SPREAD_MAX = 0.10

FAILURES = []


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check(ok: bool, msg: str):
    """Record a failed check and go on; ``main`` exits non-zero at the
    end."""
    if not ok:
        FAILURES.append(msg)
        say(phase="FAIL", msg=repr(msg))


def check_device():
    """The first device, if it is a TPU on which "auto" compiles the
    Pallas kernels; fail otherwise."""
    import jax
    from repro.kernels import dispatch
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is {dev.platform!r}")
    bk = dispatch.resolve("auto")
    if bk is not dispatch.Backend.PALLAS:
        fail(f"dispatch.resolve('auto') is {bk.value!r}, not 'pallas'")
    return dev


def say(**kv):
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def kernel_cases(nt: int, nl: int = 32) -> dict:
    """name -> (kernel, oracle, argument shapes) of the four ocean kernels
    as the step calls them: ``nl`` layers and ``nt`` columns times the
    fields each call folds into its lanes."""
    from repro.kernels import column_solve, horizontal_flux, matrix_free, ref
    return {
        # stages 4-5: k=2 fields (ux, uy or T, S) per implicit solve
        "block_thomas_cell": (
            column_solve.block_thomas_cell, ref.block_thomas_cell,
            [(nl, 6, 6, nt)] * 3 + [(nl, 6, 2, nt)]),
        # pressure gradient: both components fold into the lanes
        "solve_r_cell": (
            matrix_free.solve_r_cell, ref.solve_r_cell,
            [(nl * 6, 2 * nt), (1, 2 * nt), (3, 2 * nt)]),
        "solve_w_cell": (
            matrix_free.solve_w_cell, ref.solve_w_cell,
            [(nl * 6, nt), (1, nt), (3, nt)]),
        # momentum + tracers stacked as k=4
        "lateral_flux_cell": (
            horizontal_flux.lateral_flux_cell, ref.lateral_flux_cell,
            [(nl * 6, 4 * nt), (nl * 12, 4 * nt), (nl * 12, 4 * nt),
             (6, 4 * nt)]),
    }


def kernel_inputs(name: str, shapes, seed: int = 0) -> list:
    """Seeded f32 inputs of the kinds the step passes: diagonally dominant
    6x6 blocks with no coupling past the column ends, positive areas and
    quadrature weights."""
    import numpy as np
    rng = np.random.default_rng(seed)
    xs = [rng.standard_normal(s, dtype=np.float32) for s in shapes]
    if name == "block_thomas_cell":
        lo, dg, up, _ = xs
        for x in (lo, dg, up):
            x *= 0.1
        lo[0] = 0.0
        up[-1] = 0.0
        dg += 2.0 * np.eye(6, dtype=np.float32)[None, :, :, None]
    elif name == "lateral_flux_cell":
        xs[3] = np.abs(xs[3]) + 0.1
    else:
        xs[1] = np.abs(xs[1]) + 0.5
    return xs


def kernel_oracles(nt: int):
    """Phase 0: each compiled kernel against its oracle."""
    import jax
    import numpy as np
    for name, (kernel, oracle, shapes) in kernel_cases(nt).items():
        xs = jax.device_put(kernel_inputs(name, shapes))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(oracle)(*xs))
        got = np.asarray(jax.jit(
            lambda *a, k=kernel: k(*a, interpret=False))(*xs))
        err = float(np.abs(got - want).max() / np.abs(want).max())
        say(phase="kernel_vs_oracle", kernel=name, rel_err=err)
        check(err <= KERNEL_TOL, f"{name} off its oracle by {err}")


def _fields(st):
    return {"eta": st.ext.eta, "ux": st.ux, "uy": st.uy, "T": st.T,
            "S": st.S}


def deviation(a, b) -> dict:
    """Per field: max |a - b| over the scale max |b| of the field; ux and
    uy share one speed scale."""
    import numpy as np
    fa = {k: np.asarray(v, np.float64) for k, v in _fields(a).items()}
    fb = {k: np.asarray(v, np.float64) for k, v in _fields(b).items()}
    scale = {k: np.abs(v).max() for k, v in fb.items()}
    scale["ux"] = scale["uy"] = max(scale["ux"], scale["uy"])
    return {k: float(np.abs(fa[k] - fb[k]).max() / max(scale[k], 1e-30))
            for k in fb}


def compare(label: str, a: tuple, b: tuple):
    """Gate two (after step 1, after N_STEPS) state pairs against TOL; a
    non-finite deviation fails too."""
    for step, x, y in zip((1, N_STEPS), a, b):
        dev = deviation(x, y)
        say(phase=label, after_step=step, **dev)
        bad = {k: v for k, v in dev.items() if not v <= TOL[step][k]}
        check(not bad, f"{label}: past tolerance after step {step}: {bad}")


def drift_policy(reference: dict):
    from repro.obs import diagnostics as obs_diag
    return obs_diag.MonitorPolicy(
        cfl_max=None, volume_drift_max=VOLUME_DRIFT_MAX,
        mass_drift_max=MASS_DRIFT_MAX, on_violation="silent",
        reference={k: reference[k] for k in ("volume", "mass_T", "mass_S")})


def diagnostics(cfg):
    """jitted (geom, vg, state) -> host diagnostics dict."""
    import jax
    from repro.obs import diagnostics as obs_diag
    fn = jax.jit(lambda g, v, s: obs_diag.compute(g, v, cfg, s))
    return lambda geom, vg, st: obs_diag.to_dict(fn(geom, vg, st))


def conservation(diag0: dict, diag1: dict, label: str):
    """Check for a non-finite state and drift past the MonitorPolicy
    bounds."""
    viol = drift_policy(diag0).check(diag1)
    for key in ("volume", "mass_T", "mass_S"):
        drift = abs(diag1[key] - diag0[key]) / abs(diag0[key])
        say(phase=label, metric=f"{key}_drift", value=drift)
    check(not (diag1["nonfinite"] or viol),
          f"{label}: {viol or 'non-finite state'}")


def check_peaks(devs, label: str):
    """Check that every device reports peak_bytes_in_use and that they
    agree within PEAK_SPREAD_MAX."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    say(phase="distributed", cell=label, peak_bytes_in_use=peaks)
    if None in peaks:
        check(False, "peak_bytes_in_use not reported")
        return
    spread = (max(peaks) - min(peaks)) / max(peaks)
    check(spread <= PEAK_SPREAD_MAX,
          f"peak_bytes_in_use spread {spread:.3f} over the devices")


def run_steps(fn, st, n):
    """The states after step 1 and after step n of ``fn``."""
    import jax
    first = st = fn(st)
    for _ in range(n - 1):
        st = fn(st)
    return jax.block_until_ready((first, st))


def one_chip(cell):
    """The one-chip phases (module docstring) on ``cell``."""
    import jax
    import jax.numpy as jnp
    from repro.core import stepper
    from repro.kernels import dispatch
    from repro.launch import sim_campaign
    from repro.obs import metrics as obs_metrics
    from repro.runtime.fault_tolerance import RunnerConfig, SimulationRunner

    dev = jax.devices()[0]
    kernels_compiled = dispatch.auto_backend() is dispatch.Backend.PALLAS
    case = sim_campaign.build_case(cell=cell, dtype=jnp.float32)
    geom, vg, cfg, st0 = case.geom, case.vg, case.cfg, case.state
    say(phase="setup", cell=cell.name, triangles=geom.nt, nl=cfg.nl,
        m_2d=cfg.m_2d, dt=cfg.dt, matmul_precision=cfg.matmul_precision,
        backend=dispatch.resolve(cfg.backend).value)

    # 0. the kernels alone (compiled only where the chip is)
    if kernels_compiled:
        kernel_oracles(geom.nt)

    # 1. ahead-of-time compile of the kernel path, outside the runner
    t0 = time.perf_counter()
    compiled = sim_campaign.step_program.lower(geom, vg, cfg, st0).compile()
    compile_s = time.perf_counter() - t0
    n_custom = compiled.as_text().count("tpu_custom_call")
    ma = compiled.memory_analysis()
    say(phase="compile", compile_s=round(compile_s, 3),
        tpu_custom_call=n_custom,
        temp_bytes=ma.temp_size_in_bytes,
        argument_bytes=ma.argument_size_in_bytes,
        output_bytes=ma.output_size_in_bytes)
    check(n_custom > 0 or not kernels_compiled,
          "no tpu_custom_call in the compiled step: kernels not compiled")

    # 2. the runner, with one checkpoint
    exact = diagnostics(cfg)
    diag0 = exact(geom, vg, st0)

    def factory(rung_cfg):
        check(rung_cfg == cfg, f"dt ladder engaged (dt={rung_cfg.dt})")
        return lambda s: compiled(geom, vg, s)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt") as ckdir:
        runner = SimulationRunner(
            factory, cfg,
            RunnerConfig(checkpoint_dir=ckdir, checkpoint_every=CKPT_EVERY,
                         max_retries=0),
            policy=sim_campaign.default_policy())
        t0 = time.perf_counter()
        try:
            st_k = jax.block_until_ready(
                runner.run(st0, N_STEPS, resume=False))
        except Exception as e:      # reported; phase 3 still runs
            st_k = None
            check(False, f"runner: {type(e).__name__}: {e}")
        wall = time.perf_counter() - t0
        intact = runner.ckpt.intact_steps()
    stats = runner.stats
    say(phase="runner", steps=stats["steps"], retries=stats["retries"],
        ladder_transitions=stats["ladder_transitions"],
        cold_restores=stats["cold_restores"], checkpoints=intact)
    check(stats["steps"] == N_STEPS and not stats["retries"]
          and not stats["ladder_transitions"]
          and not stats["cold_restores"], f"runner stats {stats}")
    check(intact == [CKPT_EVERY],
          f"expected one intact checkpoint at step {CKPT_EVERY}, "
          f"found {intact}")
    hist = obs_metrics.default().histogram("runner.step_time_s").snapshot()
    say(phase="runner", wall_ms_per_step=1e3 * wall / N_STEPS,
        runner_step_ms_p50=1e3 * hist["p50"],
        physical_to_wall=N_STEPS * cfg.dt / wall)
    counters = obs_metrics.default().snapshot()["counter"]
    for name, v in sorted(counters.items()):
        if name.startswith("kernel_dispatch"):
            say(phase="runner", counter=name, value=int(v))
    mem = dev.memory_stats() or {}
    say(phase="runner", peak_bytes_in_use=mem.get("peak_bytes_in_use"))
    if st_k is not None:
        conservation(diag0, exact(geom, vg, st_k), "runner")
    del compiled, st_k

    # 3. the plain reference and the kernel path; the states go to the host
    # as each path ends
    paths = {"ref": dataclasses.replace(cfg, backend="ref",
                                        fused_horizontal=False),
             "kernel": cfg,
             "kernel_default": dataclasses.replace(
                 cfg, matmul_precision="default")}
    step = jax.jit(stepper.step, static_argnums=2)
    finals = {}
    for name, c in paths.items():
        t0 = time.perf_counter()
        finals[name] = jax.device_get(run_steps(
            lambda s, c=c: step(geom, vg, c, s), st0, N_STEPS))
        say(phase="paths", path=name,
            backend=dispatch.resolve(c.backend).value,
            fused_horizontal=c.fused_horizontal,
            matmul_precision=c.matmul_precision,
            seconds_incl_compile=round(time.perf_counter() - t0, 3))
    conservation(diag0, exact(geom, vg, finals["ref"][1]), "ref")
    # ungated, for ROADMAP S2: the kernel path at TPU's default precision
    for step_i, st, st_ref in zip((1, N_STEPS), finals["kernel_default"],
                                  finals["ref"]):
        say(phase="kernel_default_vs_ref", after_step=step_i,
            **deviation(st, st_ref))
    compare("kernel_vs_ref", finals["kernel"], finals["ref"])


def four_chips(n_chips: int, full, share):
    """The four-chip phases (module docstring) on the cells given."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import stepper
    from repro.distributed.ocean import DistributedOcean
    from repro.launch import sim_campaign
    from repro.launch.ocean_dryrun import cell_setup

    devs = jax.devices()
    if len(devs) != n_chips:
        fail(f"{n_chips} devices asked for, JAX sees {len(devs)}")
    dmesh = Mesh(np.array(devs), ("x",))

    def distributed(cell):
        m, _, b, cfg = cell_setup(cell)
        case = sim_campaign.build_case(cell=cell, dtype=jnp.float32)
        do = DistributedOcean(m, b, cfg, dmesh, ("x",))
        say(phase="distributed", cell=cell.name, triangles=m.nt,
            devices=n_chips, nt_local=do.spec.n_loc, backend=do.backend,
            matmul_precision=do.cfg.matmul_precision)
        return case, do

    # 1. the whole cell over the chips.  Its global arrays (the case, the
    # per-partition geometry before placement, the diagnostics) live on the
    # host, so no chip holds more than its partition and the peaks compare
    with jax.default_device(jax.devices("cpu")[0]):
        case, do = distributed(full)
        exact = diagnostics(case.cfg)
        diag0 = exact(case.geom, case.vg, case.state)
        stk = do.scatter_state(case.state)
        case = dataclasses.replace(case, state=None)
        step = do.make_step()
        t0 = time.perf_counter()
        stk = jax.block_until_ready(step(stk))
        say(phase="distributed", cell=full.name,
            first_step_s_incl_compile=round(time.perf_counter() - t0, 3))
        t0 = time.perf_counter()
        stk = run_steps(step, stk, N_STEPS - 1)[1]
        wall = time.perf_counter() - t0
        say(phase="distributed", cell=full.name,
            wall_ms_per_step=1e3 * wall / (N_STEPS - 1),
            physical_to_wall=(N_STEPS - 1) * do.cfg.dt / wall)
        check_peaks(devs, full.name)
        st1 = do.gather_state(stk)
        del stk
        conservation(diag0, exact(case.geom, case.vg, st1),
                     "distributed_full")
    del case, do, st1

    # 2. equivalence at the one-chip share
    case, do = distributed(share)
    ref_cfg = dataclasses.replace(case.cfg, backend="ref")
    step = jax.jit(stepper.step, static_argnums=2)
    one = jax.device_get(run_steps(
        lambda s: step(case.geom, case.vg, ref_cfg, s), case.state,
        N_STEPS))
    stk = run_steps(do.make_step(), do.scatter_state(case.state), N_STEPS)
    compare("distributed_vs_one_chip", tuple(map(do.gather_state, stk)), one)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import compile_cache
    compile_cache.configure()
    import jax
    from repro.launch.ocean_dryrun import OCEAN_CELLS, chip_share

    dev = check_device()
    benchmark = OCEAN_CELLS["benchmark"]
    if args.chips == 1:
        one_chip(chip_share(benchmark, 4))
    else:
        four_chips(args.chips, benchmark, chip_share(benchmark, 4))
    if FAILURES:
        fail(f"{len(FAILURES)} check(s) failed: {FAILURES}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
