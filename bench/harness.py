"""One run of one benchmark cell: set-up, warm-up, the measured window, the
check against the plain reference, and the result line.

Everything a cell needs is found by name: the workload in ``BENCHMARK.json``
names its configuration (a file of sizes and physics under
``bench/configs/``) and its traffic mix (``bench/traffic/<mix>.json``); each
per-layer metric is a reader ``bench/layers/<metric>.py``; the limits of the
check are ``bench/limits/<workload>.json``.  Nothing here changes with a new
cell.

The window drives the program's normal path: ``SimulationRunner.run`` over
``sim_campaign.step_program`` (the kernel path, the fused horizontal RHS,
highest matmul precision, diagnostics every step and the halt-mode
``default_policy``), whole steps until ``--seconds`` have passed, with no
checkpoint inside it.  A cell on more than one chip runs the program's
multi-chip entry point instead, ``DistributedOcean`` (``bench/sharded.py``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
N_WARMUP = 2          # steps outside the window; step 1 is the checked one
DRIFT_STEPS = 8       # conservation is read from step 1 to step 1 + this
NEVER = 1 << 62       # checkpoint_every: no save inside the window


def say(**kv):
    """An earlier line of standard output: ``key=value ...``."""
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


# ---------------------------------------------------------------- lookup
@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    workload: dict
    conf: dict          # the configuration file
    mix: dict           # the traffic mix file
    limits: dict        # bench/limits/<workload>.json
    per_layer: list     # BENCHMARK.json per_layer entries for this cell
    end_to_end: list


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    return json.loads(path.read_text())


def load_cell(name: str, repo: Path = REPO) -> Cell:
    """The cell ``name`` of ``<repo>/BENCHMARK.json`` with its files."""
    bm = _json(repo / "BENCHMARK.json")
    wl = {w["name"]: w for w in bm["workloads"]}
    if name not in wl:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(wl)})")
    w = wl[name]
    cf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    bench = repo / "bench"

    def mine(m):
        return name in m.get("workloads", [name])

    from . import traffic
    return Cell(name=name, workload=w, conf=_json(repo / cf["file"]),
                mix=traffic.load(w["traffic"], bench),
                limits=_json(bench / "limits" / f"{name}.json"),
                per_layer=[m for m in bm["per_layer"] if mine(m)],
                end_to_end=[m for m in bm["end_to_end"] if mine(m)])


def layer_reader(metric: str, bench: Path = BENCH):
    """``read(trace, ctx)`` of ``bench/layers/<metric>.py``."""
    path = bench / "layers" / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {metric!r} "
                                f"at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_layer_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------- device
class NoChip(SystemExit):
    pass


def devices(chips: int, require_tpu: bool = True):
    """The first ``chips`` devices; exit (no result) unless they are TPUs,
    where ``require_tpu``, and there are that many."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"bench: no TPU: JAX's first device is "
                     f"{devs[0].platform!r}; there is no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def peaks(kind: str, bench: Path = BENCH) -> dict:
    """Peak rates of ``device_kind`` from ``bench/peaks.json``; a device
    that is not in the table is an error."""
    table = _json(bench / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"bench: no peaks for device kind {kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[kind]


# ---------------------------------------------------------------- program
PHYSICS = ("nl", "dt", "m_2d", "coriolis_f", "cd_bottom", "cs_smag",
           "eos_kind", "h_min", "implicit_stage1", "exact_consistency",
           "nu_v_bg", "kappa_v_bg", "use_gls", "matmul_precision")


def same_physics(cfg, conf: dict):
    """Refuses an ``OceanConfig`` whose physics departs from the file."""
    got = {k: getattr(cfg, k) for k in PHYSICS}
    want = {k: conf[k] for k in PHYSICS}
    if got != want:
        raise ValueError(f"bench: the program's OceanConfig departs from "
                         f"{conf['name']}: {got} != {want}")


def program_parts(conf: dict) -> tuple:
    """(mesh, geometry, nodal bathymetry, OceanConfig) of ``conf``:
    ``ocean_dryrun.cell_setup`` of the configuration's sizes.  Refuses a
    program whose physics or triangle count departs from the file."""
    import jax.numpy as jnp
    from repro.launch.ocean_dryrun import OceanCell, cell_setup
    cell = OceanCell(conf["name"], conf["nx"], conf["ny"], conf["lx"],
                     conf["ly"], conf["nl"], conf["m_2d"], conf["dt"],
                     conf["depth"], reef=conf["reef"])
    m, geom, b, cfg = cell_setup(cell, jnp.float32)
    same_physics(cfg, conf)
    if m.nt != conf["triangles"]:
        raise ValueError(f"bench: {m.nt} triangles, the file states "
                         f"{conf['triangles']}")
    return m, geom, b, cfg


def program_case(conf: dict, fields: dict, parts: Optional[tuple] = None):
    """The program's case for ``conf`` (``program_parts``, or ``parts`` where
    the caller has them), its state with the traffic's fields."""
    import jax.numpy as jnp
    from repro.core import dg2d, stepper
    from repro.core.extrusion import VGrid
    from repro.launch.sim_campaign import Case
    _, geom, b, cfg = parts or program_parts(conf)
    vg = VGrid(b=jnp.asarray(b), nl=cfg.nl)
    st = stepper.init_state(geom, vg, dtype=jnp.float32)
    f = {k: jnp.asarray(fields[k], jnp.float32)
         for k in ("eta", "qx", "qy", "ux", "uy", "T", "S")}
    st = dataclasses.replace(
        st, ext=dg2d.State2D(f["eta"], f["qx"], f["qy"]), ux=f["ux"],
        uy=f["uy"], T=f["T"], S=f["S"])
    return Case(geom=geom, vg=vg, cfg=cfg, state=st), np.asarray(b)


class Keep:
    """Wraps the campaign's step factory and keeps ``pick`` of the step's
    ``(state, diagnostics)`` (the on-device ``Diagnostics`` by default) of
    step 1 and of step 1 + ``DRIFT_STEPS`` (or the last step, in a shorter
    run): references only, no copy, no sync.  A fixed count of steps keeps
    the drift a property of the program, not of how many steps a window
    holds."""

    def __init__(self, factory, pick=lambda out: out[1]):
        self.factory = factory
        self.pick = pick
        self.seen = 0
        self.first = self.last = None

    def __call__(self, cfg):
        fn = self.factory(cfg)

        def step(s):
            out = fn(s)
            self.seen += 1
            if self.first is None:
                self.first = self.pick(out)
            if self.seen <= 1 + DRIFT_STEPS:
                self.last = self.pick(out)
            return out
        return step


class _CompileCount:
    """Backend compilations seen by ``jax.monitoring`` while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# ---------------------------------------------------------------- check
# the prognostic fields the check compares
FIELDS = ("eta", "ux", "uy", "T", "S", "turb_k", "turb_eps", "nu_t",
          "kappa_t")


def deviation(got: dict, want: dict, start: dict) -> dict:
    """Per field, two readings of the gap between the program's state
    ``got`` and the reference's ``want`` after one step from ``start``, each
    over the same reading of the field's change in that step, so that a
    number reads how much of the step's work on the field went wrong,
    whatever the field's magnitude: ``<field>``, the widest gap over the
    widest change; ``<field>_mean``, the mean gap over the mean change."""
    out = {}
    for k in FIELDS:
        g, w, s = (np.asarray(d[k], np.float64) for d in (got, want, start))
        gap, change = np.abs(g - w), np.abs(w - s)
        out[k] = float(gap.max() / max(change.max(), 1e-30))
        out[f"{k}_mean"] = float(gap.mean() / max(change.mean(), 1e-30))
    return out


def fields(st) -> dict:
    """The prognostic fields compared, as host arrays."""
    import jax
    return jax.device_get({
        "eta": st.ext.eta, "ux": st.ux, "uy": st.uy, "T": st.T, "S": st.S,
        "turb_k": st.turb_k, "turb_eps": st.turb_eps, "nu_t": st.nu_t,
        "kappa_t": st.kappa_t})


def drift(first: dict, last: dict) -> dict:
    """Relative change of volume and tracer masses between two diagnostics
    of the program (``obs/diagnostics.py``)."""
    return {f"drift_{k}": abs(last[k] - first[k]) / abs(first[k])
            for k in ("volume", "mass_T", "mass_S")}


def same_as_file(node_x, b, want_x, want_b):
    """Refuses a mesh or bathymetry that is not the configuration's."""
    if not np.array_equal(np.asarray(node_x), want_x):
        raise ValueError("bench: the program's mesh is not the "
                         "configuration's")
    if not np.array_equal(np.asarray(b), want_b):
        raise ValueError("bench: the program's bathymetry is not the "
                         "configuration's")


def reference_step1(conf: dict, fields0: dict) -> tuple:
    """(start, after): the plain reference's initial state from the
    traffic's fields and its state after one step, as host arrays."""
    import jax
    import jax.numpy as jnp
    from . import reference
    case = reference.build(conf, jnp.float32)
    st0 = reference.initial_state(case, fields0)
    st1 = jax.block_until_ready(reference.step_fn(case)(st0))
    return fields(st0), fields(st1)


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]): every number of the limits file
    at or under its limit, and finite."""
    rows = []
    ok = True
    for name, spec in limits["numbers"].items():
        v = numbers.get(name, math.nan)
        lim = spec["limit"]
        rows.append((name, v, lim))
        ok &= bool(v <= lim)            # nan fails
    return ok, rows


# ---------------------------------------------------------------- run
@dataclasses.dataclass
class Options:
    workload: str
    seed: int
    seconds: float
    trace: bool
    t_start: float                 # perf_counter at process start
    require_tpu: bool = True
    repo: Path = REPO
    run_dir: Optional[Path] = None
    # tests only: wraps the step factory, to break the timed path underneath
    break_step: Optional[Callable] = None


def run(opt: Options) -> dict:
    """One run; returns the result object (the last line's content)."""
    import jax
    from . import traffic
    from repro.launch import sim_campaign
    from repro.runtime.fault_tolerance import RunnerConfig, SimulationRunner

    cell = load_cell(opt.workload, opt.repo)
    conf = cell.conf
    devs = devices(cell.workload["chips"], opt.require_tpu)
    dev = devs[0]
    run_dir = opt.run_dir or (opt.repo / "runs" / "bench" / opt.workload)
    run_dir.mkdir(parents=True, exist_ok=True)
    compiles = _CompileCount()
    if len(devs) > 1:
        from . import sharded
        return sharded.run(opt, cell, devs, run_dir, compiles)

    # ------------------------------------------------------------ set-up
    from . import reference
    node_x, node_y, b = reference.nodes(conf)
    fields0 = traffic.initial_fields(cell.mix, conf, node_x, node_y, b,
                                     opt.seed)
    case, program_b = program_case(conf, fields0)
    same_as_file(case.geom.node_x, program_b, node_x, b)
    say(phase="setup", cell=opt.workload, triangles=case.geom.nt,
        nl=case.cfg.nl, dt=case.cfg.dt, m_2d=case.cfg.m_2d,
        backend=case.cfg.backend, fused=case.cfg.fused_horizontal,
        precision=case.cfg.matmul_precision,
        **{k: round(v, 6) for k, v in fields0["draw"].items()})
    tdir = run_dir / "trace"
    # the step is compiled ahead of time in every run and the runner calls
    # that executable: a jit dispatch after an ahead-of-time compile is
    # keyed apart in the compile cache on the TPU, so a traced and an
    # untraced run each compiled once in a checkout
    t0 = time.perf_counter()
    compiled = sim_campaign.step_program.lower(
        case.geom, case.vg, case.cfg, case.state).compile()
    say(phase="compile", seconds=round(time.perf_counter() - t0, 3))
    if opt.trace:
        program_report(compiled, tdir)
    geom, vg = case.geom, case.vg

    def factory(cfg):
        if cfg != case.cfg:             # a dt-ladder rung compiles its own
            return sim_campaign.make_step_factory(case)(cfg)
        return lambda s: compiled(geom, vg, s)
    if opt.break_step is not None:
        factory = opt.break_step(factory)
    diags = Keep(factory)
    runner = SimulationRunner(
        diags, case.cfg,
        RunnerConfig(checkpoint_dir=str(run_dir / "ckpt"),
                     checkpoint_every=NEVER, max_retries=0),
        policy=sim_campaign.default_policy())

    # ------------------------------------------------------------ warm-up
    # the window's own object and call: step 1 is what the check compares
    st = runner.run(case.state, 1, start_step=0, resume=False)
    step1 = fields(st)
    for k in range(1, N_WARMUP):
        st = runner.run(st, k + 1, start_step=k, resume=False)
    jax.block_until_ready(st)
    case = dataclasses.replace(case, state=None)

    held = [st]
    del st
    st, w = window(runner, held, opt, tdir, compiles)

    from repro.obs import diagnostics as obs_diag
    numbers = {}
    if diags.first is not None and diags.last is not None:
        numbers.update(drift(obs_diag.to_dict(diags.first),
                             obs_diag.to_dict(diags.last)))
    # peak_bytes_in_use counts arrays only; the runtime reserves a
    # program's temporaries apart (bytes_reserved), so the peak the chip
    # gave this process is the sum of the two peaks
    mem = dev.memory_stats() or {}
    say(phase="memory", **{k: mem[k] for k in sorted(mem)})
    memory_peak = int(mem.get("peak_bytes_in_use", 0)
                      + mem.get("peak_bytes_reserved", 0))
    del st, runner, diags

    # ------------------------------------------------------------ check
    t0 = time.perf_counter()
    start, ref1 = reference_step1(conf, fields0)
    say(phase="reference", seconds=time.perf_counter() - t0)
    numbers.update(deviation(step1, ref1, start))
    return finish(opt, cell, devs, numbers, w, memory_peak, tdir)


def program_report(compiled, tdir: Path):
    """In a traced run: the compiled step's memory and, beside the trace,
    its scopes (the trace names each op by its HLO instruction; the
    compiled program's metadata gives the op's name-scope path)."""
    from . import trace_reduce
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    say(phase="program", temp_bytes=ma.temp_size_in_bytes,
        argument_bytes=ma.argument_size_in_bytes,
        output_bytes=ma.output_size_in_bytes,
        alias_bytes=ma.alias_size_in_bytes,
        tpu_custom_call=hlo.count("tpu_custom_call"))
    shutil.rmtree(tdir, ignore_errors=True)
    tdir.mkdir(parents=True)
    (tdir / trace_reduce.SCOPES_FILE).write_text(
        json.dumps(trace_reduce.hlo_scopes(hlo)))


@dataclasses.dataclass
class Window:
    steps: int
    failed: int
    wall_s: float
    setup_s: float


def window(runner, held: list, opt: Options, tdir: Path,
           compiles: _CompileCount, sync: Optional[Callable] = None) -> tuple:
    """(state, Window): whole steps through ``runner`` from the state in
    ``held`` until ``opt.seconds`` have passed, each inside a
    ``bench.runner_run`` span that ends with ``sync(state)`` where one is
    given; traced where ``opt.trace``.  The state is taken out of ``held``
    so that no caller's reference keeps an earlier step's state alive."""
    import jax
    from . import trace_reduce
    st = held.pop()
    if opt.trace:
        jax.profiler.start_trace(str(tdir))
    steps = failed = 0
    error = None
    compiles.on = True
    t_window = time.perf_counter()
    setup_s = t_window - opt.t_start
    with trace_reduce.span("bench.window"):
        while True:
            k = N_WARMUP + steps
            try:
                with trace_reduce.span("bench.runner_run"):
                    st = runner.run(st, k + 1, start_step=k, resume=False)
                    if sync is not None:
                        sync(st)
            except Exception as e:          # a halt or a non-finite step
                failed += 1
                error = f"{type(e).__name__}: {e}"
                break
            steps += 1
            if time.perf_counter() - t_window >= opt.seconds:
                break
    wall = time.perf_counter() - t_window
    compiles.on = False
    if opt.trace:
        jax.profiler.stop_trace()
    failed = max(failed, runner.stats["retries"])
    say(phase="window", steps=steps, failed=failed, wall_s=wall,
        compiles_in_window=compiles.n, error=repr(error))
    return st, Window(steps, failed, wall, setup_s)


def finish(opt: Options, cell: Cell, devs, numbers: dict, w: Window,
           memory_peak: int, tdir: Path, sound: bool = True) -> dict:
    """The check's verdict on ``numbers`` (and ``sound``: nothing else
    failed), the metrics of the window (from the trace, where traced) and
    the result object, with the numbers compared printed last."""
    from . import trace_reduce
    conf, dev = cell.conf, devs[0]
    say(phase="numbers", **numbers)
    correct, rows = judge(numbers, cell.limits)
    correct = correct and sound and w.failed == 0 and w.steps > 0

    # ------------------------------------------------------------ result
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    metrics = {}
    result = {"correct": bool(correct), "attempted": N_WARMUP + w.steps,
              "failed": w.failed, "metrics": metrics, "device": device}
    if not opt.trace:
        values = {"sim_per_wall": (conf["dt"] * w.steps / w.wall_s
                                   if w.steps else 0.0),
                  "setup_s": w.setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        t0 = time.perf_counter()
        tr = trace_reduce.reduce(tdir)
        # device time no stage scope claims: diagnostics, state assembly
        other = tr.op_seconds(lambda o: not any(
            c.startswith("stage.") for c in o.scope.split("/")))
        say(phase="reduce", seconds=time.perf_counter() - t0,
            ops=len(tr.ops),
            other_ms_per_step=1e3 * other / max(w.steps, 1))
        ctx = trace_reduce.Context(conf=conf, steps=w.steps, wall_s=w.wall_s,
                                   kind=dev.device_kind,
                                   peaks=lambda: peaks(dev.device_kind,
                                                       opt.repo / "bench"),
                                   chips=len(devs))
        for m in cell.per_layer:
            v = layer_reader(m["name"], opt.repo / "bench")(tr, ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        result["breakdown"] = tr.breakdown()
        for line in ctx.notes:
            say(**line)
    result["check"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for n, v, lim in rows:
        print(f"check {n}={v!r} limit={lim!r}", file=sys.stderr)
    return result
