"""A cell on several chips: one run through the program's multi-chip entry
point, ``distributed.ocean.DistributedOcean``, over the whole mesh.

The whole-mesh case is built on the host's CPU device, so no chip ever holds
the whole mesh; the program's sharded ocean places each partition on its own
chip.  The window drives the same ``SimulationRunner.run`` loop as a
one-chip cell over the ahead-of-time compiled shard-mapped step, which
returns no ``Diagnostics``: each step ends in one blocking host read of the
new state's time.  The check gathers step 1 of the window's own runner and
compares it with one step of the plain reference, jitted over the same
chips with its arrays split on the triangle axis (the whole-mesh reference
step needs more memory than one chip has); the drift of volume and tracer
mass is read on the host from the gathered states.
"""
from __future__ import annotations

import copy
import dataclasses
import time

import numpy as np

from . import harness
from .harness import DRIFT_STEPS, N_WARMUP, NEVER, say


def _cpu():
    import jax
    return jax.devices("cpu")[0]


class Program:
    """The program's sharded ocean for ``conf`` over ``devs``: the mesh,
    bathymetry and physics the harness checks against the configuration,
    handed to ``DistributedOcean`` (what ``ocean_dryrun.build_cell`` does
    with the same parts)."""

    def __init__(self, conf: dict, devs):
        import jax
        from jax.sharding import Mesh
        from repro.distributed.ocean import DistributedOcean
        self.conf = conf
        with jax.default_device(_cpu()):
            self.parts = harness.program_parts(conf)
            m, _, b, cfg = self.parts
            self.do = DistributedOcean(m, b, cfg, Mesh(np.array(devs), ("x",)),
                                       ("x",))
        harness.same_physics(self.do.cfg, conf)

    def case(self, fields0: dict):
        """(host case, program bathymetry) with the traffic's state."""
        import jax
        with jax.default_device(_cpu()):
            return harness.program_case(self.conf, fields0, self.parts)

    def compile(self, stk, precision: str | None = None):
        """The shard-mapped step compiled ahead of time for the placed
        partition data and the stacked state ``stk``, at the configuration's
        matmul precision or at ``precision``."""
        import jax
        do = self.do
        if precision is not None:
            do = copy.copy(do)
            do.cfg = dataclasses.replace(do.cfg, matmul_precision=precision)
        return jax.jit(do.make_step_args()).lower(
            do.geom_stk, do.b_stk, do.tables, stk).compile()

    def step(self, compiled):
        """``state -> state`` of a compiled step."""
        do = self.do
        return lambda s: compiled(do.geom_stk, do.b_stk, do.tables, s)

    def gather(self, stk):
        """The whole-mesh state of a stacked one, on the host."""
        import jax
        with jax.default_device(_cpu()):
            return self.do.gather_state(stk)


def diagnostics(case, st) -> dict:
    """The program's monitors (``obs/diagnostics.py``) of a whole-mesh host
    state, on the host."""
    import jax
    from repro.obs import diagnostics as obs_diag
    with jax.default_device(_cpu()):
        return obs_diag.to_dict(jax.jit(obs_diag.compute, static_argnums=2)(
            case.geom, case.vg, case.cfg, st))


def finite(st) -> bool:
    return all(bool(np.isfinite(v).all())
               for v in harness.fields(st).values())


class Reference:
    """The plain reference of ``conf``, built on the host and its step
    jitted over ``devs``: every array whose last axis is the triangle axis
    split along it, the rest replicated."""

    def __init__(self, conf: dict, devs):
        import jax
        from jax.sharding import Mesh
        from . import reference
        self.mesh = Mesh(np.array(devs), ("x",))
        self.nt = conf["triangles"]
        with jax.default_device(_cpu()):
            self.case = reference.build(conf, jax.numpy.float32)
        tm = jax.tree_util.tree_map
        self.placed = dataclasses.replace(
            self.case, geom=tm(self._put, self.case.geom),
            vg=tm(self._put, self.case.vg))

    def _put(self, x):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        split = x.ndim > 0 and x.shape[-1] == self.nt
        spec = PartitionSpec(*([None] * (x.ndim - 1)), "x") if split \
            else PartitionSpec()
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def step1(self, fields0: dict) -> tuple:
        """(start, after) as ``harness.reference_step1``."""
        import jax
        from . import reference
        with jax.default_device(_cpu()):
            st0 = reference.initial_state(self.case, fields0)
        st1 = jax.block_until_ready(reference.step_fn(self.placed)(
            jax.tree_util.tree_map(self._put, st0)))
        return harness.fields(st0), harness.fields(st1)


def run(opt, cell, devs, run_dir, compiles) -> dict:
    """``harness.run`` for a cell on ``len(devs)`` chips."""
    import jax
    from . import reference, traffic
    from repro.runtime.fault_tolerance import RunnerConfig, SimulationRunner
    conf = cell.conf

    # ------------------------------------------------------------ set-up
    node_x, node_y, b = reference.nodes(conf)
    fields0 = traffic.initial_fields(cell.mix, conf, node_x, node_y, b,
                                     opt.seed)
    prog = Program(conf, devs)
    do = prog.do
    case, program_b = prog.case(fields0)
    harness.same_as_file(case.geom.node_x, program_b, node_x, b)
    stk = do.scatter_state(case.state)
    case = dataclasses.replace(case, state=None)
    say(phase="setup", cell=opt.workload, triangles=case.geom.nt,
        chips=len(devs), nt_local=do.spec.n_loc, nt_own=do.spec.n_own,
        exchange_offsets=len(do.spec.tables), nl=do.cfg.nl, dt=do.cfg.dt,
        m_2d=do.cfg.m_2d, backend=do.cfg.backend,
        fused=do.cfg.fused_horizontal, precision=do.cfg.matmul_precision,
        **{k: round(v, 6) for k, v in fields0["draw"].items()})
    tdir = run_dir / "trace"
    t0 = time.perf_counter()
    compiled = prog.compile(stk)
    say(phase="compile", seconds=round(time.perf_counter() - t0, 3))
    if opt.trace:
        harness.program_report(compiled, tdir)
    step = prog.step(compiled)

    def factory(cfg):
        return lambda s: (step(s), None)
    if opt.break_step is not None:
        factory = opt.break_step(factory)
    # the states of step 1 and step 1 + DRIFT_STEPS, gathered after the
    # window: the program gives no diagnostics to keep
    kept = harness.Keep(factory, pick=lambda out: out[0])
    runner = SimulationRunner(
        kept, do.cfg,
        RunnerConfig(checkpoint_dir=str(run_dir / "ckpt"),
                     checkpoint_every=NEVER, max_retries=0))

    def sync(s):
        jax.device_get(s.time)

    # ------------------------------------------------------------ warm-up
    st = runner.run(stk, 1, start_step=0, resume=False)
    sync(st)
    for k in range(1, N_WARMUP):
        st = runner.run(st, k + 1, start_step=k, resume=False)
        sync(st)
    held = [st]
    del stk, st
    st, w = harness.window(runner, held, opt, tdir, compiles, sync)

    mems = [d.memory_stats() or {} for d in devs]
    for i, mem in enumerate(mems):
        say(phase="memory", device=i, **{k: mem[k] for k in sorted(mem)})
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)
                          + m.get("peak_bytes_reserved", 0)) for m in mems)
    first, last = kept.first, kept.last
    del st, runner, kept
    numbers, sound = {}, first is not None
    if sound:
        step1, last = prog.gather(first), prog.gather(last)
        del first
        sound = finite(step1) and finite(last)
        numbers.update(harness.drift(diagnostics(case, step1),
                                     diagnostics(case, last)))
        say(phase="drift", steps=min(w.steps + N_WARMUP, 1 + DRIFT_STEPS) - 1,
            finite=sound)
        step1 = harness.fields(step1)
    del last, prog, do, compiled, step, factory

    # ------------------------------------------------------------ check
    t0 = time.perf_counter()
    start, ref1 = Reference(conf, devs).step1(fields0)
    say(phase="reference", seconds=time.perf_counter() - t0,
        device=f"{len(devs)} x {devs[0].device_kind}")
    if sound:
        numbers.update(harness.deviation(step1, ref1, start))
    return harness.finish(opt, cell, devs, numbers, w, memory_peak, tdir,
                          sound=sound)
