"""Fault-tolerant simulation campaign launcher (and chaos-test harness).

Runs the standing-wave ocean case through ``SimulationRunner``: compiled
``step_with_diagnostics`` steps, a halt-mode ``MonitorPolicy``, periodic
verified checkpoints, and the graceful-degradation dt ladder.  With
``--fault`` specs (``kind@site[:k=v,...]``, see ``runtime/chaos.py``) the
same campaign runs under a seeded ``FaultPlan`` — the reproduce-a-recovery
entry point documented in README "Resilience":

  PYTHONPATH=src python -m repro.launch.sim_campaign --steps 12 \
      --ckpt-every 3 --fault poison_nan@sim.state:step=7,field=T

The builders here are the single source of the tiny campaign case used by
``scripts/chaos_smoke.py`` and ``tests/test_chaos.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..core import dg2d, geometry, mesh2d, stepper
from ..core.extrusion import VGrid
from ..obs import diagnostics as obs_diag
from ..obs import metrics as obs_metrics
from ..runtime import chaos
from ..runtime.fault_tolerance import (LadderConfig, RunnerConfig,
                                       SimulationRunner)
from . import compile_cache
from .ocean_dryrun import OceanCell, cell_setup


@dataclasses.dataclass(frozen=True)
class Case:
    geom: object
    vg: VGrid
    cfg: stepper.OceanConfig
    state: stepper.OceanState


def build_case(nx: int = 6, ny: int = 5, lx: float = 2000.0,
               ly: float = 1500.0, depth: float = 20.0, nl: int = 4,
               dt: float = 5.0, m_2d: int = 6, amp: float = 0.05,
               dtype=jnp.float64, seed: int = 3,
               cell: Optional[OceanCell] = None) -> Case:
    """Standing-wave case over a temperature front.

    Without ``cell``: the tiny flat-bottom obs-smoke configuration built
    from the other arguments.  With ``cell`` (an ``OCEAN_CELLS`` entry or a
    ``chip_share`` of one): that cell's mesh, bathymetry and OceanConfig,
    and ``nx`` .. ``m_2d`` and ``seed`` are ignored.  Either way the
    surface carries a basin-scale standing wave of amplitude ``amp`` and
    the temperature a 4 K front across the middle of the basin, so the
    external mode, advection, the EOS, the pressure gradient and GLS all
    have work from the first step."""
    if cell is None:
        m = mesh2d.rect_mesh(nx, ny, lx, ly, jitter=0.2, seed=seed)
        geom = geometry.geom2d_from_mesh(m, dtype=dtype)
        cfg = stepper.OceanConfig(dt=dt, nl=nl, m_2d=m_2d)
        b = jnp.full((3, m.nt), depth, dtype)
    else:
        _, geom, b, cfg = cell_setup(cell, dtype)
        lx = cell.lx
    vg = VGrid(b=jnp.asarray(b), nl=cfg.nl)
    st = stepper.init_state(geom, vg, dtype=dtype)
    eta = (amp * jnp.cos(jnp.pi * geom.node_x / lx)).astype(dtype)
    front = (10.0 + 4.0 * jnp.tanh((0.5 * lx - geom.node_x)
                                   / (0.1 * lx))).astype(dtype)
    T = jnp.broadcast_to(jnp.concatenate([front, front])[None], st.T.shape)
    st = dataclasses.replace(st, ext=dg2d.State2D(eta, st.ext.qx, st.ext.qy),
                             T=T)
    return Case(geom=geom, vg=vg, cfg=cfg, state=st)


# (geom, vg, cfg, state) -> (state, Diagnostics); the geometry is an
# argument, not a constant baked into the program, and each OceanConfig
# (each dt-ladder rung) compiles once
step_program = jax.jit(obs_diag.step_with_diagnostics, static_argnums=2)


def make_step_factory(case: Case) -> Callable:
    """step_factory for SimulationRunner: cfg -> jitted
    ``state -> (state, Diagnostics)`` (dt-ladder rungs recompile here)."""
    def factory(cfg: stepper.OceanConfig):
        return lambda s: step_program(case.geom, case.vg, cfg, s)
    return factory


def default_policy(cfl_max: float = 1.0):
    return obs_diag.MonitorPolicy(cfl_max=cfl_max, on_violation="halt")


def run_campaign(case: Case, n_steps: int, runner_cfg: RunnerConfig,
                 ladder: Optional[LadderConfig] = None,
                 policy=None, plan: Optional[chaos.FaultPlan] = None,
                 resume: bool = True):
    """One campaign leg; returns (final_state, runner).  A preempted leg
    returns early with a blocking checkpoint on disk — rerun with
    ``resume=True`` to finish (what the scheduler does after SIGTERM)."""
    runner = SimulationRunner(make_step_factory(case), case.cfg, runner_cfg,
                              policy=policy, ladder=ladder)
    ctx = chaos.active(plan) if plan is not None else _null_ctx()
    with ctx:
        out = runner.run(case.state, n_steps, resume=resume)
    return out, runner


class _null_ctx:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--nx", type=int, default=6)
    ap.add_argument("--ny", type=int, default=5)
    ap.add_argument("--nl", type=int, default=4)
    ap.add_argument("--dt", type=float, default=5.0)
    ap.add_argument("--ckpt", default="checkpoints/sim")
    ap.add_argument("--ckpt-every", type=int, default=3)
    ap.add_argument("--max-retries", type=int, default=3)
    ap.add_argument("--cfl-max", type=float, default=1.0)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--fault", action="append", default=[],
                    help="chaos spec kind@site[:k=v,...] (repeatable)")
    ap.add_argument("--seed", type=int, default=0, help="FaultPlan seed")
    ap.add_argument("--metrics", default=None,
                    help="JSONL metrics sink path")
    ap.add_argument("--dtype", choices=("float32", "float64"),
                    default="float32",
                    help="float32 is the TPU path; float64 turns on x64 "
                         "(CPU only: Mosaic takes no f64)")
    args = ap.parse_args(argv)

    compile_cache.configure()
    if args.dtype == "float64":
        jax.config.update("jax_enable_x64", True)
    if args.metrics:
        obs_metrics.configure(args.metrics)

    case = build_case(nx=args.nx, ny=args.ny, nl=args.nl, dt=args.dt,
                      dtype=jnp.dtype(args.dtype))
    runner_cfg = RunnerConfig(checkpoint_dir=args.ckpt,
                              checkpoint_every=args.ckpt_every,
                              max_retries=args.max_retries,
                              backoff_base_s=0.01)
    plan = (chaos.plan_from_specs(args.fault, seed=args.seed)
            if args.fault else None)
    st, runner = run_campaign(case, args.steps, runner_cfg,
                              policy=default_policy(args.cfl_max),
                              plan=plan, resume=not args.no_resume)
    print(f"steps={runner.stats['steps']} retries={runner.stats['retries']} "
          f"cold_restores={runner.stats['cold_restores']} "
          f"ladder={runner.stats['ladder_transitions']} "
          f"preempted={runner.stats['preempted']} "
          f"t={float(st.time):.1f}s")
    if plan is not None:
        for rec in plan.log:
            print(f"chaos fired: {rec}")
    if args.metrics:
        obs_metrics.default().flush(step=args.steps)
        obs_metrics.default().close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
