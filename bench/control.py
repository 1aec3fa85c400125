"""The readings the check's limits are set from (not run by benchmark runs).

    python3 bench/control.py --workload <name> --seeds 1,2,...,12 \
        --control-seeds 101,102,103

For each seed of ``--seeds``, the program's step (the window's compiled
``step_program``, as configured, or on several chips its shard-mapped
``DistributedOcean`` step) and the plain reference each take one step from
the same initial state, and the program takes ``harness.DRIFT_STEPS`` more
for the volume and tracer-mass drift: the readings of a sound run, from
which each limit's lower reading comes.  For each of ``--control-seeds`` the
control does the same: the program with its own lower-precision path switched
on (``OceanConfig.matmul_precision="high"``, three bf16 passes, where the
configuration states f32 at ``highest``), the upper reading.  Each reading is
judged against the cell's limits file as a run is (``harness.judge``).  One
JSON line per reading; the last line sums them up.  Everything runs in this
one process, on the chips the cell asks for (no CPU fallback); every program
reading is taken before the first reference step.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]

# the program's own lower-precision path: three bf16 passes where the
# configuration states f32 at "highest"
CONTROL_PRECISION = "high"


def _one_chip(conf: dict, devs, node_x, b):
    """(program, reference) readers of a one-chip cell: the window's
    ``step_program`` and the plain reference on the chip."""
    import jax.numpy as jnp
    from bench import harness, reference
    from repro.launch import sim_campaign
    from repro.obs import diagnostics as obs_diag
    ref_case = reference.build(conf, jnp.float32)
    ref_step = reference.step_fn(ref_case)

    def program(f0, precision):
        case, program_b = harness.program_case(conf, f0)
        harness.same_as_file(case.geom.node_x, program_b, node_x, b)
        cfg = case.cfg if precision is None else dataclasses.replace(
            case.cfg, matmul_precision=precision)
        st, diag0 = sim_campaign.step_program(case.geom, case.vg, cfg,
                                              case.state)
        got = harness.fields(st)
        for _ in range(harness.DRIFT_STEPS):
            st, diag = sim_campaign.step_program(case.geom, case.vg, cfg, st)
        return got, harness.drift(obs_diag.to_dict(diag0),
                                  obs_diag.to_dict(diag))

    def ref(f0):
        st0 = reference.initial_state(ref_case, f0)
        return harness.fields(st0), harness.fields(ref_step(st0))
    return program, ref


def _sharded(conf: dict, devs, node_x, b):
    """(program, reference) readers of a cell on several chips: the
    window's shard-mapped step and the reference over the same chips
    (``bench/sharded.py``).  The reference is built when first asked for,
    after the program's last reading has let its compiled steps go.  The
    control takes no drift steps: the drift's limits are the configuration's
    stated guarantees, and every step holds all the chips."""
    from bench import harness, sharded
    prog = sharded.Program(conf, devs)
    steps = {}

    def program(f0, precision):
        case, program_b = prog.case(f0)
        harness.same_as_file(case.geom.node_x, program_b, node_x, b)
        stk = prog.do.scatter_state(case.state)
        if precision not in steps:
            steps.clear()               # one compiled step on the chips
            steps[precision] = prog.step(prog.compile(stk, precision))
        step = steps[precision]
        first = st = step(stk)
        if precision is not None:
            return harness.fields(prog.gather(first)), {}
        for _ in range(harness.DRIFT_STEPS):
            st = step(st)
        first, st = prog.gather(first), prog.gather(st)
        return harness.fields(first), harness.drift(
            sharded.diagnostics(case, first), sharded.diagnostics(case, st))

    refs = []

    def ref(f0):
        if not refs:
            steps.clear()
            refs.append(sharded.Reference(conf, devs))
        return refs[0].step1(f0)
    return program, ref


def readings(workload: str, seeds, control_seeds, require_tpu: bool = True,
             repo: Path = REPO) -> dict:
    from bench import harness, reference, traffic

    cell = harness.load_cell(workload, repo)
    conf = cell.conf
    devs = harness.devices(cell.workload["chips"], require_tpu)
    node_x, node_y, b = reference.nodes(conf)
    program, ref = (_sharded if len(devs) > 1 else _one_chip)(
        conf, devs, node_x, b)
    runs = [(s, "sound") for s in seeds] + [(s, "control")
                                             for s in control_seeds]
    # every program reading first, then every reference step: the program
    # and the reference never hold the chips' memory together
    got = []
    for seed, kind in runs:
        f0 = traffic.initial_fields(cell.mix, conf, node_x, node_y, b, seed)
        t0 = time.perf_counter()
        got.append(program(f0, None if kind == "sound"
                           else CONTROL_PRECISION))
        got[-1] += (time.perf_counter() - t0,)
    out = {"sound": [], "control": []}
    for (seed, kind), (fields1, drift, t_prog) in zip(runs, got):
        f0 = traffic.initial_fields(cell.mix, conf, node_x, node_y, b, seed)
        t0 = time.perf_counter()
        start, want = ref(f0)
        numbers = {**harness.deviation(fields1, want, start), **drift}
        correct, _ = harness.judge(numbers, cell.limits)
        row = {"kind": kind, "seed": seed, "correct": correct,
               "program_s": t_prog, "reference_s": time.perf_counter() - t0,
               **numbers}
        print(json.dumps(row), flush=True)
        out[kind].append(row)
    keys = [k for k in out["sound"][0]
            if k not in ("kind", "seed", "correct", "program_s",
                         "reference_s")]
    summary = {"lower": {k: max(r[k] for r in out["sound"]) for k in keys},
               "sound_correct": [r["correct"] for r in out["sound"]]}
    if out["control"]:
        summary["upper"] = {k: min(r[k] for r in out["control"])
                            for k in keys if k in out["control"][0]}
        summary["control_correct"] = [r["correct"] for r in out["control"]]
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_cache")
    sys.path.insert(0, str(REPO / "src"))
    sys.path.insert(0, str(REPO))
    from repro.launch import compile_cache
    compile_cache.configure()
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    summary = readings(args.workload, ints(args.seeds),
                       ints(args.control_seeds))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
