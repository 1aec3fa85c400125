"""Staged tracing helpers: host spans for profiler traces + an opt-in
``jax.profiler`` trace session that lands in a run directory.

``span(name)`` marks a stretch of host time in any active profiler trace,
on the clock of the device ops (``jax.profiler.TraceAnnotation``; with
``step`` a ``StepTraceAnnotation``, by which xprof groups the device ops of
a step).  It costs next to nothing when no trace is active.  Traced library
code names its ops with bare ``jax.named_scope`` instead — zero runtime
cost, pure trace-time metadata in the compiled HLO.

``trace_session`` wraps ``jax.profiler.start_trace/stop_trace``; it is
opt-in: enabled explicitly, or via the ``REPRO_TRACE=1`` environment
variable (run directory override: ``REPRO_RUN_DIR``).  Profiles land in
``<run_dir>/plugins/profile/...`` — point TensorBoard or xprof at the run
directory.
"""
from __future__ import annotations

import contextlib
import datetime
import os
from typing import Iterator, Optional

import jax

ENV_TRACE = "REPRO_TRACE"
ENV_RUN_DIR = "REPRO_RUN_DIR"
DEFAULT_RUNS_ROOT = "runs"


def trace_enabled() -> bool:
    return os.environ.get(ENV_TRACE, "0") not in ("", "0", "false", "False")


def default_run_dir(prefix: str = "trace") -> str:
    env = os.environ.get(ENV_RUN_DIR)
    if env:
        return env
    stamp = datetime.datetime.now().strftime("%Y%m%d-%H%M%S")
    return os.path.join(DEFAULT_RUNS_ROOT, f"{prefix}-{stamp}-{os.getpid()}")


def span(name: str, step: Optional[int] = None):
    """A host span ``name`` in any active profiler trace; with ``step``, the
    span of that step (``step_num``).  Host code only: inside traced code it
    would time the tracing, not the ops."""
    if step is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


@contextlib.contextmanager
def trace_session(run_dir: Optional[str] = None,
                  enabled: Optional[bool] = None) -> Iterator[Optional[str]]:
    """Opt-in profiler trace over the enclosed block.

    Yields the run directory when tracing is active, else None.  ``enabled``
    defaults to the REPRO_TRACE environment toggle, so harnesses can wrap
    their hot section unconditionally and let the environment decide."""
    if enabled is None:
        enabled = trace_enabled()
    if not enabled:
        yield None
        return
    run_dir = run_dir or default_run_dir()
    os.makedirs(run_dir, exist_ok=True)
    jax.profiler.start_trace(run_dir)
    try:
        yield run_dir
    finally:
        jax.profiler.stop_trace()
