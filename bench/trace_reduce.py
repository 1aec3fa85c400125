"""From a profiler trace of the window to what the per-layer readers read.

``reduce(trace_dir)`` reads the one ``.xplane.pb`` under ``trace_dir`` with
``jax.profiler.ProfileData`` and keeps:

- the device's op events (the ``XLA Ops`` line of each ``/device:TPU:<n>``
  plane).  A TPU trace names each event by its HLO instruction and carries
  no name scope, so the scope path comes from the compiled program's
  metadata (``op_name``), which the harness writes beside the trace
  (``scopes.json``, from ``hlo_scopes``).  An op that encloses others
  (a while loop around its body) counts only its own time;
- the benchmark's own host spans (``bench.*``), which fix the window;
- the busy time (union of op intervals inside the window, averaged over the
  chips) and the idle gaps between them, each labelled by the innermost host
  span that covers it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import re
from pathlib import Path
from typing import Callable, Iterator, List, Optional


SCOPES_FILE = "scopes.json"
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?op_name="([^"]*)"')


def hlo_scopes(hlo_text: str) -> dict:
    """HLO instruction name -> ``op_name`` metadata (the name-scope path)
    of every instruction of a compiled program's text that has one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def op_name(event_name: str) -> str:
    """"%fusion.12 = f32[...] fusion(...)" -> "fusion.12"."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """A host span in any active profiler trace (free when none is)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


@dataclasses.dataclass(frozen=True)
class Op:
    name: str           # HLO op name, e.g. "fusion.123"
    scope: str          # name-scope path, "" where the program gives none
    start_ns: float
    dur_ns: float       # its own time: nested ops' time taken out
    device: int
    kernel: bool        # a Pallas kernel (a TPU custom call)
    end_ns: float       # its end, nested ops' time kept in


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]
    window_s: float
    busy_s: float
    n_devices: int
    gaps: List[tuple]   # (seconds, host span name), longest first

    def op_seconds(self, pred: Callable[[Op], bool]) -> float:
        """Device seconds of the ops ``pred`` selects, averaged over the
        chips."""
        return sum(o.dur_ns for o in self.ops if pred(o)) * 1e-9 / max(
            self.n_devices, 1)

    def under(self, scopes) -> float:
        """Device seconds of the ops under any of ``scopes`` (whole
        components of the scope path), averaged over the chips."""
        want = set(scopes)
        return self.op_seconds(
            lambda o: not want.isdisjoint(o.scope.split("/")))

    def breakdown(self, n: int = 10) -> dict:
        by = {}
        for o in self.ops:
            # "fusion.12 [stage.flux_prediction/.../gather]": the op and the
            # innermost scopes the program gives it
            key = f"{o.name} [{'/'.join(o.scope.split('/')[-3:])}]"
            by[key] = by.get(key, 0.0) + o.dur_ns * 1e-9
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v / max(self.n_devices, 1)]
                               for k, v in top],
                "idle_gaps": [[name, s] for s, name in self.gaps[:n]]}


@dataclasses.dataclass
class Context:
    """What a per-layer reader may use besides the trace."""
    conf: dict                      # the configuration file
    steps: int                      # whole steps in the traced window
    wall_s: float                   # host wall time of those steps
    kind: str                       # device_kind
    peaks: Callable[[], dict]       # bench/peaks.json entry; raises if none
    chips: int = 1                  # devices the cell's step runs on
    notes: List[dict] = dataclasses.field(default_factory=list)


def _stat(ev, key) -> Optional[str]:
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals) -> List[tuple]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _self_times(events):
    """(event, own ns) in start order: an event's time less that of the
    events nested in it on the same line."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [e[2] for e in evs]
    stack = []          # indices of the enclosing events
    for i, (_, s, d) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return [(e, max(o, 0.0)) for e, o in zip(evs, own)]


def reduce(trace_dir: Path, window_span: str = "bench.window") -> Trace:
    from jax.profiler import ProfileData
    trace_dir = Path(trace_dir)
    pd = ProfileData.from_file(str(xplane(trace_dir)))
    sf = trace_dir / SCOPES_FILE
    scopes = json.loads(sf.read_text()) if sf.is_file() else {}
    spans, raw, n_dev = [], [], 0
    for plane in pd.planes:
        pre = "/device:TPU:"
        if plane.name.startswith(pre) and plane.name[len(pre):].isdigit():
            dev = int(plane.name[len(pre):])
            n_dev += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    raw.append((dev, [(ev.name, ev.start_ns, ev.duration_ns)
                                      for ev in line.events]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append(Span(ev.name, ev.start_ns,
                                          ev.duration_ns))
    win = [s for s in spans if s.name == window_span]
    if not win:
        raise ValueError(f"no {window_span} span in the trace")
    w0, w1 = win[0].start_ns, win[0].start_ns + win[0].dur_ns
    ops, busy_ns, gaps = [], 0.0, []
    inner = sorted((s for s in spans if s.name != window_span),
                   key=lambda s: s.start_ns)
    for dev, events in raw:
        events = [e for e in events if w0 <= e[1] < w1]
        for (name, start, dur), own in _self_times(events):
            short = op_name(name)
            ops.append(Op(short, scopes.get(short, ""), start, own, dev,
                          "tpu_custom_call" in name, start + dur))
        iv = _union((s, min(s + d, w1)) for _, s, d in events)
        busy_ns += sum(e - s for s, e in iv)
        edges = [w0] + [x for se in iv for x in se] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append(((e - s) * 1e-9, _label(inner, (s + e) / 2)))
    gaps.sort(key=lambda g: -g[0])
    return Trace(ops=ops, spans=spans, window_s=(w1 - w0) * 1e-9,
                 busy_s=busy_ns * 1e-9 / max(n_dev, 1), n_devices=n_dev,
                 gaps=gaps)


def _label(spans: List[Span], t: float) -> str:
    """The innermost benchmark span covering time ``t``, or where none does,
    the window itself (between the runner's calls)."""
    best = None
    for s in spans:
        if s.start_ns <= t < s.start_ns + s.dur_ns:
            if best is None or s.dur_ns < best.dur_ns:
                best = s
    return best.name if best else "bench.window"
