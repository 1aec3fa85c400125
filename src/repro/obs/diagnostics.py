"""On-device physics monitors: a ``Diagnostics`` pytree computed inside the
jitted step at near-zero cost, and a host-side ``MonitorPolicy``.

The monitors are the quantities that tell you a run has gone physically
wrong *before* the output does (paper §4: the headline numbers are only
meaningful for physically sane runs):

  * total water volume  ∫ H dA          (exactly conserved in a closed basin)
  * tracer masses       ∫ T dV, ∫ S dV  (conserved to roundoff by the scheme)
  * tracer min/max      (DG advection of a tracer must stay inside the
                         initial bounds up to the diffusion terms)
  * max |eta|, max horizontal speed
  * external-mode wave CFL  (|u| + sqrt(gH)) * dt_2d / h   per element
  * a non-finite flag WITH localisation: the first offending field and the
    2D cell (triangle) it occurs in — argmax on device, so a NaN report
    costs two int32 scalars, not a host readback of the state.

All reductions are O(state) elementwise work fused into the step by XLA —
measured overhead on the CPU fused path is well under the 3%% budget.

Host-side, ``MonitorPolicy.check`` turns a Diagnostics into violation
events: warn, halt (raise ``MonitorHalt`` — which
``runtime/fault_tolerance.py`` treats as a step failure and answers with
restore-and-retry), or silent collection, and mirrors everything into the
metrics registry.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import geometry as G
from ..core import stepper, vertical
from ..core.extrusion import VGrid, layer_geometry

# localisation priority: first listed field wins when several go bad at once
FIELDS = ("eta", "qx", "qy", "ux", "uy", "T", "S", "turb_k", "turb_eps")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Diagnostics:
    """Scalar physics monitors for one model state (all on-device)."""
    time: jax.Array         # model time [s]
    volume: jax.Array       # total water volume ∫ H dA [m^3]
    mass_T: jax.Array       # ∫ T dV (tracer content)
    mass_S: jax.Array
    T_min: jax.Array
    T_max: jax.Array
    S_min: jax.Array
    S_max: jax.Array
    eta_max: jax.Array      # max |eta| [m]
    speed_max: jax.Array    # max horizontal |u| [m/s]
    cfl_2d: jax.Array       # max external-mode wave CFL over elements
    nonfinite: jax.Array    # bool: any NaN/Inf in the prognostic state
    bad_field: jax.Array    # int32 index into FIELDS (-1 if finite)
    bad_cell: jax.Array     # int32 triangle index (-1 if finite)


def _colwise_nonfinite(x: jax.Array) -> jax.Array:
    """(…, nt) -> (nt,) bool: any non-finite entry in each cell column."""
    bad = ~jnp.isfinite(x)
    return bad.reshape(-1, x.shape[-1]).any(axis=0)


def compute(geom: G.Geom2D, vg: VGrid, cfg: stepper.OceanConfig,
            st: stepper.OceanState) -> Diagnostics:
    """Pure-jnp monitor bundle; call inside jit right after the step."""
    vge = layer_geometry(vg, st.ext.eta, cfg.h_min)

    # conservation integrals: ∫ of a P1 field over a triangle is
    # area * mean(vertex values); tracer content uses the same 3D mass
    # matrix the stepper conserves with, contracted in full f32 on a TPU
    # too, whatever the step's own precision
    volume = (geom.area * vge.H.mean(axis=0)).sum()
    with jax.default_matmul_precision("highest"):
        mass_T = vertical.mass_apply3d(geom, vge.jz, st.T).sum()
        mass_S = vertical.mass_apply3d(geom, vge.jz, st.S).sum()

    speed2 = st.ux ** 2 + st.uy ** 2
    speed_max = jnp.sqrt(speed2.max())

    # external-mode wave CFL per element: the 2D burst runs m_2d substeps
    # per internal dt, element length scale h = 2 area / longest edge
    dt2d = cfg.dt / max(cfg.m_2d, 1)
    h = 2.0 * geom.area / geom.edge_len.max(axis=0)
    c = jnp.sqrt(G.G_GRAV * vge.H.max(axis=0))
    umax_el = jnp.sqrt(speed2.reshape(-1, geom.nt).max(axis=0))
    cfl_2d = ((c + umax_el) * dt2d / h).max()

    # non-finite localisation: stack per-cell badness of every prognostic
    # field; row-major argmax -> (first bad field, first bad cell in it)
    fields = dict(eta=st.ext.eta, qx=st.ext.qx, qy=st.ext.qy,
                  ux=st.ux, uy=st.uy, T=st.T, S=st.S,
                  turb_k=st.turb_k, turb_eps=st.turb_eps)
    bad = jnp.stack([_colwise_nonfinite(fields[f]) for f in FIELDS])
    any_bad = bad.any()
    idx = jnp.argmax(bad.reshape(-1)).astype(jnp.int32)
    nt = geom.nt
    bad_field = jnp.where(any_bad, idx // nt, jnp.int32(-1))
    bad_cell = jnp.where(any_bad, idx % nt, jnp.int32(-1))

    return Diagnostics(
        time=st.time, volume=volume, mass_T=mass_T, mass_S=mass_S,
        T_min=st.T.min(), T_max=st.T.max(),
        S_min=st.S.min(), S_max=st.S.max(),
        eta_max=jnp.abs(st.ext.eta).max(), speed_max=speed_max,
        cfl_2d=cfl_2d, nonfinite=any_bad, bad_field=bad_field,
        bad_cell=bad_cell)


def step_with_diagnostics(geom: G.Geom2D, vg: VGrid,
                          cfg: stepper.OceanConfig, st: stepper.OceanState,
                          forcing: Optional[stepper.Forcing3D] = None,
                          **kw) -> Tuple[stepper.OceanState, Diagnostics]:
    """One stepper.step + the monitor bundle of the NEW state, in one jit
    region — the diagnostics fuse into the step's epilogue."""
    if forcing is None:
        forcing = stepper.Forcing3D()
    st1 = stepper.step(geom, vg, cfg, st, forcing, **kw)
    with jax.named_scope("obs.diagnostics"):
        diag = compute(geom, vg, cfg, st1)
    return st1, diag


def to_dict(diag: Diagnostics) -> Dict[str, Any]:
    """Host-side python scalars (one device sync for the whole bundle)."""
    leaves = jax.device_get(diag)
    out: Dict[str, Any] = {}
    for f in dataclasses.fields(Diagnostics):
        v = getattr(leaves, f.name)
        if f.name == "nonfinite":
            out[f.name] = bool(v)
        elif f.name in ("bad_field", "bad_cell"):
            out[f.name] = int(v)
        else:
            out[f.name] = float(v)
    bf = out["bad_field"]
    out["bad_field_name"] = FIELDS[bf] if 0 <= bf < len(FIELDS) else None
    return out


class MonitorHalt(RuntimeError):
    """Raised by MonitorPolicy(on_violation='halt'); carries the diagnostics
    dict so fault handling can log/act on the physics reason."""

    def __init__(self, violations: List[dict], diag: Dict[str, Any]):
        self.violations = violations
        self.diagnostics = diag
        super().__init__("physics monitor violation: " + "; ".join(
            v["rule"] + (f" ({v['detail']})" if v.get("detail") else "")
            for v in violations))


@dataclasses.dataclass
class MonitorPolicy:
    """Host-side thresholds + what to do when one trips.

    ``on_violation``: "warn" (warnings.warn, keep running), "halt" (raise
    MonitorHalt — the fault-tolerance runner restores a checkpoint and
    retries), or "silent" (collect only, caller inspects the return).
    Conservation drift limits are relative to the reference values captured
    on the FIRST check (or set explicitly via ``reference``)."""
    cfl_max: Optional[float] = 1.0
    eta_max: Optional[float] = None          # [m]
    speed_max: Optional[float] = None        # [m/s]
    tracer_bounds: Optional[Dict[str, Tuple[float, float]]] = None
    volume_drift_max: Optional[float] = None     # relative
    mass_drift_max: Optional[float] = None       # relative, T and S
    on_violation: str = "warn"
    reference: Optional[Dict[str, float]] = None

    def check(self, diag, step: Optional[int] = None,
              registry=None) -> List[dict]:
        """Evaluate all configured rules; emit events; warn/halt per policy.

        ``diag`` is a Diagnostics pytree or an already-converted dict."""
        d = diag if isinstance(diag, dict) else to_dict(diag)
        if self.reference is None:
            self.reference = {k: d[k] for k in ("volume", "mass_T", "mass_S")}
        v: List[dict] = []

        def rule(name, value, limit, detail=""):
            v.append(dict(rule=name, value=value, limit=limit, detail=detail))

        if d["nonfinite"]:
            rule("nonfinite", 1.0, 0.0,
                 f"field={d['bad_field_name']} cell={d['bad_cell']}")
        if self.cfl_max is not None and d["cfl_2d"] > self.cfl_max:
            rule("cfl_2d", d["cfl_2d"], self.cfl_max)
        if self.eta_max is not None and d["eta_max"] > self.eta_max:
            rule("eta_max", d["eta_max"], self.eta_max)
        if self.speed_max is not None and d["speed_max"] > self.speed_max:
            rule("speed_max", d["speed_max"], self.speed_max)
        for tr, (lo, hi) in (self.tracer_bounds or {}).items():
            if d[f"{tr}_min"] < lo:
                rule(f"{tr}_min", d[f"{tr}_min"], lo, "monotonicity floor")
            if d[f"{tr}_max"] > hi:
                rule(f"{tr}_max", d[f"{tr}_max"], hi, "monotonicity ceiling")
        for key, lim in (("volume", self.volume_drift_max),
                         ("mass_T", self.mass_drift_max),
                         ("mass_S", self.mass_drift_max)):
            if lim is None:
                continue
            ref = self.reference[key]
            drift = abs(d[key] - ref) / max(abs(ref), 1e-30)
            if drift > lim:
                rule(f"{key}_drift", drift, lim)

        if registry is not None:
            registry.diagnostics("physics", d, step=step)
            for viol in v:
                registry.event("monitor.violation", viol, step=step)
        if v:
            if self.on_violation == "halt":
                raise MonitorHalt(v, d)
            if self.on_violation == "warn":
                warnings.warn(
                    "physics monitor violation(s): "
                    + "; ".join(f"{x['rule']}={x['value']:.4g} "
                                f"(limit {x['limit']:.4g})" for x in v),
                    RuntimeWarning, stacklevel=2)
        return v
