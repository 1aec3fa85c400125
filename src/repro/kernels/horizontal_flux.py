"""Pallas TPU kernel: fused lateral advective-flux term (paper §2.2).

The hottest horizontal-RHS term is the lateral upwind advective flux: the
interior/exterior field states at the 12 lateral quadrature points of each
prism (2 zeta-Gauss x 3 edges x 2 edge-Gauss), an upwind select against the
signed normal flux speed, and the scatter of speed * f_up back onto the 6
prism nodes.  The seed path materialises every intermediate — the
(k, nl, 2qz, 3, 2qs, nt) qp arrays are 12x the field size — between XLA
ops; SLIM's CUDA kernel never leaves registers (Klöckner et al.: fusing the
face-gather with the flux evaluation is the decisive optimisation).

On TPU one *lane* per prism column does the same in cell layout.  The
irregular part — the neighbour gather — is done *outside* by XLA at nodal
size: a TPU lane cannot gather from arbitrary other lanes, so the gather
crosses HBM once at (3 edge x 2 node) nodal width (with boundary fixups
already applied nodally; they are linear, see core/dg3d.py) instead of the
12-qp width.  Everything downstream — vertical zeta-interp, edge s-interp,
upwind select, speed multiply, weighted edge scatter with the vertical
test-function split — is fused here, with the interpolation constants baked
in as trace-time scalars and the (3, BC) accumulators living in VREGs
across the unrolled edge/qp/zeta loops.

Layouts (C = lane axis = prism columns; rows follow core/layout.py):
  f     (nl*6, C)    nodal field, row = layer*6 + node
  fext  (nl*12, C)   neighbour nodal values, row = l*12 + e*4 + j*2 + v
                     (e: edge, j: facing my node a|b, v: top|bottom face)
  speed (nl*12, C)   signed normal flux speed, row = l*12 + z*6 + e*2 + q
                     (z: zeta-Gauss level, q: edge-Gauss point)
  wq    (6, C)       edge quadrature weights edge_len * W_GAUSS, row = e*2+q
  out   (nl*6, C)    assembled lateral term  <<phi f_up speed Jl>>

Ragged C is zero-padded to the 128-lane cell width and sliced back: the
term is purely multiplicative (speed 0 in pad lanes -> contribution 0), so
zero padding is the identity here — the counterpart of the identity-block
scheme in column_solve.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import dispatch
from ..core import geometry as _G

# trace-time interpolation constants (exact f64 python floats)
_EDGE_A = [int(a) for a in _G.EDGE_A]
_EDGE_B = [int(b) for b in _G.EDGE_B]
_PHIA = [float(1.0 - s) for s in _G.S_GAUSS]   # node-a basis at the 2 edge qps
_PHIB = [float(s) for s in _G.S_GAUSS]
_PZ = [[float(_G.PHI_ZQ[z, v]) for v in range(2)] for z in range(2)]


def _lateral_flux_kernel(f_ref, fe_ref, sp_ref, wq_ref, out_ref):
    nl = f_ref.shape[0] // 6
    wq = wq_ref[...]                                   # (6, BC)

    def body(l, carry):
        base = l * 6
        ft = f_ref[pl.dslice(base, 3), :]              # (3, BC) top-face nodal
        fb = f_ref[pl.dslice(base + 3, 3), :]          # bottom-face nodal
        ext = fe_ref[pl.dslice(l * 12, 12), :]         # (12, BC)
        spd = sp_ref[pl.dslice(l * 12, 12), :]         # (12, BC)
        # three lane-vectors per face, stacked once before the store (Mosaic
        # has no scatter, so no `.at[]` update may appear here)
        acc_t = [jnp.zeros_like(ft[0]) for _ in range(3)]
        acc_b = [jnp.zeros_like(fb[0]) for _ in range(3)]
        for e in range(3):
            na, nb = _EDGE_A[e], _EDGE_B[e]
            for z in range(2):
                pzt, pzb = _PZ[z]
                # zeta-interp to the Gauss level: interior at my nodes a/b,
                # exterior from the pre-gathered neighbour values
                fi_a = pzt * ft[na] + pzb * fb[na]
                fi_b = pzt * ft[nb] + pzb * fb[nb]
                fe_a = pzt * ext[e * 4 + 0] + pzb * ext[e * 4 + 1]
                fe_b = pzt * ext[e * 4 + 2] + pzb * ext[e * 4 + 3]
                for q in range(2):
                    fi = _PHIA[q] * fi_a + _PHIB[q] * fi_b
                    fe = _PHIA[q] * fe_a + _PHIB[q] * fe_b
                    sp = spd[(z * 3 + e) * 2 + q]
                    g = jnp.where(sp > 0, fi, fe) * sp * wq[e * 2 + q]
                    ca = _PHIA[q] * g                  # node-a test function
                    cb = _PHIB[q] * g
                    acc_t[na] = acc_t[na] + pzt * ca
                    acc_t[nb] = acc_t[nb] + pzt * cb
                    acc_b[na] = acc_b[na] + pzb * ca
                    acc_b[nb] = acc_b[nb] + pzb * cb
        out_ref[pl.dslice(base, 3), :] = jnp.stack(acc_t)
        out_ref[pl.dslice(base + 3, 3), :] = jnp.stack(acc_b)
        return carry

    jax.lax.fori_loop(0, nl, body, 0)


@functools.partial(jax.jit, static_argnames=("block_cols", "interpret"))
def lateral_flux_cell(f: jax.Array, fext: jax.Array, speed: jax.Array,
                      wq: jax.Array, block_cols: int = 128,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Fused lateral advective term in cell layout (shapes in the module
    docstring).  C need not be a multiple of block_cols; zero-padded lanes
    contribute 0 and are sliced back off.  interpret=None auto-selects:
    compiled on TPU, interpreted elsewhere."""
    if interpret is None:
        interpret = dispatch.interpret_default()
    from ..core.layout import pad_nt
    rows, C = f.shape
    nl = rows // 6
    pad = (-C) % block_cols
    if pad:
        f = pad_nt(f, block_cols)
        fext = pad_nt(fext, block_cols)
        speed = pad_nt(speed, block_cols)
        wq = pad_nt(wq, block_cols)
    Cp = C + pad
    grid = (Cp // block_cols,)
    out = pl.pallas_call(
        _lateral_flux_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, block_cols), lambda i: (0, i)),
                  pl.BlockSpec((nl * 12, block_cols), lambda i: (0, i)),
                  pl.BlockSpec((nl * 12, block_cols), lambda i: (0, i)),
                  pl.BlockSpec((6, block_cols), lambda i: (0, i))],
        out_specs=pl.BlockSpec((rows, block_cols), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows, Cp), f.dtype),
        interpret=interpret,
    )(f, fext, speed, wq)
    return out[:, :C] if pad else out
