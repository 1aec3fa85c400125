"""Pallas TPU kernel: block-tridiagonal (6x6 blocks) column solver.

Paper §2.4: the vertically-implicit momentum/tracer systems couple each
prism's 6 nodes to the prisms above and below.  SLIM assigns one CUDA thread
per column and runs banded Gaussian elimination with a 36-scalar register
buffer.  On TPU one *lane* per column does the same: every 6x6 block entry is
a (BC,)-wide vector, the block-Thomas recurrence

    S_l = D_l - L_l C_{l-1};  C_l = S_l^{-1} U_l;  y_l = S_l^{-1}(b_l - L_l y_{l-1})
    x_{nl-1} = y_{nl-1};      x_l = y_l - C_l x_{l+1}

is swept over layers with the 6x6 elimination fully unrolled (no pivoting —
the operators are strictly diagonally dominant mass + dissipation blocks,
like the paper's).  C_l and y_l are staged in VMEM scratch for the backward
sweep; the 36-entry 'register buffer' of the paper becomes 36 lane-vectors
live in VREGs inside the unrolled elimination.

VMEM budget per grid step (nl=32, k=2, BC=128, f32):
  blocks 3*32*36*128*4 = 2.3 MB, rhs/x 32*12*128*4 = 0.2 MB,
  scratch C 2.3 MB + y 0.2 MB  ->  ~5 MB: fits; BC=256 does not. The §Perf
  sweep therefore fixes BC=128 for nl=32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch


def _mm6(A, B):
    """(6,6,BC) @ (6,m,BC) per-lane matmul, unrolled into f32 multiply-adds
    on the vector unit.  (An einsum here lowers to a matmul whose f32
    precision follows the caller's default: on a TPU, bf16 passes that put
    the solve 7e-4 off its oracle.)"""
    out = A[:, 0:1] * B[0:1]
    for k in range(1, 6):
        out = out + A[:, k:k + 1] * B[k:k + 1]
    return out


def _solve6(S, *rhs):
    """Per-lane solve of S x = r for each r in rhs via unrolled Gauss-Jordan
    elimination.

    S: (6, 6, BC); each r: (6, m, BC); returns one (6, m, BC) solution per
    r.  No pivoting (diagonally dominant).  The entries of S are held as 36
    separate (BC,) lane-vectors and the rows of each r as six (m, BC)
    values, stacked once at the end: Mosaic lowers neither a scatter (an
    `.at[]` update) nor a sublane-axis concatenate of the right-hand sides.
    Columns left of the pivot are already zero in the pivot row, so only
    columns right of it are updated (their updates would add exact zeros)."""
    s = [[S[r, c] for c in range(6)] for r in range(6)]
    xs = [[x[r] for r in range(6)] for x in rhs]
    for col in range(6):
        inv = 1.0 / s[col][col]
        s[col] = [s[col][j] * inv for j in range(6)]
        for x in xs:
            x[col] = x[col] * inv
        for r in range(6):
            if r == col:
                continue
            f = s[r][col]
            s[r] = [s[r][j] - f * s[col][j] if j > col else s[r][j]
                    for j in range(6)]
            for x in xs:
                x[r] = x[r] - f * x[col]
    return tuple(jnp.stack(x) for x in xs)


def _block_thomas_kernel(lo_ref, dg_ref, up_ref, b_ref, x_ref, C_ref):
    nl = dg_ref.shape[0]
    k = b_ref.shape[2]

    def fwd(l, carry):
        C_prev, y_prev = carry               # (6,6,BC), (6,k,BC)
        L = lo_ref[l]
        S = dg_ref[l] - _mm6(L, C_prev)
        C, y = _solve6(S, up_ref[l], b_ref[l] - _mm6(L, y_prev))
        C_ref[l] = C
        x_ref[l] = y                         # stash y; fixed in backward sweep
        return C, y

    z6 = jnp.zeros_like(dg_ref[0])
    zk = jnp.zeros_like(b_ref[0])
    jax.lax.fori_loop(0, nl, fwd, (z6, zk))

    def bwd(j, x_next):
        l = nl - 2 - j
        x = x_ref[l] - _mm6(C_ref[l], x_next)
        x_ref[l] = x
        return x

    jax.lax.fori_loop(0, nl - 1, bwd, x_ref[nl - 1])


@functools.partial(jax.jit, static_argnames=("block_cols", "interpret"))
def block_thomas_cell(lo: jax.Array, dg: jax.Array, up: jax.Array,
                      b: jax.Array, block_cols: int = 128,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Solve block-tridiagonal systems in cell layout.

    lo, dg, up: (nl, 6, 6, C); b: (nl, 6, k, C); returns x: (nl, 6, k, C).
    lo[0] and up[nl-1] are ignored (set to 0 by the assembler).

    C need not be a multiple of block_cols: ragged tails are padded with
    identity diagonal blocks and zero RHS (solution 0 in the pad lanes) and
    sliced back off.  interpret=None auto-selects: compiled on TPU,
    interpreted elsewhere."""
    if interpret is None:
        interpret = dispatch.interpret_default()
    from ..core.layout import pad_nt
    nl, _, _, C = dg.shape
    k = b.shape[2]
    pad = (-C) % block_cols
    if pad:
        lo = pad_nt(lo, block_cols)
        up = pad_nt(up, block_cols)
        b = pad_nt(b, block_cols)
        # pad columns get the identity system  I x = 0  so the unpivoted
        # elimination never divides by zero
        dg = pad_nt(dg, block_cols).at[:, :, :, C:].add(
            jnp.eye(6, dtype=dg.dtype)[None, :, :, None])
    Cp = C + pad
    grid = (Cp // block_cols,)
    bspec = pl.BlockSpec((nl, 6, 6, block_cols), lambda i: (0, 0, 0, i))
    rspec = pl.BlockSpec((nl, 6, k, block_cols), lambda i: (0, 0, 0, i))
    out = pl.pallas_call(
        _block_thomas_kernel,
        grid=grid,
        in_specs=[bspec, bspec, bspec, rspec],
        out_specs=rspec,
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        scratch_shapes=[pltpu.VMEM((nl, 6, 6, block_cols), dg.dtype)],
        interpret=interpret,
    )(lo, dg, up, b)
    return out[..., :C] if pad else out
