"""Pure-jnp oracles for every Pallas kernel (the `ref.py` of each kernel).

Each function is numerically identical (up to fp reassociation) to its
kernel; tests sweep shapes/dtypes and assert allclose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def tridiag(dl, d, du, b):
    """Thomas solve, (nl, C) operands. See kernels/tridiag.py."""
    from ..core.turbulence import thomas_solve
    return thomas_solve(dl, d, du, b)


def solve_r_cell(F, area, r_surf):
    """Matrix-free D_vu solve in cell layout: F (nl*6, C), area (1, C)."""
    rows, C = F.shape
    nl = rows // 6
    Ff = F.reshape(nl, 6, C)
    inva = 12.0 / area
    def minv(face):
        # face (nl, 3, C): M_h^{-1} mixes the 3 nodes of each face
        return inva * (face - 0.25 * face.sum(axis=1, keepdims=True))
    gt = minv(Ff[:, 0:3, :])
    gb = minv(Ff[:, 3:6, :])
    s = jnp.cumsum(gt + gb, axis=0)
    rb = r_surf[None] - s
    rt = rb + 2.0 * gb
    return jnp.concatenate([rt, rb], axis=1).reshape(rows, C)


def solve_w_cell(F, area, w_floor):
    rows, C = F.shape
    nl = rows // 6
    Ff = F.reshape(nl, 6, C)
    inva = 12.0 / area
    def minv(face):
        return inva * (face - 0.25 * face.sum(axis=1, keepdims=True))
    gt = minv(Ff[:, 0:3, :])
    gb = minv(Ff[:, 3:6, :])
    s = jnp.flip(jnp.cumsum(jnp.flip(gt + gb, 0), axis=0), 0)
    wt = w_floor[None] + s
    wb = wt - 2.0 * gt
    return jnp.concatenate([wt, wb], axis=1).reshape(rows, C)


def block_thomas_cell(lo, dg, up, b):
    """Block-tridiagonal solve; shapes as kernels/column_solve.py."""
    from ..core.vertical import Blocks, block_thomas_solve
    # core solver wants (k, nl, 6, nt) rhs
    rhs = jnp.moveaxis(b, 2, 0)
    x = block_thomas_solve(Blocks(lo=lo, dg=dg, up=up), rhs)
    return jnp.moveaxis(x, 0, 2)


def lateral_flux_cell(f, fext, speed, wq):
    """Lateral advective-flux term; shapes as kernels/horizontal_flux.py.

    f (nl*6, C) nodal; fext (nl*12, C) neighbour nodal (e, a|b, top|bot);
    speed (nl*12, C) at lateral qps (qz, e, qs); wq (6, C) edge weights.
    Returns (nl*6, C): <<phi f_up speed Jl>> assembled on the 6 prism nodes.
    """
    import numpy as np
    from ..core import geometry as G
    rows, C = f.shape
    nl = rows // 6
    ff = f.reshape(nl, 2, 3, C)                   # (l, top|bot, node, C)
    ext = fext.reshape(nl, 3, 2, 2, C)            # (l, e, a|b, top|bot, C)
    sp = speed.reshape(nl, 2, 3, 2, C)            # (l, qz, e, qs, C)
    w = wq.reshape(3, 2, C)                       # (e, qs, C)
    # single-source quadrature constants from geometry.py
    PZ = jnp.asarray(np.asarray(G.PHI_ZQ))        # (2qz, 2[top,bot])
    pa, pb = G._PHIA, G._PHIB                     # (2qs,) edge basis at qps
    # node-scatter phi tensor = _EDGE_SCATTER without its W_GAUSS factor
    # (the Gauss weights live in wq here)
    P = jnp.asarray(G._EDGE_SCATTER / G.W_GAUSS[None, :, None])
    # zeta-interp to the 2 Gauss levels
    fzi = jnp.einsum("zv,lvnc->lznc", PZ, ff)      # interior nodal at qz
    fze = jnp.einsum("zv,lejvc->lzejc", PZ, ext)   # exterior per edge at qz
    # edge s-interp -> (l, qz, e, qs, C)
    with jax.named_scope("gather.edge_local"):
        fia = fzi[..., np.asarray(G.EDGE_A), :]
        fib = fzi[..., np.asarray(G.EDGE_B), :]
    fi = fia[..., None, :] * pa[:, None] + fib[..., None, :] * pb[:, None]
    fe = (fze[..., 0, :][..., None, :] * pa[:, None]
          + fze[..., 1, :][..., None, :] * pb[:, None])
    g = jnp.where(sp > 0, fi, fe) * sp * w[None, None]
    nodes = jnp.einsum("eqn,lzeqc->lznc", P, g)
    top = jnp.einsum("z,lznc->lnc", PZ[:, 0], nodes)
    bot = jnp.einsum("z,lznc->lnc", PZ[:, 1], nodes)
    return jnp.concatenate([top, bot], axis=1).reshape(rows, C)


def soa_to_cell(x):
    from ..core import layout
    nl, six, nt = x.shape
    return layout.soa_to_cell(x)


def cell_to_soa(x, nt):
    from ..core import layout
    nc, rows, c = x.shape
    return layout.cell_to_soa(x, rows // 6, 6, nt)


def wkv6(r, k, v, w, u):
    """RWKV6 recurrence via lax.scan: shapes as kernels/wkv6.py."""
    def one_head(r_h, k_h, v_h, w_h):
        def step(S, inp):
            rt, kt, vt, wt = inp
            kv = kt[:, None] * vt[None, :]
            out = (rt[:, None] * (S + u[:, None] * kv)).sum(axis=0)
            return wt[:, None] * S + kv, out
        S0 = jnp.zeros((r.shape[-1], v.shape[-1]), jnp.float32)
        _, out = jax.lax.scan(step, S0, (r_h, k_h, v_h, w_h))
        return out
    return jax.vmap(one_head)(r, k, v, w)


def attention(q, k, v, causal=True, window=None, softcap=None):
    """Dense reference attention: (BH, T, d)."""
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) / (d ** 0.5)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    Tq, Tk = q.shape[1], k.shape[1]
    q_ids = jnp.arange(Tq)[:, None]
    k_ids = jnp.arange(Tk)[None, :]
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask = mask & (k_ids <= q_ids)
    if window is not None:
        mask = mask & (k_ids > q_ids - window)
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)


def chunked_attention(q, k, v, causal=True, window=None, softcap=None,
                      chunk: int = 1024, q_block: int = 512):
    """Doubly-blocked online-softmax attention (flash-style in plain XLA) —
    the fallback used on CPU/dry-run.  An outer lax.map over query blocks and
    an inner lax.scan over KV chunks keep live buffers at
    O(BH * q_block * chunk) regardless of sequence length (32k prefill cells
    would otherwise need a (BH, T, chunk) score buffer)."""
    BH, Tq, d = q.shape
    Tk = k.shape[1]
    ck = min(chunk, Tk)
    qb = min(q_block, Tq)
    assert Tk % ck == 0 and Tq % qb == 0
    nk = Tk // ck
    nq = Tq // qb
    qs = q.astype(jnp.float32) / (d ** 0.5)
    ks = k.reshape(BH, nk, ck, d).swapaxes(0, 1)
    vs = v.reshape(BH, nk, ck, d).swapaxes(0, 1)

    def one_qblock(args):
        qc, iq = args                          # (BH, qb, d), scalar
        q_ids = iq * qb + jnp.arange(qb)[:, None]

        def body(carry, inp):
            m, l, acc = carry
            kc, vc, j = inp
            s = jnp.einsum("bqd,bkd->bqk", qc, kc.astype(jnp.float32))
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            k_ids = j * ck + jnp.arange(ck)[None, :]
            mask = jnp.ones((qb, ck), bool)
            if causal:
                mask = mask & (k_ids <= q_ids)
            if window is not None:
                mask = mask & (k_ids > q_ids - window)
            s = jnp.where(mask[None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            acc = alpha * acc + jnp.einsum("bqk,bkd->bqd", p,
                                           vc.astype(jnp.float32))
            return (m_new, l, acc), None

        init = (jnp.full((BH, qb, 1), -1e30, jnp.float32),
                jnp.zeros((BH, qb, 1), jnp.float32),
                jnp.zeros((BH, qb, d), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(body, init, (ks, vs, jnp.arange(nk)))
        return acc / jnp.maximum(l, 1e-30)

    qblocks = qs.reshape(BH, nq, qb, d).swapaxes(0, 1)
    out = jax.lax.map(one_qblock, (qblocks, jnp.arange(nq)))
    return out.swapaxes(0, 1).reshape(BH, Tq, d).astype(q.dtype)
