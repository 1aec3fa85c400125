"""Halo exchange on device (paper §3.1-3.3), shard_map + ppermute.

Each exchange offset becomes one `ppermute` ring shift over the flattened
device axes; gather (pack) and scatter (unpack) are the paper's pack/unpack
kernels, fused here into the surrounding XLA program.  Emitting the pack +
ppermute first and the interior compute afterwards lets XLA's latency-hiding
scheduler overlap the collective with interior work — the stream-priority
trick of §3.1 without explicit streams.

The 2D mode's latency wall (§3.3) is attacked structurally: the entire
m-substep external burst is one fused scan (no launch gaps), and with
`exchange_period = j > 1` + a (3j)-deep halo the burst exchanges only every
j-th substep (communication-avoiding halos, beyond-paper opt #2).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import partition as part
from ..obs import metrics as _metrics
from ..runtime import chaos as _chaos


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HaloTables:
    """Per-device exchange tables (leaf arrays are the device-local rows)."""
    send: Tuple[jax.Array, ...]     # each (S_off,) int32 local slots to pack
    recv: Tuple[jax.Array, ...]     # each (S_off,) int32 local slots to fill
    offsets: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    n_devices: int = dataclasses.field(metadata=dict(static=True))
    axes: Tuple[str, ...] = dataclasses.field(metadata=dict(static=True))


def tables_from_spec(spec: part.PartitionSpec2D,
                     axes: Sequence[str]) -> HaloTables:
    """Stacked (P, S) numpy tables -> HaloTables pytree of host arrays
    (stacked; the caller places them, shard_map shards the leading axis)."""
    offs = tuple(sorted(spec.tables.keys()))
    send = tuple(np.asarray(spec.tables[o][0], np.int32) for o in offs)
    recv = tuple(np.asarray(spec.tables[o][1], np.int32) for o in offs)
    return HaloTables(send=send, recv=recv, offsets=offs,
                      n_devices=spec.n_parts, axes=tuple(axes))


def exchange(x: jax.Array, t: HaloTables) -> jax.Array:
    """Refresh halo slots of one field (..., n_loc). Inside shard_map.

    The metrics counters increment at TRACE time (shapes are static), so
    ``halo.ppermute`` / ``halo.bytes`` record per-rank collective count and
    wire bytes per compiled program — the §3.3 latency-model inputs."""
    P = t.n_devices
    reg = _metrics.default()
    with jax.named_scope("halo.exchange"):
        for off, sidx, ridx in zip(t.offsets, t.send, t.recv):
            buf = x[..., sidx]
            reg.counter("halo.ppermute").inc()
            reg.counter("halo.bytes").inc(buf.size * buf.dtype.itemsize)
            perm = [(i, (i + off) % P) for i in range(P)]
            rbuf = jax.lax.ppermute(buf, t.axes, perm)
            # chaos site: corrupt the received payload (fires at TRACE time,
            # so an armed halo fault is baked into the compiled program —
            # the diagnostics layer must catch it downstream)
            rbuf = _chaos.site("halo.payload", rbuf, offset=off)
            x = x.at[..., ridx].set(rbuf)
    return x


def exchange_tree(tree, t: HaloTables):
    """Exchange every array leaf of a pytree of (..., n_loc) fields."""
    return jax.tree_util.tree_map(lambda x: exchange(x, t), tree)


def exchange_batch(fields, t: HaloTables):
    """Exchange several same-shaped (..., n_loc) fields with ONE ppermute
    per ring offset (fields stacked on a new leading axis) — the paper's
    message aggregation; cuts the 2D mode's collective count by the field
    count (latency is its Amdahl wall, §3.3)."""
    stacked = jnp.stack(fields)
    out = exchange(stacked, t)
    return [out[i] for i in range(len(fields))]


def squeeze_local(tree):
    """Strip the leading per-device axis of size 1 inside shard_map."""
    return jax.tree_util.tree_map(
        lambda x: x[0] if hasattr(x, "ndim") and x.ndim > 0 else x, tree)
