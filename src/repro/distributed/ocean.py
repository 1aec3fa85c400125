"""Distributed ocean runtime: shard_map-wrapped split-IMEX stepper.

One device per partition (the paper's one-GPU-per-rank), triangles sharded as
Hilbert stripes, ghost-ring halo exchange via ppermute (halo.py).  All
per-partition data is stacked along a leading axis and sharded over the
flattened device mesh axes, so the same SPMD program runs on 4 test devices
or a 512-chip double pod.

Every partition is padded to one local size ``n_loc`` (partition.py), so the
shard-local step has static shapes and runs the same kernel backend as one
device: ``cfg.backend`` ("auto" by default: compiled Pallas kernels on a TPU,
interpreted on the CPU) reaches the column solves and the lateral flux
inside ``shard_map``, each kernel padding the ``n_loc`` columns to its
128-lane cell width.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..core import geometry, mesh2d, stepper
from ..core.dg2d import State2D
from ..core.extrusion import VGrid
from ..kernels import dispatch
from ..obs import metrics as _metrics
from . import halo, partition


class DistributedOcean:
    """Builds partition data and the sharded step function.  ``cfg.backend``
    is the one-device stepper's: any backend runs under ``shard_map``."""

    def __init__(self, mesh: mesh2d.Mesh2D, b_nodal: np.ndarray,
                 cfg: stepper.OceanConfig, device_mesh: jax.sharding.Mesh,
                 axes: Sequence[str], halo_depth: Optional[int] = None,
                 dtype=jnp.float32):
        self.cfg = cfg
        self.device_mesh = device_mesh
        self.axes = tuple(axes)
        n_parts = int(np.prod([device_mesh.shape[a] for a in self.axes]))
        if halo_depth is None:
            halo_depth = max(1, 3 * cfg.halo_exchange_period)
        self.spec = partition.build_partition(mesh, n_parts, halo_depth)
        self.n_parts = n_parts
        self.pspec = PartitionSpec(self.axes)
        # every per-partition array lives on its own device: the leading
        # partition axis is sharded over the mesh, and nothing is placed
        # whole on one device first
        self.sharding = NamedSharding(device_mesh, self.pspec)

        lms = partition.local_meshes(mesh, self.spec)
        geoms = [jax.tree_util.tree_map(
            np.asarray, geometry.geom2d_from_mesh(lm, dtype=dtype))
            for lm in lms]
        self.geom_stk = self._place(jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *geoms))
        self.b_stk = self._place(partition.scatter_field(
            self.spec, np.asarray(b_nodal, dtype)))
        self.tables = self._place(halo.tables_from_spec(self.spec,
                                                        self.axes))

        # static partition facts -> metrics (per-rank halo sizing context
        # for the traced halo.bytes / halo.ppermute counters)
        reg = _metrics.default()
        reg.gauge("distributed.n_parts").set(n_parts)
        reg.gauge("distributed.halo_depth").set(halo_depth)
        reg.gauge("distributed.nt_local").set(self.spec.n_loc)
        reg.gauge("distributed.halo_slots").set(
            sum(int(s.shape[-1]) for s in self.tables.send))
        reg.gauge("distributed.backend", backend=self.backend).set(1)

    @property
    def backend(self) -> str:
        """The kernel backend the shard-local step runs ("auto" resolved
        on this platform)."""
        return dispatch.resolve(self.cfg.backend).value

    def _place(self, tree):
        """Host arrays with a leading partition axis -> sharded arrays."""
        return jax.device_put(tree, self.sharding)

    # -- state scatter/gather -------------------------------------------------
    def scatter_state(self, st: stepper.OceanState) -> stepper.OceanState:
        spec = self.spec
        def sc(x):
            x = np.asarray(x)
            if x.ndim == 0:
                return np.broadcast_to(x, (spec.n_parts,))
            return partition.scatter_field(spec, x)
        return self._place(jax.tree_util.tree_map(sc, st))

    def gather_state(self, st_stk: stepper.OceanState) -> stepper.OceanState:
        spec = self.spec
        def ga(x):
            x = np.asarray(x)
            if x.ndim == 1:       # time
                return jnp.asarray(x[0])
            return jnp.asarray(partition.gather_field(spec, x))
        return jax.tree_util.tree_map(ga, st_stk)

    def _init_local(self) -> stepper.OceanState:
        """One partition's initial state (partition 0's geometry), in the
        partition data's dtype."""
        geom0 = jax.tree_util.tree_map(lambda x: x[0], self.geom_stk)
        vg = VGrid(b=self.b_stk[0], nl=self.cfg.nl)
        return stepper.init_state(geom0, vg, dtype=self.b_stk.dtype)

    def init_state(self) -> stepper.OceanState:
        """Stacked initial state (already partitioned)."""
        return self._place(jax.tree_util.tree_map(
            lambda x: np.broadcast_to(np.asarray(x)[None],
                                      (self.n_parts,) + x.shape),
            self._init_local()))

    # -- the sharded step -------------------------------------------------------
    def make_step_args(self, forcing: Optional[stepper.Forcing3D] = None):
        """The shard-mapped step as a function of (geom, b, tables, state),
        all stacked over the partition axis; ``abstract_args`` gives their
        shapes for an ahead-of-time lowering."""
        cfg = self.cfg
        forcing = forcing if forcing is not None else stepper.Forcing3D()

        def local_step(geom_s, b_s, tables_s, state_s):
            geom = halo.squeeze_local(geom_s)
            b = b_s[0]
            tables = halo.squeeze_local(tables_s)
            st = halo.squeeze_local(state_s)
            vg = VGrid(b=b, nl=cfg.nl)

            def ex2d(s2):
                eta, qx, qy = halo.exchange_batch(
                    [s2.eta, s2.qx, s2.qy], tables)
                return State2D(eta, qx, qy)

            exf = lambda f: halo.exchange(f, tables)
            with jax.named_scope("distributed.local_step"):
                st1 = stepper.step(geom, vg, cfg, st, forcing,
                                   exchange2d=ex2d, exchange_field=exf)
            return jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], st1)

        return jax.shard_map(
            local_step, mesh=self.device_mesh,
            in_specs=(self.pspec, self.pspec, self.pspec, self.pspec),
            out_specs=self.pspec, check_vma=False)

    def make_step(self, forcing: Optional[stepper.Forcing3D] = None):
        """Jitted ``state_stk -> state_stk``.  The placed partition data go
        in as arguments, not as constants of the program."""
        fn = jax.jit(self.make_step_args(forcing))
        return partial(fn, self.geom_stk, self.b_stk, self.tables)

    def abstract_args(self):
        """ShapeDtypeStruct stand-ins for (geom, b, tables, state); the
        state's shapes come from ``jax.eval_shape``, so nothing is built or
        placed."""
        sds = lambda t, lead=(): jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(lead + x.shape, x.dtype), t)
        return (sds(self.geom_stk), sds(self.b_stk), sds(self.tables),
                sds(jax.eval_shape(self._init_local), (self.n_parts,)))
