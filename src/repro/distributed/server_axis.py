"""ServerAxis: one switch for dense-replicated vs mesh-sharded server state.

Every layer of the consolidation plane owns at least one ``[m, ...]`` array
(the pairwise-D tables of :class:`~repro.core.binpack_jax.PackedCluster`, the
stacked :class:`EstimatorBank` rows, CUSUM state, per-server metric columns).
At 16 servers those live happily replicated on one device; at 10k the D
stack alone is gigabytes and the Q x m scorer is the per-decision bottleneck.

:class:`ServerAxis` names the policy once so each layer can be written a
single time:

``ServerAxis()`` (dense)
    ``mesh is None``. Every helper is the *identity at trace time* -- no
    ``- 0`` offsets, no size-1 collectives, no ``shard_map`` wrapper. A
    program threaded through a dense axis traces to the byte-identical jaxpr
    of the unthreaded code (the PR 8 ``metrics=None`` off-switch pattern),
    so the single-device path keeps its equivalence oracles, retrace
    guarantees and purity-registry snapshots untouched.

``ServerAxis(mesh=...)`` (sharded)
    ``[m, ...]`` arrays shard on their leading dim over ``mesh.axis``; the
    helpers become real collectives (``lax.pmin``/``psum``/``axis_index``)
    and :meth:`shard_map` wraps the SPMD body. The contract for exactness
    (DESIGN.md section 15): per-server arithmetic is shard-local and
    bitwise-equal to the dense rows, and only *order-insensitive* scalars
    (min / max / single-owner sums) cross the mesh.

The dataclass is frozen and hashable (``jax.sharding.Mesh`` hashes by
value), so an axis rides in ``static_argnames`` of jitted entry points and
in the static ``ClosedLoopConfig``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec

def _wrap_shard_map(fn: Callable, mesh: Mesh, in_specs, out_specs) -> Callable:
    """shard_map with replication checking off: the scheduler bodies return
    post-``pmin`` values the checker cannot prove replicated."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@dataclasses.dataclass(frozen=True)
class ServerAxis:
    """How the server dimension ``m`` is laid out across devices.

    mesh
        ``None`` for the dense-replicated layout; otherwise a
        :class:`jax.sharding.Mesh` whose ``axis`` names the dimension the
        server axis shards over.
    axis
        Mesh axis name carrying server shards.
    pods
        Scheduler pods for hierarchical greedy selection (independent of the
        mesh: a single device may still schedule hierarchically, and each
        shard owns ``pods // shards`` pods). ``1`` disables the hierarchy.
    """

    mesh: Optional[Mesh] = None
    axis: str = "servers"
    pods: int = 1

    # -- layout queries ----------------------------------------------------
    @property
    def is_sharded(self) -> bool:
        return self.mesh is not None

    @property
    def shards(self) -> int:
        return 1 if self.mesh is None else int(self.mesh.shape[self.axis])

    def local_m(self, m: int) -> int:
        return m // self.shards

    def validate(self, m: int) -> "ServerAxis":
        """Divisibility contract: shards | pods | m (each pod whole within
        one shard, each shard an integer number of servers)."""
        if m % max(self.pods, 1):
            raise ValueError(f"m={m} not divisible by pods={self.pods}")
        if self.is_sharded:
            if self.axis not in self.mesh.shape:
                raise ValueError(
                    f"mesh has no axis {self.axis!r}: {self.mesh.shape}")
            if m % self.shards:
                raise ValueError(f"m={m} not divisible by shards={self.shards}")
            if self.pods > 1 and self.pods % self.shards:
                raise ValueError(
                    f"pods={self.pods} not divisible by shards={self.shards}")
        return self

    # -- collectives (identity when dense) ---------------------------------
    # Only call these from code that runs under self.shard_map(...); on the
    # dense axis they return their argument untouched *at trace time* so the
    # dense jaxpr carries no sharding residue.
    def pmin(self, x):
        return lax.pmin(x, self.axis) if self.is_sharded else x

    def pmax(self, x):
        return lax.pmax(x, self.axis) if self.is_sharded else x

    def psum(self, x):
        return lax.psum(x, self.axis) if self.is_sharded else x

    def index(self):
        return lax.axis_index(self.axis) if self.is_sharded else 0

    def offset(self, m_local: int):
        """Global index of this shard's first server (0 when dense)."""
        return lax.axis_index(self.axis) * m_local if self.is_sharded else 0

    def all_gather(self, x, axis: int = 0):
        return (lax.all_gather(x, self.axis, axis=axis, tiled=True)
                if self.is_sharded else x)

    def any(self, x):
        """Global boolean any over the axis (bools psum as i32)."""
        if not self.is_sharded:
            return x
        return lax.psum(x.astype(np.int32), self.axis) > 0

    # -- spec / wrapper helpers --------------------------------------------
    def spec(self, *rest) -> PartitionSpec:
        """PartitionSpec sharding the leading dim (replicated when dense)."""
        if not self.is_sharded:
            return PartitionSpec()
        return PartitionSpec(self.axis, *rest)

    def rep(self) -> PartitionSpec:
        return PartitionSpec()

    def shard_leading(self, tree, m: int):
        """Spec pytree for ``tree``: leaves whose leading dim is ``m`` shard
        on the axis, everything else replicates. The one rule of DESIGN.md
        section 15 -- a new ``[m, ...]`` array picks up the right layout by
        construction."""
        def leaf_spec(x):
            shape = getattr(x, "shape", None)
            if shape and len(shape) >= 1 and shape[0] == m:
                return self.spec()
            return PartitionSpec()
        return jax.tree_util.tree_map(leaf_spec, tree)

    def rep_tree(self, tree):
        """All-replicated spec pytree matching ``tree``."""
        return jax.tree_util.tree_map(lambda _: PartitionSpec(), tree)

    def shard_map(self, fn: Callable, in_specs, out_specs) -> Callable:
        """SPMD-map ``fn`` over the mesh; the dense axis returns ``fn``
        itself (no wrapper, no tracing overhead, byte-identical program)."""
        if not self.is_sharded:
            return fn
        return _wrap_shard_map(fn, self.mesh, in_specs, out_specs)

    def device_put(self, tree, spec_tree):
        """Lay out ``tree`` per ``spec_tree`` (no-op when dense)."""
        if not self.is_sharded:
            return tree
        from jax.sharding import NamedSharding
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            tree, spec_tree,
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    # -- constructors ------------------------------------------------------
    @classmethod
    def over_host_devices(cls, shards: int, pods: int = 1,
                          axis: str = "servers") -> "ServerAxis":
        """A 1-D mesh over the first ``shards`` local devices. With
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` this is the
        CPU multi-device test harness; ``shards=1`` still exercises the
        full shard_map path (size-1 collectives included)."""
        devs = jax.devices()
        if len(devs) < shards:
            raise ValueError(
                f"need {shards} devices, have {len(devs)} "
                "(set --xla_force_host_platform_device_count)")
        mesh = Mesh(np.asarray(devs[:shards]), (axis,))
        return cls(mesh=mesh, axis=axis, pods=pods)


#: The dense-replicated axis: the default everywhere, byte-identical to the
#: pre-ServerAxis program.
DENSE = ServerAxis()
