"""Worker-topology descriptors and reduce plans shared by both backends.

A `Topology` answers the questions every cross-worker reduction needs:
how many workers there are, which mesh axes carry them, how a worker
derives its index inside SPMD code, how many floats of the shared vector
each worker actually moves per round (feature sharding divides it), and
how to combine a per-worker value across workers.

Two flavors share the dataclass:

  * `simulated(K)` -- the vmap backend: K workers live on the leading axis
    of every array, collectives are driver-side array ops.
  * `from_mesh(mesh, data_axis, model_axis)` -- the shard_map backend: the
    data axis (or axes, mixed-radix) carries workers, collectives are
    lax primitives over those axes, and an optional model axis shards the
    feature dimension d so each device only moves d/|model| floats.

On top of the flavor sits the *reduce kind* -- how the cross-worker sum is
actually routed, selected by a spec string:

    flat      one all-reduce over every worker (the paper's eq.-14 single
              psum; the default and the PR-2 behavior)
    hier:<g>  two-level hierarchical reduce: intra-group sum over groups of
              g consecutive workers, then an inter-group sum -- the
              multi-pod layout where intra-pod links are cheap and only
              K/g group aggregates cross pods. On a mixed-radix mesh the
              two levels are real sequential psums (g must equal the size
              of a trailing run of data axes); on a single named axis the
              grouped association runs through axis_index_groups
              all_gathers (psum's axis_index_groups is unimplemented under
              shard_map), and the vmap flavor mirrors it with a
              (K/g, g, ...) reshape-sum.
    a2a       all-to-all: reduce-scatter the padded vector so each worker
              sums one 1/K chunk, then all-gather the reduced chunks --
              the bandwidth-optimal 2(K-1)/K * d schedule.

All kinds compute the same sum (parity-tested to 1e-6; only the fp
association differs); what changes is the wire plan. `hops()` exposes that
plan as `Hop` descriptors -- per hop: how many messages travel and how many
equivalent f32 floats each carries -- which `comm.tracer.CommTracer` turns
into per-round volume. Compressed *gather* (per-worker top-k (index, value)
sets decompressed server-side, see `comm.aggregate.exchange`) swaps the
dense reduce for `gather_sets`, so the reduce itself moves ~2kK floats
instead of dK.

Both backends in `core.cocoa` build their reduction through
`comm.aggregate.exchange(topo, ...)`, so swapping topologies is a
descriptor change, not a solver rewrite.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .compress import merge_sets
from .placement import WSpec


def auto_axes(mesh):
    """`mesh` with every axis typed `Auto`. `jax.make_mesh` types its axes
    `Explicit` by default, which puts shardings into the array types; the
    rounds place data only through shard_map specs and leave the global
    ops around them (certificate scatters, host gathers) to the compiler,
    so they need the untyped mesh."""
    from jax.sharding import AxisType, Mesh
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))

REDUCE_KINDS = ("flat", "hier", "a2a")


@dataclasses.dataclass(frozen=True)
class Hop:
    """One stage of a reduce plan, as the wire model sees it.

    `messages` is how many wire messages this hop carries per round (summed
    over all senders); `floats_per_message` is the equivalent f32 floats in
    each. Up-link counting only, matching the PR-2 model (the flat reduce
    is one hop of K messages of `floats_per_message(d_local)`). `axis`
    names which mesh direction the hop crosses ("data" for the Delta-w
    reduce plan; "model" for the feature-sharded solver's partial-dot
    exchange) so per-axis accounting can split the wire bill.
    """
    name: str
    messages: int
    floats_per_message: int
    axis: str = "data"

    @property
    def floats(self) -> int:
        return self.messages * self.floats_per_message


def parse_reduce(spec: Optional[str]) -> Tuple[str, int]:
    """Reduce kind + group size from a topology spec string:
    "flat" | "hier:<g>" | "a2a" (None/"" -> flat)."""
    if spec in (None, "", "flat"):
        return "flat", 0
    if spec == "a2a":
        return "a2a", 0
    if isinstance(spec, str) and spec.startswith("hier:"):
        g = int(spec.split(":", 1)[1])
        if g < 2:
            raise ValueError(f"hier group must be >= 2, got {g}")
        return "hier", g
    raise ValueError(f"unknown topology {spec!r}; "
                     f"use 'flat', 'hier:<g>', or 'a2a'")


@dataclasses.dataclass(frozen=True)
class Topology:
    K: int                                  # number of CoCoA workers
    data_axes: Tuple[str, ...] = ()         # () -> simulated (vmap) topology
    model_axis: Optional[str] = None        # feature-sharding axis, if any
    mesh: Any = None                        # jax Mesh for the shard_map flavor
    reduce: str = "flat"                    # "flat" | "hier" | "a2a"
    group: int = 0                          # hier intra-group size (divides K)

    def __post_init__(self):
        if self.reduce not in REDUCE_KINDS:
            raise ValueError(f"unknown reduce kind {self.reduce!r}; "
                             f"use one of {REDUCE_KINDS}")
        if self.reduce == "hier":
            g = self.group
            if not 2 <= g <= self.K or self.K % g:
                raise ValueError(
                    f"hier group {g} must divide K={self.K} (2 <= g <= K)")
            if self.is_mesh and len(self.data_axes) > 1:
                # mixed-radix meshes need g to be a trailing-axes product so
                # the intra level is a real psum over those axes
                self._hier_axis_split()

    @property
    def is_mesh(self) -> bool:
        return bool(self.data_axes)

    @property
    def M(self) -> int:
        """Model-axis size: how many shards the w vector splits into."""
        if self.model_axis is not None and self.mesh is not None:
            return self.mesh.shape[self.model_axis]
        return 1

    def wspec(self, d: int) -> WSpec:
        """The w placement this topology implies for a d-feature problem."""
        return WSpec(d=d, M=self.M, model_axis=self.model_axis)

    # -- construction --------------------------------------------------------

    @staticmethod
    def simulated(K: int, topology: Optional[str] = None) -> "Topology":
        """The vmap backend: K workers on the leading array axis."""
        kind, g = parse_reduce(topology)
        return Topology(K=K, reduce=kind, group=g)

    @staticmethod
    def from_mesh(mesh, data_axis, model_axis: Optional[str] = None,
                  topology: Optional[str] = None) -> "Topology":
        """The shard_map backend: workers = product of the data axes."""
        daxes = ((data_axis,) if isinstance(data_axis, str)
                 else tuple(data_axis))
        K = 1
        for a in daxes:
            K *= mesh.shape[a]
        kind, g = parse_reduce(topology)
        return Topology(K=K, data_axes=daxes, model_axis=model_axis,
                        mesh=auto_axes(mesh), reduce=kind, group=g)

    # -- SPMD helpers --------------------------------------------------------

    def worker_index(self) -> jnp.ndarray:
        """Mixed-radix worker id from the data axes (inside shard_map only)."""
        assert self.is_mesh, "worker_index is meaningful only inside shard_map"
        widx = jnp.zeros((), jnp.int32)
        for a in self.data_axes:
            widx = widx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return widx

    def all_sum(self, x):
        """Cross-worker sum routed per the reduce kind. Simulated flavor:
        `x` carries the leading K axis and the sum is an array op over it
        (a2a sums each worker's chunk, which is the flat sum elementwise);
        mesh flavor: `x` is the per-worker value inside shard_map, and the
        collectives run under the named scope `all_reduce`, so a trace
        times them apart from the rest of the exchange. Every kind returns
        the same total (to fp association)."""
        if not self.is_mesh:
            return (self._hier_sum(x) if self.reduce == "hier"
                    else jnp.sum(x, axis=0))
        with jax.named_scope("all_reduce"):
            if self.reduce == "hier":
                return self._hier_sum(x)
            if self.reduce == "a2a":
                return self._a2a_sum(x)
            return jax.lax.psum(x, self.data_axes)

    # -- hierarchical (two-level) reduce ------------------------------------

    def _hier_axis_split(self):
        """(prefix_axes, suffix_axes) with prod(suffix sizes) == group, for
        mixed-radix meshes where the intra level is a psum over the suffix.
        Raises when the group doesn't align with a trailing-axes product."""
        sizes = [self.mesh.shape[a] for a in self.data_axes]
        prod = 1
        for i in range(len(sizes) - 1, -1, -1):
            prod *= sizes[i]
            if prod == self.group:
                return self.data_axes[:i], self.data_axes[i:]
            if prod > self.group:
                break
        raise ValueError(
            f"hier group {self.group} must equal a trailing product of the "
            f"data-axis sizes {dict(zip(self.data_axes, sizes))}")

    def _index_groups(self) -> Tuple[list, list]:
        """Contiguous intra groups of g workers, and the stride (inter)
        groups holding one member of each -- the single-axis grouping."""
        K, g = self.K, self.group
        intra = [[i * g + j for j in range(g)] for i in range(K // g)]
        inter = [[j * g + i for j in range(K // g)] for i in range(g)]
        return intra, inter

    def _hier_sum(self, x):
        K, g = self.K, self.group
        if not self.is_mesh:
            # same association as the mesh path: groups first, then across
            xg = x.reshape((K // g, g) + x.shape[1:])
            return jnp.sum(jnp.sum(xg, axis=1), axis=0)
        if len(self.data_axes) > 1:
            pre, suf = self._hier_axis_split()
            s = jax.lax.psum(x, suf)             # intra-pod
            return jax.lax.psum(s, pre) if pre else s
        # single named axis: grouped all_gathers + local sums carry the
        # two-level association (axis_index_groups psum is unimplemented
        # under shard_map); after the inter gather every worker holds one
        # group-sum per group
        ax = self.data_axes[0]
        intra, inter = self._index_groups()
        gsum = jnp.sum(jax.lax.all_gather(
            x, ax, axis=0, axis_index_groups=intra), axis=0)
        return jnp.sum(jax.lax.all_gather(
            gsum, ax, axis=0, axis_index_groups=inter), axis=0)

    # -- all-to-all (reduce-scatter + all-gather) ----------------------------

    def _a2a_sum(self, x):
        shape = x.shape
        xf = x.reshape(-1)
        pad = (-xf.size) % self.K
        xp = jnp.pad(xf, (0, pad))
        chunk = jax.lax.psum_scatter(xp, self.data_axes,
                                     scatter_dimension=0, tiled=True)
        full = jax.lax.all_gather(chunk, self.data_axes, axis=0, tiled=True)
        return full[:xf.size].reshape(shape)

    # -- compressed gather (sparse (idx, val) sets; see comm.compress) -------

    def _gather_one(self, m):
        K, g = self.K, self.group
        if self.reduce == "hier":
            if len(self.data_axes) > 1:
                pre, suf = self._hier_axis_split()
                a = jax.lax.all_gather(m, suf, axis=0)        # (g, ...)
                b = jax.lax.all_gather(a, pre, axis=0) if pre else a[None]
            else:
                intra, inter = self._index_groups()
                ax = self.data_axes[0]
                a = jax.lax.all_gather(m, ax, axis=0,
                                       axis_index_groups=intra)   # (g, ...)
                b = jax.lax.all_gather(a, ax, axis=0,
                                       axis_index_groups=inter)   # (K/g, g, .)
            return b.reshape((K,) + m.shape)
        # flat and a2a gather the same stack; only the wire plan differs
        return jax.lax.all_gather(m, self.data_axes, axis=0)

    def gather_sets(self, idx, val, d: int, stats: Optional[dict] = None):
        """Gather per-worker SparseMessage (idx, val) sets for server-side
        `decode_sum`, deduplicating coincident coordinates at the pod
        boundary under hier: after the intra gather each pod merges its g
        sets (`compress.merge_sets`), so the inter hop forwards at most
        g*k live pairs and strictly fewer whenever workers' index sets
        overlap. `stats["inter_gather"]`, when a dict is passed, receives
        the *measured* post-dedup inter volume in floats per round (2
        words per live pair, summed over pods) -- feed it to
        `CommTracer.observe` so the accounting reflects the wire, not the
        static upper bound. Flat/a2a run the one-shot gather unchanged
        (one hop; dedup could only move the scatter-add work, not wire
        volume).

        Returns (idx_stack, val_stack) ready for `decode_sum(..., d)`;
        merged duplicate slots sit at the sentinel index `d` with value 0.
        """
        if self.reduce != "hier":
            if not self.is_mesh:
                return idx, val
            return self._gather_one(idx), self._gather_one(val)
        K, g = self.K, self.group
        if not self.is_mesh:
            gi = idx.reshape((K // g, g) + idx.shape[1:])
            gv = val.reshape((K // g, g) + val.shape[1:])
            mi, mv, uniq = jax.vmap(lambda i, v: merge_sets(i, v, d))(gi, gv)
            if stats is not None:
                stats["inter_gather"] = 2 * jnp.sum(uniq)
            return mi, mv
        if len(self.data_axes) > 1:
            pre, suf = self._hier_axis_split()
            ii = jax.lax.all_gather(idx, suf, axis=0)          # (g, k)
            vv = jax.lax.all_gather(val, suf, axis=0)
            mi, mv, uniq = merge_sets(ii, vv, d)
            oi = jax.lax.all_gather(mi, pre, axis=0) if pre else mi[None]
            ov = jax.lax.all_gather(mv, pre, axis=0) if pre else mv[None]
        else:
            intra, inter = self._index_groups()
            ax = self.data_axes[0]
            ii = jax.lax.all_gather(idx, ax, axis=0, axis_index_groups=intra)
            vv = jax.lax.all_gather(val, ax, axis=0, axis_index_groups=intra)
            mi, mv, uniq = merge_sets(ii, vv, d)
            oi = jax.lax.all_gather(mi, ax, axis=0, axis_index_groups=inter)
            ov = jax.lax.all_gather(mv, ax, axis=0, axis_index_groups=inter)
        if stats is not None:
            # every device in a pod holds the same unique count, so the
            # data-axes psum counts each pod g times -- normalize it away
            stats["inter_gather"] = (
                jax.lax.psum(2 * uniq, self.data_axes) // g)
        return oi, ov

    # -- the wire plan -------------------------------------------------------

    def hops(self, f_msg: int, d_local: int,
             f_set: Optional[int] = None) -> Tuple[Hop, ...]:
        """The round's reduce plan for the tracer.

        `f_msg` is the compressor's dense wire model per worker message
        (`floats_per_message(d_local)`); `d_local` the dense floats each
        worker owns; `f_set` the floats in one sparse (idx, val) set when
        compressed gather is on (None -> dense reduce). Up-link counting:

            flat        reduce          K * f_msg
            hier:g      intra           K * f_msg      (within pods)
                        inter           K/g * f_msg    (pod aggregates)
            a2a         reduce_scatter  K * (K-1) * ceil(f_msg / K)
                        all_gather      K * (K-1) * ceil(d_local / K)
                                        (reduced chunks are dense again)
            gather      flat, a2a       K * f_set       (~2kK for top-k;
                                        both run the same one-shot
                                        all_gather of the sets, so both
                                        are charged the same)
                        hier:g intra    K * f_set, inter K/g * (g * f_set)
                               (leaders forward concatenated group sets)
        """
        K, g = self.K, self.group
        if f_set is not None:
            if self.reduce == "hier":
                return (Hop("intra_gather", K, f_set),
                        Hop("inter_gather", K // g, g * f_set))
            return (Hop("gather", K, f_set),)
        if self.reduce == "hier":
            return (Hop("intra", K, f_msg), Hop("inter", K // g, f_msg))
        if self.reduce == "a2a":
            return (Hop("reduce_scatter", K, (K - 1) * (-(-f_msg // K))),
                    Hop("all_gather", K, (K - 1) * (-(-d_local // K))))
        return (Hop("reduce", K, f_msg),)

    def d_local(self, d: int) -> int:
        """Floats of the shared d-vector each worker moves per reduce
        (feature sharding over the model axis divides it: d/M)."""
        return self.wspec(d).d_local

    # -- shard_map PartitionSpecs -------------------------------------------

    def _dspec(self):
        return (self.data_axes[0] if len(self.data_axes) == 1
                else self.data_axes)

    def w_spec(self) -> P:
        """Spec of the shared primal vector (replicated, or model-sharded)."""
        return P(self.model_axis) if self.model_axis else P()

    def row_spec(self, *trailing) -> P:
        """Spec of a worker-major (K, nk, ...) array: shard the K axis over
        the data axes, pass trailing dim specs through (None or model axis)."""
        return P(self._dspec(), *trailing)
