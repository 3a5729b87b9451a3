"""CoCoA+ framework driver (paper Algorithm 1), generalized over the
regularizer g(w) (CoCoA general, Smith et al. 1611.02189).

One outer round:
    1. each worker k solves the sigma'-damped local subproblem (eq. 9,
       with the regularizer's tau = reg.tau(lam) in place of lambda)
       Theta-approximately (any solver from core.solvers, incl. the Pallas
       TPU kernel paths, dense and sparse),
    2. communicates a single d-vector Delta v_k = (1/tau n) A Delta a_[k]
       (optionally compressed with error feedback -- repro.comm.compress),
    3. the comm layer aggregates  v <- v + gamma * sum_k C(Delta v_k),
       alpha_[k] <- alpha_[k] + gamma * Delta a_[k].

The shared state is the *scaled dual-side* vector v = A alpha / (tau n);
the primal iterate is recovered through the conjugate map w = grad g*(tau
v) (`Regularizer.conj_grad`, elementwise and therefore shard-local on a
2-D mesh). Under the default L2 regularizer the map is the identity and
v IS the paper's w(alpha) -- every formula below reduces to the hard-coded
original bit-for-bit. The comm stack (compression, EF residuals, reduce
topologies, gather sets, WSpec placement) operates on v-space deltas and
is untouched by the choice of g.

The (gamma, sigma') pair is a pluggable repro.comm.aggregate strategy:
gamma = 1/K, sigma' = 1  -> original CoCoA (averaging)   [Remark 12]
gamma = 1,   sigma' = K  -> CoCoA+ (adding, safe bound)  [Lemma 4]

Two execution backends share the same per-worker body and route every
cross-worker reduction through repro.comm (exchange -> apply_update):
  * "vmap":      simulates K workers on any device count (tests, laptops),
  * "shard_map": production SPMD over a mesh axis; the aggregate is a psum
                 and each device keeps only its own (A_[k], alpha_[k]) shard
                 -- dense (K, nk, d) blocks or padded-ELL SparseShards
                 feeding the sparse LocalSDCA solvers.

w placement is a first-class `comm.WSpec`: on a 2-D (data=K, model=M)
mesh w lives feature-sharded over the model axis (d/M floats per device,
never a d-sized replicated buffer). Dense data shards its feature axis
through the in_specs; sparse data arrives as `data.sparse.FeatureShards`
whose ELL column ids are already remapped to each device's local w slice.
The solvers complete their per-step gather-dot with one scalar psum over
the model axis, so every model shard takes identical coordinate
decisions; the per-round Delta-w reduce then crosses the *data* axes
only, one w-shard (d/M floats) per device per round -- the paper's
one-vector-per-round communication model, tensor-sharded. M=1 reproduces
the 1-D replicated layout bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import comm
from repro.comm.placement import WSpec
from repro.comm.topology import Topology
from repro.data import sparse as sparse_data
from repro.data.sparse import FeatureShards, SparseShards
from repro.obs.events import Aggregator, EventBus
from repro.obs.metrics import RoundRecord, aot_compile, fenced_call

from . import duality
from .accel import AccelSpec, init_accel_state, parse_accel, wrap_round
from .losses import Loss, get_loss
from .regularizers import L2, Regularizer, get_regularizer
from .solvers import (LocalSolver, SDCAResult, SOLVERS, get_solver,
                      sparse_counterpart)


@dataclasses.dataclass(frozen=True)
class CoCoAConfig:
    loss: str = "hinge"
    lam: float = 1e-4
    gamma: float = 1.0                 # aggregation parameter in (0, 1]
    sigma_p: Optional[float] = None    # None -> safe bound gamma * K (Lemma 4)
    H: int = 1000                      # local solver iterations per round
    solver: str = "sdca"               # core.solvers.SOLVERS key or "sdca_kernel"
    backend: str = "vmap"              # "vmap" | "shard_map"
    data_axis: str = "data"            # mesh axis carrying the partition
    model_axis: Optional[str] = None   # optional feature-sharding axis
    average_iterates: bool = False     # Theorem-8 averaged iterate output
    aggregator: Optional[str] = None   # "add"|"average"|"gamma:<g>" strategy;
                                       # overrides (gamma, sigma_p) when set
    compress: str = "none"             # comm.compress scheme for Delta w_k
    compress_k: int = 0                # sparsifier budget for topk/randk
    topology: str = "flat"             # reduce plan: "flat"|"hier:<g>"|"a2a"
    gather: bool = False               # compressed sparse gather: the reduce
                                       # moves (idx, val) sets, ~2kK floats
    reg: str = "l2"                    # regularizer g(w): "l2" |
                                       # "elastic:<eta>" | "l1s:<eps>"
    accel: str = "none"                # outer momentum over the round
                                       # operator (core.accel): "none" |
                                       # "nesterov" | "catalyst:<kappa>"

    def resolved_sigma(self, K: int) -> float:
        return self.agg_params(K).sigma_prime

    def agg_params(self, K: int) -> comm.AggParams:
        """The (gamma, sigma') pair this config runs with at K workers."""
        return comm.from_config(self.gamma, self.sigma_p, K,
                                aggregator=self.aggregator)

    def regularizer(self) -> Regularizer:
        """The Regularizer instance this config's rounds evaluate."""
        return get_regularizer(self.reg)

    def accel_spec(self) -> AccelSpec:
        """The parsed outer-momentum schedule this config runs with."""
        return parse_accel(self.accel)

    def compressor(self, M: int = 1) -> comm.Compressor:
        """The wire compressor; under compressed gather on a feature-
        sharded mesh (`M` > 1) the sparsifier's budget k is split across
        the model shards (ceil(k/M) slots, remainder to low shards) so the
        gathered-set wire volume stays M-invariant at ~2kK floats/round
        instead of growing to 2kKM. The dense reduce form is NOT split --
        there each shard's masked d/M-vector message already shrinks with
        M, and k stays the per-shard budget it always was."""
        comp = comm.resolve_compressor(self.compress, self.compress_k)
        if self.gather and not comp.supports_gather:
            raise ValueError(
                f"gather=True needs a sparse-set compressor (topk/randk); "
                f"compress={self.compress!r} only has a dense wire form")
        if M > 1 and self.gather:
            comp = comp.with_shards(M, self.model_axis)
        return comp

    @staticmethod
    def averaging(K: int, **kw) -> "CoCoAConfig":
        """Original CoCoA (Remark 12)."""
        return CoCoAConfig(gamma=1.0 / K, sigma_p=1.0, **kw)

    @staticmethod
    def adding(K: int, **kw) -> "CoCoAConfig":
        """CoCoA+ with the safe bound sigma' = K."""
        return CoCoAConfig(gamma=1.0, sigma_p=float(K), **kw)


class CoCoAState(NamedTuple):
    w: jnp.ndarray        # (d,) shared vector -- the *scaled dual-side*
                          # point v = A alpha/(tau n); the primal iterate
                          # is reg.conj_grad(w, lam) (`primal_w`), which is
                          # the identity under L2 (then this IS the paper's
                          # w). Kept under its historical leaf name so
                          # checkpoints / pytree signatures are unchanged.
                          # d is the *placed* width (WSpec.d_padded under
                          # feature sharding)
    alpha: jnp.ndarray    # (K, nk) partitioned duals
    rng: jax.Array
    rounds: jnp.ndarray   # scalar int32
    alpha_bar: jnp.ndarray  # running sum for averaged iterate (or zeros)
    ef: jnp.ndarray       # (K, d) per-worker error-feedback residuals
                          # (zeros while compression is off)
    wire: Optional[jnp.ndarray] = None
                          # measured post-dedup inter_gather floats of the
                          # last round (hier compressed gather only; None
                          # elsewhere -- not a pytree leaf then, so legacy
                          # checkpoints and jit signatures are unchanged)
    v_prev: Optional[jnp.ndarray] = None
                          # outer momentum: last round's v (core.accel;
                          # inherits w's placement so the extrapolation is
                          # shard-local). None while accel="none" -- same
                          # not-a-leaf contract as `wire`, so legacy
                          # checkpoints and plain-run jit signatures are
                          # byte-identical
    alpha_prev: Optional[jnp.ndarray] = None
                          # outer momentum: last round's duals; the pair
                          # extrapolates together so v(alpha) consistency
                          # is exact (core.accel module docstring)
    accel_a: Optional[jnp.ndarray] = None
                          # catalyst alpha-recursion scalar (carried inert
                          # under nesterov; None while accel="none")


def init_state(d: int, K: int, nk: int, seed: int = 0,
               dtype=jnp.float32) -> CoCoAState:
    return CoCoAState(
        w=jnp.zeros((d,), dtype),
        alpha=jnp.zeros((K, nk), dtype),
        rng=jax.random.PRNGKey(seed),
        rounds=jnp.zeros((), jnp.int32),
        alpha_bar=jnp.zeros((K, nk), dtype),
        ef=comm.init_residual(K, d, dtype),
    )


def primal_w(state: CoCoAState, cfg: CoCoAConfig) -> jnp.ndarray:
    """The primal iterate the run serves: w = grad g*(tau v) applied to the
    state's shared v-vector (identity under L2). Elementwise, so it is
    valid on padded feature-sharded widths (conj_grad(0) = 0 for every
    instance -- padding stays zero)."""
    return cfg.regularizer().conj_grad(state.w, cfg.lam)


def reshard_w_state(state: CoCoAState, old: WSpec, new: WSpec,
                    params: comm.AggParams) -> CoCoAState:
    """Carry (w, ef) across a w-placement change -- a legacy replicated-w
    checkpoint restored onto a 2-D mesh, or an elastic re-partition that
    changes M. The EF residuals are un-transmitted message mass in the
    *old* placement's frame, so they are flushed into w first (the
    existing comm.flush_ef path -- nothing is silently dropped), then w is
    lifted to the global frame and re-padded for the new placement, and
    fresh zero residuals are laid out at the new width."""
    if old.d != new.d:
        raise ValueError(f"placements disagree on the feature count: "
                         f"{old.d} vs {new.d}")
    w = comm.flush_ef(state.w, state.ef, params)
    w = new.pad_w(old.unpad_w(w))
    K = state.ef.shape[0]
    return state._replace(w=w,
                          ef=comm.init_residual(K, new.d_padded,
                                                state.ef.dtype))


def _scoped(name: str, fn):
    """Label `fn`'s ops with a jax.named_scope so the region is visible
    in profiler traces (obs.ProfilerSink); free when not tracing."""
    def wrapped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    return wrapped


def _resolve_solver(name, sparse: bool,
                    feature_sharded: bool = False) -> LocalSolver:
    """Resolve a registry key (or descriptor) against the round's input
    format and mesh shape, purely through LocalSolver capability flags --
    an externally `register_solver`-ed solver with the right flags
    dispatches through here with no framework edit. Dense inputs require
    `dense`; SparseShards inputs map through `sparse_counterpart` (the
    descriptor's declared ELL twin, identity when already sparse); a
    feature-sharded mesh (M>1) additionally requires `model_axis`."""
    ls = get_solver(name)
    if not sparse:
        if not ls.dense:
            raise ValueError(
                f"solver {ls.name!r} needs SparseShards inputs; dense arrays "
                f"take 'sdca' / 'sdca_kernel' (mapped automatically when the "
                f"data is sparse)")
        resolved = ls
    else:
        twin = sparse_counterpart(ls)
        if twin is None:
            raise ValueError(
                f"solver {ls.name!r} has no sparse path; pick one of "
                f"{sorted(n for n in SOLVERS if sparse_counterpart(n))} "
                f"for SparseShards inputs")
        resolved = get_solver(twin)
    if feature_sharded and not resolved.model_axis:
        # e.g. the dense kernel (a pallas body cannot host the per-step
        # model-axis collective) and gd/deadline; M>1 routes through the
        # jnp solvers or the sparse kernel's z-exchange schedule
        # (block-batched partial-dot psums between kernel invocations)
        raise ValueError(
            f"solver {resolved.name!r} cannot run feature-sharded (M>1): "
            f"use 'sdca' (dense jnp), 'sdca_sparse' (ELL jnp), or "
            f"'sdca_sparse_kernel' (ELL Pallas, z-exchange schedule)")
    return resolved


def _worker_body(X_k, y_k, alpha_k, mask_k, v, rng, *, loss: Loss, lam: float,
                 n, sigma_p: float, H: int, solver: LocalSolver,
                 budget=None, sqnorms=None, model_axis=None,
                 reg: Regularizer = L2) -> SDCAResult:
    """One worker's Theta-approximate local solve, dispatched through the
    LocalSolver descriptor's capability flags (never its name)."""
    fn = solver.fn
    if solver.deadline:
        return fn(X_k, y_k, alpha_k, mask_k, v, rng, loss, lam, n, sigma_p, H,
                  budget if budget is not None else jnp.asarray(H),
                  sqnorms=sqnorms, reg=reg)
    if solver.model_axis:
        return fn(X_k, y_k, alpha_k, mask_k, v, rng, loss, lam, n, sigma_p, H,
                  sqnorms=sqnorms, model_axis=model_axis, reg=reg)
    assert model_axis is None, (solver.name, "has no feature-sharded path")
    if solver.sqnorms:
        return fn(X_k, y_k, alpha_k, mask_k, v, rng, loss, lam, n, sigma_p, H,
                  sqnorms=sqnorms, reg=reg)
    return fn(X_k, y_k, alpha_k, mask_k, v, rng, loss, lam, n, sigma_p, H,
              reg=reg)


# ----------------------------------------------------------------------------
# vmap backend (simulation of K workers; exact same math as production)
# ----------------------------------------------------------------------------

def make_round_vmap(cfg: CoCoAConfig, K: int,
                    n_total=None) -> Callable[..., CoCoAState]:
    """Simulated K-worker round. `X` may be a dense (K, nk, d) array or a
    SparseShards pytree -- vmap maps over the leading K axis of either, and
    cfg.solver is transparently mapped to its ELL counterpart for sparse
    inputs (sdca -> sdca_sparse, sdca_kernel -> sdca_sparse_kernel)."""
    loss = get_loss(cfg.loss)
    reg = cfg.regularizer()
    topo = Topology.simulated(K, topology=cfg.topology)
    p = cfg.agg_params(K)
    compressor = cfg.compressor()

    def round_fn(state: CoCoAState, X, y, mask, budget=None) -> CoCoAState:
        n = duality.effective_n(mask) if n_total is None else n_total
        rng, sub = jax.random.split(state.rng)
        # fold_in (not split) so worker k's stream is identical to the
        # shard_map backend's fold_in(sub, axis_index) -- backend parity is
        # exact, not statistical (tests/test_sharded.py)
        rngs = jax.vmap(lambda i: jax.random.fold_in(sub, i))(jnp.arange(K))
        solver = _resolve_solver(cfg.solver, isinstance(X, SparseShards))
        body = functools.partial(
            _worker_body, loss=loss, lam=cfg.lam, n=n, sigma_p=p.sigma_prime,
            H=cfg.H, solver=solver, reg=reg)
        # the named scopes label the solver vs. exchange regions in a
        # jax.profiler trace (obs.ProfilerSink) -- no-ops otherwise
        with jax.named_scope("cocoa/local_solve"):
            if budget is None:
                res = jax.vmap(lambda Xk, yk, ak, mk, r: body(Xk, yk, ak, mk, state.w, r)
                               )(X, y, alpha_split(state.alpha, K), mask, rngs)
            else:
                res = jax.vmap(lambda Xk, yk, ak, mk, r, b: body(
                    Xk, yk, ak, mk, state.w, r, budget=b)
                )(X, y, alpha_split(state.alpha, K), mask, rngs, budget)
        # --- the communication step: damp, compress, reduce, apply ---
        with jax.named_scope("cocoa/exchange"):
            crngs = jax.vmap(comm.comm_rng)(rngs)
            stats = {}
            dw_sum, ef = comm.exchange(topo, res.du, state.ef, crngs, p,
                                       compressor, gather=cfg.gather,
                                       stats=stats)
            w, alpha = comm.apply_update(state.w, state.alpha, dw_sum,
                                         res.dalpha, p)
        return CoCoAState(w, alpha, rng, state.rounds + 1,
                          state.alpha_bar + alpha, ef,
                          stats.get("inter_gather"))

    return round_fn


def alpha_split(alpha, K):
    # alpha is already (K, nk); kept as a hook for future ragged layouts.
    assert alpha.shape[0] == K
    return alpha


# ----------------------------------------------------------------------------
# shard_map backend (production SPMD)
# ----------------------------------------------------------------------------

def make_round_sharded(cfg: CoCoAConfig, mesh) -> Callable[..., CoCoAState]:
    """Rounds over a mesh: K = prod(mesh.shape[data_axes]) workers, with w
    placed per the topology's `WSpec` (replicated, or feature-sharded over
    cfg.model_axis into M shards of d_loc = ceil(d/M) floats).

    Layouts (global -> per-shard under shard_map), dense:
      X     (K, nk, d_pad)  P(data, None, model?) -> (1, nk, d_loc)
      y,mask,alpha (K, nk)  P(data, None)         -> (1, nk)
      w     (d_pad,)    WSpec.spec()              -> (d_loc,)
      ef    (K, d_pad)  P(data, model?)           -> (1, d_loc)
    sparse replicated (padded-ELL SparseShards, global column ids):
      cols/vals (K, nk, r_max)  P(data, None, None) -> (1, nk, r_max)
      nnz       (K, nk)         P(data, None)       -> (1, nk)
      w         (d,)            P()                 -> (d,) replicated
    and sparse feature-sharded (FeatureShards, shard-LOCAL column ids):
      cols/vals (K, M, nk, r_loc) P(data, model, None, None)
                                                  -> (1, 1, nk, r_loc)
      nnz       (K, M, nk)      P(data, model, None) -> (1, 1, nk)
      w         (M*d_loc,)      P(model)          -> (d_loc,)
      sqnorms   (K, nk) global  P(data, None)     -> (1, nk) replicated
    The per-round communication is one psum of w-shards over the *data*
    axes per feature shard (the paper's single-vector reduce, eq. 14,
    d_loc floats per device) -- plus, under feature sharding, the scalar
    partial-dot psum over the model axis inside each solver step. Both
    route through comm exactly like the vmap backend.
    """
    loss = get_loss(cfg.loss)
    reg = cfg.regularizer()
    topo = Topology.from_mesh(mesh, cfg.data_axis, cfg.model_axis,
                              topology=cfg.topology)
    mesh = topo.mesh
    K = topo.K
    M = topo.M
    sharded_w = M > 1
    p = cfg.agg_params(K)
    # compressed gather at M > 1 splits the sparsifier's budget across
    # model shards (k/M each) so gathered-set wire volume stays M-invariant
    compressor = cfg.compressor(M=M)
    mspec = cfg.model_axis  # None -> replicated features
    # measured post-dedup inter volume only exists for hier gather
    want_wire = cfg.gather and topo.reduce == "hier"

    def _per_worker(w, Xk, yk, ak, mk, efk, rng, n, sqn_k, solver,
                    model_axis=None):
        # fold the worker index into the rng so workers de-correlate (and
        # match the vmap backend's fold_in(sub, k) stream exactly); the
        # index runs over the data axes only, so every model shard of a
        # worker draws the identical coordinate sequence
        rngk = jax.random.fold_in(rng, topo.worker_index())
        with jax.named_scope("cocoa/local_solve"):
            res = _worker_body(Xk, yk, ak, mk, w, rngk, loss=loss,
                               lam=cfg.lam, n=n, sigma_p=p.sigma_prime,
                               H=cfg.H, solver=solver, sqnorms=sqn_k,
                               model_axis=model_axis, reg=reg)
        # --- the one communicated w-shard per round per worker ---
        with jax.named_scope("cocoa/exchange"):
            stats = {}
            dw_sum, ef_new = comm.exchange(topo, res.du, efk,
                                           comm.comm_rng(rngk), p,
                                           compressor, gather=cfg.gather,
                                           stats=stats)
            wire = stats.get("inter_gather")
        if wire is not None and sharded_w:
            # each model shard ran its own per-shard gather; the tracer
            # prices hops per model shard (d/M-scaled), so report the
            # mean shard's measured volume to keep the units consistent
            wire = jax.lax.psum(wire, mspec) // M
        return res, dw_sum, ef_new, wire

    def _build_dense():
        solver = _resolve_solver(cfg.solver, sparse=False,
                                 feature_sharded=sharded_w)
        maxis = mspec if sharded_w else None

        def per_shard(w, X, y, alpha, mask, ef, rng, n, rounds, alpha_bar,
                      sqn):
            # shapes: w (d_loc,), X (1, nk, d_loc), y/alpha/mask (1, nk);
            # sqn carries the *global* row norms (replicated over model)
            res, dw_sum, ef_new, wire = _per_worker(
                w, X[0], y[0], alpha[0], mask[0], ef[0], rng, n, sqn[0],
                solver, maxis)
            w_new, alpha_new = comm.apply_update(w, alpha, dw_sum,
                                                 res.dalpha[None], p)
            out = (w_new, alpha_new, rounds + 1, alpha_bar + alpha_new,
                   ef_new[None])
            return out + ((wire,) if want_wire else ())

        in_specs = (topo.w_spec(),                 # w
                    topo.row_spec(None, mspec),    # X
                    topo.row_spec(None),           # y
                    topo.row_spec(None),           # alpha
                    topo.row_spec(None),           # mask
                    topo.row_spec(mspec),          # ef
                    P(), P(), P(),                 # rng, n, rounds
                    topo.row_spec(None),           # alpha_bar
                    topo.row_spec(None))           # sqnorms
        out_specs = (topo.w_spec(), topo.row_spec(None), P(),
                     topo.row_spec(None), topo.row_spec(mspec)) \
            + ((P(),) if want_wire else ())
        return jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _build_sparse():
        # replicated-w ELL path (global column ids); feature sharding
        # arrives as FeatureShards through _build_sparse_fs instead
        solver = _resolve_solver(cfg.solver, sparse=True)

        def per_shard(w, cols, vals, nnz, y, alpha, mask, ef, rng, n, rounds,
                      alpha_bar):
            # shapes: w (d,) replicated, cols/vals (1, nk, r_max),
            # nnz/y/alpha/mask (1, nk), ef (1, d)
            shard = SparseShards(cols[0], vals[0], nnz[0], d=w.shape[0])
            res, dw_sum, ef_new, wire = _per_worker(
                w, shard, y[0], alpha[0], mask[0], ef[0], rng, n, None,
                solver)
            w_new, alpha_new = comm.apply_update(w, alpha, dw_sum,
                                                 res.dalpha[None], p)
            out = (w_new, alpha_new, rounds + 1, alpha_bar + alpha_new,
                   ef_new[None])
            return out + ((wire,) if want_wire else ())

        in_specs = (P(),                           # w (replicated)
                    topo.row_spec(None, None),     # cols
                    topo.row_spec(None, None),     # vals
                    topo.row_spec(None),           # nnz
                    topo.row_spec(None),           # y
                    topo.row_spec(None),           # alpha
                    topo.row_spec(None),           # mask
                    topo.row_spec(None),           # ef
                    P(), P(), P(),                 # rng, n, rounds
                    topo.row_spec(None))           # alpha_bar
        out_specs = (P(), topo.row_spec(None), P(), topo.row_spec(None),
                     topo.row_spec(None)) + ((P(),) if want_wire else ())
        return jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _build_sparse_fs():
        # feature-sharded ELL path: shard-local column ids against the
        # local w slice; works for any M >= 1 (M=1 is the identity map)
        solver = _resolve_solver(cfg.solver, sparse=True,
                                 feature_sharded=sharded_w)
        maxis = mspec if sharded_w else None

        def per_shard(w, cols, vals, nnz, y, alpha, mask, ef, rng, n, rounds,
                      alpha_bar, sqn):
            # shapes: w (d_loc,), cols/vals (1, 1, nk, r_loc),
            # nnz (1, 1, nk), y/alpha/mask/sqn (1, nk), ef (1, d_loc)
            shard = SparseShards(cols[0, 0], vals[0, 0], nnz[0, 0],
                                 d=w.shape[0])
            res, dw_sum, ef_new, wire = _per_worker(
                w, shard, y[0], alpha[0], mask[0], ef[0], rng, n,
                sqn[0] if sharded_w else None, solver, maxis)
            w_new, alpha_new = comm.apply_update(w, alpha, dw_sum,
                                                 res.dalpha[None], p)
            out = (w_new, alpha_new, rounds + 1, alpha_bar + alpha_new,
                   ef_new[None])
            return out + ((wire,) if want_wire else ())

        in_specs = (topo.w_spec(),                  # w
                    topo.row_spec(mspec, None, None),  # cols
                    topo.row_spec(mspec, None, None),  # vals
                    topo.row_spec(mspec, None),     # nnz
                    topo.row_spec(None),            # y
                    topo.row_spec(None),            # alpha
                    topo.row_spec(None),            # mask
                    topo.row_spec(mspec),           # ef
                    P(), P(), P(),                  # rng, n, rounds
                    topo.row_spec(None),            # alpha_bar
                    topo.row_spec(None))            # sqnorms (global)
        out_specs = (topo.w_spec(), topo.row_spec(None), P(),
                     topo.row_spec(None), topo.row_spec(mspec)) \
            + ((P(),) if want_wire else ())
        return jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    built = {}

    def _unpack(outs):
        if want_wire:
            return outs[:-1], outs[-1]
        return outs, None

    def round_fn(state: CoCoAState, X, y, mask, n=None,
                 sqnorms=None) -> CoCoAState:
        n_ = duality.effective_n(mask) if n is None else n
        rng, sub = jax.random.split(state.rng)
        if isinstance(X, FeatureShards):
            if X.M != M:
                raise ValueError(
                    f"FeatureShards sliced for M={X.M} but the mesh's "
                    f"model axis carries M={M}")
            if sqnorms is None:
                sqnorms = sparse_data.row_sqnorms(X) * mask
            if "sparse_fs" not in built:
                built["sparse_fs"] = _build_sparse_fs()
            outs = built["sparse_fs"](
                state.w, X.cols, X.vals, X.nnz, y, state.alpha, mask,
                state.ef, sub, n_, state.rounds, state.alpha_bar, sqnorms)
            (w, alpha, rounds, abar, ef), wire = _unpack(outs)
        elif isinstance(X, SparseShards):
            if sharded_w:
                raise ValueError(
                    "feature sharding (M>1) needs FeatureShards with "
                    "shard-local column ids; slice the shards with "
                    "data.sparse.shard_features (or partition_sparse "
                    "with M=...)")
            if "sparse" not in built:
                built["sparse"] = _build_sparse()
            outs = built["sparse"](
                state.w, X.cols, X.vals, X.nnz, y, state.alpha, mask,
                state.ef, sub, n_, state.rounds, state.alpha_bar)
            (w, alpha, rounds, abar, ef), wire = _unpack(outs)
        else:
            if sqnorms is None:
                sqnorms = jnp.sum(X * X, axis=-1) * mask
            if "dense" not in built:
                built["dense"] = _build_dense()
            outs = built["dense"](
                state.w, X, y, state.alpha, mask, state.ef, sub, n_,
                state.rounds, state.alpha_bar, sqnorms)
            (w, alpha, rounds, abar, ef), wire = _unpack(outs)
        return CoCoAState(w, alpha, rng, rounds, abar, ef, wire)

    return round_fn


# ----------------------------------------------------------------------------
# High-level solve loop with certificates, history, checkpoint/elastic hooks
# ----------------------------------------------------------------------------

class SolveResult(NamedTuple):
    state: CoCoAState
    history: dict   # lists: round, gap, primal, dual, comm_vectors,
                    # comm_floats, comm_bytes, comm_psums -- a thin view
                    # over the emitted RoundRecords (obs.Aggregator.history)


def solve(cfg: CoCoAConfig, X, y, mask, *, rounds: int, eps_gap: float = 0.0,
          seed: int = 0, gap_every: int = 1, mesh=None, budget_fn=None,
          on_round: Optional[Callable[[int, CoCoAState, float], None]] = None,
          state: Optional[CoCoAState] = None,
          obs: Optional[EventBus] = None,
          throughput=None) -> SolveResult:
    """Run CoCoA+/CoCoA until `rounds` or duality gap <= eps_gap.

    `X` is a dense (K, nk, d) array, a data.sparse.SparseShards (either
    backend), or a data.sparse.FeatureShards for the feature-sharded 2-D
    mesh (shard_map backend with cfg.model_axis). `on_round(t, state,
    gap)` is the legacy checkpoint hook; `obs` is its generalization --
    an `repro.obs.EventBus` that receives one frozen, schema-versioned
    `RoundRecord` per certified round (gap/primal/dual, the per-hop wire
    plan, and the compile/execute/certificate wall-clock split measured
    with `block_until_ready` fencing; the round step is AOT-compiled so
    compile is priced separately from steady-state execution). The
    returned history is itself derived from those records. `budget_fn(t)
    -> (K,) int array` enables deadline-budgeted solving (vmap backend);
    `throughput` is an optional `runtime.straggler.ThroughputTracker`
    fed each round with (steps_done, fenced round seconds) -- its EMA
    rates and the budgets land in the records.

    The state's w width follows the placement: WSpec.d_padded (= M *
    ceil(d/M)) under feature sharding, d otherwise; dense X is zero-padded
    along its feature axis to match (padded coordinates carry no data and
    stay exactly zero).
    """
    if isinstance(X, FeatureShards):
        K, _, nk = X.cols.shape[:3]
        d = X.d
        dtype = X.vals.dtype
    elif isinstance(X, SparseShards):
        K, nk = X.cols.shape[:2]
        d = X.d
        dtype = X.vals.dtype
    else:
        K, nk, d = X.shape
        dtype = X.dtype
    loss = get_loss(cfg.loss)
    reg = cfg.regularizer()

    if cfg.backend == "shard_map":
        assert mesh is not None, "shard_map backend needs a mesh"
        topo = Topology.from_mesh(mesh, cfg.data_axis, cfg.model_axis,
                                  topology=cfg.topology)
        wspec = topo.wspec(d)
        if isinstance(X, FeatureShards) and X.M != wspec.M:
            raise ValueError(f"FeatureShards sliced for M={X.M} but the "
                             f"mesh's model axis carries M={wspec.M}")
        if wspec.sharded and not isinstance(X, (FeatureShards,
                                                SparseShards)):
            X = jnp.pad(X, ((0, 0), (0, 0), (0, wspec.d_padded - d)))
        base_round_fn = make_round_sharded(cfg, mesh)
    else:
        topo = Topology.simulated(K, topology=cfg.topology)
        wspec = topo.wspec(d)
        if isinstance(X, FeatureShards):
            raise ValueError("FeatureShards need the shard_map backend on "
                             "a 2-D mesh; the vmap reference runs on "
                             "SparseShards with the global column ids")
        base_round_fn = make_round_vmap(cfg, K)
    # outer momentum lifts the round operator BEFORE jit, so extrapolate +
    # solve + exchange compile as one computation; accel="none" returns
    # the base round itself (bit-for-bit the plain path, not a wrapper)
    aspec = cfg.accel_spec()
    round_fn = jax.jit(wrap_round(base_round_fn, aspec))
    if state is None:
        state = init_state(wspec.d_padded, K, nk, seed, dtype)
    if cfg.gather and topo.reduce == "hier" and state.wire is None:
        # the round emits a measured-wire scalar under hier gather; give
        # it a stable leaf up front so round 1 and round 2 share one jit
        # signature (None -> array would retrace the whole round)
        state = state._replace(wire=jnp.zeros((), jnp.int32))
    # same stable-leaf contract for the momentum pair (v_prev = w makes
    # the first accelerated round exactly a plain round); a checkpoint
    # from a plain run restores leafless and momentum simply starts here
    state = init_accel_state(state, aspec)

    compressed = cfg.compress not in (None, "none", "")
    # lossy messages AND extrapolated exchange points both make the
    # carried v drift from v(alpha) -- either way the certificate must
    # price the iterate the algorithm actually holds
    drifted = compressed or aspec.enabled
    if drifted:
        # certify the primal point w = grad g*(tau v) at the state's
        # carried (NON-extrapolated) v (still >= D by weak duality)
        gap_fn = jax.jit(_scoped("cocoa/certificate", functools.partial(
            duality.gap_at_v, loss=loss, lam=cfg.lam, reg=reg)))
    else:
        gap_fn = jax.jit(_scoped("cocoa/certificate", functools.partial(
            duality.gap_decomposed, loss=loss, lam=cfg.lam, reg=reg)))

    # per-round communication accounting: the topology's reduce plan priced
    # by the compressor's wire model (per hop under hier/a2a, the sparse
    # (idx, val) sets under compressed gather); feature sharding divides
    # the dense message length to d/M per hop -- Fig-2 claims stay honest
    # under tensor sharding, compression, and multi-hop topologies. The
    # model-axis tax of the sharded solver is carried as its own hop so
    # per-axis tables add up: one scalar psum per coordinate step on the
    # jnp path, or the kernel path's block-batched z-exchange (priced from
    # the same resolve/clamp arithmetic the dispatch launches with).
    zx_plan = None
    if wspec.sharded and isinstance(X, FeatureShards) and \
            sparse_counterpart(cfg.solver) == "sdca_sparse_kernel":
        from repro.kernels.ops import sparse_zx_plan
        zx_plan = sparse_zx_plan(nk, wspec.d_local, cfg.H,
                                 r_max=int(X.cols.shape[-1]),
                                 reg_family=getattr(reg, "family", "other"),
                                 model_shards=wspec.M)
    tracer = comm.CommTracer.for_run(
        K=K, d_local=topo.d_local(d),
        compressor=cfg.compressor(M=wspec.M),
        topo=topo, gather=cfg.gather,
        extra_hops=comm.model_hops(wspec, K, cfg.H, zx_plan=zx_plan)
        # momentum's priced (empty) wire plan -- asserts zero extra floats
        + comm.accel_hops(cfg.accel))

    # --- the instrumented round loop -----------------------------------
    # `agg` collects the emitted records; the returned history is its
    # view, so history and any external bus sink describe the same bytes.
    agg = Aggregator()
    if budget_fn is not None and cfg.backend != "shard_map":
        extra_args = lambda t: (budget_fn(t),)
    else:
        extra_args = lambda t: ()
    # AOT-split trace+compile out of the per-round fenced timings. The
    # round decides where the state lives: a carried state (resumed, or
    # rebuilt after a failure) enters from the host, so the executable is
    # compiled for the placement its own output keeps. The data is
    # loop-invariant, so it is placed once where the executable reads it
    # instead of being transferred again every round
    state = jax.tree.map(np.asarray, state)
    run_fn, pending_compile = aot_compile(round_fn, state, X, y, mask,
                                          *extra_args(0))
    X, y, mask = jax.device_put((X, y, mask),
                                run_fn.input_shardings[0][1:4])
    gap_run = None
    base_round = int(state.rounds)
    gap = float("inf")
    exec_acc = 0.0
    covered = 0
    prev_floats = 0
    for t in range(rounds):
        with jax.profiler.StepTraceAnnotation("cocoa_round", step_num=t):
            state, dt = fenced_call(run_fn, state, X, y, mask,
                                    *extra_args(t))
        exec_acc += dt
        covered += 1
        tracer.tick()
        if state.wire is not None:
            # hier compressed gather: replace the inter hop's analytic
            # upper bound with the measured post-dedup volume
            tracer.observe("inter_gather", state.wire)
        budgets = (np.asarray(budget_fn(t))
                   if budget_fn is not None else None)
        if throughput is not None:
            # bulk-synchronous round: every worker shares the fenced
            # round wall-clock; steps actually run are the budgets (or H)
            throughput.observe_round(
                budgets if budgets is not None else float(cfg.H), dt)
        if (t + 1) % gap_every == 0 or t == rounds - 1:
            alpha_eval = state.alpha
            if cfg.average_iterates:
                alpha_eval = state.alpha_bar / jnp.maximum(state.rounds, 1)
            if aspec.enabled and loss.project is not None:
                # extrapolated coordinates can sit a whisker outside the
                # conjugate's domain (where l* = +inf would read the dual
                # as -inf); certify a feasible dual point instead -- still
                # a true bound by weak duality, and the projection
                # residual vanishes as the iterates converge
                alpha_eval = loss.project(alpha_eval, y)
            gargs = ((state.w, alpha_eval, X, y, mask) if drifted
                     else (alpha_eval, X, y, mask))
            if gap_run is None:
                gap_run, dtc = aot_compile(gap_fn, *gargs)
                pending_compile += dtc
            with jax.profiler.TraceAnnotation("cocoa_certificate"):
                (pval, dval, g), cert_s = fenced_call(gap_run, *gargs)
            gap = float(g)
            totals = tracer.totals()
            rec = RoundRecord(
                round=t + 1,
                round_global=base_round + t + 1,
                rounds_in_record=covered,
                gap=gap, primal=float(pval), dual=float(dval),
                compile_s=pending_compile, execute_s=exec_acc,
                certificate_s=cert_s,
                wire_floats=totals["comm_floats"] - prev_floats,
                wire_bytes=4 * (totals["comm_floats"] - prev_floats),
                hops=tuple(tracer.per_hop()),
                comm=totals,
                budgets=(tuple(int(b) for b in budgets)
                         if budgets is not None else None),
                throughput=(tuple(float(r) for r in throughput.rate)
                            if throughput is not None else None))
            prev_floats = totals["comm_floats"]
            pending_compile, exec_acc, covered = 0.0, 0.0, 0
            agg.emit(rec)
            if obs is not None:
                obs.emit(rec)
            if on_round is not None:
                on_round(t + 1, state, gap)
            if gap <= eps_gap:
                break
    return SolveResult(state, agg.history())
