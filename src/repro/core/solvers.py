"""Local solvers for the CoCoA+ subproblem (Assumption 1: any Theta < 1 works).

Every solver is registered as a frozen `LocalSolver` descriptor (callable +
capability flags), mirroring the `Regularizer` refactor: the framework
driver (`core.cocoa`) picks solvers by contract -- can it consume padded-ELL
shards, can it complete a feature-sharded partial dot over `model_axis`,
does it take a per-round step `budget` -- instead of string-matching names.
Registration is open (`register_solver`): an external solver satisfies the
paper's Assumption 1 by contract (return a Theta-approximate `SDCAResult`
whose `du` is the sigma'-scaled v-space delta and whose `steps` honestly
reports the inner work done) and plugs into both backends, the comm layer,
and the accelerated outer loop (`core.accel`) unchanged.
`tests/test_solver_conformance.py` runs the contract over every registered
descriptor.

LOCALSDCA (Algorithm 2): H steps of single-coordinate exact maximization of
G_k^{sigma'}, using the closed forms from losses.py. The solver carries the
local *scaled dual-side* estimate

    v_loc = v + (sigma'/(tau n)) * A Delta_alpha     (Appendix C, eq. 50,
                                                      generalized: tau is the
                                                      regularizer's strong-
                                                      convexity constant)

and evaluates the primal point through the conjugate map per step,

    z_i = x_i^T grad g*(tau v_loc)  =  x_i^T reg.conj_grad(v_loc)

so each coordinate step costs one d-dot plus one elementwise map and one
d-axpy. Under the default L2 regularizer conj_grad is the identity and
tau = lambda, so v_loc IS the old u = w + (sigma'/(lambda n)) A Delta_alpha
and the emitted jaxpr is bit-for-bit the paper's hard-coded path. For the
L1 family the map is a soft-threshold, which keeps every z evaluated at the
*actual* (sparse) primal iterate -- the prox-SDCA flavor of the generalized
subproblem. The sparse Pallas kernel fuses the same soft-threshold in-kernel
(static `prox_kappa`, applied per gathered entry -- per-step exact, identical
to this loop); only the dense kernel and regularizers without the scalar
threshold form keep the round-start hoisted map (the linearized
CoCoA-general subproblem), see repro.kernels.ops. Likewise the per-step
model-axis psum below (feature-sharded mode) has a kernel-path counterpart:
the block-batched z-exchange schedule in repro.kernels.sparse_sdca
(`sparse_local_sdca_zx`), which trades per-step scalar collectives for one
block_rows-sized psum per block at the cost of within-block staleness (a
Theta-approximation, gap-certified).

This is the hot loop that the Pallas TPU kernel in repro.kernels.local_sdca
implements; the pure JAX version here is the reference/portable path (and
the oracle the kernel is validated against lives in repro.kernels.ref).

LOCALGD: full-(local)-batch projected(-free) gradient ascent on G_k --
demonstrates the "arbitrary local solver" claim with a structurally different
method (only valid for smooth losses).

Both are written per-worker on (nk, d) blocks so the same body runs under
vmap (simulation) and shard_map (production).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .losses import Loss
from .regularizers import L2, Regularizer


class SDCAResult(NamedTuple):
    dalpha: jnp.ndarray     # (nk,) local dual update
    du: jnp.ndarray         # (d,)  = (sigma'/(tau n)) * A dalpha  (local
                            #        v-space delta, already sigma'-scaled)
    steps: jnp.ndarray      # number of inner steps actually executed


def local_sdca(X_k: jnp.ndarray, y_k: jnp.ndarray, alpha_k: jnp.ndarray,
               mask_k: jnp.ndarray, v: jnp.ndarray, rng: jax.Array,
               loss: Loss, lam: float, n, sigma_p: float, H: int,
               sqnorms=None, model_axis=None,
               reg: Regularizer = L2) -> SDCAResult:
    """H randomized coordinate-ascent steps on G_k^{sigma'}. X_k: (nk, d).

    `v` is the shared scaled dual-side vector (== the primal w under L2).

    `sqnorms`: optional precomputed ||x_i||^2 (they are round-invariant;
    recomputing them costs one full X stream per round -- hoisted per
    EXPERIMENTS.md section Perf, iteration C2).

    `model_axis`: feature-sharded mode (inside shard_map on a 2-D mesh):
    X_k and v are this device's feature slice (nk, d_local) / (d_local,),
    the per-step dot is a *partial* z that one scalar psum over the model
    axis completes (the conjugate map is elementwise, hence shard-local),
    and the axpy touches only the local v shard. The coordinate decisions
    (delta) are then identical on every model shard by construction.
    Requires precomputed *global* `sqnorms` -- the local slice can't see
    the other shards' mass."""
    nk = X_k.shape[0]
    if model_axis is not None and sqnorms is None:
        raise ValueError("feature-sharded local_sdca needs global sqnorms; "
                         "the local slice can't reconstruct ||x_i||^2")
    if sqnorms is None:
        sqnorms = jnp.sum(X_k * X_k, axis=-1) * mask_k   # padded rows -> 0
    scale = sigma_p / (reg.tau(lam) * n)
    idxs = jax.random.randint(rng, (H,), 0, nk)

    def body(h, carry):
        dalpha, u = carry
        i = idxs[h]
        # barrier: x feeds two consumers (dot + axpy); without it XLA
        # duplicates the row gather per consumer (2x row traffic; measured
        # in EXPERIMENTS.md section Perf, iteration C3)
        x = jax.lax.optimization_barrier(X_k[i])
        z = jnp.dot(x, reg.conj_grad(u, lam))
        if model_axis is not None:
            z = jax.lax.psum(z, model_axis)     # complete the sharded dot
        abar = alpha_k[i] + dalpha[i]
        q = scale * sqnorms[i]
        delta = loss.cd_update(abar, z, q, y_k[i]) * mask_k[i]
        dalpha = dalpha.at[i].add(delta)
        u = u + (scale * delta) * x
        return dalpha, u

    dalpha0 = jnp.zeros(nk, X_k.dtype)
    dalpha, u = jax.lax.fori_loop(0, H, body, (dalpha0, v.astype(X_k.dtype)))
    return SDCAResult(dalpha, u - v, jnp.asarray(H))


def local_sdca_deadline(X_k, y_k, alpha_k, mask_k, v, rng, loss, lam, n,
                        sigma_p: float, H: int, budget, sqnorms=None,
                        reg: Regularizer = L2) -> SDCAResult:
    """Straggler-tolerant variant: runs min(H, budget) steps.

    `budget` is a per-worker scalar (steps affordable before the round
    deadline, e.g. measured throughput x remaining time). Theta degrades, the
    round never blocks: this is the paper's Assumption-1 knob used as
    straggler mitigation (DESIGN.md section 8).

    `sqnorms`: optional precomputed ||x_i||^2, hoisted exactly like
    `local_sdca`'s (they are round-invariant; recomputing streams the whole
    shard once per round for nothing).

    A *static* (plain Python/NumPy int) `budget` bounds the `fori_loop`
    itself at min(H, budget) -- a concrete small budget no longer pays the
    full H iterations of dead masked steps. A traced `budget` keeps the
    fixed-H loop with the `where` mask (the trip count must be static under
    jit). Both paths draw the same (H,) index stream and take identical
    coordinate steps, so the returned `SDCAResult` is bit-for-bit the same
    (tests/test_runtime.py pins it)."""
    nk = X_k.shape[0]
    if sqnorms is None:
        sqnorms = jnp.sum(X_k * X_k, axis=-1) * mask_k
    scale = sigma_p / (reg.tau(lam) * n)
    idxs = jax.random.randint(rng, (H,), 0, nk)
    static_budget = isinstance(budget, (int, np.integer))
    hmax = (min(int(H), int(budget)) if static_budget
            else jnp.minimum(jnp.asarray(H), budget))

    def body(h, carry):
        dalpha, u = carry
        i = idxs[h]
        # same barrier as local_sdca: x feeds two consumers (dot + axpy);
        # without it XLA duplicates the row gather per consumer (2x row
        # traffic -- measured in EXPERIMENTS.md section Perf, iteration C3)
        x = jax.lax.optimization_barrier(X_k[i])
        z = jnp.dot(x, reg.conj_grad(u, lam))
        abar = alpha_k[i] + dalpha[i]
        q = scale * sqnorms[i]
        delta = loss.cd_update(abar, z, q, y_k[i]) * mask_k[i]
        if not static_budget:
            # dead (past-deadline) steps are exact no-ops: delta 0 leaves
            # both dalpha and u untouched, so the masked fixed-H loop and
            # the bounded static loop take identical live steps
            delta = jnp.where(h < hmax, delta, 0.0)
        dalpha = dalpha.at[i].add(delta)
        u = u + (scale * delta) * x
        return dalpha, u

    dalpha0 = jnp.zeros(nk, X_k.dtype)
    trip = hmax if static_budget else H
    dalpha, u = jax.lax.fori_loop(0, trip, body,
                                  (dalpha0, v.astype(X_k.dtype)))
    return SDCAResult(dalpha, u - v, jnp.asarray(hmax))


def local_gd(X_k, y_k, alpha_k, mask_k, v, rng, loss, lam, n,
             sigma_p: float, H: int, lr_scale: float = 1.0,
             reg: Regularizer = L2) -> SDCAResult:
    """Projected-gradient ascent on G_k, full local batch -- the "arbitrary
    local solver" demonstration (Assumption 1 only needs Theta < 1).

    grad_i(n*G_k) = -conj'(a_i + da_i) - x_i^T grad g*(tau v_loc) ,
        v_loc = v + (sigma'/(tau n)) A da.
    Step size 1/L with L = sigma' sigma_k /(tau n) + conj''_max, using
    sigma_k <= max_i ||x_i||^2 * n_k and conj'' ~ max(mu, 1). Iterates are
    projected onto the dual-feasible set after every step (losses.project).
    """
    del rng
    assert loss.conj_grad is not None and loss.project is not None
    nk = X_k.shape[0]
    scale = sigma_p / (reg.tau(lam) * n)
    sqmax = jnp.max(jnp.sum(X_k * X_k, axis=-1) * mask_k)
    L = scale * sqmax * nk + max(loss.mu, 1.0)
    lr = lr_scale / L

    def body(_, carry):
        dalpha, u = carry
        a = alpha_k + dalpha
        g = (-loss.conj_grad(a, y_k)
             - jnp.einsum("id,d->i", X_k, reg.conj_grad(u, lam))) * mask_k
        a_new = loss.project(a + lr * g, y_k) * mask_k
        step = a_new - a
        dalpha = dalpha + step
        u = u + scale * jnp.einsum("id,i->d", X_k, step)
        return dalpha, u

    dalpha0 = jnp.zeros(nk, X_k.dtype)
    dalpha, u = jax.lax.fori_loop(0, H, body, (dalpha0, v.astype(X_k.dtype)))
    return SDCAResult(dalpha, u - v, jnp.asarray(H))


def local_sdca_importance(X_k, y_k, alpha_k, mask_k, v, rng, loss, lam, n,
                          sigma_p: float, H: int, sqnorms=None,
                          reg: Regularizer = L2) -> SDCAResult:
    """LocalSDCA with importance sampling p_i ~ ||x_i||^2 + mean||x||^2
    (Zhao & Zhang-style mixed sampling). The paper's Appendix C explicitly
    invites plugging better local solvers -- Assumption 1 only needs Theta<1.
    On datasets with skewed row norms this reaches a given Theta in fewer
    inner steps (tests/test_cocoa.py::test_importance_sampling_helps)."""
    nk = X_k.shape[0]
    if sqnorms is None:
        sqnorms = jnp.sum(X_k * X_k, axis=-1) * mask_k
    scale = sigma_p / (reg.tau(lam) * n)
    mean_sq = jnp.sum(sqnorms) / jnp.maximum(jnp.sum(mask_k), 1.0)
    probs = (sqnorms + mean_sq) * mask_k
    probs = probs / jnp.sum(probs)
    idxs = jax.random.choice(rng, nk, (H,), p=probs)

    def body(h, carry):
        dalpha, u = carry
        i = idxs[h]
        # same two-consumer row gather as local_sdca -- barrier dedups it
        x = jax.lax.optimization_barrier(X_k[i])
        z = jnp.dot(x, reg.conj_grad(u, lam))
        abar = alpha_k[i] + dalpha[i]
        q = scale * sqnorms[i]
        delta = loss.cd_update(abar, z, q, y_k[i]) * mask_k[i]
        dalpha = dalpha.at[i].add(delta)
        u = u + (scale * delta) * x
        return dalpha, u

    dalpha0 = jnp.zeros(nk, X_k.dtype)
    dalpha, u = jax.lax.fori_loop(0, H, body, (dalpha0, v.astype(X_k.dtype)))
    return SDCAResult(dalpha, u - v, jnp.asarray(H))


def local_sdca_sparse(shard, y_k, alpha_k, mask_k, v, rng, loss: Loss,
                      lam: float, n, sigma_p: float, H: int,
                      sqnorms=None, model_axis=None,
                      reg: Regularizer = L2) -> SDCAResult:
    """LocalSDCA over a padded-ELL shard (repro.data.sparse.SparseShards,
    per-worker: cols/vals (nk, r_max)). Per step one r_max-gather dot and
    one r_max scatter-axpy (a segment-sum over the row's columns) instead
    of the dense d-dot/d-axpy -- O(nnz) work at the paper's densities.

    The conjugate map commutes with the gather (it is elementwise), so the
    generalized z costs reg.conj_grad on just the r_max gathered entries:
    z = sum_r vals[r] * grad g*(tau v_loc)[cols[r]] -- the sparse fast path
    stays O(nnz) for every regularizer (identity under L2, bit-for-bit).

    This is the portable jnp fallback for the Pallas kernel in
    repro.kernels.sparse_sdca; padding slots (col 0, val 0) are exact
    arithmetic no-ops, so no per-row nnz bookkeeping is needed here.

    `model_axis`: feature-sharded mode -- the shard's `cols` are
    *shard-local* column ids into the local v slice (d_local floats, see
    data.sparse.shard_features), the gather-dot yields a partial z
    completed by one scalar psum over the model axis, and the scatter-axpy
    touches only the local v shard. Requires precomputed *global*
    `sqnorms` (the slice only sees its own entries' mass)."""
    cols, vals = shard.cols, shard.vals
    nk = cols.shape[0]
    if model_axis is not None and sqnorms is None:
        raise ValueError("feature-sharded local_sdca_sparse needs global "
                         "sqnorms; the local ELL slice can't reconstruct "
                         "||x_i||^2")
    if sqnorms is None:
        sqnorms = jnp.sum(vals * vals, axis=-1) * mask_k
    scale = sigma_p / (reg.tau(lam) * n)
    idxs = jax.random.randint(rng, (H,), 0, nk)

    def body(h, carry):
        dalpha, u = carry
        i = idxs[h]
        # same barrier as the dense solver: ci/vi each feed two consumers
        # (gather-dot + scatter-axpy); without it XLA duplicates the row
        # gather per consumer (2x ELL-row traffic)
        ci, vi = jax.lax.optimization_barrier((cols[i], vals[i]))
        z = jnp.dot(vi, reg.conj_grad(u[ci], lam))
        if model_axis is not None:
            z = jax.lax.psum(z, model_axis)     # complete the sharded dot
        abar = alpha_k[i] + dalpha[i]
        q = scale * sqnorms[i]
        delta = loss.cd_update(abar, z, q, y_k[i]) * mask_k[i]
        dalpha = dalpha.at[i].add(delta)
        u = u.at[ci].add((scale * delta) * vi)
        return dalpha, u

    dalpha0 = jnp.zeros(nk, vals.dtype)
    dalpha, u = jax.lax.fori_loop(0, H, body, (dalpha0, v.astype(vals.dtype)))
    return SDCAResult(dalpha, u - v, jnp.asarray(H))


# ----------------------------------------------------------------------------
# The LocalSolver registry: frozen descriptors + open registration
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LocalSolver:
    """A Theta-approximate local subproblem solver, by contract.

    `fn` has the shared solver signature
        fn(X_k, y_k, alpha_k, mask_k, v, rng, loss, lam, n, sigma_p, H,
           [budget,] [sqnorms=, model_axis=,] reg=) -> SDCAResult
    where `X_k` is a dense (nk, d) block when `dense`, a padded-ELL
    `SparseShards` when `sparse`. Capability flags tell the framework
    driver what the callable can host; `core.cocoa` dispatches purely on
    them (no name matching), so an externally registered solver with the
    right flags runs under both backends, every reduce topology, and the
    accelerated outer loop without touching the framework:

        sparse       consumes padded-ELL SparseShards (cols/vals (nk, r))
        dense        consumes dense (nk, d) row blocks
        model_axis   completes feature-sharded partial dots over a named
                     mesh axis (takes `model_axis=` and requires *global*
                     `sqnorms` when sharded) -- 2-D mesh capable
        deadline     takes a per-round step `budget` operand (straggler /
                     Assumption-1 Theta knob); static budgets bound the
                     inner loop itself
        sqnorms      accepts hoisted round-invariant ||x_i||^2
        theta_steps  `SDCAResult.steps` honestly reports the inner steps
                     executed (the Theta accounting the conformance suite
                     checks); every built-in reports honestly
        sparse_name  registry key of the padded-ELL counterpart the driver
                     transparently maps to when round inputs are sparse
    """
    name: str
    fn: Callable[..., SDCAResult]
    dense: bool = True
    sparse: bool = False
    model_axis: bool = False
    deadline: bool = False
    sqnorms: bool = False
    theta_steps: bool = True
    sparse_name: Optional[str] = None

    def __hash__(self):  # usable as a static jit arg, like Loss/Regularizer
        return hash(self.name)

    def __eq__(self, other):
        # name-keyed equality, including against the bare registry key
        # (consistent with __hash__, so dicts accept either form)
        if isinstance(other, str):
            return self.name == other
        return isinstance(other, LocalSolver) and self.name == other.name


SOLVERS: dict = {}


def register_solver(solver: LocalSolver, *,
                    overwrite: bool = False) -> LocalSolver:
    """Register a LocalSolver descriptor under its name. External solvers
    satisfy Assumption 1 by contract: return an `SDCAResult` whose `du` is
    the sigma'-scaled v-space delta (sigma'/(tau n)) A dalpha restricted
    to the local shard, zero `dalpha` on masked (padding) rows, and an
    honest `steps` count. Registration is open -- plugging in a new solver
    is one call, not a framework edit."""
    if not isinstance(solver, LocalSolver):
        raise TypeError(f"register_solver wants a LocalSolver descriptor, "
                        f"got {type(solver).__name__}")
    if solver.name in SOLVERS and not overwrite:
        raise ValueError(f"solver {solver.name!r} is already registered; "
                         f"pass overwrite=True to replace it")
    SOLVERS[solver.name] = solver
    return solver


def get_solver(name) -> LocalSolver:
    """LocalSolver descriptor by registry key (instances pass through)."""
    if isinstance(name, LocalSolver):
        return name
    try:
        return SOLVERS[name]
    except KeyError:
        raise KeyError(f"unknown solver {name!r}; registered: "
                       f"{sorted(SOLVERS)}") from None


def _lazy_kernel(attr: str) -> Callable[..., SDCAResult]:
    """Import-cycle-free binding for the Pallas kernel entry points
    (repro.kernels.ops imports SDCAResult from here). The indirection is
    one Python call per round trace -- free under jit."""
    def call(*args, **kwargs):
        from repro.kernels import ops as kernel_ops
        return getattr(kernel_ops, attr)(*args, **kwargs)
    call.__name__ = attr
    return call


register_solver(LocalSolver(
    "sdca", local_sdca, model_axis=True, sqnorms=True,
    sparse_name="sdca_sparse"))
register_solver(LocalSolver(
    "sdca_deadline", local_sdca_deadline, deadline=True, sqnorms=True))
register_solver(LocalSolver(
    "sdca_importance", local_sdca_importance, sqnorms=True))
register_solver(LocalSolver(
    "sdca_sparse", local_sdca_sparse, dense=False, sparse=True,
    model_axis=True, sqnorms=True))
register_solver(LocalSolver("gd", local_gd))
# Pallas kernel paths: the dense kernel is M=1-only (a pallas body cannot
# host the per-step model-axis collective); the sparse kernel runs M>1
# natively via the block-batched z-exchange schedule.
register_solver(LocalSolver(
    "sdca_kernel", _lazy_kernel("local_sdca_block"),
    sparse_name="sdca_sparse_kernel"))
register_solver(LocalSolver(
    "sdca_sparse_kernel", _lazy_kernel("sparse_local_sdca_block"),
    dense=False, sparse=True, model_axis=True, sqnorms=True))


def sparse_counterpart(name) -> Optional[str]:
    """Registry key of the padded-ELL solver `name` resolves to on sparse
    round inputs (itself when already sparse), or None when it has no
    sparse path."""
    ls = get_solver(name)
    if ls.sparse:
        return ls.name
    return ls.sparse_name
