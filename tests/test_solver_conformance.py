"""LocalSolver conformance: every registered solver honors Assumption 1's
contract, checked through the same capability-flag dispatch the framework
uses (core.cocoa._worker_body), so what passes here is what a round runs.

The contract (core.solvers.register_solver):
  * du is the sigma'-scaled v-space delta of its own dalpha --
    du ~= (sigma'/(tau n)) A_k^T dalpha -- so the one-vector-per-round
    exchange reconstructs exactly the update the dual step took,
  * masked (padding) rows are EXACT no-ops: dalpha there is identically
    zero and contributes nothing to du,
  * SDCAResult.steps honestly reports inner steps executed (the Theta /
    deadline accounting `runtime.straggler` budgets against),
  * dense/sparse twins (LocalSolver.sparse_name) agree on the same data.

Each contract test runs every solver under every loss of `core.losses`;
where a solver has no update for a loss (the Pallas kernels take only the
closed-form ones) the case checks that it says so with a ValueError.

Also pins the two `local_sdca_deadline` satellites of the accel PR: the
hoisted-sqnorms path is bit-for-bit with the self-computed one, and a
static (python int) budget -- which bounds the fori_loop trip count
itself instead of paying all H iterations -- is bit-for-bit with the
traced-budget lowering of the same value.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cocoa import _worker_body
from repro.core.losses import LOSSES, get_loss
from repro.core.regularizers import L2, get_regularizer
from repro.core.solvers import (SOLVERS, LocalSolver, SDCAResult, get_solver,
                                local_sdca_deadline, register_solver,
                                sparse_counterpart)
from repro.data.sparse import SparseShards, densify
from repro.kernels.local_sdca import CLOSED_FORM_LOSSES

NK, D = 64, 96
MASKED = 9          # trailing padded rows
LAM = 1e-3
SIGMA_P = 4.0
H = 128


def _dense_inputs(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((NK, D)).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    mask = np.ones(NK, np.float32)
    mask[NK - MASKED:] = 0.0
    X *= mask[:, None]                     # padding carries no data
    y = np.sign(rng.standard_normal(NK)).astype(np.float32)
    alpha = (0.1 * rng.standard_normal(NK)).astype(np.float32) * mask
    v = (0.2 * rng.standard_normal(D)).astype(np.float32)
    return (jnp.asarray(X, dtype), jnp.asarray(y), jnp.asarray(alpha),
            jnp.asarray(mask), jnp.asarray(v, dtype))


def _to_sparse(X):
    """Exact padded-ELL form of a dense block (every row fully stored)."""
    Xn = np.asarray(X)
    nk, d = Xn.shape
    cols = np.tile(np.arange(d, dtype=np.int32), (nk, 1))
    nnz = np.full((nk,), d, np.int32)
    return SparseShards(jnp.asarray(cols), jnp.asarray(Xn), jnp.asarray(nnz),
                        d=d)


KERNELS = ("sdca_kernel", "sdca_sparse_kernel")


def _rejects(solver: LocalSolver, loss: str) -> bool:
    """The Pallas kernels implement only the closed-form coordinate
    updates; logistic's guarded Newton step stays on the jnp solvers."""
    return solver.name in KERNELS and not loss.startswith(CLOSED_FORM_LOSSES)


def _run(solver: LocalSolver, *, loss="smooth_hinge1", budget=None,
         sqnorms=None, seed=0):
    """One worker's local solve on the shared inputs. Where the solver
    rejects `loss`, asserts the ValueError and returns None for the
    result."""
    X, y, alpha, mask, v = _dense_inputs(seed)
    data = _to_sparse(X) if solver.sparse else X
    n = float(NK - MASKED)
    solve = functools.partial(
        _worker_body, data, y, alpha, mask, v, jax.random.PRNGKey(seed),
        loss=get_loss(loss), lam=LAM, n=n, sigma_p=SIGMA_P, H=H,
        solver=solver, budget=budget, sqnorms=sqnorms, reg=L2)
    if _rejects(solver, loss):
        with pytest.raises(ValueError,
                           match=f"kernel supports closed-form losses .*"
                                 f"got {loss!r}"):
            solve()
        return None, (X, y, alpha, mask, v, n)
    return solve(), (X, y, alpha, mask, v, n)


ALL_SOLVERS = sorted(SOLVERS)
ALL_LOSSES = sorted(LOSSES)


@pytest.mark.parametrize("loss", ALL_LOSSES)
@pytest.mark.parametrize("name", ALL_SOLVERS)
def test_du_consistent_with_dalpha(name, loss):
    """du ~= (sigma'/(tau n)) A^T dalpha for every registered solver --
    the exchanged vector is exactly the image of the dual step taken."""
    res, (X, y, alpha, mask, v, n) = _run(get_solver(name), loss=loss)
    if res is None:
        return
    scale = SIGMA_P / (L2.tau(LAM) * n)
    ref = scale * (np.asarray(X).T @ np.asarray(res.dalpha))
    np.testing.assert_allclose(np.asarray(res.du), ref, rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("loss", ALL_LOSSES)
@pytest.mark.parametrize("name", ALL_SOLVERS)
def test_masked_rows_are_exact_noops(name, loss):
    """Padding rows (mask 0) take no dual step at all."""
    res, _ = _run(get_solver(name), loss=loss)
    if res is None:
        return
    tail = np.asarray(res.dalpha)[NK - MASKED:]
    assert float(np.max(np.abs(tail))) == 0.0, (name, tail)


@pytest.mark.parametrize("loss", ALL_LOSSES)
@pytest.mark.parametrize("name", ALL_SOLVERS)
def test_steps_honestly_reported(name, loss):
    """SDCAResult.steps reports the inner steps actually executed: H for
    the fixed-H solvers (the kernel rounds H onto whole passes of its
    padded block), min(H, budget) for the deadline solver."""
    ls = get_solver(name)
    assert ls.theta_steps, f"{name} must report Theta steps honestly"
    res, _ = _run(ls, loss=loss, budget=(37 if ls.deadline else None))
    if res is None:
        return
    if ls.deadline:
        expected = min(H, 37)
    elif ls.name in ("sdca_kernel", "sdca_sparse_kernel"):
        # kernels round H onto whole random-permutation passes of the block
        expected = max(1, round(H / NK)) * NK
    else:
        expected = H
    assert int(res.steps) == expected, (ls.name, int(res.steps), expected)


@pytest.mark.parametrize("loss", ALL_LOSSES)
@pytest.mark.parametrize("name", [n for n in ALL_SOLVERS
                                  if get_solver(n).dense
                                  and sparse_counterpart(n) not in (None, n)])
def test_dense_sparse_twins_agree(name, loss):
    """A solver and its declared padded-ELL twin take the same step on the
    same data (the ELL form here stores every row exactly), and reject the
    same losses."""
    dense = get_solver(name)
    twin = get_solver(sparse_counterpart(name))
    r_dense, _ = _run(dense, loss=loss)
    r_sparse, _ = _run(twin, loss=loss)
    assert (r_dense is None) == (r_sparse is None)
    if r_dense is None:
        return
    np.testing.assert_allclose(np.asarray(r_dense.du),
                               np.asarray(r_sparse.du),
                               rtol=5e-4, atol=5e-5)


def test_registry_is_open():
    """register_solver admits an external descriptor, _worker_body runs it
    through the same flag dispatch, and duplicate names are rejected."""
    def trivial(X_k, y_k, alpha_k, mask_k, v, rng, loss, lam, n, sigma_p, H,
                reg=L2):
        z = jnp.zeros_like(alpha_k)
        return SDCAResult(z, jnp.zeros_like(v), jnp.asarray(0))

    ls = register_solver(LocalSolver("_conformance_trivial", trivial,
                                     theta_steps=True))
    try:
        assert get_solver("_conformance_trivial") == ls
        res, _ = _run(ls)
        assert float(jnp.max(jnp.abs(res.dalpha))) == 0.0
        with pytest.raises(ValueError, match="already registered"):
            register_solver(LocalSolver("_conformance_trivial", trivial))
        with pytest.raises(TypeError):
            register_solver(trivial)
    finally:
        del SOLVERS["_conformance_trivial"]


def test_get_solver_unknown_name():
    with pytest.raises(KeyError, match="unknown solver"):
        get_solver("no_such_solver")


# ----------------------------------------------------------------------------
# deadline-solver pins (sqnorms hoisting + static-budget loop bounding)
# ----------------------------------------------------------------------------

def _deadline(budget, sqnorms=None, H_=H):
    X, y, alpha, mask, v = _dense_inputs(3)
    return local_sdca_deadline(X, y, alpha, mask, v, jax.random.PRNGKey(3),
                               get_loss("smooth_hinge"), LAM,
                               float(NK - MASKED), SIGMA_P, H_, budget,
                               sqnorms=sqnorms)


def test_deadline_hoisted_sqnorms_bit_for_bit():
    """Passing round-invariant ||x_i||^2 in (the hoisting the round loop
    does once) is bit-for-bit with the self-computed path."""
    X, _, _, mask, _ = _dense_inputs(3)
    sq = jnp.sum(X * X, axis=-1) * mask
    a = _deadline(50)
    b = _deadline(50, sqnorms=sq)
    assert np.array_equal(np.asarray(a.dalpha), np.asarray(b.dalpha))
    assert np.array_equal(np.asarray(a.du), np.asarray(b.du))


def test_deadline_static_budget_bounds_loop_bit_for_bit():
    """A concrete python-int budget bounds the fori_loop trip count itself
    (satellite bugfix: no more paying all H iterations for a small
    budget); the result is bit-for-bit identical to the traced-`where`
    lowering of the same value, because both draw the identical (H,)
    index stream."""
    for b in (1, 17, 50, H, H + 40):
        static = _deadline(b)                       # python int -> bounded
        traced = _deadline(jnp.asarray(b))          # traced -> where-guard
        assert np.array_equal(np.asarray(static.dalpha),
                              np.asarray(traced.dalpha)), b
        assert np.array_equal(np.asarray(static.du),
                              np.asarray(traced.du)), b
        assert int(static.steps) == int(traced.steps) == min(b, H)


def _loop_trip_counts(jaxpr):
    """Every scan length / concrete while-bound reachable in a jaxpr."""
    trips = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            trips.append(int(eqn.params["length"]))
        for sub in jax.core.jaxprs_in_params(eqn.params) \
                if hasattr(jax.core, "jaxprs_in_params") else []:
            trips += _loop_trip_counts(sub)
    return trips


def test_deadline_static_budget_trip_count():
    """The static-budget lowering really bounds the loop: its jaxpr's
    scan runs min(H, budget) trips, the traced lowering's runs H."""
    X, y, alpha, mask, v = _dense_inputs(3)
    fn = functools.partial(local_sdca_deadline, X, y, alpha, mask, v,
                           jax.random.PRNGKey(3), get_loss("smooth_hinge"),
                           LAM, float(NK - MASKED), SIGMA_P, H)
    short = _loop_trip_counts(jax.make_jaxpr(lambda: fn(5))().jaxpr)
    full = _loop_trip_counts(jax.make_jaxpr(lambda: fn(jnp.asarray(5)))()
                             .jaxpr)
    assert 5 in short and H not in short, short
    assert H in full, full
