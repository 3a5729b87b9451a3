"""`solve` across the objectives a config can name: every loss of
`core.losses.LOSSES` x every regularizer family of `CoCoAConfig.reg` x
both data formats (dense blocks, padded-ELL shards), K = 4 on the vmap
backend at the safe sigma' = gamma K.

Each case holds the run to what CoCoA+ guarantees there:
  * the certificate the last record reports is the duality gap of the
    returned duals, recomputed in float64 by `core.duality`,
  * the returned shared vector is v(alpha) of the returned duals, and the
    gap certified at the primal point it serves is that same gap,
  * weak duality: no record's gap is negative,
  * the dual objective never decreases from one round to the next (Lemma 3
    at sigma' = gamma K; for the L1 family through the convexity of g*),
  * every alpha_i stays in its loss's dual domain (where l*(-alpha_i) is
    finite), so the dual is a real lower bound.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CoCoAConfig, duality, solve
from repro.core.losses import LOSSES, get_loss
from repro.core.regularizers import get_regularizer
from repro.data import partition
from repro.data.sparse import make_sparse_classification, partition_sparse

K = 4
N, D = 250, 64          # 250 rows over 4 workers: two padding rows
DENSITY = 0.1
# a few rounds of half a pass each at a small lambda: every gap stays
# near 0.1 or above, far over the float32 rounding of P and D (~1e-7)
LAM = 1e-3
H = 32
ROUNDS = 4
REGS = ("l2", "elastic:0.5", "l1s:0.1")


@functools.lru_cache(maxsize=None)
def _data(fmt: str):
    """The same rows and the same partition in either format (the dense
    and sparse partitioners share one rng stream)."""
    csr, y = make_sparse_classification(N, D, density=DENSITY, seed=0)
    if fmt == "dense":
        return tuple(jnp.asarray(a) for a in partition(csr.toarray(), y, K,
                                                       seed=1))
    return partition_sparse(csr, y, K, seed=1)


def _in64(state, X, y, mask, loss, reg):
    """From the returned state, in float64: the duality gap of its duals,
    v(alpha) of its duals, and the gap certified at the w it serves."""
    with jax.enable_x64(True):
        up = lambda a: (jnp.asarray(np.asarray(a), jnp.float64)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a)
        X64, y64, m64, a64, v64 = jax.tree.map(
            up, (X, y, mask, state.alpha, state.w))
        gap = duality.gap_decomposed(a64, X64, y64, m64, loss, LAM, reg)[2]
        v = duality.v_of_alpha(X64, a64, LAM, duality.effective_n(m64), reg)
        served = duality.gap_at_v(v64, a64, X64, y64, m64, loss, LAM, reg)[2]
        return float(gap), np.asarray(v), float(served)


@pytest.mark.parametrize("fmt", ["dense", "ell"])
@pytest.mark.parametrize("reg", REGS)
@pytest.mark.parametrize("loss", sorted(LOSSES))
def test_solve_certifies_every_objective(loss, reg, fmt):
    X, y, mask = _data(fmt)
    cfg = CoCoAConfig.adding(K, loss=loss, lam=LAM, H=H, reg=reg)
    r = solve(cfg, X, y, mask, rounds=ROUNDS, gap_every=1)
    h = r.history
    assert h["round"] == list(range(1, ROUNDS + 1))

    want, v, served = _in64(r.state, X, y, mask, get_loss(loss),
                            get_regularizer(reg))
    np.testing.assert_allclose(h["gap"][-1], want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(r.state.w), v, rtol=1e-5,
                               atol=1e-6 * np.max(np.abs(v)))
    np.testing.assert_allclose(h["gap"][-1], served, rtol=1e-5)

    assert min(h["gap"]) >= -1e-6, h["gap"]

    dual = np.asarray(h["dual"], np.float64)
    assert np.all(dual[1:] >= dual[:-1] - 1e-6 * np.abs(dual[:-1])), dual

    alpha, yy = np.asarray(r.state.alpha), np.asarray(y)
    live = np.asarray(mask) > 0
    feasible = np.asarray(get_loss(loss).project(alpha, yy))
    np.testing.assert_allclose(alpha[live], feasible[live], atol=1e-6)
    conj = np.asarray(get_loss(loss).conj(alpha, yy))
    assert np.all(np.isfinite(conj[live])), loss
