"""Node-failure handling for CoCoA+.

Dual-safe drop: losing worker k's state = resetting alpha_[k] to 0. Any
alpha with alpha_[k] = 0 is still dual-feasible, so D(alpha) remains a valid
lower bound and the duality-gap certificate stays correct -- the run degrades
instead of corrupting. The shared w must then be re-derived as w(alpha)
(eq. 3) to stay consistent with the surviving duals; the data shard itself is
re-read from storage (here: regenerated/reloaded by the caller).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import duality
from repro.core.cocoa import CoCoAState
from repro.core.regularizers import L2, Regularizer


def drop_worker(state: CoCoAState, k: int) -> CoCoAState:
    """Zero worker k's duals (its machine died and lost local state).

    The error-feedback residual dies with the machine too: it is
    uncommunicated local compression debt, and zeroing it is always safe
    (EF residuals only affect future messages, never dual feasibility)."""
    alpha = state.alpha.at[k].set(0.0)
    bar = state.alpha_bar.at[k].set(0.0)
    ef = state.ef.at[k].set(0.0)
    return state._replace(alpha=alpha, alpha_bar=bar, ef=ef)


def recover_consistent_w(state: CoCoAState, X, mask, lam: float,
                         reg: Regularizer = L2) -> CoCoAState:
    """Recompute the shared state after a drop so it is consistent with the
    surviving duals. The state's leaf carries v = A alpha/(tau n) (the
    primal w is reg.conj_grad of it); under L2 this is exactly the old
    w(alpha) rebuild."""
    n = duality.effective_n(mask)
    v = duality.v_of_alpha(X, state.alpha, lam, n, reg)
    return state._replace(w=v)


def fail_and_recover(state: CoCoAState, X, mask, lam: float, k: int,
                     reg: Regularizer = L2) -> CoCoAState:
    return recover_consistent_w(drop_worker(state, k), X, mask, lam, reg)
