"""End-to-end behaviour: the paper's workload solved to certificate accuracy."""
import numpy as np
import jax.numpy as jnp

from repro.core import CoCoAConfig, solve
from repro.data import load, partition


def test_end_to_end_svm_to_certificate():
    """covtype-like hinge SVM: CoCoA+ reaches a small duality gap, and the
    primal accuracy is sane -- the full paper pipeline."""
    X, y = load("tiny")
    Xp, yp, mk = partition(X, y, 8, seed=0)
    cfg = CoCoAConfig.adding(8, loss="hinge", lam=1e-3, H=512)
    r = solve(cfg, Xp, yp, mk, rounds=60, eps_gap=5e-3, gap_every=5)
    assert r.history["gap"][-1] < 5e-2
    # training accuracy of the learned w
    z = np.asarray(jnp.einsum("kid,d->ki", Xp, r.state.w))
    acc = float((np.sign(z) == np.asarray(yp))[np.asarray(mk) > 0].mean())
    assert acc > 0.8

