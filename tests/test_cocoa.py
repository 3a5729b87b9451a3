"""Algorithm-1 behaviour: convergence, adding-vs-averaging, divergence of
naive adding, Assumption-1 solver quality, Theorem-10 style linear rate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CoCoAConfig, duality, solve
from repro.core.losses import get_loss
from repro.core.solvers import local_gd, local_sdca
from repro.core.subproblem import subproblem_value
from repro.data import make_classification, partition


@pytest.fixture(scope="module")
def problem():
    X, y = make_classification(2048, 48, seed=0)
    return partition(X, y, 8, seed=1)


def test_adding_converges_and_beats_averaging(problem):
    Xp, yp, mk = problem
    K = Xp.shape[0]
    kw = dict(loss="hinge", lam=1e-3, H=256)
    r_add = solve(CoCoAConfig.adding(K, **kw), Xp, yp, mk, rounds=40,
                  gap_every=40)
    r_avg = solve(CoCoAConfig.averaging(K, **kw), Xp, yp, mk, rounds=40,
                  gap_every=40)
    assert r_add.history["gap"][-1] < 0.1
    assert r_add.history["gap"][-1] < r_avg.history["gap"][-1]


def test_naive_adding_diverges_or_stalls(problem):
    """gamma=1 with sigma'=1 (no damping) must NOT converge -- the paper's
    motivating failure case."""
    Xp, yp, mk = problem
    bad = CoCoAConfig(gamma=1.0, sigma_p=1.0, loss="hinge", lam=1e-3, H=256)
    good = CoCoAConfig.adding(Xp.shape[0], loss="hinge", lam=1e-3, H=256)
    rb = solve(bad, Xp, yp, mk, rounds=15, gap_every=15)
    rg = solve(good, Xp, yp, mk, rounds=15, gap_every=15)
    assert rb.history["gap"][-1] > 5 * rg.history["gap"][-1]


def test_gap_certificate_monotone_trend(problem):
    Xp, yp, mk = problem
    r = solve(CoCoAConfig.adding(Xp.shape[0], loss="smooth_hinge1", lam=1e-3,
                                 H=256), Xp, yp, mk, rounds=30, gap_every=5)
    gaps = r.history["gap"]
    assert gaps[-1] < gaps[0]
    assert gaps[-1] >= 0


def test_smooth_loss_linear_rate(problem):
    """Theorem 10: smooth losses converge linearly in dual suboptimality;
    check the gap decays at least geometrically over round blocks."""
    Xp, yp, mk = problem
    r = solve(CoCoAConfig.adding(Xp.shape[0], loss="squared", lam=1e-2,
                                 H=512), Xp, yp, mk, rounds=24, gap_every=4)
    g = r.history["gap"]
    # require roughly geometric decay: every 3 observations shrink 1.5x
    assert g[-1] < g[0] / 10


@pytest.mark.parametrize("solver", ["sdca", "gd"])
def test_assumption1_positive_progress(problem, solver):
    """Any Theta<1 solver must improve G_k over the zero update (Assumption 1
    with Theta<1 implies G(dA) > G(0) whenever 0 is not optimal)."""
    Xp, yp, mk = problem
    K, nk, d = Xp.shape
    loss = get_loss("smooth_hinge1" if solver == "gd" else "hinge")
    lam, sp = 1e-3, float(K)
    n = float(jnp.sum(mk))
    w = jnp.zeros(d)
    alpha = jnp.zeros(nk)
    fn = local_gd if solver == "gd" else local_sdca
    res = fn(Xp[0], yp[0], alpha, mk[0], w, jax.random.PRNGKey(0), loss,
             lam, n, sp, 200)
    g0 = subproblem_value(jnp.zeros(nk), w, alpha, Xp[0], yp[0], mk[0],
                          loss, lam, n, K, sp)
    g1 = subproblem_value(res.dalpha, w, alpha, Xp[0], yp[0], mk[0],
                          loss, lam, n, K, sp)
    assert float(g1) > float(g0)


def test_kernel_solver_plugs_in(problem):
    Xp, yp, mk = problem
    r = solve(CoCoAConfig.adding(Xp.shape[0], loss="hinge", lam=1e-3, H=256,
                                 solver="sdca_kernel"),
              Xp, yp, mk, rounds=10, gap_every=10)
    assert r.history["gap"][-1] < 0.6


def test_averaged_iterate_certificate(problem):
    """Theorem 8 outputs the averaged iterate; its gap must also be valid."""
    Xp, yp, mk = problem
    cfg = CoCoAConfig.adding(Xp.shape[0], loss="hinge", lam=1e-3, H=256,
                             average_iterates=True)
    r = solve(cfg, Xp, yp, mk, rounds=20, gap_every=20)
    assert r.history["gap"][-1] >= 0
    assert r.history["gap"][-1] < 1.0


def test_scaling_K_strong_scaling():
    """Fig-2 phenomenon: with fixed total work per round (H ~ n/K), adding
    stays useful as K grows while averaging degrades markedly."""
    X, y = make_classification(4096, 32, seed=3)
    gaps_add, gaps_avg = [], []
    for K in (4, 16):
        Xp, yp, mk = partition(X, y, K, seed=4)
        H = 4096 // K
        a = solve(CoCoAConfig.adding(K, loss="hinge", lam=1e-3, H=H),
                  Xp, yp, mk, rounds=25, gap_every=25)
        v = solve(CoCoAConfig.averaging(K, loss="hinge", lam=1e-3, H=H),
                  Xp, yp, mk, rounds=25, gap_every=25)
        gaps_add.append(a.history["gap"][-1])
        gaps_avg.append(v.history["gap"][-1])
    # averaging degrades faster with K than adding
    assert gaps_avg[1] / max(gaps_avg[0], 1e-9) > \
        gaps_add[1] / max(gaps_add[0], 1e-9)


def test_theorem10_rate_bound(problem):
    """Quantitative Theorem 10 check (smooth loss): the dual suboptimality
    must decay at least as fast as the proven worst-case linear rate
    (1 - gamma(1-Theta) * lam*mu*n / (lam*mu*n + sigma_max*sigma'))^t,
    taking Theta ~ 0 for a near-exact local solver (large H)."""
    from repro.core import sigma as S

    Xp, yp, mk = problem
    K, nk, d = Xp.shape
    lam, n = 1e-2, float(jnp.sum(mk))
    cfg = CoCoAConfig.adding(K, loss="squared", lam=lam, H=4096)
    # dual optimum proxy: run long
    r_star = solve(cfg, Xp, yp, mk, rounds=120, gap_every=120)
    d_star = r_star.history["dual"][-1]
    r = solve(cfg, Xp, yp, mk, rounds=12, gap_every=1)
    sig_max = float(jnp.max(S.sigma_k(Xp, mk)))
    mu = 1.0                                      # squared loss
    rate = 1.0 - (lam * mu * n) / (lam * mu * n + sig_max * float(K))
    subopt = [max(d_star - dv, 1e-12) for dv in r.history["dual"]]
    bound = subopt[0]
    for t in range(1, len(subopt)):
        bound *= rate
        assert subopt[t] <= bound * 1.05 + 1e-8, (t, subopt[t], bound)


def test_importance_sampling_helps_on_skewed_data():
    """With heavy-tailed row norms, norm-proportional sampling reaches a
    smaller gap in the same number of inner steps (Appendix-C style
    'plug a better local solver')."""
    rng = np.random.default_rng(0)
    n, d, K = 2048, 32, 8
    X = rng.standard_normal((n, d)).astype(np.float32)
    scales = (0.05 + 2.0 * (rng.random(n) ** 6)).astype(np.float32)
    X = X / np.linalg.norm(X, axis=1, keepdims=True) * scales[:, None]
    w_star = rng.standard_normal(d).astype(np.float32)
    y = np.sign(X @ w_star).astype(np.float32)
    y[y == 0] = 1
    Xp, yp, mk = partition(X, y, K, seed=1)
    kw = dict(loss="hinge", lam=1e-3, H=128)
    r_u = solve(CoCoAConfig.adding(K, solver="sdca", **kw),
                Xp, yp, mk, rounds=25, gap_every=25, seed=3)
    r_i = solve(CoCoAConfig.adding(K, solver="sdca_importance", **kw),
                Xp, yp, mk, rounds=25, gap_every=25, seed=3)
    assert r_i.history["gap"][-1] < r_u.history["gap"][-1] * 1.02


def test_solve_sums_n_once_and_hands_it_to_every_round(problem,
                                                       monkeypatch):
    """The number of real rows is fixed for a solve: `solve` sums the mask
    once and passes n to each round, which then takes no sum of its own
    (on a mesh, a scalar all-reduce a round)."""
    from repro.core import cocoa
    Xp, yp, mk = problem
    K = Xp.shape[0]
    seen, make = [], cocoa.make_round_vmap

    def spy(cfg, K):
        round_fn = make(cfg, K)

        def traced(state, X, y, mask, n=None, *rest):
            seen.append(n)
            return round_fn(state, X, y, mask, n, *rest)
        return traced
    monkeypatch.setattr(cocoa, "make_round_vmap", spy)
    solve(CoCoAConfig.adding(K, loss="hinge", lam=1e-3, H=64), Xp, yp, mk,
          rounds=3, gap_every=3)
    assert seen and all(n is not None for n in seen)
