"""Optimizer substrate: CoCoA-DP (localdp), compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import compress as C
from repro.optim.localdp import LocalDPConfig, init_state, make_round_fn


def _mlp_problem(K=4, n_per=64, d=8, seed=0):
    rng = np.random.default_rng(seed)
    Xs = rng.standard_normal((K, n_per, d)).astype(np.float32)
    w_star = rng.standard_normal((d, 1)).astype(np.float32)
    ys = np.tanh(Xs @ w_star) + 0.01 * rng.standard_normal((K, n_per, 1)).astype(np.float32)
    params = {"w1": jnp.asarray(rng.standard_normal((d, 16)).astype(np.float32) * 0.3),
              "w2": jnp.asarray(rng.standard_normal((16, 1)).astype(np.float32) * 0.3)}

    def loss_fn(p, batch):
        X, y = batch
        h = jnp.tanh(X @ p["w1"])
        return jnp.mean((h @ p["w2"] - y) ** 2)

    return params, loss_fn, (jnp.asarray(Xs), jnp.asarray(ys))


def _global_loss(loss_fn, params, batches):
    return float(np.mean([loss_fn(params, (batches[0][k], batches[1][k]))
                          for k in range(batches[0].shape[0])]))


def test_localdp_adding_converges():
    params, loss_fn, batches = _mlp_problem()
    cfg = LocalDPConfig.adding(K=4, H=8, inner_lr=5e-2)
    rf = jax.jit(make_round_fn(loss_fn, cfg))
    st = init_state(params, cfg)
    l0 = _global_loss(loss_fn, st.params, batches)
    for _ in range(30):
        st = rf(st, batches)
    l1 = _global_loss(loss_fn, st.params, batches)
    assert np.isfinite(l1)
    assert l1 < 0.5 * l0


def test_localdp_adding_at_least_matches_averaging():
    params, loss_fn, batches = _mlp_problem(seed=1)
    radd = jax.jit(make_round_fn(
        loss_fn, LocalDPConfig.adding(K=4, H=8, inner_lr=5e-2)))
    ravg = jax.jit(make_round_fn(
        loss_fn, LocalDPConfig.averaging(K=4, H=8, inner_lr=5e-2)))
    sa = init_state(params, LocalDPConfig.adding(K=4))
    sv = init_state(params, LocalDPConfig.averaging(K=4))
    for _ in range(25):
        sa, sv = radd(sa, batches), ravg(sv, batches)
    la = _global_loss(loss_fn, sa.params, batches)
    lv = _global_loss(loss_fn, sv.params, batches)
    assert la <= lv * 1.5          # adding must not blow up vs averaging


@pytest.mark.parametrize("method", ["int8", "topk:0.25"])
def test_compression_error_feedback_converges(method):
    params, loss_fn, batches = _mlp_problem(seed=2)
    cfg = LocalDPConfig.adding(K=4, H=8, inner_lr=5e-2,
                               compress=method)
    rf = jax.jit(make_round_fn(loss_fn, cfg))
    st = init_state(params, cfg)
    l0 = _global_loss(loss_fn, st.params, batches)
    for _ in range(40):
        st = rf(st, batches)
    l1 = _global_loss(loss_fn, st.params, batches)
    assert l1 < 0.6 * l0


def test_compress_roundtrip_properties():
    tree = {"a": jnp.asarray(np.random.default_rng(0)
                             .standard_normal(64).astype(np.float32))}
    c8, ef = C.compress(tree, None, "int8")
    assert float(jnp.max(jnp.abs(c8["a"] - tree["a"]))) < \
        float(jnp.max(jnp.abs(tree["a"]))) / 64
    ck, ef2 = C.compress(tree, None, "topk:0.1")
    nz = int(jnp.sum(ck["a"] != 0))
    assert nz <= max(1, int(0.1 * 64)) + 1
    # error feedback holds the residual
    assert float(jnp.max(jnp.abs(ef2.residual["a"] + ck["a"] - tree["a"]))) < 1e-6
    assert C.compressed_bytes(tree, "int8") < C.compressed_bytes(tree, "none")
