"""Pallas LocalSDCA kernel vs pure-jnp oracle: exact order-matched allclose
across shapes, dtypes, losses, block sizes, passes, masking."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.losses import get_loss
from repro.kernels.local_sdca import local_sdca_pallas, CLOSED_FORM_LOSSES
from repro.kernels.ops import local_sdca_block
from repro.kernels.ref import local_sdca_ref


def _mk(nk, d, seed=0, dtype=np.float32, masked=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((nk, d)).astype(np.float32)
    X /= np.maximum(np.linalg.norm(X, axis=1, keepdims=True), 1e-9)
    y = np.sign(rng.standard_normal(nk)).astype(np.float32)
    y[y == 0] = 1
    mask = np.ones(nk, np.float32)
    if masked:
        mask[-masked:] = 0
        X[-masked:] = 0
    w = (rng.standard_normal(d) * 0.01).astype(np.float32)
    return (jnp.asarray(X, dtype), jnp.asarray(y), jnp.zeros(nk, jnp.float32),
            jnp.asarray(mask), jnp.asarray(w))


SHAPES = [(64, 128, 32), (128, 128, 128), (256, 256, 64), (512, 128, 256)]


@pytest.mark.parametrize("loss_name", ["hinge", "smooth_hinge1", "squared",
                                       "absolute"])
@pytest.mark.parametrize("nk,d,br", SHAPES)
def test_kernel_matches_oracle(loss_name, nk, d, br):
    loss = get_loss(loss_name)
    X, y, a, m, w = _mk(nk, d, seed=nk + d)
    scale = 4.0 / (1e-3 * nk)
    da_k, du_k = local_sdca_pallas(X, y, a, m, w, scale, loss=loss,
                                   n_passes=1, block_rows=br, interpret=True)
    da_r, du_r = local_sdca_ref(X, y, a, m, w, scale, loss=loss, n_passes=1)
    np.testing.assert_allclose(np.asarray(da_k), np.asarray(da_r),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(du_k), np.asarray(du_r),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("passes", [2, 3])
def test_kernel_multi_pass(passes):
    loss = get_loss("hinge")
    X, y, a, m, w = _mk(128, 128, seed=7)
    scale = 2.0 / (1e-3 * 128)
    da_k, du_k = local_sdca_pallas(X, y, a, m, w, scale, loss=loss,
                                   n_passes=passes, block_rows=64,
                                   interpret=True)
    da_r, du_r = local_sdca_ref(X, y, a, m, w, scale, loss=loss,
                                n_passes=passes)
    np.testing.assert_allclose(np.asarray(da_k), np.asarray(da_r),
                               rtol=2e-5, atol=2e-5)


def test_kernel_masked_rows_are_noops():
    loss = get_loss("hinge")
    X, y, a, m, w = _mk(128, 128, seed=9, masked=13)
    scale = 2.0 / (1e-3 * 115)
    da_k, _ = local_sdca_pallas(X, y, a, m, w, scale, loss=loss,
                                n_passes=1, block_rows=64, interpret=True)
    assert float(jnp.max(jnp.abs(da_k[-13:]))) == 0.0


def test_kernel_bf16_data():
    """bf16 inputs upcast internally to f32 accumulation."""
    loss = get_loss("hinge")
    X, y, a, m, w = _mk(128, 128, seed=11, dtype=jnp.bfloat16)
    scale = 2.0 / (1e-3 * 128)
    da_k, du_k = local_sdca_pallas(X, y, a, m, w, scale, loss=loss,
                                   n_passes=1, block_rows=64, interpret=True)
    da_r, du_r = local_sdca_ref(X.astype(jnp.float32), y, a, m, w, scale,
                                loss=loss, n_passes=1)
    np.testing.assert_allclose(np.asarray(da_k), np.asarray(da_r),
                               rtol=2e-2, atol=2e-2)


def test_ops_wrapper_solver_interface():
    """local_sdca_block: permutation + padding + SDCAResult contract."""
    loss = get_loss("hinge")
    X, y, a, m, w = _mk(100, 130, seed=13)        # non-aligned shapes
    res = local_sdca_block(X, y, a, m, w, jax.random.PRNGKey(0), loss,
                           1e-3, 100.0, 4.0, 200, interpret=True)
    assert res.dalpha.shape == (100,)
    assert res.du.shape == (130,)
    # du must equal scale * X^T dalpha
    scale = 4.0 / (1e-3 * 100)
    ref = scale * (np.asarray(X).T @ np.asarray(res.dalpha))
    np.testing.assert_allclose(np.asarray(res.du), ref, rtol=2e-4, atol=1e-4)


def test_kernel_rejects_logistic():
    with pytest.raises(ValueError):
        X, y, a, m, w = _mk(64, 128)
        local_sdca_pallas(X, y, a, m, w, 1.0, loss=get_loss("logistic"),
                          interpret=True)


def test_interpret_false_is_not_overridden_off_tpu():
    """The interpreter is chosen only for interpret=None on the CPU
    backend; an explicit interpret=False is compiled as asked, so off a
    TPU the launch fails instead of silently interpreting."""
    from repro.kernels.local_sdca import resolve_interpret
    from repro.kernels.sparse_sdca import sparse_local_sdca
    assert jax.default_backend() == "cpu"
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    X, y, a, m, w = _mk(64, 128)
    with pytest.raises(ValueError, match="interpret"):
        local_sdca_pallas(X, y, a, m, w, 1.0, loss=get_loss("hinge"),
                          block_rows=64, interpret=False)
    cols = jnp.zeros((64, 8), jnp.int32)
    with pytest.raises(ValueError, match="interpret"):
        sparse_local_sdca(cols, jnp.zeros((64, 8)), y, a, m, w, 1.0,
                          loss=get_loss("hinge"), block_rows=64,
                          interpret=False)

