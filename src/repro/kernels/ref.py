"""Pure-jnp oracle for the Pallas LocalSDCA kernel.

Implements the *identical* block-sequential visit order (rows 0..nk-1, for
n_passes passes) so kernel-vs-oracle comparison is exact (same arithmetic,
same order), not statistical.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.losses import Loss


def local_sdca_ref(X, y, alpha, mask, w, scale, *, loss: Loss,
                   n_passes: int = 1):
    """Reference for kernels.local_sdca.local_sdca_pallas (same signature
    minus tiling details). Returns (dalpha (nk,), du (d,))."""
    nk, d = X.shape
    X = X.astype(jnp.float32)
    y = y.astype(jnp.float32)
    alpha = alpha.astype(jnp.float32)
    mask = mask.astype(jnp.float32)
    scale = jnp.asarray(scale, jnp.float32)

    def body(h, carry):
        dalpha, u = carry
        i = h % nk
        x = X[i]
        z = jnp.dot(x, u)
        q = scale * jnp.dot(x, x)
        abar = alpha[i] + dalpha[i]
        delta = loss.cd_update(abar, z, q, y[i]) * mask[i]
        dalpha = dalpha.at[i].add(delta)
        u = u + (scale * delta) * x
        return dalpha, u

    dalpha0 = jnp.zeros(nk, jnp.float32)
    u0 = w.astype(jnp.float32)
    dalpha, u = jax.lax.fori_loop(0, n_passes * nk, body, (dalpha0, u0))
    return dalpha, u - u0


def sparse_local_sdca_ref(cols, vals, y, alpha, mask, w, scale, *,
                          loss: Loss, n_passes: int = 1,
                          prox_kappa: float | None = None):
    """Reference for kernels.sparse_sdca.sparse_local_sdca.

    Replays the kernel's exact op sequence -- scalar-indexed gather dot
    (accumulated in row-slot order), the row norms as one
    jnp.sum(vals * vals, axis=1) ahead of the walk, and sequential
    per-slot scatter-axpy -- so the comparison is bit-for-bit in
    interpret mode, including rows with duplicate columns. Padding slots
    (col 0, val 0.0) are exact no-ops, as in the kernel. `prox_kappa`
    mirrors the kernel's fused conjugate map: the same scalar
    soft-threshold (sign(u) * max(|u| - kappa, 0)) applied to each
    gathered u entry, with the scatter still updating raw (v-space) u."""
    nk, r_max = cols.shape
    cols = cols.astype(jnp.int32)
    vals = vals.astype(jnp.float32)
    y = y.astype(jnp.float32)
    alpha = alpha.astype(jnp.float32)
    mask = mask.astype(jnp.float32)
    scale = jnp.asarray(scale, jnp.float32)
    sq = jnp.sum(vals * vals, axis=1)

    def prox(uv):
        if prox_kappa is None:
            return uv
        kap = jnp.float32(prox_kappa)
        return jnp.sign(uv) * jnp.maximum(jnp.abs(uv) - kap,
                                          jnp.float32(0.0))

    def body(h, carry):
        dalpha, u = carry
        i = h % nk
        ci = jax.lax.dynamic_index_in_dim(cols, i, axis=0, keepdims=False)
        vi = jax.lax.dynamic_index_in_dim(vals, i, axis=0, keepdims=False)

        def gather_dot(r, z):
            c = jax.lax.dynamic_index_in_dim(ci, r, keepdims=False)
            uv = jax.lax.dynamic_index_in_dim(u, c, keepdims=False)
            vv = jax.lax.dynamic_index_in_dim(vi, r, keepdims=False)
            return z + prox(uv) * vv

        z = jax.lax.fori_loop(0, r_max, gather_dot, jnp.float32(0.0))
        q = scale * sq[i]
        abar = alpha[i] + dalpha[i]
        delta = loss.cd_update(abar, z, q, y[i]) * mask[i]
        dalpha = dalpha.at[i].add(delta)
        coef = scale * delta

        def scatter_axpy(r, u):
            c = jax.lax.dynamic_index_in_dim(ci, r, keepdims=False)
            uv = jax.lax.dynamic_index_in_dim(u, c, keepdims=False)
            vv = jax.lax.dynamic_index_in_dim(vi, r, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(
                u, uv + coef * vv, c, axis=0)

        u = jax.lax.fori_loop(0, r_max, scatter_axpy, u)
        return dalpha, u

    dalpha0 = jnp.zeros(nk, jnp.float32)
    u0 = w.astype(jnp.float32)
    dalpha, u = jax.lax.fori_loop(0, n_passes * nk, body, (dalpha0, u0))
    return dalpha, u - u0


def ssm_scan_ref(xin, dt, Bm, Cm, A, D):
    """Oracle for kernels.ssm_scan: direct sequential recurrence in f64-ish
    f32, same math as models/ssm.py's chunked associative scan."""
    B, S, di = xin.shape
    N = Bm.shape[-1]
    h = jnp.zeros((B, di, N), jnp.float32)
    ys = []
    for t in range(S):
        decay = jnp.exp(dt[:, t, :, None] * A[None])             # (B,di,N)
        h = decay * h + (dt[:, t] * xin[:, t])[..., None] * Bm[:, t, None, :]
        ys.append(jnp.einsum("bdn,bn->bd", h, Cm[:, t]) + D * xin[:, t])
    return jnp.stack(ys, axis=1)
