"""Primal/dual objectives, the alpha -> (v, w) maps, and the duality-gap
certificate -- generalized over the regularizer g(w).

Data layout: the global data matrix A (paper: d x n, columns = examples) is
stored partitioned as X with shape (K, n_k, d)  -- K workers, n_k rows each,
row i = x_i^T. Labels y and duals alpha are (K, n_k). A `mask` (K, n_k) of
{0,1} marks real rows (padding rows are all-zero and masked out of n).

`X` may equivalently be a `repro.data.sparse.SparseShards` padded-ELL
container; every objective then evaluates via the sparse matvec family
(gather for A^T w, segment-sum scatter for A alpha) so gap certificates on
sparse runs cost O(nnz), not O(n d). A `repro.data.sparse.CertLayout` of
those shards walks only the tiles of its sorted rows that hold entries.
It is taken by `primal` and the gap functions alone: y and mask then
come in its row order (`in_rows`, once per data set), alpha in the
shards' own, and the gap functions put alpha in the layout's themselves.

Objectives (regularizers.Regularizer, default the paper's L2):

    P(w)     = (1/n) sum_i l_i(x_i^T w) + g(w)
    D(alpha) = -(1/n) sum_i l_i*(-alpha_i) - g*(tau v),  v = A alpha/(tau n)

with the primal recovered through the conjugate map w = grad g*(tau v)
(`Regularizer.conj_grad` in the scaled frame; the identity for L2, where
v IS the old w(alpha) = A alpha/(lambda n)). Weak duality P(w) >= D(alpha)
holds for ANY (w, alpha) pair by Fenchel-Young, so every gap below remains
a valid primal-suboptimality certificate under drifted/compressed iterates.

All objective functions take the *global effective n* so that padded
partitions reproduce the unpadded math exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.data import sparse as sparse_data
from repro.data.sparse import CertLayout, FeatureShards, SparseShards

from .losses import Loss
from .regularizers import L2, Regularizer


_SPARSE = (SparseShards, FeatureShards, CertLayout)


def effective_n(mask: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(mask)


def in_rows(X, *xs: jnp.ndarray) -> tuple:
    """The (K, nk) arrays `xs` in X's row order: a `CertLayout` sorts each
    worker's rows; any other X keeps them as they are."""
    if isinstance(X, CertLayout):
        return tuple(X.rows(x) for x in xs)
    return xs


def _own_rows(X) -> None:
    """Refuse a `CertLayout` where alpha is read in the shards' order."""
    if isinstance(X, CertLayout):
        raise TypeError("a CertLayout is read by `primal` and the gap "
                        "functions alone")


def _Atw(X, w: jnp.ndarray) -> jnp.ndarray:
    """Per-row predictions z = A^T w, shape (K, nk). `FeatureShards` + a
    padded (M*d_local,) w evaluate as per-shard local gathers summed over
    the model axis -- the one model-axis reduction a sharded certificate
    needs (sparse_data.matvec dispatches)."""
    if isinstance(X, _SPARSE):
        return sparse_data.matvec(X, w)
    return jnp.einsum("kid,d->ki", X, w)


def v_of_alpha(X, alpha: jnp.ndarray, lam: float, n,
               reg: Regularizer = L2) -> jnp.ndarray:
    """v(alpha) = A alpha / (tau n) -- the scaled conjugate pre-image the
    rounds carry as shared state. X: (K, nk, d) or shards (FeatureShards
    yield the padded M*d_local global vector). Equals the paper's
    w(alpha) (eq. 3) under L2, where tau = lambda."""
    _own_rows(X)
    return _v(X, alpha, lam, n, reg)


def _v(X, alpha, lam, n, reg):
    """`v_of_alpha` on any X; on a `CertLayout`, alpha in its rows."""
    tau = reg.tau(lam)
    if isinstance(X, _SPARSE):
        return sparse_data.rmatvec(X, alpha) / (tau * n)
    return jnp.einsum("kid,ki->d", X, alpha) / (tau * n)


def w_of_alpha(X, alpha: jnp.ndarray, lam: float, n,
               reg: Regularizer = L2) -> jnp.ndarray:
    """w(alpha) = grad g*(tau v(alpha)) -- eq. 3 generalized through the
    conjugate map (the identity for L2, soft-thresholding for the L1
    family, applied elementwise so it is shard-local under a 2-D mesh)."""
    return reg.conj_grad(v_of_alpha(X, alpha, lam, n, reg), lam)


def primal(w: jnp.ndarray, X, y: jnp.ndarray, mask: jnp.ndarray,
           loss: Loss, lam: float, reg: Regularizer = L2) -> jnp.ndarray:
    n = effective_n(mask)
    z = _Atw(X, w)
    vals = loss.value(z, y) * mask
    return jnp.sum(vals) / n + reg.value(w, lam)


def dual_at_v(v: jnp.ndarray, alpha: jnp.ndarray, y: jnp.ndarray,
              mask: jnp.ndarray, loss: Loss, lam: float,
              reg: Regularizer = L2) -> jnp.ndarray:
    """D(alpha) evaluated at a precomputed v = v_of_alpha(...) -- lets
    callers that already paid the rmatvec (gap_decomposed) share it."""
    n = effective_n(mask)
    conj = loss.conj(alpha, y) * mask
    return -jnp.sum(conj) / n - reg.conj(v, lam)


def dual(alpha: jnp.ndarray, X, y: jnp.ndarray, mask: jnp.ndarray,
         loss: Loss, lam: float, reg: Regularizer = L2) -> jnp.ndarray:
    n = effective_n(mask)
    v = v_of_alpha(X, alpha, lam, n, reg)
    return dual_at_v(v, alpha, y, mask, loss, lam, reg)


def duality_gap(alpha: jnp.ndarray, X, y: jnp.ndarray,
                mask: jnp.ndarray, loss: Loss, lam: float,
                reg: Regularizer = L2) -> jnp.ndarray:
    """G(alpha) = P(w(alpha)) - D(alpha)  (eq. 4). Non-negative by weak duality."""
    return gap_decomposed(alpha, X, y, mask, loss, lam, reg)[2]


def gap_decomposed(alpha, X, y, mask, loss, lam, reg: Regularizer = L2):
    """Returns (P, D, gap) sharing the one v(alpha) rmatvec -- the
    dominant cost of a certificate -- between the primal and dual sides
    (rather than rebuilding it inside `dual`). The three passes run under
    the named scopes `rmatvec`, `primal` and `dual`, so a profile times
    each apart."""
    n = effective_n(mask)
    with jax.named_scope("rmatvec"):
        alpha, = in_rows(X, alpha)
        v = _v(X, alpha, lam, n, reg)
    with jax.named_scope("primal"):
        w = reg.conj_grad(v, lam)
        p = primal(w, X, y, mask, loss, lam, reg)
    with jax.named_scope("dual"):
        d = dual_at_v(v, alpha, y, mask, loss, lam, reg)
    return p, d, p - d


def gap_at_w(w, alpha, X, y, mask, loss, lam, reg: Regularizer = L2):
    """(P(w), D(alpha), P(w) - D(alpha)) for an arbitrary primal iterate.

    Under compressed communication (comm.compress with error feedback) the
    algorithm's shared state drifts from v(alpha) -- only the exact duals
    are aggregated, the wire carries a lossy Delta v. Weak duality still
    gives P(w) >= P(w*) >= D(alpha) for ANY w, so certifying the w the
    algorithm actually serves stays a valid (if slightly larger) gap
    certificate. Rounds carry v, not w -- use `gap_at_v` for raw state.

    Feature-sharded runs pass the padded (M*d_local,) w with
    `FeatureShards` data: predictions assemble via one model-axis
    reduction inside `_Atw`, and the padded coordinates (always zero, no
    column maps to them) contribute nothing to g(w). Named scopes as in
    `gap_decomposed`."""
    with jax.named_scope("primal"):
        p = primal(w, X, y, mask, loss, lam, reg)
    with jax.named_scope("rmatvec"):
        alpha, = in_rows(X, alpha)
        v = _v(X, alpha, lam, effective_n(mask), reg)
    with jax.named_scope("dual"):
        d = dual_at_v(v, alpha, y, mask, loss, lam, reg)
    return p, d, p - d


def gap_at_v(v, alpha, X, y, mask, loss, lam, reg: Regularizer = L2):
    """`gap_at_w` for a raw v-space iterate (e.g. `CoCoAState.w`, which
    carries v): certifies the primal point w = grad g*(tau v) the
    algorithm serves. Identical to `gap_at_w(v, ...)` under L2."""
    return gap_at_w(reg.conj_grad(v, lam), alpha, X, y, mask, loss, lam, reg)


def u_vector(w: jnp.ndarray, X, y: jnp.ndarray, loss: Loss) -> jnp.ndarray:
    """u with -u_i in d l_i(x_i^T w)  (eq. 17) -- used in Lemma-5 style tests."""
    _own_rows(X)
    z = _Atw(X, w)
    return loss.u_subgrad(z, y)
