"""Jitted wrapper exposing the Pallas LocalSDCA kernel with the same
interface as core.solvers.local_sdca, so CoCoAConfig(solver="sdca_kernel")
plugs it straight into Algorithm 1.

Responsibilities of the wrapper (kept out of the kernel):
  * pad nk up to a multiple of block_rows and d up to a multiple of 128
    (padded rows get mask=0 -> the closed-form updates are exact no-ops),
  * apply a fresh random row *permutation* per call (random-permutation-epoch
    SDCA) and scatter dalpha back through it,
  * map the solver's H (total coordinate steps) onto whole passes:
    n_passes = max(1, round(H / nk)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.losses import Loss
from repro.core.regularizers import L2, Regularizer
from repro.core.solvers import SDCAResult
from .autotune import resolve_sparse_config
from .local_sdca import local_sdca_pallas
from .sparse_sdca import sparse_local_sdca, sparse_local_sdca_zx, \
    zx_exchanges

# last launch config the sparse dispatch actually launched with
# (observability hook for tests and the bench harness): {"block_rows",
# "slot_unroll", "buffer_depth", "source", "clamped", "model_shards",
# "prox_fused", "zx"}. block_rows is the *effective* post-clamp value
# (small shards clamp the resolved block down to the padded nk;
# "clamped" flags when that happened), so the reported config is always
# one the kernel ran with; "model_shards"/"zx" state whether the launch
# was the M>1 z-exchange schedule and "prox_fused" whether the conjugate
# map ran in-kernel (vs the hoisted round-level map). Set at *trace*
# time -- a jit cache hit reuses the traced kernel without updating
# this, so read it right after a fresh-shape call.
LAST_SPARSE_CONFIG = None


def _pad_to(x, m, axis):
    size = x.shape[axis]
    pad = (-size) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _check_placement(model_axis, name):
    # dense-kernel guard only: the *sparse* kernel runs feature-sharded
    # via the block-batched z-exchange schedule (sparse_local_sdca_zx --
    # the block_rows-sized psum happens between per-block invocations),
    # but the dense streaming kernel has no such schedule yet
    if model_axis is not None:
        raise NotImplementedError(
            f"{name} has no model-axis exchange schedule; feature-sharded "
            f"(M>1) dense rounds use the jnp solver ('sdca'). The sparse "
            f"kernel path ('sdca_sparse_kernel') runs M>1 via the "
            f"z-exchange schedule.")


def local_sdca_block(X_k, y_k, alpha_k, mask_k, v, rng, loss: Loss,
                     lam: float, n, sigma_p: float, H: int,
                     *, block_rows: int = 128,
                     interpret: bool | None = None,
                     model_axis=None, reg: Regularizer = L2) -> SDCAResult:
    """Drop-in solver: block-shuffled SDCA via the Pallas kernel.

    `v` is the shared scaled dual-side vector (== the primal w under L2).
    The conjugate map w0 = grad g*(tau v) is *hoisted outside* the
    pallas_call -- one elementwise pass per round, not per step -- so the
    kernel body is untouched and runs the exact linearized CoCoA-general
    subproblem around w0 (identical to the jnp solvers under L2, where
    the map is the identity; for the L1 family the jnp solvers re-apply
    the map per step, a Theta difference, not a correctness one).

    Placement: `X_k`/`v` may be a feature *slice* (nk, d_loc)/(d_loc,) --
    the kernel is shard-shape-agnostic -- but only at M=1 (see
    `_check_placement`)."""
    _check_placement(model_axis, "local_sdca_block")
    w0 = reg.conj_grad(v, lam)        # hoisted conjugate map (round-level)
    nk, d = X_k.shape
    n_passes = max(1, int(round(H / max(nk, 1))))

    perm = jax.random.permutation(rng, nk)
    Xp = jnp.take(X_k, perm, axis=0)
    yp = jnp.take(y_k, perm)
    ap = jnp.take(alpha_k, perm)
    mp = jnp.take(mask_k, perm)

    br = min(block_rows, max(8, nk))
    Xp = _pad_to(_pad_to(Xp, br, 0), 128, 1)
    yp = _pad_to(yp, br, 0)
    ap = _pad_to(ap, br, 0)
    mp = _pad_to(mp, br, 0)
    wp = _pad_to(w0, 128, 0)

    scale = sigma_p / (reg.tau(lam) * jnp.asarray(n, jnp.float32))
    da_p, du_p = local_sdca_pallas(Xp, yp, ap, mp, wp, scale, loss=loss,
                                   n_passes=n_passes, block_rows=br,
                                   interpret=interpret)
    # un-permute dalpha; drop padding. du is u - w0 with scale-weighted
    # axpy accumulations only, i.e. already the sigma'-scaled v-space delta
    dalpha = jnp.zeros(nk, da_p.dtype).at[perm].set(da_p[:nk])
    return SDCAResult(dalpha.astype(X_k.dtype), du_p[:d].astype(v.dtype),
                      jnp.asarray(n_passes * nk))


def _prox_kappa_of(reg: Regularizer, lam: float) -> float | None:
    """Static fused-prox threshold for `reg`, or None when the kernel
    should fall back to the hoisted round-level conjugate map. kappa=0
    (L2) is treated as not-fused: the identity map needs no ops, and
    skipping it keeps the L2 kernel byte-identical to the PR-8 jaxpr."""
    if getattr(reg, "prox_kappa", None) is None:
        return None
    kappa = float(reg.prox_kappa(lam))
    return kappa if kappa != 0.0 else None


def sparse_local_sdca_block(shard, y_k, alpha_k, mask_k, v, rng, loss: Loss,
                            lam: float, n, sigma_p: float, H: int,
                            *, block_rows: int | None = None,
                            slot_unroll: int | None = None,
                            buffer_depth: int | None = None,
                            interpret: bool | None = None,
                            model_axis=None,
                            sqnorms=None,
                            zx: bool | None = None,
                            reg: Regularizer = L2) -> SDCAResult:
    """Drop-in solver: block-shuffled SDCA over a padded-ELL shard.

    `shard` is a per-worker SparseShards (cols/vals (nk, r_max)). Same
    responsibilities as `local_sdca_block` -- fresh row permutation per
    call, padding nk to a multiple of block_rows (padded rows are masked
    no-ops; the kernel lays u out in 128-lane rows itself), H -> whole
    passes.

    Conjugate map: when `reg` carries a scalar soft-threshold form
    (`reg.prox_kappa`), the map is *fused into the kernel* -- applied to
    each gathered u entry, the same per-step-exact subproblem as the jnp
    solvers (this is what collapsed the ~3x elastic-net rounds penalty
    of the old hoisted map). L2 (kappa 0) and custom regularizers
    without `prox_kappa` keep the hoisted round-level map: one
    elementwise pass before the pallas_call, the kernel solving the
    linearized CoCoA-general subproblem around w0 -- exact for L2,
    Theta-approximate otherwise.

    Placement: the kernel gathers/scatters against whatever w vector it
    is handed, so a FeatureShards slice (shard-local ids, (d_loc,) w)
    works at any M. M>1 (`model_axis` set) launches the z-exchange
    schedule (`sparse_local_sdca_zx`): block-batched partial gather-dots
    psum'd over the model axis between per-block kernel invocations,
    `block_rows` floats per exchange. It needs `sqnorms` -- the *global*
    row squared norms (psum'd over model shards here if not provided).
    `zx=True` forces the same schedule on a single shard (bench/tests);
    `zx=False` with a model_axis is invalid.
    """
    cols, vals = shard.cols, shard.vals
    nk, r_max = cols.shape
    d = v.shape[0]
    use_zx = (model_axis is not None) if zx is None else zx
    if model_axis is not None and not use_zx:
        raise ValueError(
            "sparse_local_sdca_block: model_axis set but zx=False -- the "
            "kernel's only feature-sharded schedule is the z-exchange; "
            "use the jnp 'sdca_sparse' solver to opt out")
    kappa = _prox_kappa_of(reg, lam)
    fused = kappa is not None
    # launch config: explicit kwargs win, else the persisted autotune
    # cache (kernel_bench --autotune), else the static defaults -- keyed
    # on static shapes only (d, r_max, backend) plus the reg family and
    # model-shard count (fused-prox and zx schedules tune differently;
    # zx wants smaller blocks, less intra-block staleness), since nnz is
    # traced here
    M = int(jax.lax.psum(1, model_axis)) if model_axis is not None else 1
    cfg = resolve_sparse_config(d=d, r_max=r_max, block_rows=block_rows,
                                slot_unroll=slot_unroll,
                                buffer_depth=buffer_depth,
                                reg_family=getattr(reg, "family", "other"),
                                model_shards=M if use_zx else 1)
    # clamp the block to the (padded) shard *before* reporting: on small
    # shards the kernel never runs with the resolved block_rows, and the
    # observability hook must state the launch that actually happened
    br = min(cfg["block_rows"], max(8, nk))
    global LAST_SPARSE_CONFIG
    LAST_SPARSE_CONFIG = {**cfg, "block_rows": br,
                          "clamped": br != cfg["block_rows"],
                          "model_shards": M, "prox_fused": fused,
                          "zx": use_zx}
    slot_unroll = cfg["slot_unroll"]
    depth = cfg["buffer_depth"]
    n_passes = max(1, int(round(H / max(nk, 1))))

    # fused prox gathers against v itself (u lives in v-space); the
    # hoisted path gathers against the round-frozen w0 = grad g*(tau v).
    # Either way du = u_final - u_0 = scale * A_[k] dalpha.
    w_in = v if fused else reg.conj_grad(v, lam)

    perm = jax.random.permutation(rng, nk)
    cp = jnp.take(cols, perm, axis=0)
    vp = jnp.take(vals, perm, axis=0)
    yp = jnp.take(y_k, perm)
    ap = jnp.take(alpha_k, perm)
    mp = jnp.take(mask_k, perm)

    cp = _pad_to(cp, br, 0)
    vp = _pad_to(vp, br, 0)
    yp = _pad_to(yp, br, 0)
    ap = _pad_to(ap, br, 0)
    mp = _pad_to(mp, br, 0)

    scale = sigma_p / (reg.tau(lam) * jnp.asarray(n, jnp.float32))
    if use_zx:
        # the zx subproblem's quadratic coefficient must see the full
        # (cross-shard) row norm; fall back to the local one -- exact at
        # M=1 -- only when the caller provided none
        if sqnorms is None:
            sq = jnp.sum(vals * vals, axis=1)
            if model_axis is not None:
                sq = jax.lax.psum(sq, model_axis)
        else:
            sq = sqnorms
        sqp = _pad_to(jnp.take(sq, perm), br, 0)
        da_p, du_p = sparse_local_sdca_zx(
            cp, vp, yp, ap, mp, w_in, scale, sqp, loss=loss,
            n_passes=n_passes, block_rows=br, slot_unroll=slot_unroll,
            prox_kappa=kappa, model_axis=model_axis, interpret=interpret)
    else:
        da_p, du_p = sparse_local_sdca(cp, vp, yp, ap, mp, w_in, scale,
                                       loss=loss, n_passes=n_passes,
                                       block_rows=br,
                                       slot_unroll=slot_unroll,
                                       buffer_depth=depth,
                                       prox_kappa=kappa,
                                       interpret=interpret)
    dalpha = jnp.zeros(nk, da_p.dtype).at[perm].set(da_p[:nk])
    return SDCAResult(dalpha.astype(vals.dtype), du_p[:d].astype(v.dtype),
                      jnp.asarray(n_passes * nk))


def sparse_zx_plan(nk: int, d: int, H: int, *, r_max: int,
                   block_rows: int | None = None,
                   slot_unroll: int | None = None,
                   reg_family: str = "l2", model_shards: int = 1,
                   backend: str | None = None) -> dict:
    """The z-exchange wire plan the dispatch above would launch with --
    pure shape arithmetic (resolve + clamp + pad, no tracing), so
    `core.cocoa.solve` / the tracer can price the model-axis hop exactly:
    `exchanges` psums of `block_rows` floats per round per device."""
    backend = backend or jax.default_backend()
    cfg = resolve_sparse_config(d=d, r_max=r_max, block_rows=block_rows,
                                slot_unroll=slot_unroll, buffer_depth=1,
                                backend=backend,
                                reg_family=reg_family,
                                model_shards=model_shards)
    br = min(cfg["block_rows"], max(8, nk))
    nk_pad = nk + (-nk) % br
    n_passes = max(1, int(round(H / max(nk, 1))))
    nb = nk_pad // br
    return dict(block_rows=br, n_passes=n_passes, blocks=nb,
                exchanges=zx_exchanges(nk_pad, br, n_passes))
