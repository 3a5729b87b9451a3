"""Pallas TPU kernel for the LocalSDCA inner loop (paper Algorithm 2).

Why a kernel: each CoCoA+ round spends essentially all of its time in the
H-step coordinate loop -- per step one d-dot (x_i . u) and one d-axpy
(u += c x_i). The loop is *sequential* (every step reads the u produced by
the previous one), so the GPU picture of one-thread-per-coordinate does not
transfer. The TPU-native formulation instead:

  * keeps u (d floats) and dalpha (nk floats) **persistent in VMEM scratch
    across the sequential Pallas grid** (TPU grid steps run in order on a
    core -- the idiomatic replacement for a persistent CUDA block),
  * streams X through VMEM in (block_rows, d) tiles via BlockSpec -- the only
    HBM traffic; `n_passes` full passes over the shard amortize nothing here
    because every pass must re-stream X, which is exactly the HBM-bound
    behavior of SDCA (arithmetic intensity ~2 flops/byte),
  * visits coordinates in *block-shuffled order* (the wrapper in ops.py
    applies a fresh random row permutation per call), the standard
    random-permutation-epoch variant of SDCA. The pure-jnp oracle in ref.py
    follows the identical order, so kernel-vs-oracle equivalence is exact,
    not statistical.

Grid layout: grid = (n_passes, nk // block_rows); grid step (p, b) processes
rows [b*B, (b+1)*B) sequentially with a fori_loop. dalpha/u land in the
outputs only at the final grid step (no cross-step output aliasing hazards).

Mosaic lowers no dynamic slice of a *value*, so every per-step access is an
indexed ref access: the row as `x_ref[pl.ds(i, 1), :]`, the per-row scalars
(y, alpha, mask) from (1, B) SMEM tiles, and dalpha through the 128-lane
layout below. On the chip B is a multiple of 128 (or the whole shard).

VMEM budget (f32): 2*B*d (double-buffered X tile) + 3*nk (dalpha scratch
and output) + 5*d (w, u, du) floats; ops.py pads d to a multiple of 128.

Supported losses: the closed-form family ("hinge", "smooth_hinge*",
"squared", "absolute"). "logistic" has no closed form -> use the pure-JAX
solver path (core.solvers) which runs its guarded Newton.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.losses import Loss, get_loss

CLOSED_FORM_LOSSES = ("hinge", "smooth_hinge", "squared", "absolute")


def _check_loss(loss: Loss):
    if not loss.name.startswith(CLOSED_FORM_LOSSES):
        raise ValueError(
            f"kernel supports closed-form losses {CLOSED_FORM_LOSSES}, "
            f"got {loss.name!r}; use the core.solvers JAX path instead")


# ---------------------------------------------------------------------------
# lane layout: a length-n f32 vector held as a (ceil(n/128), 128) VMEM ref.
# Mosaic has no dynamic slice of a *value*, so a kernel that touches one
# element at a runtime index loads the (1, 128) row holding it and picks or
# replaces its lane with an iota mask -- O(1) vregs per access. The masked
# lanes contribute exact zeros, so a read returns the element bit for bit,
# and an update computes the new element as a scalar, the same float ops
# (and, in interpret mode, the same XLA scalar fusion) as `x.at[i].set`.
# ---------------------------------------------------------------------------

LANES = 128


def lane_rows(n: int) -> int:
    """Rows of the (rows, 128) lane layout of a length-n vector."""
    return -(-n // LANES)


def to_lanes(x: jnp.ndarray) -> jnp.ndarray:
    """(n,) -> (lane_rows(n), 128), zero padded."""
    return jnp.pad(x, (0, (-x.shape[0]) % LANES)).reshape(-1, LANES)


def _lane_of(idx):
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return pl.ds(jax.lax.shift_right_logical(idx, 7), 1), \
        lane == jnp.bitwise_and(idx, LANES - 1)


def lane_get(ref, idx):
    """ref[idx] of a lane-layout ref, as a scalar."""
    row, hit = _lane_of(idx)
    return jnp.sum(jnp.where(hit, ref[row, :], jnp.float32(0.0)))


def lane_put(ref, idx, val):
    """ref[idx] = val (a scalar) on a lane-layout ref."""
    row, hit = _lane_of(idx)
    ref[row, :] = jnp.where(hit, val, ref[row, :])


def _sdca_kernel(scale_ref,                    # SMEM (1, 1): sigma'/(lambda n)
                 x_ref,                        # VMEM (block_rows, d) tile
                 y_ref, a_ref, m_ref,          # SMEM (1, block_rows) tiles
                 w_ref,                        # VMEM (1, d)
                 da_out, du_out,               # VMEM (rows(nk), 128), (1, d)
                 da_scr, u_scr,                # VMEM scratch, same shapes
                 *, loss: Loss, block_rows: int):
    p = pl.program_id(0)
    b = pl.program_id(1)
    nb = pl.num_programs(1)
    npass = pl.num_programs(0)
    scale = scale_ref[0, 0]

    @pl.when(jnp.logical_and(p == 0, b == 0))
    def _init():
        da_scr[...] = jnp.zeros_like(da_scr)
        u_scr[...] = w_ref[...]

    base = b * block_rows

    def step(i, carry):
        x = x_ref[pl.ds(i, 1), :]                                  # (1, d)
        u = u_scr[...]                                             # (1, d)
        z = jnp.sum(x * u)
        q = scale * jnp.sum(x * x)
        dai = lane_get(da_scr, base + i)
        delta = loss.cd_update(a_ref[0, i] + dai, z, q, y_ref[0, i]) \
            * m_ref[0, i]
        lane_put(da_scr, base + i, dai + delta)
        u_scr[...] = u + (scale * delta) * x
        return carry

    jax.lax.fori_loop(0, block_rows, step, 0)

    @pl.when(jnp.logical_and(p == npass - 1, b == nb - 1))
    def _emit():
        da_out[...] = da_scr[...]
        du_out[...] = u_scr[...] - w_ref[...]


def resolve_interpret(interpret: bool | None) -> bool:
    """`interpret=None` runs the Pallas interpreter on the CPU backend only
    (tests, laptops). Anywhere else the kernel is compiled; an explicit
    value is never overridden."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def smem_rows(block_rows: int) -> pl.BlockSpec:
    """SMEM BlockSpec of per-row scalars laid out (1, nk): one
    (1, block_rows) block per grid row block. Mosaic wants the last block
    dim a multiple of 128 or the whole array, so on the chip block_rows
    is a multiple of 128 or the whole (padded) shard."""
    return pl.BlockSpec((1, block_rows), lambda p, b: (0, b),
                        memory_space=pltpu.SMEM)


def local_sdca_pallas(X: jnp.ndarray, y: jnp.ndarray, alpha: jnp.ndarray,
                      mask: jnp.ndarray, w: jnp.ndarray, scale: jnp.ndarray,
                      *, loss: Loss, n_passes: int = 1,
                      block_rows: int = 128, interpret: bool | None = None):
    """Run `n_passes` block-sequential SDCA passes over the shard.

    X: (nk, d); y/alpha/mask: (nk,); w: (d,);
    scale: scalar  sigma' / (lambda n).
    Returns (dalpha (nk,), du (d,)) with du = scale * A_[k] dalpha.
    nk must be divisible by block_rows (ops.py pads).
    """
    _check_loss(loss)
    nk, d = X.shape
    assert nk % block_rows == 0, (nk, block_rows)
    nb = nk // block_rows
    interpret = resolve_interpret(interpret)

    f32 = jnp.float32
    kernel = functools.partial(_sdca_kernel, loss=loss,
                               block_rows=block_rows)
    grid = (n_passes, nb)
    rows = smem_rows(block_rows)
    da_shape = (lane_rows(nk), LANES)
    da, du = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # scale
            pl.BlockSpec((block_rows, d), lambda p, b: (b, 0)),    # X
            rows, rows, rows,                                      # y, a, m
            pl.BlockSpec((1, d), lambda p, b: (0, 0)),             # w
        ],
        out_specs=[
            pl.BlockSpec(da_shape, lambda p, b: (0, 0)),           # dalpha
            pl.BlockSpec((1, d), lambda p, b: (0, 0)),             # du
        ],
        out_shape=[
            jax.ShapeDtypeStruct(da_shape, f32),
            jax.ShapeDtypeStruct((1, d), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM(da_shape, f32),
            pltpu.VMEM((1, d), f32),
        ],
        interpret=interpret,
    )(
        jnp.asarray(scale, f32).reshape(1, 1),
        X.astype(f32),
        y.astype(f32).reshape(1, nk),
        alpha.astype(f32).reshape(1, nk),
        mask.astype(f32).reshape(1, nk),
        w.astype(f32).reshape(1, d),
    )
    return da.reshape(-1)[:nk], du[0]
