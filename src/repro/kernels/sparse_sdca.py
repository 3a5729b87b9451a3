"""Pallas TPU kernel for the LocalSDCA inner loop over padded-ELL rows.

The dense kernel (local_sdca.py) streams (block_rows, d) tiles of X through
VMEM -- O(d) bytes per coordinate step. At the paper's densities (rcv1
0.0016, news20 3e-4) almost all of that traffic is zeros. This kernel
streams (block_rows, r_max) tiles of (col_idx, value) pairs instead, so a
step costs one r_max-gather dot and one r_max scatter-axpy against the
primal estimate u -- O(nnz) bytes, a 0.5/density reduction in HBM traffic
(8 bytes per stored entry vs 4 per dense element).

Structure mirrors the dense kernel exactly:

  * u (d floats) and dalpha (nk floats) live in VMEM scratch in the
    128-lane layout (`local_sdca.to_lanes`), persistent across the
    sequential grid (p, b) = (pass, row block); outputs are emitted at the
    final grid step only.
  * the (B, r_max) cols/vals tiles and the per-row scalars (y, alpha, mask,
    row sqnorm) arrive in SMEM, so every ELL slot is a scalar load. The
    gather and the scatter touch u through one (1, 128) row per slot
    (`lane_get` / `lane_put`), O(1) vregs per non-zero whatever d is.
    Padding slots are (col 0, val 0.0), making them exact arithmetic
    no-ops (gather adds u[0]*0, scatter adds 0 to u[0]) -- no per-row nnz
    bound is needed inside the kernel.
  * the pure-jnp oracle `kernels.ref.sparse_local_sdca_ref` replays the
    identical op sequence (same gather order, row sqnorms computed the same
    way ahead of the walk, same scatter order), so kernel-vs-oracle
    equivalence is bit-for-bit in interpret mode, not statistical.
  * block-shuffled visit order and the closed-form loss family are shared
    with the dense path (the wrapper in ops.py applies the per-call row
    permutation; `_check_loss` rejects logistic).

Pipelining (`buffer_depth`): the coordinate walk of block b only touches
on-chip memory (u, dalpha, and the already-resident (B, r_max) cols/vals
tiles), so the HBM fetch of block b+1 can hide entirely behind it.
`buffer_depth=1` is the single-buffered kernel above, with the tiles
delivered by the implicit Pallas pipeline. `buffer_depth>=2` switches to
an explicitly multi-buffered kernel: cols/vals stay in HBM (`pl.ANY`), a
(depth*B, r_max) SMEM scratch ring holds in-flight tiles, and
`pltpu.make_async_copy` DMAs prefetch block b+depth-1 while block b is
walked (double buffering at depth 2 keeps one fetch in flight, quad at
depth 4 keeps three -- deeper rings absorb burstier DMA latency). Both
kernels run the identical `_block_walk` on identical tile values, so
every depth is bit-for-bit the depth-1 kernel, which the oracle pins.

Fused prox (`prox_kappa`): the generalized-objective solvers apply the
v -> w conjugate map `reg.conj_grad` at every gather (per-step-exact
subproblem). When the map is a scalar soft-threshold -- L2 (kappa 0),
elastic-net (eta/(1-eta)), smoothed-L1 (lam/eps) -- the kernel applies it
*inside* `gather_dot` on only the r_max gathered u entries, in-register
(zero extra VMEM), instead of the once-per-round hoisted map that made
the kernel solve a linearized subproblem (and cost ~3x the rounds on
elastic-net). `prox_kappa=None` is a static Python branch, so the L2 /
legacy path emits today's jaxpr unchanged -- bit-for-bit with the PR-8
kernel. The caller passes w = v (the scaled dual state) when fusing; u
then lives in v-space and du = u - v is still scale * A_[k] dalpha.

Budget: SMEM holds the cols+vals tile ring (2 or depth slots of
B*r_max*8 bytes) within `SMEM_BUDGET_KB`; VMEM holds the lane-layout u, w,
du and dalpha vectors. `vmem_budget` prices every schedule (including the
zx exchange buffers) and the entry points REJECT configs over either
limit instead of leaning on the compiler clamp.

Placement / M>1 (`sparse_local_sdca_zx`): `w` here is whatever shard the
caller hands in -- gather-dot/scatter-axpy are coordinate-frame-agnostic,
so under the 2-D (data, model) mesh a device's local w slice with
shard-local ELL ids (data.sparse.FeatureShards) satisfies the same
contract with d = d_local. The per-step partial-dot psum the jnp solver
does is what a single kernel launch cannot -- so the zx schedule
restructures the walk into block-batched partial-dot exchanges: each
invocation walks one `block_rows` block using *exchanged* z dots (psum'd
over `model_axis` between invocations, block_rows floats per hop instead
of one scalar per step), then computes the local partial gather-dots for
the next block at the updated u into a z-buffer output. Within a block
the z dots are stale (computed before the block's own updates) -- that
staleness, dialed by block_rows, is exactly the Theta-approximation
Ma et al. 1512.04039 licenses, certified by `gap_at_v`; every shard sees
identical (z, q, y, alpha, dalpha) so the delta decisions -- and the
replicated dalpha -- stay identical across model shards by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.losses import Loss
from .local_sdca import (LANES, _check_loss, lane_get, lane_put, lane_rows,
                         resolve_interpret, smem_rows, to_lanes)


def _unrolled_fori(n: int, unroll: int, body, init):
    """`fori_loop(0, n, body, init)` with `unroll` consecutive iterations
    per loop step -- same visit order, same carry chain, so results are
    bit-for-bit identical to the rolled loop for any unroll that divides
    n (otherwise falls back to rolled -- `autotune.resolve_sparse_config`
    rounds dispatch-time unrolls down to a divisor so the fallback never
    silently voids a cached config). Deeper unroll trades instruction-
    stream size for fewer loop-carried branches on the r_max slot walk."""
    if unroll <= 1 or n % unroll != 0:
        return jax.lax.fori_loop(0, n, body, init)

    def block(j, carry):
        base = j * unroll
        for t in range(unroll):
            carry = body(base + t, carry)
        return carry

    return jax.lax.fori_loop(0, n // unroll, block, init)


def _prox(uv, prox_kappa):
    """In-register scalar soft-threshold: the fused `reg.conj_grad` map
    applied to one gathered u entry. `prox_kappa` is a *static* Python
    float (or None), so the None path adds no ops to the jaxpr -- the
    L2 / hoisted-map kernels stay bit-for-bit with PR 8."""
    if prox_kappa is None:
        return uv
    kap = jnp.float32(prox_kappa)
    return jnp.sign(uv) * jnp.maximum(jnp.abs(uv) - kap, jnp.float32(0.0))


def _gather_dot(u_ref, c_ref, v_ref, row, *, r_max: int, slot_unroll: int,
                prox_kappa: float | None):
    """z = sum_r prox(u[c_r]) * v_r over ELL row `row` of the SMEM refs
    c_ref/v_ref, accumulated in slot order."""

    def body(r, z):
        uv = lane_get(u_ref, c_ref[row, r])
        return z + _prox(uv, prox_kappa) * v_ref[row, r]

    return _unrolled_fori(r_max, slot_unroll, body, jnp.float32(0.0))


def _scatter_axpy(u_ref, c_ref, v_ref, row, coef, *, r_max: int,
                  slot_unroll: int):
    """u[c_r] += coef * v_r over ELL row `row`, one slot at a time (so
    duplicate columns accumulate exactly as the sequential oracle)."""

    def body(r, carry):
        c = c_ref[row, r]
        lane_put(u_ref, c, lane_get(u_ref, c) + coef * v_ref[row, r])
        return carry

    _unrolled_fori(r_max, slot_unroll, body, 0)


def _block_walk(c_ref, v_ref, row0, y_ref, a_ref, m_ref, sq_ref, base,
                da_scr, u_scr, scale, *, loss: Loss, block_rows: int,
                r_max: int, slot_unroll: int,
                prox_kappa: float | None = None):
    """The sequential coordinate walk of one block of `block_rows` ELL rows
    against the persistent lane-layout u/dalpha scratch. c_ref/v_ref are
    SMEM refs holding the block's (col, val) rows from row `row0`;
    y/alpha/mask/sqnorm come from (1, block_rows) SMEM tiles.
    Shared verbatim by the single-buffered and the pipelined kernels --
    identical tile values in, bit-for-bit identical scratch updates out,
    whatever delivered the tile (implicit Pallas pipeline or explicit DMA
    ring). With `prox_kappa` set, each gathered u entry passes through
    the soft-threshold conjugate map before the dot -- the per-step-exact
    generalized subproblem; the scatter still updates raw (v-space) u."""
    kw = dict(r_max=r_max, slot_unroll=slot_unroll)

    def step(i, carry):
        row = row0 + i
        z = _gather_dot(u_scr, c_ref, v_ref, row, prox_kappa=prox_kappa,
                        **kw)
        q = scale * sq_ref[0, i]
        dai = lane_get(da_scr, base + i)
        delta = loss.cd_update(a_ref[0, i] + dai, z, q, y_ref[0, i]) \
            * m_ref[0, i]
        lane_put(da_scr, base + i, dai + delta)
        _scatter_axpy(u_scr, c_ref, v_ref, row, scale * delta, **kw)
        return carry

    jax.lax.fori_loop(0, block_rows, step, 0)


def _sparse_sdca_kernel(scale_ref,                     # SMEM (1, 1)
                        c_ref, v_ref,                  # SMEM (B, r_max)
                        y_ref, a_ref, m_ref, sq_ref,   # SMEM (1, B) tiles
                        w_ref,                         # VMEM (rows(d), 128)
                        da_out, du_out,                # VMEM lane layouts
                        da_scr, u_scr,                 # VMEM scratch
                        *, loss: Loss, block_rows: int, r_max: int,
                        slot_unroll: int = 1,
                        prox_kappa: float | None = None):
    """Single-buffered (buffer_depth=1) kernel: cols/vals tiles arrive in
    SMEM via the implicit Pallas pipeline, one block resident at a time."""
    p = pl.program_id(0)
    b = pl.program_id(1)
    nb = pl.num_programs(1)
    npass = pl.num_programs(0)
    scale = scale_ref[0, 0]

    @pl.when(jnp.logical_and(p == 0, b == 0))
    def _init():
        da_scr[...] = jnp.zeros_like(da_scr)
        u_scr[...] = w_ref[...]

    _block_walk(c_ref, v_ref, 0, y_ref, a_ref, m_ref, sq_ref,
                b * block_rows, da_scr, u_scr, scale, loss=loss,
                block_rows=block_rows, r_max=r_max, slot_unroll=slot_unroll,
                prox_kappa=prox_kappa)

    @pl.when(jnp.logical_and(p == npass - 1, b == nb - 1))
    def _emit():
        da_out[...] = da_scr[...]
        du_out[...] = u_scr[...] - w_ref[...]


def _sparse_sdca_pipelined_kernel(scale_ref,           # SMEM (1, 1)
                                  c_hbm, v_hbm,        # ANY (nk, r_max)
                                  y_ref, a_ref, m_ref, sq_ref,  # SMEM tiles
                                  w_ref,               # VMEM (rows(d), 128)
                                  da_out, du_out,      # VMEM lane layouts
                                  da_scr, u_scr,       # VMEM scratch
                                  c_buf, v_buf,        # SMEM (depth*B, r_max)
                                  c_sem, v_sem,        # DMA sems (depth,)
                                  *, loss: Loss, block_rows: int,
                                  r_max: int, slot_unroll: int,
                                  buffer_depth: int,
                                  prox_kappa: float | None = None):
    """Explicitly multi-buffered kernel: cols/vals stay in HBM and a
    depth-slot SMEM ring is fed by `make_async_copy` DMAs.

    Chunk c of the flattened schedule (c = pass * nb + block) lands in
    ring slot c % depth. The warm-up step starts chunks 0..depth-2; every
    step then starts chunk g+depth-1 (whose slot held chunk g-1, consumed
    last step), waits on chunk g, and walks the resident tile -- so up to
    depth-1 fetches are in flight behind each block's compute. The walk
    itself is `_block_walk`, identical to the single-buffered kernel."""
    p = pl.program_id(0)
    b = pl.program_id(1)
    nb = pl.num_programs(1)
    npass = pl.num_programs(0)
    total = npass * nb
    g = p * nb + b                                     # flattened chunk id
    scale = scale_ref[0, 0]
    B = block_rows

    def dma(chunk, slot):
        blk = jax.lax.rem(jnp.asarray(chunk, jnp.int32), jnp.int32(nb))
        src = pl.ds(blk * B, B)
        dst = pl.ds(slot * B, B)
        return (pltpu.make_async_copy(c_hbm.at[src], c_buf.at[dst],
                                      c_sem.at[slot]),
                pltpu.make_async_copy(v_hbm.at[src], v_buf.at[dst],
                                      v_sem.at[slot]))

    def start(chunk):
        slot = jax.lax.rem(jnp.asarray(chunk, jnp.int32),
                           jnp.int32(buffer_depth))
        for d_ in dma(chunk, slot):
            d_.start()

    @pl.when(g == 0)
    def _init():
        da_scr[...] = jnp.zeros_like(da_scr)
        u_scr[...] = w_ref[...]
        for c in range(min(buffer_depth - 1, total)):  # warm the ring
            start(c)

    @pl.when(g + buffer_depth - 1 < total)
    def _prefetch():
        start(g + buffer_depth - 1)

    slot = jax.lax.rem(jnp.asarray(g, jnp.int32), jnp.int32(buffer_depth))
    for d_ in dma(g, slot):
        d_.wait()

    _block_walk(c_buf, v_buf, slot * B, y_ref, a_ref, m_ref, sq_ref,
                b * block_rows, da_scr, u_scr, scale, loss=loss,
                block_rows=block_rows, r_max=r_max, slot_unroll=slot_unroll,
                prox_kappa=prox_kappa)

    @pl.when(jnp.logical_and(p == npass - 1, b == nb - 1))
    def _emit():
        da_out[...] = da_scr[...]
        du_out[...] = u_scr[...] - w_ref[...]


def _compiler_params(vmem_limit_mb: int | None, interpret: bool) -> dict:
    if vmem_limit_mb and not interpret:
        return {"compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=int(vmem_limit_mb) * 2**20)}
    return {}


def sparse_local_sdca(cols: jnp.ndarray, vals: jnp.ndarray, y: jnp.ndarray,
                      alpha: jnp.ndarray, mask: jnp.ndarray, w: jnp.ndarray,
                      scale: jnp.ndarray, *, loss: Loss, n_passes: int = 1,
                      block_rows: int = 128, slot_unroll: int = 1,
                      buffer_depth: int = 1,
                      prox_kappa: float | None = None,
                      vmem_limit_mb: int | None = None,
                      interpret: bool | None = None):
    """Run `n_passes` block-sequential SDCA passes over one ELL shard.

    cols/vals: (nk, r_max) padded-ELL rows (padding = col 0 / val 0);
    y/alpha/mask: (nk,); w: (d,); scale: scalar sigma' / (lambda n).
    Returns (dalpha (nk,), du (d,)) with du = scale * A_[k] dalpha.
    nk must be divisible by block_rows (ops.py pads); on the chip
    block_rows is also a multiple of 128 or the whole shard (SMEM tiling).

    `block_rows`, `slot_unroll`, and `buffer_depth` are the autotune
    knobs (`kernel_bench --autotune`): all three preserve the sequential
    visit order exactly, so any setting returns bit-for-bit identical
    results. `buffer_depth=1` is the single-buffered kernel (tiles via
    the implicit Pallas pipeline); >=2 runs the explicitly multi-buffered
    kernel with a depth-slot DMA prefetch ring over the cols/vals tiles
    (2 = double, 4 = quad buffering). Its tiles stay in HBM, which Mosaic
    cannot batch, so on the chip it runs unbatched (shard_map), not under
    the vmap backend's `jax.vmap`.

    `prox_kappa` (static float, None = off) fuses the soft-threshold
    conjugate map into every gather -- pass w = v (scaled dual state)
    when set. None emits exactly the PR-8 jaxpr.

    `vmem_limit_mb` both raises Mosaic's VMEM ceiling on real TPUs and
    is the budget `vmem_budget` is enforced against (default 16 MiB) --
    configs that blow it (or the fixed SMEM budget) raise ValueError
    instead of relying on the compiler clamp.
    """
    _check_loss(loss)
    nk, r_max = cols.shape
    d = w.shape[0]
    assert nk % block_rows == 0, (nk, block_rows)
    assert vals.shape == (nk, r_max), (vals.shape, cols.shape)
    assert buffer_depth >= 1, buffer_depth
    nb = nk // block_rows
    interpret = resolve_interpret(interpret)
    _enforce_vmem(vmem_budget(nk=nk, d=d, r_max=r_max,
                              block_rows=block_rows,
                              buffer_depth=buffer_depth,
                              prox_fused=prox_kappa is not None),
                  vmem_limit_mb, where="sparse_local_sdca")

    f32 = jnp.float32
    vals = vals.astype(f32)
    sq = jnp.sum(vals * vals, axis=1)          # = kernels.ref's row norms
    u_shape = (lane_rows(d), LANES)
    da_shape = (lane_rows(nk), LANES)
    scratch = [pltpu.VMEM(da_shape, f32), pltpu.VMEM(u_shape, f32)]
    if buffer_depth == 1:
        kernel = functools.partial(_sparse_sdca_kernel, loss=loss,
                                   block_rows=block_rows, r_max=r_max,
                                   slot_unroll=slot_unroll,
                                   prox_kappa=prox_kappa)
        tile_spec = pl.BlockSpec((block_rows, r_max), lambda p, b: (b, 0),
                                 memory_space=pltpu.SMEM)
        tile_specs = [tile_spec, tile_spec]                # cols, vals
    else:
        kernel = functools.partial(_sparse_sdca_pipelined_kernel, loss=loss,
                                   block_rows=block_rows, r_max=r_max,
                                   slot_unroll=slot_unroll,
                                   buffer_depth=buffer_depth,
                                   prox_kappa=prox_kappa)
        # cols/vals stay in HBM; the kernel DMAs tiles into an SMEM ring.
        # A DMA moves whole 128-lane rows, so the slots are padded to a
        # lane multiple; the walk still visits only the r_max real slots
        tile_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        r_pad = (-r_max) % LANES
        cols = jnp.pad(cols, ((0, 0), (0, r_pad)))
        vals = jnp.pad(vals, ((0, 0), (0, r_pad)))
        scratch += [
            pltpu.SMEM((buffer_depth * block_rows, r_max + r_pad), jnp.int32),
            pltpu.SMEM((buffer_depth * block_rows, r_max + r_pad), f32),
            pltpu.SemaphoreType.DMA((buffer_depth,)),
            pltpu.SemaphoreType.DMA((buffer_depth,)),
        ]
    rows = smem_rows(block_rows)
    lanes = pl.BlockSpec(u_shape, lambda p, b: (0, 0))
    da, du = pl.pallas_call(
        kernel,
        grid=(n_passes, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # scale
            *tile_specs,
            rows, rows, rows, rows,                        # y, a, m, sq
            lanes,                                                 # w
        ],
        out_specs=[
            pl.BlockSpec(da_shape, lambda p, b: (0, 0)),           # dalpha
            lanes,                                                 # du
        ],
        out_shape=[
            jax.ShapeDtypeStruct(da_shape, f32),
            jax.ShapeDtypeStruct(u_shape, f32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        **_compiler_params(vmem_limit_mb, interpret),
    )(
        jnp.asarray(scale, f32).reshape(1, 1),
        cols.astype(jnp.int32),
        vals,
        y.astype(f32).reshape(1, nk),
        alpha.astype(f32).reshape(1, nk),
        mask.astype(f32).reshape(1, nk),
        sq.reshape(1, nk),
        to_lanes(w.astype(f32)),
    )
    return da.reshape(-1)[:nk], du.reshape(-1)[:d]


def _sparse_sdca_zx_kernel(scale_ref,                  # SMEM (1, 1)
                           c_ref, v_ref,               # SMEM (B, r_max) walk
                           cn_ref, vn_ref,             # SMEM (B, r_max) next
                           z_ref,                      # SMEM (1, B) exchanged
                           y_ref, a_ref, m_ref,        # SMEM (1, B)
                           sq_ref,                     # SMEM (1, B) global q
                           da_ref,                     # SMEM (1, B) dalpha in
                           u_ref,                      # VMEM (rows(d), 128)
                           u_out, da_out, zn_out,      # VMEM, SMEM, SMEM
                           *, loss: Loss, block_rows: int, r_max: int,
                           slot_unroll: int, prox_kappa: float | None):
    """One block of the z-exchange (M>1) schedule.

    Walks the resident block's rows consuming the *exchanged* z dots
    (z_ref -- already psum'd over the model axis by the driver; within
    the block they are stale w.r.t. this block's own updates, the Theta
    knob), with q from the global row sqnorms input, then computes the
    local partial gather-dots of the *next* block at the updated u into
    zn_out for the driver to psum. Every input that feeds a delta
    decision (z, q, y, alpha, dalpha, mask, scale) is identical on all
    model shards, so the emitted dalpha is replicated by construction;
    only the u scatter touches shard-local columns."""
    scale = scale_ref[0, 0]
    kw = dict(r_max=r_max, slot_unroll=slot_unroll)
    u_out[...] = u_ref[...]

    def step(i, carry):
        q = scale * sq_ref[0, i]
        dai = da_ref[0, i]
        delta = loss.cd_update(a_ref[0, i] + dai, z_ref[0, i], q,
                               y_ref[0, i]) * m_ref[0, i]
        da_out[0, i] = dai + delta
        _scatter_axpy(u_out, c_ref, v_ref, i, scale * delta, **kw)
        return carry

    jax.lax.fori_loop(0, block_rows, step, 0)

    # local partial gather-dots for the next block at the updated u --
    # same ascending slot order as _block_walk's gather, prox fused
    def next_dot(i, carry):
        zn_out[0, i] = _gather_dot(u_out, cn_ref, vn_ref, i,
                                   prox_kappa=prox_kappa, **kw)
        return carry

    jax.lax.fori_loop(0, block_rows, next_dot, 0)


def sparse_local_sdca_zx(cols: jnp.ndarray, vals: jnp.ndarray,
                         y: jnp.ndarray, alpha: jnp.ndarray,
                         mask: jnp.ndarray, w: jnp.ndarray,
                         scale: jnp.ndarray, sqnorms: jnp.ndarray, *,
                         loss: Loss, n_passes: int = 1,
                         block_rows: int = 16, slot_unroll: int = 1,
                         prox_kappa: float | None = None,
                         model_axis: str | None = None,
                         vmem_limit_mb: int | None = None,
                         interpret: bool | None = None):
    """`n_passes` SDCA passes via the block-batched z-exchange schedule.

    Same contract as `sparse_local_sdca` plus `sqnorms` (nk,), the
    *global* row squared norms (psum'd over model shards by the caller
    when M>1 -- the subproblem's quadratic coefficient must see the full
    row). cols/vals hold shard-local column ids and w the matching local
    slice; `model_axis` names the mesh axis to psum the block_rows-sized
    z buffer over between kernel invocations (None = single shard, same
    schedule, no collective -- the bench/test harness path).

    The scan carries (u, dalpha, z_ex): invocation g walks block g % nb
    with z_ex and emits the next block's local partial dots, which the
    psum turns into the next z_ex -- n_passes*nb + 1 exchanges of
    block_rows floats per round (the +1 is the prologue priming block
    0's dots at u = w), vs one scalar psum per coordinate step in the
    jnp path.
    """
    _check_loss(loss)
    nk, r_max = cols.shape
    d = w.shape[0]
    B = block_rows
    assert nk % B == 0, (nk, B)
    assert vals.shape == (nk, r_max), (vals.shape, cols.shape)
    assert sqnorms.shape == (nk,), sqnorms.shape
    nb = nk // B
    interpret = resolve_interpret(interpret)
    _enforce_vmem(vmem_budget(nk=nk, d=d, r_max=r_max, block_rows=B,
                              buffer_depth=1,
                              prox_fused=prox_kappa is not None, zx=True),
                  vmem_limit_mb, where="sparse_local_sdca_zx")

    f32 = jnp.float32
    cols = cols.astype(jnp.int32)
    vals = vals.astype(f32)
    y = y.astype(f32)
    alpha = alpha.astype(f32)
    mask = mask.astype(f32)
    w = w.astype(f32)
    sq = sqnorms.astype(f32)
    scale = jnp.asarray(scale, f32)

    kernel = functools.partial(_sparse_sdca_zx_kernel, loss=loss,
                               block_rows=B, r_max=r_max,
                               slot_unroll=slot_unroll,
                               prox_kappa=prox_kappa)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    u_shape = (lane_rows(d), LANES)
    zx_call = pl.pallas_call(
        kernel,
        in_specs=[smem] * 11 + [vmem],
        out_specs=[vmem, smem, smem],
        out_shape=[jax.ShapeDtypeStruct(u_shape, f32),
                   jax.ShapeDtypeStruct((1, B), f32),
                   jax.ShapeDtypeStruct((1, B), f32)],
        interpret=interpret,
        **_compiler_params(vmem_limit_mb, interpret),
    )

    def partial_dots(u, c_blk, v_blk):
        # per-row accumulation in ascending slot order -- the same
        # sequence the kernel's gather loop walks
        def body(r, z):
            return z + _prox(u[c_blk[:, r]], prox_kappa) * v_blk[:, r]

        return jax.lax.fori_loop(0, r_max, body, jnp.zeros(B, f32))

    def exchange(z):
        return jax.lax.psum(z, model_axis) if model_axis else z

    z0 = exchange(partial_dots(w, cols[:B], vals[:B]))

    def body(carry, g):
        u, dal, z_ex = carry
        blk = g % nb
        nxt = (g + 1) % nb
        sl = lambda arr, at: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            arr, at * B, B, axis=0)
        row = lambda arr: sl(arr, blk).reshape(1, B)       # noqa: E731
        u2, da2, zn = zx_call(
            scale.reshape(1, 1), sl(cols, blk), sl(vals, blk),
            sl(cols, nxt), sl(vals, nxt), z_ex.reshape(1, B), row(y),
            row(alpha), row(mask), row(sq), row(dal), u)
        dal = jax.lax.dynamic_update_slice_in_dim(dal, da2[0], blk * B,
                                                  axis=0)
        return (u2, dal, exchange(zn[0])), None

    (u, dalpha, _), _ = jax.lax.scan(
        body, (to_lanes(w), jnp.zeros(nk, f32), z0),
        jnp.arange(n_passes * nb, dtype=jnp.int32))
    return dalpha, u.reshape(-1)[:d] - w


def zx_exchanges(nk: int, block_rows: int, n_passes: int = 1) -> int:
    """Number of block_rows-sized z psums one zx round performs: one per
    scheduled block plus the prologue priming block 0 at u = w."""
    return n_passes * (nk // block_rows) + 1


# v5e has 1 MiB of SMEM per core; the ELL tiles may take three quarters,
# the rest is left to the compiler's own scalars
SMEM_BUDGET_KB = 768


def vmem_budget(nk: int, d: int, r_max: int, block_rows: int = 128,
                buffer_depth: int = 1, prox_fused: bool = False,
                model_shards: int = 1, zx: bool | None = None) -> dict:
    """Static on-chip working set of one launch (f32/int32 = 4 bytes).

    SMEM holds the cols/vals tiles and the per-row scalars
    (y/alpha/mask/sqnorm). Depth 1 gets its tiles from the implicit Pallas
    pipeline, which double-buffers them; depth >= 2 keeps a depth-slot DMA
    ring whose rows are padded to 128 lanes. VMEM holds the lane-layout
    vectors: w in and du out (each double-buffered by the pipeline) and
    the u scratch, plus dalpha's scratch and output. The fused prox is
    applied in-register on each gathered scalar -- zero extra memory
    (prox_kb stays 0; the flag is recorded so callers can see which
    schedule was priced). `zx` prices the z-exchange kernel instead
    (defaults on when model_shards > 1): two (B, r_max) cols+vals tile
    pairs (walk + next block), u resident twice (in + out), eight
    B-sized scalar rows (z/y/alpha/mask/sqnorms/dalpha-in/out/zn) -- and
    no full-nk dalpha, which lives outside the kernel in the scan carry.
    `zx_exchange_kb` is the psum'd wire buffer (block_rows floats)."""
    f = 4
    B = block_rows
    if zx is None:
        zx = model_shards > 1
    depth = max(1, buffer_depth)
    dense_tile = B * d * f
    u = -(-d // 128) * 128 * f
    if zx:
        tile = 2 * B * r_max * 2 * f           # walk + next (cols + vals)
        dalpha = B * f                         # in-kernel slice only
        smem = tile + 8 * B * f
        total = 2 * u
    else:
        slots, r_w = (2, r_max) if depth == 1 else \
            (depth, r_max + (-r_max) % 128)
        tile = slots * B * r_w * 2 * f         # cols + vals ring
        dalpha = -(-nk // 128) * 128 * f
        smem = tile + 2 * 4 * B * f
        total = 5 * u + 3 * dalpha
    return dict(ell_tile_kb=tile / 1024, smem_kb=smem / 1024,
                u_kb=u / 1024, dalpha_kb=dalpha / 1024,
                total_mb=total / 2**20,
                fits_16mb=(total < 16 * 2**20
                           and smem <= SMEM_BUDGET_KB * 1024),
                dense_tile_mb=dense_tile / 2**20,
                buffer_depth=depth, prox_fused=bool(prox_fused),
                prox_kb=0.0, zx=bool(zx),
                zx_exchange_kb=(B * f / 1024 if zx else 0.0),
                model_shards=max(1, model_shards))


def _enforce_vmem(budget: dict, vmem_limit_mb: int | None, *,
                  where: str) -> None:
    """Reject launch configs whose priced working set exceeds the VMEM
    limit (default 16 MiB) or the SMEM budget -- a loud ValueError at
    dispatch beats the compiler silently clamping/spilling (or interpret
    mode hiding it)."""
    limit = float(vmem_limit_mb) if vmem_limit_mb else 16.0
    if budget["total_mb"] > limit:
        raise ValueError(
            f"{where}: priced VMEM working set {budget['total_mb']:.2f} "
            f"MiB exceeds the {limit:.0f} MiB limit "
            f"(u {budget['u_kb']:.0f} KiB, dalpha "
            f"{budget['dalpha_kb']:.0f} KiB, zx={budget['zx']}); raise "
            f"vmem_limit_mb")
    if budget["smem_kb"] > SMEM_BUDGET_KB:
        raise ValueError(
            f"{where}: priced SMEM working set {budget['smem_kb']:.0f} KiB "
            f"exceeds the {SMEM_BUDGET_KB} KiB budget (block_rows x r_max "
            f"tile ring {budget['ell_tile_kb']:.0f} KiB, zx={budget['zx']}); "
            f"shrink block_rows/buffer_depth")
