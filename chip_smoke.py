"""Bring-up check: CoCoA+ on a TPU through its normal entry points, at the
paper's own data sizes.

    python chip_smoke.py              # one chip: sparse and dense phases
    python chip_smoke.py --chips 4    # four chips: the cross-chip path only

Everything runs in this one process, which holds the chip(s). Data is
generated from --seed at the shapes of the paper's data sets: rcv1
(677,399 x 47,236, density 0.0016) and epsilon (400,000 x 2,000, dense),
split over K=8 workers on the vmap backend, smooth hinge loss, adding
(gamma=1, sigma'=K). Each solver phase prints its certified gap per round
(which must be finite and fall), compile seconds, fenced seconds per round
and the device's peak bytes. Each SDCA kernel is run on one real shard and
compared with its `kernels/ref.py` oracle; the largest difference is
printed next to its bound.

`--chips 4` runs only what exists across chips: rcv1 on the shard_map
backend over a 4-chip data mesh against the vmap backend on one chip
(K=4), then a 2x2 (data, model) feature-sharded run against vmap K=2.

A failed check or phase exits non-zero. Without a TPU the script exits
non-zero before doing any work. The last line of standard output is one
JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

K = 8
ROUNDS = 5
LAM = 1e-4
LOSS = "smooth_hinge"
RCV1 = dict(n=677_399, d=47_236, density=0.0016)
EPSILON = dict(n=400_000, d=2_000)
# kernel vs oracle, both f32 on the chip: the kernel's scalar unit and
# XLA's vector code round differently, and a shard is one long sequential
# chain of coordinate steps, so the bound is relative to the answer's size
ORACLE_RTOL = 1e-3
# shard_map vs vmap: the same rounds with the cross-worker sum reduced in
# another order (all-reduce over chips vs one reduction on one chip); the
# CPU parity tests pin the same 1e-6
PARITY_RTOL = 1e-6
# local steps per round on the mesh path: the cross-chip phase compares
# backends round by round, so it needs rounds, not epochs (one epoch of a
# K=2 shard is 338,700 sequential steps)
MESH_H = 16_384


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


class Phase:
    """Collects the failed checks of one phase."""

    def __init__(self, name: str):
        self.name = name
        self.failures: list[str] = []

    def check(self, ok: bool, what: str) -> None:
        log(f"[{self.name}] check {'ok' if ok else 'FAILED'}: {what}")
        if not ok:
            self.failures.append(what)


def run_solve(ph: Phase, label: str, cfg, X, y, mask, *, seed: int,
              rounds: int = ROUNDS, mesh=None):
    """`solve` for `rounds` certified rounds; prints and checks the gaps.
    Returns (gaps, SolveResult)."""
    from repro.core import solve
    from repro.obs import Aggregator, EventBus

    bus = EventBus()
    agg = bus.subscribe(Aggregator())
    t0 = time.perf_counter()
    res = solve(cfg, X, y, mask, rounds=rounds, gap_every=1, seed=seed,
                mesh=mesh, obs=bus)
    wall = time.perf_counter() - t0
    recs = agg.records
    gaps = [r.gap for r in recs]
    for r in recs:
        log(f"[{ph.name}] {label} round {r.round}: gap={r.gap!r} "
            f"execute_s={r.execute_s!r} certificate_s={r.certificate_s!r}")
    compile_s = sum(r.compile_s for r in recs)
    per_round = sum(r.execute_s for r in recs) / max(len(recs), 1)
    log(f"[{ph.name}] {label}: compile_s={compile_s!r} "
        f"s_per_round={per_round!r} wall_s={wall!r} "
        f"peak_bytes_in_use={peak_bytes()}")
    ph.check(len(gaps) == rounds and all(math.isfinite(g) for g in gaps),
             f"{label}: {len(gaps)} finite certified gaps")
    ph.check(len(gaps) > 1 and gaps[-1] < gaps[0],
             f"{label}: gap falls {gaps[0]!r} -> {gaps[-1]!r}")
    return gaps, res


def compare(ph: Phase, label: str, got, want, rtol: float) -> None:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    diff = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    bound = rtol * max(scale, 1e-30)
    ph.check(bool(np.all(np.isfinite(got))) and diff <= bound,
             f"{label}: max|kernel - oracle| = {diff!r} "
             f"(max|oracle| = {scale!r}, bound {bound!r})")


def pad_rows(arrs, block):
    """Pad the leading (row) axis to a multiple of `block` with zeros --
    padded rows carry mask 0, so every kernel step on them is a no-op."""
    import jax.numpy as jnp
    n = arrs[0].shape[0]
    extra = (-n) % block
    return [jnp.pad(a, [(0, extra)] + [(0, 0)] * (a.ndim - 1))
            for a in arrs]


# ----------------------------------------------------------------------------
# one chip
# ----------------------------------------------------------------------------

def sparse_phase(seed: int) -> Phase:
    import jax.numpy as jnp
    from repro.core import CoCoAConfig
    from repro.core.losses import get_loss
    from repro.data.sparse import make_sparse_classification, partition_sparse
    from repro.kernels.ref import sparse_local_sdca_ref
    from repro.kernels.sparse_sdca import (sparse_local_sdca,
                                           sparse_local_sdca_zx)

    ph = Phase("sparse")
    t0 = time.perf_counter()
    csr, yv = make_sparse_classification(RCV1["n"], RCV1["d"],
                                         density=RCV1["density"], seed=seed)
    sh, y, mask = partition_sparse(csr, yv, K, seed=seed)
    X, y, mask = jax.device_put((sh, y, mask))
    nk, r_max = X.cols.shape[1:]
    log(f"[sparse] data: {RCV1['n']} x {RCV1['d']} density "
        f"{csr.density!r}, K={K} shards of {nk} rows, r_max={r_max}, "
        f"made in {time.perf_counter() - t0!r} s")
    del csr, yv, sh

    state = None
    for solver in ("sdca", "sdca_sparse_kernel"):
        cfg = CoCoAConfig.adding(K, loss=LOSS, lam=LAM, H=nk, solver=solver)
        _, res = run_solve(ph, solver, cfg, X, y, mask, seed=seed)
        state = res.state

    # the kernels on worker 0's real shard, at the state the kernel run
    # reached, against the sequential oracle
    loss = get_loss(LOSS)
    scale = jnp.float32(K / (LAM * RCV1["n"]))
    cols, vals, y0, a0, m0 = pad_rows(
        [X.cols[0], X.vals[0], y[0], state.alpha[0], mask[0]], 128)
    w = state.w
    ref = jax.jit(lambda *a: sparse_local_sdca_ref(*a, loss=loss))
    t0 = time.perf_counter()
    da_r, du_r = jax.block_until_ready(ref(cols, vals, y0, a0, m0, w,
                                           scale))
    log(f"[sparse] oracle on one shard: {time.perf_counter() - t0!r} s")
    for depth in (1, 2):
        kern = jax.jit(lambda *a, depth=depth: sparse_local_sdca(
            *a, loss=loss, buffer_depth=depth, interpret=False))
        t0 = time.perf_counter()
        da_k, du_k = jax.block_until_ready(kern(cols, vals, y0, a0, m0, w,
                                                scale))
        log(f"[sparse] kernel buffer_depth={depth} on one shard: "
            f"{time.perf_counter() - t0!r} s (compile included)")
        compare(ph, f"sparse kernel depth {depth} dalpha", da_k, da_r,
                ORACLE_RTOL)
        compare(ph, f"sparse kernel depth {depth} du", du_k, du_r,
                ORACLE_RTOL)

    # z-exchange schedule at block_rows=1 is sequential SDCA: the same
    # oracle, on the first 1,024 rows of the shard
    rows = slice(0, 1024)
    sub = (cols[rows], vals[rows], y0[rows], a0[rows], m0[rows])
    sq = jnp.sum(sub[1] * sub[1], axis=1)
    zx = jax.jit(lambda *a: sparse_local_sdca_zx(
        *a, loss=loss, block_rows=1, interpret=False))
    da_z, du_z = jax.block_until_ready(zx(*sub, w, scale, sq))
    da_zr, du_zr = ref(*sub, w, scale)
    compare(ph, "zx kernel (block_rows=1) dalpha", da_z, da_zr, ORACLE_RTOL)
    compare(ph, "zx kernel (block_rows=1) du", du_z, du_zr, ORACLE_RTOL)
    return ph


def dense_phase(seed: int) -> Phase:
    import jax.numpy as jnp
    from repro.core import CoCoAConfig
    from repro.core.losses import get_loss
    from repro.data.synthetic import make_classification, partition
    from repro.kernels.local_sdca import local_sdca_pallas
    from repro.kernels.ref import local_sdca_ref

    ph = Phase("dense")
    t0 = time.perf_counter()
    Xh, yv = make_classification(EPSILON["n"], EPSILON["d"], seed=seed)
    Xp, y, mask = partition(Xh, yv, K, seed=seed)
    del Xh, yv
    X, y, mask = jax.device_put((Xp, y, mask))
    del Xp
    nk = X.shape[1]
    log(f"[dense] data: {EPSILON['n']} x {EPSILON['d']}, K={K} shards of "
        f"{nk} rows, made in {time.perf_counter() - t0!r} s")

    state = None
    for solver in ("sdca", "sdca_kernel"):
        cfg = CoCoAConfig.adding(K, loss=LOSS, lam=LAM, H=nk, solver=solver)
        _, res = run_solve(ph, solver, cfg, X, y, mask, seed=seed)
        state = res.state

    loss = get_loss(LOSS)
    scale = jnp.float32(K / (LAM * EPSILON["n"]))
    X0, y0, a0, m0 = pad_rows([X[0], y[0], state.alpha[0], mask[0]], 128)
    w = state.w
    da_r, du_r = jax.jit(lambda *a: local_sdca_ref(*a, loss=loss))(
        X0, y0, a0, m0, w, scale)
    kern = jax.jit(lambda *a: local_sdca_pallas(*a, loss=loss,
                                                interpret=False))
    t0 = time.perf_counter()
    da_k, du_k = jax.block_until_ready(kern(X0, y0, a0, m0, w, scale))
    log(f"[dense] kernel on one shard: {time.perf_counter() - t0!r} s "
        f"(compile included)")
    compare(ph, "dense kernel dalpha", da_k, da_r, ORACLE_RTOL)
    compare(ph, "dense kernel du", du_k, du_r, ORACLE_RTOL)
    return ph


# ----------------------------------------------------------------------------
# four chips
# ----------------------------------------------------------------------------

def mesh_phase(seed: int) -> Phase:
    from repro.core import CoCoAConfig
    from repro.data.sparse import make_sparse_classification, partition_sparse

    ph = Phase("mesh")
    devs = jax.devices()[:4]
    csr, yv = make_sparse_classification(RCV1["n"], RCV1["d"],
                                         density=RCV1["density"], seed=seed)

    def parity(label, gaps_ref, gaps):
        rel = max(abs(a - b) / abs(b) for a, b in zip(gaps, gaps_ref))
        ph.check(rel <= PARITY_RTOL,
                 f"{label}: max relative gap difference per round "
                 f"{rel!r} (bound {PARITY_RTOL!r})")

    # (1) K=4 workers, one per chip, against K=4 simulated on one chip
    sh, y, mask = partition_sparse(csr, yv, 4, seed=seed)
    kw = dict(loss=LOSS, lam=LAM, H=MESH_H, solver="sdca")
    with jax.default_device(devs[0]):
        g_vmap, _ = run_solve(ph, "vmap K=4 (one chip)",
                              CoCoAConfig.adding(4, **kw), sh, y, mask,
                              seed=seed)
    mesh = jax.make_mesh((4,), ("data",), devices=devs)
    g_mesh, res = run_solve(ph, "shard_map K=4 (4 chips)",
                            CoCoAConfig.adding(4, backend="shard_map", **kw),
                            sh, y, mask, seed=seed, mesh=mesh)
    on = sorted(s.device.id for s in res.state.alpha.addressable_shards)
    log(f"[mesh] alpha shards live on devices {on}")
    ph.check(len(set(on)) == 4, f"alpha spread over 4 devices: {on}")
    parity("shard_map K=4 vs vmap K=4", g_vmap, g_mesh)

    # (2) 2x2 (data, model): w feature-sharded over the model axis
    sh2, y2, mask2 = partition_sparse(csr, yv, 2, seed=seed)
    fs, _, _ = partition_sparse(csr, yv, 2, seed=seed, M=2)
    kw2 = dict(loss=LOSS, lam=LAM, H=MESH_H, solver="sdca")
    with jax.default_device(devs[0]):
        g_vmap2, _ = run_solve(ph, "vmap K=2 (one chip)",
                               CoCoAConfig.adding(2, **kw2), sh2, y2, mask2,
                               seed=seed)
    mesh2 = jax.make_mesh((2, 2), ("data", "model"), devices=devs)
    g_2d, res2 = run_solve(ph, "shard_map 2x2 feature-sharded (4 chips)",
                           CoCoAConfig.adding(2, backend="shard_map",
                                              model_axis="model", **kw2),
                           fs, y2, mask2, seed=seed, mesh=mesh2)
    on = sorted(s.device.id for s in res2.state.w.addressable_shards)
    log(f"[mesh] w shards live on devices {on}")
    ph.check(len(set(on)) == 4, f"w placed on 4 devices: {on}")
    parity("2x2 feature-sharded vs vmap K=2", g_vmap2, g_2d)
    return ph


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the cross-chip phase, on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    cache = pathlib.Path(enable_compile_cache())
    entries = len(list(cache.glob("*"))) if cache.is_dir() else 0
    log(f"device: {dev.device_kind} x {len(jax.devices())}; compile cache "
        f"{cache} (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
        f") holds {entries} entries")

    t0 = time.perf_counter()
    phases = ([mesh_phase] if args.chips == 4
              else [sparse_phase, dense_phase])
    failed = []
    for fn in phases:
        ph = fn(args.seed)
        failed += [f"{ph.name}: {f}" for f in ph.failures]
    after = len(list(cache.glob("*"))) if cache.is_dir() else 0
    log(f"total {time.perf_counter() - t0!r} s; compile cache now holds "
        f"{after} entries")
    if failed:
        for f in failed:
            print(f"FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
