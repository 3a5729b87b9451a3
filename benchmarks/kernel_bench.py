"""LocalSDCA micro-benchmark: pure-JAX solver vs the Pallas kernel path
(interpret mode on CPU -- correctness/structure, not TPU timing) plus the
VMEM working-set analysis that substitutes for a hardware profile.

Reported: us per coordinate step (jnp path, jitted, CPU), the kernel's
per-block VMEM footprint vs the 16 MiB budget at production shapes, and the
dense-vs-sparse HBM roofline at the paper's densities (bytes one SDCA pass
must stream per layout: 4 bytes/element dense vs 8 bytes/stored-entry
padded-ELL, i.e. a 0.5/density traffic cut).

`--comm` runs the comm-volume vs gap-per-round sweep instead: the
repro.comm wire compressors at equal round count (floats actually
transmitted per round next to the duality gap reached); `--topology
hier:<g>|a2a` routes it through that reduce plan and adds the
cross-topology parity + per-hop volume sweep.

`--mesh KxM` runs the 2-D (data x model) feature-sharded mesh sweep --
vmap reference vs 1-D shard_map vs the KxM mesh across reduce plans, with
per-axis wire accounting -- and writes the machine-readable
benchmarks/results/BENCH_cocoa.json that tracks the gap/floats/wall-time
trajectory across PRs.

`--reg elastic:<eta>|l1s:<eps>` runs the generalized-objective sweep
instead: the requested regularizer vs the L2 baseline at equal settings
(rounds-to-gap, primal-w sparsity through the conjugate map, jnp vs
kernel solver), merged into BENCH_cocoa.json under "reg_sweep"."""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.losses import get_loss
from repro.core.solvers import local_sdca, local_sdca_sparse
from repro.kernels.ops import local_sdca_block, sparse_local_sdca_block
from repro.launch.compile_cache import enable_compile_cache

from .common import fenced_call, fenced_time, save


def bench_jnp(nk=2048, d=512, H=4096, iters=3):
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((nk, d)).astype(np.float32))
    y = jnp.asarray(np.sign(rng.standard_normal(nk)).astype(np.float32))
    a = jnp.zeros(nk)
    m = jnp.ones(nk)
    w = jnp.zeros(d)
    loss = get_loss("hinge")
    fn = jax.jit(lambda r: local_sdca(X, y, a, m, w, r, loss, 1e-4,
                                      float(nk), 8.0, H))
    s = fenced_time(fn, jax.random.PRNGKey(0), iters=iters, warmup=1)
    return s / H * 1e6


def vmem_analysis(nk=16384, d=16384, block_rows=128):
    """Static working-set check for the production paper-svm shard shape."""
    f = 4
    tile = block_rows * d * f
    u = d * f
    dalpha = nk * f
    total = tile + u + dalpha + 3 * block_rows * f
    return dict(x_tile_mb=tile / 2**20, u_kb=u / 1024,
                dalpha_kb=dalpha / 1024, total_mb=total / 2**20,
                fits_16mb=total < 16 * 2**20)


def sparse_roofline(densities=(0.003, 0.01, 0.05, 0.1), d=4096, nk=1024,
                    quick=True):
    """Dense vs padded-ELL bytes streamed per full SDCA pass over a shard,
    plus measured us/step of the jnp sparse solver at one paper density.

    One pass must re-stream the whole shard (SDCA is HBM-bound): dense moves
    nk*d*4 bytes, ELL moves nk*r_max*(4+4) bytes (int32 col + f32 val), so
    the cut is 0.5/density -- >= 5x everywhere at density <= 0.1."""
    from repro.data import sparse as sp

    rows = []
    for rho in densities:
        r_max = max(1, int(rho * d))           # exact-density rows
        dense_b = nk * d * 4
        ell_b = nk * r_max * 8
        rows.append(dict(density=rho, r_max=r_max, dense_bytes=dense_b,
                         ell_bytes=ell_b, cut=dense_b / ell_b))
        print(f"kernel,sparse_roofline,density={rho},bytes_cut="
              f"{dense_b / ell_b:.1f}x")

    # measured: jnp sparse solver vs dense solver, same shard, H steps
    rho = 0.01
    H = 512 if quick else 4096
    csr, y = sp.make_sparse_classification(nk, d, density=rho, seed=0)
    sh, yp, mk = sp.partition_sparse(csr, y, 1, seed=0)
    shard = jax.tree.map(lambda a: a[0], sh)
    Xd = sp.densify(sh)[0]
    loss = get_loss("hinge")
    w = jnp.zeros(d)
    a0 = jnp.zeros(yp.shape[1])

    def timed(fn):
        return fenced_time(fn, jax.random.PRNGKey(0),
                           iters=3, warmup=1) / H * 1e6

    f_sp = jax.jit(lambda r, s: local_sdca_sparse(
        s, yp[0], a0, mk[0], w, r, loss, 1e-4, float(nk), 4.0, H))
    f_de = jax.jit(lambda r, X: local_sdca(
        X, yp[0], a0, mk[0], w, r, loss, 1e-4, float(nk), 4.0, H))
    us_sp = timed(lambda r: f_sp(r, shard))
    us_de = timed(lambda r: f_de(r, Xd))
    print(f"kernel,sparse_jnp_us_per_step,{us_sp:.2f},dense={us_de:.2f},"
          f"speedup={us_de / us_sp:.1f}x")

    # interpret-mode sparse kernel roundtrip (interface under jit)
    _, dt = fenced_call(
        sparse_local_sdca_block,
        jax.tree.map(lambda a: a[:256], shard), yp[0][:256], a0[:256],
        mk[0][:256], w, jax.random.PRNGKey(0), loss, 1e-4, 256.0, 4.0, 256,
        interpret=True)
    print(f"kernel,sparse_pallas_interpret_roundtrip_s,{dt:.2f}")

    from repro.kernels.sparse_sdca import vmem_budget as sparse_vmem
    svm = sparse_vmem(nk=16384, d=47236, r_max=128)   # rcv1-scale shard
    print(f"kernel,sparse_vmem_total_mb,{svm['total_mb']:.2f},"
          f"fits={svm['fits_16mb']},dense_tile_mb={svm['dense_tile_mb']:.1f}")
    return dict(roofline=rows, sparse_us_per_step=us_sp,
                dense_us_per_step=us_de, vmem=svm)


def autotune_sweep(quick=True, nk=512, d=512, density=0.05,
                   reg_spec="elastic:0.5"):
    """`--autotune`: sweep the sparse SDCA kernel's launch knobs, persist
    the winner, and profile it.

    Sweeps block_rows (ELL block shape) x slot_unroll (slot-walk unroll
    depth) x buffer_depth (DMA prefetch ring: 1 = single-buffered via
    the implicit Pallas pipeline, 2/4 = explicit double/quad buffering)
    -- all visit-order-preserving, so every config returns bit-for-bit
    identical results and only time differs. The fenced-wall-clock
    winner is recorded into the autotune cache that `kernels.ops`
    dispatch consults (per (kernel, backend, d, r_max, density)), then
    the winning (block_rows, slot_unroll) is profiled at *every* swept
    depth through `repro.obs.prof.profile_fn` -- each depth's
    KernelProfile states the DMA-vs-compute split (t_memory_s vs
    t_compute_s, the overlap the multi-buffering is there to win) next
    to the measured wall -- plus the jnp sparse solver for reference.

    `reg_spec` (the `--reg` flag) extends the sweep along the v3 cache
    axes: the fused-prox kernel (the per-gather soft-threshold changes
    the slot-walk cost, so the non-L2 family gets its own winner under
    the (reg=family, model_shards=1) key and the
    `sparse_sdca_prox_wall_s` metric), and the M>1 z-exchange schedule
    (block_rows swept as the staleness window, winner recorded under
    model_shards=2, wall pinned as `sparse_sdca_zx_m2_wall_s` -- timed
    single-process with the psum elided, i.e. the schedule's scan +
    per-block-launch overhead, not a multi-host wire measurement).

    The whole run lands in `results/autotune.json` *and* appends to
    `results/history/autotune.jsonl` -- the trajectory the
    `repro.obs.regress` gate compares against its pinned baseline
    (per-depth `sparse_sdca_depth<k>_wall_s` metrics included)."""
    import functools

    from repro.core import get_regularizer
    from repro.data import sparse as sp
    from repro.kernels.autotune import get_cache
    from repro.kernels.ops import _prox_kappa_of
    from repro.kernels.sparse_sdca import (sparse_local_sdca,
                                           sparse_local_sdca_zx)
    from repro.obs.prof import default_hardware, profile_fn

    from .common import save

    loss = get_loss("hinge")
    csr, y = sp.make_sparse_classification(nk, d, density=density, seed=0)
    sh, yp, mk = sp.partition_sparse(csr, y, 1, seed=0)
    shard = jax.tree.map(lambda a: a[0], sh)
    cols, vals = shard.cols, shard.vals
    r_max = int(cols.shape[1])
    a0, m, w = jnp.zeros(nk), mk[0], jnp.zeros(d)
    scale = jnp.float32(1.0 / (1e-3 * nk))
    backend = jax.default_backend()
    interpret = backend != "tpu"

    brs = [b for b in ((64, 128) if quick else (32, 64, 128, 256))
           if nk % b == 0]
    uns = (1, 2) if quick else (1, 2, 4)
    depths = (1, 2) if quick else (1, 2, 4)
    iters = 2 if quick else 5
    knobs = ("block_rows", "slot_unroll", "buffer_depth")
    trials = []
    for br in brs:
        for un in uns:
            for dp in depths:
                fn = jax.jit(functools.partial(
                    sparse_local_sdca, loss=loss, n_passes=1, block_rows=br,
                    slot_unroll=un, buffer_depth=dp, interpret=interpret))
                s = fenced_time(fn, cols, vals, yp[0], a0, m, w, scale,
                                iters=iters, warmup=1)
                trials.append(dict(block_rows=br, slot_unroll=un,
                                   buffer_depth=dp, wall_s=float(s)))
                print(f"kernel,autotune,block_rows={br},slot_unroll={un},"
                      f"buffer_depth={dp},wall_s={s:.4f}")
    best = min(trials, key=lambda t: t["wall_s"])
    cache = get_cache()
    cache.record("sparse_sdca", backend, d=d, r_max=r_max, density=density,
                 config={k: best[k] for k in knobs}, wall_s=best["wall_s"])
    print(f"kernel,autotune,winner=block_rows={best['block_rows']}/"
          f"slot_unroll={best['slot_unroll']}/"
          f"buffer_depth={best['buffer_depth']},cache={cache.path}")

    # profile the winning (block_rows, slot_unroll) at every swept depth
    # -- the per-depth DMA(t_memory)-vs-compute split -- plus the jnp
    # sparse solver: measured wall next to the analytic HLO cost on the
    # active HardwareSpec
    hw = default_hardware()
    depth_profiles = []
    for dp in depths:
        fn = functools.partial(sparse_local_sdca, loss=loss, n_passes=1,
                               block_rows=best["block_rows"],
                               slot_unroll=best["slot_unroll"],
                               buffer_depth=dp, interpret=interpret)
        p = profile_fn(fn, cols, vals, yp[0], a0, m, w, scale,
                       name=f"sparse_sdca_depth{dp}", hw=hw, iters=iters,
                       shape=dict(nk=nk, d=d, r_max=r_max, density=density,
                                  block_rows=best["block_rows"],
                                  slot_unroll=best["slot_unroll"],
                                  buffer_depth=dp))
        depth_profiles.append(p)
        overlap = (p.t_memory_s + p.t_compute_s) / max(p.bound_s, 1e-30)
        print(f"kernel,profile,{p.name},wall_s={p.wall_s:.4f},"
              f"dma_s={p.t_memory_s:.3g},compute_s={p.t_compute_s:.3g},"
              f"overlap_headroom={overlap:.2f}x,dominant={p.dominant},"
              f"model_vs_measured={p.model_vs_measured:.2f}")
    p_kern = depth_profiles[depths.index(best["buffer_depth"])]
    H = nk
    p_jnp = profile_fn(
        lambda r: local_sdca_sparse(shard, yp[0], a0, m, w, r, loss, 1e-3,
                                    float(nk), 1.0, H),
        jax.random.PRNGKey(0), name="sdca_sparse_jnp", hw=hw, iters=iters,
        shape=dict(nk=nk, d=d, r_max=r_max, density=density, H=H))
    print(f"kernel,profile,{p_jnp.name},wall_s={p_jnp.wall_s:.4f},"
          f"flops={p_jnp.flops:.3g},hbm_bytes={p_jnp.hbm_bytes:.3g},"
          f"dominant={p_jnp.dominant},model_vs_measured="
          f"{p_jnp.model_vs_measured:.2f}")

    metrics = {"sparse_sdca_wall_s": p_kern.wall_s,
               "sdca_sparse_jnp_wall_s": p_jnp.wall_s}
    for p in depth_profiles:
        metrics[f"{p.name}_wall_s"] = p.wall_s

    # -- fused-prox axis: the requested non-L2 family, own cache key -------
    reg = get_regularizer(reg_spec) if reg_spec and reg_spec != "l2" else None
    prox_payload = None
    if reg is not None:
        kappa = _prox_kappa_of(reg, 1e-3)
        family = getattr(reg, "family", "other")
        trials_p = []
        for br in brs:
            for un in uns:
                fn = jax.jit(functools.partial(
                    sparse_local_sdca, loss=loss, n_passes=1, block_rows=br,
                    slot_unroll=un, buffer_depth=best["buffer_depth"],
                    prox_kappa=kappa, interpret=interpret))
                s = fenced_time(fn, cols, vals, yp[0], a0, m, w, scale,
                                iters=iters, warmup=1)
                trials_p.append(dict(block_rows=br, slot_unroll=un,
                                     buffer_depth=best["buffer_depth"],
                                     wall_s=float(s)))
                print(f"kernel,autotune,reg={family},block_rows={br},"
                      f"slot_unroll={un},wall_s={s:.4f}")
        best_p = min(trials_p, key=lambda t: t["wall_s"])
        cache.record("sparse_sdca", backend, d=d, r_max=r_max,
                     density=density, config={k: best_p[k] for k in knobs},
                     wall_s=best_p["wall_s"], reg=family)
        p_prox = profile_fn(
            functools.partial(sparse_local_sdca, loss=loss, n_passes=1,
                              block_rows=best_p["block_rows"],
                              slot_unroll=best_p["slot_unroll"],
                              buffer_depth=best_p["buffer_depth"],
                              prox_kappa=kappa, interpret=interpret),
            cols, vals, yp[0], a0, m, w, scale,
            name="sparse_sdca_prox", hw=hw, iters=iters,
            shape=dict(nk=nk, d=d, r_max=r_max, density=density,
                       reg=family, **{k: best_p[k] for k in knobs}))
        print(f"kernel,profile,{p_prox.name},wall_s={p_prox.wall_s:.4f},"
              f"reg={family},winner=block_rows={best_p['block_rows']}/"
              f"slot_unroll={best_p['slot_unroll']}")
        metrics["sparse_sdca_prox_wall_s"] = p_prox.wall_s
        depth_profiles.append(p_prox)

        # -- M=2 z-exchange schedule: block_rows is the staleness window --
        sq = jnp.sum(vals * vals, axis=1)
        trials_z = []
        for br in (8, 16, 32):
            if nk % br:
                continue
            fn = jax.jit(functools.partial(
                sparse_local_sdca_zx, loss=loss, n_passes=1, block_rows=br,
                prox_kappa=kappa, interpret=interpret))
            s = fenced_time(fn, cols, vals, yp[0], a0, m, w, scale, sq,
                            iters=iters, warmup=1)
            trials_z.append(dict(block_rows=br, slot_unroll=1,
                                 buffer_depth=1, wall_s=float(s)))
            print(f"kernel,autotune,zx_m2,block_rows={br},wall_s={s:.4f}")
        best_z = min(trials_z, key=lambda t: t["wall_s"])
        cache.record("sparse_sdca", backend, d=d, r_max=r_max,
                     density=density, config={k: best_z[k] for k in knobs},
                     wall_s=best_z["wall_s"], reg=family, model_shards=2)
        print(f"kernel,autotune,zx_m2,winner=block_rows="
              f"{best_z['block_rows']} (single-process schedule wall; "
              f"psum elided)")
        metrics["sparse_sdca_zx_m2_wall_s"] = best_z["wall_s"]
        prox_payload = dict(reg=family, trials=trials_p, winner=best_p,
                            zx_trials=trials_z, zx_winner=best_z)

    payload = dict(backend=backend, hw=hw.name, nk=nk, d=d, density=density,
                   r_max=r_max, trials=trials, winner=best,
                   cache_path=str(cache.path), prox=prox_payload,
                   profiles=[p.to_dict() for p in depth_profiles]
                   + [p_jnp.to_dict()],
                   metrics=metrics)
    save("autotune", payload)      # snapshot + history/autotune.jsonl
    return payload


def comm_sweep(quick=True, K=4, n=512, d=2048, density=0.01,
               topology="flat"):
    """Comm-volume vs gap-per-round: the repro.comm compressors at equal
    round count on one sparse problem, under the requested reduce topology.

    For each wire scheme (dense baseline, top-k, top-k with compressed
    sparse gather, rand-k, 8-bit stochastic quantization, int8) run the
    same CoCoA+ rounds and report the tracer's actual floats/round next to
    the duality gap reached -- the trade the paper's Fig-2 communication
    model prices. The gap under compression is certified at the w the
    algorithm carries (duality.gap_at_w)."""
    from repro.core import CoCoAConfig, solve
    from repro.data import sparse as sp

    rounds = 6 if quick else 24
    H = 256 if quick else 1024
    k = 64
    csr, y = sp.make_sparse_classification(n, d, density=density, seed=0)
    sh, yp, mk = sp.partition_sparse(csr, y, K, seed=1)

    rows = []
    dense_floats = None
    schemes = (("none", False), ("topk", False), ("topk", True),
               ("randk", False), ("qsgd", False), ("int8", False))
    for method, gather in schemes:
        cfg = CoCoAConfig.adding(K, loss="hinge", lam=1e-3, H=H,
                                 compress=method, compress_k=k,
                                 topology=topology, gather=gather)
        r = solve(cfg, sh, yp, mk, rounds=rounds, gap_every=1, seed=2)
        fl = r.history["comm_floats"][-1] // r.history["round"][-1]
        if method == "none":
            dense_floats = fl
        cut = dense_floats / max(fl, 1)
        label = method + ("+gather" if gather else "")
        rows.append(dict(method=label, k=k, topology=topology,
                         floats_per_round=fl, cut=cut,
                         gap=r.history["gap"][-1],
                         gap_first=r.history["gap"][0],
                         monotone=all(b <= a * 1.05 for a, b in
                                      zip(r.history["gap"],
                                          r.history["gap"][1:]))))
        print(f"comm,sweep,topology={topology},method={label},k={k},"
              f"floats_per_round={fl},cut={cut:.1f}x,"
              f"gap={r.history['gap'][-1]:.3e}")
    save("comm_sweep", dict(K=K, n=n, d=d, density=density, rounds=rounds,
                            topology=topology, rows=rows))
    return rows


def topology_sweep(quick=True, K=4, n=512, d=2048, density=0.01):
    """Reduce-topology sweep: flat vs hier:2 vs a2a, dense and compressed-
    gather wire, at equal round count -- per-hop volumes from the tracer
    plus the w-parity error vs the flat reduce (the collectives must
    compute the same sum; only the wire plan changes)."""
    import jax.numpy as jnp

    from repro import comm
    from repro.core import CoCoAConfig, solve
    from repro.data import sparse as sp

    rounds = 4 if quick else 12
    H = 256 if quick else 1024
    k = 64
    csr, y = sp.make_sparse_classification(n, d, density=density, seed=0)
    sh, yp, mk = sp.partition_sparse(csr, y, K, seed=1)

    rows = []
    w_ref = {}
    for gather in (False, True):
        comp = dict(compress="topk", compress_k=k) if gather else {}
        for topo in ("flat", "hier:2", "a2a"):
            cfg = CoCoAConfig.adding(K, loss="hinge", lam=1e-3, H=H,
                                     topology=topo, gather=gather, **comp)
            r = solve(cfg, sh, yp, mk, rounds=rounds, gap_every=rounds,
                      seed=2)
            if topo == "flat":
                w_ref[gather] = r.state.w
            err = float(jnp.max(jnp.abs(r.state.w - w_ref[gather])))
            tr = comm.CommTracer.for_run(
                K=K, d_local=d, compressor=cfg.compressor(),
                topo=comm.Topology.simulated(K, topology=topo),
                gather=gather)
            hops = ";".join(f"{h['hop']}={h['floats']}"
                            for h in tr.per_hop())
            label = topo + ("+gather" if gather else "")
            rows.append(dict(topology=label, floats_per_round=tr.per_round()
                             ["floats"], hops=tr.per_hop(), w_err_vs_flat=err,
                             gap=r.history["gap"][-1]))
            print(f"comm,topology,{label},floats_per_round="
                  f"{tr.per_round()['floats']},hops={hops},"
                  f"w_err_vs_flat={err:.2e},gap={r.history['gap'][-1]:.3e}")
            assert err < 1e-5, (label, err)
    save("topology_sweep", dict(K=K, n=n, d=d, rounds=rounds, rows=rows))
    return rows


def mesh_sweep(mesh_spec="2x2", quick=True, n=512, d=2048, density=0.01):
    """2-D (data x model) mesh sweep -> machine-readable BENCH_cocoa.json.

    Runs the same sparse CoCoA+ problem as (1) the vmap reference, (2)
    shard_map on a 1-D (K,) data mesh (replicated w), and (3) shard_map on
    the requested (K, M) mesh with w feature-sharded, across
    flat / hier / a2a reduce plans. Each row records gap-vs-round, the
    tracer's floats/round with the per-axis and per-hop split, wall time,
    and the w-parity error vs the vmap reference -- the perf/correctness
    trajectory file CI keeps across PRs. Asserts parity (1e-5) and that
    the data-axis reduce volume is the analytic K * ceil(d/M)."""
    from repro import comm
    from repro.core import CoCoAConfig, solve
    from repro.data import sparse as sp

    K, M = (int(v) for v in mesh_spec.lower().split("x"))
    need = K * M
    if jax.device_count() < need:
        print(f"cocoa,mesh_sweep,SKIPPED: needs {need} devices, have "
              f"{jax.device_count()} (set XLA_FLAGS="
              f"--xla_force_host_platform_device_count={need})")
        return []
    rounds = 4 if quick else 16
    H = 256 if quick else 1024
    csr, y = sp.make_sparse_classification(n, d, density=density, seed=0)
    sh, yp, mk = sp.partition_sparse(csr, y, K, seed=1)
    fs = sp.shard_features(sh, M)
    kw = dict(loss="hinge", lam=1e-3, H=H)

    rows = []

    def record(label, backend, mesh_shape, topo, r, dt, w_ref=None):
        cfg_ = r[0]
        hist = r[1].history
        st = r[1].state
        w_err = (float(jnp.max(jnp.abs(st.w[:d] - w_ref[:d])))
                 if w_ref is not None else 0.0)
        wspec = comm.WSpec(d=d, M=mesh_shape[1] if len(mesh_shape) > 1
                           else 1, model_axis="model"
                           if len(mesh_shape) > 1 and mesh_shape[1] > 1
                           else None)
        tr = comm.CommTracer.for_run(
            K=K, d_local=wspec.d_local, compressor=cfg_.compressor(),
            topo=comm.Topology.simulated(K, topology=topo), gather=False,
            extra_hops=comm.model_hops(wspec, K, H))
        reduce_floats = sum(h["floats"] for h in tr.per_hop()
                            if h["axis"] == "data")
        rows.append(dict(
            label=label, backend=backend, mesh="x".join(map(str, mesh_shape)),
            topology=topo, M=wspec.M, d_local=wspec.d_local,
            rounds=hist["round"], gap_vs_round=hist["gap"],
            floats_per_round=hist["comm_floats"][-1] // hist["round"][-1],
            reduce_floats_per_round=reduce_floats,
            per_axis=tr.per_axis(), per_hop=tr.per_hop(),
            wall_time_s=round(dt, 3), w_err_vs_vmap=w_err))
        print(f"cocoa,mesh_sweep,{label},gap={hist['gap'][-1]:.3e},"
              f"floats_per_round={rows[-1]['floats_per_round']},"
              f"reduce_floats={reduce_floats},wall_s={dt:.2f},"
              f"w_err={w_err:.2e}")
        return w_err

    def timed_solve(cfg, X, mesh=None):
        r, dt = fenced_call(solve, cfg, X, yp, mk, rounds=rounds,
                            gap_every=1, seed=2, mesh=mesh)
        return (cfg, r), dt

    # 1) vmap reference
    cfgv = CoCoAConfig.adding(K, **kw)
    rv, dt = timed_solve(cfgv, sh)
    record("vmap_flat", "vmap", (K,), "flat", rv, dt)
    w_ref = rv[1].state.w

    # 2) shard_map 1-D data mesh (replicated w)
    mesh1 = jax.make_mesh((K,), ("data",))
    cfg1 = CoCoAConfig.adding(K, backend="shard_map", **kw)
    r1, dt = timed_solve(cfg1, sh, mesh1)
    err = record("shard_map_1d_flat", "shard_map", (K,), "flat", r1, dt,
                 w_ref)
    assert err < 1e-5, err

    # 3) shard_map 2-D feature-sharded mesh, across reduce plans
    mesh2 = jax.make_mesh((K, M), ("data", "model"))
    topos = ["flat"] + (["hier:2"] if K % 2 == 0 and K >= 2 else []) \
        + ["a2a"]
    for topo in topos:
        cfg2 = CoCoAConfig.adding(K, backend="shard_map",
                                  model_axis="model", topology=topo, **kw)
        r2, dt = timed_solve(cfg2, fs, mesh2)
        err = record(f"shard_map_{mesh_spec}_{topo}", "shard_map", (K, M),
                     topo, r2, dt, w_ref)
        assert err < 1e-5, (topo, err)
        # the data-axis reduce prices at d/M per message -- analytically
        d_loc = -(-d // M)
        flat_reduce = K * d_loc
        if topo == "flat":
            assert rows[-1]["reduce_floats_per_round"] == flat_reduce, \
                (rows[-1]["reduce_floats_per_round"], flat_reduce)
    from .common import save_updated
    save_updated("BENCH_cocoa", dict(mesh=mesh_spec, K=K, M=M, n=n, d=d,
                                     density=density, rounds=rounds, H=H,
                                     rows=rows))
    print(f"cocoa,mesh_sweep,saved=BENCH_cocoa.json,rows={len(rows)}")
    return rows


def reg_sweep(reg_spec="elastic:0.5", quick=True, K=4, n=512, d=2048,
              density=0.01):
    """Generalized-objective sweep -> merged into BENCH_cocoa.json.

    Runs the same sparse CoCoA+ problem under L2 and under the requested
    regularizer (elastic net / smoothed L1) at equal (lam, H, aggregator)
    settings, jnp and Pallas-kernel solver paths, and records rounds-to-gap,
    the final generalized duality gap, and the primal-w sparsity the
    conjugate map produces. Asserts the regularized run still certifies
    (gap decreases and stays nonnegative) and that the kernel path -- with
    the conjugate map now fused *inside* pallas_call, applied per step on
    the gathered entries exactly like the jnp solver -- lands in the same
    gap regime at equal rounds (the old hoisted-map path only had to get
    within 10x; the fused path is held to 1.5x)."""
    import jax.numpy as jnp

    from repro.core import CoCoAConfig, get_regularizer, primal_w, solve
    from repro.data import sparse as sp

    from .common import save_updated

    rounds = 8 if quick else 32
    H = 256 if quick else 1024
    eps = 1e-3
    csr, y = sp.make_sparse_classification(n, d, density=density, seed=0)
    sh, yp, mk = sp.partition_sparse(csr, y, K, seed=1)

    rows = []
    for spec, solver in (("l2", "sdca"), (reg_spec, "sdca"),
                         (reg_spec, "sdca_kernel")):
        cfg = CoCoAConfig.adding(K, loss="smooth_hinge", lam=1e-3, H=H,
                                 solver=solver, reg=spec)
        r = solve(cfg, sh, yp, mk, rounds=rounds, eps_gap=eps, gap_every=1,
                  seed=2)
        reg = get_regularizer(spec)
        w = primal_w(r.state, cfg)
        nnz = int(jnp.sum(jnp.abs(w) > 0))
        gaps = r.history["gap"]
        # a run that hits eps at the very first gap check has one entry --
        # that's convergence, not a regression
        assert min(gaps) > -1e-6, (spec, gaps)
        assert len(gaps) == 1 or gaps[-1] < gaps[0], (spec, gaps)
        rows.append(dict(reg=reg.name, solver=solver,
                         rounds=r.history["round"][-1], gap=gaps[-1],
                         gap_vs_round=gaps, w_nnz=nnz, w_dim=int(w.shape[0]),
                         floats_per_round=(r.history["comm_floats"][-1]
                                           // r.history["round"][-1])))
        print(f"cocoa,reg_sweep,reg={reg.name},solver={solver},"
              f"rounds={rows[-1]['rounds']},gap={gaps[-1]:.3e},"
              f"w_nnz={nnz}/{d}")
    # the kernel path applies the conjugate map per step in-kernel, same
    # algorithm as the jnp path -- hold it to the same gap regime
    assert rows[2]["gap"] < 1.5 * max(rows[1]["gap"], eps), rows

    save_updated("BENCH_cocoa", {"reg_sweep": dict(
        reg=reg_spec, K=K, n=n, d=d, density=density, rounds=rounds, H=H,
        rows=rows)})
    print(f"cocoa,reg_sweep,saved=BENCH_cocoa.json,rows={len(rows)}")
    return rows


def accel_sweep(quick=True, schedules=("nesterov:16", "catalyst:20")):
    """Accelerated-outer-rounds sweep -> `accel_sweep` in BENCH_cocoa.json
    plus the `accel` regression trajectory (history/accel.jsonl, gated by
    `python -m repro.obs.regress --name accel`).

    Runs the pinned ill-conditioned regression problem (data.synthetic
    "illcond" family: cond=100, Gram condition ~1e4 -- the regime where
    plain rounds crawl and outer momentum pays) at identical (loss, lam,
    H, aggregator) under accel=none and each momentum schedule, and
    records rounds-to-1e-4-gap. Fewer rounds is the cheapest bandwidth:
    momentum moves ZERO extra floats per round (tests/test_accel.py
    asserts it against the tracer), so the rounds ratio IS the wire
    ratio. The run asserts the suite-wide >= 1.3x win (measured ~2.8x:
    none = 125, nesterov:16 = 45, catalyst:20 = 45) so CI smoke catches
    a broken schedule, and the regress gate catches a slow drift.

    The problem is solver-deterministic (seeded), so quick and full run
    the SAME config -- the gated metrics must stay comparable to the
    pinned baseline across modes."""
    del quick  # deterministic metric: one config for CI smoke and full
    from repro.core import CoCoAConfig, solve
    from repro.data import make_classification, partition

    from .common import Timer, save, save_updated

    n, d, K, rounds, eps = 2048, 128, 8, 300, 1e-4
    X, y = make_classification(n, d, seed=0, cond=100.0)
    Xp, yp, mk = partition(X, y, K, seed=0)
    kw = dict(loss="squared", lam=5e-4, H=128, solver="sdca",
              aggregator="add")

    rows = []
    for accel in ("none",) + tuple(schedules):
        cfg = CoCoAConfig(accel=accel, **kw)
        with Timer() as t:
            r = solve(cfg, Xp, yp, mk, rounds=rounds, eps_gap=eps,
                      gap_every=1, seed=0)
        gaps = r.history["gap"]
        assert gaps[-1] <= eps, (accel, gaps[-1])   # everyone must certify
        rows.append(dict(accel=accel, rounds=r.history["round"][-1],
                         gap=gaps[-1], wall_s=t.s,
                         floats_per_round=(r.history["comm_floats"][-1]
                                           // r.history["round"][-1]),
                         gap_vs_round=gaps))
        print(f"cocoa,accel_sweep,accel={accel},rounds={rows[-1]['rounds']},"
              f"gap={gaps[-1]:.3e},wall_s={t.s:.2f}")
    r_none = rows[0]["rounds"]
    for row in rows[1:]:
        assert r_none >= 1.3 * row["rounds"], (row["accel"], row["rounds"],
                                               r_none)
        # zero extra wire: identical per-round floats
        assert row["floats_per_round"] == rows[0]["floats_per_round"], row

    save_updated("BENCH_cocoa", {"accel_sweep": dict(
        n=n, d=d, K=K, cond=100.0, eps_gap=eps, config=kw,
        rows=[{k: v for k, v in r.items() if k != "gap_vs_round"}
              for r in rows],
        gap_vs_round={r["accel"]: r["gap_vs_round"] for r in rows})})
    # separate regress trajectory: rounds are deterministic (smaller is
    # better, same comparator as the wall-clock metrics)
    metrics = {"rounds_to_gap_none": float(r_none),
               "rounds_to_gap_accel": float(min(r["rounds"]
                                                for r in rows[1:]))}
    for row in rows[1:]:
        key = row["accel"].replace(":", "_")
        metrics[f"rounds_to_gap_{key}"] = float(row["rounds"])
    save("accel", dict(n=n, d=d, K=K, cond=100.0, eps_gap=eps, config=kw,
                       metrics=metrics))
    print(f"cocoa,accel_sweep,saved=BENCH_cocoa.json+accel.json,"
          f"none={r_none},best_accel={metrics['rounds_to_gap_accel']:.0f}")
    return rows


def obs_quick(quick=True, K=4, rounds=None):
    """Small end-to-end CoCoA+ solve through the obs pipeline -> the
    wall-clock fields in BENCH_cocoa.json (compile/execute/certify split,
    round latency percentiles, sustained wire floats/sec). Runs in the
    default `--quick` CI step, so the trajectory file carries measured
    time next to gap and floats across PRs -- same fenced timers as the
    trainer's RoundRecords, so the two are directly comparable."""
    from repro.core import CoCoAConfig, solve
    from repro.data import load, partition
    from repro.obs import Aggregator, EventBus

    from .common import save_updated

    rounds = rounds or (6 if quick else 24)
    X, y = load("tiny")
    Xp, yp, mk = partition(X, y, K, seed=0)
    cfg = CoCoAConfig.adding(K, loss="hinge", lam=1e-4,
                             H=256 if quick else 1024)
    bus = EventBus()
    agg = bus.subscribe(Aggregator())
    solve(cfg, Xp, yp, mk, rounds=rounds, gap_every=2, seed=2, obs=bus)
    bus.close()
    s = agg.summary()
    save_updated("BENCH_cocoa", {"kernel_quick": s})
    print(f"cocoa,obs_quick,rounds={s['rounds']},gap={s['final_gap']:.3e},"
          f"compile_s={s['compile_s']:.2f},"
          f"round_p50_ms={1e3 * s['round_p50_s']:.2f},"
          f"round_p99_ms={1e3 * s['round_p99_s']:.2f},"
          f"wire_floats_per_sec={s['wire_floats_per_sec']:.3g}")
    return s


def run(quick: bool = True):
    us = bench_jnp(H=1024 if quick else 8192)
    print(f"kernel,jnp_sdca_us_per_step,{us:.2f}")
    # kernel interpret path end-to-end (correctness exercised in tests; here
    # we time a small call to show the interface works under jit)
    rng = np.random.default_rng(0)
    nk, d = 256, 256
    X = jnp.asarray(rng.standard_normal((nk, d)).astype(np.float32))
    y = jnp.asarray(np.sign(rng.standard_normal(nk)).astype(np.float32))
    _, dt = fenced_call(local_sdca_block, X, y, jnp.zeros(nk), jnp.ones(nk),
                        jnp.zeros(d), jax.random.PRNGKey(0),
                        get_loss("hinge"), 1e-4, float(nk), 4.0, nk,
                        interpret=True)
    print(f"kernel,pallas_interpret_roundtrip_s,{dt:.2f}")
    vm = vmem_analysis()
    print(f"kernel,vmem_total_mb,{vm['total_mb']:.2f},fits={vm['fits_16mb']}")
    sparse = sparse_roofline(quick=quick)
    save("kernel_bench", dict(jnp_us_per_step=us, vmem=vm, sparse=sparse))
    obs_quick(quick=quick)
    return vm


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--quick", action="store_true",
                      help="CI smoke mode: fewer inner steps (the default)")
    mode.add_argument("--full", action="store_true",
                      help="full step counts for stable timings")
    ap.add_argument("--comm", action="store_true",
                    help="run only the comm-volume vs gap sweep")
    ap.add_argument("--topology", default="flat",
                    help="reduce plan for --comm: flat | hier:<g> | a2a "
                         "(also triggers the cross-topology parity sweep "
                         "when not flat)")
    ap.add_argument("--mesh", default="",
                    help="run the 2-D (data x model) mesh sweep for this "
                         "'KxM' shape and write BENCH_cocoa.json (needs "
                         "K*M devices; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count)")
    ap.add_argument("--reg", default="",
                    help="run the generalized-objective sweep for this "
                         "regularizer (elastic:<eta> | l1s:<eps>) vs the "
                         "L2 baseline; merges into BENCH_cocoa.json. "
                         "Combined with --autotune it instead selects the "
                         "regularizer axis of the launch-config sweep "
                         "(default elastic:0.5)")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep the sparse kernel launch config (L2, the "
                         "--reg fused-prox family, and the M=2 z-exchange "
                         "schedule), persist the winners to the autotune "
                         "cache, and append a profiled run record to "
                         "results/history/ for the repro.obs.regress gate")
    ap.add_argument("--accel", action="store_true",
                    help="run the accelerated-outer-rounds sweep (none vs "
                         "nesterov:16 vs catalyst:20 rounds-to-gap on the "
                         "ill-conditioned pin) -> accel_sweep in "
                         "BENCH_cocoa.json + the accel regress trajectory "
                         "(gate: python -m repro.obs.regress --name accel)")
    args = ap.parse_args()
    if args.accel:
        accel_sweep(quick=not args.full)
    elif args.autotune:
        autotune_sweep(quick=not args.full,
                       reg_spec=args.reg or "elastic:0.5")
    elif args.reg:
        reg_sweep(reg_spec=args.reg, quick=not args.full)
    elif args.mesh:
        mesh_sweep(mesh_spec=args.mesh, quick=not args.full)
    elif args.comm:
        comm_sweep(quick=not args.full, topology=args.topology)
        if args.topology != "flat":
            topology_sweep(quick=not args.full)
    else:
        run(quick=not args.full)


if __name__ == "__main__":
    main()
