"""Production CoCoA+ trainer CLI — the paper's workload end to end with the
framework's operational features (checkpoint/restart, straggler budgeting,
elastic re-partitioning).

    PYTHONPATH=src python -m repro.launch.cocoa_train \
        --dataset covtype_like --workers 8 --rounds 60 --eps 1e-3 \
        --gamma add --ckpt /tmp/cocoa_ckpt [--simulate-failure 20] \
        [--simulate-straggler 2] [--elastic-to 16@30]

    # the paper's sparse regime: padded-ELL shards + sparse LocalSDCA
    PYTHONPATH=src python -m repro.launch.cocoa_train \
        --dataset rcv1_sparse --format sparse --workers 16 --rounds 40

    # compressed communication: top-64 sparsified Delta w with error
    # feedback -- the tracer reports actual floats on the wire per round
    PYTHONPATH=src python -m repro.launch.cocoa_train \
        --dataset rcv1_sparse --workers 16 --rounds 40 \
        --compress topk --compress-k 64

    # hierarchical (multi-pod) reduce + compressed sparse gather: groups of
    # 4 workers psum intra-pod, pod aggregates cross; the reduce itself
    # moves 2kK floats of (idx, val) sets, not dense d-vectors
    PYTHONPATH=src python -m repro.launch.cocoa_train \
        --dataset rcv1_sparse --workers 16 --rounds 40 \
        --topology hier:4 --compress topk --compress-k 64 --gather

    # 2-D (data x model) mesh: 4 workers x 2 feature shards of w -- each
    # device stores and reduces d/2 floats (ELL column ids remapped to the
    # local slice); needs 8 devices (XLA_FLAGS=...device_count=8 on CPU)
    PYTHONPATH=src python -m repro.launch.cocoa_train \
        --dataset rcv1_sparse --mesh 4x2 --rounds 40

    # generalized objective: elastic-net (sparse w) via the conjugate map
    # w = grad g*(v); the duality-gap certificate generalizes with it
    PYTHONPATH=src python -m repro.launch.cocoa_train \
        --dataset rcv1_sparse --rounds 40 --reg elastic:0.5

On a real TPU mesh pass --backend shard_map (workers = data-axis shards);
the default vmap backend simulates any K on one device with identical
math. Both layouts run on both backends (sparse = per-device padded-ELL
shards + one psum of w-sized shards per round). --format auto picks the
layout from the dataset spec; --aggregator {add,avg,gamma:<g>} picks the
repro.comm aggregation strategy (overriding the legacy --gamma switch).
"""
from __future__ import annotations

import argparse
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro import comm
from repro.checkpoint import CheckpointManager
from repro.core import CoCoAConfig, solve
from repro.core.cocoa import CoCoAState, init_state, reshard_w_state
from repro.core.solvers import sparse_counterpart
from repro.core.regularizers import get_regularizer
from repro.data import DATASETS, load, partition
from repro.data.sparse import (FeatureShards, SparseShards, partition_sparse,
                               shard_features)
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import Aggregator, Dashboard, EventBus, JsonlSink, ProfilerSink
from repro.runtime import elastic, failures, straggler


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="covtype_like")
    ap.add_argument("--loss", default="hinge")
    ap.add_argument("--reg", default="l2",
                    help="regularizer g(w): l2 | elastic:<eta> (elastic "
                         "net, lam*(eta*L1 + (1-eta)/2*L2)) | l1s:<eps> "
                         "(smoothed L1 / Lasso, lam*L1 + eps/2*L2)")
    ap.add_argument("--lam", type=float, default=1e-4)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--H", type=int, default=2048)
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--eps", type=float, default=1e-3)
    ap.add_argument("--gamma", choices=["add", "avg"], default="add")
    ap.add_argument("--aggregator", default="",
                    help="comm aggregation strategy: add | avg | gamma:<g> "
                         "(overrides --gamma when set)")
    ap.add_argument("--compress", default="none",
                    choices=["none", "topk", "randk", "qsgd", "int8"],
                    help="wire compression for Delta w_k (error feedback)")
    ap.add_argument("--compress-k", type=int, default=64,
                    help="kept coordinates for --compress topk/randk")
    ap.add_argument("--topology", default="flat",
                    help="reduce plan: flat | hier:<g> (two-level, groups "
                         "of g workers) | a2a (reduce-scatter + all-gather)")
    ap.add_argument("--gather", action="store_true",
                    help="compressed sparse gather: the reduce moves each "
                         "worker's top-k (idx, val) set (~2kK floats/round) "
                         "instead of dense vectors; needs --compress "
                         "topk/randk")
    ap.add_argument("--solver", default="sdca",
                    choices=["sdca", "sdca_kernel", "sdca_sparse",
                             "sdca_sparse_kernel", "gd", "sdca_deadline"])
    ap.add_argument("--accel", default="none",
                    help="outer momentum over the round operator: none | "
                         "nesterov[:<restart>] | catalyst:<kappa> -- fewer "
                         "rounds at zero extra wire floats (core.accel)")
    ap.add_argument("--backend", default="vmap", choices=["vmap", "shard_map"])
    ap.add_argument("--mesh", default="",
                    help="'KxM' 2-D (data x model) mesh: K workers, w "
                         "feature-sharded into M slices of ceil(d/M) "
                         "floats each (forces --backend shard_map and "
                         "overrides --workers; needs K*M devices)")
    ap.add_argument("--format", default="auto",
                    choices=["auto", "dense", "sparse"],
                    help="data layout; auto follows the dataset spec "
                         "(sparse -> padded-ELL shards + sparse solvers)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--simulate-failure", type=int, default=0,
                    help="drop worker 0 at this round (dual-safe recovery)")
    ap.add_argument("--simulate-straggler", type=int, default=-1,
                    help="worker index running at 10%% speed (deadline budget)")
    ap.add_argument("--elastic-to", default="",
                    help="'K@round': re-partition to K workers at round")
    ap.add_argument("--metrics-out", default="",
                    help="write one schema-versioned JSONL RoundRecord per "
                         "certified round (validate with "
                         "python -m repro.obs.validate)")
    ap.add_argument("--dashboard", action="store_true",
                    help="live terminal dashboard: gap trajectory, per-hop "
                         "wire rates, per-worker throughput (plain per-round "
                         "lines when stdout is not a tty)")
    ap.add_argument("--profile", default="",
                    help="jax.profiler trace directory; the trace carries "
                         "cocoa/local_solve, cocoa/exchange and "
                         "cocoa/certificate named-scope regions per round. "
                         "With --metrics-out also emits one KernelProfile "
                         "per certified round (<metrics-out>.prof.jsonl): "
                         "measured round wall vs the lowered round fn's "
                         "analytic HLO cost")
    args = ap.parse_args()

    # validate the comm flags before the (possibly minutes-long) dataset
    # load/partition: bad specs, gather without a sparsifier, and hier
    # groups that don't divide --workers all fail in milliseconds
    M = 1
    if args.mesh:
        try:
            K_mesh, M = (int(v) for v in args.mesh.lower().split("x"))
        except ValueError:
            raise SystemExit(f"--mesh wants 'KxM', got {args.mesh!r}")
        if K_mesh < 1 or M < 1:
            raise SystemExit(f"--mesh axes must be >= 1, got {args.mesh}")
        args.workers = K_mesh
        args.backend = "shard_map"
        if jax.device_count() < K_mesh * M:
            raise SystemExit(
                f"--mesh {args.mesh} needs {K_mesh * M} devices, have "
                f"{jax.device_count()} (CPU: set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={K_mesh * M})")
    if args.gather and args.compress not in ("topk", "randk"):
        raise SystemExit("--gather needs --compress topk or randk "
                         "(the sparse (idx, val) wire form)")
    try:
        get_regularizer(args.reg)
    except (KeyError, ValueError) as e:
        raise SystemExit(f"--reg: {e}")
    try:
        comm.Topology.simulated(args.workers, topology=args.topology)
        if args.elastic_to:
            # the re-partition target must fit the topology too, or the
            # crash just moves to round el_round
            comm.Topology.simulated(int(args.elastic_to.split("@")[0]),
                                    topology=args.topology)
    except ValueError as e:
        raise SystemExit(f"--topology: {e}")

    spec = DATASETS[args.dataset]
    fmt = spec.format if args.format == "auto" else args.format
    K = args.workers
    if fmt == "sparse":
        if spec.format != "sparse":
            raise SystemExit(f"--format sparse needs a sparse dataset spec; "
                             f"{args.dataset!r} is {spec.format}")
        csr, y = load(args.dataset)
        Xp, yp, mk = partition_sparse(csr, y, K, seed=0, M=M)
        if isinstance(Xp, FeatureShards):
            print(f"sparse feature shards: M={M} d_local={Xp.d_local} "
                  f"r_loc={Xp.r_loc} density={csr.density:.4g} d={Xp.d}")
        else:
            print(f"sparse shards: nnz/row r_max={Xp.r_max} "
                  f"density={csr.density:.4g} d={Xp.d}")
    else:
        X, y = load(args.dataset)
        if spec.format == "sparse":
            # --format dense on a sparse spec: densified baseline run
            X = X.toarray()
        Xp, yp, mk = partition(X, y, K, seed=0)

    mk_cfg = dict(loss=args.loss, lam=args.lam, H=args.H, solver=args.solver,
                  backend=args.backend, compress=args.compress,
                  compress_k=args.compress_k, topology=args.topology,
                  gather=args.gather, reg=args.reg, accel=args.accel,
                  model_axis="model" if M > 1 else None)

    def make_cfg(K):
        if args.aggregator:
            return CoCoAConfig(aggregator=args.aggregator, **mk_cfg)
        return (CoCoAConfig.adding(K, **mk_cfg) if args.gamma == "add"
                else CoCoAConfig.averaging(K, **mk_cfg))

    cfg = make_cfg(K)
    mesh = None
    if args.backend == "shard_map":
        mesh = (jax.make_mesh((K, M), ("data", "model")) if M > 1
                else jax.make_mesh((K,), ("data",)))

    def dims(Xp):
        if isinstance(Xp, FeatureShards):
            return Xp.d, Xp.cols.shape[2]
        if isinstance(Xp, SparseShards):
            return Xp.d, Xp.cols.shape[1]
        return Xp.shape[2], Xp.shape[1]

    mgr = CheckpointManager(pathlib.Path(args.ckpt), keep=2) if args.ckpt else None
    d_dim, nk_dim = dims(Xp)
    wspec = comm.WSpec(d=d_dim, M=M, model_axis="model" if M > 1 else None)
    state = init_state(wspec.d_padded, K, nk_dim)
    start = 0
    if mgr and mgr.latest_step():
        tmpl = state._asdict()
        try:
            loaded, man = mgr.restore(tmpl)
        except KeyError:
            # checkpoint predates the comm subsystem (no 'ef' leaf):
            # restore the old layout, start with zero EF residuals
            tmpl.pop("ef")
            loaded, man = mgr.restore(tmpl)
            loaded["ef"] = comm.init_residual(K, loaded["w"].shape[0])
        state = CoCoAState(**loaded)
        if state.w.shape[0] != wspec.d_padded:
            # legacy replicated-w checkpoint restored onto a 2-D mesh:
            # flush the old EF debt into w (nothing dropped), then re-pad
            # w and lay out fresh residuals for this run's placement
            if state.w.shape[0] != d_dim:
                raise SystemExit(
                    f"checkpoint w has {state.w.shape[0]} floats; this "
                    f"run places {wspec.d_padded} (d={d_dim}, M={M}) -- "
                    f"only replicated (M=1) checkpoints reshard "
                    f"automatically")
            state = reshard_w_state(state, comm.WSpec(d=d_dim),
                                    wspec, cfg.agg_params(K))
            print(f"resharded legacy checkpoint w: 1 -> {M} feature shards")
        start = man["step"]
        print(f"resumed from round {start}")

    # observability: one bus; solve emits a RoundRecord per certified
    # round and every sink below sees the same frozen record. The
    # profiler sink is built first so its trace brackets compile.
    bus = EventBus()
    if args.profile:
        bus.subscribe(ProfilerSink(args.profile))
    agg = bus.subscribe(Aggregator())
    if args.metrics_out:
        bus.subscribe(JsonlSink(args.metrics_out))
    prof_path, prof_sink = None, None
    if args.profile and args.metrics_out:
        # the compute-side twin of the RoundRecord stream: lower the same
        # round fn solve will run, extract its analytic HLO cost once, and
        # mirror every RoundRecord with a kind="round" KernelProfile that
        # shares its round_global (checked by repro.obs.validate --prof).
        # Never fails the run -- profiling is observability, not control.
        import time as _time

        from repro.core.cocoa import make_round_sharded, make_round_vmap
        from repro.launch.hlo_analysis import stats_of_compiled
        from repro.obs.prof import RoundProfileSink
        try:
            rf = jax.jit(make_round_sharded(cfg, mesh) if mesh is not None
                         else make_round_vmap(cfg, K))
            t0 = _time.perf_counter()
            stats = stats_of_compiled(rf.lower(state, Xp, yp, mk).compile())
            prof_path = pathlib.Path(args.metrics_out).with_suffix(
                ".prof.jsonl")
            prof_sink = bus.subscribe(RoundProfileSink(
                prof_path, stats, name="cocoa_round",
                shape=dict(K=K, d=int(d_dim), nk=int(nk_dim), H=args.H,
                           solver=args.solver),
                compile_s=_time.perf_counter() - t0))
        except Exception as e:                         # pragma: no cover
            prof_path = None
            print(f"[obs] per-round profiling disabled: {e}")
    if args.dashboard:
        # subscribed after the profile sink, so the compute/roofline row
        # can read the profile already emitted for the same record
        bus.subscribe(Dashboard(total_rounds=args.rounds,
                                prof_source=prof_sink))

    def make_tracker(K):
        # measured per-round wall-clock feeds the EMA; a simulated
        # straggler scales one worker's clock instead of inventing rates
        slow = np.ones(K)
        if 0 <= args.simulate_straggler < K:
            slow[args.simulate_straggler] = 10.0
        tr = straggler.ThroughputTracker(K, slowdown=slow)
        if 0 <= args.simulate_straggler < K:
            tr.rate[args.simulate_straggler] = 1e3   # pre-measurement seed
        return tr

    tracker = make_tracker(K)

    def make_budget_fn():
        if args.simulate_straggler < 0:
            return None
        return straggler.budget_fn_from_tracker(
            tracker, deadline_s=args.H / 1e4, H_max=args.H)

    budget_fn = make_budget_fn()
    if budget_fn is not None:
        print(f"straggler budgets: {np.asarray(budget_fn(0))} "
              f"(re-derived per round from measured throughput)")

    el_K, el_round = 0, -1
    if args.elastic_to:
        el_K, el_round = (int(v) for v in args.elastic_to.split("@"))

    reg = get_regularizer(args.reg)
    done = start
    while done < args.rounds:
        stop = min(r for r in
                   [args.rounds,
                    args.simulate_failure if args.simulate_failure > done else args.rounds,
                    el_round if el_round > done else args.rounds]
                   if r > done)
        rounds_before = int(state.rounds)
        r = solve(cfg, Xp, yp, mk, rounds=stop - done, eps_gap=args.eps,
                  gap_every=2, state=state, mesh=mesh, budget_fn=budget_fn,
                  obs=bus, throughput=tracker,
                  on_round=(lambda t, st, gap:
                            mgr.save(done + t, st._asdict(),
                                     {"gap": gap})
                            if mgr and (done + t) % args.ckpt_every == 0
                            else None))
        state = r.state
        # advance by the rounds the solver actually ran (its round counter
        # delta) -- robust to eps-early exit at any gap_every phase, with
        # no history fallback to go stale
        done += int(state.rounds) - rounds_before
        gap = agg.final_gap
        last = agg.last
        fl = last.wire_floats // last.rounds_in_record if last else 0
        print(f"round {done}: gap={gap:.3e} comm={fl} floats/round")
        if gap <= args.eps:
            break
        if done == args.simulate_failure and args.simulate_failure:
            print("simulating loss of worker 0 (dual-safe drop + recovery)")
            state = failures.fail_and_recover(state, Xp, mk, args.lam, k=0,
                                              reg=reg)
            # v_of_alpha on dense (unpadded) data returns a (d,) vector;
            # re-place it for the mesh (identity when already padded --
            # FeatureShards rmatvec emits d_padded directly)
            state = state._replace(w=wspec.pad_w(state.w))
            args.simulate_failure = 0
        if done == el_round and el_K:
            print(f"elastic re-partition {K} -> {el_K} workers")
            if args.compress != "none":
                # every worker is alive here (unlike drop_worker): flush the
                # outstanding EF debt into w before the per-worker residual
                # state is rebuilt at the new K, so no update mass is lost
                state = state._replace(w=comm.flush_ef(
                    state.w, state.ef, cfg.agg_params(K)))
            if isinstance(Xp, FeatureShards):
                # rows re-split across workers with their M feature slices
                # attached; the w placement (M, d_local) is untouched
                Xp, yp, new_alpha, mk = elastic.repartition_features(
                    Xp, yp, state.alpha, mk, el_K)
                new = {"alpha": new_alpha}
            elif isinstance(Xp, SparseShards):
                # every leaf shares the (K, nk) leading layout, so the ELL
                # shards re-split exactly like dense rows (alpha travels too)
                arrs = {"cols": Xp.cols, "vals": Xp.vals, "nnz": Xp.nnz,
                        "y": yp, "alpha": state.alpha}
                new, mk = elastic.repartition(arrs, mk, el_K)
                Xp = SparseShards(new["cols"], new["vals"], new["nnz"], d=Xp.d)
                yp = new["y"]
            else:
                arrs = {"X": Xp, "y": yp, "alpha": state.alpha}
                new, mk = elastic.repartition(arrs, mk, el_K)
                Xp, yp = new["X"], new["y"]
            K = el_K
            cfg = make_cfg(K)
            tracker = make_tracker(K)          # per-worker EMA is K-shaped
            budget_fn = make_budget_fn()
            d_dim, nk_dim = dims(Xp)
            if mesh is not None:
                mesh = (jax.make_mesh((K, M), ("data", "model")) if M > 1
                        else jax.make_mesh((K,), ("data",)))
            st = init_state(wspec.d_padded, K, nk_dim)
            # solve re-places the carried leaves for the new mesh
            state = st._replace(alpha=new["alpha"], w=state.w,
                                rounds=state.rounds)
            el_round = -1

    if mgr:
        mgr.wait()
    if args.reg != "l2":
        from repro.core import primal_w
        w_fin = primal_w(state, cfg)
        nz = int(jnp.sum(jnp.abs(w_fin) > 0))
        print(f"reg[{reg.name}]: tau={reg.tau(args.lam):.3g} "
              f"primal w nonzeros: {nz}/{w_fin.shape[0]}")
    # one source of truth for the certificate: the last RoundRecord solve
    # emitted (the solver certifies its final round unconditionally, on
    # exactly the primal point the run carries -- no recomputation here
    # that could drift from what the records/JSONL say)
    print(agg.format_summary())
    topo = comm.Topology.simulated(K, topology=args.topology)
    # price the model hop the way the run actually paid it: the kernel
    # path exchanges block-batched partial dots (zx plan), the jnp path
    # one scalar psum per coordinate step
    zx_plan = None
    if wspec.sharded and isinstance(Xp, FeatureShards) and \
            sparse_counterpart(args.solver) == "sdca_sparse_kernel":
        from repro.kernels.ops import sparse_zx_plan
        zx_plan = sparse_zx_plan(Xp.cols.shape[2], wspec.d_local, args.H,
                                 r_max=int(Xp.cols.shape[-1]),
                                 reg_family=getattr(reg, "family", "other"),
                                 model_shards=M)
    tr = comm.CommTracer.for_run(K=K, d_local=wspec.d_local,
                                 compressor=cfg.compressor(M=M),
                                 topo=topo, gather=args.gather,
                                 extra_hops=comm.model_hops(wspec, K, args.H,
                                                            zx_plan=zx_plan)
                                 + comm.accel_hops(args.accel))
    pr = tr.per_round()
    dense_floats = K * d_dim
    print(f"comm[{args.topology}{'+gather' if args.gather else ''}"
          f"{f' mesh={K}x{M}' if M > 1 else ''}]: "
          f"{pr['floats']} floats/round "
          f"({pr['bytes']} bytes, {pr['psums']} hop) -- "
          f"{dense_floats / max(pr['floats'], 1):.1f}x cut vs flat "
          f"uncompressed {dense_floats}")
    for h in tr.per_hop():
        print(f"  hop {h['hop']}[{h['axis']}]: {h['messages']} msgs x "
              f"{h['floats_per_message']} floats = {h['floats']}/round")
    if M > 1:
        ax = tr.per_axis()
        print(f"  per-axis floats/round: data={ax.get('data', 0)} "
              f"model={ax.get('model', 0)}; w memory/device: "
              f"{wspec.d_local} floats (replicated would be {d_dim})")
    bus.close()                  # flush JSONL, stop the profiler trace
    if args.metrics_out:
        print(f"metrics: {agg.rounds} rounds -> {args.metrics_out} "
              f"(validate: python -m repro.obs.validate {args.metrics_out})")
    if args.profile:
        print(f"profile: trace written to {args.profile}")
    if prof_path is not None:
        print(f"profile: per-round KernelProfiles -> {prof_path} "
              f"(validate both streams: python -m repro.obs.validate "
              f"{args.metrics_out} --prof {prof_path})")


if __name__ == "__main__":
    main()
