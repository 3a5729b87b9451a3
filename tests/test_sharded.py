"""Multi-device integration tests (subprocess with forced host devices)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# XLA:CPU runs each forced host device's part of a collective on its own
# thread and aborts the process when one has not arrived after 40 s; on a
# loaded machine a starved thread is late, not stuck, so the abort comes
# only well past that, still inside `_run`'s own timeout
COLLECTIVE_TIMEOUTS = ("--xla_cpu_collective_call_warn_stuck_timeout_seconds"
                       "=120 --xla_cpu_collective_call_terminate_timeout_"
                       "seconds=600")


def _run(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        f"{COLLECTIVE_TIMEOUTS}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout


def test_cocoa_shard_map_matches_vmap():
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import CoCoAConfig, solve
        from repro.data import make_classification, partition
        X, y = make_classification(1024, 32, seed=0)
        Xp, yp, mk = partition(X, y, 8, seed=1)
        mesh = jax.make_mesh((8,), ("data",))
        rv = solve(CoCoAConfig.adding(8, loss="hinge", lam=1e-3, H=128),
                   Xp, yp, mk, rounds=8, gap_every=8)
        rs = solve(CoCoAConfig.adding(8, loss="hinge", lam=1e-3, H=128,
                                      backend="shard_map"),
                   Xp, yp, mk, rounds=8, gap_every=8, mesh=mesh)
        err = float(jnp.max(jnp.abs(rv.state.w - rs.state.w)))
        assert err < 1e-4, err
        assert abs(rv.history["gap"][-1] - rs.history["gap"][-1]) < 1e-4
        print("PARITY OK", err)
    """)
    assert "PARITY OK" in out


def test_cocoa_shard_map_sparse_matches_vmap():
    """The shard_map sparse backend (per-device padded-ELL shards + one psum
    of w-sized shards per round) must reproduce the vmap backend's (alpha,
    w, gap) histories on tiny_sparse under a 1xK CPU mesh -- same fold_in
    rng contract, same solver, same comm layer."""
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import CoCoAConfig, solve
        from repro.data import load
        from repro.data.sparse import partition_sparse
        csr, y = load("tiny_sparse")
        sh, yp, mk = partition_sparse(csr, y, 4, seed=0)
        mesh = jax.make_mesh((4,), ("data",))
        kw = dict(loss="hinge", lam=1e-3, H=128)
        rv = solve(CoCoAConfig.adding(4, **kw), sh, yp, mk,
                   rounds=5, gap_every=1)
        rs = solve(CoCoAConfig.adding(4, backend="shard_map", **kw),
                   sh, yp, mk, rounds=5, gap_every=1, mesh=mesh)
        w_err = float(jnp.max(jnp.abs(rv.state.w - rs.state.w)))
        a_err = float(jnp.max(jnp.abs(rv.state.alpha - rs.state.alpha)))
        assert w_err < 1e-5, w_err
        assert a_err < 1e-5, a_err
        assert rv.history["round"] == rs.history["round"]
        np.testing.assert_allclose(rv.history["gap"], rs.history["gap"],
                                   rtol=1e-4, atol=1e-6)
        assert rv.history["gap"][-1] < rv.history["gap"][0]
        print("SPARSE PARITY OK", w_err, a_err)
    """, devices=4)
    assert "SPARSE PARITY OK" in out


def test_shard_map_certificate_layout_matches_raw_shards():
    """On a 4-device mesh each device sorts its own rows for the
    certificate: every record's (P, D, gap) equals the certificate of the
    round's state on the raw ELL shards."""
    out = _run("""
        import jax, numpy as np
        from repro.core import CoCoAConfig, duality, solve
        from repro.core.losses import get_loss
        from repro.data import load
        from repro.data.sparse import partition_sparse
        csr, y = load("tiny_sparse")
        sh, yp, mk = partition_sparse(csr, y, 4, seed=0)
        mesh = jax.make_mesh((4,), ("data",))
        loss = get_loss("smooth_hinge")
        want = []
        def hook(t, state, gap):
            want.append(duality.gap_decomposed(state.alpha, sh, yp, mk,
                                               loss, 1e-3))
        r = solve(CoCoAConfig.adding(4, loss="smooth_hinge", lam=1e-3,
                                     H=128, backend="shard_map"),
                  sh, yp, mk, rounds=4, gap_every=1, mesh=mesh,
                  on_round=hook)
        got = np.stack([r.history["primal"], r.history["dual"],
                        r.history["gap"]], axis=1)
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5)
        print("MESH CERTIFICATE OK")
    """, devices=4)
    assert "MESH CERTIFICATE OK" in out


def test_cocoa_shard_map_compressed_matches_vmap():
    """Compressed exchange (top-k + error feedback) keeps backend parity:
    the per-worker compression rng and EF residuals are derived identically
    under vmap and shard_map."""
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import CoCoAConfig, solve
        from repro.data import make_classification, partition
        X, y = make_classification(512, 64, seed=0)
        Xp, yp, mk = partition(X, y, 4, seed=1)
        mesh = jax.make_mesh((4,), ("data",))
        kw = dict(loss="hinge", lam=1e-3, H=64, compress="topk",
                  compress_k=8)
        rv = solve(CoCoAConfig.adding(4, **kw), Xp, yp, mk,
                   rounds=4, gap_every=4)
        rs = solve(CoCoAConfig.adding(4, backend="shard_map", **kw),
                   Xp, yp, mk, rounds=4, gap_every=4, mesh=mesh)
        w_err = float(jnp.max(jnp.abs(rv.state.w - rs.state.w)))
        e_err = float(jnp.max(jnp.abs(rv.state.ef - rs.state.ef)))
        assert w_err < 1e-5, w_err
        assert e_err < 1e-5, e_err
        assert rv.history["comm_floats"] == rs.history["comm_floats"]
        print("COMPRESSED PARITY OK", w_err, e_err)
    """, devices=4)
    assert "COMPRESSED PARITY OK" in out


def test_cocoa_shard_map_topologies_match_flat():
    """Reduce-topology parity on a real CPU mesh: hier:<g> (grouped
    all_gather association on a single named axis) and a2a (psum_scatter +
    all_gather) reproduce the flat psum's (w, alpha) within 1e-6, dense
    wire, with the vmap backend as the cross-backend anchor."""
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import CoCoAConfig, solve
        from repro.data import load
        from repro.data.sparse import partition_sparse
        csr, y = load("tiny_sparse")
        sh, yp, mk = partition_sparse(csr, y, 4, seed=0)
        mesh = jax.make_mesh((4,), ("data",))
        kw = dict(loss="hinge", lam=1e-3, H=128)
        rv = solve(CoCoAConfig.adding(4, **kw), sh, yp, mk,
                   rounds=4, gap_every=4)
        rf = solve(CoCoAConfig.adding(4, backend="shard_map", **kw),
                   sh, yp, mk, rounds=4, gap_every=4, mesh=mesh)
        for topo in ("hier:2", "a2a"):
            rt = solve(CoCoAConfig.adding(4, backend="shard_map",
                                          topology=topo, **kw),
                       sh, yp, mk, rounds=4, gap_every=4, mesh=mesh)
            w_err = float(jnp.max(jnp.abs(rt.state.w - rf.state.w)))
            a_err = float(jnp.max(jnp.abs(rt.state.alpha - rf.state.alpha)))
            v_err = float(jnp.max(jnp.abs(rt.state.w - rv.state.w)))
            assert w_err < 1e-6, (topo, w_err)
            assert a_err < 1e-6, (topo, a_err)
            assert v_err < 1e-5, (topo, v_err)
        print("TOPOLOGY PARITY OK")
    """, devices=4)
    assert "TOPOLOGY PARITY OK" in out


def test_cocoa_shard_map_compressed_gather_topologies():
    """Compressed gather on the mesh: every topology's gathered-and-
    decompressed reduce matches the flat gather within 1e-6 (same EF
    residuals, same fold_in rng streams), the vmap gather run matches
    across backends, and the tracer's reduce volume is the analytic 2kK
    floats per round -- not dK."""
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import CoCoAConfig, solve
        from repro.data import make_classification, partition
        X, y = make_classification(512, 64, seed=0)
        Xp, yp, mk = partition(X, y, 4, seed=1)
        mesh = jax.make_mesh((4,), ("data",))
        K, k = 4, 8
        kw = dict(loss="hinge", lam=1e-3, H=64, compress="topk",
                  compress_k=k, gather=True)
        rv = solve(CoCoAConfig.adding(K, **kw), Xp, yp, mk,
                   rounds=3, gap_every=1)
        assert rv.history["comm_floats"] == [2*k*K, 4*k*K, 6*k*K], \\
            rv.history["comm_floats"]
        ref = None
        for topo in ("flat", "hier:2", "a2a"):
            rs = solve(CoCoAConfig.adding(K, backend="shard_map",
                                          topology=topo, **kw),
                       Xp, yp, mk, rounds=3, gap_every=1, mesh=mesh)
            if ref is None:
                ref = rs
                v_err = float(jnp.max(jnp.abs(rs.state.w - rv.state.w)))
                e_err = float(jnp.max(jnp.abs(rs.state.ef - rv.state.ef)))
                assert v_err < 1e-5, v_err
                assert e_err < 1e-5, e_err
                assert rs.history["comm_floats"] == rv.history["comm_floats"]
            else:
                w_err = float(jnp.max(jnp.abs(rs.state.w - ref.state.w)))
                e_err = float(jnp.max(jnp.abs(rs.state.ef - ref.state.ef)))
                assert w_err < 1e-6, (topo, w_err)
                assert e_err < 1e-6, (topo, e_err)
        print("GATHER TOPOLOGY PARITY OK")
    """, devices=4)
    assert "GATHER TOPOLOGY PARITY OK" in out


def test_cocoa_mixed_radix_hier_reduce():
    """Multi-pod descriptor: on a (2, 2) mesh with both axes as data axes,
    hier:2 runs real sequential psums (intra = trailing axis, inter =
    leading) and matches the flat joint psum."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.core import CoCoAConfig, solve
        from repro.data import make_classification, partition
        X, y = make_classification(512, 48, seed=0)
        Xp, yp, mk = partition(X, y, 4, seed=1)
        mesh = jax.make_mesh((2, 2), ("pod", "core"))
        kw = dict(loss="hinge", lam=1e-3, H=64, backend="shard_map",
                  data_axis=("pod", "core"))
        rf = solve(CoCoAConfig.adding(4, **kw), Xp, yp, mk,
                   rounds=3, gap_every=3, mesh=mesh)
        rh = solve(CoCoAConfig.adding(4, topology="hier:2", **kw),
                   Xp, yp, mk, rounds=3, gap_every=3, mesh=mesh)
        w_err = float(jnp.max(jnp.abs(rh.state.w - rf.state.w)))
        assert w_err < 1e-6, w_err
        print("MIXED RADIX OK", w_err)
    """, devices=4)
    assert "MIXED RADIX OK" in out


def test_cocoa_2d_mesh_all_axes_as_workers():
    """2-D mesh: K workers spread over BOTH axes -- the production paper-cell
    mapping (CoCoA+ scales in K; the model axis hosts more workers)."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.core import CoCoAConfig, solve
        from repro.data import make_classification, partition
        X, y = make_classification(512, 64, seed=0)
        Xp, yp, mk = partition(X, y, 8, seed=1)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        cfg = CoCoAConfig.adding(8, loss="hinge", lam=1e-3, H=128,
                                 backend="shard_map",
                                 data_axis=("data", "model"))
        r = solve(cfg, Xp, yp, mk, rounds=6, gap_every=6, mesh=mesh)
        assert r.history["gap"][-1] < 0.6
        print("2D OK", r.history["gap"][-1])
    """)
    assert "2D OK" in out


def test_localdp_shard_map_parity():
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.optim.localdp import (LocalDPConfig, init_state,
                                         make_round_fn, make_round_sharded)
        rng = np.random.default_rng(0)
        K, n, d = 4, 32, 8
        Xs = jnp.asarray(rng.standard_normal((K, n, d)).astype(np.float32))
        ys = jnp.asarray(rng.standard_normal((K, n, 1)).astype(np.float32))
        params = {"w": jnp.asarray(rng.standard_normal((d, 1)).astype(np.float32))}
        loss_fn = lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2)
        cfg = LocalDPConfig.adding(K=K, H=4, inner_lr=1e-2)
        rf = make_round_fn(loss_fn, cfg)
        st = init_state(params, cfg)
        st = rf(st, (Xs, ys))
        mesh = jax.make_mesh((4,), ("data",))
        rs = make_round_sharded(loss_fn, cfg, mesh)
        p2 = rs(params, (Xs, ys))
        err = float(jnp.max(jnp.abs(st.params["w"] - p2["w"])))
        assert err < 1e-5, err
        print("LOCALDP OK", err)
    """)
    assert "LOCALDP OK" in out


@pytest.mark.slow
def test_dryrun_cell_small_mesh():
    """The dry-run driver end-to-end on a shrunken mesh (2x2 / 2x2x2)."""
    env = dict(os.environ)
    env["REPRO_DRYRUN_DEVICES"] = "8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "stablelm-1.6b", "--shape", "train_4k", "--mesh", "both",
         "--out", "/tmp/dryrun_test"],
        capture_output=True, text=True, timeout=1200, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.count("[ok]") == 2


@pytest.mark.slow
def test_dryrun_paper_cell_small_mesh():
    env = dict(os.environ)
    env["REPRO_DRYRUN_DEVICES"] = "8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--paper", "--mesh",
         "single", "--out", "/tmp/dryrun_test"],
        capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "paper-svm" in p.stdout


def test_moe_shardmap_matches_portable():
    """Explicit-EP MoE (shard_map) == portable grouped dispatch, both modes."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.configs import smoke_config
        from repro.models import layers as L

        cfg = dataclasses.replace(smoke_config("llama4-scout-17b-a16e"),
                                  capacity_factor=64.0)  # dropless -> exact
        rng = np.random.default_rng(0)
        B, S, d = 4, 16, cfg.d_model
        x = jnp.asarray(rng.standard_normal((B, S, d)).astype(np.float32))
        p = L.init_moe(jax.random.PRNGKey(1), cfg, cfg.d_ff, jnp.float32)
        mesh = jax.make_mesh((4, 2), ("data", "model"))

        L.set_moe_ctx(groups=4)            # portable grouped path
        ref, aux_ref = L.moe_forward(p, x, cfg, cfg.d_ff)

        for gather in (True, False):
            L.set_moe_ctx(mesh=mesh, dp="data", tp="model", fsdp="data",
                          gather_weights=gather)
            got, aux = jax.jit(lambda p, x: L.moe_forward(p, x, cfg, cfg.d_ff)
                               )(p, x)
            err = float(jnp.max(jnp.abs(got - ref)))
            assert err < 2e-4, (gather, err)
            # aux is E*sum(mean_e * count_e): the sharded path averages the
            # per-shard statistic (GShard-style per-group balance), the
            # portable path uses global means -- close but not identical
            assert abs(float(aux) - float(aux_ref)) < 0.05
        L.set_moe_ctx()                     # reset
        print("MOE PARITY OK")
    """)
    assert "MOE PARITY OK" in out
