"""Feature-sharded model axis: WSpec placement, FeatureShards slicing,
dedup gather decompression, placement migration, and the 2-D
(data x model) mesh end-to-end parity (subprocess with forced host
devices)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import comm
from repro.comm import aggregate, compress, topology, tracer
from repro.core import cocoa
from repro.data import sparse as sp
from repro.runtime import elastic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------------
# WSpec: the placement abstraction
# ----------------------------------------------------------------------------

def test_wspec_geometry():
    ws = comm.WSpec(d=10, M=4, model_axis="model")
    assert ws.sharded and ws.d_local == 3 and ws.d_padded == 12
    assert ws.shard_offset(2) == 6
    assert ws.shard_bounds(3) == (9, 10)          # last shard is ragged
    # replicated spec: everything degenerates to the 1-D layout
    r = comm.WSpec(d=10)
    assert not r.sharded and r.d_local == 10 and r.d_padded == 10
    assert r.spec() == jax.sharding.PartitionSpec()
    assert ws.spec() == jax.sharding.PartitionSpec("model")


def test_wspec_column_map_roundtrip():
    ws = comm.WSpec(d=100, M=3, model_axis="m")
    cols = jnp.asarray([0, 33, 34, 67, 99])
    owners = ws.owner_of(cols)
    np.testing.assert_array_equal(np.asarray(owners), [0, 0, 1, 1, 2])
    for m in range(3):
        local = ws.to_local(cols, m)
        np.testing.assert_array_equal(np.asarray(ws.to_global(local, m)),
                                      np.asarray(cols))


def test_model_hops_zx_plan_prices_block_exchange():
    """The kernel path's model-axis hop: `exchanges * block_rows` floats
    per device per round -- block-granular psums, NOT the jnp path's H
    scalar psums and NOT d/M-sized messages."""
    ws = comm.WSpec(d=100, M=2, model_axis="model")
    (h,) = comm.model_hops(ws, 3, 256)
    assert (h.name, h.axis) == ("model_z", "model")
    assert h.messages == 6 and h.floats_per_message == 256
    plan = dict(block_rows=16, exchanges=9)       # 8 blocks + prologue
    (hz,) = comm.model_hops(ws, 3, 256, zx_plan=plan)
    assert (hz.name, hz.axis) == ("model_zx", "model")
    assert hz.messages == 6 and hz.floats_per_message == 9 * 16
    assert hz.floats == 6 * 9 * 16
    # replicated w: no model hop, zx or not
    assert comm.model_hops(comm.WSpec(d=100), 3, 256, zx_plan=plan) == ()


def test_wspec_pad_unpad():
    ws = comm.WSpec(d=10, M=4, model_axis="model")
    w = jnp.arange(10, dtype=jnp.float32)
    wp = ws.pad_w(w)
    assert wp.shape == (12,) and float(jnp.sum(wp[10:])) == 0.0
    np.testing.assert_array_equal(np.asarray(ws.unpad_w(wp)), np.asarray(w))
    assert ws.pad_w(wp) is wp                     # already placed
    with pytest.raises(ValueError):
        ws.pad_w(jnp.zeros(11))
    with pytest.raises(ValueError):
        comm.WSpec(d=8, M=2)                      # sharded needs an axis
    with pytest.raises(ValueError):
        comm.WSpec(d=0)


def test_sparse_message_rebase():
    msg = compress.SparseMessage(jnp.asarray([0, 2, 5]),
                                 jnp.asarray([1.0, 2.0, 3.0]))
    ws = comm.WSpec(d=30, M=3, model_axis="m")
    up = msg.rebase(ws.shard_offset(2))
    np.testing.assert_array_equal(np.asarray(up.idx), [20, 22, 25])
    np.testing.assert_array_equal(np.asarray(up.val), np.asarray(msg.val))
    back = up.rebase(-ws.shard_offset(2))
    np.testing.assert_array_equal(np.asarray(back.idx), np.asarray(msg.idx))
    # local sets from every shard, rebased, reproduce the global decode
    d_loc = ws.d_local
    local = [compress.SparseMessage(jnp.asarray([1, 3]),
                                    jnp.asarray([float(m), 1.0]))
             for m in range(3)]
    glob_idx = jnp.stack([l.rebase(ws.shard_offset(m)).idx
                          for m, l in enumerate(local)])
    glob_val = jnp.stack([l.val for l in local])
    dense = compress.decode_sum(glob_idx, glob_val, ws.d_padded)
    for m, l in enumerate(local):
        seg = dense[m * d_loc:(m + 1) * d_loc]
        np.testing.assert_array_equal(
            np.asarray(seg), np.asarray(compress.decode_sum(l.idx, l.val,
                                                            d_loc)))


# ----------------------------------------------------------------------------
# merge_sets: deduplicated gather decompression
# ----------------------------------------------------------------------------

def test_merge_sets_dedup_and_decode():
    idx = jnp.asarray([[1, 3, 5], [3, 5, 7], [9, 3, 1]])
    val = jnp.asarray([[1., 2., 3.], [4., 5., 6.], [7., 8., 9.]])
    mi, mv, uniq = compress.merge_sets(idx, val, 16)
    assert int(uniq) == 5                          # {1, 3, 5, 7, 9}
    # duplicates parked at the sentinel d with value 0
    assert int(jnp.sum(mi == 16)) == 9 - 5
    ref = compress.decode_sum(idx, val, 16)
    got = compress.decode_sum(mi, mv, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)
    # merged values: coordinate 3 summed across all three workers
    assert float(mv[np.asarray(mi).tolist().index(3)]) == 2. + 4. + 8.


def test_merge_sets_no_overlap_is_identity_sum():
    idx = jnp.asarray([[0, 1], [2, 3]])
    val = jnp.asarray([[1., 2.], [3., 4.]])
    mi, mv, uniq = compress.merge_sets(idx, val, 8)
    assert int(uniq) == 4
    np.testing.assert_allclose(
        np.asarray(compress.decode_sum(mi, mv, 8)),
        np.asarray(compress.decode_sum(idx, val, 8)))


def test_exchange_hier_gather_dedup_measures_volume():
    """Overlapping top-k sets: the hier gather's measured post-dedup inter
    volume comes in strictly below the analytic g*2k-per-pod bound, while
    the decoded sum still matches the flat gather."""
    K, d, k = 8, 64, 8
    rng = np.random.default_rng(0)
    base = np.zeros(d, np.float32)
    base[:k] = 10.0 + rng.standard_normal(k)       # shared heavy coords
    du = jnp.asarray(np.stack([base + 0.01 * rng.standard_normal(d)
                               for _ in range(K)]).astype(np.float32))
    ef = comm.init_residual(K, d)
    rngs = jax.random.split(jax.random.PRNGKey(0), K)
    p = aggregate.AggParams(1.0, float(K))
    c = compress.TopK(k)
    flat, _ = aggregate.exchange(topology.Topology.simulated(K),
                                 du, ef, rngs, p, c, gather=True)
    stats = {}
    hier, _ = aggregate.exchange(
        topology.Topology.simulated(K, topology="hier:4"),
        du, ef, rngs, p, c, gather=True, stats=stats)
    np.testing.assert_allclose(np.asarray(hier), np.asarray(flat),
                               rtol=1e-5, atol=1e-6)
    measured = int(stats["inter_gather"])
    pods = K // 4
    analytic = pods * 4 * 2 * k                    # g sets of 2k per pod
    # all workers share the same top-k support -> ~k unique per pod
    assert measured < analytic, (measured, analytic)
    assert measured <= pods * 2 * 2 * k            # well below, in fact
    assert measured >= pods * 2 * k                # at least k live pairs


def test_solve_history_reflects_measured_dedup_volume():
    """End-to-end: a hier compressed-gather run's comm_floats history uses
    the measured post-dedup inter volume, i.e. it lands strictly below the
    analytic hop plan whenever worker top-k sets overlap."""
    from repro.core import CoCoAConfig, solve

    csr, y = sp.make_sparse_classification(128, 64, density=0.3, seed=0)
    sh, yp, mk = sp.partition_sparse(csr, y, 4, seed=0)
    cfg = CoCoAConfig.adding(4, loss="hinge", lam=1e-3, H=64,
                             compress="topk", compress_k=8,
                             topology="hier:2", gather=True)
    r = solve(cfg, sh, yp, mk, rounds=3, gap_every=1)
    topo = comm.Topology.simulated(4, topology="hier:2")
    analytic = sum(h.floats for h in topo.hops(
        cfg.compressor().floats_per_message(64), 64,
        cfg.compressor().gather_floats(64)))
    floats = r.history["comm_floats"]
    assert floats[-1] < 3 * analytic, (floats, analytic)
    assert floats[0] >= 4 * 2 * 8                  # intra hop is still full
    # history deltas are the per-round measured volumes (monotone sums)
    assert all(b > a for a, b in zip(floats, floats[1:]))


# ----------------------------------------------------------------------------
# FeatureShards: global -> local ELL slicing
# ----------------------------------------------------------------------------

def _toy_shards(n=96, d=37, K=3, density=0.2, seed=0):
    csr, y = sp.make_sparse_classification(n, d, density=density, seed=seed)
    return sp.partition_sparse(csr, y, K, seed=seed)


@pytest.mark.parametrize("M", [1, 2, 3, 4])
def test_shard_features_densify_parity(M):
    sh, yp, mk = _toy_shards()
    fs = sp.shard_features(sh, M)
    assert fs.M == M and fs.d == sh.d
    assert fs.d_local == -(-sh.d // M)
    D = np.asarray(sp.densify(sh))
    Dfs = np.asarray(sp.densify(fs))
    np.testing.assert_allclose(Dfs[:, :, :sh.d], D, atol=1e-7)
    assert np.all(Dfs[:, :, sh.d:] == 0)          # padding never populated
    # local ids stay inside the local slice
    assert int(jnp.max(fs.cols)) < fs.d_local


def test_shard_features_matvec_rmatvec_sqnorms():
    sh, yp, mk = _toy_shards()
    fs = sp.shard_features(sh, 3)
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal(fs.d_padded).astype(np.float32))
    np.testing.assert_allclose(np.asarray(sp.matvec(fs, w)),
                               np.asarray(sp.matvec(sh, w[:sh.d])),
                               rtol=1e-4, atol=1e-5)
    coef = jnp.asarray(rng.standard_normal(yp.shape).astype(np.float32))
    out = np.asarray(sp.rmatvec(fs, coef))
    np.testing.assert_allclose(out[:sh.d], np.asarray(sp.rmatvec(sh, coef)),
                               rtol=1e-4, atol=1e-5)
    assert np.all(out[sh.d:] == 0)
    np.testing.assert_allclose(np.asarray(sp.row_sqnorms(fs)),
                               np.asarray(sp.row_sqnorms(sh)), rtol=1e-5)


def test_shard_features_m1_is_identity_layout():
    sh, _, _ = _toy_shards()
    fs = sp.shard_features(sh, 1)
    assert fs.M == 1 and fs.d_local == sh.d and fs.d_padded == sh.d
    np.testing.assert_array_equal(np.asarray(fs.nnz[:, 0]),
                                  np.asarray(sh.nnz))
    # same entries in the same order (possibly narrower padding)
    r = fs.r_loc
    np.testing.assert_array_equal(np.asarray(fs.cols[:, 0]),
                                  np.asarray(sh.cols[:, :, :r]))
    np.testing.assert_array_equal(np.asarray(fs.vals[:, 0]),
                                  np.asarray(sh.vals[:, :, :r]))


def test_partition_sparse_model_axis():
    csr, y = sp.make_sparse_classification(64, 40, density=0.2, seed=3)
    sh, yp1, mk1 = sp.partition_sparse(csr, y, 4, seed=0)
    fs, yp2, mk2 = sp.partition_sparse(csr, y, 4, seed=0, M=2)
    assert isinstance(fs, sp.FeatureShards)
    # the row partition is M-invariant: y/mask identical
    np.testing.assert_array_equal(np.asarray(yp1), np.asarray(yp2))
    np.testing.assert_array_equal(np.asarray(mk1), np.asarray(mk2))
    np.testing.assert_allclose(np.asarray(sp.densify(fs))[:, :, :40],
                               np.asarray(sp.densify(sh)), atol=1e-7)


def test_duality_gap_from_feature_shards():
    from repro.core import duality
    from repro.core.losses import get_loss

    sh, yp, mk = _toy_shards()
    fs = sp.shard_features(sh, 3)
    loss = get_loss("hinge")
    rng = np.random.default_rng(2)
    # dual-feasible hinge duals: alpha_i * y_i in [0, 1]
    alpha = jnp.asarray((np.asarray(yp) * rng.random(yp.shape)
                         * np.asarray(mk)).astype(np.float32))
    p1, d1, g1 = duality.gap_decomposed(alpha, sh, yp, mk, loss, 1e-3)
    p2, d2, g2 = duality.gap_decomposed(alpha, fs, yp, mk, loss, 1e-3)
    assert abs(float(p1) - float(p2)) < 1e-5
    assert abs(float(d1) - float(d2)) < 1e-5
    assert abs(float(g1) - float(g2)) < 1e-5
    # certified gap at a padded sharded w (one model-axis reduction)
    w = comm.WSpec(d=sh.d, M=3, model_axis="m").pad_w(
        jnp.asarray(rng.standard_normal(sh.d).astype(np.float32)))
    pa, da, ga = duality.gap_at_w(w, alpha, fs, yp, mk, loss, 1e-3)
    pb, db, gb = duality.gap_at_w(w[:sh.d], alpha, sh, yp, mk, loss, 1e-3)
    assert abs(float(ga) - float(gb)) < 1e-5


# ----------------------------------------------------------------------------
# budget-splitting sparsifier: compressed-gather wire volume is M-invariant
# ----------------------------------------------------------------------------

def test_sparsifier_budget_split_math():
    """with_shards deals the total budget k across the M model shards:
    ceil(k/M) static slots each, live counts k//M + (m < k%M) -- remainder
    to low shards, summing exactly to k."""
    c = compress.TopK(10)
    s = c.with_shards(4, "model")
    assert s.k == 10 and s.shards == 4 and s.slots == 3
    assert [int(s.live_budget(m)) for m in range(4)] == [3, 3, 2, 2]
    assert sum(int(s.live_budget(m)) for m in range(4)) == 10
    assert s.floats_per_message(1000) == 2 * 3
    assert s.gather_floats(1000) == 2 * 3
    # M=1 split is the identity object; randk splits the same way
    assert c.with_shards(1, None) is c
    r = compress.RandK(7).with_shards(2, "model")
    assert r.slots == 4
    assert [int(r.live_budget(m)) for m in range(2)] == [4, 3]
    with pytest.raises(ValueError):
        compress.TopK(8, shards=2)        # a split needs its mesh axis
    with pytest.raises(ValueError):
        compress.TopK(8, shards=0)


@pytest.mark.parametrize("M", [1, 2, 4])
def test_gather_wire_volume_m_invariant(M):
    """The satellite's accounting claim: under a k-budget split the
    compressed-gather reduce moves ~2*(k/M)*K*M floats per round across
    the whole mesh -- 2kK up to ceil rounding, M-invariant -- instead of
    the 2kKM a naive per-shard budget of k would cost."""
    K, d, k = 4, 1000, 32
    ws = comm.WSpec(d=d, M=M, model_axis="model" if M > 1 else None)
    cfg = cocoa.CoCoAConfig.adding(
        K, compress="topk", compress_k=k, gather=True,
        model_axis="model" if M > 1 else None)
    comp = cfg.compressor(M=M)
    tr = tracer.CommTracer.for_run(
        K=K, d_local=ws.d_local, compressor=comp,
        topo=topology.Topology.simulated(K), gather=True)
    per_shard = tr.per_hop()[0]
    k_shard = -(-k // M)
    assert per_shard["hop"] == "gather"
    assert per_shard["floats_per_message"] == 2 * k_shard
    assert per_shard["floats"] == K * 2 * k_shard    # per model shard
    total = M * per_shard["floats"]                  # across the mesh
    assert 2 * k * K <= total <= 2 * k * K + 2 * K * M
    # the naive (unsplit) budget would cost 2kK *per shard*: M x more
    naive = M * K * 2 * k
    assert total <= naive / max(M, 1) + 2 * K * M


def test_budget_split_encode_masks_dead_slots():
    """Inside shard_map each model shard encodes ceil(k/M) slots but only
    its live budget survives: dead slots carry the sentinel index d_local
    (dropped by decode_sum) and value 0, and their mass stays in the EF
    residual. Checked through a real (1, M) mesh so lax.axis_index sees
    the model axis (subprocess with forced host devices)."""
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.comm import compress
        from repro.comm.topology import auto_axes

        M, d_loc, k = 2, 16, 5
        comp = compress.TopK(k).with_shards(M, "model")
        mesh = auto_axes(jax.make_mesh((1, M), ("data", "model")))
        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((M, d_loc)).astype(np.float32))
        ef = jnp.zeros((M, d_loc))
        rngs = jax.random.split(jax.random.PRNGKey(0), M)

        def per_shard(xm, em, rm):
            msg, res = comp.encode(xm[0], em[0], rm[0])
            return msg.idx[None], msg.val[None], res[None]

        idx, val, res = jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(P("model"), P("model"), P("model")),
            out_specs=(P("model"), P("model"), P("model")),
            check_vma=False)(x, ef, rngs)
        slots = -(-k // M)
        assert idx.shape == (M, slots), idx.shape
        live = [int(np.sum(np.asarray(idx[m]) < d_loc)) for m in range(M)]
        assert live == [3, 2], live              # remainder to low shards
        for m in range(M):
            im, vm = np.asarray(idx[m]), np.asarray(val[m])
            assert np.all(vm[im >= d_loc] == 0.0)
            # transmitted + residual reconstructs the input exactly (EF
            # holds the dead slots' mass)
            dec = np.asarray(compress.decode_sum(idx[m], val[m], d_loc))
            np.testing.assert_allclose(dec + np.asarray(res[m]),
                                       np.asarray(x[m]), rtol=1e-6,
                                       atol=1e-7)
        print("BUDGET SPLIT ENCODE OK", live)
    """, devices=2)
    assert "BUDGET SPLIT ENCODE OK" in out


def test_budget_split_gather_2d_history_wire_accounting():
    """End-to-end on the (2,2) mesh: a compressed-gather run's comm_floats
    history prices the split budget -- per model shard K * 2*ceil(k/M)
    gather floats plus the model-axis solver exchange -- and the run still
    certifies (EF keeps the masked mass). Mesh total = M x the per-shard
    plan: ~2kK, M-invariant (the naive unsplit budget would be 2kKM)."""
    out = _run("""
        import jax
        from repro.core import CoCoAConfig, solve
        from repro.data.sparse import make_sparse_classification, \\
            partition_sparse
        K, M, H, d, k = 2, 2, 32, 50, 8
        csr, y = make_sparse_classification(128, d, density=0.1, seed=0)
        fs, yp, mk = partition_sparse(csr, y, K, seed=0, M=M)
        mesh = jax.make_mesh((K, M), ("data", "model"))
        r = solve(CoCoAConfig.adding(K, backend="shard_map",
                                     model_axis="model", loss="hinge",
                                     lam=1e-3, H=H, compress="topk",
                                     compress_k=k, gather=True),
                  fs, yp, mk, rounds=2, gap_every=1, mesh=mesh)
        k_shard = -(-k // M)
        per_round = K * 2 * k_shard + K * M * H
        assert r.history["comm_floats"] == [per_round, 2 * per_round], \\
            (r.history["comm_floats"], per_round)
        gaps = r.history["gap"]
        assert gaps[-1] < gaps[0] * 1.5 and min(gaps) > -1e-6, gaps
        print("BUDGET SPLIT 2D WIRE OK", per_round)
    """, devices=4)
    assert "BUDGET SPLIT 2D WIRE OK" in out


# ----------------------------------------------------------------------------
# tracer: reduce volume scales as d/M, per-axis split, measured overrides
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("M", [1, 2, 4, 8])
def test_tracer_reduce_volume_scales_as_d_over_M(M):
    K, d = 4, 1000
    ws = comm.WSpec(d=d, M=M, model_axis="model" if M > 1 else None)
    tr = tracer.CommTracer.for_run(
        K=K, d_local=ws.d_local, topo=topology.Topology.simulated(K))
    assert tr.per_round()["floats"] == K * (-(-d // M))
    hop = tr.per_hop()[0]
    assert hop["axis"] == "data"
    assert hop["floats_per_message"] == -(-d // M)


def test_tracer_per_axis_split_and_model_hop():
    K, M, d, H = 4, 2, 512, 128
    ws = comm.WSpec(d=d, M=M, model_axis="model")
    tr = tracer.CommTracer.for_run(
        K=K, d_local=ws.d_local, topo=topology.Topology.simulated(K),
        extra_hops=(topology.Hop("model_z", K * M, H, axis="model"),))
    ax = tr.per_axis()
    assert ax["data"] == K * ws.d_local
    assert ax["model"] == K * M * H
    assert tr.per_round()["floats"] == ax["data"] + ax["model"]


def test_tracer_observe_overrides_analytic():
    K, d, g, k = 8, 512, 2, 16
    topo = topology.Topology.simulated(K, topology=f"hier:{g}")
    tr = tracer.CommTracer.for_run(K=K, d_local=d,
                                   compressor=compress.TopK(k),
                                   topo=topo, gather=True)
    tr.tick()
    tr.observe("inter_gather", 40)
    tr.tick()
    tr.observe("inter_gather", 44)
    intra = K * 2 * k
    assert tr.floats == 2 * intra + 84              # measured, not analytic
    hop = [h for h in tr.per_hop() if h["hop"] == "inter_gather"][0]
    assert hop["measured_floats"] == 84
    assert hop["floats"] == (K // g) * g * 2 * k    # analytic bound intact


# ----------------------------------------------------------------------------
# placement migration + feature-sharded elastic
# ----------------------------------------------------------------------------

def test_reshard_w_state_flushes_ef_and_pads():
    K, d = 3, 10
    rng = np.random.default_rng(0)
    state = cocoa.init_state(d, K, 4)
    w = jnp.asarray(rng.standard_normal(d).astype(np.float32))
    ef = jnp.asarray(rng.standard_normal((K, d)).astype(np.float32))
    state = state._replace(w=w, ef=ef)
    old = comm.WSpec(d=d)
    new = comm.WSpec(d=d, M=4, model_axis="model")
    p = aggregate.AggParams(0.5, 2.0)
    out = cocoa.reshard_w_state(state, old, new, p)
    assert out.w.shape == (new.d_padded,)
    # EF debt flushed (w += gamma * sum_k ef_k), then padded with zeros
    np.testing.assert_allclose(
        np.asarray(out.w[:d]),
        np.asarray(w + 0.5 * jnp.sum(ef, axis=0)), rtol=1e-6)
    assert np.all(np.asarray(out.w[d:]) == 0)
    assert out.ef.shape == (K, new.d_padded)
    assert float(jnp.max(jnp.abs(out.ef))) == 0.0
    # and back down: unpad keeps the global coordinates
    back = cocoa.reshard_w_state(out, new, old, p)
    np.testing.assert_allclose(np.asarray(back.w), np.asarray(out.w[:d]))
    with pytest.raises(ValueError):
        cocoa.reshard_w_state(state, old, comm.WSpec(d=d + 1), p)


def test_repartition_features_keeps_rows_and_slices():
    sh, yp, mk = _toy_shards(n=90, d=37, K=3)
    fs = sp.shard_features(sh, 2)
    alpha = jnp.asarray(np.random.default_rng(0)
                        .random(yp.shape).astype(np.float32) * np.asarray(mk))
    fs2, y2, a2, mk2 = elastic.repartition_features(fs, yp, alpha, mk, 5)
    assert fs2.M == 2 and fs2.d == fs.d and fs2.cols.shape[0] == 5
    # every real row survives with its slices: compare densified row sets
    D1 = np.asarray(sp.densify(fs)).reshape(-1, fs.d_padded)
    D1 = D1[np.asarray(mk).reshape(-1) > 0]
    D2 = np.asarray(sp.densify(fs2)).reshape(-1, fs2.d_padded)
    D2 = D2[np.asarray(mk2).reshape(-1) > 0]
    np.testing.assert_allclose(D2, D1, atol=1e-7)   # worker-major order kept
    np.testing.assert_array_equal(
        np.asarray(a2).reshape(-1)[np.asarray(mk2).reshape(-1) > 0],
        np.asarray(alpha).reshape(-1)[np.asarray(mk).reshape(-1) > 0])


# ----------------------------------------------------------------------------
# solver/config guards
# ----------------------------------------------------------------------------

def test_feature_sharded_solver_guards():
    with pytest.raises(ValueError, match="feature-sharded"):
        cocoa._resolve_solver("sdca_kernel", sparse=False,
                              feature_sharded=True)
    # the sparse kernel runs M>1 natively via the z-exchange schedule
    assert cocoa._resolve_solver(
        "sdca_sparse_kernel", sparse=True,
        feature_sharded=True) == "sdca_sparse_kernel"
    assert cocoa._resolve_solver(
        "sdca_kernel", sparse=True,
        feature_sharded=True) == "sdca_sparse_kernel"
    assert cocoa._resolve_solver("sdca", sparse=True,
                                 feature_sharded=True) == "sdca_sparse"
    from repro.core.solvers import local_sdca, local_sdca_sparse
    X = jnp.zeros((4, 8))
    with pytest.raises(ValueError, match="global sqnorms"):
        local_sdca(X, jnp.ones(4), jnp.zeros(4), jnp.ones(4), jnp.zeros(8),
                   jax.random.PRNGKey(0), None, 1e-3, 4.0, 1.0, 4,
                   model_axis="model")
    shard = sp.SparseShards(jnp.zeros((4, 2), jnp.int32), jnp.zeros((4, 2)),
                            jnp.ones((4,), jnp.int32), d=8)
    with pytest.raises(ValueError, match="global sqnorms"):
        local_sdca_sparse(shard, jnp.ones(4), jnp.zeros(4), jnp.ones(4),
                          jnp.zeros(8), jax.random.PRNGKey(0), None, 1e-3,
                          4.0, 1.0, 4, model_axis="model")
    from repro.kernels import ops
    with pytest.raises(NotImplementedError, match="model-axis"):
        ops.local_sdca_block(X, jnp.ones(4), jnp.zeros(4), jnp.ones(4),
                             jnp.zeros(8), jax.random.PRNGKey(0), None,
                             1e-3, 4.0, 1.0, 4, model_axis="model")


def test_solve_rejects_feature_shards_on_vmap():
    from repro.core import CoCoAConfig, solve

    sh, yp, mk = _toy_shards()
    fs = sp.shard_features(sh, 2)
    with pytest.raises(ValueError, match="shard_map"):
        solve(CoCoAConfig.adding(3, loss="hinge", H=8), fs, yp, mk, rounds=1)


# ----------------------------------------------------------------------------
# the 2-D mesh end-to-end (subprocess with forced host devices)
# ----------------------------------------------------------------------------

def _run(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout


def test_cocoa_2d_feature_sharded_matches_vmap_all_topologies():
    """The acceptance bar: on a (2, 2) CPU mesh, the feature-sharded
    shard_map backend matches the vmap reference to 1e-6 on tiny_sparse
    across flat / hier / a2a reduce plans."""
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import CoCoAConfig, solve
        from repro.data import load
        from repro.data.sparse import partition_sparse
        csr, y = load("tiny_sparse")
        sh, yp, mk = partition_sparse(csr, y, 2, seed=0)
        fs, yp2, mk2 = partition_sparse(csr, y, 2, seed=0, M=2)
        assert np.array_equal(np.asarray(yp), np.asarray(yp2))
        d = sh.d
        kw = dict(loss="hinge", lam=1e-3, H=128)
        rv = solve(CoCoAConfig.adding(2, **kw), sh, yp, mk,
                   rounds=4, gap_every=1)
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        for topo in ("flat", "hier:2", "a2a"):
            rs = solve(CoCoAConfig.adding(2, backend="shard_map",
                                          model_axis="model",
                                          topology=topo, **kw),
                       fs, yp, mk, rounds=4, gap_every=1, mesh=mesh)
            w_err = float(jnp.max(jnp.abs(rs.state.w[:d] - rv.state.w)))
            a_err = float(jnp.max(jnp.abs(rs.state.alpha - rv.state.alpha)))
            assert w_err < 1e-6, (topo, w_err)
            assert a_err < 1e-6, (topo, a_err)
            assert float(jnp.sum(jnp.abs(rs.state.w[d:]))) == 0.0
            np.testing.assert_allclose(rv.history["gap"],
                                       rs.history["gap"],
                                       rtol=1e-4, atol=1e-6)
        print("2D FEATURE-SHARDED PARITY OK")
    """, devices=4)
    assert "2D FEATURE-SHARDED PARITY OK" in out


def test_cocoa_2d_m1_bit_for_bit_with_1d_backend():
    """M=1 on the 2-D code path (FeatureShards + model axis of size 1)
    reproduces the 1-D replicated backend bit-for-bit."""
    out = _run("""
        import jax, numpy as np
        from repro.core import CoCoAConfig, solve
        from repro.data import load
        from repro.data.sparse import partition_sparse, shard_features
        csr, y = load("tiny_sparse")
        sh, yp, mk = partition_sparse(csr, y, 4, seed=0)
        fs1 = shard_features(sh, 1)
        kw = dict(loss="hinge", lam=1e-3, H=128)
        r1 = solve(CoCoAConfig.adding(4, backend="shard_map", **kw),
                   sh, yp, mk, rounds=4, gap_every=4,
                   mesh=jax.make_mesh((4,), ("data",)))
        r2 = solve(CoCoAConfig.adding(4, backend="shard_map",
                                      model_axis="model", **kw),
                   fs1, yp, mk, rounds=4, gap_every=4,
                   mesh=jax.make_mesh((4, 1), ("data", "model")))
        assert np.array_equal(np.asarray(r1.state.w), np.asarray(r2.state.w))
        assert np.array_equal(np.asarray(r1.state.alpha),
                              np.asarray(r2.state.alpha))
        assert np.array_equal(np.asarray(r1.state.ef), np.asarray(r2.state.ef))
        assert r1.history["gap"] == r2.history["gap"]
        print("M1 BITWISE OK")
    """, devices=4)
    assert "M1 BITWISE OK" in out


def test_cocoa_2d_dense_feature_sharded_matches_vmap():
    """Dense path: X sliced along d through the in_specs, solver completes
    the partial dot with a model-axis psum; 1e-6 vs the vmap reference."""
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import CoCoAConfig, solve
        from repro.data import make_classification, partition
        X, y = make_classification(512, 51, seed=0)   # 51 % 2 != 0: pads
        Xp, yp, mk = partition(X, y, 4, seed=1)
        kw = dict(loss="hinge", lam=1e-3, H=64)
        rv = solve(CoCoAConfig.adding(4, **kw), Xp, yp, mk,
                   rounds=3, gap_every=3)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rs = solve(CoCoAConfig.adding(4, backend="shard_map",
                                      model_axis="model", **kw),
                   Xp, yp, mk, rounds=3, gap_every=3, mesh=mesh)
        w_err = float(jnp.max(jnp.abs(rs.state.w[:51] - rv.state.w)))
        assert w_err < 1e-6, w_err
        assert rs.state.w.shape == (52,)                # padded to 2*26
        assert float(jnp.max(jnp.abs(rs.state.w[51:]))) == 0.0
        print("2D DENSE PARITY OK", w_err)
    """)
    assert "2D DENSE PARITY OK" in out


def test_cocoa_2d_compressed_gather_local_coords():
    """Compressed gather under feature sharding: per-shard top-k sets in
    local coordinates, reduced per shard over the data axis. Every reduce
    topology yields the identical (w, ef) -- the wire routing (including
    pod-level dedup) never changes the algorithm."""
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import CoCoAConfig, solve
        from repro.data.sparse import make_sparse_classification, \\
            partition_sparse, shard_features
        csr, y = make_sparse_classification(256, 60, density=0.1, seed=0)
        sh, yp, mk = partition_sparse(csr, y, 4, seed=0)
        fs = shard_features(sh, 2)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        kw = dict(loss="hinge", lam=1e-3, H=64, compress="topk",
                  compress_k=8, gather=True)
        ref = None
        for topo in ("flat", "hier:2", "a2a"):
            rs = solve(CoCoAConfig.adding(4, backend="shard_map",
                                          model_axis="model",
                                          topology=topo, **kw),
                       fs, yp, mk, rounds=3, gap_every=3, mesh=mesh)
            if ref is None:
                ref = rs
            else:
                w_err = float(jnp.max(jnp.abs(rs.state.w - ref.state.w)))
                e_err = float(jnp.max(jnp.abs(rs.state.ef - ref.state.ef)))
                assert w_err < 1e-6, (topo, w_err)
                assert e_err < 1e-6, (topo, e_err)
        assert ref.history["gap"][-1] < ref.history["gap"][0] * 1.05
        print("2D GATHER CONSISTENT OK")
    """)
    assert "2D GATHER CONSISTENT OK" in out


def test_cocoa_2d_dense_failure_recovery_repads_w():
    """Dual-safe worker drop on a dense feature-sharded run: w_of_alpha
    rebuilds w at the unpadded width d, so the recovery must re-place it
    (WSpec.pad_w) before the next sharded round -- the cocoa_train
    sequence, exercised at d % M != 0."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro import comm
        from repro.core import CoCoAConfig, solve
        from repro.data import make_classification, partition
        from repro.runtime import failures
        X, y = make_classification(256, 51, seed=0)     # 51 % 2 != 0
        Xp, yp, mk = partition(X, y, 4, seed=1)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        cfg = CoCoAConfig.adding(4, loss="hinge", lam=1e-3, H=32,
                                 backend="shard_map", model_axis="model")
        r = solve(cfg, Xp, yp, mk, rounds=2, gap_every=2, mesh=mesh)
        st = failures.fail_and_recover(r.state, Xp, mk, 1e-3, k=0)
        assert st.w.shape == (51,)                      # unpadded rebuild
        wspec = comm.WSpec(d=51, M=2, model_axis="model")
        st = st._replace(w=wspec.pad_w(st.w))
        r2 = solve(cfg, Xp, yp, mk, rounds=2, gap_every=2, mesh=mesh,
                   state=st)
        assert r2.state.w.shape == (52,)
        assert r2.history["gap"][-1] < 2.0
        print("2D FAILURE RECOVERY OK")
    """)
    assert "2D FAILURE RECOVERY OK" in out


def test_cocoa_2d_history_tracks_per_axis_volume():
    """The solve history's comm_floats on a 2-D mesh carries the analytic
    per-shard reduce (K * ceil(d/M) floats) plus the model-axis solver
    exchange (K*M*H) -- the d/M scaling asserted from the wire plan."""
    out = _run("""
        import jax
        from repro.core import CoCoAConfig, solve
        from repro.data.sparse import make_sparse_classification, \\
            partition_sparse, shard_features
        csr, y = make_sparse_classification(128, 50, density=0.1, seed=0)
        sh, yp, mk = partition_sparse(csr, y, 2, seed=0)
        K, M, H, d = 2, 2, 32, 50
        fs = shard_features(sh, M)
        mesh = jax.make_mesh((K, M), ("data", "model"))
        r = solve(CoCoAConfig.adding(K, backend="shard_map",
                                     model_axis="model", loss="hinge",
                                     lam=1e-3, H=H),
                  fs, yp, mk, rounds=2, gap_every=1, mesh=mesh)
        d_loc = -(-d // M)
        per_round = K * d_loc + K * M * H
        assert r.history["comm_floats"] == [per_round, 2 * per_round], \\
            (r.history["comm_floats"], per_round)
        print("2D WIRE ACCOUNTING OK")
    """, devices=4)
    assert "2D WIRE ACCOUNTING OK" in out


def test_cocoa_2d_sparse_kernel_path_parity():
    """Acceptance: CoCoAConfig(solver="sdca_kernel") on a (2, 2) mesh
    runs the sparse kernel's z-exchange schedule -- no jnp fallback,
    LAST_SPARSE_CONFIG pins the launch (model_shards=2, zx, fused prox)
    -- and its final certified gap (duality.gap_at_v inside solve) lands
    within 1e-5 of the jnp sharded path's at equal rounds. Bit-equality
    is NOT the contract here: the zx schedule's within-block stale z is
    a Theta-approximation knob (Ma et al. 1512.04039); the duality gap
    is the certificate."""
    out = _run("""
        import jax, numpy as np
        from repro.core import CoCoAConfig, solve
        from repro.data.sparse import make_sparse_classification, \\
            partition_sparse, shard_features
        from repro.kernels import ops
        csr, y = make_sparse_classification(256, 512, density=0.02, seed=0)
        sh, yp, mk = partition_sparse(csr, y, 2, seed=1)
        fs = shard_features(sh, 2)
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        kw = dict(loss="smooth_hinge", lam=1e-3, H=256, reg="elastic:0.5",
                  backend="shard_map", model_axis="model")
        rounds = 40
        rj = solve(CoCoAConfig.adding(2, solver="sdca", **kw), fs, yp, mk,
                   rounds=rounds, gap_every=rounds, seed=2, mesh=mesh)
        rk = solve(CoCoAConfig.adding(2, solver="sdca_kernel", **kw),
                   fs, yp, mk, rounds=rounds, gap_every=rounds, seed=2,
                   mesh=mesh)
        cfgd = ops.LAST_SPARSE_CONFIG
        assert cfgd["zx"] is True and cfgd["model_shards"] == 2, cfgd
        assert cfgd["prox_fused"] is True, cfgd
        gj, gk = rj.history["gap"][-1], rk.history["gap"][-1]
        assert gk >= -1e-7, gk                 # certified nonneg
        assert abs(gj - gk) < 1e-5, (gj, gk)
        print("2D KERNEL PATH OK", gj, gk)
    """, devices=4)
    assert "2D KERNEL PATH OK" in out


def test_cocoa_2d_kernel_history_prices_zx_wire():
    """The kernel path's model-axis hop is the z-exchange, not the jnp
    per-step scalar psum: history must price K*M devices each moving
    `exchanges * block_rows` floats per round -- the same resolve/clamp
    arithmetic the dispatch launches with (sparse_zx_plan), asserted
    against the analytic n_passes * blocks + 1 prologue."""
    out = _run("""
        import jax
        from repro.core import CoCoAConfig, solve
        from repro.data.sparse import make_sparse_classification, \\
            partition_sparse, shard_features
        from repro.kernels.ops import sparse_zx_plan
        csr, y = make_sparse_classification(128, 50, density=0.1, seed=0)
        sh, yp, mk = partition_sparse(csr, y, 2, seed=0)
        K, M, H, d = 2, 2, 32, 50
        fs = shard_features(sh, M)
        mesh = jax.make_mesh((K, M), ("data", "model"))
        r = solve(CoCoAConfig.adding(K, backend="shard_map",
                                     model_axis="model", loss="hinge",
                                     lam=1e-3, H=H, solver="sdca_kernel"),
                  fs, yp, mk, rounds=2, gap_every=1, mesh=mesh)
        nk, r_max = fs.cols.shape[2], fs.cols.shape[3]
        d_loc = -(-d // M)
        plan = sparse_zx_plan(nk, d_loc, H, r_max=r_max, reg_family="l2",
                              model_shards=M)
        assert plan["exchanges"] == plan["n_passes"] * plan["blocks"] + 1
        per_round = K * d_loc \\
            + K * M * plan["exchanges"] * plan["block_rows"]
        assert r.history["comm_floats"] == [per_round, 2 * per_round], \\
            (r.history["comm_floats"], per_round, plan)
        print("2D ZX WIRE OK")
    """, devices=4)
    assert "2D ZX WIRE OK" in out
