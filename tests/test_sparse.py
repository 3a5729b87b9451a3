"""Sparse subsystem: LIBSVM parser, CSR<->ELL round-trip, SparseShards
partitioner parity with the dense contract, sparse duality-gap evaluation,
and the Pallas sparse LocalSDCA kernel vs its pure-jnp oracle (bit-for-bit,
same visit order -- not statistical)."""
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CoCoAConfig, duality, solve
from repro.core.losses import get_loss
from repro.core.solvers import ell_width, local_sdca, local_sdca_sparse
from repro.data import sparse as sp
from repro.data.synthetic import partition
from repro.kernels.ops import sparse_local_sdca_block
from repro.kernels.ref import local_sdca_ref, sparse_local_sdca_ref
from repro.kernels.sparse_sdca import sparse_local_sdca, vmem_budget


def _problem(n=256, d=128, density=0.05, K=4, seed=0):
    csr, y = sp.make_sparse_classification(n, d, density=density, seed=seed)
    return csr, y, sp.partition_sparse(csr, y, K, seed=seed + 1)


# ----------------------------------------------------------------------------
# parser
# ----------------------------------------------------------------------------

def test_libsvm_parser_basic():
    lines = [
        "+1 1:0.5 3:-0.25   # trailing comment",
        "-1 2:1.0",
        "",                     # blank line ignored
        "1 1:2.0 2:3.0 4:4.0",
    ]
    csr, y = sp.load_libsvm(lines)
    np.testing.assert_array_equal(y, [1.0, -1.0, 1.0])
    assert csr.shape == (3, 4)
    assert csr.nnz == 6
    expect = np.array([[0.5, 0.0, -0.25, 0.0],
                       [0.0, 1.0, 0.0, 0.0],
                       [2.0, 3.0, 0.0, 4.0]], np.float32)
    np.testing.assert_allclose(csr.toarray(), expect)


def test_libsvm_parser_file_and_options(tmp_path):
    p = tmp_path / "data.svm"
    p.write_text("2.5 0:1.0 7:2.0\n-1.5 3:4.0\n")
    csr, y = sp.load_libsvm(p, zero_based=True, n_features=10)
    assert csr.shape == (2, 10)
    np.testing.assert_allclose(y, [2.5, -1.5])
    np.testing.assert_allclose(csr.toarray()[0, [0, 7]], [1.0, 2.0])
    with pytest.raises(ValueError):
        sp.load_libsvm(["1 0:1.0"])     # 1-based parse of a 0 index


def test_libsvm_parser_sorts_columns():
    csr, _ = sp.load_libsvm(["1 5:5.0 2:2.0 9:9.0"])
    np.testing.assert_array_equal(csr.indices, [1, 4, 8])
    np.testing.assert_allclose(csr.data, [2.0, 5.0, 9.0])


def _libsvm_file(tmp_path, n=10, d=12, seed=0):
    rng = np.random.default_rng(seed)
    lines = []
    for i in range(n):
        nnz = rng.integers(1, 5)
        cols = np.sort(rng.choice(d, nnz, replace=False)) + 1
        toks = " ".join(f"{c}:{rng.standard_normal():.4f}" for c in cols)
        lines.append(f"{1 if i % 2 else -1} {toks}")
    p = tmp_path / "chunked.svm"
    p.write_text("\n".join(lines) + "\n")
    return p


def test_libsvm_chunked_matches_unchunked(tmp_path):
    """chunk_rows streams CSR blocks; the stitched result is exactly the
    one-pass parse (multi-chunk file: 10 rows / chunk_rows=3 -> 4 blocks,
    the last partial)."""
    p = _libsvm_file(tmp_path, n=10, d=12)
    csr_full, y_full = sp.load_libsvm(p)
    csr_chunked, y_chunked = sp.load_libsvm(p, chunk_rows=3)
    np.testing.assert_array_equal(y_chunked, y_full)
    np.testing.assert_array_equal(csr_chunked.indices, csr_full.indices)
    np.testing.assert_allclose(csr_chunked.data, csr_full.data)
    np.testing.assert_array_equal(csr_chunked.indptr, csr_full.indptr)
    assert csr_chunked.shape == csr_full.shape
    np.testing.assert_allclose(csr_chunked.toarray(), csr_full.toarray())


def test_libsvm_chunk_iterator_blocks(tmp_path):
    p = _libsvm_file(tmp_path, n=10, d=12, seed=3)
    blocks = list(sp.iter_libsvm_chunks(p, chunk_rows=3, n_features=12))
    assert [b.shape[0] for b, _ in blocks] == [3, 3, 3, 1]
    assert all(b.shape[1] == 12 for b, _ in blocks)
    stitched = sp.csr_vstack([b for b, _ in blocks])
    csr_full, _ = sp.load_libsvm(p, n_features=12)
    np.testing.assert_allclose(stitched.toarray(), csr_full.toarray())
    # per-chunk n_features validation still rejects out-of-range indices
    with pytest.raises(ValueError, match="out of range"):
        list(sp.iter_libsvm_chunks(p, chunk_rows=3, n_features=2))


def test_libsvm_chunks_comments_blanks_dont_count_toward_chunk():
    """Comment-only and blank lines are skipped entirely by the chunker:
    they neither produce rows nor advance the chunk_rows counter, even
    when they straddle a chunk boundary."""
    lines = [
        "# leading comment line",
        "+1 1:1.0",
        "",
        "-1 2:2.0  # trailing comment",
        "   ",                       # whitespace-only
        "# comment between chunks",
        "+1 3:3.0",
        "-1 1:0.5 3:1.5",
        "",
        "+1 2:-1.0",
    ]
    blocks = list(sp.iter_libsvm_chunks(lines, chunk_rows=2, n_features=4))
    assert [b.shape[0] for b, _ in blocks] == [2, 2, 1]   # 5 real rows
    stitched = sp.csr_vstack([b for b, _ in blocks])
    csr_full, y = sp.load_libsvm([l for l in lines], n_features=4)
    np.testing.assert_allclose(stitched.toarray(), csr_full.toarray())
    np.testing.assert_array_equal(
        np.concatenate([yy for _, yy in blocks]), y)


def test_libsvm_empty_feature_row_roundtrip():
    """A label-only row (zero features) survives the whole pipeline:
    iter_libsvm_chunks -> csr_vstack -> partition_sparse. Its ELL row is
    all padding (exact no-ops), its sqnorm is 0, and the mask keeps it a
    real (if vacuous) datapoint."""
    lines = [
        "+1 1:1.0 2:0.5",
        "-1",                        # empty-feature row
        "+1 3:2.0",
        "-1",                        # another, at a chunk boundary
        "+1 1:-1.0",
    ]
    blocks = list(sp.iter_libsvm_chunks(lines, chunk_rows=2, n_features=4))
    assert [b.shape[0] for b, _ in blocks] == [2, 2, 1]
    csr = sp.csr_vstack([b for b, _ in blocks], d=4)
    y = np.concatenate([yy for _, yy in blocks])
    assert csr.shape == (5, 4)
    np.testing.assert_array_equal(csr.row_nnz(), [2, 0, 1, 0, 1])
    shards, yp, mk = sp.partition_sparse(csr, y, 2, seed=0)
    assert float(jnp.sum(mk)) == 5                 # all rows real
    # the empty rows' ELL slots are pure padding -> zero sqnorm, and the
    # densified partition reproduces the CSR exactly
    dense = np.asarray(sp.densify(shards)).reshape(-1, 4)
    order_restored = dense[np.asarray(mk).reshape(-1) > 0]
    assert sorted(map(tuple, order_restored.tolist())) == \
        sorted(map(tuple, csr.toarray().tolist()))
    sq = np.asarray(sp.row_sqnorms(shards)).reshape(-1)
    assert (sq[np.asarray(mk).reshape(-1) > 0] == 0).sum() == 2


def test_libsvm_trailing_partial_chunk_and_exact_multiple(tmp_path):
    """The trailing partial chunk flushes; an exact-multiple file does not
    emit a phantom empty block; an empty input yields one empty block."""
    p = _libsvm_file(tmp_path, n=6, d=8, seed=5)
    exact = list(sp.iter_libsvm_chunks(p, chunk_rows=3, n_features=8))
    assert [b.shape[0] for b, _ in exact] == [3, 3]
    partial = list(sp.iter_libsvm_chunks(p, chunk_rows=4, n_features=8))
    assert [b.shape[0] for b, _ in partial] == [4, 2]
    np.testing.assert_allclose(
        sp.csr_vstack([b for b, _ in exact]).toarray(),
        sp.csr_vstack([b for b, _ in partial]).toarray())
    empty = list(sp.iter_libsvm_chunks([], chunk_rows=4, n_features=8))
    assert len(empty) == 1 and empty[0][0].shape == (0, 8)
    with pytest.raises(ValueError, match="chunk_rows"):
        list(sp.iter_libsvm_chunks([], chunk_rows=0))


# ----------------------------------------------------------------------------
# CSR <-> ELL round-trip
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("density", [0.01, 0.1, 0.5])
def test_ell_roundtrip(density):
    csr, _ = sp.make_sparse_classification(97, 64, density=density, seed=3)
    cols, vals, nnz = sp.csr_to_ell(csr)
    back = sp.ell_to_csr(cols, vals, nnz, csr.shape[1])
    np.testing.assert_array_equal(back.indices, csr.indices)
    np.testing.assert_allclose(back.data, csr.data)
    np.testing.assert_array_equal(back.indptr, csr.indptr)
    assert back.shape == csr.shape
    # padding slots are exact no-op entries
    slot = np.arange(cols.shape[1])[None, :] >= nnz[:, None]
    assert np.all(cols[slot] == 0) and np.all(vals[slot] == 0.0)


def test_ell_r_max_override_and_validation():
    csr, _ = sp.make_sparse_classification(31, 32, density=0.1, seed=1)
    need = int(csr.row_nnz().max())
    cols, vals, _ = sp.csr_to_ell(csr, r_max=need + 5)
    assert cols.shape == (31, need + 5)
    with pytest.raises(ValueError):
        sp.csr_to_ell(csr, r_max=need - 1)


# ----------------------------------------------------------------------------
# partitioner: dense-contract parity
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("heterogeneity", [1.0, 0.5])
def test_partition_sparse_matches_dense_contract(heterogeneity):
    """Same seed => the sparse partitioner places rows exactly like the dense
    one (shared split_order, same rng stream), with identical mask/padding."""
    csr, y, _ = _problem(n=131, K=4, seed=5)      # prime n: padding rows
    Xd = csr.toarray()
    Xp, yp_d, mk_d = partition(Xd, y, 4, seed=9, heterogeneity=heterogeneity)
    sh, yp_s, mk_s = sp.partition_sparse(csr, y, 4, seed=9,
                                         heterogeneity=heterogeneity)
    np.testing.assert_array_equal(np.asarray(mk_s), np.asarray(mk_d))
    np.testing.assert_array_equal(np.asarray(yp_s), np.asarray(yp_d))
    np.testing.assert_allclose(np.asarray(sp.densify(sh)), np.asarray(Xp),
                               rtol=1e-6, atol=1e-7)


def test_partition_heterogeneity_preserves_shuffle():
    """The non-sorted fraction must stay in permutation order, not index
    order (regression: np.setdiff1d silently sorted it)."""
    from repro.data.synthetic import split_order
    n = 400
    order = split_order(n, np.random.default_rng(3), 0.75,
                        lambda r: r.standard_normal(n))
    assert sorted(order) == list(range(n))        # still a permutation
    rest = order[100:]                            # the shuffled 75%
    # a sorted tail would be monotonically increasing; a shuffle is not
    assert np.sum(np.diff(rest) < 0) > len(rest) // 4


# ----------------------------------------------------------------------------
# sparse matvec family + duality certificates
# ----------------------------------------------------------------------------

def test_sparse_gap_matches_densified():
    _, _, (sh, yp, mk) = _problem(seed=2)
    Xd = sp.densify(sh)
    loss = get_loss("hinge")
    rng = np.random.default_rng(0)
    alpha = (jnp.asarray(rng.random(yp.shape).astype(np.float32)) * yp) * mk
    for fn in (duality.w_of_alpha,):
        np.testing.assert_allclose(np.asarray(fn(sh, alpha, 1e-3, 256.0)),
                                   np.asarray(fn(Xd, alpha, 1e-3, 256.0)),
                                   rtol=1e-5, atol=1e-6)
    ps, ds, gs = duality.gap_decomposed(alpha, sh, yp, mk, loss, 1e-3)
    pd, dd, gd = duality.gap_decomposed(alpha, Xd, yp, mk, loss, 1e-3)
    assert abs(float(ps) - float(pd)) < 1e-5
    assert abs(float(ds) - float(dd)) < 1e-5
    assert abs(float(gs) - float(gd)) < 1e-5


# ----------------------------------------------------------------------------
# the certificate's layout: rows sorted by length, walked in live tiles
# ----------------------------------------------------------------------------

def _csr_of_lengths(lengths, d, seed=0):
    """Rows with the given entry counts, distinct sorted columns."""
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths, np.int64)
    cols = [np.sort(rng.choice(d, int(n), replace=False)) for n in lengths]
    indptr = np.concatenate([[0], np.cumsum(lengths)])
    data = (0.2 * rng.standard_normal(indptr[-1])).astype(np.float32)
    csr = sp.CSRMatrix(data, np.concatenate(cols + [np.zeros(0)]).astype(
        np.int32), indptr, (len(lengths), d))
    y = rng.choice([-1.0, 1.0], len(lengths)).astype(np.float32)
    return csr, y


def _layout_case(case, K):
    """(shards, y, mask) of one case; row counts leave padding rows on
    eight workers and blocks of 16 rows that the rows do not fill."""
    rng = np.random.default_rng(5)
    if case == "tiny_sparse":
        from repro.data import load
        csr, y = load("tiny_sparse")
        return sp.partition_sparse(csr, y, K, seed=0)
    n, d, r_max = 203, 96, 40
    lengths = {
        "skewed": np.minimum(rng.geometric(0.08, n), r_max),
        "empty_rows": np.where(rng.random(n) < 0.5, 0,
                               rng.integers(1, r_max + 1, n)),
        "full_width": np.full(n, r_max),
    }[case]
    lengths[0] = r_max        # every case reaches the full width once
    csr, y = _csr_of_lengths(lengths, d)
    return sp.partition_sparse(csr, y, K, seed=1, r_max=r_max)


LAYOUT_CASES = ["tiny_sparse", "skewed", "empty_rows", "full_width"]


def _undo(layout, z):
    """z in the layout's row order -> the shards' (padding rows dropped)."""
    order, z = np.asarray(layout.order), np.asarray(z)
    out = np.zeros((order.shape[0], layout.nk), z.dtype)
    for k in range(order.shape[0]):
        real = order[k] < layout.nk
        out[k, order[k, real]] = z[k, real]
    return out


@pytest.mark.parametrize("K", [1, 8])
@pytest.mark.parametrize("case", LAYOUT_CASES)
def test_cert_layout_passes_match_the_ell_passes(case, K):
    """z = A^T w and v = A alpha over the live tiles equal the raw ELL
    passes, once the rows are put back in their order; the rows the
    layout adds read 0."""
    sh, yp, mk = _layout_case(case, K)
    lay = jax.jit(lambda s: sp.cert_layout(s, block_rows=16))(sh)
    K_, nk, _ = sh.cols.shape
    assert nk % 16 or case == "tiny_sparse"
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.standard_normal(sh.d).astype(np.float32))
    alpha = jnp.asarray(rng.standard_normal(yp.shape).astype(np.float32))
    z = jax.jit(sp.matvec)(lay, w)
    np.testing.assert_allclose(_undo(lay, z), np.asarray(sp.matvec(sh, w)),
                               rtol=1e-6, atol=1e-6)
    assert not np.any(np.asarray(z)[np.asarray(lay.order) >= nk])
    np.testing.assert_allclose(
        np.asarray(jax.jit(sp.rmatvec)(lay, lay.rows(alpha))),
        np.asarray(sp.rmatvec(sh, alpha)), rtol=1e-6, atol=1e-6)
    # each worker's rows longest first, and a permutation of its own
    fills = -(-np.asarray(lay.rows(sh.nnz)) // sp.CERT_CHUNK)
    assert np.all(np.diff(fills, axis=1) <= 0)
    for k in range(K_):
        real = np.asarray(lay.order[k])
        assert sorted(real[real < nk]) == list(range(nk))


@pytest.mark.parametrize("K", [1, 8])
@pytest.mark.parametrize("case", LAYOUT_CASES)
@pytest.mark.parametrize("gap", ["gap_decomposed", "gap_at_v"])
def test_cert_layout_certificate_matches_the_ell_certificate(gap, case, K):
    """(P, D, gap) on the layout, with y and mask in its row order, equal
    the certificate on the raw shards to 1e-6 of the certificate's scale,
    max(|P|, |D|): D and the gap are differences of sums of that size,
    and the layout adds in another order."""
    sh, yp, mk = _layout_case(case, K)
    lay = jax.jit(lambda s: sp.cert_layout(s, block_rows=16))(sh)
    rng = np.random.default_rng(4)
    alpha = jnp.asarray(rng.random(yp.shape).astype(np.float32)) * yp * mk
    fn = functools.partial(getattr(duality, gap),
                           loss=get_loss("smooth_hinge"), lam=1e-3)
    lead = ()
    if gap == "gap_at_v":
        lead = (duality.v_of_alpha(sh, alpha, 1e-3, jnp.sum(mk))
                + 1e-3 * jnp.asarray(rng.standard_normal(sh.d), jnp.float32),)
    want = fn(*lead, alpha, sh, yp, mk)
    got = jax.jit(fn)(*lead, alpha, lay, *duality.in_rows(lay, yp, mk))
    scale = max(abs(float(want[0])), abs(float(want[1])))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-6 * scale)


def test_cert_layout_walks_only_the_tiles_that_hold_entries():
    """Rows of 1 to 16 slots in blocks of 16 rows and chunks of 8: the
    layout walks one chunk where a block's rows all fit in 8 slots."""
    lengths = np.array([16] * 3 + [8] * 29 + [0] * 32)
    csr, y = _csr_of_lengths(lengths, 64)
    sh, _, _ = sp.partition_sparse(csr, y, 1, seed=0, r_max=16)
    lay = sp.cert_layout(sh, block_rows=16)
    # four blocks of 16 rows: (3 x 16 + 13 x 8), 16 x 8, then empty rows
    assert lay.cols.shape == (1, 4, 2, 8, 16)
    assert int(lay.n_tiles) == 3
    assert [tuple(t) for t in np.asarray(lay.tiles[:3])] == [
        (0, 0), (0, 1), (1, 0)]
    assert int(lay.n_tiles) * lay.tile_slots < lay.cols.size


def test_cert_layout_lowers_the_same_for_every_nnz_histogram():
    """Two data sets of one (K, nk, r_max, d) with different row lengths
    lower the layout's build and the certificate to the same program
    text: only the tile list and its count are data."""
    n, d, r_max, K = 4200, 96, 40, 2      # two blocks of 2,048 rows
    rng = np.random.default_rng(9)
    short = np.minimum(rng.geometric(0.3, n), r_max)
    long_ = np.maximum(r_max - rng.geometric(0.3, n), 1)
    short[0] = long_[0] = r_max
    from repro.core.cocoa import _certificate_rows
    fn = functools.partial(duality.gap_decomposed,
                           loss=get_loss("smooth_hinge"), lam=1e-3)
    texts, tiles = [], []
    for lengths in (short, long_):
        csr, y = _csr_of_lengths(lengths, d)
        sh, yp, mk = sp.partition_sparse(csr, y, K, seed=1, r_max=r_max)
        build = jax.jit(_certificate_rows)
        lay, ys, ms = build(sh, yp, mk)
        alpha = jnp.zeros(yp.shape, jnp.float32)
        texts.append((build.lower(sh, yp, mk).as_text(),
                      jax.jit(fn).lower(alpha, lay, ys, ms).as_text()))
        tiles.append(int(lay.n_tiles))
    assert tiles[0] < tiles[1]
    assert texts[0] == texts[1]


@pytest.mark.parametrize("accel", ["none", "nesterov"])
def test_solve_certifies_the_raw_shards_gap(accel):
    """`solve` on ELL shards certifies through the layout
    (`gap_decomposed`, or `gap_at_v` under momentum): every record's
    (P, D, gap) equals the certificate of the round's state on the raw
    shards."""
    _, _, (sh, yp, mk) = _problem(n=512, d=256, density=0.05, K=4, seed=23)
    cfg = CoCoAConfig.adding(4, loss="smooth_hinge", lam=1e-3, H=64,
                             accel=accel)
    fn = (duality.gap_at_v if accel != "none" else
          lambda v, *a, **kw: duality.gap_decomposed(*a, **kw))
    want = []

    def hook(t, state, gap):
        want.append(fn(state.w, state.alpha, sh, yp, mk,
                       get_loss("smooth_hinge"), 1e-3))
    r = solve(cfg, sh, yp, mk, rounds=4, gap_every=1, seed=3, on_round=hook)
    got = np.stack([r.history["primal"], r.history["dual"],
                    r.history["gap"]], axis=1)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5)


def test_only_primal_and_the_gap_functions_take_a_cert_layout():
    """The functions that read alpha in the shards' own order refuse a
    `CertLayout`, whose passes read it in the layout's."""
    sh, yp, mk = _layout_case("skewed", 2)
    lay = sp.cert_layout(sh, block_rows=16)
    alpha = jnp.zeros(yp.shape, jnp.float32)
    loss = get_loss("smooth_hinge")
    for call in (lambda: duality.v_of_alpha(lay, alpha, 1e-3, 1.0),
                 lambda: duality.w_of_alpha(lay, alpha, 1e-3, 1.0),
                 lambda: duality.dual(alpha, lay, yp, mk, loss, 1e-3),
                 lambda: duality.u_vector(jnp.zeros(sh.d), lay, yp, loss)):
        with pytest.raises(TypeError, match="CertLayout"):
            call()
    assert duality.in_rows(sh, yp, mk) == (yp, mk)


def test_solve_keeps_the_raw_passes_where_the_layout_does_not_fit(
        monkeypatch):
    """On a device with no memory free the certificate reads the raw
    shards, and certifies the same gaps as through the layout."""
    from repro.core import cocoa
    _, _, (sh, yp, mk) = _problem(n=512, d=256, density=0.05, K=4, seed=23)
    cfg = CoCoAConfig.adding(4, loss="smooth_hinge", lam=1e-3, H=64)
    built = []

    class Counted:
        def __init__(self, build):
            self.build, self.eval_shape = build, build.eval_shape

        def __call__(self, *args):
            built.append(1)
            return self.build(*args)

    monkeypatch.setattr(cocoa, "_layout_rows", Counted(cocoa._layout_rows))
    want = solve(cfg, sh, yp, mk, rounds=3, gap_every=1, seed=3).history
    assert built
    built.clear()
    monkeypatch.setattr(cocoa, "_free_bytes", lambda device: 0)
    got = solve(cfg, sh, yp, mk, rounds=3, gap_every=1, seed=3).history
    assert not built
    np.testing.assert_allclose(
        np.asarray([got["primal"], got["dual"], got["gap"]]),
        np.asarray([want["primal"], want["dual"], want["gap"]]), rtol=1e-5)


# ----------------------------------------------------------------------------
# kernel vs oracle: bit-for-bit on every closed-form loss
# ----------------------------------------------------------------------------

def _shard(nk, d, density, seed=0):
    csr, y = sp.make_sparse_classification(nk, d, density=density, seed=seed)
    sh, yp, mk = sp.partition_sparse(csr, y, 1, seed=seed + 1)
    shard = jax.tree.map(lambda a: a[0], sh)
    rng = np.random.default_rng(seed + 2)
    w = jnp.asarray((rng.standard_normal(d) * 0.01).astype(np.float32))
    return shard, yp[0], jnp.zeros(nk), mk[0], w


@pytest.mark.parametrize("loss_name", ["hinge", "smooth_hinge1", "squared",
                                       "absolute"])
@pytest.mark.parametrize("nk,d,br", [(64, 128, 32), (128, 256, 64)])
def test_sparse_kernel_bitexact_vs_oracle(loss_name, nk, d, br):
    loss = get_loss(loss_name)
    shard, y, a, m, w = _shard(nk, d, density=0.08, seed=nk + d)
    scale = 4.0 / (1e-3 * nk)
    da_k, du_k = sparse_local_sdca(shard.cols, shard.vals, y, a, m, w, scale,
                                   loss=loss, n_passes=1, block_rows=br,
                                   interpret=True)
    da_r, du_r = sparse_local_sdca_ref(shard.cols, shard.vals, y, a, m, w,
                                       scale, loss=loss, n_passes=1)
    np.testing.assert_array_equal(np.asarray(da_k), np.asarray(da_r))
    np.testing.assert_array_equal(np.asarray(du_k), np.asarray(du_r))


@pytest.mark.parametrize("depth", [2, 3, 4])
def test_sparse_kernel_pipelined_bitexact_vs_oracle(depth):
    """The pipelined kernel (explicit multi-buffered DMA prefetch ring)
    walks coordinates in the identical order at every buffer_depth, so
    the pure-jnp oracle pins it bit-for-bit -- depth is a pure schedule
    knob, never a results knob."""
    loss = get_loss("hinge")
    shard, y, a, m, w = _shard(128, 256, density=0.08, seed=384)
    scale = 4.0 / (1e-3 * 128)
    da_r, du_r = sparse_local_sdca_ref(shard.cols, shard.vals, y, a, m, w,
                                       scale, loss=loss, n_passes=1)
    da_k, du_k = sparse_local_sdca(shard.cols, shard.vals, y, a, m, w, scale,
                                   loss=loss, n_passes=1, block_rows=32,
                                   buffer_depth=depth, interpret=True)
    np.testing.assert_array_equal(np.asarray(da_k), np.asarray(da_r))
    np.testing.assert_array_equal(np.asarray(du_k), np.asarray(du_r))


@pytest.mark.parametrize("loss_name", ["smooth_hinge1", "squared"])
@pytest.mark.parametrize("br,un,depth", [(32, 1, 2), (64, 2, 2), (128, 1, 4),
                                         (64, 1, 3), (128, 2, 4)])
def test_sparse_kernel_pipelined_config_grid(loss_name, br, un, depth):
    """Every (block_rows, slot_unroll, buffer_depth) launch config --
    including depth > number of blocks and multi-pass wraparound of the
    prefetch ring -- returns bit-for-bit the oracle's answer."""
    loss = get_loss(loss_name)
    shard, y, a, m, w = _shard(128, 128, density=0.1, seed=23)
    scale = 2.0 / (1e-3 * 128)
    da_r, du_r = sparse_local_sdca_ref(shard.cols, shard.vals, y, a, m, w,
                                       scale, loss=loss, n_passes=2)
    da_k, du_k = sparse_local_sdca(shard.cols, shard.vals, y, a, m, w, scale,
                                   loss=loss, n_passes=2, block_rows=br,
                                   slot_unroll=un, buffer_depth=depth,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(da_k), np.asarray(da_r))
    np.testing.assert_array_equal(np.asarray(du_k), np.asarray(du_r))


def test_sparse_kernel_bitexact_multipass():
    loss = get_loss("hinge")
    shard, y, a, m, w = _shard(128, 128, density=0.1, seed=7)
    scale = 2.0 / (1e-3 * 128)
    da_k, du_k = sparse_local_sdca(shard.cols, shard.vals, y, a, m, w, scale,
                                   loss=loss, n_passes=3, block_rows=64,
                                   interpret=True)
    da_r, du_r = sparse_local_sdca_ref(shard.cols, shard.vals, y, a, m, w,
                                       scale, loss=loss, n_passes=3)
    np.testing.assert_array_equal(np.asarray(da_k), np.asarray(da_r))
    np.testing.assert_array_equal(np.asarray(du_k), np.asarray(du_r))


def test_sparse_oracle_matches_dense_oracle():
    """Same rows, sparse vs densified layout: identical math up to fp
    reduction order."""
    loss = get_loss("hinge")
    shard, y, a, m, w = _shard(96, 64, density=0.15, seed=11)
    Xd = sp.densify(shard)
    scale = 4.0 / (1e-3 * 96)
    da_s, du_s = sparse_local_sdca_ref(shard.cols, shard.vals, y, a, m, w,
                                       scale, loss=loss, n_passes=1)
    da_d, du_d = local_sdca_ref(Xd, y, a, m, w, scale, loss=loss, n_passes=1)
    np.testing.assert_allclose(np.asarray(da_s), np.asarray(da_d),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(du_s), np.asarray(du_d),
                               rtol=2e-4, atol=2e-5)


def test_sparse_kernel_masked_rows_are_noops():
    loss = get_loss("hinge")
    shard, y, a, m, w = _shard(64, 64, density=0.1, seed=13)
    m = m.at[-9:].set(0.0)
    scale = 2.0 / (1e-3 * 55)
    da_k, _ = sparse_local_sdca(shard.cols, shard.vals, y, a, m, w, scale,
                                loss=loss, n_passes=1, block_rows=32,
                                interpret=True)
    assert float(jnp.max(jnp.abs(da_k[-9:]))) == 0.0


def test_sparse_kernel_rejects_logistic():
    shard, y, a, m, w = _shard(32, 32, density=0.2, seed=1)
    with pytest.raises(ValueError):
        sparse_local_sdca(shard.cols, shard.vals, y, a, m, w, 1.0,
                          loss=get_loss("logistic"), interpret=True)


def test_sparse_ops_wrapper_solver_interface():
    """sparse_local_sdca_block: permutation + padding + SDCAResult contract
    (du == scale * A^T dalpha) on non-aligned shapes."""
    loss = get_loss("hinge")
    shard, y, a, m, w = _shard(100, 130, density=0.1, seed=17)
    res = sparse_local_sdca_block(shard, y, a, m, w, jax.random.PRNGKey(0),
                                  loss, 1e-3, 100.0, 4.0, 200, interpret=True)
    assert res.dalpha.shape == (100,)
    assert res.du.shape == (130,)
    scale = 4.0 / (1e-3 * 100)
    Xd = np.asarray(sp.densify(shard))
    ref = scale * (Xd.T @ np.asarray(res.dalpha))
    np.testing.assert_allclose(np.asarray(res.du), ref, rtol=2e-4, atol=1e-4)


def test_sparse_vmem_budget_production_shape():
    vm = vmem_budget(nk=16384, d=47236, r_max=128)    # rcv1-scale shard
    assert vm["fits_16mb"]
    assert vm["dense_tile_mb"] > 10 * vm["total_mb"]  # the point of the kernel
    # multi-buffering scales only the cols/vals tile ring, which lives in
    # SMEM: depth 1 is double-buffered by the implicit pipeline, so depth
    # 2 prices the same and depth 4 twice; the VMEM side (u, dalpha) does
    # not move, and the rcv1-scale shard still fits quad-buffered
    vm2 = vmem_budget(nk=16384, d=47236, r_max=128, buffer_depth=2)
    vm4 = vmem_budget(nk=16384, d=47236, r_max=128, buffer_depth=4)
    assert vm2["buffer_depth"] == 2 and vm2["fits_16mb"]
    assert vm4["fits_16mb"]
    assert vm2["ell_tile_kb"] == pytest.approx(vm["ell_tile_kb"])
    assert vm4["ell_tile_kb"] == pytest.approx(2 * vm["ell_tile_kb"])
    assert vm4["smem_kb"] - vm["smem_kb"] \
        == pytest.approx(vm["ell_tile_kb"])
    assert vm4["total_mb"] == vm["total_mb"]


# ----------------------------------------------------------------------------
# solvers + end-to-end CoCoA+ parity
# ----------------------------------------------------------------------------

def test_sparse_jnp_solver_matches_dense_solver():
    """local_sdca_sparse visits the same coordinates (same rng) as the dense
    local_sdca on the densified shard -> same updates up to fp order."""
    loss = get_loss("smooth_hinge1")
    shard, y, a, m, w = _shard(128, 64, density=0.1, seed=19)
    Xd = sp.densify(shard)
    rng = jax.random.PRNGKey(4)
    rs = local_sdca_sparse(shard, y, a, m, w, rng, loss, 1e-3, 128.0, 4.0, 256)
    rd = local_sdca(Xd, y, a, m, w, rng, loss, 1e-3, 128.0, 4.0, 256)
    np.testing.assert_allclose(np.asarray(rs.dalpha), np.asarray(rd.dalpha),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(rs.du), np.asarray(rd.du),
                               rtol=2e-4, atol=2e-5)


# ----------------------------------------------------------------------------
# the ELL width the vmap backend runs the jnp solver at
# ----------------------------------------------------------------------------

def _shard_at_width(width, nk=128, d=256, density=0.1, seed=23, K=1):
    """ELL shards whose slot axis is `width` wide (K workers; K=1 gives one
    worker's shard without the leading axis)."""
    csr, y = sp.make_sparse_classification(nk * K, d, density=density,
                                           seed=seed)
    sh, yp, mk = sp.partition_sparse(csr, y, K, seed=seed + 1, r_max=width)
    assert sh.cols.shape == (K, nk, width)
    if K > 1:
        return sh, yp, mk
    rng = np.random.default_rng(seed + 2)
    w = jnp.asarray((rng.standard_normal(d) * 0.01).astype(np.float32))
    shard = jax.tree.map(lambda a: a[0], sh)
    return shard, yp[0], jnp.zeros(nk), mk[0], w


@pytest.mark.parametrize("r_max,workers,width", [
    (122, 8, 128), (128, 8, 128), (129, 8, 129), (256, 8, 256),
    (42, 8, 128), (36, 8, 36), (122, 4, 256), (63, 16, 64),
    (122, 16, 122), (122, 1, 122), (122, 2, 122), (200, 2, 512)])
def test_ell_width_reaches_the_flat_scatter(r_max, workers, width):
    """K * width reaches the 1024 updates at which XLA flattens the batched
    scatter-add, unless that takes more than 3.5 times the slots; one
    worker runs as it is."""
    assert ell_width(r_max, workers) == width


def test_widened_shard_adds_padding_slots():
    shard, *_ = _shard_at_width(122)
    wide = shard.widened(128)
    assert wide.cols.shape == wide.vals.shape == (128, 128)
    np.testing.assert_array_equal(np.asarray(wide.cols[:, :122]),
                                  np.asarray(shard.cols))
    np.testing.assert_array_equal(np.asarray(wide.vals[:, :122]),
                                  np.asarray(shard.vals))
    # the added slots are the ELL padding slot: column 0, value 0.0
    assert not np.any(np.asarray(wide.cols[:, 122:]))
    assert not np.any(np.asarray(wide.vals[:, 122:]))
    assert wide.nnz is shard.nnz and wide.d == shard.d
    assert shard.widened(122) is shard
    with pytest.raises(ValueError, match="cannot widen"):
        shard.widened(121)


@pytest.mark.parametrize("loss_name", ["hinge", "smooth_hinge1", "squared"])
def test_sparse_solver_widened_shard_matches_unpadded(loss_name):
    """A width-122 shard widened to 128 gives what it gives at 122, to
    float32 rounding: the six added slots per row are no-ops."""
    loss = get_loss(loss_name)
    shard, y, a, m, w = _shard_at_width(122)
    rng = jax.random.PRNGKey(5)
    # the row norms of the rows as given, as the vmap backend passes them
    sq = jnp.sum(shard.vals * shard.vals, axis=-1) * m
    wide = local_sdca_sparse(shard.widened(128), y, a, m, w, rng, loss,
                             1e-3, 128.0, 4.0, 512, sqnorms=sq)
    res = local_sdca_sparse(shard, y, a, m, w, rng, loss, 1e-3, 128.0, 4.0,
                            512)
    assert float(jnp.max(jnp.abs(res.dalpha))) > 0
    np.testing.assert_allclose(np.asarray(wide.dalpha),
                               np.asarray(res.dalpha), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(wide.du), np.asarray(res.du),
                               rtol=1e-6)


def test_sparse_solver_masked_rows_are_noops_at_widened_width():
    """Masked rows move nothing at the widened width: their dalpha stays
    exactly 0, and what they hold does not reach the result."""
    loss = get_loss("smooth_hinge1")
    shard, y, a, m, w = _shard_at_width(122)
    m = m.at[-40:].set(0.0)
    noisy = sp.SparseShards(
        shard.cols, shard.vals.at[-40:].set(7.0), shard.nnz, d=shard.d)
    rng = jax.random.PRNGKey(6)
    r1 = local_sdca_sparse(shard.widened(128), y, a, m, w, rng, loss, 1e-3,
                           128.0, 4.0, 512)
    r2 = local_sdca_sparse(noisy.widened(128), y, a, m, w, rng, loss, 1e-3,
                           128.0, 4.0, 512)
    assert float(jnp.max(jnp.abs(r1.dalpha[-40:]))) == 0.0
    assert float(jnp.max(jnp.abs(r1.dalpha))) > 0
    np.testing.assert_array_equal(np.asarray(r1.dalpha), np.asarray(r2.dalpha))
    np.testing.assert_array_equal(np.asarray(r1.du), np.asarray(r2.du))


@pytest.mark.parametrize("width", [122, 128])
def test_vmap_round_widens_only_short_rows(width, monkeypatch):
    """K = 8 workers: rows of 122 slots run at 128 and agree with the same
    solver at 122 to float32 rounding; rows of 128 slots run as they are,
    bit for bit. The reference is local_sdca_sparse registered under
    another solver, which the backend never widens."""
    from repro.core import cocoa, solvers
    as_given = solvers.LocalSolver(
        "sdca_sparse_as_given",
        lambda *a, **kw: solvers.local_sdca_sparse(*a, **kw),
        dense=False, sparse=True, model_axis=True, sqnorms=True)
    monkeypatch.setitem(solvers.SOLVERS, as_given.name, as_given)
    X, y, mask = _shard_at_width(width, nk=32, K=8)
    state0 = cocoa.init_state(X.d, 8, 32, 3, jnp.float32)
    out = {}
    for name in ("sdca", as_given.name):
        cfg = CoCoAConfig.adding(8, loss="smooth_hinge", lam=1e-3, H=64,
                                 solver=name)
        round_fn = cocoa.make_round_vmap(cfg, 8)
        jaxpr = str(jax.make_jaxpr(round_fn)(state0, X, y, mask))
        out[name] = (jax.jit(round_fn)(state0, X, y, mask), " pad[" in jaxpr)
    (ours, widened), (ref, ref_widened) = out["sdca"], out[as_given.name]
    assert widened == (width == 122) and not ref_widened
    assert float(jnp.max(jnp.abs(ref.alpha))) > 0
    for a, b in ((ours.alpha, ref.alpha), (ours.w, ref.w)):
        if width == 128:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-9)


def test_feature_sharded_unaligned_width_matches_vmap():
    """On a (2, 2) CPU mesh the feature-sharded solve runs its shard-local
    ELL slices as they are (widths not lane multiples, unbatched) and
    matches the vmap reference, which widens its 200 slots to 512, to the
    mesh tests' 1e-6."""
    code = """
        import jax, jax.numpy as jnp
        from repro.core import CoCoAConfig, solve
        from repro.core.solvers import ell_width
        from repro.data import load
        from repro.data.sparse import partition_sparse
        csr, y = load("tiny_sparse")
        sh, yp, mk = partition_sparse(csr, y, 2, seed=0, r_max=200)
        fs, _, _ = partition_sparse(csr, y, 2, seed=0, M=2)
        assert ell_width(sh.r_max, 2) == 512 and fs.r_loc % 128
        kw = dict(loss="smooth_hinge", lam=1e-3, H=128)
        rv = solve(CoCoAConfig.adding(2, **kw), sh, yp, mk, rounds=3,
                   gap_every=1)
        rs = solve(CoCoAConfig.adding(2, backend="shard_map",
                                      model_axis="model", **kw),
                   fs, yp, mk, rounds=3, gap_every=1,
                   mesh=jax.make_mesh((2, 2), ("data", "model")))
        w_err = float(jnp.max(jnp.abs(rs.state.w[:sh.d] - rv.state.w)))
        a_err = float(jnp.max(jnp.abs(rs.state.alpha - rv.state.alpha)))
        assert w_err < 1e-6 and a_err < 1e-6, (w_err, a_err)
        assert float(jnp.max(jnp.abs(rv.state.alpha))) > 0
        print("UNALIGNED FEATURE-SHARDED OK")
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))), "src"))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "UNALIGNED FEATURE-SHARDED OK" in p.stdout


@pytest.mark.parametrize("solver", ["sdca", "sdca_kernel"])
def test_cocoa_sparse_matches_densified_run(solver):
    """Acceptance: CoCoA+ on sparse shards reaches the same duality gap per
    round as the equivalent densified run (identical rng stream)."""
    _, _, (sh, yp, mk) = _problem(n=512, d=256, density=0.05, K=4, seed=23)
    Xd = sp.densify(sh)
    cfg = CoCoAConfig.adding(4, loss="hinge", lam=1e-3, H=256, solver=solver)
    rs = solve(cfg, sh, yp, mk, rounds=5, gap_every=1, seed=3)
    rd = solve(cfg, Xd, yp, mk, rounds=5, gap_every=1, seed=3)
    assert rs.history["round"] == rd.history["round"]
    np.testing.assert_allclose(rs.history["gap"], rd.history["gap"],
                               rtol=1e-4, atol=1e-5)
    assert rs.history["gap"][-1] < rs.history["gap"][0]    # actually converges


def test_cocoa_sparse_rejects_solver_without_sparse_path():
    _, _, (sh, yp, mk) = _problem(seed=29)
    cfg = CoCoAConfig.adding(4, loss="smooth_hinge1", lam=1e-3, H=32,
                             solver="gd")
    with pytest.raises(ValueError, match="no sparse path"):
        solve(cfg, sh, yp, mk, rounds=1)


def test_cocoa_sparse_comm_floats_accounting():
    _, _, (sh, yp, mk) = _problem(seed=31)
    cfg = CoCoAConfig.adding(4, loss="hinge", lam=1e-3, H=64)
    r = solve(cfg, sh, yp, mk, rounds=3, gap_every=1)
    K, d = 4, sh.d
    assert r.history["comm_floats"] == [K * d, 2 * K * d, 3 * K * d]
    assert r.history["comm_vectors"] == [K, 2 * K, 3 * K]


# ----------------------------------------------------------------------------
# fused in-kernel prox (prox_kappa) + z-exchange schedule
# ----------------------------------------------------------------------------

def _kappa(reg_spec, lam=1e-3):
    from repro.core import get_regularizer
    from repro.kernels.ops import _prox_kappa_of
    return _prox_kappa_of(get_regularizer(reg_spec), lam)


def test_prox_kappa_resolution():
    """kappa=0 (L2) and regularizers without the scalar-threshold form
    resolve to None -- the not-fused hoisted-map path; elastic / smoothed
    L1 resolve to their scaled-frame thresholds."""
    from dataclasses import replace

    from repro.core import get_regularizer
    from repro.kernels.ops import _prox_kappa_of
    assert _kappa("l2") is None
    assert _kappa("elastic:0.5") == pytest.approx(1.0)
    assert _kappa("l1s:0.01") == pytest.approx(0.1)        # lam/eps
    legacy = replace(get_regularizer("elastic:0.5"), prox_kappa=None)
    assert _prox_kappa_of(legacy, 1e-3) is None


@pytest.mark.parametrize("reg_spec", ["elastic:0.5", "l1s:0.01"])
@pytest.mark.parametrize("br,un,depth", [(32, 1, 1), (64, 2, 2),
                                         (128, 1, 4)])
def test_sparse_kernel_fused_prox_bitexact_vs_oracle(reg_spec, br, un,
                                                     depth):
    """The conjugate map fused into the kernel -- the scalar
    soft-threshold applied to each gathered u entry -- against the
    prox-aware jnp oracle replaying the identical op order: bitwise at
    every launch config, multi-pass, exactly like the L2 grid."""
    loss = get_loss("smooth_hinge1")
    shard, y, a, m, w = _shard(128, 128, density=0.1, seed=37)
    kap = _kappa(reg_spec)
    scale = 2.0 / (1e-3 * 128)
    da_r, du_r = sparse_local_sdca_ref(shard.cols, shard.vals, y, a, m, w,
                                       scale, loss=loss, n_passes=2,
                                       prox_kappa=kap)
    da_k, du_k = sparse_local_sdca(shard.cols, shard.vals, y, a, m, w,
                                   scale, loss=loss, n_passes=2,
                                   block_rows=br, slot_unroll=un,
                                   buffer_depth=depth, prox_kappa=kap,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(da_k), np.asarray(da_r))
    np.testing.assert_array_equal(np.asarray(du_k), np.asarray(du_r))


def test_sparse_dispatch_l2_not_fused_elastic_fused():
    """reg='l2' must NOT fuse (kappa 0 == identity map): the dispatch
    reports prox_fused=False and returns byte-identical results to a
    reg-less call -- the PR-8 L2 jaxpr is untouched. An elastic reg on
    the same inputs reports prox_fused=True."""
    from repro.core import get_regularizer
    from repro.kernels import ops
    loss = get_loss("hinge")
    shard, y, a, m, w = _shard(100, 130, density=0.1, seed=41)
    args = (shard, y, a, m, w, jax.random.PRNGKey(0), loss, 1e-3, 100.0,
            4.0, 200)
    r_plain = sparse_local_sdca_block(*args, interpret=True)
    r_l2 = sparse_local_sdca_block(*args, interpret=True,
                                   reg=get_regularizer("l2"))
    assert ops.LAST_SPARSE_CONFIG["prox_fused"] is False
    assert ops.LAST_SPARSE_CONFIG["model_shards"] == 1
    assert ops.LAST_SPARSE_CONFIG["zx"] is False
    np.testing.assert_array_equal(np.asarray(r_l2.dalpha),
                                  np.asarray(r_plain.dalpha))
    np.testing.assert_array_equal(np.asarray(r_l2.du),
                                  np.asarray(r_plain.du))
    sparse_local_sdca_block(*args, interpret=True,
                            reg=get_regularizer("elastic:0.5"))
    assert ops.LAST_SPARSE_CONFIG["prox_fused"] is True


def test_cocoa_fused_prox_rounds_to_gap_regression():
    """Acceptance: the fused-prox kernel path reaches gap <= 1e-4 on
    elastic-net tiny_sparse in at most 1.25x the jnp solver's rounds --
    the old hoisted-map path needed ~3x. Both runs share the rng stream,
    and both gaps are certified at the carried v (duality.gap_at_v
    inside solve's gap evaluation)."""
    from repro.data.synthetic import load

    csr, y = load("tiny_sparse")
    sh, yp, mk = sp.partition_sparse(csr, y, 4, seed=0)
    eps = 1e-4
    rounds = dict()
    for solver in ("sdca", "sdca_kernel"):
        cfg = CoCoAConfig.adding(4, loss="smooth_hinge", lam=1e-3, H=256,
                                 solver=solver, reg="elastic:0.5")
        r = solve(cfg, sh, yp, mk, rounds=64, eps_gap=eps, gap_every=1,
                  seed=5)
        assert r.history["gap"][-1] <= eps, (solver, r.history["gap"])
        rounds[solver] = r.history["round"][-1]
    assert rounds["sdca_kernel"] <= 1.25 * rounds["sdca"] + 1, rounds


def test_sparse_zx_block1_bitexact_vs_fused_sequential():
    """The z-exchange schedule at block_rows=1 *is* sequential SDCA --
    every row's z is exchanged fresh, the staleness window is empty --
    so it must reproduce the fused sequential kernel bit for bit. This
    anchors the schedule's arithmetic: only the staleness (block_rows >
    1) may ever change a result, never the exchange plumbing."""
    from repro.kernels.sparse_sdca import sparse_local_sdca_zx
    loss = get_loss("hinge")
    shard, y, a, m, w = _shard(48, 96, density=0.1, seed=43)
    kap = _kappa("elastic:0.5")
    scale = 4.0 / (1e-3 * 48)
    sq = jnp.sum(shard.vals * shard.vals, axis=1)
    da_z, du_z = sparse_local_sdca_zx(shard.cols, shard.vals, y, a, m, w,
                                      scale, sq, loss=loss, n_passes=2,
                                      block_rows=1, prox_kappa=kap,
                                      interpret=True)
    da_s, du_s = sparse_local_sdca(shard.cols, shard.vals, y, a, m, w,
                                   scale, loss=loss, n_passes=2,
                                   block_rows=1, prox_kappa=kap,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(da_z), np.asarray(da_s))
    np.testing.assert_array_equal(np.asarray(du_z), np.asarray(du_s))


def test_sparse_zx_multiblock_keeps_du_contract():
    """At block_rows > 1 the schedule runs each block against a stale z
    (the Theta knob) -- the trajectory may differ from sequential SDCA,
    but du == scale * A^T dalpha must hold exactly as for every other
    solver path (the scatter updates raw u through the same axpy)."""
    from repro.kernels.sparse_sdca import sparse_local_sdca_zx
    loss = get_loss("smooth_hinge1")
    shard, y, a, m, w = _shard(96, 64, density=0.15, seed=47)
    scale = 4.0 / (1e-3 * 96)
    sq = jnp.sum(shard.vals * shard.vals, axis=1)
    da, du = sparse_local_sdca_zx(shard.cols, shard.vals, y, a, m, w,
                                  scale, sq, loss=loss, n_passes=1,
                                  block_rows=16, prox_kappa=None,
                                  interpret=True)
    Xd = np.asarray(sp.densify(shard))
    ref = scale * (Xd.T @ np.asarray(da))
    np.testing.assert_allclose(np.asarray(du), ref, rtol=2e-4, atol=1e-4)
    assert float(jnp.max(jnp.abs(da))) > 0.0


def test_sparse_zx_dispatch_forced_single_shard():
    """zx=True forces the z-exchange schedule without a mesh (the bench
    path); the dispatch reports it and the SDCAResult contract holds.
    zx=False under a model_axis is invalid."""
    from repro.kernels import ops
    loss = get_loss("hinge")
    shard, y, a, m, w = _shard(100, 130, density=0.1, seed=53)
    res = sparse_local_sdca_block(shard, y, a, m, w, jax.random.PRNGKey(0),
                                  loss, 1e-3, 100.0, 4.0, 200,
                                  interpret=True, zx=True)
    assert ops.LAST_SPARSE_CONFIG["zx"] is True
    assert ops.LAST_SPARSE_CONFIG["model_shards"] == 1
    scale = 4.0 / (1e-3 * 100)
    Xd = np.asarray(sp.densify(shard))
    ref = scale * (Xd.T @ np.asarray(res.dalpha))
    np.testing.assert_allclose(np.asarray(res.du), ref, rtol=2e-4,
                               atol=1e-4)
    with pytest.raises(ValueError, match="zx=False"):
        sparse_local_sdca_block(shard, y, a, m, w, jax.random.PRNGKey(0),
                                loss, 1e-3, 100.0, 4.0, 200,
                                interpret=True, model_axis="model",
                                zx=False)


def test_sparse_zx_exchanges_and_vmem_pricing():
    """zx wire arithmetic (n_passes * blocks + 1 prologue) and the
    priced z-exchange buffer / scratch in vmem_budget; the zx working
    set is block-sized, not shard-sized, so production shapes that fit
    sequentially fit the schedule with room to spare."""
    from repro.kernels.sparse_sdca import zx_exchanges
    assert zx_exchanges(128, 16) == 9                  # 8 blocks + prologue
    assert zx_exchanges(128, 16, n_passes=3) == 25
    vm = vmem_budget(nk=16384, d=47236, r_max=128, block_rows=16,
                     model_shards=2)
    assert vm["zx"] is True and vm["model_shards"] == 2
    assert vm["zx_exchange_kb"] == pytest.approx(16 * 4 / 1024)
    assert vm["fits_16mb"]
    vm1 = vmem_budget(nk=16384, d=47236, r_max=128)
    assert vm1["zx"] is False and vm1["zx_exchange_kb"] == 0.0
    assert vm1["prox_fused"] is False


def test_sparse_vmem_rejection():
    """Over-budget configs are rejected at dispatch, not silently
    launched: the priced working set names the limit it exceeds, and an
    explicit vmem_limit_mb raises the ceiling."""
    loss = get_loss("hinge")
    cols = jnp.zeros((1024, 64), jnp.int32)
    vals = jnp.zeros((1024, 64))
    one = jnp.ones(1024)
    w_big = jnp.zeros(2_000_000)
    with pytest.raises(ValueError, match="exceeds"):
        sparse_local_sdca(cols, vals, one, jnp.zeros(1024), one, w_big,
                          1.0, loss=loss, block_rows=128, buffer_depth=4,
                          interpret=True)
    # same config under a raised explicit limit prices fine
    from repro.kernels.sparse_sdca import _enforce_vmem
    b = vmem_budget(nk=1024, d=2_000_000, r_max=64, block_rows=128,
                    buffer_depth=4)
    _enforce_vmem(b, 64, where="test")                  # no raise
    with pytest.raises(ValueError, match="test"):
        _enforce_vmem(b, 16, where="test")
    # a tile ring over the SMEM budget is rejected whatever the VMEM limit
    big = vmem_budget(nk=1024, d=1024, r_max=1024, block_rows=1024,
                      buffer_depth=4)
    with pytest.raises(ValueError, match="SMEM"):
        _enforce_vmem(big, 64, where="test")


# ----------------------------------------------------------------------------
# streaming shard ingest: chunks -> per-shard FeatureShards, no global array
# ----------------------------------------------------------------------------

def _csr_to_libsvm_lines(csr, y):
    """Render (CSRMatrix, labels) back to 1-based LIBSVM text lines."""
    lines = []
    for i in range(csr.shape[0]):
        lo, hi = csr.indptr[i], csr.indptr[i + 1]
        # .9g: float32 round-trips exactly through 9 significant digits
        toks = " ".join(f"{int(c) + 1}:{v:.9g}"
                        for c, v in zip(csr.indices[lo:hi], csr.data[lo:hi]))
        lines.append(f"{y[i]:g} {toks}".rstrip())
    return lines


def _materialized_roundrobin(csr, y, K, M):
    """The materialized reference for the streaming path: deal rows
    round-robin (row j -> worker j % K), pad per worker, then route through
    the existing csr_to_ell -> SparseShards -> shard_features pipeline
    (which does build the host-side full-width ELL the streaming path
    avoids)."""
    n, d = csr.shape
    cols_e, vals_e, nnz_e = sp.csr_to_ell(csr)
    nk = -(-n // K)
    rm = cols_e.shape[1]
    cols = np.zeros((K, nk, rm), np.int32)
    vals = np.zeros((K, nk, rm), np.float32)
    nnz = np.zeros((K, nk), np.int32)
    yp = np.zeros((K, nk), np.float32)
    mask = np.zeros((K, nk), np.float32)
    for k in range(K):
        rows = np.arange(k, n, K)
        cols[k, :len(rows)] = cols_e[rows]
        vals[k, :len(rows)] = vals_e[rows]
        nnz[k, :len(rows)] = nnz_e[rows]
        yp[k, :len(rows)] = np.asarray(y)[rows]
        mask[k, :len(rows)] = 1.0
    sh = sp.SparseShards(jnp.asarray(cols), jnp.asarray(vals),
                         jnp.asarray(nnz), d=d)
    return sp.shard_features(sh, M), jnp.asarray(yp), jnp.asarray(mask)


@pytest.mark.parametrize("K,M", [(3, 2), (4, 1), (2, 4)])
def test_shard_features_streaming_equals_materialized(K, M):
    """The ROADMAP ingest follow-up, reduced scope: streaming chunked
    LIBSVM text straight into per-shard FeatureShards blocks produces
    exactly what the materialized partition + shard_features path builds
    for the same row assignment -- on tiny_sparse, leaf for leaf (the
    streaming side never holds a full-width global array; equality is up
    to the per-slice ELL width, which both sides derive as the max live
    slice length)."""
    from repro.data.synthetic import DATASETS
    spec = DATASETS["tiny_sparse"]
    csr, y = sp.make_sparse_classification(spec.n, spec.d,
                                           density=spec.density, seed=0)
    lines = _csr_to_libsvm_lines(csr, y)
    chunks = sp.iter_libsvm_chunks(iter(lines), chunk_rows=97,
                                   n_features=csr.shape[1])
    fs, yp, mk = sp.shard_features_streaming(chunks, K, M)
    ref, yr, mr = _materialized_roundrobin(csr, y, K, M)
    assert fs.d == ref.d and fs.M == ref.M and fs.d_local == ref.d_local
    assert fs.r_loc == ref.r_loc
    np.testing.assert_array_equal(np.asarray(fs.nnz), np.asarray(ref.nnz))
    np.testing.assert_array_equal(np.asarray(fs.cols), np.asarray(ref.cols))
    np.testing.assert_allclose(np.asarray(fs.vals), np.asarray(ref.vals),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))


def test_shard_features_streaming_solves_on_mesh_shapes():
    """The streamed shards are drop-in FeatureShards: duality certificates
    evaluate identically to the materialized layout (the matvec family
    only sees the pytree)."""
    csr, y = sp.make_sparse_classification(96, 40, density=0.15, seed=2)
    chunks = sp.iter_libsvm_chunks(iter(_csr_to_libsvm_lines(csr, y)),
                                   chunk_rows=10, n_features=40)
    fs, yp, mk = sp.shard_features_streaming(chunks, K=2, M=2)
    loss = get_loss("hinge")
    rng = np.random.default_rng(1)
    alpha = jnp.asarray((np.asarray(yp) * rng.random(yp.shape)
                         * np.asarray(mk)).astype(np.float32))
    ref, yr, mr = _materialized_roundrobin(csr, y, 2, 2)
    g1 = float(duality.duality_gap(alpha, fs, yp, mk, loss, 1e-3))
    g2 = float(duality.duality_gap(alpha, ref, yr, mr, loss, 1e-3))
    assert abs(g1 - g2) < 1e-5
    assert g1 >= -1e-5


def test_shard_features_streaming_guards():
    csr, y = sp.make_sparse_classification(8, 10, density=0.3, seed=3)
    with pytest.raises(ValueError, match="n_features"):
        sp.shard_features_streaming(iter([]), K=2, M=1)
    with pytest.raises(ValueError, match="empty stream"):
        # width known but zero rows: refuse rather than emit a phantom
        # all-masked shard that certifies NaN gaps
        sp.shard_features_streaming(iter([]), K=2, M=1, n_features=10)
    with pytest.raises(ValueError, match="exceeds"):
        wide, yw = sp.make_sparse_classification(4, 20, density=0.3, seed=4)
        sp.shard_features_streaming(iter([(csr, y), (wide, yw)]), K=2, M=1)
    with pytest.raises(ValueError):
        sp.shard_features_streaming(iter([(csr, y)]), K=0, M=1)
