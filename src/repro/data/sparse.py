"""Sparse data subsystem: CSR on the host, padded-ELL on the device.

The paper's headline datasets (rcv1, news20, url, webspam) have densities
0.0003-0.16, so storing them dense moves 10-100x more bytes per SDCA step
than necessary. This module provides the sparse pipeline end to end:

  * `CSRMatrix` -- a scipy-free host-side CSR triple (data, indices, indptr)
    produced by `load_libsvm` (LIBSVM text format) or the synthetic
    generators (`make_sparse_classification`).
  * `csr_to_ell` / `ell_to_csr` -- conversion to/from the padded-ELL layout
    `(n, r_max)` of (col_idx, value) pairs. Padding entries are (col 0,
    val 0.0), which makes every gather/scatter an exact arithmetic no-op:
    gather contributes u[0] * 0, scatter adds 0 to u[0].
  * `SparseShards` -- the device container mirroring the dense `(K, nk, d)`
    partition contract: `cols`/`vals` are `(K, nk, r_max)`, `nnz` holds the
    true per-row entry count, `d` is static metadata. Registered as a JAX
    pytree so it flows through jit / vmap unchanged (vmap over the leading
    K axis yields per-worker shards).
  * `partition_sparse` -- worker partitioner with the same shuffle, padding
    and mask semantics as `data.synthetic.partition` (shared `split_order`).
  * `CertLayout` / `cert_layout` -- the certificate's copy of each
    worker's rows, sorted by length and cut into fixed tiles, so its two
    passes walk only the tiles that hold an entry.
  * `matvec` / `rmatvec` / `row_sqnorms` / `densify` -- the sparse matvec
    family used by `core.duality` for gap certificates and by tests.
"""
from __future__ import annotations

import dataclasses
import functools
import pathlib
from typing import Iterable, NamedTuple, Optional, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from .synthetic import split_order


# ----------------------------------------------------------------------------
# Host-side CSR + LIBSVM parser
# ----------------------------------------------------------------------------

class CSRMatrix(NamedTuple):
    """Compressed sparse rows: row i owns indices[indptr[i]:indptr[i+1]]."""
    data: np.ndarray       # (nnz,) float32
    indices: np.ndarray    # (nnz,) int32, column ids, sorted within a row
    indptr: np.ndarray     # (n + 1,) int64
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    @property
    def density(self) -> float:
        n, d = self.shape
        return self.nnz / max(n * d, 1)

    def row_nnz(self) -> np.ndarray:
        return np.diff(self.indptr).astype(np.int32)

    def toarray(self) -> np.ndarray:
        n, d = self.shape
        out = np.zeros((n, d), np.float32)
        rows = np.repeat(np.arange(n), self.row_nnz())
        # accumulate, don't assign: duplicate (row, col) entries must agree
        # with the device path (densify/matvec sum them)
        np.add.at(out, (rows, self.indices), self.data)
        return out


def _iter_source_lines(source: Union[str, pathlib.Path, Iterable[str]]
                       ) -> Iterable[str]:
    """Lazily yield lines: a path streams through open() (never holding the
    file in memory -- url/webspam-sized inputs), an iterable passes through."""
    if isinstance(source, (str, pathlib.Path)):
        with open(source, "r") as f:
            yield from f
    else:
        yield from source


def iter_libsvm_chunks(source: Union[str, pathlib.Path, Iterable[str]], *,
                       chunk_rows: int,
                       n_features: Optional[int] = None,
                       zero_based: bool = False
                       ) -> Iterable[Tuple[CSRMatrix, np.ndarray]]:
    """Stream LIBSVM text as (CSRMatrix, labels) blocks of <= chunk_rows rows.

    Memory stays O(chunk nnz) regardless of file size -- the ingest path for
    datasets that don't fit as one parse (ROADMAP real-dataset item). Pass
    `n_features` for a stable column count across chunks; without it each
    chunk's width is its own max index + 1 (`load_libsvm` widens to the
    global max when it stitches chunks back together).
    """
    if chunk_rows < 1:
        raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
    off = 0 if zero_based else 1
    labels, data, indices, indptr = [], [], [], [0]
    row_no = 0   # global data-row count, for error messages across chunks

    def flush():
        top = int(max(indices)) + 1 if indices else 0
        d = n_features if n_features is not None else top
        if top > d:
            # reject here: the jnp gather path would silently clamp the index
            raise ValueError(f"feature index {top - 1} out of range for "
                             f"n_features={d}")
        csr = CSRMatrix(np.asarray(data, np.float32),
                        np.asarray(indices, np.int32),
                        np.asarray(indptr, np.int64),
                        (len(labels), d))
        return csr, np.asarray(labels, np.float32)

    for line in _iter_source_lines(source):
        line = line.split("#", 1)[0].strip()
        if not line:
            continue
        parts = line.split()
        labels.append(float(parts[0]))
        row_no += 1
        row = []
        for tok in parts[1:]:
            i, v = tok.split(":")
            idx = int(i) - off
            if idx < 0:
                raise ValueError(f"negative feature index in {tok!r} "
                                 f"(zero_based={zero_based})")
            row.append((idx, float(v)))
        row.sort()
        for (a, _), (b, _) in zip(row, row[1:]):
            if a == b:
                raise ValueError(f"duplicate feature index {a + off} on "
                                 f"line {row_no}")
        indices.extend(i for i, _ in row)
        data.extend(v for _, v in row)
        indptr.append(len(indices))
        if len(labels) == chunk_rows:
            yield flush()
            labels, data, indices, indptr = [], [], [], [0]
    if labels or row_no == 0:     # trailing partial chunk, or empty input
        yield flush()


def csr_vstack(blocks: Iterable[CSRMatrix],
               d: Optional[int] = None) -> CSRMatrix:
    """Stack CSR blocks row-wise. `d` defaults to the widest block (chunked
    parses without n_features infer width per chunk)."""
    blocks = list(blocks)
    if not blocks:
        raise ValueError("csr_vstack needs at least one block")
    d = max(b.shape[1] for b in blocks) if d is None else d
    for b in blocks:
        if b.shape[1] > d:
            raise ValueError(f"block width {b.shape[1]} exceeds d={d}")
    indptr = [np.asarray([0], np.int64)]
    base = 0
    for b in blocks:
        indptr.append(b.indptr[1:] + base)
        base += b.nnz
    return CSRMatrix(np.concatenate([b.data for b in blocks]),
                     np.concatenate([b.indices for b in blocks]),
                     np.concatenate(indptr),
                     (sum(b.shape[0] for b in blocks), d))


def load_libsvm(source: Union[str, pathlib.Path, Iterable[str]], *,
                n_features: Optional[int] = None,
                zero_based: bool = False,
                chunk_rows: Optional[int] = None
                ) -> Tuple[CSRMatrix, np.ndarray]:
    """Parse LIBSVM-format text: ``<label> <idx>:<val> <idx>:<val> ...``.

    `source` is a path or an iterable of lines. Indices are 1-based by
    default (the LIBSVM convention); '#' starts a comment. Columns are
    sorted within each row. Returns (CSRMatrix, labels float32).

    `chunk_rows` streams the parse in CSR blocks of that many rows instead
    of materializing all parsed rows at once (same result, bounded python
    list overhead); use `iter_libsvm_chunks` directly to keep even the
    stitched CSR from materializing.
    """
    chunks = list(iter_libsvm_chunks(
        source, chunk_rows=chunk_rows if chunk_rows is not None else 2**62,
        n_features=n_features, zero_based=zero_based))
    labels = np.concatenate([y for _, y in chunks])
    if len(chunks) == 1:
        return chunks[0][0], labels
    return csr_vstack([c for c, _ in chunks], d=n_features), labels


# ----------------------------------------------------------------------------
# CSR <-> padded-ELL
# ----------------------------------------------------------------------------

def csr_to_ell(csr: CSRMatrix, r_max: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(cols (n, r_max) int32, vals (n, r_max) f32, nnz (n,) int32).

    Padding entries are (0, 0.0) -- exact no-ops for gather/scatter."""
    nnz = csr.row_nnz()
    need = int(nnz.max()) if nnz.size else 0
    r_max = need if r_max is None else r_max
    if r_max < need:
        raise ValueError(f"r_max={r_max} < max row nnz {need}")
    n = csr.shape[0]
    slot = np.arange(max(r_max, 1))[None, :] < nnz[:, None]   # (n, r_max)
    cols = np.zeros((n, max(r_max, 1)), np.int32)
    vals = np.zeros((n, max(r_max, 1)), np.float32)
    cols[slot] = csr.indices
    vals[slot] = csr.data
    return cols, vals, nnz


def ell_to_csr(cols: np.ndarray, vals: np.ndarray, nnz: np.ndarray,
               d: int) -> CSRMatrix:
    """Inverse of `csr_to_ell` (drops padding entries)."""
    cols = np.asarray(cols)
    vals = np.asarray(vals)
    nnz = np.asarray(nnz).astype(np.int64)
    n, r_max = cols.shape
    slot = np.arange(max(r_max, 1))[None, :] < nnz[:, None]
    indptr = np.concatenate([[0], np.cumsum(nnz)])
    return CSRMatrix(vals[slot].astype(np.float32),
                     cols[slot].astype(np.int32),
                     indptr, (n, d))


# ----------------------------------------------------------------------------
# Device container
# ----------------------------------------------------------------------------

@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("cols", "vals", "nnz"), meta_fields=("d",))
@dataclasses.dataclass(frozen=True)
class SparseShards:
    """Padded-ELL worker shards: the sparse analogue of the dense (K, nk, d)
    partition. Leaves carry a leading K axis (per-worker shards under vmap
    drop it); `d` is static so shapes stay available under jit."""
    cols: jnp.ndarray    # (..., nk, r_max) int32, padding -> 0
    vals: jnp.ndarray    # (..., nk, r_max) float32, padding -> 0.0
    nnz: jnp.ndarray     # (..., nk) int32 true entries per row
    d: int

    @property
    def r_max(self) -> int:
        return self.cols.shape[-1]

    def widened(self, r_max: int) -> "SparseShards":
        """The same rows with `r_max` slots each, the added slots padding
        (column 0, value 0.0); a shard that wide already comes back as
        it is."""
        pad = r_max - self.r_max
        if pad < 0:
            raise ValueError(f"cannot widen {self.r_max} ELL slots to "
                             f"{r_max}")
        if pad == 0:
            return self
        widths = ((0, 0),) * (self.cols.ndim - 1) + ((0, pad),)
        return SparseShards(jnp.pad(self.cols, widths),
                            jnp.pad(self.vals, widths), self.nnz, d=self.d)

    @property
    def density(self) -> float:
        rows = int(np.prod(self.nnz.shape))
        return float(jnp.sum(self.nnz)) / max(rows * self.d, 1)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("cols", "vals", "nnz"),
                   meta_fields=("d", "M", "d_local"))
@dataclasses.dataclass(frozen=True)
class FeatureShards:
    """Feature-sliced padded-ELL shards for a 2-D (data=K, model=M) mesh.

    Worker k's rows are split by feature block: model shard m keeps only
    the entries whose global column falls in [m*d_local, (m+1)*d_local)
    and stores them with *shard-local* column ids (global - m*d_local), so
    device (k, m) gathers/scatters against its local w slice without ever
    materializing the global w. The global->local map is the contiguous
    block map carried by `comm.WSpec(d, M)` (same d_local); padding slots
    are (local col 0, val 0.0) -- exact no-ops against any shard.

    Leaves: cols/vals (K, M, nk, r_loc), nnz (K, M, nk) per-slice true
    entry counts. `d` is the global (unpadded) feature count; the padded
    global width is M * d_local. M=1 degenerates to `SparseShards` with
    an extra singleton axis (identical arrays, identical r_max)."""
    cols: jnp.ndarray    # (K, M, nk, r_loc) int32 shard-LOCAL ids
    vals: jnp.ndarray    # (K, M, nk, r_loc) float32
    nnz: jnp.ndarray     # (K, M, nk) int32 true entries per row-slice
    d: int
    M: int
    d_local: int

    @property
    def r_loc(self) -> int:
        return self.cols.shape[-1]

    @property
    def d_padded(self) -> int:
        return self.M * self.d_local


def shard_features(sh: SparseShards, M: int) -> FeatureShards:
    """Slice worker ELL shards along the feature axis into M model shards
    with locally remapped column ids (host-side numpy; the device never
    sees a global column id again). M=1 is the identity layout."""
    cols = np.asarray(sh.cols)
    vals = np.asarray(sh.vals)
    if cols.ndim != 3:
        raise ValueError(f"expected worker-major (K, nk, r_max) shards, "
                         f"got {cols.shape}")
    K, nk, r_max = cols.shape
    d_local = -(-sh.d // M)
    live = np.arange(r_max)[None, None, :] < np.asarray(sh.nnz)[:, :, None]
    owner = np.where(live, cols // d_local, -1)        # padding owns nothing
    slice_nnz = np.stack([(owner == m).sum(-1) for m in range(M)], axis=1)
    r_loc = max(int(slice_nnz.max()) if slice_nnz.size else 0, 1)
    out_c = np.zeros((K, M, nk, r_loc), np.int32)
    out_v = np.zeros((K, M, nk, r_loc), np.float32)
    for m in range(M):
        sel = owner == m                               # (K, nk, r_max)
        slot = np.cumsum(sel, axis=-1) - 1             # dest slot per entry
        kk, ii, _ = np.nonzero(sel)
        out_c[kk, m, ii, slot[sel]] = cols[sel] - m * d_local
        out_v[kk, m, ii, slot[sel]] = vals[sel]
    return FeatureShards(jnp.asarray(out_c), jnp.asarray(out_v),
                         jnp.asarray(slice_nnz.astype(np.int32)),
                         d=sh.d, M=M, d_local=d_local)


def shard_features_streaming(chunks, K: int, M: int = 1, *,
                             n_features: Optional[int] = None):
    """Build per-shard `FeatureShards` incrementally from streamed
    (CSRMatrix, labels) blocks -- e.g. `iter_libsvm_chunks` -- without ever
    materializing a host-side full-width global array (neither the padded
    (n, r_max) global ELL nor the (K, nk, r_max) worker ELL that the
    `partition_sparse` -> `shard_features` path routes through). This is
    the url/webspam-scale ingest (d ~ 3.2M): peak host memory is O(nnz)
    entry lists plus the final per-shard padded blocks, independent of
    n * r_max.

    Rows are dealt round-robin in arrival order (row j -> worker j % K; a
    streaming source has no global row count to split contiguously, and
    round-robin keeps worker loads balanced for any stream length). Each
    row is sliced into its M feature blocks on arrival and stored with
    shard-local column ids -- the same contiguous block map as
    `shard_features` (d_local = ceil(d/M)), so the result is exactly the
    `FeatureShards` the materialized path produces for the same row
    assignment (equality-tested in tests/test_sparse.py).

    `n_features` fixes the global width d up front (required unless the
    chunks already carry a stable width, i.e. `iter_libsvm_chunks` was
    given n_features). Returns (FeatureShards, y (K, nk), mask (K, nk))
    with the usual zero-pad + mask tail on each worker.
    """
    if K < 1 or M < 1:
        raise ValueError(f"need K >= 1 and M >= 1, got K={K} M={M}")
    d = n_features
    d_local = None
    # O(1) python objects per *chunk*: each chunk contributes one tuple of
    # flat per-entry arrays (k, m, local row, ELL slot, local col, val) and
    # one (rows, M) slice-count block; the padded output is allocated once
    # at the end when n and r_loc are known
    entry_blocks, count_blocks, label_blocks = [], [], []
    n = 0
    for csr, y in chunks:
        if d is None:
            d = csr.shape[1]
            if d < 1:
                raise ValueError("cannot infer d from an empty first chunk; "
                                 "pass n_features")
        if csr.shape[1] > d:
            raise ValueError(f"chunk width {csr.shape[1]} exceeds d={d}; "
                             f"pass n_features for a stable column count")
        if d_local is None:
            d_local = -(-d // M)
        nc = csr.shape[0]
        if nc == 0:
            continue
        ip = csr.indptr.astype(np.int64)
        row_nnz = np.diff(ip)
        row_of = np.repeat(np.arange(nc, dtype=np.int64), row_nnz)
        owner = csr.indices.astype(np.int64) // d_local
        # entries are column-sorted within a row, so each row's m-slices
        # are contiguous runs: the slice counts give every entry's ELL
        # slot without any per-row python work
        counts = np.zeros((nc, M), np.int64)
        np.add.at(counts, (row_of, owner), 1)
        starts = np.zeros((nc, M), np.int64)
        starts[:, 1:] = np.cumsum(counts, axis=1)[:, :-1]
        pos_in_row = np.arange(len(row_of)) - np.repeat(ip[:-1], row_nnz)
        slot = pos_in_row - starts[row_of, owner]
        g = n + row_of                       # global arrival row id
        entry_blocks.append((
            (g % K).astype(np.int32), owner.astype(np.int32),
            (g // K).astype(np.int64), slot,
            (csr.indices - owner * d_local).astype(np.int32),
            csr.data.astype(np.float32)))
        gr = n + np.arange(nc, dtype=np.int64)
        count_blocks.append(((gr % K).astype(np.int32), gr // K, counts))
        label_blocks.append((np.asarray(y, np.float32),))
        n += nc
    if d is None:
        raise ValueError("empty stream and no n_features; cannot size d")
    if n == 0:
        raise ValueError("empty stream: no rows to shard (a zero-row "
                         "FeatureShards would certify NaN gaps downstream)")
    d_local = -(-d // M)
    nk = -(-n // K)
    r_loc = max((int(c.max()) for _, _, c in count_blocks if c.size),
                default=0)
    r_loc = max(r_loc, 1)
    cols = np.zeros((K, M, nk, r_loc), np.int32)
    vals = np.zeros((K, M, nk, r_loc), np.float32)
    nnz = np.zeros((K, M, nk), np.int32)
    yp = np.zeros((K, nk), np.float32)
    mask = np.zeros((K, nk), np.float32)
    for (ke, me, re, se, ce, ve), (kr, rr, cnt), (yb,) in zip(
            entry_blocks, count_blocks, label_blocks):
        cols[ke, me, re, se] = ce
        vals[ke, me, re, se] = ve
        nnz[kr, :, rr] = cnt
        yp[kr, rr] = yb
        mask[kr, rr] = 1.0
    fs = FeatureShards(jnp.asarray(cols), jnp.asarray(vals),
                       jnp.asarray(nnz), d=d, M=M, d_local=d_local)
    return fs, jnp.asarray(yp), jnp.asarray(mask)


# ----------------------------------------------------------------------------
# The certificate's layout: rows sorted by length, walked in fixed tiles
# ----------------------------------------------------------------------------

CERT_BLOCK_ROWS = 2048   # rows of a tile
CERT_CHUNK = 8           # ELL slots of a tile


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("order", "cols", "vals", "tiles", "n_tiles"),
                   meta_fields=("d", "nk"))
@dataclasses.dataclass(frozen=True)
class CertLayout:
    """A copy of `SparseShards` rows for the certificate's two passes.

    Each worker's rows are sorted by the chunks of c slots they fill,
    longest first, padded with empty rows to `nb` blocks of B rows, and
    their slots padded to `nch` chunks; tile (b, j) holds chunk j of block b's rows, stored
    slot-major (c, B) so the rows lie along the lanes. `tiles[:n_tiles]`
    lists the (block, chunk) pairs whose chunk some worker's row reaches:
    the passes walk those and skip the rest, which hold only padding.

    Every shape is a function of (K, nk, r_max) alone; the data sets
    only `tiles` and `n_tiles`, as values, so one compiled certificate
    serves every data set of a shape. The tile list is shared by the
    workers, so a walk sharded by workers needs no collective.

    Row order: `matvec` returns z and `rmatvec` takes its coefficients in
    the layout's order; `rows(x)` puts a (K, nk) array in it (the padding
    rows read 0)."""
    order: jnp.ndarray    # (K, nb*B) int32 source row; nk on padding rows
    cols: jnp.ndarray     # (K, nb, nch, c, B) int32, padding -> 0
    vals: jnp.ndarray     # (K, nb, nch, c, B) float32, padding -> 0.0
    tiles: jnp.ndarray    # (nb*nch, 2) int32 (block, chunk), walked first
    n_tiles: jnp.ndarray  # () int32 tiles that hold an entry
    d: int
    nk: int

    def rows(self, x: jnp.ndarray) -> jnp.ndarray:
        """(K, nk) in the source order -> (K, nb*B) in the layout's."""
        return _in_order(x, self.order)

    @property
    def tile_slots(self) -> int:
        """Slots of one tile over all workers: K * B * c."""
        K, _, _, c, B = self.cols.shape
        return K * B * c


def _in_order(x: jnp.ndarray, order: jnp.ndarray) -> jnp.ndarray:
    return jnp.take_along_axis(x, order, axis=-1, mode="fill", fill_value=0)


def cert_layout(sh: SparseShards,
                block_rows: int = CERT_BLOCK_ROWS) -> CertLayout:
    """Build the certificate's layout of worker shards (K, nk, r_max) on
    the device: a counting sort of each worker's rows by the chunks they
    fill, longest first and stable (a comparison sort of nk keys compiles
    for a TPU in about 16 s, the counting sort in about 2 s), and one
    gather of whole rows. Jit-able; under a mesh each worker's rows stay
    on its chip, and only the per-block chunk counts are reduced over
    workers."""
    K, nk, r_max = sh.cols.shape
    chunk = CERT_CHUNK
    B = min(block_rows, -(-nk // 128) * 128)
    nb, nch = -(-nk // B), -(-r_max // chunk)
    fills = -(-sh.nnz // chunk)                                  # (K, nk)
    hot = (fills[..., None] == jnp.arange(nch, -1, -1)).astype(jnp.int32)
    count = jnp.sum(hot, axis=1)                                 # (K, nch+1)
    first = jnp.cumsum(count, axis=-1) - count
    pos = jnp.sum((jnp.cumsum(hot, axis=1) - hot + first[:, None]) * hot,
                  axis=-1)
    order = jax.vmap(lambda p: jnp.full(nb * B, nk, jnp.int32).at[p].set(
        jnp.arange(nk, dtype=jnp.int32), unique_indices=True))(pos)

    def tiled(a):
        # whole rows in the sorted order, then (nb, B, nch, c) -> slot-major
        a = jax.vmap(lambda ak, ok: ak.at[ok].get(mode="fill", fill_value=0)
                     )(a, order)
        a = jnp.pad(a, ((0, 0), (0, 0), (0, nch * chunk - r_max)))
        return a.reshape(K, nb, B, nch, chunk).transpose(0, 1, 3, 4, 2)

    # a block's first row is its longest on every worker
    need = jnp.max(_in_order(fills, order)[:, ::B], axis=0)      # (nb,)
    live = (jnp.arange(nch)[None, :] < need[:, None]).reshape(-1)
    flat, = jnp.nonzero(live, size=nb * nch, fill_value=0)
    tiles = jnp.stack([flat // nch, flat % nch], axis=-1).astype(jnp.int32)
    return CertLayout(order, tiled(sh.cols), tiled(sh.vals), tiles,
                      jnp.sum(live).astype(jnp.int32), d=sh.d, nk=nk)


def _walk(lay: CertLayout, tile_fn, init, per_worker=None):
    """Fold `tile_fn(block, cols (c, B), vals (c, B), x, acc)` over the
    layout's live tiles, per worker (vmapped: a (K, c, B) tile a step);
    `x` is the worker's slice of `per_worker`, or None."""
    def worker(cols, vals, x, acc):
        def step(t, acc):
            b, j = lay.tiles[t, 0], lay.tiles[t, 1]
            return tile_fn(b, cols[b, j], vals[b, j], x, acc)
        return jax.lax.fori_loop(0, lay.n_tiles, step, acc)
    return jax.vmap(worker, in_axes=(0, 0, None if per_worker is None
                                     else 0, 0))(lay.cols, lay.vals,
                                                 per_worker, init)


def matvec(sh, w: jnp.ndarray) -> jnp.ndarray:
    """z = A^T w per row:  z_i = sum_r vals[i, r] * w[cols[i, r]].

    `FeatureShards` + padded (M*d_local,) w: per-shard local gathers
    summed over the model axis -- the one model-axis reduction a sharded
    prediction needs. `CertLayout`: z over the live tiles, (K, nb*B) in
    the layout's row order."""
    if isinstance(sh, CertLayout):
        K, nb, _, _, B = sh.cols.shape
        z = _walk(sh, lambda b, c, v, _, z: z.at[b].add(
            jnp.sum(v * w[c], axis=0)), jnp.zeros((K, nb, B), w.dtype))
        return z.reshape(K, nb * B)
    if isinstance(sh, FeatureShards):
        w2 = w.reshape(sh.M, sh.d_local)
        per_m = jax.vmap(lambda wm, cm, vm: jnp.sum(vm * wm[cm], axis=-1),
                         in_axes=(0, 1, 1), out_axes=0)(w2, sh.cols, sh.vals)
        return jnp.sum(per_m, axis=0)
    return jnp.sum(sh.vals * w[sh.cols], axis=-1)


def rmatvec(sh, coef: jnp.ndarray) -> jnp.ndarray:
    """A coef = sum_i coef_i x_i as a scatter-add (segment sum). Dense
    output is (d,) for `SparseShards`, the padded (M*d_local,) global
    vector for `FeatureShards` (per-shard local scatters, concatenated --
    padded coordinates receive nothing). `CertLayout` takes `coef` in its
    row order, (K, nb*B), scatters each worker's live tiles into its own
    (d,) and sums the workers once at the end."""
    if isinstance(sh, CertLayout):
        K, nb, _, _, B = sh.cols.shape
        part = _walk(sh, lambda b, c, v, a, acc: acc.at[c].add(
            v * a[b][None, :]), jnp.zeros((K, sh.d), sh.vals.dtype),
            coef.reshape(K, nb, B))
        return jnp.sum(part, axis=0)
    if isinstance(sh, FeatureShards) and sh.M == 1:
        # one slice holds the global ids: take the replicated layout's
        # scatter, so an M=1 certificate partitions (and rounds) the same
        sh = SparseShards(sh.cols[:, 0], sh.vals[:, 0], sh.nnz[:, 0], d=sh.d)
    if isinstance(sh, FeatureShards):
        contrib = sh.vals * coef[:, None, :, None]        # (K, M, nk, r)
        per_m = jax.vmap(
            lambda cm, xm: jnp.zeros(sh.d_local, xm.dtype)
            .at[cm.reshape(-1)].add(xm.reshape(-1)),
            in_axes=(1, 1), out_axes=0)(sh.cols, contrib)
        return per_m.reshape(sh.d_padded)
    contrib = sh.vals * coef[..., None]
    return jnp.zeros(sh.d, contrib.dtype).at[sh.cols.reshape(-1)].add(
        contrib.reshape(-1))


def row_sqnorms(sh) -> jnp.ndarray:
    """||x_i||^2 per row, (K, nk). For `FeatureShards` the per-slice
    masses sum over the model axis -- these are the *global* sqnorms the
    feature-sharded solver needs precomputed."""
    if isinstance(sh, FeatureShards):
        return jnp.sum(sh.vals * sh.vals, axis=(-3, -1))
    return jnp.sum(sh.vals * sh.vals, axis=-1)


def densify(sh) -> jnp.ndarray:
    """Materialize (..., nk, d) dense rows (tests / densified baselines).
    `FeatureShards` densify to the padded (K, nk, M*d_local) width with
    local ids lifted back to global (offset rebasing)."""
    if isinstance(sh, FeatureShards):
        cols = np.asarray(sh.cols) + (np.arange(sh.M, dtype=np.int32)
                                      [None, :, None, None] * sh.d_local)
        vals = np.asarray(sh.vals)
        K, M, nk, r = cols.shape
        flat = np.zeros((K * nk, sh.d_padded), np.float32)
        # row index per entry: worker-major row id, same for every m
        ridx = (np.arange(K)[:, None, None, None] * nk
                + np.arange(nk)[None, None, :, None])
        ridx = np.broadcast_to(ridx, cols.shape)
        np.add.at(flat, (ridx.reshape(-1), cols.reshape(-1)),
                  vals.reshape(-1))
        return jnp.asarray(flat.reshape(K, nk, sh.d_padded))
    cols = np.asarray(sh.cols)
    vals = np.asarray(sh.vals)
    lead = cols.shape[:-1]
    rows = int(np.prod(lead)) if lead else 1
    flat = np.zeros((rows, sh.d), np.float32)
    ridx = np.repeat(np.arange(rows), cols.shape[-1])
    np.add.at(flat, (ridx, cols.reshape(-1)), vals.reshape(-1))
    return jnp.asarray(flat.reshape(*lead, sh.d))


# ----------------------------------------------------------------------------
# Synthetic sparse generators (true density, unlike the dense zeroed stand-ins)
# ----------------------------------------------------------------------------

def make_sparse_classification(n: int, d: int, *, density: float,
                               seed: int = 0, noise: float = 0.1
                               ) -> Tuple[CSRMatrix, np.ndarray]:
    """Binary labels in {-1, +1} on rows with ~density*d nonzeros, ||x|| <= 1.

    Row nnz is Poisson around density*d (clipped to [1, d]) so r_max stays a
    small multiple of the mean -- the padded-ELL waste is bounded."""
    rng = np.random.default_rng(seed)
    base = max(1, int(round(density * d)))
    nnz = np.clip(rng.poisson(base, n), 1, d).astype(np.int64)
    indptr = np.concatenate([[0], np.cumsum(nnz)])
    indices = np.empty(int(indptr[-1]), np.int32)
    data = rng.standard_normal(int(indptr[-1])).astype(np.float32)
    for i in range(n):
        lo, hi = indptr[i], indptr[i + 1]
        indices[lo:hi] = np.sort(rng.choice(d, hi - lo, replace=False))
    # normalize rows (paper Remark 7: ||x_i|| <= 1)
    norms = np.sqrt(np.add.reduceat(data * data, indptr[:-1]))
    data /= np.maximum(np.repeat(norms, nnz), 1e-12)
    csr = CSRMatrix(data, indices, indptr, (n, d))
    w_star = rng.standard_normal(d).astype(np.float32)
    margin = np.add.reduceat(data * w_star[indices], indptr[:-1])
    flip = rng.random(n) < noise
    yv = np.sign(margin) * np.where(flip, -1.0, 1.0)
    yv[yv == 0] = 1.0
    return csr, yv.astype(np.float32)


# ----------------------------------------------------------------------------
# Worker partitioner (mirrors data.synthetic.partition: shuffle, pad, mask)
# ----------------------------------------------------------------------------

def partition_sparse(csr: CSRMatrix, y: np.ndarray, K: int, *, seed: int = 0,
                     heterogeneity: float = 1.0,
                     r_max: Optional[int] = None,
                     M: int = 1):
    """Shuffle + split CSR rows into (shards, y (K, nk), mask (K, nk)).

    Same contract as the dense `partition` (identical rng stream, padding
    rows are all-zero with mask 0); heterogeneity < 1 concentrates
    correlated rows on the same worker via the shared `split_order`.

    `M` > 1 additionally slices each worker's rows along the feature axis
    for a 2-D (data=K, model=M) mesh: the returned shards are
    `FeatureShards` with shard-local column ids (see `shard_features`).
    The row partition (and therefore y/mask) is identical for every M --
    the model axis re-slices features, never rows."""
    n, d = csr.shape
    cols_e, vals_e, nnz_e = csr_to_ell(csr, r_max)
    rng = np.random.default_rng(seed)
    order = split_order(
        n, rng, heterogeneity,
        lambda r: np.sum(
            vals_e * r.standard_normal(d).astype(np.float32)[cols_e], axis=1))
    nk = (n + K - 1) // K
    pad = nk * K - n
    rm = cols_e.shape[1]
    colsp = np.concatenate([cols_e[order], np.zeros((pad, rm), np.int32)])
    valsp = np.concatenate([vals_e[order], np.zeros((pad, rm), np.float32)])
    nnzp = np.concatenate([nnz_e[order], np.zeros(pad, np.int32)])
    yp = np.concatenate([np.asarray(y)[order],
                         np.zeros(pad, np.asarray(y).dtype)])
    mk = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
    shards = SparseShards(jnp.asarray(colsp.reshape(K, nk, rm)),
                          jnp.asarray(valsp.reshape(K, nk, rm)),
                          jnp.asarray(nnzp.reshape(K, nk)), d=d)
    if M > 1:
        shards = shard_features(shards, M)
    return shards, jnp.asarray(yp.reshape(K, nk)), jnp.asarray(mk.reshape(K, nk))
