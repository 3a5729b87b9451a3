"""Ahead-of-time compiles for a described TPU v5e, at the paper's widths.

Nothing here runs on a chip: each test lowers and compiles for a v5e chip
that the TPU compiler describes without one being attached, so a kernel
that Mosaic refuses (or a program that does not fit) fails here first.
The kernels are called directly with `interpret=False`, since the
dispatch in `kernels.ops` reads `jax.default_backend()` (the CPU here).

Shard widths: rcv1 (677,399 x 47,236, ~0.16% dense, r_max 114) and
epsilon (400,000 x 2,000, dense) split over K=8 workers, rows padded to a
multiple of 128; the mesh round splits rcv1 over the 2x2 host's four
chips, one worker a chip. The topology is described inside a fixture,
never at import time: only the worker that runs this file loads the TPU
library.
"""
import functools
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core import CoCoAConfig
from repro.core.cocoa import init_state, make_round_sharded, make_round_vmap
from repro.core.losses import get_loss
from repro.core.solvers import _FLAT_SCATTER_UPDATES, ell_width
from repro.data.sparse import SparseShards
from repro.kernels.local_sdca import local_sdca_pallas
from repro.kernels.sparse_sdca import sparse_local_sdca, sparse_local_sdca_zx

K = 8
RCV1 = dict(nk=84_736, d=47_236, r_max=114)      # 677,399 / 8 rows, padded
EPS = dict(nk=50_048, d=2_048)                   # 400,000 / 8 rows, padded
LOSS = get_loss("smooth_hinge")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _sparse_args(S, nk, d, r_max, lead=()):
    return (S(lead + (nk, r_max), jnp.int32), S(lead + (nk, r_max)),
            S(lead + (nk,)), S(lead + (nk,)), S(lead + (nk,)))


def _kernel_case(case, S):
    """(fn, args) of one kernel launch at real width."""
    if case == "dense":
        fn = lambda X, y, a, m, w, s: local_sdca_pallas(  # noqa: E731
            X, y, a, m, w, s, loss=LOSS, interpret=False)
        return fn, (S((EPS["nk"], EPS["d"])), S((EPS["nk"],)),
                    S((EPS["nk"],)), S((EPS["nk"],)), S((EPS["d"],)), S(()))
    if case == "dense_vmap8":
        one = lambda X, y, a, m, w, s: local_sdca_pallas(  # noqa: E731
            X, y, a, m, w, s, loss=LOSS, interpret=False)
        fn = jax.vmap(one, in_axes=(0, 0, 0, 0, None, None))
        return fn, (S((K, EPS["nk"], EPS["d"])), S((K, EPS["nk"])),
                    S((K, EPS["nk"])), S((K, EPS["nk"])), S((EPS["d"],)),
                    S(()))
    nk, d, r_max = RCV1["nk"], RCV1["d"], RCV1["r_max"]
    if case in ("sparse_depth1", "sparse_depth2", "sparse_prox"):
        kw = dict(buffer_depth=2 if case == "sparse_depth2" else 1,
                  prox_kappa=0.5 if case == "sparse_prox" else None)
        fn = lambda *a: sparse_local_sdca(  # noqa: E731
            *a, loss=LOSS, interpret=False, **kw)
        return fn, _sparse_args(S, nk, d, r_max) + (S((d,)), S(()))
    if case == "sparse_vmap8":
        one = lambda c, v, y, a, m, w, s: sparse_local_sdca(  # noqa: E731
            c, v, y, a, m, w, s, loss=LOSS, interpret=False)
        fn = jax.vmap(one, in_axes=(0,) * 5 + (None, None))
        return fn, _sparse_args(S, nk, d, r_max, (K,)) + (S((d,)), S(()))
    if case == "sparse_zx":
        d_loc = -(-d // 2)                       # one of M=2 feature shards
        fn = lambda c, v, y, a, m, w, s, sq: sparse_local_sdca_zx(  # noqa
            c, v, y, a, m, w, s, sq, loss=LOSS, block_rows=16,
            interpret=False)
        return fn, _sparse_args(S, nk, d_loc, r_max) + (
            S((d_loc,)), S(()), S((nk,)))
    raise ValueError(case)


@pytest.mark.parametrize("case", ["dense", "dense_vmap8", "sparse_depth1",
                                  "sparse_depth2", "sparse_prox",
                                  "sparse_vmap8", "sparse_zx"])
def test_kernel_compiles_for_v5e(spec, case):
    """Every SDCA kernel variant passes Mosaic at real width and lands in
    the program as a TPU custom call."""
    fn, args = _kernel_case(case, spec)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jnp_round_compiles_for_v5e_rcv1_shape(spec):
    """One vmap-backend round of the jnp sparse solver, K=8 workers over
    the full rcv1 shape, compiles for one chip and fits its 16 GB."""
    S = spec
    nk, d, r_max = 84_675, RCV1["d"], RCV1["r_max"]
    X = SparseShards(S((K, nk, r_max), jnp.int32), S((K, nk, r_max)),
                     S((K, nk), jnp.int32), d=d)
    state = jax.tree.map(lambda a: S(a.shape, a.dtype),
                         jax.eval_shape(lambda: init_state(d, K, nk)))
    cfg = CoCoAConfig.adding(K, loss="smooth_hinge", lam=1e-4, H=nk)
    t0 = time.perf_counter()
    compiled = jax.jit(make_round_vmap(cfg, K)).lower(
        state, X, S((K, nk)), S((K, nk))).compile()
    assert time.perf_counter() - t0 < 60
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def _has_flat_scatter(hlo_text: str, size: int) -> bool:
    return any(" scatter(" in ln and f"f32[{size}]" in ln
               for ln in hlo_text.splitlines())


@pytest.mark.parametrize("workers,width", [(8, 127), (8, 128), (4, 255),
                                           (4, 256), (16, 63), (16, 64)])
def test_batched_scatter_add_flattens_at_1024_updates(spec, workers, width):
    """The compiler fact `solvers.ell_width` rests on: a vmap-batched
    `u.at[c].add(v)` becomes one scatter over the flattened (K * d)
    vector exactly when K * width reaches 1024 updates."""
    d = RCV1["d"]
    fn = jax.vmap(lambda u, c, v: u.at[c].add(v))
    text = jax.jit(fn).lower(spec((workers, d)),
                             spec((workers, width), jnp.int32),
                             spec((workers, width))).compile().as_text()
    assert _has_flat_scatter(text, workers * d) == (
        workers * width >= _FLAT_SCATTER_UPDATES)


def test_jnp_round_scatter_is_flat_at_rcv1_cell_width(spec):
    """The rcv1 cell's rows of 122 slots run widened to 128: the round's
    per-step scatter-add is the flat one."""
    S = spec
    nk, d, r_max = 4_096, RCV1["d"], 122
    assert ell_width(r_max, K) == 128
    X = SparseShards(S((K, nk, r_max), jnp.int32), S((K, nk, r_max)),
                     S((K, nk), jnp.int32), d=d)
    state = jax.tree.map(lambda a: S(a.shape, a.dtype),
                         jax.eval_shape(lambda: init_state(d, K, nk)))
    cfg = CoCoAConfig.adding(K, loss="smooth_hinge", lam=1e-4, H=nk)
    text = jax.jit(make_round_vmap(cfg, K)).lower(
        state, X, S((K, nk)), S((K, nk))).compile().as_text()
    assert _has_flat_scatter(text, K * d)


def test_mesh_round_all_reduces_once_under_its_scope(topo):
    """The shard_map round at the rcv1 shape, one worker on each of the
    four chips, with n handed in as `solve` does: its one collective is
    the exchange's all-reduce of d float32, under the named scope the
    benchmark's `all_reduce_ms` reads."""
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    mesh = Mesh(topo.devices, ("data",), axis_types=(AxisType.Auto,))
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    workers, nk, d, r_max = 4, 169_350, RCV1["d"], 122

    def S(shape, dtype=jnp.float32, sharding=rows):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    X = SparseShards(S((workers, nk, r_max), jnp.int32),
                     S((workers, nk, r_max)), S((workers, nk), jnp.int32),
                     d=d)
    state = jax.tree.map(lambda a: S(a.shape, a.dtype, rep),
                         jax.eval_shape(lambda: init_state(d, workers, nk)))
    cfg = CoCoAConfig.adding(workers, loss="smooth_hinge", lam=1e-4, H=nk,
                             backend="shard_map")
    text = jax.jit(make_round_sharded(cfg, mesh)).lower(
        state, X, S((workers, nk)), S((workers, nk)),
        S((), sharding=rep)).compile().as_text()
    collectives = [ln for ln in text.splitlines()
                   if " = " in ln and any(f" {op}(" in ln for op in (
                       "all-reduce", "all-reduce-start", "all-gather",
                       "all-to-all", "collective-permute",
                       "reduce-scatter"))]
    assert len(collectives) == 1, collectives
    assert f"f32[{d}]" in collectives[0]
    assert "cocoa/exchange/all_reduce" in collectives[0]


def _computations(hlo_text: str) -> dict:
    """{computation name: its instruction lines} of compiled HLO text."""
    out, name = {}, None
    for ln in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", ln)
        if head:
            name = head.group(1)
            out[name] = []
        elif ln == "}":
            name = None
        elif name is not None:
            out[name].append(ln)
    return out


def _collectives(lines) -> list:
    return [ln for ln in lines if " = " in ln and any(f" {op}(" in ln for op in (
        "all-reduce", "all-reduce-start", "all-gather", "all-to-all",
        "collective-permute", "reduce-scatter"))]


def _certificate_layout_of(workers, nk, r_max, S):
    """(certificate operands, the layout's compiled build) on ELL shards
    (workers, nk, r_max) placed by `S`: alpha as the rounds keep it, and
    the layout, y and mask where the build leaves them."""
    from repro.core.cocoa import _certificate_rows
    X = SparseShards(S((workers, nk, r_max), jnp.int32),
                     S((workers, nk, r_max)), S((workers, nk), jnp.int32),
                     d=RCV1["d"])
    build = jax.jit(_certificate_rows).lower(
        X, S((workers, nk)), S((workers, nk))).compile()
    outs = jax.eval_shape(_certificate_rows, X, S((workers, nk)),
                          S((workers, nk)))
    outs = jax.tree.map(lambda a, sh: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sh), outs, build.output_shardings)
    return (S((workers, nk)),) + tuple(outs), build


def _certificate_text(args) -> tuple:
    """(compiled text, compile seconds) of `solve`'s certificate."""
    from repro.core import duality
    from repro.core.cocoa import _scoped
    fn = jax.jit(_scoped("cocoa/certificate", functools.partial(
        duality.gap_decomposed, loss=LOSS, lam=1e-4)))
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled.as_text(), time.perf_counter() - t0


def test_certificate_walks_tiles_at_rcv1_shape(spec):
    """At the rcv1 cell's shape the certificate's gather and scatter-add
    run on one (K, 8, 2,048) tile a step, never on the (K, nk, r_max)
    slots: the primal gathers a f32[8, 8, 2048] tile, and the rmatvec
    scatters its 131,072 updates into the workers' flat (K * d) vector."""
    nk, r_max = 84_675, 122
    args, _ = _certificate_layout_of(K, nk, r_max, spec)
    assert args[1].cols.shape == (K, 42, 16, 8, 2048)
    text, _ = _certificate_text(args)
    tile = K * 8 * 2048
    assert any(" gather(" in ln and f"f32[{K},8,2048]" in ln
               and "cocoa/certificate/primal" in ln
               for ln in text.splitlines())
    assert any(any(f"s32[{tile}]" in ln for ln in lines)
               for lines in _computations(text).values()
               if any(" scatter(" in ln and f"f32[{K * RCV1['d']}]" in ln
                      for ln in lines))
    assert f"[{K},{nk},{r_max}]" not in text
    assert f"[{K * nk * r_max}]" not in text


def test_mesh_certificate_all_reduces_outside_its_walk(topo):
    """The certificate on the rcv1 rows, one worker on each chip of the
    v5e 2x2: its collectives are the parent's two all-reduces (v with n;
    the primal and dual scalars), none inside a `while` loop, and it
    compiles in seconds (the raw-ELL certificate took about 126 s). The
    layout's build all-reduces once: the widest row of each block."""
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    mesh = Mesh(topo.devices, ("data",), axis_types=(AxisType.Auto,))
    workers, nk, r_max = 4, 169_350, 122

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P("data")))
    args, build = _certificate_layout_of(workers, nk, r_max, S)
    text, seconds = _certificate_text(args)
    assert seconds < 60, seconds
    comps = _computations(text)
    collectives = _collectives(ln for lines in comps.values()
                               for ln in lines)
    assert len(collectives) == 2, collectives
    assert all(" all-reduce(" in ln for ln in collectives), collectives
    assert any(f"f32[{RCV1['d']}]" in ln for ln in collectives)
    bodies = {m for lines in comps.values() for ln in lines
              for m in re.findall(r"body=%?([\w.\-]+)", ln)}
    assert bodies
    assert not _collectives(ln for b in bodies for ln in comps[b])
    reduced, = _collectives(build.as_text().splitlines())
    assert " all-reduce(" in reduced and "s32[83]" in reduced
