"""Ahead-of-time compiles for a described TPU v5e, at the paper's widths.

Nothing here runs on a chip: each test lowers and compiles for a v5e chip
that the TPU compiler describes without one being attached, so a kernel
that Mosaic refuses (or a program that does not fit) fails here first.
The kernels are called directly with `interpret=False`, since the
dispatch in `kernels.ops` reads `jax.default_backend()` (the CPU here).

Shard widths: rcv1 (677,399 x 47,236, ~0.16% dense, r_max 114) and
epsilon (400,000 x 2,000, dense) split over K=8 workers, rows padded to a
multiple of 128. The topology is described inside a fixture, never at
import time: only the worker that runs this file loads the TPU library.
"""
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core import CoCoAConfig
from repro.core.cocoa import init_state, make_round_vmap
from repro.core.losses import get_loss
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
