"""Host spans inside `solve` and the certificate's device scopes.

`solve` runs under the host spans `cocoa_solve` > `cocoa_lower`,
`cocoa_compile`, `cocoa_place`, `cocoa_round`, `cocoa_certificate`,
`cocoa_record`, `cocoa_on_round` (`obs.metrics.span`), and its records
carry their totals: `compile_s` with its `lower_s` part, `execute_s`,
`certificate_s` and the solver's own `host_s`. The certificate labels its
passes `rmatvec`, `primal` and `dual` under `cocoa/certificate`. How a
profiler trace shows them is tested beside the benchmark's trace reader
(`bench/tests/test_bench_spans.py`).
"""
import functools
import glob
import os
import pathlib
import re
import time

import jax
import numpy as np
import pytest

from repro.core import CoCoAConfig, duality, solve
from repro.core.cocoa import _ell_attrs, _layout_rows, _scoped
from repro.core.solvers import ell_width
from repro.core.losses import get_loss
from repro.data import load, partition, partition_sparse
from repro.data import sparse as sparse_data
from repro.obs import (Aggregator, EventBus, aot_compile, aot_stages, span,
                       validate_record)

K = 4
SCOPES = ("cocoa/certificate/rmatvec", "cocoa/certificate/primal",
          "cocoa/certificate/dual")


def _data(kind):
    if kind == "dense":
        X, y = load("tiny")
        return partition(X, y, K, seed=0)
    csr, y = load("tiny_sparse")
    return partition_sparse(csr, y, K, seed=0)


def _solve(kind, rounds=4, hook_s=0.0):
    """A small vmap solve with a bus and a hook that sleeps `hook_s`;
    returns (records, wall seconds of the call, seconds in the hook)."""
    X, y, mask = _data(kind)
    cfg = CoCoAConfig.adding(K, loss="hinge", lam=1e-3, H=32)
    bus = EventBus()
    agg = bus.subscribe(Aggregator())
    in_hook = []

    def hook(t, state, gap):
        t0 = time.perf_counter()
        time.sleep(hook_s)
        in_hook.append(time.perf_counter() - t0)

    t0 = time.perf_counter()
    solve(cfg, X, y, mask, rounds=rounds, gap_every=1, seed=0, obs=bus,
          on_round=hook)
    return agg.records, time.perf_counter() - t0, sum(in_hook)


def test_span_times_and_annotates():
    with span("outer", what="x") as s:
        time.sleep(0.01)
    assert 0.01 <= s.seconds < 1.0
    with pytest.raises(KeyError):
        with span("raises") as s2:
            raise KeyError("propagates")
    assert s2.seconds >= 0


def test_aot_compile_is_its_two_stages():
    compiled, lower_s, compile_s = aot_stages(jax.jit(lambda x: x + 1),
                                              np.ones(3, np.float32),
                                              what="probe")
    assert lower_s > 0 and compile_s > 0
    assert float(compiled(np.ones(3, np.float32))[0]) == 2.0
    compiled, seconds = aot_compile(jax.jit(lambda x: 2 * x),
                                    np.ones(3, np.float32))
    assert seconds > 0 and float(compiled(np.ones(3, np.float32))[1]) == 2.0


def test_records_partition_the_call():
    records, wall, hook = _solve("dense", rounds=5, hook_s=0.002)
    assert len(records) == 5
    for rec in records:
        validate_record(rec.to_dict())
        assert 0 <= rec.lower_s <= rec.compile_s
        assert rec.host_s >= 0
    assert records[0].lower_s > 0
    assert all(r.compile_s == r.lower_s == 0 for r in records[1:])
    accounted = sum(r.compile_s + r.execute_s + r.certificate_s + r.host_s
                    for r in records) + hook
    assert abs(accounted - wall) <= 0.05 * wall + 0.005


@pytest.mark.parametrize("kind", ["dense", "ell"])
@pytest.mark.parametrize("gap", ["gap_decomposed", "gap_at_v"])
def test_lowered_certificate_op_names(kind, gap):
    X, y, mask = _data(kind)
    fn = functools.partial(getattr(duality, gap), loss=get_loss("hinge"),
                           lam=1e-3, reg=duality.L2)
    alpha = np.zeros(y.shape, np.float32)
    args = (alpha, X, y, mask)
    if gap == "gap_at_v":
        args = (np.zeros(X.d if kind == "ell" else X.shape[-1],
                         np.float32),) + args
    text = jax.jit(_scoped("cocoa/certificate", fn)).trace(*args) \
        .lower().compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in SCOPES:
        assert any(scope in op for op in op_names), (scope, gap, kind)


@pytest.mark.parametrize("kind", ["dense", "ell"])
def test_solve_span_carries_the_ell_width(kind, tmp_path):
    """On ELL data `cocoa_solve` carries the width the sparse solver runs
    at and the slots the vmap backend adds to reach it; on dense data
    neither."""
    if kind == "dense":
        X, y, mask = _data("dense")
    else:
        csr, y = load("tiny_sparse")
        X, y, mask = partition_sparse(csr, y, K, seed=0, r_max=100)
    cfg = CoCoAConfig.adding(K, loss="hinge", lam=1e-3, H=32)
    with jax.profiler.trace(str(tmp_path)):
        solve(cfg, X, y, mask, rounds=1, seed=0)
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_serialized_xspace(
        pathlib.Path(path).read_bytes())
    stats = [dict(ev.stats) for plane in pd.planes
             if plane.name.startswith("/host:CPU")
             for line in plane.lines for ev in line.events
             if ev.name == "cocoa_solve"]
    assert len(stats) == 1
    if kind == "dense":
        assert not {"ell_width", "ell_pad"} & set(stats[0])
    else:
        # K = 4 workers of 100 slots: 256 slots make 1024 updates a step
        assert stats[0]["ell_width"] == 256
        assert stats[0]["ell_pad"] == 156


def test_ell_attrs_only_where_the_jnp_solver_runs():
    X, _, _ = _data("ell")
    r_max = X.cols.shape[-1]
    want = {"ell_width": ell_width(r_max, K),
            "ell_pad": ell_width(r_max, K) - r_max}
    assert _ell_attrs(CoCoAConfig.adding(K), X) == want
    assert _ell_attrs(CoCoAConfig.adding(K, solver="sdca_sparse"), X) == want
    # unbatched under shard_map: the rows run as they are
    assert _ell_attrs(CoCoAConfig.adding(K, backend="shard_map"), X) == {
        "ell_width": r_max, "ell_pad": 0}
    # the Pallas kernel walks the shard's own slots
    assert _ell_attrs(CoCoAConfig.adding(K, solver="sdca_kernel"), X) == {}
    assert _ell_attrs(CoCoAConfig.adding(K), _data("dense")[0]) == {}


def _traced_solve_spans(kind, tmp_path):
    """The `cocoa_solve` and `cocoa_place` events of one traced vmap
    solve: [(name, stats)]."""
    X, y, mask = _data(kind)
    cfg = CoCoAConfig.adding(K, loss="hinge", lam=1e-3, H=32)
    with jax.profiler.trace(str(tmp_path)):
        solve(cfg, X, y, mask, rounds=1, seed=0)
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_serialized_xspace(
        pathlib.Path(path).read_bytes())
    return [(ev.name, dict(ev.stats)) for plane in pd.planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for ev in line.events
            if ev.name in ("cocoa_solve", "cocoa_place")]


@pytest.mark.parametrize("kind", ["dense", "ell"])
def test_solve_span_carries_the_certificate_slots(kind, tmp_path):
    """On ELL data `cocoa_solve` carries the slots the certificate walks
    a call and the shards' own, and the layout's build runs under its own
    `cocoa_place` span; on dense data there is neither."""
    events = _traced_solve_spans(kind, tmp_path)
    stats, = [s for n, s in events if n == "cocoa_solve"]
    builds = [s for n, s in events
              if n == "cocoa_place" and s.get("what") == "certificate_layout"]
    if kind == "dense":
        assert not {"cert_slots", "ell_slots"} & set(stats)
        assert not builds
        return
    X, _, _ = _data(kind)
    lay = jax.jit(sparse_data.cert_layout)(X)
    assert len(builds) == 1
    assert stats["ell_slots"] == int(np.prod(X.cols.shape))
    assert stats["cert_slots"] == int(lay.n_tiles) * lay.tile_slots


def test_certificate_layout_ops_are_not_the_certificate():
    """The layout's build labels its ops `cocoa/place/certificate_layout`,
    which the benchmark's substring match on `cocoa/certificate` does not
    count as the certificate."""
    X, y, mask = _data("ell")
    build = _layout_rows.lower(*jax.device_put((X, y, mask))).compile()
    op_names = set(re.findall(r'op_name="([^"]*)"', build.as_text()))
    assert any("cocoa/place/certificate_layout" in op for op in op_names)
    assert not any("cocoa/certificate" in op for op in op_names)
