"""The program's spans and scopes as the benchmark reads them: a small
`solve` under `jax.profiler.trace` on the CPU, read back with
`bench.trace`, and the readers of `lower_s`, `solve_host_ms` and
`certificate_rmatvec_ms` on synthetic contexts."""
import glob
import os
import pathlib
import time
import types

import jax
import numpy as np
import pytest

from bench import spec, trace

HOST_SPANS = ("cocoa_solve", "cocoa_lower", "cocoa_compile", "cocoa_place",
              "cocoa_round", "cocoa_certificate", "cocoa_record",
              "cocoa_on_round")
SCOPES = ("cocoa/certificate/rmatvec", "cocoa/certificate/primal",
          "cocoa/certificate/dual")
FIXTURE = pathlib.Path(__file__).parent / "data" / "tiny_rcv1.xplane.pb"


def _solve(kind, rounds):
    from repro.core import CoCoAConfig, solve
    from repro.data import load, partition, partition_sparse
    from repro.obs import Aggregator, EventBus
    if kind == "dense":
        X, y = load("tiny")
        X, y, mask = partition(X, y, 4, seed=0)
    else:
        csr, y = load("tiny_sparse")
        X, y, mask = partition_sparse(csr, y, 4, seed=0)
    bus = EventBus()
    agg = bus.subscribe(Aggregator())
    solve(CoCoAConfig.adding(4, loss="hinge", lam=1e-3, H=32), X, y, mask,
          rounds=rounds, gap_every=1, seed=0, obs=bus,
          on_round=lambda t, state, gap: time.sleep(1e-3))
    return agg.records


@pytest.fixture(scope="module", params=["dense", "ell"])
def traced(request, tmp_path_factory):
    """One solve traced whole: its records, its host spans and its
    programs' instructions, read as a chip trace is read."""
    logdir = tmp_path_factory.mktemp(f"trace_{request.param}")
    with jax.profiler.trace(str(logdir)):
        records = _solve(request.param, rounds=3)
    path, = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                      recursive=True)
    raw = pathlib.Path(path).read_bytes()
    pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
    return records, trace._host_spans(pd), trace.program_ops(raw)


def test_every_host_span_lies_in_the_solve(traced):
    _, host, _ = traced
    assert set(HOST_SPANS) <= {n for _, _, n in host}
    (lo, hi), = [(s, e) for s, e, n in host if n == "cocoa_solve"]
    for name in ("cocoa_lower", "cocoa_compile", "cocoa_round",
                 "cocoa_certificate"):
        spans = [(s, e) for s, e, n in host if n == name]
        assert spans, name
        assert all(lo <= s <= e <= hi for s, e in spans), name
    # the round's lowering and the certificate's
    assert sum(n == "cocoa_lower" for _, _, n in host) == 2


def test_traced_rounds_are_their_fenced_seconds(traced):
    """A round's span on the profiler's clock lasts what `fenced_call`
    timed: the records and the trace share one clock."""
    records, host, _ = traced
    rounds = sorted((s, e) for s, e, n in host if n == "cocoa_round")
    assert len(rounds) == len(records) == 3
    for (s, e), rec in zip(rounds, records):
        assert rec.rounds_in_record == 1
        assert abs((e - s) - rec.execute_s) < 1e-3


def test_traced_certificate_carries_its_pass_scopes(traced):
    _, _, programs = traced
    op_names = {op for ops in programs.values() for _, op in ops.values()
                if "cocoa/certificate" in op}
    for scope in SCOPES:
        assert any(scope in op for op in op_names), scope


# ----------------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------------

def _read(name, ctx):
    return spec.load_reader(name)(ctx)


def _rec(round, **kw):
    return types.SimpleNamespace(round=round, rounds_in_record=1, **kw)


def test_lower_s_reads_the_warm_up():
    ctx = types.SimpleNamespace(warm_records=[
        _rec(1, compile_s=2.0, lower_s=0.75),
        _rec(2, compile_s=0.5, lower_s=0.25)])
    assert _read("lower_s", ctx) == pytest.approx(1.0)
    assert _read("lower_s", types.SimpleNamespace(warm_records=[])) is None
    # a program whose records lack the field reads nothing
    old = types.SimpleNamespace(warm_records=[_rec(1, compile_s=2.0)])
    assert _read("lower_s", old) is None


def test_solve_host_ms_leaves_out_the_traced_round():
    first = types.SimpleNamespace(traced_round=2, records=[
        _rec(1, host_s=0.010), _rec(2, host_s=0.500), _rec(3, host_s=0.020)])
    second = types.SimpleNamespace(traced_round=None, records=[
        types.SimpleNamespace(round=2, rounds_in_record=2, host_s=0.030)])
    ctx = types.SimpleNamespace(solves=[first, second])
    # (10 + 20 + 30) ms over 1 + 1 + 2 rounds
    assert _read("solve_host_ms", ctx) == pytest.approx(15.0)
    assert _read("solve_host_ms", types.SimpleNamespace(solves=[])) is None
    old = types.SimpleNamespace(solves=[types.SimpleNamespace(
        traced_round=None, records=[_rec(1, execute_s=0.1)])])
    assert _read("solve_host_ms", old) is None


def _reduced(intervals):
    """A one-chip `trace.Reduced` from (start, end, op_name) triples."""
    names = [("jit_wrapped(1)", f"fusion.{i}", "fusion", op)
             for i, (_, _, op) in enumerate(intervals)]
    chip = trace.Chip(np.array([s for s, _, _ in intervals]),
                      np.array([e for _, e, _ in intervals]),
                      np.arange(len(intervals), dtype=np.int64), names)
    busy = trace._union(chip.start, chip.end)
    return trace.Reduced(chips=[chip], busy_s=busy, window_s=1.0,
                         top_ops=[], idle=[])


def test_certificate_rmatvec_ms_reads_its_scope():
    red = _reduced([
        (0.100, 0.104, "jit(wrapped)/cocoa/certificate/rmatvec/dot_general"),
        (0.104, 0.105, "jit(wrapped)/cocoa/certificate/rmatvec/div"),
        (0.105, 0.109, "jit(wrapped)/cocoa/certificate/primal/dot_general"),
        (0.109, 0.110, "jit(wrapped)/cocoa/certificate/dual/reduce_sum"),
        (0.200, 0.300, "jit(round_fn)/cocoa/local_solve/while")])
    ctx = types.SimpleNamespace(trace=red)
    assert _read("certificate_rmatvec_ms", ctx) == pytest.approx(5.0)
    # certificate_ms still reads every pass of the certificate
    assert _read("certificate_ms", ctx) == pytest.approx(10.0)
    assert _read("certificate_rmatvec_ms",
                 types.SimpleNamespace(trace=None)) is None


def test_certificate_rmatvec_ms_on_a_trace_without_the_scope():
    # recorded before the certificate's passes had scopes of their own
    ctx = types.SimpleNamespace(trace=trace.reduce_file(str(FIXTURE)))
    assert _read("certificate_rmatvec_ms", ctx) is None
    assert _read("certificate_ms", ctx) > 0
