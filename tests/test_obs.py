"""Observability layer: record schema, event bus, aggregator, dashboard.

The contract under test is the ISSUE-6 acceptance bar: `solve` emits one
schema-valid `RoundRecord` per certified round with nonzero fenced
execute time, the per-hop wire plan in each record is the tracer's
`per_hop()` verbatim, and the history `solve` returns is *derived from*
the bus (an external `Aggregator` subscribed to the same bus rebuilds it
bit-for-bit).
"""
import io
import json
import pathlib

import numpy as np
import pytest

from repro import comm
from repro.core import CoCoAConfig, solve
from repro.data import load, partition
from repro.obs import (Aggregator, Dashboard, EventBus, Histogram,
                       JsonlSink, RoundRecord, SCHEMA_VERSION, fenced_call,
                       sparkline, validate_record)
from repro.obs.validate import validate_file


def make_record(round=1, round_global=None, gap=0.5, execute_s=1e-3,
                **kw):
    hops = kw.pop("hops", ({"hop": "reduce", "axis": "data", "messages": 4,
                            "floats_per_message": 64, "floats": 256,
                            "bytes": 1024},))
    wire = kw.pop("wire_floats", 256)
    return RoundRecord(
        round=round, round_global=round_global or round,
        rounds_in_record=kw.pop("rounds_in_record", 1), gap=gap,
        primal=gap + 0.1, dual=0.1, compile_s=kw.pop("compile_s", 0.0),
        lower_s=kw.pop("lower_s", 0.0), execute_s=execute_s,
        certificate_s=kw.pop("certificate_s", 1e-4),
        host_s=kw.pop("host_s", 2e-5),
        wire_floats=wire, wire_bytes=4 * wire, hops=hops,
        comm={"comm_vectors": 4 * round, "comm_floats": 256 * round,
              "comm_bytes": 1024 * round, "comm_psums": round}, **kw)


# ----------------------------------------------------------------------------
# schema: round-trip, golden key order, rejection cases
# ----------------------------------------------------------------------------

def test_record_roundtrip_json():
    rec = make_record(round=3, round_global=7, budgets=(64, 16, 64, 64),
                      throughput=(1e4, 1e3, 1e4, 1e4))
    d = json.loads(json.dumps(rec.to_dict()))
    back = RoundRecord.from_dict(d)
    assert back == rec
    assert isinstance(back.hops, tuple) and isinstance(back.budgets, tuple)


def test_record_golden_key_order():
    """The JSONL field order is part of the schema: downstream parsers and
    the golden files CI diffs rely on it being stable across runs."""
    keys = list(make_record().to_dict())
    assert keys == ["schema", "round", "round_global", "rounds_in_record",
                    "gap", "primal", "dual", "compile_s", "lower_s",
                    "execute_s", "certificate_s", "host_s", "wire_floats",
                    "wire_bytes", "hops", "comm", "budgets", "throughput"]
    assert make_record().to_dict()["schema"] == SCHEMA_VERSION


@pytest.mark.parametrize("mutate,msg", [
    (lambda d: d.pop("gap"), "missing field"),
    (lambda d: d.update(gap="0.5"), "wants"),
    (lambda d: d.update(round=True), "wants"),          # bools are not ints
    (lambda d: d.update(schema=99), "schema version"),
    (lambda d: d.update(extra=1), "unknown record fields"),
    (lambda d: d.update(round=0), ">= 1"),
    (lambda d: d.update(round_global=0), "round_global"),
    (lambda d: d.update(execute_s=-1.0), "finite and >= 0"),
    (lambda d: d.update(execute_s=float("nan")), "finite and >= 0"),
    (lambda d: d.update(compile_s=0.5, lower_s=0.75), "cannot exceed"),
    (lambda d: d.update(lower_s=-1e-3), "finite and >= 0"),
    (lambda d: d.update(host_s=-1e-3), "finite and >= 0"),
    (lambda d: d.pop("host_s"), "missing field"),
    (lambda d: d.update(wire_bytes=1), "4 \\* wire_floats"),
    (lambda d: d.update(hops=[{"hop": "reduce"}]), "hop row missing"),
    (lambda d: d.update(comm={}), "comm totals missing"),
])
def test_validate_record_rejects(mutate, msg):
    d = make_record().to_dict()
    mutate(d)
    with pytest.raises(ValueError, match=msg):
        validate_record(d)


def test_validate_file_catches_bad_line_and_regression(tmp_path):
    p = tmp_path / "run.jsonl"
    good = make_record(round=2, round_global=2).to_dict()
    p.write_text(json.dumps(good) + "\n" + "{not json}\n")
    with pytest.raises(ValueError, match=r"run\.jsonl:2"):
        validate_file(str(p))
    # round_global must be monotone across solve segments
    p.write_text(json.dumps(make_record(round=4, round_global=4).to_dict())
                 + "\n" + json.dumps(good) + "\n")
    with pytest.raises(ValueError, match="strictly increasing"):
        validate_file(str(p))
    # --require-timing rejects unfenced records
    zero = make_record(execute_s=0.0).to_dict()
    p.write_text(json.dumps(zero) + "\n")
    with pytest.raises(ValueError, match="execute_s"):
        validate_file(str(p), require_timing=True)
    p.write_text("")
    with pytest.raises(ValueError, match="no records"):
        validate_file(str(p))


# ----------------------------------------------------------------------------
# metric primitives
# ----------------------------------------------------------------------------

def test_primitives():
    h = Histogram("lat")
    samples = [0.4, 0.1, 0.9, 0.2, 0.7, 0.3]
    for s in samples:
        h.observe(s)
    # exact percentiles: numpy linear interpolation is the definition
    assert h.percentile(50) == pytest.approx(np.percentile(samples, 50))
    assert h.percentile(99) == pytest.approx(np.percentile(samples, 99))
    assert h.summary()["count"] == len(samples)
    assert np.isnan(Histogram().percentile(50))


def test_fenced_call_blocks_and_times():
    import jax.numpy as jnp
    out, dt = fenced_call(lambda x: x * 2, jnp.arange(8))
    assert dt >= 0 and int(out[3]) == 6


# ----------------------------------------------------------------------------
# bus + sinks
# ----------------------------------------------------------------------------

def test_event_bus_ordering_and_close():
    bus = EventBus()
    order = []

    class Sink:
        def __init__(self, name):
            self.name = name

        def emit(self, rec):
            order.append(("emit", self.name, rec.round))

        def close(self):
            order.append(("close", self.name, None))

    bus.subscribe(Sink("a"))
    bus.subscribe(lambda rec: order.append(("emit", "fn", rec.round)))
    bus.subscribe(Sink("b"))
    bus.emit(make_record(round=1))
    bus.emit(make_record(round=2, round_global=2))
    bus.close()
    assert bus.emitted == 2
    # fan-out in subscription order, every record to every sink; close
    # walks the same order (callables have no close)
    assert order == [("emit", "a", 1), ("emit", "fn", 1), ("emit", "b", 1),
                     ("emit", "a", 2), ("emit", "fn", 2), ("emit", "b", 2),
                     ("close", "a", None), ("close", "b", None)]
    with pytest.raises(TypeError):
        bus.subscribe(object())


def test_jsonl_sink_one_line_per_record(tmp_path):
    p = tmp_path / "out" / "run.jsonl"          # parent dir auto-created
    sink = JsonlSink(p)
    recs = [make_record(round=i, round_global=i, gap=1.0 / i)
            for i in (1, 2, 3)]
    for r in recs:
        sink.emit(r)
    sink.close()
    lines = p.read_text().splitlines()
    assert len(lines) == 3
    assert [RoundRecord.from_dict(json.loads(ln)) for ln in lines] == recs
    assert validate_file(str(p), require_timing=True) == 3


def test_aggregator_rollups():
    agg = Aggregator()
    # gap_every=2 shape: each record covers 2 rounds of fenced time
    agg.emit(make_record(round=2, rounds_in_record=2, execute_s=0.4,
                         gap=0.5, compile_s=1.0, wire_floats=512))
    agg.emit(make_record(round=4, round_global=4, rounds_in_record=2,
                         execute_s=0.2, gap=0.05, wire_floats=512))
    assert agg.rounds == 4 and agg.final_gap == 0.05
    assert agg.total_compile_s == 1.0
    assert agg.total_execute_s == pytest.approx(0.6)
    assert agg.total_wire_floats == 1024
    assert agg.floats_per_sec() == pytest.approx(1024 / 0.6)
    # latency histogram weights rounds equally: samples [.2,.2,.1,.1]
    assert agg.round_latency_s.count == 4
    assert agg.summary()["round_p50_s"] == pytest.approx(
        np.percentile([0.2, 0.2, 0.1, 0.1], 50))
    assert agg.rounds_to_gap(0.1) == 4 and agg.rounds_to_gap(1e-9) is None
    assert "gap=5.000e-02 at round 4" in agg.format_summary()
    assert Aggregator().format_summary() == "obs: no certified rounds recorded"


# ----------------------------------------------------------------------------
# solve() integration: history IS the bus-derived view
# ----------------------------------------------------------------------------

def test_solve_history_is_bus_view():
    X, y = load("tiny")
    K = 4
    Xp, yp, mk = partition(X, y, K, seed=0)
    cfg = CoCoAConfig.adding(K, loss="hinge", lam=1e-3, H=32)
    bus = EventBus()
    agg = bus.subscribe(Aggregator())
    seen = bus.subscribe(lambda rec: None)
    r = solve(cfg, Xp, yp, mk, rounds=7, gap_every=3, seed=0, obs=bus)

    # one record per certified round: gap checkpoints at 3, 6 and the
    # unconditional final round
    assert [rec.round for rec in agg.records] == [3, 6, 7]
    assert [rec.rounds_in_record for rec in agg.records] == [3, 3, 1]
    # the external aggregator rebuilds solve's return value bit-for-bit
    assert agg.history() == r.history
    # fenced timing: every record carries real execute time; only the
    # first paid trace+compile
    assert all(rec.execute_s > 0 for rec in agg.records)
    assert agg.records[0].compile_s >= 0
    assert all(rec.compile_s == 0 for rec in agg.records[1:])
    # the wire plan is the tracer's per_hop() verbatim
    tr = comm.CommTracer.for_run(K=K, d_local=X.shape[1])
    assert all(list(rec.hops) == tr.per_hop() for rec in agg.records)
    # wire deltas tile the cumulative totals
    assert sum(rec.wire_floats for rec in agg.records) \
        == agg.records[-1].comm["comm_floats"]
    for rec in agg.records:
        validate_record(rec.to_dict())


def test_solve_emits_budgets_and_throughput():
    from repro.runtime import straggler

    X, y = load("tiny")
    K = 4
    Xp, yp, mk = partition(X, y, K, seed=0)
    cfg = CoCoAConfig.adding(K, loss="hinge", lam=1e-3, H=64,
                             solver="sdca_deadline")
    slow = np.ones(K)
    slow[2] = 10.0                           # simulated straggler, measured clock
    tracker = straggler.ThroughputTracker(K, slowdown=slow)
    budget_fn = straggler.budget_fn_from_tracker(tracker, deadline_s=1e-3,
                                                 H_max=64, H_min=16)
    bus = EventBus()
    agg = bus.subscribe(Aggregator())
    solve(cfg, Xp, yp, mk, rounds=4, gap_every=2, seed=0, obs=bus,
          budget_fn=budget_fn, throughput=tracker)
    rec = agg.last
    assert rec.budgets is not None and len(rec.budgets) == K
    assert rec.throughput is not None and len(rec.throughput) == K
    # the slowdown shows up in the measured EMA: worker 2 is 10x slower
    assert rec.throughput[2] < rec.throughput[0]
    validate_record(rec.to_dict())


def test_solve_eps_break_records_final_round():
    X, y = load("tiny")
    Xp, yp, mk = partition(X, y, 4, seed=0)
    cfg = CoCoAConfig.adding(4, loss="hinge", lam=1e-3, H=512)
    bus = EventBus()
    agg = bus.subscribe(Aggregator())
    r = solve(cfg, Xp, yp, mk, rounds=50, gap_every=1, seed=0, eps_gap=0.3,
              obs=bus)
    assert agg.final_gap <= 0.3
    assert agg.records[-1].round == r.history["round"][-1] < 50


# ----------------------------------------------------------------------------
# dashboard
# ----------------------------------------------------------------------------

def test_sparkline_scaling():
    assert sparkline([]) == ""
    assert sparkline([1.0, 1.0]) == "▄▄"              # flat series mid-block
    s = sparkline([0.0, 1.0, 2.0, 3.0])
    assert len(s) == 4 and s[0] == "▁" and s[-1] == "█"
    assert len(sparkline(list(range(100)), width=48)) == 48


def test_dashboard_plain_stream():
    out = io.StringIO()
    db = Dashboard(out=out, total_rounds=6)
    db.emit(make_record(round=2, rounds_in_record=2, compile_s=0.9))
    db.emit(make_record(round=4, round_global=4, rounds_in_record=2,
                        gap=0.25))
    db.close()
    lines = out.getvalue().splitlines()
    assert len(lines) == 2                    # piped: one line per record
    assert "round 2: gap=5.000e-01" in lines[0]
    assert "compile_s=0.90" in lines[0]
    assert "wire_floats=256" in lines[1]
    assert "\x1b[" not in out.getvalue()      # no ANSI when not a tty


class _FakeTty(io.StringIO):
    def isatty(self):
        return True


def test_dashboard_tty_redraws_in_place():
    out = _FakeTty()
    db = Dashboard(out=out, total_rounds=8)
    hop = {"hop": "inter_gather", "axis": "data", "messages": 2,
           "floats_per_message": 64, "floats": 128, "bytes": 512,
           "measured_floats": 100, "measured_floats_round": 60}
    db.emit(make_record(round=2, rounds_in_record=2, gap=0.5,
                        budgets=(64, 16, 64, 64),
                        throughput=tuple(1e4 if i != 1 else 1e3
                                         for i in range(4)),
                        hops=(hop,)))
    first = out.getvalue()
    assert "\x1b[" not in first.split("\n", 1)[0].replace(
        "\x1b[1m", "").replace("\x1b[0m", "").replace("\x1b[2m", "")
    assert "round 2/8" in first and "measured 60" in first
    assert "w1 █ 1e+03@16" in first            # straggler bar + budget
    db.emit(make_record(round=4, round_global=4, rounds_in_record=2,
                        gap=0.05, hops=(hop,)))
    second = out.getvalue()[len(first):]
    # in-place redraw: cursor up over the previous block, then clear
    assert second.startswith(f"\x1b[{first.count(chr(10))}F\x1b[0J")
    db.close()


def test_dashboard_folds_many_workers():
    out = io.StringIO()
    db = Dashboard(out=out)
    rec = make_record(throughput=tuple(float(i + 1) for i in range(12)))
    lines = db._render(rec)
    thru = [ln for ln in lines if ln.startswith("thru")][0]
    assert "+4 more" in thru and "w8" not in thru


# ----------------------------------------------------------------------------
# shim hygiene (satellite: DeprecationWarning-free suite)
# ----------------------------------------------------------------------------

def test_no_src_importers_of_optim_compress_shim():
    """Nothing under src/ may import the deprecated repro.optim.compress
    shim (it warns on import; `-W error::DeprecationWarning` runs must
    stay clean). The shim file itself is the only mention allowed."""
    import re

    pat = re.compile(r"^\s*(import\s+repro\.optim\.compress"
                     r"|from\s+repro\.optim\.compress\s+import"
                     r"|from\s+repro\.optim\s+import\s+.*\bcompress\b"
                     r"|from\s+\.\s*import\s+.*\bcompress\b"
                     r"|from\s+\.compress\s+import)", re.M)
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    offenders = []
    for p in (src / "repro").rglob("*.py"):
        if p.parent.name == "optim" and p.name == "compress.py":
            continue
        rel = str(p.relative_to(src))
        hits = pat.findall(p.read_text())
        # comm/* legitimately does `from .compress import ...` -- that is
        # the real module, not the shim
        if hits and not rel.startswith("repro/comm/"):
            offenders.append(rel)
    assert not offenders, f"import repro.comm.compress instead: {offenders}"
