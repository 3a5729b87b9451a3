"""Metric primitives, spans and fenced wall-clock timing, and the
per-round record.

Everything this reproduction claims is a statement about *gap vs. rounds
vs. communication vs. time*; the first three were always measured (the
duality certificate and `comm.CommTracer`) and this module adds the
fourth. Three layers:

  * `Histogram` -- an in-process sample distribution (no external deps;
    keeps raw samples so percentiles are exact at round-count scale).
  * spans and fenced timing -- `span` is a host span that lands in any
    active `jax.profiler` trace (a `TraceAnnotation`, on the same clock
    as the device planes) and keeps its own `perf_counter` seconds.
    `fenced_call` runs a JAX computation and blocks until every output
    buffer is ready before reading the clock, so the number is device
    wall-clock, not dispatch latency. `aot_compile` splits the one-time
    cost out of the steady-state per-round time under two spans,
    `cocoa_lower` (`jit(...).trace(args).lower()`) and `cocoa_compile`
    (`.compile()`: a backend compile or a persistent-cache load); the
    trainer and the benchmarks share these helpers, so their numbers are
    comparable by construction.
  * `RoundRecord` -- the frozen, schema-versioned record `core.cocoa.
    solve` emits once per certified round: the certificate triple, the
    wall-clock split (compile, of it lowering / execute / certificate /
    the solver's own host time), the wire plan (`hops` is
    `CommTracer.per_hop()` verbatim, `comm` its cumulative totals,
    `wire_floats` the measured-aware delta since the previous record),
    and the per-worker step budgets / EMA throughput when a
    `runtime.straggler.ThroughputTracker` is attached.

`validate_record` is the schema gate: the JSONL files `obs.events.
JsonlSink` writes are validated row-by-row in CI (`python -m
repro.obs.validate run.jsonl`).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional, Sequence, Tuple

import numpy as np

SCHEMA_VERSION = 2


# ----------------------------------------------------------------------------
# metric primitives
# ----------------------------------------------------------------------------

class Histogram:
    """Sample distribution with exact percentiles.

    Keeps the raw samples (rounds-scale cardinality, so memory is not a
    concern) and computes percentiles with numpy's linear interpolation
    -- the same definition the aggregator's p50/p99 report uses.
    """

    def __init__(self, name: str = ""):
        self.name = name
        self._samples: list = []

    def observe(self, value: float) -> None:
        self._samples.append(float(value))

    def __len__(self) -> int:
        return len(self._samples)

    @property
    def count(self) -> int:
        return len(self._samples)

    @property
    def sum(self) -> float:
        return float(np.sum(self._samples)) if self._samples else 0.0

    def percentile(self, q: float) -> float:
        if not self._samples:
            return float("nan")
        return float(np.percentile(self._samples, q))

    def summary(self) -> dict:
        if not self._samples:
            return {"count": 0, "sum": 0.0, "mean": float("nan"),
                    "p50": float("nan"), "p99": float("nan")}
        return {"count": self.count, "sum": self.sum,
                "mean": self.sum / self.count,
                "p50": self.percentile(50), "p99": self.percentile(99)}


# ----------------------------------------------------------------------------
# spans and fenced timing
# ----------------------------------------------------------------------------

class span:
    """Host span: `with span("cocoa_round", step=t) as s: ...`.

    Enters a `jax.profiler.TraceAnnotation(name, **attrs)` (a
    `StepTraceAnnotation` when `step` is given), so the span lies in any
    active profiler trace, nested, on the clock of the device planes; and
    reads `time.perf_counter()` at entry and exit, so `s.seconds` holds
    its length whether or not a trace is running. With the profiler off
    a span costs about a microsecond."""

    def __init__(self, name: str, *, step: Optional[int] = None, **attrs):
        import jax
        if step is None:
            self._annotation = jax.profiler.TraceAnnotation(name, **attrs)
        else:
            self._annotation = jax.profiler.StepTraceAnnotation(
                name, step_num=step, **attrs)
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        return False

    def annotate(self, **attrs) -> None:
        """Add attributes known only once the span has started."""
        self._annotation.set_metadata(**attrs)


def fenced_call(fn, *args, **kwargs):
    """Run `fn(*args)` and return `(out, seconds)` with the clock read
    only after `jax.block_until_ready` fenced every output buffer --
    device wall-clock, not async-dispatch latency. The one timing path
    shared by `solve`'s per-round split and the benchmarks."""
    import jax

    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    out = jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def fenced_time(fn, *args, iters: int = 3, warmup: int = 1, **kwargs):
    """Steady-state seconds per call: `warmup` unfenced-cost calls (first
    one pays compile), then the mean of `iters` fenced calls."""
    for _ in range(warmup):
        fenced_call(fn, *args, **kwargs)
    total = 0.0
    for _ in range(iters):
        _, dt = fenced_call(fn, *args, **kwargs)
        total += dt
    return total / max(iters, 1)


def aot_stages(jit_fn, *args, what: str = "program"):
    """AOT-compile `jit_fn` for `args` under two spans tagged `what`:
    `cocoa_lower` (Python tracing and lowering) and `cocoa_compile` (the
    backend compile, or a load from the persistent compile cache).
    Returns `(runnable, lower_s, compile_s)`. A lowering or compile
    error (a kernel the device's compiler refuses) is raised here, on
    the first attempt."""
    with span("cocoa_lower", what=what) as lo:
        lowered = jit_fn.trace(*args).lower()
    with span("cocoa_compile", what=what) as co:
        compiled = lowered.compile()
    return compiled, lo.seconds, co.seconds


def aot_compile(jit_fn, *args, what: str = "program"):
    """Split trace+compile out of execution: returns `(runnable,
    seconds)` where `runnable(*args)` is the AOT-compiled executable and
    `seconds` the one-time lowering + compile wall-clock (`aot_stages`
    keeps the two apart)."""
    compiled, lower_s, compile_s = aot_stages(jit_fn, *args, what=what)
    return compiled, lower_s + compile_s


# ----------------------------------------------------------------------------
# the per-round record
# ----------------------------------------------------------------------------

# field -> (type check, required). Kept next to the dataclass so the
# validator and the record can never drift apart.
_NUMERIC = (int, float)
_SCHEMA: dict = {
    "schema": (int,),
    "round": (int,),                # round index within this solve call
    "round_global": (int,),         # cumulative state.rounds (checkpoint-safe)
    "rounds_in_record": (int,),     # rounds covered since the last record
    "gap": _NUMERIC,
    "primal": _NUMERIC,
    "dual": _NUMERIC,
    "compile_s": _NUMERIC,          # lowering + compile (or cache load)
    "lower_s": _NUMERIC,            # of compile_s: tracing and lowering
    "execute_s": _NUMERIC,
    "certificate_s": _NUMERIC,
    "host_s": _NUMERIC,             # the solver's own host time
    "wire_floats": (int,),
    "wire_bytes": (int,),
    "hops": (list, tuple),
    "comm": (dict,),
    "budgets": (list, tuple, type(None)),
    "throughput": (list, tuple, type(None)),
}
_HOP_KEYS = ("hop", "axis", "messages", "floats_per_message", "floats",
             "bytes")
_COMM_KEYS = ("comm_vectors", "comm_floats", "comm_bytes", "comm_psums")


@dataclasses.dataclass(frozen=True)
class RoundRecord:
    """One certified round, frozen. `hops` is the tracer's `per_hop()`
    output verbatim (per-round wire plan, with `measured_floats` /
    `measured_floats_round` on observed hops); `comm` its cumulative
    `totals()`; `wire_floats` the totals delta since the previous record,
    so per-round *measured* volume (hier compressed gather) is visible
    round by round, not only as a running sum. `execute_s` sums the
    fenced round-step times since the previous record; `compile_s` is
    nonzero only on the record that paid a trace+compile, and `lower_s`
    is its tracing-and-lowering part (the rest is the backend compile or
    the compile-cache load). `host_s` is the host time of the `solve`
    call since the previous record (since entry, for the first) spent
    outside lowering, compiling, the rounds, the certificates and the
    caller's `on_round` hook: preparation, placement, record building
    and the loop's own lines."""
    round: int
    round_global: int
    rounds_in_record: int
    gap: float
    primal: float
    dual: float
    compile_s: float
    lower_s: float
    execute_s: float
    certificate_s: float
    host_s: float
    wire_floats: int
    wire_bytes: int
    hops: Tuple[dict, ...]
    comm: dict
    budgets: Optional[Tuple[int, ...]] = None
    throughput: Optional[Tuple[float, ...]] = None
    schema: int = SCHEMA_VERSION

    def to_dict(self) -> dict:
        """JSON-ready dict; key order is the schema's, stable across
        runs (the golden-record test pins it)."""
        out = {"schema": self.schema}
        for key in _SCHEMA:
            if key == "schema":
                continue
            val = getattr(self, key)
            if isinstance(val, tuple):
                val = list(val)
            out[key] = val
        return out

    @staticmethod
    def from_dict(d: dict) -> "RoundRecord":
        d = validate_record(d)
        kw = dict(d)
        kw["hops"] = tuple(dict(h) for h in d["hops"])
        for key in ("budgets", "throughput"):
            if d.get(key) is not None:
                kw[key] = tuple(d[key])
        return RoundRecord(**kw)


def validate_record(d: Any) -> dict:
    """Schema gate for one record dict; returns it or raises ValueError
    with the first violation. Checks the version, every field's presence
    and type, the per-hop row shape, and internal consistency
    (bytes = 4 * floats, lower_s <= compile_s, comm totals keys)."""
    if not isinstance(d, dict):
        raise ValueError(f"record must be a dict, got {type(d).__name__}")
    unknown = set(d) - set(_SCHEMA)
    if unknown:
        raise ValueError(f"unknown record fields: {sorted(unknown)}")
    for key, types in _SCHEMA.items():
        if key not in d:
            raise ValueError(f"record missing field {key!r}")
        if not isinstance(d[key], types) or isinstance(d[key], bool):
            raise ValueError(
                f"field {key!r} wants {'/'.join(t.__name__ for t in types)}, "
                f"got {type(d[key]).__name__}")
    if d["schema"] != SCHEMA_VERSION:
        raise ValueError(f"schema version {d['schema']} != {SCHEMA_VERSION}")
    if d["round"] < 1 or d["rounds_in_record"] < 1:
        raise ValueError("round and rounds_in_record must be >= 1")
    if d["round_global"] < d["round"]:
        raise ValueError("round_global cannot trail the in-call round")
    for t_key in ("compile_s", "lower_s", "execute_s", "certificate_s",
                  "host_s"):
        if not np.isfinite(d[t_key]) or d[t_key] < 0:
            raise ValueError(f"{t_key} must be finite and >= 0")
    if d["lower_s"] > d["compile_s"]:
        raise ValueError("lower_s cannot exceed compile_s")
    if d["wire_bytes"] != 4 * d["wire_floats"]:
        raise ValueError("wire_bytes must be 4 * wire_floats")
    for row in d["hops"]:
        if not isinstance(row, dict):
            raise ValueError("hops rows must be dicts")
        missing = [k for k in _HOP_KEYS if k not in row]
        if missing:
            raise ValueError(f"hop row missing {missing}: {row}")
    missing = [k for k in _COMM_KEYS if k not in d["comm"]]
    if missing:
        raise ValueError(f"comm totals missing {missing}")
    return d
