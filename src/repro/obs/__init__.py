"""Observability: structured round telemetry, timing traces, dashboards.

Every claim this reproduction makes is a statement about gap vs. rounds
vs. communication vs. *time*; this package owns the fourth axis and the
plumbing that carries all four out of a run:

    metrics   -- the `Histogram` primitive, host spans on the
                 profiler's clock (`span`), fenced wall-clock timing
                 (`fenced_call` / `aot_compile` split lowering and
                 compile from execute), and the frozen schema-versioned
                 `RoundRecord` `core.cocoa.solve` emits per certified
                 round
    events    -- the `EventBus` that generalizes `solve`'s single
                 `on_round` callback into composable sinks: `JsonlSink`
                 (one record per line), `Aggregator` (p50/p99 latency,
                 floats/sec, rounds-to-gap, the history view), and
                 `ProfilerSink` (jax.profiler trace with `cocoa/*`
                 named-scope regions)
    dashboard -- zero-dependency live terminal dashboard
                 (`cocoa_train --dashboard`): gap trajectory, per-hop
                 wire rates, per-worker throughput, redrawn in place
    validate  -- `python -m repro.obs.validate run.jsonl` schema gate
                 (the CI smoke step for `cocoa_train --metrics-out`);
                 also validates KernelProfile streams and the
                 cross-schema `round_global` pairing (`--prof`)
    prof      -- the compute-side twin of the wire accounting: frozen
                 `KernelProfile` records pairing fenced measured
                 wall-clock with the analytic HLO cost (flops / HBM
                 bytes / collective bytes via `launch.hlo_analysis`)
                 and its roofline placement on a pluggable
                 `HardwareSpec`
    regress   -- `python -m repro.obs.regress` perf-regression gate:
                 latest bench-history run vs a pinned baseline with a
                 noise band; nonzero exit on regression

`solve`'s history is a thin view over this bus (`Aggregator.history()`),
and the benchmarks time through the same fenced helpers, so trainer and
bench numbers are comparable by construction.

What a `jax.profiler` trace of `solve` shows:

    host spans    cocoa_solve (the call), and inside it cocoa_lower and
                  cocoa_compile (what=round|certificate), cocoa_place,
                  cocoa_round (a step annotation per round),
                  cocoa_certificate, cocoa_record, cocoa_on_round
    device scopes cocoa/local_solve, cocoa/exchange, cocoa/certificate
                  and within it cocoa/certificate/rmatvec (v = A alpha /
                  (lambda n)), .../primal (the margins Xw and P(w)) and
                  .../dual (D(alpha))

Each record carries the spans' totals: `compile_s` (lowering + compile),
`lower_s` (of it, lowering), `execute_s` (rounds), `certificate_s`, and
`host_s` (the rest of the call, less the caller's hook).
"""
from .dashboard import Dashboard, sparkline
from .events import Aggregator, EventBus, JsonlSink, ProfilerSink
from .metrics import (SCHEMA_VERSION, Histogram, RoundRecord, aot_compile,
                      aot_stages, fenced_call, fenced_time, span,
                      validate_record)
from .prof import (PROF_SCHEMA_VERSION, HardwareSpec, KernelProfile,
                   RoundProfileSink, build_profile, get_hardware, profile_fn,
                   validate_profile)
