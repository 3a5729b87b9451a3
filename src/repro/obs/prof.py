"""Kernel performance observatory: analytic-vs-measured profiles.

The paper's cost model is rounds x (communication + local computation).
`RoundRecord` made the communication half observable (per-hop wire
accounting); this module is the computation half -- the compute-side twin
of `comm.CommTracer.per_hop()`:

  * `HardwareSpec` -- the peak constants a roofline is stated against
    (FLOP/s, HBM bytes/s, interconnect bytes/s), keyed by the device's
    `device_kind` with their published source, plus CPU-host defaults so
    the CPU tests produce sane achieved fractions.
  * `KernelProfile` -- frozen, schema-versioned (like `RoundRecord`): one
    profiled computation, carrying the *measured* fenced wall-clock next
    to the *analytic* cost extracted from its lowered post-optimization
    HLO (`launch.hlo_analysis`: dot + elementwise FLOPs, HBM bytes,
    collective wire bytes), the three roofline time terms on a
    `HardwareSpec`, and the achieved-vs-peak fractions. `model_vs_measured`
    = analytic bound / measured wall is the per-record analytic-vs-measured
    cost model: ~1 means the model prices the computation honestly, << 1
    means overheads the model does not see.
  * `profile_fn` -- the harness: lower+compile, extract analytic cost,
    fenced steady-state timing (`metrics.fenced_time`), assemble the
    profile. `build_profile` is the pure assembly step (testable on a
    golden HLO text without compiling anything).
  * `RoundProfileSink` -- an `EventBus` sink pairing the two streams: for
    every `RoundRecord` it emits one `KernelProfile` (kind="round") whose
    wall-clock is the record's fenced per-round execute time and whose
    analytic cost is the lowered round fn's, sharing `round_global` so
    `repro.obs.validate --prof` can check cross-schema consistency.

Validate a profile JSONL with `python -m repro.obs.validate run.prof.jsonl`
(the CLI sniffs the schema by the `kind` field).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import numpy as np

from .metrics import fenced_time

PROF_SCHEMA_VERSION = 1


# ----------------------------------------------------------------------------
# hardware peaks
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Peak rates a roofline fraction is stated against. `ici_bw` prices
    the collective term (bytes/s per link for TPU ICI; loopback-ish for a
    host CPU mesh, where collectives are memcpys)."""
    name: str
    peak_flops: float           # FLOP/s per device
    hbm_bw: float               # bytes/s per device
    ici_bw: float               # bytes/s per link

    def roofline(self, flops: float, hbm_bytes: float,
                 collective_bytes: float) -> dict:
        """The three analytic time terms, their max (perfect-overlap
        bound), and the dominant term's name."""
        t_c = flops / self.peak_flops
        t_m = hbm_bytes / self.hbm_bw
        t_x = collective_bytes / self.ici_bw
        dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
                  key=lambda kv: kv[1])
        return {"t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
                "bound_s": dom[1], "dominant": dom[0]}


# one x86 host core with AVX FMA is O(100) GFLOP/s f32 and O(20) GB/s to
# DRAM -- honest single-process defaults for the CPU tests, so their
# achieved fractions land at plausible (sub-1) values. Never a device peak.
CPU_HOST = HardwareSpec("cpu_host", peak_flops=1e11, hbm_bw=2e10,
                        ici_bw=1e10)
# per chip, named by jax's `device_kind`. Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
# inter-chip interconnect (= 200e9 bytes/s)
TPU_V5E = HardwareSpec("TPU v5 lite", peak_flops=197e12, hbm_bw=819e9,
                       ici_bw=200e9)

HARDWARE = {h.name: h for h in (CPU_HOST, TPU_V5E)}


def get_hardware(name: str) -> HardwareSpec:
    if name not in HARDWARE:
        raise KeyError(f"unknown hardware spec {name!r}; "
                       f"have {sorted(HARDWARE)}")
    return HARDWARE[name]


def default_hardware() -> HardwareSpec:
    """The peaks of the device JAX runs on, looked up by `device_kind`
    (CPU_HOST on the CPU backend). A device missing from the table is an
    error, never a stand-in."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return CPU_HOST
    return get_hardware(dev.device_kind)


# ----------------------------------------------------------------------------
# the profile record
# ----------------------------------------------------------------------------

_NUMERIC = (int, float)
_PROF_SCHEMA: dict = {
    "schema": (int,),
    "kind": (str,),                 # "kernel" | "round"
    "name": (str,),                 # what was profiled (sparse_sdca, ...)
    "backend": (str,),              # jax.default_backend() at measure time
    "hw": (str,),                   # HardwareSpec the fractions use
    "shape": (dict,),               # free-form static params (nk, d, ...)
    "iters": (int,),                # fenced timing iterations
    "wall_s": _NUMERIC,             # measured fenced seconds per call
    "compile_s": _NUMERIC,          # one-time lower+compile seconds
    "flops": _NUMERIC,              # analytic: dot + elementwise
    "dot_flops": _NUMERIC,
    "hbm_bytes": _NUMERIC,
    "collective_bytes": _NUMERIC,   # per-device wire bytes (ring model)
    "t_compute_s": _NUMERIC,        # three-term analytic roofline on hw
    "t_memory_s": _NUMERIC,
    "t_collective_s": _NUMERIC,
    "bound_s": _NUMERIC,            # max of the three (perfect overlap)
    "dominant": (str,),
    "achieved_flops": _NUMERIC,     # flops / wall_s
    "achieved_bw": _NUMERIC,        # hbm_bytes / wall_s
    "flops_frac": _NUMERIC,         # achieved_flops / hw peak
    "bw_frac": _NUMERIC,            # achieved_bw / hw peak
    "model_vs_measured": _NUMERIC,  # bound_s / wall_s  (1 = honest model)
    "round_global": (int, type(None)),  # round profiles: the paired
                                        # RoundRecord's round_global
}
_PROF_KINDS = ("kernel", "round")


@dataclasses.dataclass(frozen=True)
class KernelProfile:
    """One profiled computation, frozen: measured wall-clock next to the
    analytic HLO cost and its roofline placement on a `HardwareSpec`."""
    kind: str
    name: str
    backend: str
    hw: str
    shape: dict
    iters: int
    wall_s: float
    compile_s: float
    flops: float
    dot_flops: float
    hbm_bytes: float
    collective_bytes: float
    t_compute_s: float
    t_memory_s: float
    t_collective_s: float
    bound_s: float
    dominant: str
    achieved_flops: float
    achieved_bw: float
    flops_frac: float
    bw_frac: float
    model_vs_measured: float
    round_global: Optional[int] = None
    schema: int = PROF_SCHEMA_VERSION

    def to_dict(self) -> dict:
        out = {"schema": self.schema}
        for key in _PROF_SCHEMA:
            if key == "schema":
                continue
            out[key] = getattr(self, key)
        return out

    @staticmethod
    def from_dict(d: dict) -> "KernelProfile":
        return KernelProfile(**validate_profile(d))


def validate_profile(d: Any) -> dict:
    """Schema gate for one profile dict; returns it or raises ValueError
    with the first violation (mirrors `metrics.validate_record`)."""
    if not isinstance(d, dict):
        raise ValueError(f"profile must be a dict, got {type(d).__name__}")
    unknown = set(d) - set(_PROF_SCHEMA)
    if unknown:
        raise ValueError(f"unknown profile fields: {sorted(unknown)}")
    for key, types in _PROF_SCHEMA.items():
        if key not in d:
            raise ValueError(f"profile missing field {key!r}")
        if not isinstance(d[key], types) or isinstance(d[key], bool):
            raise ValueError(
                f"field {key!r} wants {'/'.join(t.__name__ for t in types)}, "
                f"got {type(d[key]).__name__}")
    if d["schema"] != PROF_SCHEMA_VERSION:
        raise ValueError(
            f"profile schema {d['schema']} != {PROF_SCHEMA_VERSION}")
    if d["kind"] not in _PROF_KINDS:
        raise ValueError(f"kind must be one of {_PROF_KINDS}, "
                         f"got {d['kind']!r}")
    for key in ("wall_s", "compile_s", "flops", "dot_flops", "hbm_bytes",
                "collective_bytes", "bound_s"):
        if not np.isfinite(d[key]) or d[key] < 0:
            raise ValueError(f"{key} must be finite and >= 0")
    if d["iters"] < 1:
        raise ValueError("iters must be >= 1")
    if d["dot_flops"] > d["flops"]:
        raise ValueError("dot_flops cannot exceed total flops")
    if d["kind"] == "round" and d["round_global"] is None:
        raise ValueError("round profiles must carry round_global")
    return d


# ----------------------------------------------------------------------------
# assembly + the measuring harness
# ----------------------------------------------------------------------------

def build_profile(name: str, stats: dict, wall_s: float, *,
                  kind: str = "kernel", backend: Optional[str] = None,
                  hw: Optional[HardwareSpec] = None, shape: dict = None,
                  iters: int = 1, compile_s: float = 0.0,
                  round_global: Optional[int] = None) -> KernelProfile:
    """Assemble a `KernelProfile` from `launch.hlo_analysis.full_stats`
    output + a measured wall-clock. Pure (no compiling, no timing), so
    the golden-HLO test drives it from a fixed module text."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    hw = hw or default_hardware()
    flops = float(stats.get("flops", stats.get("dot_flops", 0.0)))
    hbm = float(stats["hbm_bytes"])
    coll = float(stats.get("collective_wire_bytes", 0.0))
    roof = hw.roofline(flops, hbm, coll)
    wall = float(wall_s)
    achieved_f = flops / wall if wall > 0 else 0.0
    achieved_b = hbm / wall if wall > 0 else 0.0
    return KernelProfile(
        kind=kind, name=name, backend=backend, hw=hw.name,
        shape=dict(shape or {}), iters=int(iters), wall_s=wall,
        compile_s=float(compile_s), flops=flops,
        dot_flops=float(stats.get("dot_flops", 0.0)), hbm_bytes=hbm,
        collective_bytes=coll, round_global=round_global,
        achieved_flops=achieved_f, achieved_bw=achieved_b,
        flops_frac=achieved_f / hw.peak_flops,
        bw_frac=achieved_b / hw.hbm_bw,
        model_vs_measured=roof["bound_s"] / wall if wall > 0 else 0.0,
        **roof)


def analyze_jit(fn, *args) -> tuple:
    """Lower+compile `fn(*args)` and return `(compiled, stats, compile_s)`
    where `stats` is `hlo_analysis.full_stats` of the post-optimization
    module. `fn` may be a plain callable (jitted here) or already a
    `jax.jit` wrapper."""
    import jax

    from repro.launch.hlo_analysis import stats_of_compiled

    jf = fn if hasattr(fn, "lower") else jax.jit(fn)
    t0 = time.perf_counter()
    compiled = jf.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    return compiled, stats_of_compiled(compiled), compile_s


def profile_fn(fn, *args, name: str, shape: dict = None,
               hw: Optional[HardwareSpec] = None, iters: int = 3,
               warmup: int = 1, kind: str = "kernel") -> KernelProfile:
    """The harness: analytic cost from the lowered HLO + fenced
    steady-state wall-clock of the compiled executable, in one record."""
    compiled, stats, compile_s = analyze_jit(fn, *args)
    wall = fenced_time(compiled, *args, iters=iters, warmup=warmup)
    return build_profile(name, stats, wall, kind=kind, hw=hw, shape=shape,
                         iters=iters, compile_s=compile_s)


# ----------------------------------------------------------------------------
# pairing with the RoundRecord stream
# ----------------------------------------------------------------------------

class RoundProfileSink:
    """EventBus sink that mirrors each `RoundRecord` with a `KernelProfile`
    (kind="round"): measured wall is the record's fenced per-round execute
    time; the analytic cost is the lowered round step's `full_stats`
    (computed once by the caller -- `cocoa_train --profile`). The two
    streams share `round_global`, the consistency key
    `repro.obs.validate --prof` checks."""

    def __init__(self, path, stats: dict, *, name: str = "cocoa_round",
                 hw: Optional[HardwareSpec] = None, shape: dict = None,
                 compile_s: float = 0.0):
        from .events import JsonlSink
        self._sink = JsonlSink(path)
        self.path = self._sink.path
        self.stats = stats
        self.name = name
        self.hw = hw or default_hardware()
        self.shape = dict(shape or {})
        self._compile_s = compile_s          # reported on the first profile
        self.profiles = []

    def emit(self, record) -> None:
        wall = record.execute_s / max(record.rounds_in_record, 1)
        prof = build_profile(
            self.name, self.stats, wall, kind="round", hw=self.hw,
            shape=self.shape, iters=record.rounds_in_record,
            compile_s=self._compile_s, round_global=record.round_global)
        self._compile_s = 0.0
        self.profiles.append(prof)
        self._sink.emit(prof)

    def close(self) -> None:
        self._sink.close()
