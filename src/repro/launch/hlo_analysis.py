"""Post-SPMD HLO analysis for the roofline terms.

XLA:CPU's `compiled.cost_analysis()` counts every while body ONCE -- with
scan-over-layers that understates FLOPs/bytes by ~n_layers x. So we analyze
`compiled.as_text()` ourselves:

  * computations + call graph (while bodies/conds, fusions, calls) with
    execution multipliers; while trip counts come from the constant in the
    loop-condition computation (scan loops compare induction var < N),
  * FLOPs: 2 * prod(result dims) * prod(lhs contracting dims) per `dot`
    (matmuls dominate; elementwise flops are ignored and stated as such),
  * HBM bytes: sum of (result + operand) bytes of top-level instructions that
    actually move memory (fusions, dots, copies, scatters/gathers,
    collectives, ...); bitcasts / GTEs / tuples are free,
  * collective wire bytes per device (ring model):
      all-reduce 2*b*(g-1)/g | all-gather b_out*(g-1)/g |
      reduce-scatter b_result*(g-1) | all-to-all b*(g-1)/g |
      collective-permute b.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_FREE_OPS = {"bitcast", "get-tuple-element", "tuple", "parameter", "constant",
             "after-all", "reshape", "add-dependency", "opt-barrier",
             "partition-id", "replica-id"}

_INSTR_RE = re.compile(
    r"^(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(\(?.*?\)?)\s*([a-z][a-z0-9\-\.]*)\(")


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _first_shape_dims(text: str) -> Tuple[str, List[int]]:
    m = _SHAPE_RE.search(text)
    if not m:
        return "", []
    dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
    return m.group(1), dims


def _group_size(line: str) -> int:
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        ids = [x for x in m.group(1).split(",") if x.strip() != ""]
        return max(1, len(ids))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)   # iota [n,g]
    if m:
        return max(1, int(m.group(2)))
    if "source_target_pairs" in line:
        return 2
    return 2


class HloModule:
    """Parsed post-optimization HLO text with execution multipliers."""

    def __init__(self, hlo: str):
        self.comps: Dict[str, List[str]] = {}
        self.entry = None
        cur = None
        for raw in hlo.splitlines():
            line = raw.strip()
            # computation header: "%name (params...) -> type {" (param lists
            # may contain nested parens -> match on suffix/prefix shape only)
            if (line.endswith("{") and "->" in line
                    and "=" not in line.split("(", 1)[0]):
                tok = line.split()[0]
                is_entry = tok == "ENTRY"
                name = (line.split()[1] if is_entry else tok).lstrip("%")
                cur = name
                self.comps[cur] = []
                if is_entry:
                    self.entry = cur
                continue
            if line == "}":
                cur = None
                continue
            if cur is not None and line and not line.startswith("//"):
                self.comps[cur].append(line)
        if self.entry is None and self.comps:
            self.entry = next((n for n in self.comps if "main" in n),
                              list(self.comps)[0])

        # name -> result type text (for operand shape lookup)
        self.shape_of: Dict[str, str] = {}
        for lines in self.comps.values():
            for ln in lines:
                m = _INSTR_RE.match(ln)
                if m:
                    self.shape_of[m.group(1)] = m.group(2)

        self._build_multipliers()

    def _trip_count(self, cond_name: str) -> int:
        best = 1
        for ln in self.comps.get(cond_name, []):
            for m in re.finditer(r"constant\((\d+)\)", ln):
                best = max(best, int(m.group(1)))
        return best

    def _build_multipliers(self):
        calls: Dict[str, List[Tuple[str, int]]] = {n: [] for n in self.comps}
        for name, lines in self.comps.items():
            for ln in lines:
                if " while(" in ln:
                    body = re.search(r"body=%?([\w\.\-]+)", ln)
                    cond = re.search(r"condition=%?([\w\.\-]+)", ln)
                    # XLA stamps the resolved trip count into backend_config
                    # when it can prove it; trust that over the heuristic
                    ktc = re.search(r'known_trip_count[":{\s]+n["\s:]+(\d+)',
                                    ln)
                    if ktc:
                        trip = int(ktc.group(1))
                    else:
                        trip = self._trip_count(cond.group(1)) if cond else 1
                    if body:
                        calls[name].append((body.group(1), trip))
                    if cond:
                        calls[name].append((cond.group(1), trip + 1))
                else:
                    for attr in ("calls=", "to_apply=", "branch_computations=",
                                 "called_computations=", "true_computation=",
                                 "false_computation="):
                        for m in re.finditer(
                                re.escape(attr) +
                                r"\{?%?([\w\.\-]+(?:,\s*%?[\w\.\-]+)*)\}?", ln):
                            for c in m.group(1).split(","):
                                c = c.strip().lstrip("%")
                                if c in self.comps:
                                    calls[name].append((c, 1))
        # Jacobi relaxation over the call DAG until fixpoint: each sweep
        # recomputes every computation's multiplier from the *previous*
        # sweep's caller values, so one sweep propagates one level of
        # nesting regardless of definition order (HLO lists callees before
        # callers, so an in-sweep update would never reach nested loops)
        self.mult = defaultdict(float)
        self.mult[self.entry] = 1.0
        for _ in range(50):
            new = defaultdict(float)
            new[self.entry] = 1.0
            for name in self.comps:
                for callee, k in calls.get(name, []):
                    new[callee] += self.mult.get(name, 0.0) * k
            if all(abs(new[n] - self.mult[n]) < 0.5
                   for n in set(new) | set(self.mult)):
                self.mult = new
                break
            self.mult = new

    # ------------------------------------------------------------------
    def instructions(self):
        """Yields (comp_multiplier, name, opcode, result_type, full_line)."""
        for cname, lines in self.comps.items():
            m = self.mult.get(cname, 0.0)
            if m <= 0:
                continue
            for ln in lines:
                im = _INSTR_RE.match(ln)
                if not im:
                    continue
                yield m, im.group(1), im.group(3), im.group(2), ln

    def _operands(self, line: str) -> List[str]:
        inner = line.split("(", 1)[1]
        return re.findall(r"%([\w\.\-]+)", inner)

    # ------------------------------------------------------------------
    def dot_flops(self) -> float:
        total = 0.0
        for mult, name, op, rtype, ln in self.instructions():
            if op != "dot":
                continue
            _, rdims = _first_shape_dims(rtype)
            ops = self._operands(ln)
            if not ops:
                continue
            lhs_type = self.shape_of.get(ops[0], "")
            _, ldims = _first_shape_dims(lhs_type)
            cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ln)
            k = 1
            if cm and cm.group(1):
                for i in cm.group(1).split(","):
                    idx = int(i)
                    if idx < len(ldims):
                        k *= ldims[idx]
            n = 1
            for d in rdims:
                n *= d
            total += mult * 2.0 * n * k
        return total

    _LAYOUT_OPS = {"copy", "convert", "transpose", "broadcast", "slice",
                   "dynamic-slice", "dynamic-update-slice", "concatenate",
                   "pad", "reverse", "iota", "select"}

    def _fusion_kinds(self, line: str):
        m = re.search(r"calls=%?([\w\.\-]+)", line)
        kinds = set()
        if not m:
            return kinds
        for ln in self.comps.get(m.group(1), []):
            im = _INSTR_RE.match(ln)
            if im and im.group(3) not in _FREE_OPS:
                kinds.add(im.group(3))
        return kinds

    def _inner_slice_bytes(self, line: str) -> float:
        m = re.search(r"calls=%?([\w\.\-]+)", line)
        if not m:
            return 0.0
        total = 0.0
        for ln in self.comps.get(m.group(1), []):
            im = _INSTR_RE.match(ln)
            if im and im.group(3) in ("dynamic-slice", "gather"):
                total += 2.0 * _shape_bytes(im.group(2))
        return total

    def _fusion_is_layoutish(self, line: str) -> bool:
        """True if the fused computation only moves/converts data: every op
        is a layout op OR produces a tiny (<16 KiB) result (index math for
        update-slice offsets etc.)."""
        m = re.search(r"calls=%?([\w\.\-]+)", line)
        if not m:
            return False
        has_dus = False
        for ln in self.comps.get(m.group(1), []):
            im = _INSTR_RE.match(ln)
            if not im:
                continue
            op = im.group(3)
            if op in _FREE_OPS:
                continue
            if op == "dynamic-update-slice":
                has_dus = True
                continue
            if op in self._LAYOUT_OPS:
                continue
            if _shape_bytes(im.group(2)) < 16384:
                continue            # index math for update offsets
            if im.group(2).lstrip("(").startswith("pred"):
                continue            # mask generation fuses for free on TPU
            return False
        return True

    # elementwise arithmetic opcodes priced at one FLOP per result element
    # (ops that move/select/compare data are not FLOPs; exp/log/tanh etc.
    # are counted at 1 -- a transcendental is more, but by the time they
    # matter the dots dominate anyway)
    _EW_OPS = {"add", "subtract", "multiply", "divide", "negate", "abs",
               "maximum", "minimum", "power", "sqrt", "rsqrt", "exponential",
               "log", "tanh", "logistic", "sine", "cosine"}

    def ew_flops(self) -> float:
        """Elementwise FLOPs: sum over arithmetic instructions of result
        elements x the computation's execution multiplier, fusion bodies
        included. The scalar gather-dot/scatter-axpy loops of the sparse
        SDCA kernel lower to while bodies of scalar multiply-adds with no
        `dot` anywhere -- `dot_flops` alone would price that kernel at
        zero; this counter is what makes its analytic cost nonzero."""
        total = 0.0
        for mult, name, op, rtype, ln in self.instructions():
            if op == "reduce":
                # one combine per input element (the scalar to_apply body
                # would otherwise price a jnp.sum at 1 FLOP)
                ops = self._operands(ln)
                if ops and ops[0] in self.shape_of:
                    _, idims = _first_shape_dims(self.shape_of[ops[0]])
                    n = 1
                    for dim in idims:
                        n *= dim
                    total += mult * n
                continue
            if op not in self._EW_OPS:
                continue
            _, rdims = _first_shape_dims(rtype)
            n = 1
            for dim in rdims:
                n *= dim
            total += mult * n
        return total

    def hbm_bytes(self) -> float:
        """HBM-traffic model of the *target* (TPU) execution.

        XLA:CPU inserts convert(bf16->f32) + layout-transpose materializations
        around every bf16 dot (CPUs have no bf16 FMA; TPU MXUs consume bf16
        natively). Counting those buffers would misattribute CPU lowering
        artifacts to the TPU roofline, so layout/convert-only fusions are
        skipped; their consumers (dots, compute fusions) still count the
        operand reads, and update-slice fusions count the updated strip.
        Methodology documented in EXPERIMENTS.md section Roofline.
        """
        total = 0.0
        for mult, name, op, rtype, ln in self.instructions():
            if op in _FREE_OPS or op in ("while", "conditional", "call"):
                # control flow: bodies counted via their own multipliers
                continue
            rb = _shape_bytes(rtype)
            if op in ("convert", "copy", "transpose", "broadcast"):
                continue                       # standalone layout artifacts
            if op == "dynamic-slice":
                total += mult * 2.0 * rb       # read strip + write strip
                continue
            opbytes = [
                _shape_bytes(self.shape_of[o]) for o in self._operands(ln)
                if o in self.shape_of
            ]
            if op == "fusion":
                kinds = self._fusion_kinds(ln)
                # slice-from-big pattern: a fusion that dynamic-slices/gathers
                # a strip out of a huge operand (SDCA row access) only reads
                # the strip -- replace dwarfed operands with the internal
                # slice results (2x for read+write)
                if ("dynamic-slice" in kinds or "gather" in kinds) and                         "dynamic-update-slice" not in kinds:
                    big = [ob for ob in opbytes if ob > 64 * max(rb, 1)]
                    if big:
                        inner = self._inner_slice_bytes(ln)
                        b = (rb + inner
                             + sum(ob for ob in opbytes
                                   if ob <= 64 * max(rb, 1)))
                        total += mult * b
                        continue
                if (self._fusion_is_layoutish(ln)
                        or "dynamic-update-slice" in kinds):
                    if "dynamic-update-slice" in self._fusion_kinds(ln):
                        # in-place update: count the updated strip (operands
                        # far smaller than the aliased result) once each way;
                        # same-magnitude operands are CPU dtype-convert
                        # shadows of the aliased buffer, not real strips
                        small = sum(ob for ob in opbytes if ob < rb / 256)
                        total += mult * 2.0 * small
                    # pure layout/convert fusion: no target-side traffic
                    continue
            b = rb + sum(opbytes)
            if op in ("fusion", "dynamic-update-slice"):
                # drop the operand aliased to the result (in-place)
                for ob in opbytes:
                    if ob == rb:
                        b -= ob
                        break
            total += mult * b
        return total

    def collective_stats(self) -> Dict[str, Dict[str, float]]:
        stats = defaultdict(lambda: {"count": 0.0, "bytes": 0.0,
                                     "wire_bytes": 0.0})
        for mult, name, op, rtype, ln in self.instructions():
            base = op.replace("-start", "")
            if base not in _COLLECTIVES or op.endswith("-done"):
                continue
            bts = _shape_bytes(rtype)
            g = _group_size(ln)
            s = stats[base]
            s["count"] += mult
            s["bytes"] += mult * bts
            if base == "all-reduce":
                wire = 2.0 * bts * (g - 1) / g
            elif base == "reduce-scatter":
                wire = bts * (g - 1)
            elif base == "collective-permute":
                wire = float(bts)
            else:
                wire = bts * (g - 1) / g
            s["wire_bytes"] += mult * wire
        return dict(stats)


def total_wire_bytes(stats: Dict[str, Dict[str, float]]) -> float:
    return sum(s["wire_bytes"] for s in stats.values())


def full_stats(hlo: str) -> Dict[str, object]:
    mod = HloModule(hlo)
    coll = mod.collective_stats()
    dot = mod.dot_flops()
    ew = mod.ew_flops()
    return {
        "dot_flops": dot,
        "ew_flops": ew,
        "flops": dot + ew,
        "hbm_bytes": mod.hbm_bytes(),
        "collectives": coll,
        "collective_wire_bytes": total_wire_bytes(coll),
    }


def stats_of_compiled(compiled) -> Dict[str, object]:
    """`full_stats` of a compiled executable (`jit(f).lower(...).compile()`)
    -- the post-SPMD, post-optimization module the device actually runs,
    which is the text every analytic number in `repro.obs.prof` comes
    from."""
    texts = compiled.as_text()
    if isinstance(texts, (list, tuple)):       # one module per partition
        texts = texts[0]
    return full_stats(texts)
