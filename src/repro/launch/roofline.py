"""Roofline analysis from compiled HLO text.

Why parse HLO ourselves: `compiled.cost_analysis()` counts the body of a
`while` loop (lax.scan) ONCE, not once per trip (jax 0.9.0: a 5-step scan of
a 64x64 matmul reports the FLOPs of one step), but every model here scans
over layers (and the train step scans over
microbatches), so raw numbers undercount by the trip count(s). This parser

  1. splits `compiled.as_text()` into computations and instructions,
  2. builds the call graph (while body/condition, fusion `calls`, call,
     conditional branches, reducer `to_apply`),
  3. extracts each while loop's trip count from the `s32[] constant(K)` in
     its condition computation,
  4. propagates multipliers from ENTRY (products of enclosing trip counts),
  5. aggregates per-device:
       * dot FLOPs        — 2 * prod(out shape) * prod(contracted dims)
       * bytes accessed   — operands+outputs of materializing ops
                            (fusion bodies are NOT recursed: fused
                            intermediates never touch HBM)
       * collective bytes — operand bytes of all-gather / all-reduce /
                            reduce-scatter / all-to-all / collective-permute,
                            plus ring-model wire bytes using the replica
                            group size.

Terms (per chip — SPMD HLO is already per-device):
    compute_s    = dot_flops / PEAK_FLOPS
    memory_s     = bytes / HBM_BW
    collective_s = wire_bytes / ICI_BW
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict

# hardware constants (TPU v5e-like, per assignment)
PEAK_FLOPS = 197e12         # bf16 FLOP/s per chip
HBM_BW = 819e9              # B/s per chip
ICI_BW = 50e9               # B/s per link

_DTYPE_BYTES = {
    "pred": 1, "s2": 0.25, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "f8e4m3fnuz": 1, "f4e2m1fn": 0.5, "u1": 0.125, "s1": 0.125, "e": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)\((.*)$"
)
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->\s*.*\{\s*$")

COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
# ops whose operands/outputs represent real HBM traffic at the callsite
_MATERIALIZING = {
    "fusion", "dot", "copy", "convolution", "reduce", "sort",
    "broadcast", "transpose", "concatenate", "slice", "pad", "convert",
    "reduce-window", "select-and-scatter", "iota", "reshape",
}
# ops that touch only a window of their (possibly huge) buffer operand:
# traffic is proportional to the *slice*, not the buffer
_WINDOWED = {"dynamic-slice", "gather"}
_INPLACE = {"dynamic-update-slice", "scatter"}
# pure dtype/layout ops: the CPU backend materializes these (f32 dot inputs,
# loop-carry copies); a TPU bf16 pipeline fuses them into producers/consumers
_CHURN = {"convert", "bitcast", "copy", "reshape", "transpose", "broadcast",
          "reduce-precision"}
_FREE = {"parameter", "constant", "get-tuple-element", "tuple", "iota",
         "after-all", "partition-id", "replica-id"}


def _is_churn_fusion(callee: str, comps) -> tuple[bool, float]:
    """True if a fused computation only moves/permutes/re-types data.

    Returns (is_churn, essential_bytes): essential bytes keep any
    dynamic-update-slice windows (cache/stacking writes are real traffic).
    """
    body = comps.get(callee, [])
    if not body:
        return False, 0.0
    by_name = {i.name: i.type_str for i in body}
    essential = 0.0
    for i in body:
        if i.op in _CHURN or i.op in _FREE or i.op in _WINDOWED or i.op == "slice":
            continue
        if i.op in _INPLACE:
            ops_i = _operand_names(i.rest)
            upd_i = 1 if i.op == "dynamic-update-slice" else 2
            if len(ops_i) > upd_i and ops_i[upd_i] in by_name:
                essential += 2.0 * shape_bytes(by_name[ops_i[upd_i]])
            continue
        return False, 0.0
    return True, essential


def shape_bytes(type_str: str) -> float:
    """Bytes of a (possibly tuple) HLO type string."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def shape_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    op: str
    rest: str  # everything after the opening paren of operands


def parse_computations(hlo: str) -> dict[str, list[Instr]]:
    comps: dict[str, list[Instr]] = {}
    cur: list[Instr] | None = None
    for line in hlo.splitlines():
        if _COMP_HDR_RE.match(line):
            name = _COMP_HDR_RE.match(line).group(1)
            cur = comps.setdefault(name, [])
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if m:
            cur.append(Instr(m.group(1), m.group(2), m.group(3), m.group(4)))
    return comps


def _operand_names(rest: str) -> list[str]:
    # operands are inside the first balanced (...) span
    depth, out, buf = 1, [], []
    for ch in rest:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        buf.append(ch)
    span = "".join(buf)
    return re.findall(r"%([\w.\-]+)", span)


def _attr(rest: str, key: str) -> str | None:
    m = re.search(key + r"=\{([0-9,\s]*)\}", rest)
    return m.group(1) if m else None


def _callee(rest: str, key: str) -> str | None:
    m = re.search(key + r"=%?([\w.\-]+)", rest)
    return m.group(1) if m else None


def _replica_group_size(rest: str) -> int:
    # modern form: replica_groups=[G,S]<=[N]
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+\]", rest)
    if m:
        return int(m.group(2))
    # explicit form: replica_groups={{0,1,2,3},{...}}
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", rest)
    if m:
        return len(m.group(1).split(","))
    return 1


def wire_bytes(op: str, operand_bytes: float, out_bytes: float, group: int) -> float:
    """Ring-model wire bytes per device for one collective."""
    g = max(group, 1)
    if op == "all-gather":
        return (g - 1) * operand_bytes
    if op == "all-reduce":
        return 2.0 * (g - 1) / g * operand_bytes
    if op == "reduce-scatter":
        return (g - 1) / g * operand_bytes
    if op == "all-to-all":
        return (g - 1) / g * operand_bytes
    if op == "collective-permute":
        return operand_bytes
    return operand_bytes


@dataclasses.dataclass
class RooflineResult:
    dot_flops: float = 0.0
    bytes_accessed: float = 0.0         # as compiled (CPU-backend HLO)
    bytes_essential: float = 0.0        # discounting pure dtype/layout churn
                                        # a TPU bf16 pipeline would fuse away
    collective_bytes: float = 0.0       # operand bytes
    collective_wire_bytes: float = 0.0  # ring-model wire bytes
    by_collective: dict = dataclasses.field(default_factory=dict)
    while_trip_counts: dict = dataclasses.field(default_factory=dict)
    notes: list = dataclasses.field(default_factory=list)

    @property
    def compute_s(self) -> float:
        return self.dot_flops / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_essential / HBM_BW

    @property
    def memory_as_compiled_s(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_wire_bytes / ICI_BW

    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    def summary(self) -> dict:
        return {
            "dot_flops": self.dot_flops,
            "bytes_essential": self.bytes_essential,
            "memory_as_compiled_s": self.memory_as_compiled_s,
            "bytes_accessed": self.bytes_accessed,
            "collective_bytes": self.collective_bytes,
            "collective_wire_bytes": self.collective_wire_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant(),
            "by_collective": self.by_collective,
            "while_trip_counts": self.while_trip_counts,
            "notes": self.notes,
        }


def _fusion_bytes(ins: Instr, operand_types: list, comps) -> float:
    """HBM traffic of one fusion callsite.

    Default: sum(operands) + output. Two big corrections, both common in
    scan-over-layers models:
      * a fusion *parameter* consumed only by dynamic-slice/gather ops
        (per-layer param slice, embedding row lookup) costs the window,
        not the buffer;
      * a fusion whose root is a dynamic-update-slice writing into a
        parameter-aliased buffer (KV-cache update) costs the update window,
        not buffer + output.
    """
    callee = _callee(ins.rest, "calls")
    body = comps.get(callee, []) if callee else []
    by_name = {i.name: i for i in body}
    _PASSTHRU = {"convert", "bitcast", "copy", "reshape", "reduce-precision",
                 "transpose", "broadcast"}

    def trace_def(name):
        """Chase a value back through layout/dtype-only ops to its origin."""
        seen = set()
        while name in by_name and name not in seen:
            seen.add(name)
            i = by_name[name]
            if i.op in _PASSTHRU:
                ops = _operand_names(i.rest)
                if ops:
                    name = ops[0]
                    continue
            break
        return name

    # parameter name -> positional index
    param_idx = {}
    for i in body:
        if i.op == "parameter":
            m = re.match(r"\s*(\d+)", i.rest)
            if m:
                param_idx[i.name] = int(m.group(1))
    # consumers of each value inside the fusion
    consumers: dict[str, list[Instr]] = defaultdict(list)
    for i in body:
        for o in _operand_names(i.rest):
            consumers[o].append(i)

    def effective_consumers(name, depth=0):
        out = []
        for c in consumers.get(name, []):
            if c.op in _PASSTHRU and depth < 6:
                out.extend(effective_consumers(c.name, depth + 1))
            else:
                out.append(c)
        return out

    total = 0.0
    inplace_params: set[str] = set()
    for i in body:
        if i.op in _INPLACE:
            ops_i = _operand_names(i.rest)
            if not ops_i:
                continue
            buf = trace_def(ops_i[0])
            if buf in param_idx:
                inplace_params.add(buf)
                upd_i = 1 if i.op == "dynamic-update-slice" else 2
                upd_t = ""
                if len(ops_i) > upd_i and ops_i[upd_i] in by_name:
                    upd_t = by_name[ops_i[upd_i]].type_str
                total += 2.0 * shape_bytes(upd_t)  # RMW of the window

    for pname, idx in param_idx.items():
        if pname in inplace_params:
            continue
        cons = effective_consumers(pname)
        if cons and all(c.op in _WINDOWED for c in cons):
            total += sum(shape_bytes(c.type_str) for c in cons)
        else:
            if idx < len(operand_types) and operand_types[idx]:
                t = operand_types[idx]
                if "[]" not in t[:6]:
                    total += shape_bytes(t)
    if not inplace_params:
        total += shape_bytes(ins.type_str)  # write the output
    return total


def _trip_count(cond_comp: str, comps, fusion_callees) -> int:
    """Max s32 constant reachable from the while condition computation."""
    best = 1
    stack = [cond_comp]
    seen = set()
    while stack:
        c = stack.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for ins in comps[c]:
            if ins.op == "constant" and "s32[]" in ins.type_str:
                m = re.match(r"\s*([0-9]+)", ins.rest)
                if m:
                    best = max(best, int(m.group(1)))
            callee = _callee(ins.rest, "calls") or _callee(ins.rest, "to_apply")
            if callee:
                stack.append(callee)
    return best


def analyze_hlo(hlo: str) -> RooflineResult:
    comps = parse_computations(hlo)
    entry = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR_RE.match(line)
            entry = m.group(1)
            break
    if entry is None:  # fall back: computation named main-ish
        entry = next((n for n in comps if "main" in n), next(iter(comps)))

    res = RooflineResult()

    # shape table per computation (names are computation-local)
    def shapes_in(comp):
        return {i.name: i.type_str for i in comps.get(comp, [])}

    # ---- pass: walk from entry with multipliers -------------------------
    def visit(comp: str, mult: float, via_fusion: bool, seen: tuple):
        if comp not in comps or comp in seen:
            return
        table = shapes_in(comp)
        for ins in comps[comp]:
            ops_names = _operand_names(ins.rest)
            operand_types = [table.get(o) for o in ops_names]

            if ins.op == "dot":
                out_elems = max(1, math.prod(shape_dims(ins.type_str) or [1]))
                lhs_t = operand_types[0] if operand_types else None
                contracted = 1
                cdims = _attr(ins.rest, "lhs_contracting_dims")
                if lhs_t and cdims:
                    ldims = shape_dims(lhs_t)
                    for ci in cdims.split(","):
                        ci = ci.strip()
                        if ci:
                            contracted *= ldims[int(ci)]
                res.dot_flops += mult * 2.0 * out_elems * contracted

            if ins.op == "convolution":
                out_elems = max(1, math.prod(shape_dims(ins.type_str) or [1]))
                # approximate: 2 * out * prod(kernel spatial + in-ch) via operand
                k_t = operand_types[1] if len(operand_types) > 1 else None
                k_elems = max(1, math.prod(shape_dims(k_t) or [1])) if k_t else 1
                out_ch = shape_dims(ins.type_str)[-1] if shape_dims(ins.type_str) else 1
                res.dot_flops += mult * 2.0 * out_elems * max(1, k_elems // max(out_ch, 1))

            if ins.op in COLLECTIVES:
                ob = sum(shape_bytes(t) for t in operand_types if t)
                if ob == 0:  # operand defined in another computation scope
                    ob = shape_bytes(ins.type_str)
                    if ins.op == "all-gather":
                        ob /= max(_replica_group_size(ins.rest), 1)
                outb = shape_bytes(ins.type_str)
                g = _replica_group_size(ins.rest)
                w = wire_bytes(ins.op, ob, outb, g)
                res.collective_bytes += mult * ob
                res.collective_wire_bytes += mult * w
                d = res.by_collective.setdefault(
                    ins.op, {"count": 0.0, "bytes": 0.0, "wire_bytes": 0.0}
                )
                d["count"] += mult
                d["bytes"] += mult * ob
                d["wire_bytes"] += mult * w

            if not via_fusion:
                if ins.op in _WINDOWED:
                    # read the addressed window (~= output), write the output
                    b = mult * 2.0 * shape_bytes(ins.type_str)
                    res.bytes_accessed += b
                    res.bytes_essential += b
                elif ins.op in _INPLACE:
                    # in-place window write: read+write ~= the update operand
                    upd_i = 1 if ins.op == "dynamic-update-slice" else 2
                    upd = operand_types[upd_i] if len(operand_types) > upd_i else None
                    b = mult * 2.0 * shape_bytes(upd or "")
                    res.bytes_accessed += b
                    res.bytes_essential += b
                elif ins.op == "fusion":
                    b = mult * _fusion_bytes(ins, operand_types, comps)
                    res.bytes_accessed += b
                    callee = _callee(ins.rest, "calls")
                    churn, ess = _is_churn_fusion(callee, comps) if callee else (False, 0.0)
                    res.bytes_essential += mult * ess if churn else b
                elif ins.op in _CHURN:
                    ob = sum(shape_bytes(t) for t in operand_types
                             if t and "[]" not in t[:6])
                    res.bytes_accessed += mult * (ob + shape_bytes(ins.type_str))
                    # essential: fused away on TPU
                elif ins.op in _MATERIALIZING:
                    ob = sum(shape_bytes(t) for t in operand_types
                             if t and "[]" not in t[:6])
                    b = mult * (ob + shape_bytes(ins.type_str))
                    res.bytes_accessed += b
                    res.bytes_essential += b

            # recurse
            if ins.op == "while":
                body = _callee(ins.rest, "body")
                cond = _callee(ins.rest, "condition")
                trip = _trip_count(cond, comps, None) if cond else 1
                res.while_trip_counts[body or "?"] = trip
                if body:
                    visit(body, mult * trip, via_fusion, seen + (comp,))
                if cond:
                    visit(cond, mult * trip, True, seen + (comp,))
            elif ins.op == "fusion":
                callee = _callee(ins.rest, "calls")
                if callee:
                    # fused intermediates don't hit HBM: flops-only traversal
                    visit(callee, mult, True, seen + (comp,))
            elif ins.op in ("call", "async-start"):
                callee = _callee(ins.rest, "to_apply")
                if callee:
                    visit(callee, mult, via_fusion, seen + (comp,))
            elif ins.op == "conditional":
                for key in ("true_computation", "false_computation"):
                    callee = _callee(ins.rest, key)
                    if callee:
                        visit(callee, mult, via_fusion, seen + (comp,))

    visit(entry, 1.0, False, ())
    return res


def model_flops(cfg, shape_kind: str, seq: int, global_batch: int, dec_frac: float = 0.25) -> float:
    """Analytic useful FLOPs (global, whole step) — the 6ND / 2ND yardstick.

    train: 6*N_active*tokens;  prefill: 2*N_active*tokens;
    decode: 2*N_active*batch (one token each) + attention cache-read flops.
    """
    n = cfg.active_param_count()
    if shape_kind == "train":
        tokens = seq * global_batch
        if cfg.family == "encdec":
            tokens = seq * global_batch * (1 + dec_frac) / 2  # enc fwd-only share
        return 6.0 * n * tokens
    if shape_kind == "prefill":
        return 2.0 * n * seq * global_batch
    # decode: matmul flops + attention KV dot flops
    flops = 2.0 * n * global_batch
    if cfg.family != "ssm":
        n_attn = _num_attn_layers(cfg)
        flops += 4.0 * global_batch * seq * n_attn * cfg.n_heads * cfg.head_dim
    return flops


def kv_cache_bytes(cfg, seq: int, global_batch: int) -> float:
    """Global KV-cache (or SSM state) bytes at bf16."""
    if cfg.family == "ssm":
        per = cfg.ssm_heads * cfg.ssm_headdim * cfg.ssm_state * 4  # f32 state
        return cfg.num_layers * global_batch * per
    n_attn = _num_attn_layers(cfg)
    kv = n_attn * global_batch * seq * cfg.n_kv_heads * cfg.head_dim * 2 * 2
    if cfg.family == "hybrid":
        per = cfg.ssm_heads * cfg.ssm_headdim * cfg.ssm_state * 4
        n_ssm = cfg.num_layers - cfg.num_layers // max(cfg.hybrid_attn_period, 1)
        kv += n_ssm * global_batch * per
    return kv


def ideal_seconds(cfg, shape_kind: str, seq: int, global_batch: int,
                  chips: int, model_shards: int = 16) -> float:
    """Roofline target time for one step of this cell.

    train/prefill: compute-bound ideal (MODEL_FLOPS at peak).
    decode: bytes-bound ideal — every device must stream its weight shard
    (TP: 2N/model_shards bytes) plus its share of the KV cache once.
    """
    mf = model_flops(cfg, shape_kind, seq, global_batch)
    ideal_c = mf / chips / PEAK_FLOPS
    if shape_kind != "decode":
        return ideal_c
    w_read = 2.0 * cfg.active_param_count() / model_shards
    kv_read = kv_cache_bytes(cfg, seq, global_batch) / chips
    return max(ideal_c, (w_read + kv_read) / HBM_BW)


def _num_attn_layers(cfg) -> int:
    if cfg.family == "encdec":
        return 2 * cfg.dec_layers  # self + cross per decoder layer at decode
    if cfg.family == "hybrid" and cfg.hybrid_attn_period:
        return cfg.num_layers // cfg.hybrid_attn_period
    if cfg.family == "ssm":
        return 0
    return cfg.num_layers
