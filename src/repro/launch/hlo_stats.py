"""Static analysis of compiled (post-SPMD) HLO text.

``compiled.cost_analysis()`` counts each ``while`` body ONCE, so scan-based
models (every model here) under-report FLOPs/bytes by ~num_layers x.  This
module parses ``compiled.as_text()`` into its computations, resolves the
call graph (fusion/call/while/conditional), extracts trip counts from loop
conditions, and accumulates:

  * flops            — MXU matmul FLOPs (2·M·N·K per dot; vector-unit
                       elementwise flops are excluded, as is standard for
                       compute-roofline terms)
  * hbm_bytes        — Σ over executed top-level ops of operand+result
                       bytes (fusions counted at their boundary, the
                       HBM-traffic model XLA itself uses)
  * collective_bytes — Σ operand bytes of all-reduce / all-gather /
                       reduce-scatter / all-to-all / collective-permute,
                       per collective family

All counts are PER DEVICE (the partitioned module is per-device).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
    "token": 0, "opaque": 0,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")

_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "after-all", "iota", "broadcast", "reshape",
             "copy-done", "all-gather-done", "all-reduce-done",
             "collective-permute-done", "partition-id", "replica-id"}

# Pure elementwise ops: the CPU backend leaves many of these unfused at the
# top level, but on the TPU target they fuse into their consumers — counting
# their operand/result bytes would overstate HBM traffic ~10x.  The memory
# term therefore models TPU-style fusion: bytes are charged only at fusion
# boundaries, dots, collectives, data movement and reductions.
_ELEMENTWISE = {
    "convert", "multiply", "add", "subtract", "divide", "select", "minimum",
    "maximum", "negate", "tanh", "cosine", "sine", "exponential", "log",
    "rsqrt", "sqrt", "power", "compare", "and", "or", "not", "xor", "abs",
    "sign", "floor", "ceil", "round-nearest-even", "round-nearest-afz",
    "clamp", "is-finite", "exponential-minus-one", "log-plus-one", "tan",
    "logistic", "atan2", "remainder", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "clz", "popcnt", "real", "imag", "map",
}


# ---------------------------------------------------------------------------
# shape parsing
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()``, or an empty dict where the backend
    reports none."""
    return compiled.cost_analysis() or {}


def shape_bytes(type_str: str) -> int:
    """Bytes of a (possibly tuple) HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


def shape_elems(type_str: str) -> int:
    n = 1
    for d in shape_dims(type_str):
        n *= d
    return n


# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    result_type: str
    operands: List[str]
    attrs: str
    is_root: bool = False
    operand_str: str = ""


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[Op]
    shapes: Dict[str, str]          # %name -> result type


_HDR_RE = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s*\(")
_OP_RE = re.compile(r"^\s+(ROOT\s+)?(%[\w.\-]+)\s*=\s*(.+)$")


def _split_type_rest(s: str) -> Tuple[str, str]:
    s = s.lstrip()
    if s.startswith("("):
        depth = 0
        for i, ch in enumerate(s):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return s[:i + 1], s[i + 1:].lstrip()
    i = s.find(" ")
    return s[:i], s[i + 1:].lstrip()


def _split_opcode(rest: str) -> Tuple[str, str, str]:
    """'dot(%a, %b), attrs' -> ('dot', '%a, %b', attrs)."""
    i = rest.find("(")
    opcode = rest[:i].strip()
    depth = 0
    for j in range(i, len(rest)):
        if rest[j] == "(":
            depth += 1
        elif rest[j] == ")":
            depth -= 1
            if depth == 0:
                return opcode, rest[i + 1:j], rest[j + 1:]
    return opcode, rest[i + 1:], ""


_OPERAND_RE = re.compile(r"%[\w.\-]+")


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry_name = None
    for line in text.splitlines():
        if not line.strip():
            continue
        if not line.startswith(" "):
            m = _HDR_RE.match(line.strip())
            if m and line.rstrip().endswith("{"):
                cur = Computation(m.group(1), [], {})
                comps[cur.name] = cur
                if line.strip().startswith("ENTRY"):
                    entry_name = cur.name
                continue
            if line.strip() == "}":
                cur = None
                continue
        if cur is None:
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        is_root, name, rhs = bool(m.group(1)), m.group(2), m.group(3)
        rtype, rest = _split_type_rest(rhs)
        if "(" not in rest:
            continue
        opcode, operand_str, attrs = _split_opcode(rest)
        operands = _OPERAND_RE.findall(operand_str)
        cur.ops.append(Op(name, opcode, rtype, operands, attrs, is_root,
                          operand_str))
        cur.shapes[name] = rtype
    if entry_name:
        comps["__entry__"] = comps[entry_name]
    return comps


# ---------------------------------------------------------------------------
# cost accumulation over the call graph
# ---------------------------------------------------------------------------

_CALLS_RE = re.compile(r"calls=(%[\w.\-]+)")
_BODY_RE = re.compile(r"body=(%[\w.\-]+)")
_COND_RE = re.compile(r"condition=(%[\w.\-]+)")
_APPLY_RE = re.compile(r"to_apply=(%[\w.\-]+)")
_LHS_C_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _fusion_hbm_bytes(op: Op, comp: Computation, callee: Optional[Computation]
                      ) -> float:
    """HBM traffic of a fusion at its boundary, recognizing the loop
    patterns that would otherwise be charged at full-buffer size per
    iteration:
      * root = dynamic-update-slice → in-place write of a slice into a
        loop-carried buffer (scan ys accumulation): charge 2×slice;
      * a fusion PARAMETER consumed only by dynamic-slice/gather inside the
        fusion → the loop reads one slice of the big operand, not all of
        it: charge 2×slice-result instead of the full operand.
    """
    out_b = shape_bytes(op.result_type)
    if callee is None:
        return out_b + sum(shape_bytes(comp.shapes.get(o, ""))
                           for o in op.operands)

    # map parameter index -> param op name, and find each param's consumers
    param_names: Dict[int, str] = {}
    for o2 in callee.ops:
        if o2.opcode == "parameter":
            try:
                param_names[int(o2.operand_str)] = o2.name
            except ValueError:
                pass
    consumers: Dict[str, List[Op]] = {}
    for o2 in callee.ops:
        for ref in o2.operands:
            consumers.setdefault(ref, []).append(o2)

    read_b = 0.0
    for i, operand in enumerate(op.operands):
        full = shape_bytes(comp.shapes.get(operand, ""))
        pname = param_names.get(i)
        cons = consumers.get(pname, []) if pname else []
        if cons and all(c.opcode in ("dynamic-slice", "gather")
                        for c in cons):
            sliced = sum(shape_bytes(callee.shapes.get(c.name, ""))
                         for c in cons)
            read_b += min(2.0 * sliced, full)
        else:
            read_b += full

    root = None
    for o2 in callee.ops:
        if o2.is_root:
            root = o2
            break
    if root is None and callee.ops:
        root = callee.ops[-1]
    if root is not None and root.opcode == "dynamic-update-slice":
        upd = (shape_bytes(callee.shapes.get(root.operands[1], ""))
               if len(root.operands) > 1 else 0)
        # the aliased big buffer passes through; subtract it from reads
        big_alias = max((shape_bytes(comp.shapes.get(o, ""))
                         for o in op.operands), default=0)
        return 2.0 * upd + max(read_b - big_alias, 0.0)
    if root is not None and root.opcode in ("dynamic-slice", "gather") \
            and read_b > 8 * out_b:
        return 2.0 * out_b
    return out_b + read_b


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: float = 0.0
    by_collective: Dict[str, float] = dataclasses.field(default_factory=dict)
    dots: int = 0
    collectives: int = 0

    def add(self, other: "Cost", times: float = 1.0):
        self.flops += other.flops * times
        self.hbm_bytes += other.hbm_bytes * times
        self.collective_bytes += other.collective_bytes * times
        self.dots += int(other.dots * times)
        self.collectives += int(other.collectives * times)
        for k, v in other.by_collective.items():
            self.by_collective[k] = self.by_collective.get(k, 0.0) + v * times


def _trip_count_text(cond_text: str) -> int:
    """Largest s32 constant in the loop condition ≈ the trip count (jax
    scan/fori loops compare an s32 counter against the length)."""
    vals = [int(v) for v in re.findall(
        r"s32\[\][^=]*constant\((\d+)\)", cond_text)]
    return max(vals) if vals else 1


def module_cost(text: str) -> Cost:
    comps = parse_hlo(text)
    # keep raw per-computation text for trip-count extraction
    raw: Dict[str, List[str]] = {}
    cur = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _HDR_RE.match(line.strip())
            if m and line.rstrip().endswith("{"):
                cur = m.group(1)
                raw[cur] = []
                continue
            if line.strip() == "}":
                cur = None
                continue
        if cur is not None:
            raw[cur].append(line)

    memo: Dict[str, Cost] = {}

    def cost_of(name: str, depth: int = 0) -> Cost:
        if name in memo:
            return memo[name]
        comp = comps.get(name)
        c = Cost()
        if comp is None or depth > 64:
            memo[name] = c
            return c
        memo[name] = c          # break cycles defensively
        for op in comp.ops:
            out_b = shape_bytes(op.result_type)
            opnd_b = sum(shape_bytes(comp.shapes.get(o, "")) for o in
                         op.operands)
            oc = op.opcode
            if oc == "dot":
                k = 1
                m = _LHS_C_RE.search(op.attrs)
                lhs_t = comp.shapes.get(op.operands[0], "") \
                    if op.operands else ""
                lhs_dims = shape_dims(lhs_t)
                if m and m.group(1):
                    for d in m.group(1).split(","):
                        di = int(d)
                        if di < len(lhs_dims):
                            k *= lhs_dims[di]
                c.flops += 2.0 * shape_elems(op.result_type) * k
                c.dots += 1
                c.hbm_bytes += out_b + opnd_b
            elif oc == "convolution":
                # rare here; treat as dot over the kernel volume
                c.flops += 2.0 * shape_elems(op.result_type) * max(
                    1, shape_elems(comp.shapes.get(op.operands[1], "")))
                c.hbm_bytes += out_b + opnd_b
            elif oc in _COLLECTIVES or (oc.endswith("-start")
                                        and oc[:-6] in _COLLECTIVES):
                base = oc[:-6] if oc.endswith("-start") else oc
                if base in _COLLECTIVES:
                    cb = sum(shape_bytes(comp.shapes.get(o, ""))
                             for o in op.operands)
                    c.collective_bytes += cb
                    c.by_collective[base] = c.by_collective.get(base, 0.0) + cb
                    c.collectives += 1
                    c.hbm_bytes += out_b + opnd_b
            elif oc == "fusion":
                m = _CALLS_RE.search(op.attrs)
                callee = comps.get(m.group(1)) if m else None
                if m:
                    inner = cost_of(m.group(1), depth + 1)
                    # fusion boundary = its HBM traffic; inner dots count
                    c.flops += inner.flops
                    c.dots += inner.dots
                    c.collective_bytes += inner.collective_bytes
                    for k2, v in inner.by_collective.items():
                        c.by_collective[k2] = c.by_collective.get(k2, 0) + v
                c.hbm_bytes += _fusion_hbm_bytes(op, comp, callee)
            elif oc == "while":
                m_b = _BODY_RE.search(op.attrs)
                m_c = _COND_RE.search(op.attrs)
                trip = 1
                if m_c and m_c.group(1) in raw:
                    trip = _trip_count_text("\n".join(raw[m_c.group(1)]))
                if m_b:
                    c.add(cost_of(m_b.group(1), depth + 1), trip)
                if m_c:
                    c.add(cost_of(m_c.group(1), depth + 1), trip)
            elif oc in ("call", "custom-call"):
                m = _APPLY_RE.search(op.attrs) or _CALLS_RE.search(op.attrs)
                if m:
                    c.add(cost_of(m.group(1), depth + 1), 1.0)
                c.hbm_bytes += out_b + opnd_b
            elif oc == "conditional":
                branches = re.findall(r"branch_computations=\{([^}]*)\}",
                                      op.attrs)
                names = _OPERAND_RE.findall(branches[0]) if branches else []
                m_t = re.search(r"true_computation=(%[\w.\-]+)", op.attrs)
                m_f = re.search(r"false_computation=(%[\w.\-]+)", op.attrs)
                names += [m.group(1) for m in (m_t, m_f) if m]
                if names:
                    worst = Cost()
                    for n2 in names:
                        cc = cost_of(n2, depth + 1)
                        if cc.flops >= worst.flops:
                            worst = cc
                    c.add(worst, 1.0)
                c.hbm_bytes += out_b + opnd_b
            elif oc in ("dynamic-slice", "gather", "slice"):
                # reads only the sliced region, not the operand tensor —
                # charging the full operand would make a seq-scan quadratic
                c.hbm_bytes += 2 * out_b
            elif oc in ("dynamic-update-slice", "scatter"):
                # in-place update: read+write of the update region only
                upd = (shape_bytes(comp.shapes.get(op.operands[1], ""))
                       if len(op.operands) > 1 else out_b)
                c.hbm_bytes += 2 * min(upd, out_b) if upd else out_b
            elif oc == "pad":
                c.hbm_bytes += out_b + (shape_bytes(
                    comp.shapes.get(op.operands[0], ""))
                    if op.operands else 0)
            elif oc in _FREE_OPS or oc in _ELEMENTWISE:
                pass
            else:
                # reduce / sort / copy / concatenate / transpose ...
                c.hbm_bytes += out_b + opnd_b
        memo[name] = c
        return c

    return cost_of("__entry__")


def collective_breakdown(text: str) -> Dict[str, float]:
    return dict(module_cost(text).by_collective)


def top_contributors(text: str, k: int = 20, metric: str = "hbm"
                     ) -> List[Tuple[float, str, str, str]]:
    """Per-op attribution: (total_metric, opcode, result_type, comp) sorted
    desc — the 'profile' view used by the perf hillclimb."""
    comps = parse_hlo(text)
    raw: Dict[str, List[str]] = {}
    cur = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _HDR_RE.match(line.strip())
            if m and line.rstrip().endswith("{"):
                cur = m.group(1)
                raw[cur] = []
                continue
            if line.strip() == "}":
                cur = None
                continue
        if cur is not None:
            raw[cur].append(line)

    # execution multiplicity of every computation
    mult: Dict[str, float] = {"__entry__": 1.0}
    entry = comps.get("__entry__")
    if entry is None:
        return []
    for nm, cp in comps.items():
        if cp is entry and nm != "__entry__":
            mult[nm] = 1.0      # the real ENTRY name
    fusion_callees: set = set()
    stack = [("__entry__", 1.0)]
    seen_depth = 0
    while stack and seen_depth < 100000:
        seen_depth += 1
        name, m0 = stack.pop()
        comp = comps.get(name)
        if comp is None:
            continue
        for op in comp.ops:
            for pat, factor_fn in (
                    (_CALLS_RE, lambda a: 1.0),
                    (_APPLY_RE, lambda a: 1.0),
                    (_BODY_RE, None), (_COND_RE, None)):
                mm = pat.search(op.attrs)
                if not mm:
                    continue
                callee = mm.group(1)
                if pat is _CALLS_RE and op.opcode == "fusion":
                    fusion_callees.add(callee)
                if pat in (_BODY_RE, _COND_RE):
                    mc = _COND_RE.search(op.attrs)
                    trip = _trip_count_text("\n".join(
                        raw.get(mc.group(1), []))) if mc else 1
                    f = float(trip)
                else:
                    f = 1.0
                new = m0 * f
                if mult.get(callee, 0.0) < new:
                    mult[callee] = new
                    stack.append((callee, new))

    rows: List[Tuple[float, str, str, str]] = []
    for name, comp in comps.items():
        if name == "__entry__":
            continue
        if metric == "hbm" and name in fusion_callees:
            continue        # fusion internals are charged at the boundary
        m0 = mult.get(name, 0.0)
        if m0 <= 0:
            continue
        for op in comp.ops:
            out_b = shape_bytes(op.result_type)
            opnd_b = sum(shape_bytes(comp.shapes.get(o, ""))
                         for o in op.operands)
            if metric == "hbm":
                if op.opcode in ("dynamic-slice", "gather", "slice"):
                    val = 2 * out_b
                elif op.opcode in ("dynamic-update-slice", "scatter"):
                    upd = (shape_bytes(comp.shapes.get(op.operands[1], ""))
                           if len(op.operands) > 1 else out_b)
                    val = 2 * min(upd, out_b) if upd else out_b
                elif op.opcode == "fusion":
                    mm = _CALLS_RE.search(op.attrs)
                    val = _fusion_hbm_bytes(
                        op, comp, comps.get(mm.group(1)) if mm else None)
                elif op.opcode in _FREE_OPS or op.opcode in _ELEMENTWISE \
                        or op.opcode in ("while", "conditional"):
                    continue
                else:
                    val = out_b + opnd_b
            elif metric == "flops" and op.opcode == "dot":
                kk = 1
                mm = _LHS_C_RE.search(op.attrs)
                lhs_dims = shape_dims(comp.shapes.get(op.operands[0], ""))
                if mm and mm.group(1):
                    for d in mm.group(1).split(","):
                        if int(d) < len(lhs_dims):
                            kk *= lhs_dims[int(d)]
                val = 2.0 * shape_elems(op.result_type) * kk
            elif metric == "collective" and (
                    op.opcode in _COLLECTIVES
                    or (op.opcode.endswith("-start")
                        and op.opcode[:-6] in _COLLECTIVES)):
                val = opnd_b
            else:
                continue
            rows.append((val * m0, op.opcode, op.result_type[:60], name))
    rows.sort(reverse=True)
    return rows[:k]
