"""HLO-level analysis of compiled XLA artifacts.

``compiled.cost_analysis()`` counts each ``while`` body ONCE, so any
scan-over-layers program is undercounted by ~n_layers; and it reports no
collective traffic at all.  This module therefore implements a cost model
directly over the optimized HLO text:

* per-computation symbol tables (every op line declares its result type)
  give operand shapes;
* ``dot`` FLOPs = 2 * batch * M * N * K from the inline contracting/batch
  dims; elementwise/fusion ops are approximated at 1 FLOP per output
  element (documented approximation — dots dominate every model here);
* bytes-accessed per op = operand bytes + result bytes at fusion
  boundaries (XLA's own fusion cost convention);
* a call graph (while bodies x trip count, fusions/calls x 1) aggregates
  to module totals — trip counts are parsed from the loop condition's
  ``compare(_, constant(N)), direction=LT`` pattern;
* collective traffic = sum of *operand* sizes of every all-gather /
  all-reduce / reduce-scatter / all-to-all / collective-permute call site.

After SPMD partitioning the module is the per-device program, so all
quantities are per-device.  tests/test_hlo_analysis.py validates the
parser against ``cost_analysis`` on loop-free programs and against
hand-counted scans.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]\w*)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")
_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->")
_CALL_ATTR_RE = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations)=\{?%?([\w\.\-,%\s]+)\}?")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _shape_info(type_str: str) -> tuple[int, int]:
    """(total elements, total bytes) over all dtype[dims] tokens."""
    elems = bytes_ = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        bytes_ += n * _DTYPE_BYTES[dt]
    return elems, bytes_


def _operand_section(line: str, open_idx: int) -> str:
    depth = 0
    for i in range(open_idx, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                return line[open_idx + 1:i]
    return line[open_idx + 1:]


@dataclasses.dataclass
class _Op:
    name: str
    opcode: str
    result_type: str
    operands: list[str]
    attrs: str
    line: str


def _parse_computations(text: str) -> dict[str, dict]:
    comps: dict[str, dict] = {}
    current = None
    for raw in text.splitlines():
        line = raw.strip()
        if current is None:
            m = _HEADER_RE.match(line)
            if m and line.endswith("{"):
                current = m.group(2)
                comps[current] = {"ops": [], "entry": bool(m.group(1))}
            continue
        if line == "}":
            current = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        name, rtype, opcode = m.group(1), m.group(2), m.group(3)
        open_idx = line.index(m.group(0)) + len(m.group(0)) - 1
        osec = _operand_section(line, open_idx)
        operands = re.findall(r"%([\w\.\-]+)", osec)
        attrs = line[open_idx + len(osec) + 2:]
        comps[current]["ops"].append(
            _Op(name=name, opcode=opcode, result_type=rtype,
                operands=operands, attrs=attrs, line=line))
    return comps


_ELEMENTWISE_FLOP_OPS = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "log", "tanh", "rsqrt", "sqrt", "negate", "abs",
    "cosine", "sine", "logistic", "expm1", "log1p", "fusion", "select",
    "compare", "and", "or", "reduce", "reduce-window", "clamp",
}

_FREE_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "reshape", "after-all", "partition-id", "replica-id",
}


def _dot_flops(op: _Op, symtab: dict[str, str]) -> float:
    lhs_t = symtab.get(op.operands[0], "")
    rhs_t = symtab.get(op.operands[1], "") if len(op.operands) > 1 else ""
    lm = _SHAPE_RE.search(lhs_t)
    rm = _SHAPE_RE.search(rhs_t)
    if not lm or not rm:
        # fall back: result elements * 2 (can't see operand shapes)
        elems, _ = _shape_info(op.result_type)
        return 2.0 * elems

    def dims_of(m):
        return [int(d) for d in m.group(2).split(",") if d]

    lhs, rhs = dims_of(lm), dims_of(rm)

    def attr_dims(key):
        m = re.search(key + r"=\{([0-9,]*)\}", op.line)
        return [int(d) for d in m.group(1).split(",") if d] if m else []

    lc = attr_dims("lhs_contracting_dims")
    lb = attr_dims("lhs_batch_dims")
    k = 1
    for d in lc:
        k *= lhs[d]
    b = 1
    for d in lb:
        b *= lhs[d]
    m_ = 1
    for i, d in enumerate(lhs):
        if i not in lc and i not in lb:
            m_ *= d
    rc = attr_dims("rhs_contracting_dims")
    rb = attr_dims("rhs_batch_dims")
    n_ = 1
    for i, d in enumerate(rhs):
        if i not in rc and i not in rb:
            n_ *= d
    return 2.0 * b * m_ * n_ * k


def _trip_count(cond_name: str, comps: dict) -> int:
    """Parse `compare(iter, constant(N)), direction=LT` in the condition."""
    comp = comps.get(cond_name)
    if comp is None:
        return 1
    symtab = {op.name: op for op in comp["ops"]}
    for op in comp["ops"]:
        if op.opcode == "compare" and "direction=LT" in op.line:
            for operand in op.operands:
                target = symtab.get(operand)
                if target is not None and target.opcode == "constant":
                    m = _CONST_RE.search(target.line)
                    if m:
                        return int(m.group(1))
        # compare may be wrapped in a fusion; search constants directly
    consts = [int(m.group(1)) for op in comp["ops"]
              for m in [_CONST_RE.search(op.line)] if m]
    return max(consts) if consts else 1


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict[str, float]
    count_by_kind: dict[str, int]

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_by_kind.values()))

    @property
    def total_count(self) -> int:
        return int(sum(self.count_by_kind.values()))


@dataclasses.dataclass
class ModuleCost:
    flops: float
    bytes_accessed: float
    transcendentals: float
    collectives: CollectiveStats


def analyze_hlo_text(text: str) -> ModuleCost:
    comps = _parse_computations(text)
    entry = next((n for n, c in comps.items() if c["entry"]), None)
    # computations reachable only as fusion bodies are costed at call site
    fusion_targets = set()
    for c in comps.values():
        for op in c["ops"]:
            if op.opcode == "fusion":
                m = re.search(r"calls=%([\w\.\-]+)", op.line)
                if m:
                    fusion_targets.add(m.group(1))

    memo: dict[str, tuple] = {}

    def cost_of(name: str, depth=0) -> tuple:
        if name in memo:
            return memo[name]
        comp = comps.get(name)
        if comp is None or depth > 50:
            return (0.0, 0.0, 0.0, defaultdict(float), defaultdict(int))
        flops = bytes_ = transc = 0.0
        coll_b: dict[str, float] = defaultdict(float)
        coll_c: dict[str, int] = defaultdict(int)
        symtab = {op.name: op.result_type for op in comp["ops"]}
        for op in comp["ops"]:
            relems, rbytes = _shape_info(op.result_type)
            obytes = sum(_shape_info(symtab.get(o, ""))[1]
                         for o in op.operands)
            if op.opcode in _FREE_OPS:
                continue
            if op.opcode == "while":
                m = re.search(r"condition=%([\w\.\-]+)", op.line)
                cond = m.group(1) if m else None
                m = re.search(r"body=%([\w\.\-]+)", op.line)
                body = m.group(1) if m else None
                trips = _trip_count(cond, comps) if cond else 1
                bf, bb, bt, bcb, bcc = cost_of(body, depth + 1) if body \
                    else (0, 0, 0, {}, {})
                flops += bf * trips
                bytes_ += bb * trips
                transc += bt * trips
                for k, v in bcb.items():
                    coll_b[k] += v * trips
                for k, v in bcc.items():
                    coll_c[k] += v * trips
                continue
            if op.opcode in ("call", "conditional", "custom-call"):
                m = _CALL_ATTR_RE.search(op.line)
                if m:
                    for target in re.findall(r"[\w\.\-]+", m.group(1)):
                        tf, tb, tt, tcb, tcc = cost_of(target, depth + 1)
                        flops += tf
                        bytes_ += tb
                        transc += tt
                        for k, v in tcb.items():
                            coll_b[k] += v
                        for k, v in tcc.items():
                            coll_c[k] += v
                bytes_ += rbytes + obytes
                continue
            # leaf-ish ops.  Slicing/in-place ops move only the slice, not
            # the buffer they index into (XLA aliases the buffer through
            # the loop): charge 2x the moved region, not the operand.
            if op.opcode == "dynamic-slice":
                bytes_ += 2 * rbytes
                continue
            if op.opcode == "dynamic-update-slice":
                upd = _shape_info(symtab.get(op.operands[1], ""))[1] \
                    if len(op.operands) > 1 else rbytes
                bytes_ += 2 * upd
                continue
            if op.opcode == "fusion" and (
                    "dynamic-update-slice" in op.name
                    or "dynamic-slice" in op.name
                    or "dynamic_update_slice" in op.name):
                # DUS/DS-rooted fusion: result/largest operand are the
                # aliased buffer; traffic = everything else, twice.
                sizes = sorted((_shape_info(symtab.get(o, ""))[1]
                                for o in op.operands), reverse=True)
                moved = sum(sizes[1:]) if sizes else 0
                bytes_ += 2 * max(moved, 1)
                m = re.search(r"calls=%([\w\.\-]+)", op.line)
                if m:
                    ff, _, ft, _, _ = cost_of(m.group(1), depth + 1)
                    flops += ff
                    transc += ft
                continue
            bytes_ += rbytes + obytes
            if op.opcode == "dot":
                flops += _dot_flops(op, symtab)
            elif op.opcode == "convolution":
                flops += 2.0 * relems  # no conv ops emitted by our models
            elif op.opcode in _COLLECTIVES or \
                    op.opcode.rstrip("-start") in _COLLECTIVES:
                kind = op.opcode.replace("-start", "")
                if kind in _COLLECTIVES:
                    coll_b[kind] += obytes
                    coll_c[kind] += 1
            elif op.opcode == "fusion":
                m = re.search(r"calls=%([\w\.\-]+)", op.line)
                if m:
                    ff, _, ft, _, _ = cost_of(m.group(1), depth + 1)
                    flops += ff
                    transc += ft
            elif op.opcode in _ELEMENTWISE_FLOP_OPS:
                flops += relems
                if op.opcode in ("exponential", "log", "tanh", "logistic",
                                 "power", "expm1", "log1p", "cosine",
                                 "sine"):
                    transc += relems
        out = (flops, bytes_, transc, coll_b, coll_c)
        memo[name] = out
        return out

    if entry is None:
        return ModuleCost(0.0, 0.0, 0.0, CollectiveStats({}, {}))
    f, b, t, cb, cc = cost_of(entry)
    return ModuleCost(flops=f, bytes_accessed=b, transcendentals=t,
                      collectives=CollectiveStats(dict(cb), dict(cc)))


# fusion computations cost their internals for flops, but their internal
# bytes are free (VMEM-resident) — handled above by only adding rbytes /
# obytes at call sites.


def parse_collective_bytes(hlo_text: str) -> CollectiveStats:
    return analyze_hlo_text(hlo_text).collectives


@dataclasses.dataclass
class CompiledStats:
    """Everything the roofline needs about one compiled step."""

    flops: float                 # per-device, while-trip-corrected
    bytes_accessed: float
    transcendentals: float
    collectives: CollectiveStats
    xla_flops: float             # raw cost_analysis (body-once) for x-ref
    xla_bytes: float
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    generated_code_bytes: int

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "transcendentals": self.transcendentals,
            "collective_bytes": self.collectives.total_bytes,
            "collective_count": self.collectives.total_count,
            "collective_bytes_by_kind": self.collectives.bytes_by_kind,
            "collective_count_by_kind": self.collectives.count_by_kind,
            "xla_cost_analysis_flops": self.xla_flops,
            "xla_cost_analysis_bytes": self.xla_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
        }


def analyze_compiled(compiled, hlo_text: str | None = None) -> CompiledStats:
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    if hlo_text is None:
        hlo_text = compiled.as_text()
    mc = analyze_hlo_text(hlo_text)
    return CompiledStats(
        flops=mc.flops,
        bytes_accessed=mc.bytes_accessed,
        transcendentals=mc.transcendentals,
        collectives=mc.collectives,
        xla_flops=float(cost.get("flops", 0.0)),
        xla_bytes=float(cost.get("bytes accessed", 0.0)),
        argument_bytes=getattr(mem, "argument_size_in_bytes", 0),
        output_bytes=getattr(mem, "output_size_in_bytes", 0),
        temp_bytes=getattr(mem, "temp_size_in_bytes", 0),
        generated_code_bytes=getattr(mem, "generated_code_size_in_bytes", 0),
    )
