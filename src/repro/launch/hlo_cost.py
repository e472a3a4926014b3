"""HLO-text cost model with WHILE-LOOP TRIP-COUNT accounting.

Motivation: XLA's
``compiled.cost_analysis()`` reports a while body ONCE — a scan-over-layers
model is undercounted by ~n_layers×. This module re-derives
(flops, bytes accessed, per-kind collective bytes) by parsing the
post-SPMD HLO of ``compiled.as_text()``:

  * per-computation symbol tables give operand shapes;
  * ``while`` ops multiply body+cond cost by the ``known_trip_count``
    backend config (fallback: largest integer constant in the condition);
  * ``fusion`` bytes = fusion operands + result (XLA semantics: fused
    intermediates never touch HBM), flops recurse into the fused body;
  * collectives: per-device ICI bytes with ring multipliers —
    all-gather ≈ result·(n−1)/n, reduce-scatter ≈ operand·(n−1)/n,
    all-reduce ≈ 2·operand·(n−1)/n, all-to-all ≈ operand·(n−1)/n,
    collective-permute = result; n parsed from replica_groups.

All shapes in the post-SPMD module are per-device, so every number here is
a PER-CHIP quantity.
"""
from __future__ import annotations

import json
import re
from collections import defaultdict
from dataclasses import dataclass, field

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
               "f8e4m3fn": 1, "f8e5m2": 1, "token": 0, "opaque": 0}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
_NAME_RE = re.compile(r"^[\w.\-]+$")
_OP_RE = re.compile(r"^(\([^)]*\)|\S+)\s+([\w\-]+)\((.*)$")
_PARAM_RE = re.compile(r"%?([\w.\-]+):\s*(\([^)]*\)|[\w\[\],{}\s]+?)(?:,|\)\s*->)")
# replica_groups appears in three layouts across XLA versions:
#   dims form          replica_groups=[n,m]            (n groups of m)
#   iota form          replica_groups=[n,m]<=[k] / <=[a,b]T(1,0)  (newer XLA)
#   explicit-ids form  replica_groups={{0,1,2,3},{4,5,6,7}}
# The dims regex matches the first two (the iota suffix follows the same
# [n,m] shape prefix); the braces form counts ids in the first group.
_GROUPS_DIMS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_IDS_RE = re.compile(r"replica_groups=\{\{([\d,\s]*)\}")
_TRIP_RE = re.compile(r'"known_trip_count":\s*\{"n":\s*"(\d+)"')

ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "logistic", "sqrt", "rsqrt", "cbrt", "power", "sign", "floor",
    "ceil", "round-nearest-even", "round-nearest-afz", "compare", "select",
    "and", "or", "xor", "not", "clamp", "atan2", "remainder", "cosine",
    "sine", "tan", "erf", "is-finite", "shift-left",
    "shift-right-arithmetic", "shift-right-logical", "reduce",
    "reduce-window", "map", "sort", "clz", "popcnt",
}
ZERO_COST = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "bitcast-convert", "copy", "copy-start", "copy-done", "reshape",
    "broadcast", "transpose", "iota", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "reverse", "gather",
    "scatter", "convert", "real", "imag", "after-all", "add-dependency",
    "partition-id", "replica-id", "rng", "rng-bit-generator",
    "rng-get-and-update-state", "optimization-barrier", "domain",
    "get-dimension-size",
}
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: dict = field(default_factory=lambda: defaultdict(float))

    def add(self, other, mult=1.0):
        self.flops += mult * other.flops
        self.bytes += mult * other.bytes
        for k, v in other.coll.items():
            self.coll[k] += mult * v

    @property
    def coll_bytes(self):
        return float(sum(self.coll.values()))


def _shape_bytes(type_str):
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _shape_elems(type_str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return 0
    dims = m.group(2)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _first_dims(type_str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclass
class Op:
    name: str
    opcode: str
    result_type: str
    rest: str          # text after the opcode's '(' — operands + attrs


class Computation:
    def __init__(self, name, sig):
        self.name = name
        self.ops: list[Op] = []
        self.symbols: dict[str, str] = {}   # value name -> type string
        for pname, ptype in _PARAM_RE.findall(sig + ")"):
            self.symbols[pname] = ptype.strip()


def parse_module(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur = None
    for line in text.splitlines():
        if not line:
            continue
        if not line.startswith(" "):
            # header with or without a signature: '%name (sig) -> T {',
            # 'ENTRY name {' (unoptimized dumps omit the signature; the
            # param types then come from parameter(N) defs in the body)
            m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(\(.*)?\s*\{?\s*$",
                         line)
            if m and line.rstrip().endswith("{") and m.group(1) != "HloModule":
                cur = Computation(m.group(1), m.group(2) or "(")
                comps[cur.name] = cur
                if line.startswith("ENTRY"):
                    comps["__entry__"] = cur
            else:
                cur = None
            continue
        if cur is None:
            continue
        dm = _DEF_RE.match(line)
        if not dm:
            continue
        name, rhs = dm.group(1), dm.group(2)
        om = _OP_RE.match(rhs)
        if not om:
            continue
        rtype, opcode, rest = om.groups()
        cur.symbols[name] = rtype
        cur.ops.append(Op(name, opcode, rtype, rest))
    return comps


def _operands(op: Op):
    """Names of value operands (up to the closing paren of the op).

    Operands look like ``f32[32,64]{1,0} %Arg_0.1`` (the ``%`` and the
    leading type are both optional depending on the XLA version), so the
    comma split must not recurse into ``[dims]``/``{layout}`` brackets and
    the operand name is the LAST whitespace token of each segment."""
    depth_p, depth_b, segs, cur = 0, 0, [], []
    for ch in op.rest:
        if ch == "(":
            depth_p += 1
        elif ch == ")":
            if depth_p == 0:
                break
            depth_p -= 1
        elif ch in "[{":
            depth_b += 1
        elif ch in "]}":
            depth_b -= 1
        if ch == "," and depth_p == 0 and depth_b == 0:
            segs.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    segs.append("".join(cur).strip())
    out = []
    for seg in segs:
        if not seg:
            continue
        name = seg.split()[-1].lstrip("%")
        if _NAME_RE.match(name):
            out.append(name)
    return out


def _called(op: Op):
    """Computation names referenced via calls=/to_apply=/body=/condition=/
    branch_computations=."""
    names = []
    for key in ("calls=", "to_apply=", "body=", "condition="):
        m = re.search(re.escape(key) + r"%?([\w.\-]+)", op.rest)
        if m:
            names.append((key[:-1], m.group(1)))
    m = re.search(r"branch_computations=\{([^}]*)\}", op.rest)
    if m:
        for b in m.group(1).split(","):
            names.append(("branch", b.strip().lstrip("%")))
    return names


def _dot_flops(op: Op, comp: Computation):
    opnds = _operands(op)
    out_elems = _shape_elems(op.result_type)
    if not opnds:
        return 2.0 * out_elems
    lhs_type = comp.symbols.get(opnds[0], "")
    lhs_dims = _first_dims(lhs_type)
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.rest)
    contract = 1
    if m and lhs_dims:
        for idx in m.group(1).split(","):
            if idx and int(idx) < len(lhs_dims):
                contract *= lhs_dims[int(idx)]
    return 2.0 * out_elems * contract


def _conv_flops(op: Op, comp: Computation):
    out_elems = _shape_elems(op.result_type)
    opnds = _operands(op)
    k = 1
    if len(opnds) >= 2:
        kdims = _first_dims(comp.symbols.get(opnds[1], ""))
        for d in kdims:
            k *= d
        # divide by output features (last dim convention is ambiguous) —
        # use window size only as a conservative multiplier
        m = re.search(r"size=([\dx]+)", op.rest)
        if m:
            k = 1
            for d in m.group(1).split("x"):
                k *= int(d)
    return 2.0 * out_elems * k


def _group_size(rest: str, default=2) -> int:
    """Participant count per replica group of a collective op — the ``n``
    in the ring multipliers. Handles the dims/iota/explicit-ids layouts
    (see the regex comment above)."""
    m = _GROUPS_DIMS_RE.search(rest)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_IDS_RE.search(rest)
    if m:
        ids = [t for t in m.group(1).split(",") if t.strip()]
        if ids:
            return max(len(ids), 1)
    return default


def _trip_count(op: Op, comps, default=1):
    m = _TRIP_RE.search(op.rest)
    if m:
        return int(m.group(1))
    cname = dict(_called(op)).get("condition")
    if cname and cname in comps:
        consts = []
        for o in comps[cname].ops:
            mm = re.search(r"constant\((\d+)\)", o.opcode + "(" + o.rest)
            if mm:
                consts.append(int(mm.group(1)))
        if consts:
            return max(consts)
    return default


def _op_bytes(op: Op, comp: Computation):
    """HBM traffic model per op. In-place-updating ops (dynamic-update-slice,
    scatter) only touch the updated region, and slicing ops only the slice —
    charging full-buffer operand bytes would overcount loop bodies by the
    buffer/slice ratio (measured 1000×+ on scan-heavy models)."""
    oc = op.opcode
    opnds = _operands(op)
    if oc == "dynamic-update-slice":
        upd = opnds[1] if len(opnds) > 1 else None
        return 2.0 * _shape_bytes(comp.symbols.get(upd, "")) if upd else 0.0
    if oc in ("dynamic-slice", "slice", "gather"):
        return 2.0 * _shape_bytes(op.result_type)
    if oc == "scatter":
        upd = opnds[-1] if opnds else None
        return 2.0 * _shape_bytes(comp.symbols.get(upd, "")) if upd else 0.0
    b = _shape_bytes(op.result_type)
    for o in opnds:
        b += _shape_bytes(comp.symbols.get(o, ""))
    return float(b)


_ALIAS_OPS = {"bitcast", "reshape", "copy", "transpose", "bitcast-convert"}
_SLICE_OPS = {"dynamic-slice", "slice", "gather"}


def _fusion_bytes(op: Op, comp: Computation, inner: Computation | None):
    """HBM traffic of a fusion: intermediates stay on-chip; a parameter that
    is only read through (dynamic-)slice/gather costs the slice, not the
    buffer; a dynamic-update-slice root writes the update region in place.
    This is what makes scan-over-layers byte counts sane (fused cache reads
    inside a 4096-trip loop would otherwise charge the full cache per step).
    """
    if inner is None:
        return _op_bytes(op, comp)
    param_names = [n for n in inner.symbols
                   if not any(o.name == n for o in inner.ops)]
    alias = {}          # inner value -> originating param

    def origin(name):
        seen = set()
        while name in alias and name not in seen:
            seen.add(name)
            name = alias[name]
        return name

    sliced, fully_read = set(), set()
    bytes_total = 0.0
    root = inner.ops[-1] if inner.ops else None
    for iop in inner.ops:
        srcs = _operands(iop)
        if iop.opcode in _ALIAS_OPS and len(srcs) == 1:
            alias[iop.name] = srcs[0]
            continue
        if iop.opcode in _SLICE_OPS:
            if srcs:
                src = origin(srcs[0])
                if src in param_names:
                    sliced.add(src)
            mult = 2.0 if iop.opcode == "gather" else 1.0
            bytes_total += mult * _shape_bytes(iop.result_type)
            # index operands of slices are tiny; skip
            continue
        if iop.opcode == "dynamic-update-slice" and iop is root:
            upd = srcs[1] if len(srcs) > 1 else None
            if upd is not None:
                ub = _shape_bytes(inner.symbols.get(origin(upd), "")) or \
                    _shape_bytes(inner.symbols.get(upd, ""))
                bytes_total += 2.0 * ub
            if srcs:
                sliced.add(origin(srcs[0]))   # in-place buffer: no full read
            continue
        for s in srcs:
            so = origin(s)
            if so in param_names:
                fully_read.add(so)
    if not (root and root.opcode == "dynamic-update-slice"):
        bytes_total += _shape_bytes(op.result_type)
    for pname in fully_read:
        bytes_total += _shape_bytes(inner.symbols.get(pname, ""))
    return bytes_total


def analyze(text: str) -> Cost:
    comps = parse_module(text)
    entry = comps.get("__entry__")
    memo: dict[str, Cost] = {}

    def eval_comp(comp: Computation, want_bytes=True) -> Cost:
        key = comp.name + ("|b" if want_bytes else "|f")
        if key in memo:
            return memo[key]
        memo[key] = Cost()  # break recursion defensively
        total = Cost()
        for op in comp.ops:
            oc = op.opcode
            called = dict(_called(op))
            if oc == "while":
                trips = _trip_count(op, comps)
                for role in ("body", "condition"):
                    cn = called.get(role)
                    if cn and cn in comps:
                        total.add(eval_comp(comps[cn], want_bytes), trips)
            elif oc == "fusion":
                cn = called.get("calls")
                if cn and cn in comps:
                    inner = eval_comp(comps[cn], want_bytes=False)
                    total.flops += inner.flops
                    for k, v in inner.coll.items():
                        total.coll[k] += v
                if want_bytes:
                    total.bytes += _fusion_bytes(op, comp, comps.get(cn))
            elif oc in ("call", "custom-call", "async-start", "async-done"):
                cn = called.get("calls") or called.get("to_apply")
                if cn and cn in comps:
                    total.add(eval_comp(comps[cn], want_bytes))
                elif want_bytes:
                    total.bytes += _op_bytes(op, comp)
            elif oc == "conditional":
                branches = [n for r, n in _called(op) if r == "branch"]
                if branches:
                    sub = [eval_comp(comps[b], want_bytes) for b in branches
                           if b in comps]
                    if sub:
                        worst = max(sub, key=lambda c: c.flops)
                        total.add(worst)
            elif any(oc.startswith(c) for c in COLLECTIVES):
                n = _group_size(op.rest)
                ring = (n - 1) / n if n > 1 else 0.0
                res_b = _shape_bytes(op.result_type)
                opnd_b = sum(_shape_bytes(comp.symbols.get(o, ""))
                             for o in _operands(op))
                if oc.startswith("all-gather"):
                    total.coll["all-gather"] += res_b * ring
                elif oc.startswith("all-reduce"):
                    total.coll["all-reduce"] += 2.0 * opnd_b * ring
                elif oc.startswith("reduce-scatter"):
                    total.coll["reduce-scatter"] += opnd_b * ring
                elif oc.startswith("all-to-all"):
                    total.coll["all-to-all"] += opnd_b * ring
                else:
                    total.coll["collective-permute"] += res_b
                if want_bytes:
                    total.bytes += _op_bytes(op, comp)
            elif oc == "dot":
                total.flops += _dot_flops(op, comp)
                if want_bytes:
                    total.bytes += _op_bytes(op, comp)
            elif oc == "convolution":
                total.flops += _conv_flops(op, comp)
                if want_bytes:
                    total.bytes += _op_bytes(op, comp)
            elif oc in ELEMENTWISE:
                total.flops += float(_shape_elems(op.result_type))
                if want_bytes:
                    total.bytes += _op_bytes(op, comp)
            elif oc in ZERO_COST:
                if want_bytes and oc in ("copy", "dynamic-update-slice",
                                         "gather", "scatter", "concatenate",
                                         "dynamic-slice", "pad", "slice",
                                         "transpose", "broadcast"):
                    total.bytes += _op_bytes(op, comp)
            else:
                # unknown op: count elementwise flops + bytes conservatively
                total.flops += float(_shape_elems(op.result_type))
                if want_bytes:
                    total.bytes += _op_bytes(op, comp)
        memo[key] = total
        return total

    if entry is None:
        return Cost()
    return eval_comp(entry)


def summarize(text: str) -> dict:
    c = analyze(text)
    return {"flops": c.flops, "bytes": c.bytes,
            "collective_bytes": c.coll_bytes,
            "collectives": dict(c.coll)}
