"""Structural HLO cost model with while-loop trip-count expansion.

XLA's built-in ``compiled.cost_analysis()`` counts a ``while`` body ONCE.
Scan-over-layers models (every LM here) would under-count FLOPs, memory
traffic and collective bytes by ~n_layers, so the roofline table would be
garbage.  This module parses the optimized HLO text into computations,
walks the entry computation and multiplies each ``while`` body/cond by
its ``known_trip_count`` backend_config (annotated by XLA's
WhileLoopTripCountAnnotator), recursing through nested loops, calls,
fusions and conditionals (max over branches).

Per-op accounting (per-device, since SPMD modules are per-partition):
  flops:
    dot          2 * numel(result) * prod(contracting dims)
    convolution  2 * numel(result) * prod(kernel spatial) * C_in/groups
    elementwise  numel(result)   (cheap; dots dominate)
  memory bytes (HBM traffic — reads = operand bytes, writes = result):
    counted for top-level "real" ops; free ops (bitcast, tuple, GTE,
    parameter) cost nothing; fusions count boundary traffic only (their
    internals live in registers/cache — the XLA fusion contract);
    dynamic-slice / dynamic-update-slice count slice-sized traffic.
  collective link bytes (per chip, ring accounting):
    all-reduce 2·s·(g-1)/g | all-gather s·(g-1)/g | reduce-scatter
    s·(g-1)   | all-to-all s·(g-1)/g | collective-permute s

Pod-crossing attribution: with ``pod_block`` (devices per pod; the pod
axis is the mesh's outermost, so pod(id) = id // pod_block), each
collective's replica_groups / source_target_pairs are parsed and its
link bytes are additionally booked as *pod-crossing* when any group or
pair spans two pods.  This is what benchmarks/spmd_bench.py feeds its
emulated inter-pod link model: intra-pod collectives ride the fast
fabric, pod-crossing ones are charged at the modeled link bandwidth.
"""

from __future__ import annotations

import dataclasses
import math
import re
from functools import lru_cache
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP_NAME_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_CALL_ATTR_RE = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*"?n"?[^0-9]*(\d+)')
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_FULL_RE = re.compile(r"replica_groups=\{\{(.*?)\}\}")
_GROUPS_IOTA_FULL_RE = re.compile(
    r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{\{(.*?)\}\}")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_WINDOW_RE = re.compile(r"window=\{size=([\dx]+)")
_OP_NAME_META_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')

_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "opt-barrier",
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _parse_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """All dtype[shape] occurrences in a type string (handles tuples)."""
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        shape = tuple(int(d) for d in dims.split(",")) if dims else ()
        out.append((dt, shape))
    return out


def _numel(shape) -> int:
    return math.prod(shape) if shape else 1


def _bytes_of(type_text: str) -> int:
    return sum(_numel(s) * _DTYPE_BYTES[d] for d, s in _parse_shapes(type_text))


@dataclasses.dataclass
class Op:
    name: str
    kind: str
    type_text: str       # result type(s)
    operands: List[str]  # operand op names
    line: str
    op_name: str = ""    # metadata op_name: the JAX scope path, or ''


@dataclasses.dataclass
class Computation:
    name: str
    params: Dict[str, str]          # param name -> type text
    ops: List[Op]
    table: Dict[str, str]           # op name -> result type text
    root: Optional[str] = None      # ROOT op name
    is_entry: bool = False          # the module's ENTRY computation

    def root_op(self) -> Optional[Op]:
        for op in self.ops:
            if op.name == self.root:
                return op
        return self.ops[-1] if self.ops else None


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: Optional[dict] = None
    coll_count: int = 0
    cross: Optional[dict] = None   # pod-crossing subset of coll

    def __post_init__(self):
        if self.coll is None:
            self.coll = {k: 0.0 for k in _COLLECTIVES}
        if self.cross is None:
            self.cross = {k: 0.0 for k in _COLLECTIVES}

    def add(self, other: "Cost", times: float = 1.0):
        self.flops += other.flops * times
        self.bytes += other.bytes * times
        self.coll_count += int(other.coll_count * times)
        for k in _COLLECTIVES:
            self.coll[k] += other.coll[k] * times
            self.cross[k] += other.cross[k] * times

    @property
    def coll_bytes(self) -> float:
        return sum(self.coll.values())

    @property
    def cross_bytes(self) -> float:
        return sum(self.cross.values())


# ---------------------------------------------------------------------------
# Parsing
# ---------------------------------------------------------------------------


def _split_top_args(argstr: str) -> List[str]:
    """Split 'a, b, c' at depth 0 (parens/braces/brackets nested)."""
    parts, depth, cur = [], 0, []
    for ch in argstr:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur).strip())
    return parts


# one array type with its optional layout; TPU layouts carry tiling in
# parentheses: bf16[8,1024]{1,0:T(8,128)(2,1)}
_TYPE_RE = re.compile(r"[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?")
_KIND_RE = re.compile(r"\s+([\w\-]+)\((.*)$")


def _split_op(rest: str):
    """(result type, op kind, text after the kind's open paren) of an
    instruction's right-hand side, or None."""
    if rest.startswith("("):   # tuple type: up to its matching paren
        end = _balanced(rest, 0)
    else:
        t = _TYPE_RE.match(rest)
        if not t:
            return None
        end = t.end()
    m = _KIND_RE.match(rest, end)
    return (rest[:end], m.group(1), m.group(2)) if m else None


def _balanced(text: str, start: int) -> int:
    """Index just past the paren that matches text[start] == '('."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    comment_re = re.compile(r"/\*.*?\*/")
    for raw in text.splitlines():
        line = comment_re.sub("", raw).rstrip()
        if not line or line.startswith(("HloModule", "//", "#")):
            continue
        # computation header: [ENTRY] %name (params...) -> type {
        if line.endswith("{") and "->" in line and "=" not in line.split("->")[0]:
            hdr = _COMP_NAME_RE.match(line.strip())
            if hdr:
                popen = line.index("(", hdr.start(1))
                pclose = _balanced(line, popen)
                param_text = line[popen + 1: pclose - 1]
                params = {}
                for part in _split_top_args(param_text):
                    if ":" in part:
                        pname, ptype = part.split(":", 1)
                        params[pname.strip().lstrip("%")] = ptype.strip()
                cur = Computation(hdr.group(1), params, [], dict(params),
                                  is_entry=line.lstrip().startswith("ENTRY"))
                comps[cur.name] = cur
                continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        d = _DEF_RE.match(line)
        if not d:
            continue
        name, rest = d.group(1), d.group(2)
        is_root = line.lstrip().startswith("ROOT ")
        m = _split_op(rest)
        if not m:
            continue
        type_text, kind, tail = m
        if is_root:
            cur.root = name
        # operand list = everything until the matching close paren
        depth, i = 1, 0
        while i < len(tail) and depth:
            if tail[i] in "([{":
                depth += 1
            elif tail[i] in ")]}":
                depth -= 1
            i += 1
        arg_text = tail[: i - 1] if depth == 0 else tail
        # Operand parts are either bare names ("%p0") or, in newer XLA
        # dumps, inline-typed ("f32[32,48]{1,0} %Arg_0.1") — take the
        # trailing %name of each top-level part either way.
        operands = []
        for part in _split_top_args(arg_text):
            names = _OPERAND_NAME_RE.findall(part)
            if names:
                operands.append(names[-1])
        meta = _OP_NAME_META_RE.search(line)
        op = Op(name, kind, type_text.strip(), operands, line,
                meta.group(1) if meta else "")
        cur.ops.append(op)
        cur.table[name] = op.type_text
    return comps


def entry_computation(comps: Dict[str, Computation]) -> Optional[Computation]:
    """The module's ENTRY computation (falls back to the one named
    ``main``-ish, then the last parsed — older dumps drop the keyword)."""
    for comp in comps.values():
        if comp.is_entry:
            return comp
    for comp in comps.values():
        if comp.name.startswith("main"):
            return comp
    return next(reversed(comps.values()), None) if comps else None


def entry_param_shapes(text: str) -> List[Tuple[str, str, Tuple[int, ...]]]:
    """(param_name, dtype, shape) for every leaf of the ENTRY
    computation's parameter list, nested tuple types flattened.

    This is what a compiled program *materializes as inputs*: the
    nmlint dense-weight audit (repro/analysis) checks that a packed
    decode step's entry never carries a dense-shaped weight that the
    packed store was supposed to replace."""
    comp = entry_computation(parse_module(text))
    if comp is None:
        return []
    out = []
    for pname, ptype in comp.params.items():
        for dtype, shape in _parse_shapes(ptype):
            out.append((pname, dtype, shape))
    return out


def count_hlo_ops(text: str, kinds: Tuple[str, ...],
                  entry_only: bool = False) -> int:
    """Census of op *kinds* (``scatter``, ``custom-call``, …) over the
    parsed module — every computation by default, so ops inside while
    bodies and fusions are seen exactly once (structural presence, not
    trip-weighted)."""
    comps = parse_module(text)
    total = 0
    for comp in comps.values():
        if entry_only and not comp.is_entry:
            continue
        total += sum(1 for op in comp.ops if op.kind in kinds)
    return total


# ---------------------------------------------------------------------------
# Cost walk
# ---------------------------------------------------------------------------


def _crosses_pod(line: str, pod_block: int) -> bool:
    """Does this collective's device grouping span two pods?

    pod(id) = id // pod_block (the pod axis is the mesh's outermost).
    Handles explicit replica_groups={{0,4},{1,5}}, the iota form
    replica_groups=[G,S]<=[dims](T(perm)), and collective-permute's
    source_target_pairs.  A collective with no visible grouping spans
    the world — conservatively counted as crossing.
    """
    def spans(ids) -> bool:
        return len({int(i) // pod_block for i in ids}) > 1

    m = _PAIRS_RE.search(line)
    if m:
        for pair in m.group(1).split("},{"):
            if spans(x for x in pair.split(",") if x.strip()):
                return True
        return False
    m = _GROUPS_FULL_RE.search(line)
    if m:
        for grp in m.group(1).split("},{"):
            if spans(x for x in grp.split(",") if x.strip()):
                return True
        return False
    m = _GROUPS_IOTA_FULL_RE.search(line)
    if m:
        dims = [int(x) for x in m.group(1).split(",")]
        space = [int(x) for x in m.group(2).split(",")]
        perm = ([int(x) for x in m.group(3).split(",")] if m.group(3)
                else list(range(len(space))))
        n = math.prod(space)
        ids = list(range(n))
        # arange(n).reshape(space).transpose(perm).reshape(G, S)
        strides = [0] * len(space)
        acc = 1
        for i in reversed(range(len(space))):
            strides[i] = acc
            acc *= space[i]
        pspace = [space[p] for p in perm]
        pstrides = [strides[p] for p in perm]
        flat = []
        idx = [0] * len(pspace)
        for _ in range(n):
            flat.append(sum(i * s for i, s in zip(idx, pstrides)))
            for d in reversed(range(len(pspace))):
                idx[d] += 1
                if idx[d] < pspace[d]:
                    break
                idx[d] = 0
        gsize = n // max(dims[0], 1) if dims else n
        for g in range(0, n, max(gsize, 1)):
            if spans(flat[g:g + gsize]):
                return True
        return False
    return True


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 1


def _operand_type(comp: Computation, name: str) -> str:
    return comp.table.get(name, "")


def _dot_flops(comp: Computation, op: Op) -> float:
    res = _parse_shapes(op.type_text)
    if not res:
        return 0.0
    n_out = _numel(res[0][1])
    m = _CONTRACT_RE.search(op.line)
    k = 1
    if m and op.operands:
        lhs_shapes = _parse_shapes(_operand_type(comp, op.operands[0]))
        if lhs_shapes:
            lshape = lhs_shapes[0][1]
            dims = [int(x) for x in m.group(1).split(",") if x]
            for d in dims:
                if d < len(lshape):
                    k *= lshape[d]
    return 2.0 * n_out * k


def _conv_flops(comp: Computation, op: Op) -> float:
    res = _parse_shapes(op.type_text)
    if not res or len(op.operands) < 2:
        return 0.0
    n_out = _numel(res[0][1])
    ker = _parse_shapes(_operand_type(comp, op.operands[1]))
    if not ker:
        return 0.0
    # HWIO kernel: all dims except the last (O) contribute per-output MACs
    kshape = ker[0][1]
    per_out = _numel(kshape[:-1]) if len(kshape) > 1 else 1
    return 2.0 * n_out * per_out


class HloCostModel:
    def __init__(self, text: str, pod_block: Optional[int] = None):
        self.comps = parse_module(text)
        self.pod_block = pod_block
        self._memo: Dict[str, Cost] = {}
        entry = None
        for name in self.comps:
            if re.search(r"^main\b|\bentry\b", name) or name.startswith("main"):
                entry = name
        if entry is None:  # fall back: the computation never called by others
            called = set()
            for c in self.comps.values():
                for op in c.ops:
                    called.update(_CALL_ATTR_RE.findall(op.line))
                    b = _BRANCH_RE.search(op.line)
                    if b:
                        called.update(x.strip().lstrip("%")
                                      for x in b.group(1).split(","))
            candidates = [n for n in self.comps if n not in called]
            entry = candidates[-1] if candidates else next(iter(self.comps))
        self.entry = entry

    def cost(self, comp_name: Optional[str] = None) -> Cost:
        comp_name = comp_name or self.entry
        if comp_name in self._memo:
            return self._memo[comp_name]
        comp = self.comps.get(comp_name)
        total = Cost()
        self._memo[comp_name] = total  # cycle guard (shouldn't happen)
        if comp is None:
            return total
        for op in comp.ops:
            self._op_cost(comp, op, total)
        return total

    def _op_cost(self, comp: Computation, op: Op, total: Cost):
        kind = op.kind
        if kind in _FREE_OPS:
            return
        result_bytes = _bytes_of(op.type_text)
        operand_bytes = sum(_bytes_of(_operand_type(comp, o))
                            for o in op.operands)

        if kind == "while":
            body = cond = None
            mb = re.search(r"body=%?([\w.\-]+)", op.line)
            mc = re.search(r"condition=%?([\w.\-]+)", op.line)
            body = mb.group(1) if mb else None
            cond = mc.group(1) if mc else None
            mt = _TRIP_RE.search(op.line)
            trips = int(mt.group(1)) if mt else 1
            if body:
                total.add(self.cost(body), trips)
            if cond:
                total.add(self.cost(cond), trips + 1)
            return
        if kind == "conditional":
            b = _BRANCH_RE.search(op.line)
            names = ([x.strip().lstrip("%") for x in b.group(1).split(",")]
                     if b else _CALL_ATTR_RE.findall(op.line))
            if names:
                branch_costs = [self.cost(n) for n in names]
                worst = max(branch_costs, key=lambda c: (c.flops + c.bytes))
                total.add(worst)
            return
        if kind == "call":
            for target in _CALL_ATTR_RE.findall(op.line):
                total.add(self.cost(target))
            return
        if kind == "fusion":
            # boundary traffic + any dots hiding inside the fused comp.
            # In-place slice fusions (root = dynamic-update-slice /
            # dynamic-slice) alias the big buffer: traffic is the slice,
            # not the buffer — XLA's buffer-assignment contract.
            targets = _CALL_ATTR_RE.findall(op.line)
            fused = self.comps.get(targets[0]) if targets else None
            root = fused.root_op() if fused else None
            root_kind = root.kind if root else ""
            # unwrap elementwise/layout wrappers to find an aliasing root
            _WRAPPERS = {"bitcast", "convert", "copy", "reshape",
                         "transpose"}
            seen_wrap = 0
            while (root is not None and root_kind in _WRAPPERS
                   and root.operands and seen_wrap < 8):
                nxt = None
                for o2 in fused.ops:
                    if o2.name == root.operands[0]:
                        nxt = o2
                        break
                if nxt is None:
                    break
                root, root_kind = nxt, nxt.kind
                seen_wrap += 1
            if root_kind == "dynamic-update-slice" and root and \
                    len(root.operands) >= 2:
                upd = _bytes_of(fused.table.get(root.operands[1], ""))
                small = sum(b for b in
                            (_bytes_of(_operand_type(comp, o))
                             for o in op.operands)
                            if b < result_bytes)
                total.bytes += 2 * upd + small
            elif root_kind == "dynamic-slice":
                total.bytes += 2 * result_bytes
            else:
                total.bytes += result_bytes + operand_bytes
            for target in targets:
                inner = self.cost(target)
                total.flops += inner.flops
            return
        if kind == "dot":
            total.flops += _dot_flops(comp, op)
            total.bytes += result_bytes + operand_bytes
            return
        if kind == "convolution":
            total.flops += _conv_flops(comp, op)
            total.bytes += result_bytes + operand_bytes
            return
        base = kind.replace("-start", "")
        if base in _COLLECTIVES:
            g = _group_size(op.line)
            size = max(result_bytes, operand_bytes)
            if g > 1 or base == "collective-permute":
                frac = (g - 1) / g
                if base == "all-reduce":
                    link = 2 * operand_bytes * frac
                elif base == "all-gather":
                    link = result_bytes * frac
                elif base == "reduce-scatter":
                    link = result_bytes * (g - 1)
                elif base == "all-to-all":
                    link = size * frac
                else:
                    link = size
                total.coll[base] += link
                total.coll_count += 1
                if self.pod_block and _crosses_pod(op.line, self.pod_block):
                    total.cross[base] += link
            total.bytes += result_bytes + operand_bytes
            return
        if kind.endswith("-done"):
            return
        if kind == "dynamic-slice":
            total.bytes += 2 * result_bytes  # read slice + write slice
            return
        if kind == "dynamic-update-slice":
            if len(op.operands) >= 2:
                upd = _bytes_of(_operand_type(comp, op.operands[1]))
                total.bytes += 2 * upd
            return
        if kind in ("copy", "copy-start", "transpose", "reshape",
                    "broadcast", "iota", "reverse", "slice", "pad",
                    "concatenate", "gather", "scatter", "reduce",
                    "reduce-window", "select-and-scatter", "sort", "rng",
                    "convert", "compare", "select", "clamp", "map",
                    "custom-call"):
            total.bytes += result_bytes + operand_bytes
            if kind in ("reduce", "map", "sort"):
                total.flops += _numel(_parse_shapes(op.type_text)[0][1]) \
                    if _parse_shapes(op.type_text) else 0
            return
        # generic elementwise (add, multiply, tanh, exponential, ...)
        total.bytes += result_bytes + operand_bytes
        shapes = _parse_shapes(op.type_text)
        if shapes:
            total.flops += _numel(shapes[0][1])


def analyze(hlo_text: str, pod_block: Optional[int] = None) -> dict:
    """Entry point: optimized HLO text -> per-device cost dict.

    With ``pod_block`` (devices per pod) the collectives dict also
    carries ``pod_crossing``: the ring link bytes of collectives whose
    groups span pods — the traffic that rides the slow inter-pod links.
    """
    model = HloCostModel(hlo_text, pod_block=pod_block)
    c = model.cost()
    out = {
        "flops": c.flops,
        "bytes": c.bytes,
        "collectives": {**{k: int(v) for k, v in c.coll.items()},
                        "count": c.coll_count,
                        "total": int(c.coll_bytes)},
    }
    if pod_block:
        out["collectives"]["pod_crossing"] = int(c.cross_bytes)
    return out


# ---------------------------------------------------------------------------
# Mask-op census (pre-generation dataflow gate)
# ---------------------------------------------------------------------------
#
# The pre-generation invariant: a lowered train step derives each
# prunable parameter's N:M masks exactly ONCE (at WU time), so the traced
# step contains exactly one top_k/sort selection per prunable parameter —
# and none inside the scanned model body.  Counting jaxpr primitives is
# compiler-version-stable (optimized HLO spelling of top_k varies across
# XLA releases); benchmarks/pregen_bench.py and tests/test_pregen.py both
# gate on this census.

MASK_PRIMS = ("top_k", "sort", "approx_top_k")


def count_jaxpr_prims(jaxpr, names=MASK_PRIMS, pred=None) -> int:
    """Recursively count primitive occurrences in a (Closed)Jaxpr,
    descending through scan/while/cond/pjit/remat/custom-vjp sub-jaxprs.
    ``pred(eqn)`` optionally filters the name-matched equations."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    total = 0
    for eqn in inner.eqns:
        if eqn.primitive.name in names and (pred is None or pred(eqn)):
            total += 1
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                total += count_jaxpr_prims(sub, names, pred)
    return total


def _subjaxprs(val):
    if hasattr(val, "jaxpr") or type(val).__name__ == "Jaxpr":
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _subjaxprs(v)


def nm_selection_pred(n: int, m: int):
    """Equation predicate matching only *N:M mask* selections.

    Every mask derivation in the system scores ``(..., M)`` groups with
    a ``top_k`` of k=N (sparsity._topn_group_mask; legacy packing also
    sorts M-wide groups), so a selection whose trailing operand dim is
    not M — e.g. the MoE router's top_k over the expert dim — is routing
    compute, not a mask derivation, and must not trip the mask-once
    census.  A stacked (E, …, M) expert leaf batches all experts into
    ONE such equation: the census counts stacked leaves as one
    derivation per parameter.  Caveat: when a model's expert count
    equals M and its routing top-k equals N the shapes are
    indistinguishable — census tests/benches pick (n, m) apart from the
    router dims.
    """
    def pred(eqn) -> bool:
        if not eqn.invars or not getattr(eqn.invars[0], "aval", None):
            return False
        shape = eqn.invars[0].aval.shape
        if not shape or shape[-1] != m:
            return False
        if eqn.primitive.name == "top_k":
            return eqn.params.get("k") == n
        return True
    return pred


def count_mask_ops(fn, *args, nm=None) -> int:
    """top_k/sort census of ``fn`` traced on ``args`` (arrays or
    ShapeDtypeStructs).  ``nm=(n, m)`` restricts the count to
    N:M-mask-shaped selections (``nm_selection_pred``) — required for
    MoE models, whose router top_k would otherwise be counted."""
    import jax

    pred = nm_selection_pred(*nm) if nm is not None else None
    return count_jaxpr_prims(jax.make_jaxpr(fn)(*args), pred=pred)


# ---------------------------------------------------------------------------
# Diagnostics: where do the bytes/flops/collective terms come from?
# ---------------------------------------------------------------------------


def breakdown(hlo_text: str, top: int = 25) -> dict:
    """Attribute cost to individual top-level ops (weighted by the trip
    counts of enclosing loops).  The perf-iteration loop reads this to
    find the dominant contributors (redundant all-gathers, fat copies,
    remat recompute)."""
    model = HloCostModel(hlo_text)
    rows = []

    def walk(comp_name: str, weight: float, ctx: str):
        comp = model.comps.get(comp_name)
        if comp is None:
            return
        for op in comp.ops:
            kind = op.kind
            if kind == "while":
                mb = re.search(r"body=%?([\w.\-]+)", op.line)
                mt = _TRIP_RE.search(op.line)
                trips = int(mt.group(1)) if mt else 1
                if mb:
                    walk(mb.group(1), weight * trips,
                         f"{ctx}>while[{trips}]")
                continue
            if kind == "conditional":
                b = _BRANCH_RE.search(op.line)
                if b:
                    names = [x.strip().lstrip("%")
                             for x in b.group(1).split(",")]
                    costs = [(n, model.cost(n)) for n in names]
                    worst = max(costs, key=lambda nc: nc[1].flops + nc[1].bytes)
                    walk(worst[0], weight, f"{ctx}>cond")
                continue
            if kind == "call":
                for target in _CALL_ATTR_RE.findall(op.line):
                    walk(target, weight, f"{ctx}>call")
                continue
            one = Cost()
            model._op_cost(comp, op, one)
            if one.flops or one.bytes or one.coll_bytes:
                rows.append({
                    "op": f"{comp_name}/{op.name}", "kind": kind,
                    "ctx": ctx, "weight": weight,
                    "flops": one.flops * weight,
                    "bytes": one.bytes * weight,
                    "coll": one.coll_bytes * weight,
                    "line": op.line.strip()[:200],
                })

    walk(model.entry, 1.0, "entry")
    out = {"total_flops": sum(r["flops"] for r in rows),
           "total_bytes": sum(r["bytes"] for r in rows),
           "total_coll": sum(r["coll"] for r in rows)}
    for key in ("flops", "bytes", "coll"):
        rows.sort(key=lambda r, k=key: -r[k])
        out[f"top_{key}"] = [dict(r) for r in rows[:top]]
    by_kind = {}
    for r in rows:
        d = by_kind.setdefault(r["kind"], {"flops": 0.0, "bytes": 0.0,
                                           "coll": 0.0, "n": 0})
        d["flops"] += r["flops"]
        d["bytes"] += r["bytes"]
        d["coll"] += r["coll"]
        d["n"] += 1
    out["by_kind"] = by_kind
    return out
