"""Post-compile HLO analysis: collective inventory with loop multipliers.

XLA's ``cost_analysis`` counts a ``while`` body once regardless of trip
count, and collectives inside the layer-scan likewise appear once in the
HLO text.  This parser walks the partitioned module, finds every collective
(all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute,
incl. async ``-start`` forms), attributes it to its computation, and
multiplies by the enclosing while-loop trip counts (XLA's
``known_trip_count`` backend config when present, else parsed from the
loop condition's LT-compare constant; nesting multiplies).  Operand sizes
come from the definition table (HLO prints shapes at definitions only).
``/*index=N*/`` comments (emitted inside wide tuple types) are stripped
before matching — they otherwise break instruction parsing.

Collectives additionally carry their parsed ``replica_groups`` so
multi-axis meshes can attribute each one to a mesh axis:
``mesh_axis_groups`` computes the device groups a reduction over one axis
(or a joint axis combination) of a row-major mesh produces, and
``groups_reduce_over`` matches a record against them — how the 2-D RANL
engine proves "exactly one DATA-axis param-shard all-reduce per round"
while its model-axis solve broadcasts ride in the same loop, and how the
hierarchical engines' joint ``("pod", "data")`` init psums stay
attributable on the 3-D mesh.  ``max_array_bytes`` reports the
largest single (non-tuple) buffer in the partitioned module — the
per-device memory claim (no d×d curvature buffer) is asserted on it.

Each collective record also carries ``operand_dtypes`` (parsed from the
operand definitions) and per-collective ``operand_bytes``, so payload
compression is assertable per collective: the int8-compressed engine's
in-loop param psum must show an ``s8`` operand at ≥ 3.5× fewer bytes
than the uncompressed build's ``f32`` one.
"""

from __future__ import annotations

import itertools
import re
from dataclasses import dataclass, field

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "ragged-all-to-all")

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*"
    r"(\(?[^=]*?\)?)\s*"            # result shape (may be a tuple)
    r"([\w\-]+)\(")                  # opcode
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_COMMENT_RE = re.compile(r"/\*.*?\*/")
_GROUPS_BRACES_RE = re.compile(r"replica_groups=\{(\{[\d,\{\}]*\})\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def parse_replica_groups(line: str):
    """``replica_groups=...`` of a collective -> tuple of id tuples.

    Handles both HLO spellings: explicit braces ``{{0,2},{1,3}}`` and the
    iota form ``[G,S]<=[dims]T(perm)`` (arange over the source dims,
    transposed by ``perm``, reshaped to G groups of S).  Returns None when
    the line carries no replica_groups (single-replica modules).
    """
    m = _GROUPS_BRACES_RE.search(line)
    if m:
        return tuple(
            tuple(int(x) for x in grp.split(",") if x)
            for grp in re.findall(r"\{([\d,]*)\}", m.group(1)))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        perm = ([int(x) for x in m.group(4).split(",")] if m.group(4)
                else list(range(len(dims))))
        n = 1
        for dim in dims:
            n *= dim
        # arange(n).reshape(dims).transpose(perm).reshape(g, s), in pure
        # python (row-major strides)
        strides = [1] * len(dims)
        for i in range(len(dims) - 2, -1, -1):
            strides[i] = strides[i + 1] * dims[i + 1]
        pdims = [dims[p] for p in perm]
        pstrides = [strides[p] for p in perm]
        flat = []
        idx = [0] * len(pdims)
        for _ in range(n):
            flat.append(sum(i * st for i, st in zip(idx, pstrides)))
            for ax in range(len(pdims) - 1, -1, -1):
                idx[ax] += 1
                if idx[ax] < pdims[ax]:
                    break
                idx[ax] = 0
        return tuple(tuple(flat[i * s:(i + 1) * s]) for i in range(g))
    return None


def mesh_axis_groups(axis_sizes, axis):
    """Device-id groups of a reduction over mesh axis/axes ``axis``.

    ``axis_sizes``: the mesh shape, devices laid out row-major (the
    ``Mesh(np.array(devices).reshape(shape), names)`` convention).
    ``axis`` is one axis index or an iterable of them — each group holds
    the linearized ids that share every OTHER axis coordinate, exactly
    the replica_groups a ``psum`` over those axes lowers to (a joint
    multi-axis reduction, e.g. the hierarchical engines' init psum over
    ``("pod", "data")``, is ONE collective whose groups span both axes).
    """
    axes = sorted({axis} if isinstance(axis, int) else set(axis))
    sizes = list(axis_sizes)
    strides = [1] * len(sizes)
    for i in range(len(sizes) - 2, -1, -1):
        strides[i] = strides[i + 1] * sizes[i + 1]
    other = [i for i in range(len(sizes)) if i not in axes]

    def _offsets(dims_idx):
        offs = [0]
        for ax in dims_idx:
            offs = [o + k * strides[ax] for o in offs
                    for k in range(sizes[ax])]
        return offs

    member = _offsets(axes)
    groups = []
    coords = [0] * len(other)
    while True:
        base = sum(c * strides[o] for c, o in zip(coords, other))
        groups.append(tuple(base + m for m in member))
        for i in range(len(other) - 1, -1, -1):
            coords[i] += 1
            if coords[i] < sizes[other[i]]:
                break
            coords[i] = 0
        else:
            break
    return tuple(groups)


def groups_reduce_over(record_groups, axis_sizes, axis: int) -> bool:
    """True iff a collective's replica_groups reduce over mesh axis
    ``axis`` (group membership compared as sets, order-insensitive)."""
    if record_groups is None:
        return False
    want = {frozenset(g) for g in mesh_axis_groups(axis_sizes, axis)}
    return {frozenset(g) for g in record_groups} == want


def collective_axes(record_groups, axis_sizes, axis_names):
    """Explicit mesh-axis attribution of a collective's replica groups.

    Returns a tuple of labels: the matching axis name(s) from
    ``axis_names``, or ``("replicated",)`` for collectives that move no
    data between distinct devices — replica_groups absent (single-replica
    modules print none) or every group a singleton.  A degenerate
    size-1 mesh axis produces singleton groups, so on a 1-device mesh
    every collective is labeled "replicated" rather than ambiguously
    matching every axis (the old ``groups_reduce_over``-only callers
    silently matched ALL size-1 axes at once).  A JOINT reduction over
    several axes at once (one collective whose groups span e.g.
    ``("pod", "data")`` — the hierarchical engines' init-phase psums)
    attributes to the smallest matching axis COMBINATION, returned in
    ``axis_names`` order.  An empty tuple means the groups match no
    declared axis or combination.
    """
    if record_groups is None:
        return ("replicated",)
    if all(len(g) <= 1 for g in record_groups):
        return ("replicated",)
    labels = tuple(
        name for i, name in enumerate(axis_names)
        if axis_sizes[i] > 1
        and groups_reduce_over(record_groups, axis_sizes, i))
    if labels:
        return labels
    got = {frozenset(g) for g in record_groups}
    big = [i for i in range(len(axis_names)) if axis_sizes[i] > 1]
    for r in range(2, len(big) + 1):
        for combo in itertools.combinations(big, r):
            want = {frozenset(g)
                    for g in mesh_axis_groups(axis_sizes, combo)}
            if got == want:
                return tuple(axis_names[i] for i in combo)
    return ()


def shape_bytes(type_str: str) -> int:
    """Total bytes of all array shapes in a (possibly tuple) type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


@dataclass
class Instr:
    name: str
    comp: str
    opcode: str
    result_bytes: int
    operands: list[str]
    line: str
    tuple_result: bool = False
    result_dtypes: tuple[str, ...] = ()


@dataclass
class CollectiveRecord:
    kind: str
    comp: str
    operand_bytes: int
    result_bytes: int
    multiplier: int
    count: int = 1
    replica_groups: tuple | None = None
    operand_dtypes: tuple[str, ...] = ()

    @property
    def total_bytes(self) -> int:
        return self.operand_bytes * self.multiplier * self.count

    def reduces_over(self, axis_sizes, axis: int) -> bool:
        return groups_reduce_over(self.replica_groups, axis_sizes, axis)

    def axes(self, axis_sizes, axis_names):
        """Explicit axis attribution — see ``collective_axes``."""
        return collective_axes(self.replica_groups, axis_sizes, axis_names)


def parse_module(text: str):
    """-> (instrs by name, comp of each instr, whiles, comp order)."""
    instrs: dict[str, Instr] = {}
    comp_instrs: dict[str, list[str]] = {}
    current = "?"
    for raw in text.splitlines():
        line = _COMMENT_RE.sub("", raw).rstrip()
        mc = _COMP_RE.match(line.strip())
        if mc and ("->" in line) and line.strip().endswith("{"):
            current = mc.group(1)
            comp_instrs.setdefault(current, [])
            continue
        mi = _INSTR_RE.match(line)
        if not mi:
            continue
        name, rtype, opcode = mi.groups()
        paren = line[line.index(opcode + "(") + len(opcode):]
        # operand names: %refs inside the first paren group (rough but the
        # definition table lookup filters non-instruction refs)
        ops = _OPERAND_RE.findall(paren.split("),", 1)[0])
        instrs[name] = Instr(name=name, comp=current, opcode=opcode,
                             result_bytes=shape_bytes(rtype),
                             operands=ops, line=line.strip(),
                             tuple_result=rtype.strip().startswith("("),
                             result_dtypes=tuple(
                                 dt for dt, _ in _SHAPE_RE.findall(rtype)
                                 if dt in DTYPE_BYTES))
        comp_instrs.setdefault(current, []).append(name)
    return instrs, comp_instrs


def _while_edges(instrs):
    """[(parent_comp, body_comp, cond_comp, known_trip)] per while instr.

    ``known_trip`` is XLA's authoritative ``known_trip_count`` backend
    config when printed, else None (fall back to condition parsing)."""
    edges = []
    for ins in instrs.values():
        if ins.opcode == "while":
            mb = re.search(r"body=%?([\w\.\-]+)", ins.line)
            mc = re.search(r"condition=%?([\w\.\-]+)", ins.line)
            mk = re.search(r"known_trip_count[^\d]*(\d+)", ins.line)
            if mb and mc:
                edges.append((ins.comp, mb.group(1), mc.group(1),
                              int(mk.group(1)) if mk else None))
    return edges


def _trip_count(cond_comp: str, comp_instrs, instrs, default: int) -> int:
    """Parse `compare(iter, constant(N)), direction=LT` in the condition."""
    consts = {}
    for name in comp_instrs.get(cond_comp, ()):
        ins = instrs[name]
        if ins.opcode == "constant":
            m = re.search(r"constant\((-?\d+)\)", ins.line)
            if m:
                consts[name] = int(m.group(1))
    for name in comp_instrs.get(cond_comp, ()):
        ins = instrs[name]
        if ins.opcode == "compare" and "direction=LT" in ins.line:
            for op in ins.operands:
                if op in consts:
                    return max(consts[op], 1)
    return default


def comp_multipliers(instrs, comp_instrs, default_trip: int = 1):
    """Multiplier per computation (product of enclosing while trip counts)."""
    mult = {comp: 1 for comp in comp_instrs}
    edges = _while_edges(instrs)
    # iterate to fixpoint (nesting depth is tiny)
    for _ in range(8):
        changed = False
        for parent, body, cond, known_trip in edges:
            trip = (known_trip if known_trip is not None
                    else _trip_count(cond, comp_instrs, instrs,
                                     default_trip))
            want = mult.get(parent, 1) * trip
            if mult.get(body) != want:
                mult[body] = want
                changed = True
            if mult.get(cond, 1) != mult.get(parent, 1):
                mult[cond] = mult.get(parent, 1)
                changed = True
        if not changed:
            break
    return mult


def collect_collectives(text: str, default_trip: int = 1):
    """-> list[CollectiveRecord] (deduped -start/-done pairs)."""
    instrs, comp_instrs = parse_module(text)
    mult = comp_multipliers(instrs, comp_instrs, default_trip)
    records = []
    for ins in instrs.values():
        base = ins.opcode.removesuffix("-start")
        if base not in COLLECTIVES or ins.opcode.endswith("-done"):
            continue
        operand_bytes = sum(instrs[o].result_bytes for o in ins.operands
                            if o in instrs)
        operand_dtypes = tuple(
            dt for o in ins.operands if o in instrs
            for dt in instrs[o].result_dtypes)
        if operand_bytes == 0:
            operand_bytes = ins.result_bytes
            operand_dtypes = ins.result_dtypes
        records.append(CollectiveRecord(
            kind=base, comp=ins.comp, operand_bytes=operand_bytes,
            result_bytes=ins.result_bytes,
            multiplier=mult.get(ins.comp, 1),
            replica_groups=parse_replica_groups(ins.line),
            operand_dtypes=operand_dtypes))
    return records


def max_array_bytes(text: str) -> int:
    """Largest single (non-tuple) buffer any instruction produces.

    Tuple-typed results (while carries, wide parameters, multi-output
    fusions) are aggregates of separately-allocated buffers, so they are
    skipped; their elements are counted where they are produced.  On a
    partitioned module this bounds per-device array residency — the
    dimension-sharded engine asserts no device sees a d×d curvature
    buffer with it.
    """
    instrs, _ = parse_module(text)
    return max((i.result_bytes for i in instrs.values()
                if not i.tuple_result), default=0)


def summarize_collectives(records):
    by_kind: dict[str, dict] = {}
    for r in records:
        d = by_kind.setdefault(r.kind, {"count": 0, "bytes": 0,
                                        "in_loop_bytes": 0})
        d["count"] += r.count
        d["bytes"] += r.total_bytes
        if r.multiplier > 1:
            d["in_loop_bytes"] += r.total_bytes
    total = sum(d["bytes"] for d in by_kind.values())
    return {"total_bytes": total, "by_kind": by_kind}


def cost_raw_summary(compiled) -> dict:
    """``compiled.cost_analysis()`` -> the raw FLOPs/bytes dict the
    dry-run records and the obs journal header surfaces (scan bodies
    counted once)."""
    ca = compiled.cost_analysis() or {}
    return {k: float(v) for k, v in ca.items()
            if k in ("flops", "bytes accessed", "transcendentals")}


def module_report(text: str, default_trip: int = 1) -> dict:
    """One-call memory + communication report for a partitioned module.

    Returns ``{"max_array_bytes", "collectives": summarize_collectives
    output, "records": per-collective rows}`` — what the engines' HLO
    tests assert piecewise, packaged for human consumption (the
    ``launch.train --dump-hlo`` CLI prints it so an operator can check
    the per-device buffer ceiling and all-reduce budget of a config
    without reading HLO text).
    """
    records = collect_collectives(text, default_trip)
    return {
        "max_array_bytes": max_array_bytes(text),
        "collectives": summarize_collectives(records),
        "records": [
            {"kind": r.kind, "operand_bytes": r.operand_bytes,
             "multiplier": r.multiplier, "comp": r.comp,
             "operand_dtypes": list(r.operand_dtypes)}
            for r in sorted(records, key=lambda r: -r.total_bytes)],
    }
