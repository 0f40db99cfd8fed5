"""HLO text analysis: the communication layer's 'source code' on TPU.

The paper instruments ExaMPI's C++ source. Our communication implementation
is the collective schedule inside compiled XLA modules, so this module
parses HLO text (``lowered.as_text()`` / ``compiled.as_text()``) to extract
every collective op, its operand/result bytes, replica groups, and whether
it is asynchronous (``-start``/``-done`` pairs) — the raw material for both
the roofline collective term and the modeled device timeline.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1, "f8e4m3b11fnuz": 1,
    "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
)

# dtype[1,2,3] with optional layout {..}
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
# op definition:  %name = <type-or-tuple> opcode(
# tuple types may contain /*index=N*/ comments, so match them non-greedily
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"([\w\-]+)\("
)
_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{(\{[0-9,{} ]*\})\}")
_REPLICA_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_SOURCE_TARGET_RE = re.compile(r"source_target_pairs=\{([^}]*(?:\},\{[^}]*)*)\}")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?\bop_name="((?:[^"\\]|\\.)*)"')


_OP_START_RE = re.compile(r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=")


def logical_lines(hlo_text: str) -> List[str]:
    """Join wrapped op definitions into single logical lines.

    Printed HLO wraps long tuple types / operand lists across physical
    lines; every parser here operates on the joined form."""
    out: List[str] = []
    cur: Optional[str] = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        is_op_start = bool(_OP_START_RE.match(line))
        is_close = stripped == "}"
        is_header = (not line.startswith(" ")) and stripped.endswith("{")
        if is_op_start or is_close or is_header:
            if cur is not None:
                out.append(cur)
                cur = None
            if is_op_start:
                cur = line
            else:
                out.append(line)
        elif cur is not None:
            cur += " " + stripped
        else:
            out.append(line)
    if cur is not None:
        out.append(cur)
    return out


def shape_bytes(dtype: str, dims_str: str) -> int:
    nbytes = DTYPE_BYTES.get(dtype)
    if nbytes is None:
        return 0
    n = 1
    if dims_str.strip():
        for d in dims_str.split(","):
            n *= int(d)
    return n * nbytes


def _type_bytes(type_str: str) -> int:
    """Total bytes of a type string (possibly a tuple type)."""
    return sum(shape_bytes(dt, dims) for dt, dims in _SHAPE_RE.findall(type_str))


@dataclasses.dataclass
class CollectiveOp:
    name: str
    opcode: str                 # normalized: no -start/-done suffix
    is_async: bool
    operand_bytes: int          # sum of operand sizes (the spec's metric)
    result_bytes: int
    group_size: int             # replica group size (1 if unknown)
    num_groups: int
    line: str

    @property
    def wire_bytes(self) -> int:
        """Modeled bytes crossing links per participating device, using the
        standard ring-algorithm costs (used for the roofline collective term):

          all-reduce:        2*B*(g-1)/g     (reduce-scatter + all-gather)
          all-gather:        B_out*(g-1)/g
          reduce-scatter:    B_in*(g-1)/g
          all-to-all:        B*(g-1)/g
          collective-permute/broadcast: B
        """
        g = max(1, self.group_size)
        if self.opcode == "all-reduce":
            return int(2 * self.operand_bytes * (g - 1) / g)
        if self.opcode == "all-gather":
            return int(self.result_bytes * (g - 1) / g)
        if self.opcode == "reduce-scatter":
            return int(self.operand_bytes * (g - 1) / g)
        if self.opcode in ("all-to-all", "ragged-all-to-all"):
            return int(self.operand_bytes * (g - 1) / g)
        return self.operand_bytes


def _call_operand_str(line: str, def_end: int) -> str:
    """Everything inside the op's call parens starting at def_end."""
    call = line[def_end:]
    depth = 1
    end = len(call)
    for i, ch in enumerate(call):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return call[:end]


def symbol_table(hlo_text: str) -> Dict[str, str]:
    """name -> result-type string for every op definition in the module.

    Optimized HLO usually omits operand types at call sites, so collective
    operand sizes must be resolved through definitions."""
    table: Dict[str, str] = {}
    for line in logical_lines(hlo_text):
        m = _DEF_RE.match(line)
        if m:
            table[m.group(1)] = m.group(2)
    return table


def _operand_bytes(operand_str: str, types: Optional[Dict[str, str]]) -> int:
    inline = _type_bytes(operand_str)
    if inline:
        return inline
    if not types:
        return 0
    total = 0
    for tok in operand_str.split(","):
        m = re.search(r"%([\w.\-]+)\s*$", tok.strip())
        if m:
            total += _type_bytes(types.get(m.group(1), ""))
    return total


def collective_from_line(
    line: str, types: Optional[Dict[str, str]] = None
) -> Optional[CollectiveOp]:
    m = _DEF_RE.match(line)
    if not m:
        return None
    name, result_type, opcode = m.groups()
    base = opcode
    is_async = False
    if base.endswith("-done"):
        return None  # bytes counted at -start
    if base.endswith("-start"):
        base = base[: -len("-start")]
        is_async = True
    if base not in COLLECTIVE_OPS:
        return None
    operand_bytes = _operand_bytes(_call_operand_str(line, m.end()), types)
    result_bytes = _type_bytes(result_type)
    if is_async and result_bytes > operand_bytes:
        # async start returns (input, output, ...) tuples; keep output size
        result_bytes -= operand_bytes
    group_size, num_groups = _parse_groups(line)
    return CollectiveOp(
        name=name, opcode=base, is_async=is_async,
        operand_bytes=operand_bytes, result_bytes=result_bytes,
        group_size=group_size, num_groups=num_groups, line=line.strip(),
    )


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    """Extract every collective op (counting ``-start`` but not ``-done``)."""
    types = symbol_table(hlo_text)
    ops: List[CollectiveOp] = []
    for line in logical_lines(hlo_text):
        op = collective_from_line(line, types)
        if op is not None:
            ops.append(op)
    return ops


def _parse_groups(line: str) -> Tuple[int, int]:
    m = _REPLICA_GROUPS_IOTA_RE.search(line)
    if m:
        num, size = int(m.group(1)), int(m.group(2))
        return size, num
    m = _REPLICA_GROUPS_RE.search(line)
    if m:
        groups = re.findall(r"\{([0-9, ]*)\}", "{" + m.group(1) + "}")
        sizes = [len([x for x in g.split(",") if x.strip()]) for g in groups]
        if sizes:
            return max(sizes), len(sizes)
    # iota format like replica_groups=[2,256]<=[512] appears in newer HLO
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
    if m:
        return int(m.group(2)), int(m.group(1))
    m = _SOURCE_TARGET_RE.search(line)
    if m:
        pairs = m.group(1).count("{") + 1
        return 2, pairs
    return 1, 1


@dataclasses.dataclass
class CollectiveStats:
    total_operand_bytes: int
    total_wire_bytes: int
    count: int
    by_opcode: Dict[str, Dict[str, int]]
    async_count: int

    def summary(self) -> str:
        lines = [
            f"collectives: {self.count} ops, "
            f"{self.total_operand_bytes / 1e9:.3f} GB operands, "
            f"{self.total_wire_bytes / 1e9:.3f} GB modeled wire traffic, "
            f"{self.async_count} async"
        ]
        for op, d in sorted(self.by_opcode.items()):
            lines.append(
                f"  {op:20s} x{d['count']:<4d} {d['operand_bytes'] / 1e9:9.3f} GB op, "
                f"{d['wire_bytes'] / 1e9:9.3f} GB wire"
            )
        return "\n".join(lines)


def collective_stats(hlo_text: str) -> CollectiveStats:
    ops = parse_collectives(hlo_text)
    by: Dict[str, Dict[str, int]] = defaultdict(
        lambda: {"count": 0, "operand_bytes": 0, "wire_bytes": 0}
    )
    for op in ops:
        d = by[op.opcode]
        d["count"] += 1
        d["operand_bytes"] += op.operand_bytes
        d["wire_bytes"] += op.wire_bytes
    return CollectiveStats(
        total_operand_bytes=sum(o.operand_bytes for o in ops),
        total_wire_bytes=sum(o.wire_bytes for o in ops),
        count=len(ops),
        by_opcode=dict(by),
        async_count=sum(1 for o in ops if o.is_async),
    )


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """instruction name -> ``op_name`` for every instruction of a module
    whose metadata carries one: the path of ``jax.named_scope``s (and the
    primitive) that emitted it, e.g. ``jit(f)/halo.interior/add``. A
    fusion carries its own metadata, which XLA sets from its root op; a
    profiler trace names device ops by these instruction names."""
    out: Dict[str, str] = {}
    for line in logical_lines(hlo_text):
        m = _DEF_RE.match(line)
        n = _OP_NAME_RE.search(line) if m else None
        if n:
            out[m.group(1)] = n.group(1)
    return out


_WHILE_TRIP_RE = re.compile(r"trip_count=\"?(\d+)")


def while_trip_counts(hlo_text: str) -> List[int]:
    """Trip counts XLA annotated on while loops (layer-scan bodies)."""
    return [int(x) for x in _WHILE_TRIP_RE.findall(hlo_text)]


def op_histogram(hlo_text: str) -> Dict[str, int]:
    """Opcode histogram — useful for spotting remat-duplicated compute
    ('count duplicate op names') and layout-change churn."""
    hist: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            hist[m.group(3)] += 1
    return dict(hist)
