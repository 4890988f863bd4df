"""Compiled-HLO text parsing: the shared matcher layer under shardlint.

XLA's post-optimization module (``jitted.lower(...).compile().as_text()``)
is the ground truth for what actually runs per device: shapes there are
*per-device* (post-SPMD-partitioning) shapes, collectives are explicit
``all-reduce``/``all-gather``/... instructions, and buffer donation shows
up (or silently doesn't) in the module header's ``input_output_alias`` map.
PR 1 found the replicated ``[V, D]`` dE accumulator by hand-grepping this
text; these helpers turn that grep into reusable structure shared by
``analysis/core.py`` and ``scripts/hlo_dy_check.py``.

Nothing here imports jax — it is pure text parsing, unit-testable on
string fixtures without compiling anything.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

Shape = Tuple[str, Tuple[int, ...]]  # (dtype, dims)

DTYPE_BYTES: Dict[str, int] = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    # fp8 families (quantized gradient collectives, ops/qcomm.py)
    "f8e4m3fn": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1, "f8e4m3": 1,
    "f8e5m2": 1, "f8e5m2fnuz": 1, "f8e3m4": 1,
}

# Longer alternatives first — the regex engine takes the first match, so
# `f8e4m3fn` must not be eaten by a shorter `f8e4m3` alternative.
_SHAPE_RE = re.compile(
    r"\b(pred|bf16|f16|f32|f64"
    r"|f8e4m3b11fnuz|f8e4m3fnuz|f8e4m3fn|f8e4m3|f8e5m2fnuz|f8e5m2|f8e3m4"
    r"|s4|s8|s16|s32|s64|u4|u8|u16|u32|u64|c64|c128)"
    r"\[([0-9,]*)\]"
)

# `%name = <type> opcode(...)` — the type may be a tuple; the opcode is the
# first bare word after the (possibly layout-annotated) result type.
_INSTR_RE = re.compile(
    r"^\s*(?P<root>ROOT\s+)?%?(?P<name>[\w.\-]+)\s+=\s+(?P<rhs>.+)$")
_OPCODE_RE = re.compile(r"(?P<opcode>[a-z][a-z0-9\-]*)\(")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s+\(.*\{\s*$")

# Collectives counted toward the per-step budget.  Async pairs count once
# (the -start op carries the payload; -done is bookkeeping).
COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective-permute", "collective-broadcast",
)
_COLLECTIVE_SET = frozenset(COLLECTIVE_OPS) | frozenset(
    op + "-start" for op in COLLECTIVE_OPS)


def shape_bytes(shape: Shape) -> int:
    dtype, dims = shape
    n = DTYPE_BYTES.get(dtype, 4)
    for d in dims:
        n *= d
    return n


def iter_shapes(fragment: str) -> Iterator[Shape]:
    """All ``dtype[d0,d1,...]`` tokens in an HLO text fragment, in order."""
    for m in _SHAPE_RE.finditer(fragment):
        dims = tuple(int(d) for d in m.group(2).split(",")) \
            if m.group(2) else ()
        yield (m.group(1), dims)


@dataclasses.dataclass
class Instruction:
    """One parsed HLO instruction (output side only)."""

    name: str
    opcode: str
    shapes: List[Shape]        # result shapes (tuple types contribute all)
    computation: str
    line: str
    is_root: bool = False

    def result_bytes(self) -> int:
        return sum(shape_bytes(s) for s in self.shapes)


def _result_type_and_opcode(rhs: str) -> Optional[Tuple[str, str]]:
    """Split an instruction's RHS into (result-type text, opcode)."""
    if rhs.startswith("("):
        # tuple type: find the matching close paren
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    m = _OPCODE_RE.search(rhs, i + 1)
                    return (rhs[:i + 1], m.group("opcode")) if m else None
        return None
    m = _SHAPE_RE.match(rhs)
    if not m:
        return None
    # skip a layout annotation like {1,0} or {1,0:T(8,128)}
    rest = rhs[m.end():]
    if rest.startswith("{"):
        close = rest.find("}")
        rest = rest[close + 1:] if close >= 0 else rest
    om = _OPCODE_RE.match(rest.lstrip())
    if om is None:
        return None
    return rhs[:m.end()], om.group("opcode")


def parse_instructions(hlo_text: str) -> List[Instruction]:
    """Parse every ``%x = type op(...)`` line across all computations."""
    instrs: List[Instruction] = []
    computation = ""
    for raw in hlo_text.splitlines():
        comp = _COMPUTATION_RE.match(raw)
        if comp is not None and "=" not in raw.split("(")[0]:
            computation = comp.group("name")
            continue
        m = _INSTR_RE.match(raw)
        if m is None or "(" not in m.group("rhs"):
            continue
        split = _result_type_and_opcode(m.group("rhs"))
        if split is None:
            continue
        type_text, opcode = split
        instrs.append(Instruction(
            name=m.group("name"),
            opcode=opcode,
            shapes=list(iter_shapes(type_text)),
            computation=computation,
            line=raw.strip(),
            is_root=bool(m.group("root")),
        ))
    return instrs


def entry_computation_name(hlo_text: str) -> str:
    """Name of the module's ENTRY computation ("" when absent).

    ``parse_instructions`` strips the ``ENTRY`` prefix when recording the
    ``computation`` field, so schedule walkers (obs/memory.py) need the
    raw-line scan here to know *which* computation is the entry."""
    for raw in hlo_text.splitlines():
        s = raw.lstrip()
        if not s.startswith("ENTRY"):
            continue
        m = _COMPUTATION_RE.match(s)
        if m is not None:
            return m.group("name")
    return ""


_OPERAND_REF_RE = re.compile(r"%([\w.\-]+)")


def instruction_operands(ins: Instruction) -> List[str]:
    """Operand instruction names of one parsed instruction, in order.

    Post-optimization HLO prints operands as ``type %name`` tokens inside
    the opcode's balanced parens (``dot(f32[8,16]{1,0} %Arg_0.1, ...)``);
    attributes after the close paren (``calls=%fused_computation``,
    ``to_apply=%region``) reference computations, not values, and are
    excluded by the balanced scan.  This is the def-use edge extractor
    under the memory ledger's live-range analysis."""
    m = _INSTR_RE.match(ins.line)
    if m is None:
        return []
    rhs = m.group("rhs")
    split = _result_type_and_opcode(rhs)
    if split is None:
        return []
    type_text, opcode = split
    start = rhs.find(opcode + "(", len(type_text) - 1)
    if start < 0:
        return []
    open_paren = start + len(opcode)
    depth, i = 0, open_paren
    while i < len(rhs):
        if rhs[i] == "(":
            depth += 1
        elif rhs[i] == ")":
            depth -= 1
            if depth == 0:
                break
        i += 1
    region = rhs[open_paren + 1:i]
    return _OPERAND_REF_RE.findall(region)


_METADATA_BLOCK_RE = re.compile(r"metadata=\{[^}]*\}")
_CALLED_RE = re.compile(
    r"\b(?P<how>body|condition|to_apply|calls|true_computation"
    r"|false_computation|branch_computations|called_computations)="
    r"(?:%?(?P<one>[\w.\-]+)|\{(?P<many>[^}]*)\})")


def called_computations(ins: Instruction) -> List[Tuple[str, str]]:
    """``(attribute, computation)`` for every computation an instruction
    names after its operands: a ``while``'s ``body`` and ``condition``, a
    ``call``'s or a fusion's ``calls``, a reduce's ``to_apply``, a
    conditional's branches.  What runs inside those computations runs on
    this instruction's behalf, so an attribution that the callee's own
    metadata does not settle falls back on the caller's
    (obs/trace.py ``scope_map``)."""
    m = _INSTR_RE.match(ins.line)
    if m is None:
        return []
    # a metadata string may hold anything: taken out before the search
    tail = _METADATA_BLOCK_RE.sub("", m.group("rhs"))
    out: List[Tuple[str, str]] = []
    for c in _CALLED_RE.finditer(tail):
        names = ([c.group("one")] if c.group("one") else
                 [n.strip().lstrip("%") for n in c.group("many").split(",")])
        out.extend((c.group("how"), n) for n in names if n)
    return out


_PARAM_NUM_RE = re.compile(r"parameter\((\d+)\)")


def parameter_number(ins: Instruction) -> Optional[int]:
    """Entry-parameter number of a ``parameter(N)`` instruction, else None."""
    if ins.opcode != "parameter":
        return None
    m = _PARAM_NUM_RE.search(ins.line)
    return int(m.group(1)) if m else None


def collect_collectives(
    instrs: Iterable[Instruction],
) -> Dict[str, Dict[str, int]]:
    """Per-collective-kind ``{"count", "bytes"}`` (per-device payload)."""
    out: Dict[str, Dict[str, int]] = {}
    for ins in instrs:
        if ins.opcode not in _COLLECTIVE_SET:
            continue
        kind = ins.opcode[:-len("-start")] \
            if ins.opcode.endswith("-start") else ins.opcode
        slot = out.setdefault(kind, {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += ins.result_bytes()
    return out


# ------------------------------------------------- per-collective details

_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[([0-9,]+)\]<=\[")
_PAIRS_RE = re.compile(r"source_target_pairs=\{")
_METADATA_RE = re.compile(
    r'metadata=\{[^}]*?op_name="(?P<op_name>[^"]*)"'
    r'(?:[^}]*?source_file="(?P<file>[^"]*)")?'
    r'(?:[^}]*?source_line=(?P<line>\d+))?')


def _balanced_braces(text: str, start: int) -> str:
    """Contents of the ``{...}`` block opening at ``text[start] == '{'``."""
    depth, i = 0, start
    while i < len(text):
        if text[i] == "{":
            depth += 1
        elif text[i] == "}":
            depth -= 1
            if depth == 0:
                return text[start + 1:i]
        i += 1
    return text[start + 1:]


def parse_replica_groups(line: str) -> Tuple[int, int]:
    """``(n_groups, group_size)`` of a collective instruction line.

    Handles both encodings XLA emits: the iota form
    ``replica_groups=[G,S]<=[N]`` (G groups of S devices — leading dims
    multiply into the group count) and the explicit nested-brace form
    ``replica_groups={{0,1},{2,3}}``.  ``collective-permute`` carries
    ``source_target_pairs={{s,t},...}`` instead: each pair is reported as
    a 2-device "group".  Returns ``(1, 1)`` when no group annotation is
    present (a single-device module)."""
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        dims = [int(d) for d in m.group(1).split(",")]
        size = dims[-1] if dims else 1
        groups = 1
        for d in dims[:-1]:
            groups *= d
        return (max(1, groups), max(1, size))
    key = "replica_groups={"
    start = line.find(key)
    if start >= 0:
        block = _balanced_braces(line, start + len(key) - 1)
        groups = [g for g in re.findall(r"\{([0-9,\s]*)\}", block)]
        if groups:
            sizes = [len([t for t in g.split(",") if t.strip()])
                     for g in groups]
            return (len(groups), max(sizes))
        # replica_groups={} — all devices in one group, size unknown here
        return (1, 1)
    m = _PAIRS_RE.search(line)
    if m:
        block = _balanced_braces(line, m.end() - 1)
        pairs = re.findall(r"\{[0-9,\s]*\}", block)
        return (max(1, len(pairs)), 2)
    return (1, 1)


_CHANNEL_ID_RE = re.compile(r"\bchannel_id=(\d+)")


def parse_channel_id(line: str) -> int:
    """``channel_id=N`` of a collective instruction line, or ``-1``.

    Cross-module (multi-process) collectives carry a channel id that must
    match across every participating program — it is the rendezvous key
    NCCL/ICI uses to pair the ops up.  Single-module SPMD collectives may
    omit it; synclint canonicalizes the absent case to ``-1`` so schedule
    digests stay stable either way."""
    m = _CHANNEL_ID_RE.search(line)
    return int(m.group(1)) if m else -1


def parse_replica_group_members(line: str) -> Optional[List[List[int]]]:
    """Explicit device-id membership of each replica group, or ``None``.

    Three encodings appear in post-optimization text:

    - explicit nested braces ``replica_groups={{0,1},{2,3}}`` → member
      lists verbatim;
    - the iota form ``replica_groups=[G,S]<=[N]`` → G sequential groups of
      S ids covering ``range(N)`` (XLA's compressed spelling of the same
      partition), synthesized here so congruence checks see one shape;
    - ``source_target_pairs={{s,t},...}`` (collective-permute) → one
      2-element ``[s, t]`` list per pair (pairs may legitimately repeat a
      device across *different* pairs, so callers must not apply the
      disjoint-partition rule to permutes).

    Returns ``None`` when the line carries no group annotation at all —
    distinct from ``[[...]]`` so callers can tell "no groups" apart from
    "one group of everything"."""
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        dims = [int(d) for d in m.group(1).split(",")]
        size = dims[-1] if dims else 1
        groups = 1
        for d in dims[:-1]:
            groups *= d
        ids = iter(range(groups * size))
        return [[next(ids) for _ in range(size)] for _ in range(groups)]
    key = "replica_groups={"
    start = line.find(key)
    if start >= 0:
        block = _balanced_braces(line, start + len(key) - 1)
        groups = re.findall(r"\{([0-9,\s]*)\}", block)
        if groups:
            return [[int(t) for t in g.split(",") if t.strip()]
                    for g in groups]
        return [[]]  # replica_groups={} — one all-device group
    m = _PAIRS_RE.search(line)
    if m:
        block = _balanced_braces(line, m.end() - 1)
        return [[int(t) for t in pair.split(",") if t.strip()]
                for pair in re.findall(r"\{([0-9,\s]*)\}", block)]
    return None


def parse_op_metadata(line: str) -> Tuple[str, str]:
    """``(op_name, "file:line")`` from an instruction's ``metadata={...}``
    annotation; empty strings when absent.  ``op_name`` is the full jax
    scope path (``jit(step)/jit(main)/.../grad_sync/...``) — the hook that
    lets the comm ledger attribute a collective to the ``trace.scope`` /
    ``named_scope`` phase it lowered under."""
    m = _METADATA_RE.search(line)
    if not m:
        return ("", "")
    src = ""
    if m.group("file"):
        src = m.group("file")
        if m.group("line"):
            src += f":{m.group('line')}"
    return (m.group("op_name"), src)


@dataclasses.dataclass
class CollectiveDetail:
    """One collective instruction with its attribution fields."""

    name: str              # HLO instruction name (all-reduce.13)
    kind: str              # normalized opcode (-start folded in)
    bytes: int             # per-device result payload bytes
    shapes: List[Shape]
    n_groups: int
    group_size: int        # replica-group fan-out (devices per group)
    op_name: str           # full jax scope path from metadata
    source: str            # "file:line" from metadata
    computation: str

    def to_dict(self) -> Dict[str, object]:
        d = dataclasses.asdict(self)
        d["shapes"] = [[dt, list(dims)] for dt, dims in self.shapes]
        return d


def collect_collective_details(hlo_text: str) -> List[CollectiveDetail]:
    """Every collective in the module as an attributed record, in program
    order.  Async pairs count once (the ``-start`` op carries the payload;
    ``-done`` is bookkeeping, skipped)."""
    out: List[CollectiveDetail] = []
    for ins in parse_instructions(hlo_text):
        if ins.opcode not in _COLLECTIVE_SET:
            continue
        kind = ins.opcode[:-len("-start")] \
            if ins.opcode.endswith("-start") else ins.opcode
        n_groups, group_size = parse_replica_groups(ins.line)
        op_name, source = parse_op_metadata(ins.line)
        out.append(CollectiveDetail(
            name=ins.name, kind=kind, bytes=ins.result_bytes(),
            shapes=list(ins.shapes), n_groups=n_groups,
            group_size=group_size, op_name=op_name, source=source,
            computation=ins.computation))
    return out


# ------------------------------------------------------------ module header

_ALIAS_BLOCK_RE = re.compile(r"input_output_alias=\{(.*?)\}\s*[,)]")
_ALIAS_ENTRY_RE = re.compile(
    r"\{([0-9,\s]*)\}:\s*\((\d+),\s*\{([0-9,\s]*)\}")


def parse_input_output_alias(
    hlo_text: str,
) -> List[Tuple[Tuple[int, ...], int, Tuple[int, ...]]]:
    """The header's donation map as ``(output_path, param_num,
    param_path)`` triples; empty when nothing aliases."""
    header = hlo_text.split("\n", 1)[0]
    # the alias map nests braces: grab from `input_output_alias={` to the
    # matching close by scanning (entries themselves contain `{}`).
    key = "input_output_alias={"
    start = header.find(key)
    if start < 0:
        return []
    depth, i = 1, start + len(key)
    while i < len(header) and depth:
        if header[i] == "{":
            depth += 1
        elif header[i] == "}":
            depth -= 1
        i += 1
    block = header[start + len(key):i - 1]

    def path(text: str) -> Tuple[int, ...]:
        text = text.strip()
        return tuple(int(t) for t in text.split(",")) if text else ()

    return [
        (path(m.group(1)), int(m.group(2)), path(m.group(3)))
        for m in _ALIAS_ENTRY_RE.finditer(block)
    ]


def aliased_param_numbers(hlo_text: str) -> List[int]:
    """Entry-parameter numbers that donate their buffer to an output."""
    return sorted({p for _, p, _ in parse_input_output_alias(hlo_text)})


def _entry_layout_parts(hlo_text: str) -> Optional[Tuple[str, str]]:
    """``(params_text, outputs_text)`` of the header's
    ``entry_computation_layout={(...)->...}``, split at the top-level
    ``->`` with balanced brace/paren scanning (layout annotations like
    ``{1,0:T(8,128)}`` nest both delimiters)."""
    header = hlo_text.split("\n", 1)[0]
    key = "entry_computation_layout={"
    start = header.find(key)
    if start < 0:
        return None
    depth, i = 1, start + len(key)
    while i < len(header) and depth:
        if header[i] in "{(":
            depth += 1
        elif header[i] in "})":
            depth -= 1
        i += 1
    block = header[start + len(key):i - 1]
    depth = 0
    for j in range(len(block) - 1):
        if block[j] in "{(":
            depth += 1
        elif block[j] in "})":
            depth -= 1
        elif block[j:j + 2] == "->" and depth == 0:
            return block[:j], block[j + 2:]
    return None


def entry_parameter_shapes(hlo_text: str) -> List[Shape]:
    """Per-device entry parameter shapes, in parameter-number order, from
    the header's ``entry_computation_layout={(...)->...}``."""
    parts = _entry_layout_parts(hlo_text)
    return list(iter_shapes(parts[0])) if parts else []


def entry_output_shapes(hlo_text: str) -> List[Shape]:
    """Per-device entry *output* shapes from the header layout — the other
    half of the donation-opportunity question (an un-donated large input
    only matters if a shape-compatible output exists to alias it to)."""
    parts = _entry_layout_parts(hlo_text)
    return list(iter_shapes(parts[1])) if parts else []


# ------------------------------------------------- materialization matchers

def find_materializations(
    hlo_text: str,
    dtype: str,
    dims: Sequence[int],
    opcodes: Sequence[str] = ("fusion",),
    exclude_root: bool = True,
) -> List[Instruction]:
    """Instructions producing a buffer of exactly ``dtype[dims]``.

    The question scripts/hlo_dy_check.py asks: does XLA *materialize* a
    given intermediate (a fusion writes a buffer of that shape to memory)
    or keep it fused into its consumers?  ``opcodes=None`` matches any
    producer opcode except ``parameter``."""
    want: Shape = (dtype, tuple(int(d) for d in dims))
    out = []
    for ins in parse_instructions(hlo_text):
        if exclude_root and ins.is_root:
            continue
        if opcodes is not None and ins.opcode not in opcodes:
            continue
        if opcodes is None and ins.opcode == "parameter":
            continue
        if want in ins.shapes:
            out.append(ins)
    return out


def count_custom_call_convolutions(hlo_text: str) -> int:
    """Convolutions lowered to backend custom-calls (the CPU/TPU library
    path) — the denominator hlo_dy_check reports its fusion count against."""
    n = 0
    for line in hlo_text.splitlines():
        if "custom-call" in line and "convolution" in line.lower():
            n += 1
        elif "kind=kCustom" in line and "convolution" in line:
            n += 1
    return n


def nonparameter_shape_index(
    instrs: Iterable[Instruction],
) -> Dict[Shape, Instruction]:
    """First non-``parameter`` producer of each result shape — the lookup
    the replicated-tensor detector probes with global jaxpr shapes."""
    index: Dict[Shape, Instruction] = {}
    for ins in instrs:
        if ins.opcode == "parameter":
            continue
        for s in ins.shapes:
            index.setdefault(s, ins)
    return index
