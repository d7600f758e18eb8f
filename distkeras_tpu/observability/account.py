"""The device-time account of a compiled window program.

Every HLO instruction sorted by the PART of the step it belongs to and by
the PASS it runs in, both read off its ``op_name``: the part is the innermost
of the program's dotted ``jax.named_scope``s on the path, the pass is what
JAX's transforms left there.  A reader of a profiler trace sums device
events through the table (``benchmark/readers/trace_account.py``); the rules
are stated here, once, because the scopes and the transforms are the
program's.
"""

from __future__ import annotations

import re
from typing import Dict, FrozenSet, NamedTuple, Tuple

# in the order of data flow: a value of a later pass is read by no earlier one
PASSES = ("forward", "recompute", "backward", "other")
_PART = re.compile(r"(?<![\w.])(?:(?:attn|moe)\.[a-z_.]*[a-z_]|ffn\.dense|lm\.embed|lm\.head"
                   r"|step\.loss|step\.update|step\.commit)(?!\w)")
_BLOCK_PATH = re.compile(r"(?:^|/)blocks?_\d+(?:[./]|$)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][\w\-]*)\(")
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_NAME = re.compile(r"%([\w.\-]+)")
_MATMULS = ("convolution", "dot")
_CONTAINERS = ("while", "conditional", "call")


class DeviceAccount(NamedTuple):
    """``table``: {instruction name: (part, pass)}.  ``mixed``: {fusion name:
    (parts, passes, counted as its matmul)} of the fusions whose bodies hold
    instructions of more than one part or pass (``none`` / ``other`` not
    counted): a fusion is ONE device event, so what the table says of it is
    true of a share of it — nearly all of it where a matrix multiplication
    decides, the root's share where the root does.  ``containers``: the
    instructions whose bodies' instructions are device events of their own
    (``while``, ``conditional``, ``call``)."""

    table: Dict[str, Tuple[str, str]]
    mixed: Dict[str, Tuple[Tuple[str, ...], Tuple[str, ...], bool]]
    containers: FrozenSet[str]
    text_bytes: int                  # the size of the compiled text read


def opcode_of(text: str) -> str:
    """The opcode of an instruction's text after its `` = `` (``fusion``,
    ``while``, ``custom-call``): the first word before a ``(`` that follows
    the result's shape."""
    m = _OPCODE.search(text.split(", metadata=", 1)[0])
    return m.group(1) if m else ""


def account_of(name: str, op_name: str) -> Tuple[str, str]:
    """(part, pass) of one instruction.

    Part: the innermost ``attn.*`` / ``moe.*`` scope (kept whole:
    ``attn.linear.scan``), ``ffn.dense``, ``lm.embed``, ``lm.head``,
    ``step.loss`` / ``step.update`` / ``step.commit``; XLA's own grouped
    matmul (``ragged-dot*``) is ``moe.experts`` by its name where it carries
    no scope; else ``block.other`` under a block's module path (residual
    adds, a norm no mixer owns); else ``none`` (an instruction XLA made:
    copies, transposes, loop plumbing).

    Pass, in this order: ``rematted_computation`` -> ``recompute``;
    ``transpose(`` -> ``backward``; ``jvp(`` -> ``forward``; else ``other``
    (update, commit, plumbing)."""
    scopes = _PART.findall(op_name)
    if scopes:
        part = scopes[-1]
    elif name.startswith("ragged-dot"):
        part = "moe.experts"
    elif _BLOCK_PATH.search(op_name):
        part = "block.other"
    else:
        part = "none"
    if "rematted_computation" in op_name:
        return part, "recompute"
    if "transpose(" in op_name:
        return part, "backward"
    return part, "forward" if "jvp(" in op_name else "other"


def account(text: str) -> DeviceAccount:
    """The account of a compiled module's text.

    Two instructions take their account from others.  A FUSION counts as the
    matrix multiplication in its body where it holds one (the update's
    multiply-add fused onto a weight gradient's output is the gradient's
    time), else as its own ``op_name`` says, which is its root's; one XLA
    gave no ``op_name`` counts as the last instruction of its body that has
    one.  A body takes in the bodies of the fusions nested in it.  XLA's
    grouped matmul (``ragged-dot*``) has lost the program's path, so its pass
    is the latest of its operands' (a backward value is read by the backward
    pass alone, a recomputed one by no forward instruction), seen through
    operands that carry none themselves."""
    # computation -> [(opcode, (part, pass) or None, a fusion's computation or None)]
    bodies: Dict[str, list] = {}
    calls: Dict[str, str] = {}       # fusion instruction -> its computation
    table: Dict[str, Tuple[str, str]] = {}
    passless: Dict[str, str] = {}    # instruction with no pass -> its text
    walk = "ragged-dot" in text      # only the grouped matmul asks its operands
    containers = set()
    body = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            head = _COMPUTATION.match(line)
            if head:
                body = bodies.setdefault(head.group(1), [])
            continue
        instr, rest = m.groups()
        op = _OP_NAME.search(rest)
        opcode = opcode_of(rest)
        if op is not None or instr.startswith("ragged-dot"):
            table[instr] = account_of(instr, op.group(1) if op else "")
        if walk and table.get(instr, ("", "other"))[1] == "other":
            passless[instr] = rest
        if opcode == "fusion":
            called = _CALLS.search(rest)
            if called:
                calls[instr] = called.group(1)
        elif opcode in _CONTAINERS:
            containers.add(instr)
        if body is not None:
            body.append((opcode, table.get(instr), calls.get(instr)))

    flat: Dict[str, list] = {}       # fusion computation -> [(opcode, (part, pass))]

    def inside(comp: str) -> list:
        if comp not in flat:
            flat[comp] = out = []
            for opcode, acct, callee in bodies.get(comp, ()):
                if callee is not None:
                    out.extend(inside(callee))
                if acct is not None:
                    out.append((opcode, acct))
        return flat[comp]

    mixed = {}
    for instr, comp in calls.items():
        held = inside(comp)
        matmuls = [acct for opcode, acct in held if opcode in _MATMULS]
        if matmuls:
            table[instr] = matmuls[0]
        elif instr not in table and held:
            table[instr] = held[-1][1]
        if instr in table and table[instr][1] != "other":
            passless.pop(instr, None)
        parts = tuple(sorted({a[0] for _, a in held} - {"none"}))
        passes = tuple(sorted({a[1] for _, a in held} - {"other"}))
        if len(parts) > 1 or len(passes) > 1:
            mixed[instr] = (parts, passes, bool(matmuls))

    def latest_pass(instr: str, depth: int) -> int:
        rest = passless.get(instr)
        if rest is None:
            return PASSES.index(table[instr][1]) if instr in table else -1
        if depth == 0:
            return -1
        return max((latest_pass(o, depth - 1) for o in _operands(rest)), default=-1)

    for instr in [i for i in passless if i.startswith("ragged-dot")]:
        at = latest_pass(instr, 4)
        if at >= 0:
            table[instr] = (table[instr][0], PASSES[at])
    return DeviceAccount(table, mixed, frozenset(containers), len(text))


def _operands(rest: str):
    """Names of an instruction's operands, from its text after the `` = ``."""
    m = _OPCODE.search(rest)
    return _NAME.findall(rest[m.end():].split("), ", 1)[0]) if m else ()
