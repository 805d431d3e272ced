"""Where a kernel's operands live in the compiled program, read from the
HLO module text that ran, and which ops move them between HBM and VMEM.

On a TPU, XLA may stage a custom call's operand in VMEM (a layout ending in
``S(1)``): another op then reads it from HBM, or the value is computed in
VMEM and never reaches HBM.  ``staging`` follows each operand the yardstick
counts back (an input) or forward (an output) through data-movement ops
only, to the op that touches HBM.  Nothing here imports the program.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Tuple

# ops that move a value without computing on it (an asynchronous copy is
# the pair copy-start, copy-done); a dynamic-update-slice moves its base
# (operand 0) going back, its base or update going forward
MOVES = ("bitcast", "copy", "reshape", "transpose", "copy-start",
         "copy-done")
# the op that starts an asynchronous transfer: its events on the device's
# op line cover only the issue, the transfer runs on a line of its own
ASYNC_STARTS = ("copy-start",)
DUS = "dynamic-update-slice"
# roots of a loop fusion that only moves data
FUSED_MOVES = ("dynamic-slice", "slice", "copy", DUS)

_NAME = r"[\w.\-]+"
_INSTR = re.compile(rf"\s*(?:ROOT\s+)?%({_NAME}) = (.*)$")
_HEADER = re.compile(rf"(?:ENTRY\s+)?%({_NAME}) \(.*\{{$")


@dataclass
class Instr:
    name: str
    shape: str
    opcode: str
    operands: List[str]      # names of the instructions it reads
    args: str                # the text inside its parentheses
    attrs: str


@dataclass
class Computation:
    name: str
    instrs: Dict[str, Instr] = field(default_factory=dict)
    root: str = ""
    _users: Optional[Dict[str, List[Instr]]] = None

    def users(self, name: str) -> List[Instr]:
        if self._users is None:
            self._users = {}
            for ins in self.instrs.values():
                for o in ins.operands:
                    self._users.setdefault(o, []).append(ins)
        return self._users.get(name, [])


def _balanced(s: str, i: int) -> int:
    """Index just past the bracket group that opens at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        c = s[j]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    raise ValueError(f"unbalanced brackets in {s[i:i + 80]!r}")


def split_top(s: str) -> List[str]:
    """``s`` split at the commas outside any bracket, comments dropped."""
    s = re.sub(r"/\*.*?\*/", "", s)
    out, depth, cur = [], 0, []
    for c in s:
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        if c == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(c)
    if "".join(cur).strip():
        out.append("".join(cur).strip())
    return out


def parse_instr(name: str, rest: str) -> Instr:
    """``rest`` is the text after ``%name = ``: shape, opcode, operands,
    attributes."""
    if rest.startswith("("):
        end = _balanced(rest, 0)
    else:
        end = rest.index(" ")
    shape = rest[:end]
    tail = rest[end:].lstrip()
    op_end = tail.index("(")
    opcode = tail[:op_end]
    close = _balanced(tail, op_end)
    args = tail[op_end + 1:close - 1]
    operands = [a.split()[-1].lstrip("%") for a in split_top(args)
                if a.split() and a.split()[-1].startswith("%")]
    return Instr(name, shape, opcode, operands, args, tail[close:])


class Module:
    """One compiled HLO module, from ``compile().as_text()`` or the
    profile's own HLO: its computations and their instructions."""

    def __init__(self, text: str):
        self.computations: Dict[str, Computation] = {}
        self.where: Dict[str, Computation] = {}
        comp: Optional[Computation] = None
        for line in text.splitlines():
            if comp is None:
                m = _HEADER.match(line)
                if m:
                    comp = Computation(m.group(1))
                    self.computations[comp.name] = comp
                continue
            if line.startswith("}"):
                comp = None
                continue
            m = _INSTR.match(line)
            if m:
                ins = parse_instr(m.group(1), m.group(2))
                comp.instrs[ins.name] = ins
                self.where[ins.name] = comp
                if line.lstrip().startswith("ROOT "):
                    comp.root = ins.name

    def instr(self, name: str) -> Optional[Instr]:
        comp = self.where.get(name)
        return comp.instrs[name] if comp else None


def in_vmem(shape: str) -> bool:
    """A (non-tuple) shape whose layout puts it in memory space 1."""
    return "S(1)" in shape


def tuple_shapes(shape: str) -> List[str]:
    """The element shapes of a tuple shape; a non-tuple is its own one."""
    return split_top(shape[1:-1]) if shape.startswith("(") else [shape]


def _index(ins: Instr) -> int:
    return int(re.search(r"index=(\d+)", ins.attrs).group(1))


def _fused_source(mod: Module, fusion: Instr, forward: bool = False
                  ) -> List[int]:
    """The operand numbers of a loop fusion whose root only moves data
    (``FUSED_MOVES``, through bitcasts) that feed the moved data: the
    root's operand 0, and going forward also a dynamic-update-slice's
    update; empty for a fusion that computes."""
    m = re.search(rf"calls=%({_NAME})", fusion.attrs)
    comp = mod.computations.get(m.group(1)) if m else None
    if comp is None or not comp.root:
        return []
    root = comp.instrs[comp.root]
    while root.opcode == "bitcast":
        root = comp.instrs[root.operands[0]]
    if root.opcode not in FUSED_MOVES:
        return []
    data = root.operands[:2 if forward and root.opcode == DUS else 1]
    out = []
    for d in data:
        ins = comp.instrs[d]
        while ins.opcode in MOVES:
            ins = comp.instrs[ins.operands[0]]
        if ins.opcode == "parameter":
            out.append(int(ins.args))
    return out


def moved_from(mod: Module, ins: Instr) -> Optional[str]:
    """The value a data-movement op reads the moved data from, or None
    when ``ins`` computes."""
    if ins.opcode in MOVES or ins.opcode == DUS:
        return ins.operands[0]
    if ins.opcode == "fusion":
        src = _fused_source(mod, ins)
        if src:
            return ins.operands[src[0]]
    return None


def moves_into(mod: Module, user: Instr, value: str) -> bool:
    """Whether ``user`` only moves ``value`` on."""
    if user.opcode in MOVES:
        return user.operands[0] == value
    if user.opcode == DUS:
        return value in user.operands[:2]
    if user.opcode == "fusion":
        return any(user.operands[k] == value
                   for k in _fused_source(mod, user, forward=True))
    return False


@dataclass(frozen=True)
class Operand:
    """One operand of a kernel's call, as the compiled program holds it."""

    role: str                  # the yardstick's name for its bytes
    hbm: bool                  # its bytes cross HBM
    charged: Tuple[str, ...]   # the ops that move them, kernel side first
    end: str                   # the op the chain ends at ("" at the kernel)
    transfers: Tuple[str, ...] = ()   # the asynchronous starts among them


def _back(mod: Module, comp: Computation, role: str, value: str) -> Operand:
    charged: List[str] = []
    while in_vmem(comp.instrs[value].shape):
        ins = comp.instrs[value]
        src = moved_from(mod, ins)
        if src is None:
            return Operand(role, False, (), ins.name)
        if ins.opcode != "bitcast":      # a bitcast runs no device op
            charged.append(ins.name)
        value = src
    return Operand(role, True, tuple(charged),
                   charged[-1] if charged else "")


def _forward(mod: Module, comp: Computation, value: str, shape: str,
             index: Optional[int] = None) -> Optional[List[str]]:
    """The ops that carry ``value`` (element ``index`` of it) from VMEM to
    HBM, or None when no chain of moves reaches HBM."""
    if not in_vmem(shape):
        return []
    for user in comp.users(value):
        if index is not None:
            if user.opcode == "get-tuple-element" and _index(user) == index:
                got = _forward(mod, comp, user.name, user.shape)
                if got is not None:
                    return got
        elif moves_into(mod, user, value):
            got = _forward(mod, comp, user.name, user.shape)
            if got is not None:
                return ([user.name] if user.opcode != "bitcast" else []) + got
    return None


def staging(mod: Module, kernel: str, inputs: Sequence[Optional[str]],
            outputs: Sequence[Optional[str]]) -> List[Operand]:
    """Each operand of the custom call ``kernel`` that has a role (inputs
    in order, then outputs), with where its bytes go.  Raises ``KeyError``
    when the module has no such instruction, its operands are not the
    roles' number, or a chain cannot be followed."""
    ins = mod.instr(kernel)
    if ins is None:
        raise KeyError(f"{kernel} is not in module")
    comp = mod.where[kernel]
    outs = tuple_shapes(ins.shape)
    if len(ins.operands) != len(inputs) or len(outs) != len(outputs):
        raise KeyError(f"{kernel} has {len(ins.operands)} operands and "
                       f"{len(outs)} outputs, the yardstick names "
                       f"{len(inputs)} and {len(outputs)}")
    try:
        res = [_back(mod, comp, r, v)
               for r, v in zip(inputs, ins.operands) if r]
        tup = ins.shape.startswith("(")
        for j, (r, shape) in enumerate(zip(outputs, outs)):
            if r:
                got = _forward(mod, comp, kernel, shape, j if tup else None)
                res.append(Operand(r, got is not None, tuple(got or ()),
                                   (got[-1] if got else "")
                                   if got is not None else "no move to HBM"))
    except (KeyError, ValueError, IndexError, AttributeError) as e:
        raise KeyError(f"{kernel}: a chain cannot be followed ({e!r}) in "
                       f"module") from None
    return [replace(o, transfers=tuple(
        n for n in o.charged if mod.instr(n).opcode in ASYNC_STARTS))
        for o in res]
