"""Reading a compiled program's optimised HLO text.

``jax.jit(f).lower(...).compile().as_text()`` is what XLA will run; a
question a timing cannot answer from the sandbox (does the program
copy a weight before it multiplies by it?) is answered by which
instructions of that text write their result to memory.
"""

from __future__ import annotations

import re

__all__ = ["materialised", "dims", "written"]

_HEAD = re.compile(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTR = re.compile(
    r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\(")


def materialised(hlo: str) -> list[tuple[str, str, str]]:
    """``(name, opcode, result shape without its layout)`` of every
    array-valued instruction that writes its result to memory: those of
    the entry computation and of loop bodies and conditions, not those
    inside a fused computation (there a slice of a weight is an
    address, not a copy)."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        head = None if line.startswith(" ") else _HEAD.match(line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    fused = {c for lines in comps.values() for ln in lines
             if " fusion(" in ln
             for c in re.findall(r"calls=%?([\w.\-]+)", ln)}
    out = []
    for name, lines in comps.items():
        if name not in fused:
            out += [(m.group(1), m.group(3), m.group(2))
                    for m in map(_INSTR.match, lines) if m]
    return out


def dims(shape: str) -> tuple[int, ...]:
    """A shape's axes longer than 1, sorted: ``bf16[1,4096,1024]`` and
    a transposed ``bf16[1024,4096]`` are the same tensor moved."""
    return tuple(sorted(int(n) for n in
                        shape[shape.index("[") + 1:-1].split(",")
                        if n and n != "1"))


def written(ops, shapes) -> list[tuple[str, str, str]]:
    """Those of :func:`materialised`'s ``ops`` that produce (not merely
    pass on: parameters, tuple elements) a tensor whose :func:`dims`
    are among ``shapes``, a set of sorted tuples."""
    return [op for op in ops
            if op[1] not in ("parameter", "get-tuple-element")
            and dims(op[2]) in shapes]
