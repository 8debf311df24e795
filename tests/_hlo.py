"""Read a compiled program's HLO text: the instructions its loops run."""
import math
import re

_HEAD = re.compile(r"(?:ENTRY )?%([\w.\-]+) ")
_NAME = re.compile(r"%([\w.\-]+)")
_LOOP = re.compile(r"(?:body|condition)=%([\w.\-]+)")


def loop_ops(hlo: str, op: str):
    """(dtype, element count) of every ``op`` instruction in a computation
    that a while loop runs: its body or condition, and every computation
    they call (fusions, conditional branches, nested loops)."""
    comps = {}
    for block in hlo.split("\n\n"):
        m = _HEAD.match(block.strip())
        if m:
            comps[m.group(1)] = block
    todo = [n for b in comps.values() for n in _LOOP.findall(b)]
    seen = set()
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo += [n for n in _NAME.findall(comps[name]) if n in comps]
    shape = re.compile(r"= (\w+)\[([\d,]*)\]\S* " + re.escape(op) + r"\(")
    return [(dt, math.prod(int(d) for d in dims.split(",") if d))
            for name in seen for dt, dims in shape.findall(comps[name])]
