"""Device time per traced step of the model's attention core: ops whose HLO
``op_name`` has the path component ``attention`` (the scope the program
puts around the scores, softmax and value sum of every layer), in the
forward pass, the remat recompute and the backward pass alike, from the
profiler trace and the HLO metadata; per chip.

The layers run inside loops, so this reader sums ops of every computation
that ran, as ``recompute_ms.train`` does: a ``while``, ``conditional`` or
``call`` op's event encloses those of the computations it runs, so those
ops are skipped and each op counts once; a fusion's event is the fusion's
alone and its fused computation, which has no events, is never summed.

A Pallas call that passes kernel metadata (the splash attention kernels
do) prints that metadata as indented JSON, so its instruction runs over
several lines of the HLO text and its ``op_name`` sits on the last of
them: the lines of an instruction are joined before its ``op_name`` is
read.  None on a program whose HLO names no attention scope."""
import re

from harness.hlo_ops import _INSTR

SCOPE = "attention"
ENCLOSING = ("while", "conditional", "call")
# ``metadata=`` alone, not the ``kernel_metadata=`` of a Pallas call
_OP_NAME = re.compile(r'(?<![\w"])metadata=\{[^}]*?op_name="([^"]*)"')
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?[\w.\-]+\s.*\{\s*$")


def computations(hlo: str) -> list:
    """Each computation's instructions as (name, opcode, op_name), an
    instruction's continuation lines (those that open neither a
    computation nor an instruction, and do not close a computation)
    joined to it."""
    comps = []
    for line in hlo.splitlines():
        if line.startswith((" ", "\t")):
            m = _INSTR.match(line)
            if m is not None and comps:
                comps[-1].append([m.group(1), m.group(2), line])
        elif _HEADER.match(line):
            comps.append([])
        elif line.strip() not in ("", "}") and comps and comps[-1]:
            comps[-1][-1][2] += "\n" + line
    out = []
    for instrs in comps:
        out.append([])
        for name, op, text in instrs:
            o = _OP_NAME.search(text)
            out[-1].append((name, op, o.group(1) if o else ""))
    return out


def attention_seconds(op_s: dict, hlo: str):
    """Seconds of the attention core's ops in ``op_s``; None when no op
    that ran carries the scope."""
    total, seen = 0.0, False
    for instrs in computations(hlo):
        if not any(name in op_s for name, _, _ in instrs):
            continue
        for name, op, op_name in instrs:
            if (SCOPE in op_name.split("/")
                    and op not in ENCLOSING and name in op_s):
                total += op_s[name]
                seen = True
    return total if seen else None


def read(ctx):
    t = ctx.get("trace")
    s = attention_seconds(t["op_s"], ctx.get("hlo", "")) if t else None
    if s is None:
        return None
    return 1e3 * s / ctx["steps_traced"]
