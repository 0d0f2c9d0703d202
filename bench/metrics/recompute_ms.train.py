"""Device time per traced step of the scanned layers' remat recompute: ops
whose HLO ``op_name`` contains ``rematted_computation`` (the name JAX gives
the forward pass it reruns inside the backward pass), from the profiler
trace and the HLO metadata; per chip.

The recompute runs inside the layers' loop, so this reader sums ops of
every computation that ran (one with an event in ``trace["op_s"]``), not
only the entry computation's.  A ``while``, ``conditional`` or ``call``
op's event encloses those of the computations it runs, so those ops are
skipped and each op counts once; a fusion's event is the fusion's alone and
its fused computation, which has no events, is never summed.  None on a
program whose HLO names no recompute."""
from harness.hlo_ops import _INSTR
from harness.step_scopes import _OP_NAME

MARK = "rematted_computation"
ENCLOSING = ("while", "conditional", "call")


def computations(hlo: str) -> list:
    """Each computation's instructions as (name, opcode, op_name)."""
    out = []
    for line in hlo.splitlines():
        if not line.startswith((" ", "\t")):
            if line.rstrip().endswith("{"):
                out.append([])
            continue
        m = _INSTR.match(line)
        if m is not None and out:
            o = _OP_NAME.search(line)
            out[-1].append((m.group(1), m.group(2), o.group(1) if o else ""))
    return out


def recompute_seconds(op_s: dict, hlo: str):
    """Seconds of the recompute's ops in ``op_s``; None when no op that
    ran carries the mark."""
    total, seen = 0.0, False
    for instrs in computations(hlo):
        if not any(name in op_s for name, _, _ in instrs):
            continue
        for name, op, op_name in instrs:
            if MARK in op_name and op not in ENCLOSING and name in op_s:
                total += op_s[name]
                seen = True
    return total if seen else None


def read(ctx):
    t = ctx.get("trace")
    s = recompute_seconds(t["op_s"], ctx.get("hlo", "")) if t else None
    if s is None:
        return None
    return 1e3 * s / ctx["steps_traced"]
