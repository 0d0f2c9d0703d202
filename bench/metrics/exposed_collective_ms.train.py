"""Device time per traced step in which the first chip waits on the vote's
exchange and runs nothing else, in ms: the part of the ops under the
``exchange`` stage of ``fabric.aggregate`` (the packed sign words'
all-to-all and the ternary words' all-gather) that no other op on that
chip's timeline covers.  From the profiler trace's first device plane
inside the window and the HLO metadata.

A ``while``, ``conditional`` or ``call`` op's event encloses those of the
computations it runs, so such ops are neither exchange nor other work.
None where no exchange op ran (one chip: XLA drops a collective over one
device)."""
from harness import hlo_ops, step_scopes
from harness.trace_reduce import _union

EXCHANGE = "aggregate/exchange"
ENCLOSING = ("while", "conditional", "call")


def exposed_ns(ops: list, hlo: str):
    """Nanoseconds of ``ops`` (``[name, start_ns, end_ns]``) in which an
    exchange op runs and no other op does; None without an exchange op."""
    op_names, opcodes = step_scopes.all_op_names(hlo), hlo_ops.index(hlo)
    exchange, other = [], []
    for name, a, b in ops:
        if opcodes.get(name) in ENCLOSING:
            continue
        scope = step_scopes.scope_of(op_names.get(name, ""))
        (exchange if scope == EXCHANGE else other).append((a, b))
    if not exchange:
        return None
    covered = _union(other)
    total, k = 0, 0
    for a, b in _union(exchange):
        # subtract the other ops' merged intervals from [a, b)
        while k < len(covered) and covered[k][1] <= a:
            k += 1
        cur, j = a, k
        while j < len(covered) and covered[j][0] < b:
            total += max(0, covered[j][0] - cur)
            cur = max(cur, covered[j][1])
            j += 1
        total += max(0, b - cur)
    return total


def read(ctx):
    ops = ctx.get("chip_ops")
    ns = exposed_ns(ops, ctx.get("hlo", "")) if ops else None
    if ns is None:
        return None
    return ns / 1e6 / ctx["steps_traced"]
