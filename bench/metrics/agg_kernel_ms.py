"""Device time per step of the vote datapath's Mosaic kernels (custom
calls with target tpu_custom_call whose HLO metadata places them under
the ``fabric.aggregate`` scope), from the profiler trace; per chip.  A
kernel of the model's own, under ``model``, is not counted."""
from harness.step_scopes import in_scope, kernel_seconds


def read(ctx):
    t = ctx.get("trace")
    if not t:
        return None
    by_scope = kernel_seconds(t["op_s"], ctx["op_class"], ctx["hlo"])
    s = sum(v for k, v in by_scope.items() if in_scope(k, "aggregate"))
    if s <= 0:
        return None
    return 1e3 * s / ctx["steps_traced"]
