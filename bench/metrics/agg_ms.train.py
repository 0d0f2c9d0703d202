"""Device time per traced step of gradient aggregation: every op under the
``fabric.aggregate`` scope (bucket pack and scatter, encode, exchange,
vote, decode; ``agg_kernel_ms`` is its kernel part), from the profiler
trace and the HLO metadata; per chip."""
from harness.step_scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "aggregate")
