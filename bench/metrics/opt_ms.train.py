"""Device time per traced step of the optimizer: ops under the
``optimizer.apply`` scope, from the profiler trace and the HLO metadata;
per chip."""
from harness.step_scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "optimizer")
