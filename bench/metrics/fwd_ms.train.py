"""Device time per traced step of the model's forward pass, head and loss
included: ops under the ``jvp(model)`` scope and not its transpose, from
the profiler trace and the HLO metadata; per chip."""
from harness.step_scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "forward")
