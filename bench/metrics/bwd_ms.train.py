"""Device time per traced step of the model's backward pass, the scanned
layers' remat recompute included: ops under ``transpose(jvp(model))``,
from the profiler trace and the HLO metadata; per chip."""
from harness.step_scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "backward")
