"""Model FLOP utilization of the whole training step, in % of the chips'
bf16 peak: model FLOPs per token (bench/harness/flops.py, no recompute)
times the window's tokens per second, over chips times peak."""
from harness import flops


def read(ctx):
    tps = ctx.get("tokens_per_s")
    if not tps:
        return None
    cell = ctx["cell"]
    per_token = flops.model_flops_per_token(
        cell.config, cell.traffic["seq_len"], root=cell.root)
    return 100.0 * per_token * tps / (cell.chips * ctx["peaks"]["bf16_flops"])
