"""Share of the HBM roofline the vote kernels reach, in %: the algorithmic
bytes of one step's vote datapath on one chip (bench/harness/flops.py)
over the kernels' device time per step, over the chip's HBM bandwidth.
The datapath does no matmul, so bandwidth bounds it."""
from harness import flops
from harness.readers import load


def read(ctx):
    ms = load("agg_kernel_ms", ctx["cell"].root / "bench").read(ctx)
    if ms is None:
        return None
    cell = ctx["cell"]
    nbytes = flops.agg_bytes_per_chip(cell.config, root=cell.root)
    return 100.0 * nbytes / (ms / 1e3) / ctx["peaks"]["hbm_bytes_per_s"]
