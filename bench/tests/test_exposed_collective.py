"""The vote exchange's exposed time: the part of the exchange ops on the
first chip's timeline that no other op covers, read from synthetic trace
events and HLO metadata."""
import pytest

from conftest import BENCH
from harness import readers, trace_reduce

NAME = "exposed_collective_ms.train"
AGG = 'metadata={op_name="jit(step_fn)/shard_map/fabric.aggregate/shard_map/%s"}'

STEP_HLO = "\n".join([
    "HloModule jit_step_fn, entry_computation_layout={()->()}",
    "",
    "%body.1 (p: (s32[])) -> (s32[]) {",
    "  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f2, "
    'metadata={op_name="jit(step_fn)/shard_map/transpose(jvp(model))/while"}',
    "}",
    "",
    "ENTRY %main.9 (t: (s32[])) -> f32[8] {",
    "  %while.1 = (s32[]) while(%t), condition=%c, body=%body.1, "
    'metadata={op_name="jit(step_fn)/shard_map/transpose(jvp(model))/while"}',
    "  %all-to-all.3 = u32[4,8,128]{2,1,0} all-to-all(%w), "
    "replica_groups={{0,1,2,3}}, " + AGG % "exchange/all_to_all",
    "  %fabric_vote_combine.4 = u32[8,128]{1,0} custom-call(%r), "
    'custom_call_target="tpu_custom_call", '
    + AGG % "vote/fabric_vote_combine/pallas_call",
    "  %all-gather-start.5 = u32[32,128]{1,0} all-gather-start(%s), "
    "replica_groups={{0,1,2,3}}, " + AGG % "exchange/all_gather",
    "  %all-gather-done.6 = u32[32,128]{1,0} all-gather-done(%all-gather-start.5), "
    + AGG % "exchange/all_gather",
    "  %fusion.7 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f7, "
    'metadata={op_name="jit(step_fn)/optimizer.apply/mul"}',
    "  ROOT %all-reduce.8 = f32[8]{0} all-reduce(%x), "
    "replica_groups={{0,1,2,3}}, " + AGG % "psum",
    "}",
])


def _read(ops, steps=2):
    ctx = {"chip_ops": ops, "hlo": STEP_HLO, "steps_traced": steps}
    return readers.load(NAME, BENCH).read(ctx)


def test_fully_hidden_reads_zero():
    # the all-to-all runs under the vote kernel, the all-gather's wait under
    # an optimizer fusion and the FP32 path's all-reduce
    ops = [["all-to-all.3", 100, 200], ["fabric_vote_combine.4", 50, 250],
           ["all-gather-start.5", 300, 310], ["fusion.7", 290, 360],
           ["all-gather-done.6", 320, 400], ["all-reduce.8", 350, 420]]
    assert _read(ops) == 0.0


def test_fully_exposed_reads_every_exchange_op():
    ops = [["all-to-all.3", 100, 200], ["fabric_vote_combine.4", 200, 250],
           ["all-gather-start.5", 300, 310], ["all-gather-done.6", 320, 400],
           ["fusion.7", 400, 500]]
    assert _read(ops) == pytest.approx((100 + 10 + 80) / 1e6 / 2)


def test_partly_overlapped_reads_the_uncovered_part():
    # all-to-all [100, 200) under fusion.7 from 150: 50 exposed; the
    # all-gather's start [300, 310) and done [320, 400) under the
    # all-reduce [330, 350) and fusion.7 [380, 390): 10 + 10 + 30 + 10
    ops = [["all-to-all.3", 100, 200], ["fusion.7", 150, 260],
           ["all-gather-start.5", 300, 310], ["all-gather-done.6", 320, 400],
           ["all-reduce.8", 330, 350], ["fusion.7", 380, 390]]
    assert _read(ops, steps=1) == pytest.approx((50 + 10 + 10 + 30 + 10) / 1e6)


def test_enclosing_loop_is_not_other_work():
    # a while's event encloses its body's; it hides nothing by itself
    ops = [["while.1", 0, 1000], ["fusion.2", 100, 150],
           ["all-to-all.3", 120, 220]]
    assert _read(ops, steps=1) == pytest.approx(70 / 1e6)


def test_silent_without_an_exchange():
    ops = [["fusion.7", 0, 100], ["all-reduce.8", 100, 200]]
    assert _read(ops) is None
    assert _read([]) is None
    assert readers.load(NAME, BENCH).read({}) is None


def test_chip_ops_keeps_the_first_chip_inside_the_window():
    ev = {"devices": {
        "/device:TPU:1": [["fusion.1", 100, 50]],
        "/device:TPU:0": [["all-to-all.3", 120, 60], ["copy.4", 0, 50],
                          ["fusion.1", 80, 30], ["fusion.2", 480, 100]]},
        "host": [["bench.window", 90, 410], ["Trainer.run", 90, 200]]}
    assert trace_reduce.chip_ops(ev) == [
        ["fusion.1", 90, 110], ["all-to-all.3", 120, 180],
        ["fusion.2", 480, 500]]
    assert trace_reduce.chip_ops({"devices": {}, "host": []}) == []
