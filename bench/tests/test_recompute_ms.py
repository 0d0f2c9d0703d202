"""The remat recompute's device time, read from ops inside the layers' loop
by their HLO ``op_name``."""
import pytest

from conftest import BENCH
from harness import readers

NAME = "recompute_ms.train"
REMAT = ('metadata={op_name="jit(step_fn)/shard_map/transpose(jvp(model))/'
         'while/body/closed_call/checkpoint/rematted_computation/%s"}')
BWD = ('metadata={op_name="jit(step_fn)/shard_map/transpose(jvp(model))/'
       'while/body/%s"}')

STEP_HLO = "\n".join([
    "HloModule jit_step_fn, entry_computation_layout={()->()}",
    "",
    "%fused_computation.1 (p0: bf16[8,64]) -> bf16[8,64] {",
    "  %p0 = bf16[8,64]{1,0} parameter(0)",
    "  ROOT %dot.9 = bf16[8,64]{1,0} dot(%p0, %p0), " + REMAT % "dot_general",
    "}",
    "",
    "%inner_body.2 (q: (s32[])) -> (s32[]) {",
    "  %fusion.21 = f32[8]{0} fusion(%q), kind=kLoop, calls=%f21, "
    + REMAT % "while/body/exp",
    "}",
    "",
    "%body.3 (p: (s32[])) -> (s32[]) {",
    "  %fusion.31 = bf16[8,64]{1,0} fusion(%p), kind=kOutput, "
    "calls=%fused_computation.1, " + REMAT % "dot_general",
    "  %while.32 = (s32[]) while(%p), condition=%c, body=%inner_body.2, "
    + REMAT % "while",
    "  %fusion.33 = bf16[8,64]{1,0} fusion(%p), kind=kOutput, calls=%f33, "
    + BWD % "transpose(dot_general)",
    "  ROOT %call.34 = (s32[]) call(%p), to_apply=%inner_body.2, "
    + REMAT % "call",
    "}",
    "",
    "ENTRY %main.9 (t: (s32[])) -> f32[8] {",
    "  %while.1 = (s32[]) while(%t), condition=%c, body=%body.3, "
    'metadata={op_name="jit(step_fn)/shard_map/transpose(jvp(model))/while"}',
    "  ROOT %fusion.5 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f5, "
    'metadata={op_name="jit(step_fn)/shard_map/jvp(model)/dot_general"}',
    "}",
])

OP_S = {"while.1": 0.500, "fusion.31": 0.120, "while.32": 0.050,
        "fusion.21": 0.040, "fusion.33": 0.200, "call.34": 0.070,
        "fusion.5": 0.100}


def _read(op_s, hlo, steps=2):
    ctx = {"trace": {"op_s": op_s}, "hlo": hlo, "steps_traced": steps}
    return readers.load(NAME, BENCH).read(ctx)


def test_loop_body_ops_count_once():
    # fusion.31 and, inside the nested loop, fusion.21; not the enclosing
    # while.1, while.32 or call.34, not the backward's fusion.33, and not
    # dot.9 inside fusion.31's fused computation
    assert _read(OP_S, STEP_HLO) == pytest.approx(1e3 * (0.120 + 0.040) / 2)


def test_enclosing_ops_and_fusion_internals_do_not_count():
    # the marked while.32 and call.34 hold marked ops but are not counted
    # themselves; a fused computation has no events of its own
    only_loops = {k: v for k, v in OP_S.items()
                  if k in ("while.1", "while.32", "call.34", "fusion.5")}
    assert _read(only_loops, STEP_HLO) is None


def test_none_without_recompute():
    plain = STEP_HLO.replace("rematted_computation/", "")
    assert _read(OP_S, plain) is None
    assert _read(OP_S, "") is None
    assert readers.load(NAME, BENCH).read({}) is None
