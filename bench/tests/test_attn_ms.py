"""The attention core's device time, read from ops in every computation by
the ``attention`` component of their HLO ``op_name``."""
import pytest

from conftest import BENCH
from harness import readers

NAME = "attn_ms.train"
LOOP = "jit(step_fn)/shard_map/%s/while/body/closed_call/%s"
FWD = 'metadata={op_name="' + LOOP % ("jvp(model)", "%s") + '"}'
BWD = 'metadata={op_name="' + LOOP % ("transpose(jvp(model))", "%s") + '"}'
REMAT = BWD % "checkpoint/rematted_computation/%s"

STEP_HLO = "\n".join([
    "HloModule jit_step_fn, entry_computation_layout={()->()}",
    "",
    "%fused_computation.1 (p0: bf16[8,64]) -> bf16[8,64] {",
    "  %p0 = bf16[8,64]{1,0} parameter(0)",
    "  ROOT %dot.9 = bf16[8,64]{1,0} dot(%p0, %p0), "
    + FWD % "attention/dot_general",
    "}",
    "",
    "%fwd_body.2 (q: (s32[])) -> (s32[]) {",
    "  %fusion.21 = bf16[8,64]{1,0} fusion(%q), kind=kOutput, "
    "calls=%fused_computation.1, " + FWD % "attention/dot_general",
    "  %custom-call.22 = bf16[8,64]{1,0} custom-call(%q), "
    'custom_call_target="tpu_custom_call", '
    + FWD % "attention/shard_map/jit(_splash_attention)/pallas_call",
    "  %fusion.23 = bf16[8,64]{1,0} fusion(%q), kind=kOutput, calls=%f23, "
    + FWD % "dot_general",
    "  ROOT %call.24 = (s32[]) call(%q), to_apply=%fused_computation.1, "
    + FWD % "attention/call",
    "}",
    "",
    "%bwd_body.3 (p: (s32[])) -> (s32[]) {",
    "  %custom-call.31 = bf16[8,64]{1,0} custom-call(%p), "
    'custom_call_target="tpu_custom_call", '
    + REMAT % "attention/shard_map/pallas_call",
    "  %custom-call.32 = bf16[8,64]{1,0} custom-call(%p), "
    'custom_call_target="tpu_custom_call", '
    + BWD % "attention/shard_map/transpose(jvp(pallas_call))",
    "  %fusion.33 = bf16[8,64]{1,0} fusion(%p), kind=kLoop, calls=%f33, "
    + BWD % "attention_norm/mul",
    "  ROOT %while.34 = (s32[]) while(%p), condition=%c, body=%fwd_body.2, "
    + BWD % "attention/while",
    "}",
    "",
    "ENTRY %main.9 (t: (s32[])) -> f32[8] {",
    "  %while.1 = (s32[]) while(%t), condition=%c, body=%fwd_body.2, "
    'metadata={op_name="jit(step_fn)/shard_map/jvp(model)/while"}',
    "  %while.2 = (s32[]) while(%t), condition=%c, body=%bwd_body.3, "
    'metadata={op_name="jit(step_fn)/shard_map/transpose(jvp(model))/while"}',
    "  ROOT %fusion.5 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f5, "
    'metadata={op_name="jit(step_fn)/shard_map/jvp(model)/attention/mul"}',
    "}",
])

OP_S = {"while.1": 0.500, "while.2": 0.900, "fusion.21": 0.010,
        "custom-call.22": 0.040, "fusion.23": 0.200, "call.24": 0.070,
        "custom-call.31": 0.030, "custom-call.32": 0.090, "fusion.33": 0.050,
        "while.34": 0.300, "fusion.5": 0.005}


def _read(op_s, hlo, steps=2):
    ctx = {"trace": {"op_s": op_s}, "hlo": hlo, "steps_traced": steps}
    return readers.load(NAME, BENCH).read(ctx)


def test_forward_recompute_and_backward_count_once():
    # fusion.21 and the kernel custom-call.22 in the forward loop, the
    # rematted kernel custom-call.31 and the backward kernel custom-call.32,
    # and fusion.5 in the entry computation; not the enclosing while.1,
    # while.2, while.34 or call.24, not fusion.23 outside the scope, not
    # fusion.33 whose component only starts with the scope's name, and not
    # dot.9 inside fusion.21's fused computation
    want = 0.010 + 0.040 + 0.030 + 0.090 + 0.005
    assert _read(OP_S, STEP_HLO) == pytest.approx(1e3 * want / 2)


# a splash kernel's instruction as the TPU compiler prints it: its kernel
# metadata is indented JSON, so the instruction runs over three lines and
# its op_name sits on the last
SPLASH = "\n".join([
    "  %splash_mha_dkv_no_residuals.2 = (f32[8,512,128]{2,1,0:T(8,128)}, "
    "bf16[8,8,512,128]{3,2,1,0:T(8,128)(2,1)}) custom-call(%p, %p), "
    'custom_call_target="tpu_custom_call", '
    "operand_layout_constraints={bf16[8,16,512,128]{3,2,1,0}}, "
    "frontend_attributes={kernel_metadata={",
    '"xprof_metadata":"{\\"block_q_dkv\\": 512, \\"block_kv_dkv\\": 512}"',
    '}}, metadata={op_name="jit(grads)/transpose(jvp(model))/while/body/'
    "closed_call/checkpoint/attention/vmap(jit(_splash_attention))/"
    'splash_mha_dkv_no_residuals/splash_mha_dkv_no_residuals/pallas_call" '
    'stack_frame_id=16}, backend_config={"flag_configs":[],'
    '"custom_call_config":{"body":"TUzvUgFNTElS"}}',
])


def test_a_kernel_printed_over_several_lines_counts():
    # the splash kernel joins the backward loop's ops; the instructions
    # after it keep their own op_names, and the computation after its loop
    # is still read
    hlo = STEP_HLO.replace("%bwd_body.3 (p: (s32[])) -> (s32[]) {",
                           "%bwd_body.3 (p: (s32[])) -> (s32[]) {\n" + SPLASH)
    op_s = {**OP_S, "splash_mha_dkv_no_residuals.2": 0.120}
    want = 0.010 + 0.040 + 0.030 + 0.090 + 0.005 + 0.120
    assert _read(op_s, hlo) == pytest.approx(1e3 * want / 2)
    # out of the scope it does not count, though its kernel metadata
    # precedes a ``metadata={op_name=`` that names it
    plain = hlo.replace("checkpoint/attention/", "checkpoint/")
    assert _read(op_s, plain) == pytest.approx(1e3 * (want - 0.120) / 2)


def test_enclosing_ops_and_fusion_internals_do_not_count():
    only_loops = {k: v for k, v in OP_S.items()
                  if k in ("while.1", "while.2", "while.34", "call.24",
                           "fusion.23", "fusion.33")}
    assert _read(only_loops, STEP_HLO) is None


def test_none_without_attention_scope():
    # the parent program: the same step with no attention scope
    plain = STEP_HLO.replace("/attention/", "/")
    assert _read(OP_S, plain) is None
    assert _read(OP_S, "") is None
    assert readers.load(NAME, BENCH).read({}) is None
