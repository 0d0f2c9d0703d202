"""Device time by the train step's own scopes, read from the HLO's
metadata, and the readers of it; the trace reduction they build on is
unchanged by them."""
import pytest

from conftest import BENCH
from harness import readers, step_scopes, trace_reduce

DATA = BENCH / "tests" / "data"

STEP_HLO = "\n".join([
    "HloModule jit_step_fn, entry_computation_layout={()->()}",
    "",
    "%body.1 (p: (s32[])) -> (s32[]) {",
    '  %fusion.2 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f2, '
    'metadata={op_name="jit(step_fn)/shard_map/transpose(jvp(model))/while/'
    'body/closed_call/checkpoint/rematted_computation/dot_general" '
    'source_file="m.py" source_line=3}',
    "}",
    "",
    "ENTRY %main.9 (t: (s32[])) -> f32[8] {",
    '  %while.1 = (s32[]) while(%t), condition=%c, body=%body.1, '
    'metadata={op_name="jit(step_fn)/shard_map/transpose(jvp(model))/while"}',
    '  %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f3, '
    'metadata={op_name="jit(step_fn)/shard_map/jvp(model)/dot_general"}',
    '  %fabric_vote_combine.4 = u32[8,128]{1,0} custom-call(%w), '
    'custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/'
    'shard_map/fabric.aggregate/shard_map/vote/jit(_vote_combine_call)/'
    'fabric_vote_combine/pallas_call"}',
    '  %fusion.5 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f5, '
    'metadata={op_name="jit(step_fn)/shard_map/fabric.aggregate/reshape"}',
    '  %fusion.6 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f6, '
    'metadata={op_name="jit(step_fn)/optimizer.apply/mul"}',
    "  %copy.7 = f32[8]{0} copy(%x)",
    '  ROOT %fusion.8 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f8, '
    'metadata={op_name="jit(step_fn)/jvp(model_head)/add"}',
    "}",
])

OP_S = {"while.1": 0.300, "fusion.2": 0.250, "fusion.3": 0.100,
        "fabric_vote_combine.4": 0.006, "fusion.5": 0.004,
        "fusion.6": 0.030, "copy.7": 0.002, "fusion.8": 0.001}

SCOPE_METRICS = {"fwd_ms.train": "forward", "bwd_ms.train": "backward",
                 "agg_ms.train": "aggregate", "opt_ms.train": "optimizer"}


def test_entry_op_names_reads_the_entry_computation_only():
    meta = step_scopes.entry_op_names(STEP_HLO)
    assert set(meta) == {"while.1", "fusion.3", "fabric_vote_combine.4",
                         "fusion.5", "fusion.6", "copy.7", "fusion.8"}
    assert meta["copy.7"] == ""


@pytest.mark.parametrize("op_name, scope", [
    ("jit(s)/transpose(jvp(model))/while", "backward"),
    ("jit(s)/jvp(model)/dot_general", "forward"),
    ("jit(s)/fabric.aggregate/reshape", "aggregate"),
    ("jit(s)/fabric.aggregate/shard_map/vote/x/pallas_call",
     "aggregate/vote"),
    ("jit(s)/fabric.aggregate/bucket.scatter/dynamic_slice",
     "aggregate/bucket.scatter"),
    # aggregation and optimizer take precedence over forward and backward
    ("jit(s)/transpose(jvp(model))/fabric.aggregate/encode/x",
     "aggregate/encode"),
    ("jit(s)/jvp(model)/optimizer.apply/x", "optimizer"),
    # a scope is a whole path component, not a prefix of a function name
    ("jit(s)/jvp(model_head)/add", None),
    ("jit(s)/transpose(jvp(model_fn))/add", None),
    ("", None),
])
def test_scope_of(op_name, scope):
    assert step_scopes.scope_of(op_name) == scope


def test_while_and_body_counted_once_in_their_scope():
    # fusion.2 runs inside while.1, whose event encloses it: only the
    # loop counts
    got = step_scopes.scope_seconds(OP_S, STEP_HLO)
    assert got == pytest.approx({"backward": 0.300, "forward": 0.100,
                                 "aggregate/vote": 0.006,
                                 "aggregate": 0.004, "optimizer": 0.030})
    assert step_scopes.scope_seconds(OP_S, "") is None


def test_readers_per_step_ms():
    ctx = {"trace": {"op_s": OP_S}, "hlo": STEP_HLO, "steps_traced": 2}
    got = {n: readers.load(n, BENCH).read(ctx) for n in SCOPE_METRICS}
    assert got == pytest.approx({"fwd_ms.train": 50.0,
                                 "bwd_ms.train": 150.0,
                                 "agg_ms.train": 5.0, "opt_ms.train": 15.0})


def test_readers_silent_without_program_scopes():
    """The recorded trace comes from a program that names no scope: each
    reader returns None, not 0, with or without its HLO."""
    red = trace_reduce.reduce_events(
        trace_reduce.load_events(DATA / "q25_trace_events.json.gz"))
    unscoped = "\n".join(line.split(", metadata=")[0]
                         for line in STEP_HLO.splitlines())
    for hlo in ("", unscoped):
        ctx = {"trace": red, "hlo": hlo, "steps_traced": 6}
        for name in SCOPE_METRICS:
            assert readers.load(name, BENCH).read(ctx) is None, name
    for name in SCOPE_METRICS:
        assert readers.load(name, BENCH).read({}) is None, name


def test_recorded_trace_reduces_as_before():
    """``reduce_events`` on the recorded trace gives what it gave before
    the scopes were read beside it."""
    r = trace_reduce.reduce_events(
        trace_reduce.load_events(DATA / "q25_trace_events.json.gz"))
    assert set(r) == {"window_s", "busy_s", "chips", "op_s", "idle_gaps"}
    assert r["chips"] == 1 and len(r["op_s"]) == 638
    assert sum(r["op_s"].values()) == pytest.approx(1.929214138, abs=1e-9)
    top = sorted(r["op_s"].items(), key=lambda kv: -kv[1])[:3]
    assert [k for k, _ in top] == ["while.8", "fusion.2", "while.9"]
    assert [v for _, v in top] == pytest.approx(
        [0.505401242, 0.200673263, 0.161623973], abs=1e-9)
    assert r["idle_gaps"][:3] == [
        ["$array.py:631 _value", 0.004829946],
        ["$array.py:631 _value", 0.004608055],
        ["tpu::System::TransferFromDevice=>IssueEvent", 0.004128764]]


def _mosaic(name, op_name, indent="  "):
    meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
    return (f'{indent}%{name} = u32[8,128]{{1,0}} custom-call(%w), '
            f'custom_call_target="tpu_custom_call"{meta}')


#: a step with one Mosaic call under each of its scopes, one under none,
#: and one under the aggregation inside a loop's body
KERNEL_HLO = "\n".join([
    "HloModule jit_step_fn, entry_computation_layout={()->()}",
    "",
    "%body.1 (p: (s32[])) -> (s32[]) {",
    _mosaic("fabric_pack.9", "jit(step_fn)/fabric.aggregate/encode/"
            "fabric_pack/pallas_call"),
    "}",
    "",
    "ENTRY %main.9 (t: (s32[])) -> f32[8] {",
    '  %while.1 = (s32[]) while(%t), condition=%c, body=%body.1, '
    'metadata={op_name="jit(step_fn)/fabric.aggregate/encode/while"}',
    _mosaic("fabric_vote_combine.2", "jit(step_fn)/shard_map/"
            "fabric.aggregate/shard_map/vote/fabric_vote_combine/pallas_call"),
    _mosaic("fabric_unscoped.3", "jit(step_fn)/fabric.aggregate/"
            "pallas_call"),
    _mosaic("gmm.4", "jit(step_fn)/shard_map/jvp(model)/moe/gmm/"
            "pallas_call"),
    _mosaic("gmm.5", "jit(step_fn)/shard_map/transpose(jvp(model))/moe/"
            "gmm/pallas_call"),
    _mosaic("adam.6", "jit(step_fn)/optimizer.apply/pallas_call"),
    _mosaic("custom-call.7", ""),
    '  ROOT %fusion.8 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f8, '
    'metadata={op_name="jit(step_fn)/fabric.aggregate/vote/add"}',
    "}",
])

KERNEL_OP_S = {"while.1": 0.0105, "fabric_pack.9": 0.010,
               "fabric_vote_combine.2": 0.006, "fabric_unscoped.3": 0.002,
               "gmm.4": 0.100, "gmm.5": 0.200, "adam.6": 0.030,
               "custom-call.7": 0.004, "fusion.8": 0.001}


def test_kernel_seconds_by_scope():
    from harness import hlo_ops
    op_class = hlo_ops.classify(KERNEL_OP_S, KERNEL_HLO)
    assert sorted(k for k, c in op_class.items() if c == "kernel") == [
        "adam.6", "custom-call.7", "fabric_pack.9", "fabric_unscoped.3",
        "fabric_vote_combine.2", "gmm.4", "gmm.5"]
    got = step_scopes.kernel_seconds(KERNEL_OP_S, op_class, KERNEL_HLO)
    assert got == pytest.approx({
        "aggregate/encode": 0.010, "aggregate/vote": 0.006,
        "aggregate": 0.002, "forward": 0.100, "backward": 0.200,
        "optimizer": 0.030, "": 0.004})


def test_agg_kernel_ms_counts_the_vote_datapath_only():
    """The model's own Mosaic kernels (forward and backward), the
    optimizer's and an unscoped one are not the vote datapath's; its
    kernels count wherever their computation sits."""
    from harness import hlo_ops
    ctx = {"trace": {"op_s": KERNEL_OP_S}, "hlo": KERNEL_HLO,
           "op_class": hlo_ops.classify(KERNEL_OP_S, KERNEL_HLO),
           "steps_traced": 2}
    got = readers.load("agg_kernel_ms", BENCH).read(ctx)
    assert got == pytest.approx(1e3 * (0.010 + 0.006 + 0.002) / 2)
    # with no kernel under the aggregation the reader is silent
    no_agg = {k: v for k, v in KERNEL_OP_S.items()
              if not k.startswith("fabric_")}
    ctx["trace"] = {"op_s": no_agg}
    assert readers.load("agg_kernel_ms", BENCH).read(ctx) is None
