"""scripts/gossip_overlap.py: which collectives of the round state it counts,
and which of them it finds under compute, on a hand-written optimized-HLO
text."""
import importlib.util
import os

import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "scripts",
                     "gossip_overlap.py")
_spec = importlib.util.spec_from_file_location("gossip_overlap", _PATH)
gossip_overlap = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(gossip_overlap)

# state leaves (4, 8, 128) and (4, 256); one client's slices (1, 8, 128)
# and (1, 256)
HLO = """\
HloModule jit_chunk_step, entry_computation_layout={()}

%fused_computation.1 (param_0.1: f32[1,8,128]) -> f32[4,8,128] {
  %param_0.1 = f32[1,8,128]{2,1,0:T(8,128)} parameter(0)
  %all-gather.9 = f32[4,8,128]{2,1,0:T(8,128)} all-gather(%param_0.1), channel_id=9, replica_groups=[1,4]<=[4], dimensions={0}
  ROOT %custom-call.1 = f32[4,8,128]{2,1,0:T(8,128)} custom-call(%all-gather.9), custom_call_target="x"
}

%async_collective_fusion.2 (param_0.2: f32[1,256], param_1.2: bf16[8,128]) -> (f32[4,256], bf16[8,128]) {
  %param_0.2 = f32[1,256]{1,0:T(1,128)} parameter(0)
  %all-gather.8 = f32[4,256]{1,0:T(4,128)} all-gather(%param_0.2), channel_id=8, replica_groups=[1,4]<=[4], dimensions={0}
  ROOT %tuple.2 = (f32[4,256]{1,0:T(4,128)}, bf16[8,128]{1,0:T(8,128)}) tuple(%all-gather.8, %param_1.2)
}

%layer_body (t.0: (s32[], bf16[8,128])) -> (s32[], bf16[8,128]) {
  %t.0 = (s32[], bf16[8,128]) parameter(0)
  ROOT %t.1 = (s32[], bf16[8,128]) tuple(%i.1, %b.1)
}

%layer_cond (t.2: (s32[], bf16[8,128])) -> pred[] {
  ROOT %c.1 = pred[] constant(true)
}

%round_body (tuple.0: (s32[], f32[1,8,128], f32[1,256])) -> (s32[], f32[1,8,128], f32[1,256]) {
  %tuple.0 = (s32[], f32[1,8,128], f32[1,256]) parameter(0)
  %x.1 = f32[1,8,128]{2,1,0:T(8,128)} get-tuple-element(%tuple.0), index=1
  %x.2 = f32[1,256]{1,0:T(1,128)} get-tuple-element(%tuple.0), index=2
  %all-gather-start.1 = (f32[1,8,128]{2,1,0:T(8,128)}, f32[4,8,128]{2,1,0:T(8,128)}) all-gather-start(%x.1), channel_id=1, replica_groups=[1,4]<=[4], dimensions={0}
  %fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%b.0), kind=kLoop, calls=%fused_computation.0
  %all-gather-done.1 = f32[4,8,128]{2,1,0:T(8,128)} all-gather-done(%all-gather-start.1)
  %all-gather-start.2 = (f32[1,256]{1,0:T(1,128)}, f32[4,256]{1,0:T(4,128)}) all-gather-start(%x.2), channel_id=2, replica_groups=[1,4]<=[4], dimensions={0}
  %gte.2 = f32[1,256]{1,0:T(1,128)} get-tuple-element(%tuple.0), index=2
  %all-gather-done.2 = f32[4,256]{1,0:T(4,128)} all-gather-done(%all-gather-start.2)
  %async-collective-start = (f32[1,256]{1,0:T(1,128)}, f32[4,256]{1,0:T(4,128)}, s32[2]{0}) fusion(%x.2), kind=kOutput, calls=%fused_computation.3
  %gte.3 = f32[4,256]{1,0:T(4,128)} get-tuple-element(%async-collective-start), index=1
  %while.1 = (s32[], bf16[8,128]) while(%init.1), condition=%layer_cond, body=%layer_body
  %fusion.2 = (f32[4,256]{1,0:T(4,128)}, bf16[8,128]{1,0:T(8,128)(2,1)}) fusion(%gte.3, %b.0), kind=kLoop, calls=%async_collective_fusion.2
  %gte.4 = f32[4,256]{1,0:T(4,128)} get-tuple-element(%fusion.2), index=0
  %async-collective-done = f32[4,256]{1,0:T(4,128)} fusion(%gte.4), kind=kOutput, calls=%fused_computation.4
  %all-gather.3 = f32[4,8,128]{2,1,0:T(8,128)} all-gather(%x.1), channel_id=3, replica_groups=[1,4]<=[4], dimensions={0}
  %all-gather.4 = f32[4,8]{1,0:T(4,128)} all-gather(%y.1), channel_id=4, replica_groups=[1,4]<=[4], dimensions={0}
  %collective-permute-start.1 = (f32[1,8,128]{2,1,0:T(8,128)}, f32[1,8,128]{2,1,0:T(8,128)}) collective-permute-start(%x.1), channel_id=5, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %convolution.1 = bf16[8,128]{1,0:T(8,128)(2,1)} convolution(%b.0, %b.1), dim_labels=bf_io->bf
  %collective-permute-done.1 = f32[1,8,128]{2,1,0:T(8,128)} collective-permute-done(%collective-permute-start.1)
  %fusion.5 = f32[4,8,128]{2,1,0:T(8,128)} fusion(%x.1), kind=kOutput, calls=%fused_computation.1
  ROOT %tuple.1 = (s32[], f32[1,8,128], f32[1,256]) tuple(%i.0, %x.1, %x.2)
}

%round_cond (tuple.3: (s32[], f32[1,8,128], f32[1,256])) -> pred[] {
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.5 (p.0: (s32[], f32[1,8,128], f32[1,256])) -> (s32[], f32[1,8,128], f32[1,256]) {
  %p.0 = (s32[], f32[1,8,128], f32[1,256]) parameter(0)
  ROOT %while.0 = (s32[], f32[1,8,128], f32[1,256]) while(%p.0), condition=%round_cond, body=%round_body
}
"""


@pytest.fixture(scope="module")
def found():
    got = gossip_overlap.state_collectives(HLO, {(4, 8, 128), (4, 256)}, 4)
    return {c["collective"]: c for c in got}


def test_counts_only_collectives_of_state_leaves(found):
    # all-gather.4 gathers y-sized data; the gathers inside the async
    # collective fusion and fusion.5's kernel are counted once, at their
    # start and at the fusion
    assert sorted(found) == [
        "all-gather-start.1", "all-gather-start.2", "all-gather.3",
        "async-collective-start", "collective-permute-start.1", "fusion.5"]


@pytest.mark.parametrize("name,overlapped,loops", [
    ("all-gather-start.1", True, 0),        # a fusion between start and done
    ("all-gather-start.2", False, 0),       # only a get-tuple-element
    ("async-collective-start", True, 1),    # a loop and its steps' fusion
    ("all-gather.3", False, 0),             # synchronous
    ("collective-permute-start.1", True, 0),
    ("fusion.5", False, 0),                 # gathered inside one kernel
])
def test_marks_what_runs_under_compute(found, name, overlapped, loops):
    assert found[name]["overlapped"] is overlapped
    assert found[name]["loops_between"] == loops
    assert found[name]["place"] == "per_iteration"


def test_sizes_a_permute_by_one_clients_slice(found):
    assert found["collective-permute-start.1"]["elements"] == 8 * 128
    assert found["all-gather.3"]["elements"] == 4 * 8 * 128


def test_reads_tuple_types_and_operands():
    line = ("  %all-gather-start.1 = (f32[1,8,128]{2,1,0:T(8,128)}, "
            "f32[4,8,128]{2,1,0:T(8,128)}) all-gather-start(%x.1), "
            "channel_id=1, dimensions={0}")
    name, rtype, op, operands = gossip_overlap.instruction(line)
    assert (name, op, operands) == ("all-gather-start.1", "all-gather-start",
                                    ["x.1"])
    assert rtype.startswith("(f32[1,8,128]") and rtype.endswith(")")
