"""scripts/layout_copies.py: which copies of the round state it counts, and
where it places them, on a hand-written optimized-HLO text."""
import importlib.util
import os

import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "scripts",
                     "layout_copies.py")
_spec = importlib.util.spec_from_file_location("layout_copies", _PATH)
layout_copies = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(layout_copies)

HLO = """\
HloModule jit_chunk_step, entry_computation_layout={()}

%fused_computation.1 (param_0.1: f32[4,8,128]) -> f32[4,8,128] {
  %param_0.1 = f32[4,8,128]{2,1,0:T(8,128)} parameter(0)
  ROOT %copy.10 = f32[4,8,128]{2,0,1:T(4,128)} copy(%param_0.1)
}

%branch_log (arg.0: f32[4,8,128]) -> f32[4,8,128] {
  %arg.0 = f32[4,8,128]{2,1,0:T(8,128)} parameter(0)
  ROOT %copy.20 = f32[4,8,128]{2,0,1:T(4,128)} copy(%arg.0)
}

%branch_skip (arg.1: f32[4,8,128]) -> f32[4,8,128] {
  ROOT %arg.1 = f32[4,8,128]{2,1,0:T(8,128)} parameter(0)
}

%body.3 (tuple.0: (s32[], f32[4,8,128])) -> (s32[], f32[4,8,128]) {
  %tuple.0 = (s32[], f32[4,8,128]) parameter(0)
  %gte.1 = f32[4,8,128]{2,0,1:T(4,128)} get-tuple-element(%tuple.0), index=1
  %copy.30 = f32[4,8,128]{2,1,0:T(8,128)} copy(%gte.1)
  %copy.31 = f32[4,8,128]{2,1,0:T(8,128)} copy(%copy.30)
  %copy.32 = f32[4,8,128]{2,1,0:T(8,128)S(1)} copy(%copy.31)
  %copy.33 = bf16[4,8,128]{2,0,1:T(4,128)} copy(%bf.1)
  %copy.34 = f32[4,8]{0,1:T(4,128)} copy(%small.1)
  %fusion.1 = f32[4,8,128]{2,0,1:T(4,128)} fusion(%copy.31), kind=kLoop, calls=%fused_computation.1
  %p.1 = pred[] constant(true)
  %conditional.1 = f32[4,8,128]{2,1,0:T(8,128)} conditional(%p.1, %copy.31, %copy.31), branch_computations={%branch_log, %branch_skip}
  ROOT %tuple.1 = (s32[], f32[4,8,128]) tuple(%i.0, %fusion.1)
}

%cond.4 (tuple.2: (s32[], f32[4,8,128])) -> pred[] {
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.5 (state_x__w__.1: f32[4,8,128]) -> f32[4,8,128] {
  %state_x__w__.1 = f32[4,8,128]{2,1,0:T(8,128)} parameter(0)
  %copy.40 = f32[4,8,128]{2,0,1:T(4,128)} copy(f32[4,8,128]{2,1,0:T(8,128)} %state_x__w__.1)
  %while.1 = (s32[], f32[4,8,128]) while(%init.1), condition=%cond.4, body=%body.3
  ROOT %gte.9 = f32[4,8,128]{2,0,1:T(4,128)} get-tuple-element(%while.1), index=1
}
"""


@pytest.fixture(scope="module")
def found():
    copies = layout_copies.state_copies(HLO, {(4, 8, 128)})
    return {c["copy"]: c for c in copies}


def test_counts_only_layout_changing_f32_copies_of_state_shapes(found):
    # copy.31: same layout; copy.32: memory space only; copy.33: bf16;
    # copy.34: not a state leaf's shape
    assert sorted(found) == ["copy.10", "copy.20", "copy.30", "copy.40"]


@pytest.mark.parametrize("name,place", [
    ("copy.40", "per_call"), ("copy.30", "per_iteration"),
    ("copy.20", "in_branch"), ("copy.10", "fused")])
def test_places_each_copy_where_it_runs(found, name, place):
    assert found[name]["place"] == place


def test_reads_the_operand_type_from_its_definition(found):
    assert found["copy.30"]["from"] == "2,0,1:T(4,128)"
    assert found["copy.30"]["to"] == "2,1,0:T(8,128)"
    assert found["copy.40"]["operand"] == "state_x__w__.1"
