"""Reading the compiled step: loop trip counts, all-to-all bytes, op
names and the memory plan."""
from types import SimpleNamespace

from chipbench import hlo

TEXT = """HloModule jit_step, entry_computation_layout={()->f32[]}

%cond.1 (p: (s32[], bf16[4,8,16])) -> pred[] {
  %constant.3 = s32[]{:T(128)} constant(2)
  %p = (s32[], bf16[4,8,16]) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element(%p), index=0
  ROOT %lt.2 = pred[]{:T(512)} compare(%get-tuple-element.1, %constant.3), direction=LT
}

%body.2 (p: (s32[], bf16[4,8,16])) -> (s32[], bf16[4,8,16]) {
  %p = (s32[], bf16[4,8,16]) parameter(0)
  %get-tuple-element.2 = bf16[4,8,16]{2,1,0} get-tuple-element(%p), index=1
  %all_to_all.5 = bf16[4,8,16]{2,1,0:T(8,128)} all-to-all(%get-tuple-element.2), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(step)/while/body/shard_map/all_to_all"}
  %gmm.6 = bf16[4,8,16]{2,1,0} custom-call(%all_to_all.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/while/body/jit(_grouped_matmul_impl)/pallas_call" stack_frame_id=3}
  ROOT %tuple.3 = (s32[], bf16[4,8,16]) tuple(%get-tuple-element.1, %gmm.6)
}

ENTRY %main.9 (a: bf16[4,8,16]) -> f32[] {
  %a = bf16[4,8,16]{2,1,0} parameter(0)
  %while.7 = (s32[], bf16[4,8,16]) while(%tuple.1), condition=%cond.1, body=%body.2, metadata={op_name="jit(step)/while"}
  %all-to-all.8 = (f32[4,2], f32[4,2]) all-to-all(%b, %c), replica_groups=[1,4]<=[4], dimensions={0}
  ROOT %r = f32[] constant(0)
}
"""


def test_all_to_all_bytes_count_each_loop_trip():
    # in the loop body, 2 trips of 4 * 8 * 16 bf16; at the top, 2 f32[4,2]
    assert hlo.all_to_all_bytes(TEXT) == 2 * 4 * 8 * 16 * 2 + 2 * 4 * 2 * 4


def test_trip_counts_and_multipliers():
    assert hlo._trip_counts(TEXT) == {"cond.1": 2}
    mult = hlo._multipliers(TEXT)
    assert mult["body.2"] == 2 and mult["main.9"] == 1


def test_op_names():
    names = hlo.op_names(TEXT)
    assert names["gmm.6"].endswith("jit(_grouped_matmul_impl)/pallas_call")
    assert names["while.7"] == "jit(step)/while"


def test_shape_bytes():
    assert hlo.shape_bytes("(bf16[2,3]{1,0}, f32[4], s8[], pred[2])") == \
        12 + 16 + 1 + 2


def test_plan_bytes():
    ma = SimpleNamespace(argument_size_in_bytes=10, temp_size_in_bytes=5,
                         output_size_in_bytes=10, alias_size_in_bytes=9)
    assert hlo.plan_bytes(ma) == 16
