"""Counting collectives in optimised HLO text (lines as the v5e's
compiler printed them for the four-chip VGG-16 step, shortened)."""

from benchmark import hlo

TEXT = """
  %all-reduce = (f32[4096]{0:T(1024)S(1)}, f32[]{:T(128)}) all-reduce(%copy-done.52, %div.50), channel_id=1
  %get-tuple-element.1017 = f32[]{:T(128)} get-tuple-element(%all-reduce), index=4, metadata={op_name="jit(one_step)/shard_map/psum"}
  %psum.53 = f32[16452392]{0:T(1024)} all-reduce(%concatenate.5), channel_id=1, replica_groups={{0,1,2,3}}
  %fusion.7 = f32[8]{0} fusion(%psum.53), kind=kLoop, calls=%fused_computation.7
  %all-gather-start.1 = (f32[8]{0}, f32[32]{0}) all-gather-start(%fusion.7), dimensions={0}
  %all-gather-done.1 = f32[32]{0} all-gather-done(%all-gather-start.1)
  ROOT %collective-permute.2 = f32[8]{0} collective-permute(%fusion.7), source_target_pairs={{0,1}}
"""


def test_collectives_are_found_by_opcode_not_by_name():
    assert hlo.collective_instructions(TEXT) == [
        ("all-reduce", "all-reduce", ""),
        ("psum.53", "all-reduce", ""),
        ("all-gather-start.1", "all-gather", "-start"),
        ("all-gather-done.1", "all-gather", "-done"),
        ("collective-permute.2", "collective-permute", ""),
    ]


def test_an_asynchronous_pair_is_one_call():
    assert hlo.collective_calls(TEXT) == 4
    assert hlo.collective_calls("%a = f32[2]{0} add(%b, %c)") == 0
