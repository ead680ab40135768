"""`ray_tpu.parallel.collectives.collectives` on HLO text: what it counts
once, and what it says starts asynchronously.  Text only: no JAX."""

import pytest

from ray_tpu.parallel.collectives import collectives, format_collectives

_SUM = "bf16[4,1024,1280]{2,1,0} all-reduce(%p), channel_id=3, replica_groups=[2,2]<=[4], to_apply=%add"

# the backward pass's sum of dx as the TPU compiler leaves it without the
# overlap options: one instruction of ENTRY, the weight gradient after it
SYNC = f"""HloModule step

%fused_computation.7 (p: bf16[4,1024,1280]) -> bf16[1280,2560] {{
  ROOT %dw = bf16[1280,2560]{{1,0}} convolution(%p, %p)
}}

ENTRY %main (p: bf16[4,1024,1280]) -> bf16[1280,2560] {{
  %forward = {_SUM}
  %all-reduce.9 = {_SUM}
  %all-gather-start = (f32[1,640]{{1,0}}, f32[2,640]{{1,0}}) all-gather-start(%b), replica_groups=[2,2]<=[4], dimensions={{0}}
  %all-gather-done = f32[2,640]{{1,0}} all-gather-done(%all-gather-start)
  ROOT %fusion.7 = bf16[1280,2560]{{1,0}} fusion(%all-reduce.9), kind=kOutput, calls=%fused_computation.7
}}
"""

# the same sum in an asynchronous fusion: the start's computation, the
# weight-gradient fusion it runs beside and the done's each repeat it
NESTED_ASYNC = f"""HloModule step

%fused_computation.5 (p: bf16[4,1024,1280]) -> (bf16[4,1024,1280], u32[]) {{
  %all-reduce.1 = {_SUM}
  ROOT %custom-call.1 = (bf16[4,1024,1280]{{2,1,0}}, u32[]) custom-call(%p, %all-reduce.1)
}}

%async_collective_fusion.7 (p: bf16[4,1024,1280]) -> (bf16[1280,2560], bf16[4,1024,1280], u32[]) {{
  %all-reduce.2 = {_SUM}
  %dw = bf16[1280,2560]{{1,0}} convolution(%p, %p)
  ROOT %tuple.1 = (bf16[1280,2560]{{1,0}}, bf16[4,1024,1280]{{2,1,0}}, u32[]) tuple(%dw, %all-reduce.2, %s)
}}

%fused_computation.6 (p: bf16[4,1024,1280]) -> bf16[4,1024,1280] {{
  %all-reduce.3 = {_SUM}
  ROOT %custom-call.2 = bf16[4,1024,1280]{{2,1,0}} custom-call(%p, %all-reduce.3)
}}

ENTRY %main (p: bf16[4,1024,1280]) -> bf16[1280,2560] {{
  %forward = {_SUM}
  %async-collective-start.1 = (bf16[4,1024,1280]{{2,1,0}}, u32[]) fusion(%p), kind=kCustom, calls=%fused_computation.5
  %all-gather-start = (f32[1,640]{{1,0}}, f32[2,640]{{1,0}}) all-gather-start(%b), replica_groups=[2,2]<=[4], dimensions={{0}}
  %all-gather-done = f32[2,640]{{1,0}} all-gather-done(%all-gather-start)
  %fusion.7 = (bf16[1280,2560]{{1,0}}, bf16[4,1024,1280]{{2,1,0}}, u32[]) fusion(%async-collective-start.1), kind=kOutput, calls=%async_collective_fusion.7
  %async-collective-done.1 = bf16[4,1024,1280]{{2,1,0}} fusion(%fusion.7), kind=kCustom, calls=%fused_computation.6
  ROOT %dw = bf16[1280,2560]{{1,0}} get-tuple-element(%fusion.7), index=0
}}
"""

# a collective in a loop's body is no repeat of anything: it counts, as it always did
IN_A_LOOP = f"""HloModule step

%body (p: bf16[4,1024,1280]) -> bf16[4,1024,1280] {{
  ROOT %all-reduce.4 = {_SUM}
}}

ENTRY %main (p: bf16[4,1024,1280]) -> bf16[4,1024,1280] {{
  %forward = {_SUM}
  ROOT %while.1 = bf16[4,1024,1280]{{2,1,0}} while(%forward), condition=%cond, body=%body
}}
"""


@pytest.mark.parametrize("text, sums_async, gathers", [
    (SYNC, 0, 1),
    (NESTED_ASYNC, 1, 1),
    (IN_A_LOOP, 0, 0),
], ids=["sync", "nested-async-fusion", "loop-body"])
def test_a_sum_counts_once_and_says_whether_it_starts_async(text, sums_async, gathers):
    rows = {(r.op, r.shape): r for r in collectives(text)}
    sums = rows["all-reduce", "bf16[4,1024,1280]"]
    # the forward sum and the backward one, however many computations repeat the second
    assert (sums.count, sums.started_async, sums.groups) == (2, sums_async, "[2,2]<=[4]")
    assert abs(sums.mb - 4 * 1024 * 1280 * 2 / 1e6) < 1e-9
    assert len(rows) == 1 + gathers
    if gathers:
        # a `-start` is asynchronous by its name, and counts by the buffer it produces
        gather = rows["all-gather", "f32[2,640]"]
        assert (gather.count, gather.started_async) == (1, 1)
    listing = format_collectives(list(rows.values())).splitlines()
    assert listing[0].split()[:4] == ["operation", "groups", "count", "async"]
    assert listing[-1].split()[:3] == ["total", str(2 + gathers), str(sums_async + gathers)]
