"""The benchmark's readers of collective time (``collective_share``,
``decode_collective_ms``) on hand-made traces, with operations named as a
traced ``nemo4_15b.chat_tp4`` job on four v5e chips named them (the HLO
text of each operation, shortened)."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import collectives, harness, spec  # noqa: E402
from chipbench import trace as trace_lib  # noqa: E402

S = trace_lib.Span
MS = 1e6  # ns

AR = ("%all-reduce.19 = bf16[8,1,6144]{2,0,1:T(8,128)(2,1)S(1)} all-reduce(bf16[8,1,6144]"
      "{2,0,1:T(8,128)(2,1)S(1)} %fusion.148), channel_id=8, replica_groups=[1,4]<=[4], "
      "use_global_device_ids=true, to_apply=%add.1.clone")
AG = ("%all-gather.39 = bf16[8,8,6,128]{3,2,1,0:T(8,128)(2,1)S(1)} all-gather(bf16[8,2,6,128]"
      "{3,2,1,0:T(8,128)(2,1)S(1)} %copy.53), channel_id=3, replica_groups=[1,4]<=[4], "
      "dimensions={1}")
START = ("%async-collective-start = (bf16[8,256,8,128]{1,3,2,0:T(8,128)(2,1)S(1)}, "
         "bf16[8,1024,8,128]{1,3,2,0:T(8,128)(2,1)S(1)}, s32[2]{0:S(4)}, u32[]{:S(2)}) "
         "fusion(bf16[8,256,8,128]{1,3,2,0:T(8,128)(2,1)S(1)} %pad_maximum_fusion.9), "
         "kind=kCustom, calls=%fused_computation.221")
DONE = ("%async-collective-done = bf16[8,1024,8,128]{1,3,2,0:T(8,128)(2,1)S(1)} fusion("
        "bf16[8,256,8,128]{1,3,2,0:T(8,128)(2,1)S(1)} %get-tuple-element.691), kind=kCustom, "
        "calls=%fused_computation.225")
FUSED = ("%fusion.153 = (bf16[8,1,2,128]{3,0,2,1:T(8,128)(2,1)S(1)}, bf16[8,2,128]{2,0,1:"
         "T(8,128)(2,1)S(1)}) fusion(bf16[8,2,128]{2,0,1:T(8,128)(2,1)S(1)} %copy.53), "
         "kind=kCustom, calls=%async_collective_fusion.153")
SCATTER = ("%fusion.3 = bf16[8,256,6144]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[8,1024,6144]"
           "{2,1,0:T(8,128)(2,1)} %broadcast_select_fusion), kind=kCustom, calls=%all-reduce-scatter")
PERMUTE = ("%collective-permute-done.13 = bf16[8,1,256,6144]{3,2,0,1:T(8,128)(2,1)} "
           "collective-permute-done((bf16[8,1,256,6144]{3,2,0,1:T(8,128)(2,1)}, "
           "bf16[8,1,256,6144]{3,2,0,1:T(8,128)(2,1)}, u32[]{:S(2)}, u32[]{:S(2)}) "
           "%collective-permute-start.13)")
# compute that reads a collective's result is no collective
ADD = ("%add.318 = bf16[8,1,6144]{2,0,1:T(8,128)(2,1)S(1)} add(bf16[8,1,6144]{2,0,1:"
       "T(8,128)(2,1)S(1)} %get-tuple-element.633, bf16[8,1,6144]{2,0,1:T(8,128)(2,1)S(1)} "
       "%all-reduce.20)")
MATMUL = ("%fusion.147 = bf16[8,8,6,128] fusion(%bitcast_dynamic-update-slice_fusion.9, "
          "%all-reduce.16), kind=kOutput, calls=%fused_computation.13.clone.clone")

PREFILL = "jit_prefill_into(12345)"
DECODE = "jit_serve_step(6789)"
# chip 0 over a 100 ms traced job: one prefill call 0-40 ms, two decode
# calls 50-60 and 60-70 ms; busy 0-30, 35-40, 50-58 and 60-69 ms (52 ms)
MODULES = [S(PREFILL, 0, 40 * MS), S(DECODE, 50 * MS, 60 * MS), S(DECODE, 60 * MS, 70 * MS)]
OPS = [S(MATMUL, 0, 10 * MS), S(START, 10 * MS, 11 * MS), S(FUSED, 11 * MS, 16 * MS),
       S(DONE, 16 * MS, 17 * MS), S(SCATTER, 17 * MS, 20 * MS), S(AG, 20 * MS, 22 * MS),
       S(MATMUL, 22 * MS, 30 * MS), S(PERMUTE, 35 * MS, 40 * MS),
       # decode call 1: 3 ms of all-reduce, 1 ms of it beside an add
       S(ADD, 50 * MS, 52 * MS), S(AR, 51 * MS, 54 * MS), S(MATMUL, 54 * MS, 58 * MS),
       # decode call 2: two all-reduces overlapping (4 ms in union)
       S(AR, 60 * MS, 63 * MS), S(AR, 62 * MS, 64 * MS), S(ADD, 64 * MS, 69 * MS)]
COLLECTIVE_MS = (1 + 5 + 1 + 3 + 2 + 5) + 3 + 4   # START..AG, PERMUTE; AR; AR ∪ AR
EXPECTED = {"collective_share": 100.0 * COLLECTIVE_MS / 52,
            "decode_collective_ms": (3 + 4) / 2}


def _run(ops=OPS, modules=MODULES, traced=True, devices=True):
    tr = None
    if traced:
        devs = [trace_lib.DeviceTrace(0, modules, ops)] if devices else []
        tr = trace_lib.Trace(devs, [S(harness.TRACED_SPAN, 0, 100 * MS)], (0.0, 100 * MS))
    return harness.Run({}, {}, 4, None, 0.0, 0.0, [], 0, tr)


def test_ops_are_classed_by_their_own_name_or_called_computation():
    for name in (AR, AG, START, DONE, FUSED, SCATTER, PERMUTE, "all-reduce.5",
                 "%all-reduce-start.2 = f32[8] all-reduce-start(f32[8] %x)",
                 "%reduce-scatter.1 = f32[2] reduce-scatter(f32[8] %x)",
                 "%all-to-all.4 = f32[8] all-to-all(f32[8] %x)"):
        assert collectives.is_collective(name), name
    for name in (ADD, MATMUL, "%all-reducer.1 = f32[] add(f32[] %a, f32[] %b)",
                 "%slice-start.3 = ((bf16[1,2,128,6144]), u32[]) slice-start(bf16[2,128,6144]"
                 " %p), calls=%async_computation.3",
                 "%while.3 = (s32[]) while(%tuple), condition=%cond, body=%wide.region_0"):
        assert not collectives.is_collective(name), name


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_value_on_a_hand_made_trace(name):
    assert spec.reader(name)(_run()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_trace_without_collectives_reads_zero(name):
    plain = [o for o in OPS if not collectives.is_collective(o.name)]
    assert spec.reader(name)(_run(ops=plain)) == 0.0


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_no_trace_reads_nothing(name):
    assert spec.reader(name)(_run(traced=False)) is None
    assert spec.reader(name)(_run(devices=False)) is None


def test_no_decode_call_reads_nothing_per_call():
    assert spec.reader("decode_collective_ms")(_run(modules=MODULES[:1])) is None
