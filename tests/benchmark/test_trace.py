"""The trace reduction, on hand-worked intervals and on a small trace
recorded on a TPU v5e (``chipbench/testdata/record.py``), and the counts of
bytes and operations, on shapes worked by hand."""
from __future__ import annotations

import pytest

from chipbench import trace, work
from conftest import ROOT

SMALL = ROOT / "chipbench" / "testdata" / "small.xplane.pb"


def test_union_gaps_and_clip():
    ivs = [(0, 10), (5, 15), (20, 30), (22, 25)]
    assert trace.union_length(ivs) == 25
    assert trace.gaps(ivs, 0, 40) == [(15, 20), (30, 40)]
    assert trace.gaps(ivs, 8, 24) == [(15, 20)]
    assert trace.union_length(trace.clip(ivs, 8, 24)) == 11


def test_self_time_subtracts_nested_ops():
    ops = [(0, 100, "%cond.3.clone = c"), (10, 30, "%copy.3 = a"),
           (40, 90, "%copy.4 = b"), (50, 60, "%inner = x"),
           (120, 130, "%fusion.1 = f")]
    own = dict((name, t) for t, name in trace.self_times(ops))
    assert own["%cond.3.clone = c"] == 100 - 20 - 50
    assert own["%copy.4 = b"] == 50 - 10
    assert own["%inner = x"] == 10
    assert own["%fusion.1 = f"] == 10


def test_names():
    assert trace.module_name("jit_descriptor_copy(7182224888038617336)") \
        == "descriptor_copy"
    assert trace.op_kind("%copy.13 = bf16[4096,256] copy(x)") == "copy"
    assert trace.op_kind("%cond.3.clone = (bf16[1]) conditional(p)") \
        == "cond"
    assert trace.op_kind('%b.2 = f32[] custom-call(a), '
                         'custom_call_target="tpu_custom_call"') \
        == "tpu_custom_call"


def test_recorded_trace():
    # Worked by hand from the recorded events: three descriptor_copy
    # programs (53391, 53154, 53350 ns) and three adds (7102, 7060,
    # 6838 ns) that never overlap, from 43644386 ns to 87481536 ns; one
    # Pallas kernel in each descriptor_copy program (about 1458 ns each);
    # host sleeps of about 21 ms between rounds.
    t = trace.reduce_trace(SMALL, host_spans=["$time sleep"])
    ns = 1e-9
    assert t.devices == 1
    assert t.window_s == pytest.approx((87481536 - 43644386) * ns, abs=2e-9)
    assert t.module_s["descriptor_copy"] == pytest.approx(
        159895 * ns, abs=3e-9)
    assert t.module_s["_lambda"] == pytest.approx(21000 * ns, abs=3e-9)
    assert t.busy_s == pytest.approx(180895 * ns, abs=6e-9)
    assert t.kernel_s == {"descriptor_copy": pytest.approx(
        4377 * ns, abs=3e-9)}
    assert t.idle_share == pytest.approx(1 - 180895 / 43837150, abs=1e-6)
    # own times of every op of the programs add up to no more than the
    # programs' time; the kernel is a small part of it
    assert sum(t.op_s.values()) <= t.busy_s + 1e-9
    assert t.op_s["tpu_custom_call"] == pytest.approx(4377 * ns, abs=3e-9)
    assert t.nonkernel_s > 20 * t.op_s["tpu_custom_call"]
    # the two longest idle gaps lie between rounds, while the host slept
    (l1, g1), (l2, g2) = t.idle_gaps[:2]
    assert (l1, l2) == ("$time sleep", "$time sleep")
    assert g1 == pytest.approx((65635005 - 43821905) * ns, abs=3e-9)
    assert g2 == pytest.approx((87325585 - 65818276) * ns, abs=3e-9)


def test_copy_bytes_by_hand():
    # 360 pages of 16 tokens x 256 bf16 elements, in 72 pools
    lengths = [4096] * 360
    payload = work.copy_payload_bytes(lengths, 2)
    assert payload == 360 * 8192
    assert work.copy_needed_bytes(payload) == 2 * 360 * 8192


def test_decode_flops_by_hand():
    qwen = {"hidden_size": 2048, "num_attention_heads": 16,
            "num_key_value_heads": 2, "intermediate_size": 11008,
            "num_hidden_layers": 36, "vocab_size": 151936}
    # per layer: q 2048*2048 + k,v 2*2048*256 + o 2048*2048 = 9437184;
    # MLP 3*2048*11008 = 67633152; 36 layers + the 2048 x 151936 head
    assert work.dense_matmul_params(qwen) == 36 * (9437184 + 67633152) \
        + 2048 * 151936 == 3085697024
    flops = work.decode_flops(qwen, [10, 20])
    assert flops == 2 * 3085697024 * 2 + 4 * 36 * 16 * 128 * 30
