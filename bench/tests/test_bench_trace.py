"""The reduction from a profiler trace to busy time, kernel time, idle
share and idle gaps, on a synthetic trace."""
import pytest

import _setup  # noqa: F401
from harness import trace

MS = 1_000_000  # ns


def synthetic():
    # window 0..100 ms; ops overlap, one op straddles each edge
    ops = [("fusion.1", -5 * MS, 5 * MS),
           ("flash_kernel", 10 * MS, 30 * MS),
           ("fusion.2", 20 * MS, 40 * MS),          # overlaps the kernel
           ("flash_kernel", 60 * MS, 70 * MS),
           ("copy", 95 * MS, 110 * MS)]
    host = [("bench.flush", 40 * MS, 58 * MS),
            ("bench.sleep", 72 * MS, 94 * MS)]
    kernels = [[op for op in ops if op[0] == "flash_kernel"]]
    return trace.Trace(window=(0, 100 * MS), devices=[ops], host=host,
                       kernels=kernels)


def test_merged_union_clips_and_joins():
    assert trace.merged([(5, 8), (0, 3), (2, 4), (9, 12)], 1, 10) == \
        [(1, 4), (5, 8), (9, 10)]


def test_busy_is_the_union_inside_the_window():
    tr = synthetic()
    # [0,5] + [10,40] + [60,70] + [95,100] = 5 + 30 + 10 + 5 ms
    assert trace.busy_s(tr) == pytest.approx(0.050)
    assert tr.window_s == pytest.approx(0.100)


def test_busy_averages_over_devices():
    tr = synthetic()
    tr.devices.append([("fusion.9", 0, 10 * MS)])
    assert trace.busy_s(tr) == pytest.approx((0.050 + 0.010) / 2)


def test_op_seconds_by_name_inside_the_window():
    every = trace.op_seconds(synthetic())
    assert every["flash_kernel"] == pytest.approx(0.030)
    assert every["fusion.1"] == pytest.approx(0.005)   # clipped at 0
    assert every["copy"] == pytest.approx(0.005)       # clipped at 100


def test_idle_gaps_by_host_activity():
    gaps = trace.idle_gaps(synthetic())
    # gaps: 5-10 (other), 40-60 (flush), 70-95 (sleep, mid 82.5)
    assert gaps == pytest.approx({"other": 0.005, "flush": 0.020,
                                  "sleep": 0.025})
    assert sum(gaps.values()) == pytest.approx(0.100 - 0.050)


def test_kernel_seconds_sum_the_pallas_ops_in_the_window():
    tr = synthetic()
    assert trace.kernel_seconds(tr) == pytest.approx(0.030)
    tr.window = (15 * MS, 65 * MS)
    assert trace.kernel_seconds(tr) == pytest.approx(0.020)


def test_a_kernel_is_known_by_its_custom_call_target():
    from types import SimpleNamespace as E
    pallas = E(name="_lambda_.1",
               stats=[("hlo_op", "_lambda_.1"),
                      ("long_name", '%_lambda_.1 = custom-call(...), '
                       'custom_call_target="tpu_custom_call"')])
    concat = E(name="custom-call",
               stats=[("long_name", '%custom-call = custom-call(...), '
                       'custom_call_target="ConcatBitcast"'), ("flops", 0)])
    assert trace._is_kernel(pallas)
    assert not trace._is_kernel(concat)


def test_a_kernel_is_known_by_the_hlo_text_of_its_name():
    # the "XLA Ops" events of a TPU v5e trace: named by the instruction's
    # whole HLO text, with no statistics that name the target
    from types import SimpleNamespace as E
    pallas = E(name='%flash_attention.5 = f32[96,256,26]{2,1,0:T(8,128)} '
               'custom-call(s32[8]{0} %convert_reduce_fusion), '
               'custom_call_target="tpu_custom_call", '
               'frontend_attributes={kernel_metadata={}}',
               stats=[("device_offset_ps", "313278613750")])
    fusion = E(name="%fusion.244 = (f32[8,256]{1,0:T(8,128)}) fusion()",
               stats=[("device_offset_ps", "113589367500")])
    assert trace._is_kernel(pallas)
    assert not trace._is_kernel(fusion)
    assert trace.op_name(pallas.name) == "flash_attention"
    assert trace.op_name(fusion.name) == "fusion"
    assert trace.op_name("copy-done") == "copy-done"
