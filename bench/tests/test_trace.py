"""The trace reduction on a small synthetic event list."""
import pytest

from bench import costs, trace

KERNEL = ("%delta_apply_chain_batched_pallas.1 = u32[2,8,128]{2,1,0} "
          "custom-call(u32[2,8,128]{2,1,0} %a, u32[2,4,8,128]{3,2,1,0} %b, "
          "u32[2,4,8,128]{3,2,1,0} %c), custom_call_target=\"tpu_custom_call\", "
          "operand_layout_constraints={u32[2,8,128]{2,1,0}}")


def test_bytes_come_from_the_history_sizes_not_the_shapes():
    # 2 chains of K = 4: base, 4 adds, 4 dels, landed; the planes hold
    # 8 * 128 words, at least the 30,000 edge slots' 938 words: an edge call
    plane = 4 * -(-30_000 // 32)
    assert costs.delta_apply_bytes(KERNEL, 500, 30_000) == 2 * 10 * plane
    # the node plane of 500 slots is narrower than the call's planes
    assert costs.delta_apply_bytes(KERNEL, 500, 40_000) == 2 * 10 * 4 * 16
    assert costs.segment_sum_bytes(KERNEL, 500, 30_000) == 4 * 60_500


def test_reduction_busy_idle_kernels_and_breakdown():
    ms = 1_000_000
    device = {"/device:TPU:0": [
        # a program [10, 14) ms holding the kernel call [12, 13) ms
        ("XLA Modules", "jit_chain(123)", 10 * ms, 4 * ms),
        ("XLA Ops", KERNEL, 12 * ms, 1 * ms),
        # an op overlapping the same program, then one past the window
        ("XLA Ops", "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)",
         13 * ms, 2 * ms),
        ("XLA Modules", "jit_late(9)", 95 * ms, 10 * ms),
    ]}
    host = [("loader.window", 0, 100 * ms),
            ("loader.next", 0, 50 * ms),
            ("consumer.step", 50 * ms, 50 * ms)]
    r = trace.reduce_events(device, host, "loader.window")
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [10, 15) and [95, 100) ms
    assert r["busy_s"] == pytest.approx(0.010)
    k = r["kernels"]["delta_apply_chain_batched_pallas"]
    assert k["calls"] == 1
    assert k["shapes"] == {KERNEL.split("custom_call_target=")[0]: 1}
    assert k["kernel_seconds"] == pytest.approx(0.001)
    assert k["seconds"] == pytest.approx(0.004)        # its program's span
    idle = dict(r["breakdown"]["idle_gaps"])
    assert idle["loader.next"] == pytest.approx(0.045)  # [0, 10) + [15, 50)
    assert idle["consumer.step"] == pytest.approx(0.045)  # [50, 95)
    ops = r["breakdown"]["device_ops"]
    assert ops == [["jit_late", pytest.approx(0.005)],     # clipped
                   ["jit_chain", pytest.approx(0.004)]]


def test_no_window_span_gives_nothing():
    assert trace.reduce_events({"/device:TPU:0": []}, [],
                               "loader.window") is None


def test_union_and_gaps():
    ivs = [(0, 2), (1, 3), (5, 6)]
    assert trace.union_length(ivs) == 4
    assert trace.gaps(ivs, 0, 8) == [(3, 5), (6, 8)]
