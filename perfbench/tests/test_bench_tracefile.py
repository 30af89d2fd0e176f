"""The trace reduction on a small synthesised trace."""

import pytest

from perfbench import tracefile

KERNEL = ("%toast_kernel__flash_attention__causal_1.1 = "
          "bf16[2,8,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call("
          "bf16[2,8,1024,64]{3,2,1,0} %bitcast.45), "
          'custom_call_target="tpu_custom_call"')
FUSION = ("%fusion.11 = (f32[]{:T(128)}, bf16[2048,4096]{1,0:T(8,128)(2,1)"
          "S(1)}) fusion(bf16[2048,512]{0,1} %bitcast.48), kind=kOutput")
WHILE = "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.1)"
ALLRED = "%all-reduce.7 = f32[1024]{0} all-reduce(f32[1024]{0} %p), to_apply=%add"
AG_START = ("%all-gather-start.2 = (f32[8]{0}, f32[32]{0}) "
            "all-gather-start(f32[8]{0} %p)")


def test_instruction_parses_name_opcode_and_shape():
    assert tracefile.instruction(KERNEL) == (
        "toast_kernel__flash_attention__causal_1", "custom-call",
        "bf16[2,8,1024,64]")
    assert tracefile.instruction(FUSION)[:2] == ("fusion", "fusion")
    assert tracefile.instruction(FUSION)[2] == "f32[]"
    assert tracefile.instruction(WHILE)[1] == "while"
    name, op, _ = tracefile.instruction(ALLRED)
    assert tracefile.is_collective(name, op)
    name, op, _ = tracefile.instruction(AG_START)
    assert op == "all-gather-start" and tracefile.is_collective(name, op)


def test_interval_helpers():
    u = tracefile.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert tracefile.length(u) == 6
    assert tracefile.subtract([(0, 10)], [(2, 3), (5, 6)]) == \
        [(0, 2), (3, 5), (6, 10)]
    assert tracefile.subtract([(0, 4)], [(0, 4)]) == []
    assert tracefile.clip([(0, 5), (8, 12)], 2, 10) == [(2, 5), (8, 10)]


def trace():
    ms = 1_000_000
    # device 0: a while loop spanning its body (not work), a kernel, a
    # fusion, a collective half hidden behind the fusion, and a 3 ms gap
    dev0 = {"ops": [(0 * ms, 9 * ms, WHILE),
                    (0 * ms, 2 * ms, KERNEL),
                    (2 * ms, 4 * ms, FUSION),
                    (3 * ms, 5 * ms, ALLRED),
                    (8 * ms, 10 * ms, FUSION)],
            "async": []}
    # device 1: an async all-gather in flight with nothing else running
    dev1 = {"ops": [(0 * ms, 2 * ms, KERNEL), (2 * ms, 2 * ms, AG_START)],
            "async": [(2 * ms, 6 * ms, AG_START)]}
    host = [(0, 10 * ms, "train"),
            (0, 1 * ms, "next_batch"), (1 * ms, 2 * ms, "dispatch"),
            (2 * ms, 10 * ms, "block"), (5 * ms, 6 * ms, "python noise")]
    return {"/device:TPU:0": dev0, "/device:TPU:1": dev1}, host


def test_reduce_trace_busy_idle_kernels_collectives():
    devices, host = trace()
    r = tracefile.reduce_trace(devices, host, window_span="train",
                               host_spans=("next_batch", "dispatch",
                                           "block"))
    assert r["window_s"] == pytest.approx(0.010)
    assert r["devices"] == 2 and r["steps"] == 1
    # dev0 busy: 0-5 (kernel, fusion, all-reduce) and 8-10
    assert r["busy_s"][0] == pytest.approx(0.007)
    # dev1 busy: kernel 0-2, all-gather in flight 2-6
    assert r["busy_s"][1] == pytest.approx(0.006)
    # exposed: dev0 4-5 ms, dev1 2-6 ms
    assert r["collective_exposed_s"] == pytest.approx([0.001, 0.004])
    assert r["collective_s"] == pytest.approx([0.002, 0.004])
    kern = r["custom_calls"]["toast_kernel__flash_attention__causal_1"]
    assert [s for s, _ in kern] == ["bf16[2,8,1024,64]"] * 2
    assert sum(d for _, d in kern) == pytest.approx(0.004)
    # the 5-8 ms gap of device 0 lies inside "block"
    assert r["breakdown"]["idle_gaps"][0] == ["block",
                                               pytest.approx(0.003)]
    ops = dict(r["breakdown"]["device_ops"])
    # per instruction, averaged over the two devices
    assert ops["fusion.11 f32[]"] == pytest.approx(0.002)
    assert ops["toast_kernel__flash_attention__causal_1.1 "
               "bf16[2,8,1024,64]"] == pytest.approx(0.002)
    assert len(r["breakdown"]["device_ops"]) <= 10


def test_reduce_trace_needs_step_spans():
    devices, _ = trace()
    with pytest.raises(ValueError):
        tracefile.reduce_trace(devices, [], window_span="train",
                               host_spans=())
