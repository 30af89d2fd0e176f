"""Device time by named scope and idle time under program spans, on a
small synthesised trace."""

import pytest

from perfbench import scopes

MS = 1_000_000
KERNEL = ("%toast_kernel__flash_attention__causal_1.16 = "
          "bf16[8,14,1024,64]{3,2,1,0} custom-call(%a, %b, %c), "
          'custom_call_target="tpu_custom_call"')
MLP = "%fusion.600 = bf16[8,1024,4864]{2,1,0} fusion(%p), kind=kOutput"
BWD = "%fusion.569 = bf16[8,14,1024,1024]{3,2,1,0} fusion(%p), kind=kLoop"
HEAD = "%fusion.376 = f32[8,1024,151936]{2,1,0} fusion(%p), kind=kLoop"
OPT = "%fusion.210 = f32[] fusion(%p), kind=kLoop"
LOOSE = "%copy.3 = f32[8]{0} copy(%p)"
NONAME = "%bitcast.9 = f32[8]{0} bitcast(%p)"
WHILE = "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.1)"

HLO = """\
ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %toast_kernel__flash_attention__causal_1.16 = bf16[8,14,1024,64]{3,2,1,0} custom-call(%a, %b, %c), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/jit(toast_kernel__flash_attention__causal=1)/toast_kernel__flash_attention__causal_1/pallas_call"}
  %fusion.600 = bf16[8,1024,4864]{2,1,0} fusion(%p), kind=kOutput, calls=%f, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mlp/dot_general" stack_frame_id=3}
  %fusion.569 = bf16[8,14,1024,1024]{3,2,1,0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/jit(toast_kernel__flash_attention_bwd__causal=1)/attn_bwd/jvp()/exp"}
  %fusion.376 = f32[8,1024,151936]{2,1,0} fusion(%p), kind=kLoop, calls=%h, metadata={op_name="jit(train_step)/transpose(jvp(head_loss))/mul"}
  ROOT %fusion.210 = f32[] fusion(%p), kind=kLoop, calls=%i, metadata={op_name="jit(train_step)/optimizer/reduce_sum"}
  %copy.3 = f32[8]{0} copy(%p), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/mlp_like/copy"}
  %bitcast.9 = f32[8]{0} bitcast(%p)
}
"""


def test_op_names_reads_each_instruction_of_the_module():
    names = scopes.op_names(HLO)
    assert names["fusion.210"] == "jit(train_step)/optimizer/reduce_sum"
    assert "attn_bwd" in names["fusion.569"]
    assert "bitcast.9" not in names and len(names) == 6


@pytest.mark.parametrize("op_name, scope", [
    ("jit(f)/transpose(jvp(mlp))/dot_general", "mlp"),
    ("ckpt/rematted_computation/mlp/reduce_sum", "mlp"),
    ("jit(f)/jit(toast_kernel__flash_attention_bwd__causal=1)/attn_bwd/x",
     "attn_bwd"),
    # merged paths go to the first scope of SCOPES among them
    ("jit(f)/transpose(jvp(head_loss))/mul;jit(f)/transpose(jvp(mlp))/mul",
     "mlp"),
    ("jit(f)/optimizer", "optimizer"),
    ("jit(f)/jvp(mlp_like)/copy", None),
    ("jit(mlp_apply)/dot_general", None),
])
def test_scope_of_takes_whole_parts_of_the_path(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def trace():
    """Two devices over a 20 ms window; device 1 runs the same ops half
    as long."""
    def ops(k):
        return {"ops": [(0, 20 * MS, WHILE),
                        (0, 4 * k * MS, KERNEL),
                        (4 * MS, (4 + 3 * k) * MS, MLP),
                        (8 * MS, (8 + 2 * k) * MS, BWD),
                        (10 * MS, (10 + 2 * k) * MS, HEAD),
                        (12 * MS, (12 + 1 * k) * MS, OPT),
                        (14 * MS, (14 + 1 * k) * MS, LOOSE),
                        (16 * MS, (16 + 1 * k) * MS, NONAME)],
                "async": []}
    devices = {"/device:TPU:0": ops(1.0), "/device:TPU:1": ops(0.5)}
    host = [(0, 10 * MS, "train"), (10 * MS, 20 * MS, "train"),
            (2 * MS, 8 * MS, "next_batch"),
            (3 * MS, 7 * MS, "toast.data.wait"),
            (18 * MS, 25 * MS, "toast.data.wait"),
            (0, 30 * MS, "toast.data.make")]
    return devices, host


def test_reduce_scopes_sorts_device_time_by_scope():
    devices, host = trace()
    r = scopes.reduce_scopes(devices, host, scopes.op_names(HLO),
                             window_span="train")
    assert r["window_s"] == pytest.approx(0.020) and r["devices"] == 2
    # averaged over the devices: (x + x/2) / 2 = 0.75 x
    assert r["kernel_s"] == pytest.approx(0.75 * 0.004)
    assert r["scopes"] == pytest.approx({
        "attn_bwd": 0.75 * 0.002, "mlp": 0.75 * 0.003,
        "head_loss": 0.75 * 0.002, "optimizer": 0.75 * 0.001})
    # the copy outside every scope and the bitcast with no op_name
    assert r["unscoped_s"] == pytest.approx(0.75 * 0.002)
    assert r["unnamed_s"] == pytest.approx(0.75 * 0.001)
    assert r["busy_s"] == pytest.approx(0.75 * 0.014)


def test_idle_under_spans_and_shares_account_for_the_window():
    devices, host = trace()
    idle = scopes.idle_under_spans(devices, host, window_span="train")
    assert set(idle) == {"toast.data.wait", "toast.data.make"}
    # device 0 is idle 7-8, 13-14, 15-16 and 17-20 ms, of which the
    # wait spans (3-7, 18-25) cover 18-20; device 1 is idle 2-4, 5.5-8,
    # 9-10, 11-12, 12.5-14, 14.5-16 and 16.5-20, of which they cover
    # 3-4, 5.5-7 and 18-20
    assert idle["toast.data.wait"] == pytest.approx(
        (0.002 + 0.001 + 0.0015 + 0.002) / 2)
    assert idle["toast.data.make"] == pytest.approx((0.006 + 0.013) / 2)
    sh = scopes.shares(scopes.reduce_scopes(
        devices, host, scopes.op_names(HLO), window_span="train"), idle)
    assert sh["accounted"] == pytest.approx(100.0)
    assert sh["input_wait_share"] == pytest.approx(
        100.0 * idle["toast.data.wait"] / 0.020)
    assert sh["idle_share"] == pytest.approx(100.0 * (1 - 0.75 * 0.014 /
                                                      0.020))


def test_clock_check_reads_the_least_lead_and_lag():
    devices = {"/device:TPU:0": {"ops": [(5 * MS, 8 * MS, MLP),
                                         (12 * MS, 15 * MS, MLP)],
                                 "async": []}}
    host = [(4 * MS, 5 * MS, "dispatch"), (11 * MS, 11 * MS, "dispatch"),
            (5 * MS, 9 * MS, "block"), (12 * MS, 17 * MS, "block")]
    c = scopes.clock_check(devices, host, start_span="dispatch",
                           end_span="block")
    assert c == {"lead_s": pytest.approx(0.001),
                 "lag_s": pytest.approx(0.001)}


def test_reductions_need_step_spans():
    devices, _ = trace()
    with pytest.raises(ValueError):
        scopes.reduce_scopes(devices, [], {}, window_span="train")
