"""The reduction from a profiler trace to the numbers the per-layer
metrics read, on the small trace recorded on a v5e and kept beside it;
the interval arithmetic on hand-made traces; ``kernel_costs`` against
hand counts."""

import json
import os

import pytest

from _perfbench_tiny import ROOT

from perfbench import kernel_costs, trace_reduce as tr


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(ROOT, "perfbench", "recorded_trace.json")) as f:
        return tr.reduce(json.load(f))


def test_recorded_trace_window_and_busy(recorded):
    assert recorded["chips"] == 1
    assert recorded["window_s"] == pytest.approx(0.008527038, rel=1e-6)
    assert recorded["busy_s"] == pytest.approx(0.00468785, rel=1e-6)


def test_recorded_trace_kernels_by_name_and_shape(recorded):
    secs, calls = tr.kernel_time(recorded, "paged_decode_attention")
    assert calls == 24 and secs == pytest.approx(0.000365092, rel=1e-6)
    # 6 layers x 4 tokens at query length 1, and 6 encoder calls at 256
    q1 = tr.kernel_time(recorded, "flash_attention_fwd",
                        lambda dtype, dims: dims[2] == 1)
    every = tr.kernel_time(recorded, "flash_attention_fwd")
    assert q1[1] == 24 and every[1] == 30
    assert q1[0] == pytest.approx(0.001679725, rel=1e-6)


def test_recorded_trace_modules_hold_their_kernels(recorded):
    runs = [m for m in recorded["modules"] if m["seconds"] > 1e-4]
    assert [m["name"] for m in runs] == ["jit_split_step", "jit_multi"]
    admit, step = runs
    assert "paged_decode_attention" in step["ops"]
    assert "paged_decode_attention" not in admit["ops"]
    assert step["seconds"] == pytest.approx(0.004276831, rel=1e-6)
    assert "while" not in step["ops"]  # the scan's wrapper is not an op


def test_recorded_trace_idle_gaps_go_to_host_spans(recorded):
    gaps = dict(recorded["idle_gaps"])
    assert gaps["step"] == pytest.approx(0.003838237, rel=1e-4)
    assert gaps["step"] > 100 * gaps.get("unattributed", 0.0)
    assert recorded["device_ops"][0][0].startswith(
        "flash_attention_fwd.45_f32_8_8_1_64")


def _flat(ops, modules=(), host=(), chips=1):
    dev = {"modules": [list(m) for m in modules],
           "ops": [list(o) for o in ops]}
    return {"devices": {str(c): dev for c in range(chips)},
            "host": [list(h) for h in host]}


def test_busy_is_a_union_and_a_while_only_wraps():
    flat = _flat(ops=[("%while.1 = ...", 0, 1000),
                      ("%fusion.1 = f32[4]{0} fusion()", 100, 200),
                      ("%fusion.2 = f32[4]{0} fusion()", 250, 100),
                      ("%copy.3 = f32[4]{0} copy()", 900, 100)])
    out = tr.reduce(flat)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx(350e-9)  # 100-350 merged, 900-1000
    assert [r[0] for r in out["device_ops"]] == [
        "fusion.1_f32_4", "fusion.2_f32_4", "copy.3_f32_4"]


def test_busy_is_the_mean_over_chips():
    flat = _flat(ops=[("%fusion.1 = f32[8]{0} fusion()", 0, 100),
                      ("%fusion.1 = f32[8]{0} fusion()", 60, 100)],
                 chips=4)
    out = tr.reduce(flat)
    assert out["chips"] == 4
    assert out["window_s"] == pytest.approx(160e-9)
    assert out["busy_s"] == pytest.approx(160e-9)
    # per operation: summed over the chips and averaged, calls counted
    assert out["ops"][0][1:] == [pytest.approx(200e-9), 8]


def test_idle_gap_without_a_span_is_unattributed():
    flat = _flat(ops=[("%a.1 = f32[1]{0} add()", 0, 10),
                      ("%a.2 = f32[1]{0} add()", 110, 10),
                      ("%a.3 = f32[1]{0} add()", 220, 10)],
                 host=[("pb:admit", 5, 100)])
    gaps = dict(tr.reduce(flat)["idle_gaps"])
    assert gaps == {"admit": pytest.approx(100e-9),
                    "unattributed": pytest.approx(100e-9)}


def test_op_names_and_shapes():
    text = ("%flash_attention_fwd.45 = (f32[256,8,1,64]{3,2,1,0:T(1,128)}, "
            "f32[256,8,1,1]{3,2,1,0}) custom-call(")
    assert tr.op_name(text) == "flash_attention_fwd"
    assert tr.first_shape(text) == ("f32", (256, 8, 1, 64))
    assert tr.op_name("%all-reduce-start.3 = f32[2]{0} x") == \
        "all-reduce-start"
    assert tr.first_shape("%x = s32[]{:T(128)} y") == ("s32", ())


def test_kernel_costs_against_hand_counts():
    # one head, 2 queries over 3 keys of width 4, 2-byte operands:
    # QK^T 2*2*3*4 = 48, PV 48 -> 96 operations; Q,O 2x(2*4) + K,V 2x(3*4)
    # = 40 elements = 80 bytes
    assert kernel_costs.flash_attention_fwd(1, 1, 2, 3, 4, 2) == (96.0, 80)
    # causal, 4 queries over 4 keys: 4*5/2 = 10 pairs of 16
    assert kernel_costs.flash_attention_fwd(1, 1, 4, 4, 4, 2,
                                            causal=True)[0] == 160.0
    # decode: 2 queries over 10 context tokens in total, 8 heads of 64,
    # float32: 4*8*64*10 operations; (2*10 + 2*2) * 8*64*4 bytes
    assert kernel_costs.decode_attention(10, 2, 8, 64, 4) == (
        20480.0, 24 * 8 * 64 * 4)
    share, bound = kernel_costs.roofline_share(
        1e6, 819e3, 2e-6, {"bf16_flops_per_s": 197e12,
                           "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and share == pytest.approx(50.0)


def test_train_step_operations_by_hand():
    cfg = dict(n_layer=1, d_model=4, d_inner=8, max_length=2,
               trg_vocab_size=10)
    enc = 4 * 16 + 2 * 32          # 128 parameters a source token passes
    dec = 8 * 16 + 2 * 32 + 40     # 232 a target token passes
    dense = 2 * 3 * (enc * 2 + dec * 2)
    attn = 3 * (4 * 2 * 2 * 4 + 2 * 2 * 3 * 4 + 4 * 2 * 2 * 4)
    assert kernel_costs.transformer_train_step(cfg, 3) == 3.0 * (dense
                                                                 + attn)


def test_unknown_device_kind_is_an_error():
    from perfbench import harness

    peaks = harness.load_json(os.path.join(ROOT, "perfbench", "peaks.json"))
    assert harness.peak_for(peaks, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peak_for(peaks, "TPU v9 imaginary")
