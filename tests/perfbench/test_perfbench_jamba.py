"""The hybrid state-space decoder's cell: its CPU rehearsal through
run.py's own ``execute``, the comparison that decides ``correct`` with its
controls, the kernel-cost functions against hand counts at the published
widths, and the ``jamba_`` readers on synthetic records."""

import json
import time

import pytest

import _perfbench_tiny as tiny
from _perfbench_jamba_tiny import tiny_cell

from perfbench import harness, kernel_costs_jamba as costs
from perfbench import metric_lib_jamba as lib

CELL = "serve_jamba_saturated"


@pytest.fixture
def rehearse(tmp_path, monkeypatch):
    monkeypatch.setattr(tiny, "tiny_cell",
                        lambda name, root=tiny.ROOT: tiny_cell(name, root))
    return lambda trace: tiny.rehearse(CELL, tmp_path, trace=trace)


@pytest.mark.parametrize("trace", [0, 1])
def test_jamba_cell_rehearsal(trace, rehearse, capsys):
    cell = rehearse(trace)
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    tiny.check_line(line, cell, trace)
    text = "\n".join(out[:-1])
    for key in ("logit_rel_l2", "state_rel_l2", "state_slow_rel_l2"):
        assert "check %s" % key in text
    assert "check pool conserved after the run: True" in text
    # the compared prompts were prefilled in dispatches they shared, and
    # decoded with every slot live
    assert "check: 12 slots live" in text
    beside = text.split("were prefilled beside ")[1].split(" others")[0]
    assert all(int(n) >= 1 for n in beside.split(", ")), beside
    assert "programs compiled inside the measured window: 0" in text
    for part in ("startup_init", "program_build", "reference_check",
                 "warmup_dispatches", "frontend_start", "ramp"):
        assert part in text
    if trace:
        # no device trace on the CPU: the set-up metrics, the generator's
        # lateness (the host's clock) and nothing of the device's
        assert set(line["metrics"]) == {
            "build_s", "compile_s", "cache_misses", "trace_lower_s",
            "glm_loadgen_late_p99_ms"}
    else:
        assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_controls_are_not_correct():
    """The reference in the program's place one precision down (float8
    operands, a bfloat16 state) reads far above the program on both
    numbers; with the state's precision the ONLY change the state's number
    still fails."""
    import paddle_tpu as fluid

    from perfbench import serve_jamba_common as common

    cell = tiny_cell()
    server = common.Server(cell, 3, fluid.CPUPlace(),
                           harness.Setup(time.perf_counter()))
    checker = common.Checker(cell, server)
    limits = cell.config["check"]["limits"]
    for seed in (3, 2 ** 31 + 5):
        sound = checker.numbers(seed)
        control = checker.control_numbers(seed)
        assert sound["logit_rel_l2"] < 1e-5 and sound["state_rel_l2"] < 1e-5
        assert control["logit_rel_l2"] > 1e-2
        assert control["state_rel_l2"] > 1e-2
        assert control["state_rel_l2_bf16_state_alone"] > 1e-3
        assert control["state_slow_rel_l2_bf16_state_alone"] > 1e-3
        assert common.verdict(sound, limits)
        assert not common.verdict(control, limits)
        assert not common.verdict(
            {"logit_rel_l2": 0.0, "state_rel_l2": 0.0, "state_slow_rel_l2":
             control["state_slow_rel_l2_bf16_state_alone"]}, limits)
    sess = server.session
    assert sess.pool_conserved and not sess.active_slots


def test_kernel_costs_at_the_published_widths():
    cfg = harness.Cell(CELL).config
    count = costs.parameter_count(cfg)
    # ISSUE 31's arithmetic, from the config's keys
    assert count["mamba_mixer"] == 41_241_792
    assert count["attention_mixer"] == 13_762_560
    assert count["mlp"] == 62_914_560
    assert count["embedding"] == 167_772_160
    assert round(count["mamba_layer"] / 1e6, 1) == 104.2
    assert round(count["attention_layer"] / 1e6, 1) == 76.7
    assert round(count["total"] / 1e6, 1) == 3029.3
    assert costs.layer_kinds(cfg).count("attention") == 2
    assert [i for i, k in enumerate(costs.layer_kinds(cfg))
            if k == "attention"] == [7, 21]
    # a slot: 26 x (16 x 5120 x 4 + 3 x 5120 x 2) bytes; 256 of them 2.39 GB
    assert costs.state_bytes_per_slot(cfg) == 26 * (327_680 + 30_720)
    assert round(256 * costs.state_bytes_per_slot(cfg) / 1e9, 2) == 2.39
    assert costs.cached_bytes_per_token(cfg) == 1024
    # a decode token step at 256 live slots of ~640 rows: 11.0 GB
    assert round(costs.decode_step_bytes(cfg, 256, 256 * 640) / 1e9, 1) \
        == 11.0
    assert costs.decode_step_bytes(cfg, 0, 0) == 2 * count["total"]
    ops, moved = costs.state_update(cfg, 256)
    assert ops == 6 * 256 * 16 * 5120
    assert moved == 2 * 256 * 327_680 + 256 * 5120 * 8 + 256 * 32 * 4
    ops, moved = costs.prefill_scan(cfg, [400, 300])
    assert ops == 6 * 700 * 16 * 5120
    assert moved == 700 * (5120 * 8 + 128) + 2 * 327_680
    assert costs.causal_conv(cfg, 10) == (2 * 10 * 4 * 5120, 2 * 10 * 5120 * 2)
    ops, moved = costs.gqa_decode_attention(cfg, 1000, 10)
    assert ops == 4 * 20 * 128 * 1000
    assert moved == (2 * 1000 * 128 + 2 * 10 * 20 * 128) * 2


def _records(cfg, **serve):
    return {"config": cfg, "serve": dict({"summary": {}}, **serve),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


# the per-layer entries PR 31 declared for this cell, by name: eight of
# its own and the decoder-only cells' shared ones
DECLARED = [
    "jamba_decode_dispatch_device_ms", "jamba_prefill_dispatch_device_ms",
    "jamba_decode_hbm_roofline", "jamba_state_update_roofline",
    "jamba_prefill_scan_roofline", "jamba_gqa_decode_attention_roofline",
    "jamba_ssm_time_share", "jamba_prefill_pad_share"] + tiny.DECODER_SHARED
HOST = {"step": [(0.0, 0.1, (250, 150_000)), (0.1, 0.2, (252, 151_000)),
                 (0.2, 0.3, (0, 0)), (5.0, 5.1, (256, 1))],
        "admit": [(0.05, 0.09, [(512, [400, 300]), (128, [90])]),
                  (4.0, 4.1, [(128, [1])])]}


def check_declared(bench, root):
    """Every name this family declared is there, lists this cell, and its
    reader gives no number on records without a device trace."""
    cfg = harness.Cell(CELL, root=root).config
    tiny.check_cell_declares(bench, root, CELL, DECLARED, [
        _records(cfg, host=HOST, seconds=51.0, traced_s=3.0),
        {"config": cfg}])


def test_jamba_readers_on_synthetic_records():
    cfg = harness.Cell(CELL).config
    step_ops = [lib.UPDATE_KERNEL, lib.CONV_STEP_KERNEL, lib.GQA_KERNEL]
    runs = [{"name": "jit_multi", "start_s": 0.1 * i, "seconds": 0.08,
             "ops": {k: 0.01 for k in step_ops}} for i in range(3)]
    runs.append({"name": "jit_prefill", "start_s": 0.5, "seconds": 0.09,
                 "ops": {lib.SCAN_KERNEL: 0.03, lib.CONV_KERNEL: 0.002,
                         "flash_attention_fwd": 0.001}})
    trace = {"window_s": 1.0, "busy_s": 0.6, "modules": runs, "ops": [
        ["%ssm_state_update.3 = (bf16[256,5120]{1,0}, f32[256,16,5120]"
         "{2,1,0}) custom-call(...)", 0.09, 312],
        ["%ssm_conv_step.7 = (bf16[256,5120]{1,0}, bf16[3,256,5120]) "
         "custom-call(", 0.012, 312],
        ["%gqa_paged_decode_attention.2 = bf16[256,32,128]{2,1,0} "
         "custom-call(", 0.008, 24],
        ["%ssm_prefill_scan.5 = (bf16[2,1024,5120], f32[2,16,5120]) "
         "custom-call(", 0.03, 26],
        ["%ssm_causal_conv.4 = bf16[2,1024,5120]{2,1,0} custom-call(",
         0.002, 26],
        ["%fusion.12 = bf16[256,8192]{1,0} fusion(", 0.3, 900]]}
    rec = _records(cfg, host=HOST, seconds=51.0, traced_s=3.0)
    rec["trace"] = trace
    assert lib.decode_dispatch_ms(rec) == pytest.approx(80.0)
    assert lib.prefill_dispatch_ms(rec) == pytest.approx(90.0)
    # 4 token steps of ~11 GB at 819 GB/s over 80 ms
    want = [sum(costs.decode_step_bytes(cfg, live, rows + live * j)
                for j in range(4)) / 819e9 / 0.08
            for live, rows in ((250, 150_000), (252, 151_000))]
    hbm = lib.decode_hbm_roofline(rec)
    assert hbm == pytest.approx(100 * sum(want) / 2) and 60 < hbm < 70
    assert lib.ssm_time_share(rec) == pytest.approx(
        100 * (0.09 + 0.012 + 0.03 + 0.002) / 0.6)
    # 26 layers x 4 tokens x 2 dispatches of ~0.2 ms at the roofline
    assert 30 < lib.state_update_roofline(rec) < 60
    assert 0 < lib.prefill_scan_roofline(rec) < 20
    assert 0 < lib.gqa_decode_attention_roofline(rec) < 100
    check_declared(harness.load_json(tiny.ROOT + "/BENCHMARK.json"),
                   tiny.ROOT)
    rounds = [{"id": i, "spans": [
        {"name": "round", "t0": 0.0, "t1": 0.1, "cpu": 0.03, "parent": None,
         "prefill_tokens": 700, "prefill_pad_tokens": 1348,
         "prefill_dispatches": 1, "state_slots_live": 250 + i}]}
        for i in range(3)]
    assert lib.prefill_pad_share(rounds) == pytest.approx(100 * 1348 / 2048)
    assert lib.state_slots_live_p50(rounds) == 251
    # a program that does not count them (the parent): no number
    old = [{"id": 0, "spans": [{"name": "round", "t0": 0.0, "t1": 0.1,
                                "cpu": 0.0, "parent": None,
                                "prefill_tokens": 700}]}]
    assert lib.prefill_pad_share(old) is None
    assert lib.state_slots_live_p50(old) is None


def test_the_cell_shares_the_latent_decoders_traffic_and_pool():
    """The two decoder-only cells differ by the model alone: one traffic
    file, one pool geometry."""
    mine, theirs = harness.Cell(CELL), harness.Cell("serve_glm_saturated")
    assert mine.spec["traffic"] == theirs.spec["traffic"] == "closed_320_chat"
    assert mine.config["pool"] == theirs.config["pool"]
    assert mine.config["check"]["prompt_len_ranges"] \
        == theirs.config["check"]["prompt_len_ranges"]
    assert mine.config["reduced"] == [] and mine.config["published"] == {}


def test_the_configuration_states_every_published_key():
    """Every key of the catalog row's ``config`` under its own name and
    value; nothing reduced; the check's three limits."""
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    cfg = harness.Cell(CELL).config
    assert {k: cfg[k] for k in published} == published
    assert cfg["entry"] == "hybrid_frontend" and cfg["dtype"] == "bfloat16"
    assert set(cfg["check"]["limits"]) == {
        "logit_rel_l2", "state_rel_l2", "state_slow_rel_l2"}
    for key in ("layer_order", "head_dim", "positions", "state_dtype",
                "a_log_dt_bias", "end_of_stream", "max_position_embeddings"):
        assert cfg["assumed"][key]
