"""The window/full-attention decoder's cell: its CPU rehearsal through
run.py's own ``execute``, the comparison that decides ``correct`` with its
two controls, the kernel-cost functions against hand counts at the
published widths, and the ``trinity_`` readers on hand-made records."""

import json
import time

import pytest

import _perfbench_tiny as tiny
from _perfbench_trinity_tiny import tiny_cell

from perfbench import harness, kernel_costs_trinity as costs
from perfbench import metric_lib_trinity as lib

CELL = "serve_trinity_longctx"
LIMITS = ("logit_rel_l2", "expert_choice_diff_share",
          "expert_choice_margin_max")


@pytest.fixture
def rehearse(tmp_path, monkeypatch):
    monkeypatch.setattr(tiny, "tiny_cell",
                        lambda name, root=tiny.ROOT: tiny_cell(name, root))
    return lambda trace: tiny.rehearse(CELL, tmp_path, trace=trace)


@pytest.mark.parametrize("trace", [0, 1])
def test_trinity_cell_rehearsal(trace, rehearse, capsys):
    cell = rehearse(trace)
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    tiny.check_line(line, cell, trace)
    text = "\n".join(out[:-1])
    for key in LIMITS:
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


def test_both_controls_are_not_correct():
    """The reference in the program's place with float8 operands reads
    far above the program; with the window's band left out the prompt
    longer than the window fails on its own, and the short one's part of
    the same reading is exact."""
    import paddle_tpu as fluid

    from perfbench import serve_trinity_common as common

    cell = tiny_cell()
    server = common.Server(cell, 3, fluid.CPUPlace(),
                           harness.Setup(time.perf_counter()))
    checker = common.Checker(cell, server)
    limits = cell.config["check"]["limits"]
    for seed in (3, 2 ** 31 + 5):
        sound = checker.numbers(seed)
        control = checker.control_numbers(seed)
        assert sound["logit_rel_l2"] < 2e-5
        assert sound["expert_choice_diff_share"] == 0.0
        assert control["logit_rel_l2"] > 1e-2
        assert control["logit_rel_l2_no_band_long_prompt"] > 1e-2
        assert control["logit_rel_l2_no_band"] > 1e-2
        assert common.verdict(sound, limits)
        assert not common.verdict(control, limits)
        assert not common.verdict(
            {k: control[k + "_no_band_long_prompt"] for k in LIMITS}, limits)
    sess = server.session
    assert sess.pool_conserved and not sess.active_slots
    assert sess.pages_in_use_by_kind == [0, 0]


def test_a_decode_dispatchs_record_says_what_the_window_saw():
    import paddle_tpu as fluid

    from perfbench import serve_trinity_common as common

    cell = tiny_cell()
    server = common.Server(cell, 3, fluid.CPUPlace(),
                           harness.Setup(time.perf_counter()))
    server.instrument()
    sess = server.session
    assert sess.admit_token_budget is None       # the check fills at once
    server.start(16)
    try:
        assert sess.admit_token_budget == cell.config["pool"][
            "admit_token_budget"]
    finally:
        server.close()
    for n in (30, 5):
        sess.enqueue(list(range(3, 3 + n)))
    sess.admit_pending()
    sess.step()
    (_t0, _t1, (live, rows, seen)), = server.host["step"]
    assert (live, rows, seen) == (2, 31 + 6, 8 + 6)
    assert server.host["admit"][0][2] == [(8, [5]), (32, [30])]
    for slot in sess.active_slots:
        sess.cancel(slot)


def test_kernel_costs_at_the_published_widths():
    cfg = harness.Cell(CELL).config
    count = costs.parameter_count(cfg)
    attn = costs.attention_parameters(cfg)
    # ISSUE 33's arithmetic, from the config's keys
    assert [round(attn[k] / 1e6, 2) for k in ("q", "k", "v", "o", "gate")] \
        == [8.39, 1.05, 1.05, 8.39, 8.39]
    assert round(count["attention_a_layer"] / 1e6, 2) == 27.26
    assert round(count["dense_layer"] / 1e6, 1) == 65.0
    assert round(count["expert_layer"] / 1e6, 1) == 839.1
    assert count["routed_experts_a_layer"] == 128 * 3 * 2048 * 1024
    assert round(2 * count["embedding"] / 1e6, 1) == 820.0
    assert round(count["total"] / 1e6, 1) == 4241.5
    assert round(2 * count["total"] / 1e9, 2) == 8.48
    assert costs.row_bytes(cfg) == 2 * 4 * 128 * 2
    # a decode token step: 7.66 GB of weights; 96 slots of ~2776 + 94
    # rows: 0.28 GB in the full layer, 4 x 0.20 GB behind the windows
    assert round(costs.decode_step_bytes(cfg, 0, 0) / 1e9, 2) == 7.66
    assert costs.decode_step_bytes(cfg, 1000, 600) \
        - costs.decode_step_bytes(cfg, 0, 0) == (1000 + 4 * 600) * 2048
    ops, moved = costs.expert_matmuls(cfg, 768)
    assert ops == 2 * 768 * 3 * 2048 * 1024
    assert moved == (128 * 3 * 2048 * 1024 + 2 * 768 * 2048) * 2
    assert costs.expert_matmuls(cfg, 16)[1] \
        == (16 * 3 * 2048 * 1024 + 2 * 16 * 2048) * 2
    ops, moved = costs.decode_attention(cfg, 1000, 10)
    assert ops == 4 * 32 * 128 * 1000
    assert moved == 1000 * 2048 + 2 * 10 * 32 * 128 * 2
    assert costs.visible_pairs(100) == 5050
    assert costs.visible_pairs(100, 2048) == 5050
    assert costs.visible_pairs(3000, 2048) == 2048 * 2049 / 2 + 952 * 2048
    ops, moved = costs.prefill_attention(cfg, [3000, 100])
    assert ops == 4 * 32 * 128 * (
        3000 * 3001 / 2 + 5050 + 4 * (costs.visible_pairs(3000, 2048)
                                      + 5050))
    assert moved == 5 * 3100 * 2 * 36 * 128 * 2


def _records(cfg, **serve):
    return {"config": cfg, "serve": dict({"summary": {}}, **serve),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


# the per-layer entries PR 33 declared for this cell, by name: eleven of
# its own and the decoder-only cells' shared ones
DECLARED = [
    "trinity_decode_dispatch_device_ms", "trinity_prefill_dispatch_device_ms",
    "trinity_decode_hbm_roofline", "trinity_window_decode_attention_roofline",
    "trinity_full_decode_attention_roofline",
    "trinity_prefill_attention_roofline", "trinity_expert_matmul_roofline",
    "trinity_expert_time_share", "trinity_attention_time_share",
    "trinity_window_rows_share", "trinity_prefill_pad_share"] \
    + tiny.DECODER_SHARED
HOST = {"step": [(0.0, 0.1, (90, 250_000, 140_000)),
                 (0.1, 0.2, (92, 260_000, 150_000)),
                 (0.2, 0.3, (0, 0, 0)), (5.0, 5.1, (96, 1, 1))],
        "admit": [(0.05, 0.09, [(4096, [3000, 2500]), (256, [200])]),
                  (4.0, 4.1, [(256, [1])])]}


def check_declared(bench, root):
    """Every name this family declared is there, lists this cell, and its
    reader gives no number on records without a device trace."""
    cfg = harness.Cell(CELL, root=root).config
    tiny.check_cell_declares(bench, root, CELL, DECLARED, [
        _records(cfg, host=HOST, seconds=51.0, traced_s=3.0),
        {"config": cfg}])


def test_trinity_readers_on_hand_made_records():
    cfg = harness.Cell(CELL).config
    step_ops = [lib.WINDOW_KERNEL, lib.FULL_KERNEL, lib.EXPERT_KERNEL]
    runs = [{"name": "jit_multi", "start_s": 0.1 * i, "seconds": 0.06,
             "ops": {k: 0.01 for k in step_ops}} for i in range(3)]
    runs.append({"name": "jit_prefill", "start_s": 0.5, "seconds": 0.3,
                 "ops": {lib.PREFILL_KERNEL: 0.03, lib.EXPERT_KERNEL: 0.1}})
    trace = {"window_s": 1.0, "busy_s": 0.6, "modules": runs, "ops": [
        ["%gqa_window_decode_attention.3 = bf16[96,64,128]{2,1,0} "
         "custom-call(...)", 0.030, 96],
        ["%gqa_paged_decode_attention.2 = bf16[96,64,128]{2,1,0} "
         "custom-call(", 0.020, 24],
        ["%flash_attention_fwd.5 = bf16[1,32,8192,128] custom-call(",
         0.03, 5],
        ["%gmm.4 = f32[768,1024]{1,0} custom-call(", 0.2, 300],
        ["%sort.9 = (s32[768]) sort(", 0.01, 100],
        ["%fusion.12 = bf16[96,6144]{1,0} fusion(", 0.3, 900]]}
    rec = _records(cfg, host=HOST, seconds=51.0, traced_s=3.0)
    rec["trace"] = trace
    assert lib.decode_dispatch_ms(rec) == pytest.approx(60.0)
    assert lib.prefill_dispatch_ms(rec) == pytest.approx(300.0)
    # 4 token steps of ~8.5 GB at 819 GB/s over 60 ms
    want = [sum(costs.decode_step_bytes(cfg, rows + live * j, seen)
                for j in range(4)) / 819e9 / 0.06
            for _t0, _t1, (live, rows, seen) in HOST["step"][:2]]
    hbm = lib.decode_hbm_roofline(rec)
    assert hbm == pytest.approx(100 * sum(want) / 2) and 60 < hbm < 80
    # 4 window layers x 4 steps x 2 dispatches of ~0.29 GB at 819 GB/s
    window = lib.window_decode_attention_roofline(rec)
    assert window == pytest.approx(
        100 * 4 * 4 * (140_000 + 150_000) * 2048 / 819e9 / 0.030, rel=0.01)
    full = lib.full_decode_attention_roofline(rec)
    assert full == pytest.approx(
        100 * sum((rows + live * j) * 2048 for _t0, _t1, (live, rows, _s)
                  in HOST["step"][:2] for j in range(4)) / 819e9 / 0.020,
        rel=0.01)
    assert 0 < lib.prefill_attention_roofline(rec) < 100
    assert 0 < lib.expert_matmul_roofline(rec) < 100
    assert lib.expert_time_share(rec) == pytest.approx(100 * 0.21 / 0.6)
    assert lib.attention_time_share(rec) == pytest.approx(100 * 0.08 / 0.6)
    check_declared(harness.load_json(tiny.ROOT + "/BENCHMARK.json"),
                   tiny.ROOT)
    rounds = [{"id": i, "spans": [
        {"name": "round", "t0": 0.0, "t1": 0.1, "cpu": 0.03, "parent": None,
         "prefill_tokens": 5700, "prefill_pad_tokens": 2492,
         "full_rows_visible": 250_000 + i, "window_rows_visible": 140_000,
         "full_pages_in_use": 2000, "window_pages_in_use": 1500,
         "window_pages_released": 3}]} for i in range(3)]
    assert lib.window_rows_share(rounds) == pytest.approx(
        100 * 420_000 / 750_003)
    # a program that does not count them (the parent): no number
    old = [{"id": 0, "spans": [{"name": "round", "t0": 0.0, "t1": 0.1,
                                "cpu": 0.0, "parent": None,
                                "prefill_tokens": 700}]}]
    assert lib.window_rows_share(old) is None


def test_the_traffic_is_the_issues():
    cell = harness.Cell(CELL)
    t = cell.traffic
    assert cell.spec["traffic"] == "closed_120_longctx" and cell.chips == 1
    assert (t["loop"], t["clients"], t["stagger_s"]) == ("closed", 120, 2.0)
    assert t["src_len"] == {"dist": "lognormal", "median": 2048,
                            "sigma": 0.9, "min": 128, "max": 8192}
    assert t["trg_len"] == {"dist": "lognormal", "median": 160,
                            "sigma": 0.6, "min": 16, "max": 512}
    assert (t["ramp_s"], t["drain_s"], t["trace_s"]) == (8.0, 45.0, 3.0)
    theirs = harness.Cell("serve_glm_saturated").traffic
    assert set(t) == set(theirs)
    # the plan: four requests a caller, mean prompt ~2776, half of them
    # and ~80% of their tokens beyond the window
    from perfbench import loadgen

    plan = loadgen.make_plan(t, 2 ** 31 + 7, 51.0)
    src = plan["src_len"]
    assert len(src) == 480 and src.min() >= 128 and src.max() == 8192
    assert 2600 < src.mean() < 2950
    assert 0.45 < (src > 2048).mean() < 0.55
    assert 0.75 < src[src > 2048].sum() / float(src.sum()) < 0.85
    assert 0.04 < (src == 8192).mean() < 0.08
    assert 170 < plan["trg_len"].mean() < 205


def test_the_configuration_states_every_published_key():
    """Every key of the catalog row's ``config`` under its own name and
    value but the three that the cut in depth changes, each with its
    published value beside it; the pool's arithmetic; the check's three
    limits."""
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_expert_groups": 1,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    cfg = harness.Cell(CELL).config
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (5, 1)
    assert cfg["layer_types"] == ["sliding_attention"] * 4 \
        + ["full_attention"]
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"]) == (32, 2)
    assert pub["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 8
    assert cfg["entry"] == "windowed_frontend" and cfg["dtype"] == "bfloat16"
    assert set(cfg["check"]["limits"]) == set(LIMITS)
    assert cfg["check"]["prompt_len_ranges"] == [[256, 2048], [4096, 8192]]
    for key in ("pool", "ring", "expert_bias", "modelling_code", "weights",
                "end_of_stream", "max_position_embeddings",
                "admit_token_budget"):
        assert cfg["assumed"][key]
    # the pool of ISSUE 33: 68 full pages a slot, a ring of 18
    from paddle_tpu.models.windowed_moe_decoder import ring_pages_per_slot

    pool = cfg["pool"]
    assert pool["num_slots"] == 96 and pool["prefill_buckets"][-1] == 8192
    assert pool["admit_token_budget"] == 4 * pool["prefill_token_budget"]
    assert ring_pages_per_slot(2048, pool["tokens_per_dispatch"],
                               pool["page_size"]) == 18
    page = pool["page_size"] * costs.row_bytes(cfg) // 2     # a K or V page
    assert page == 131072
    full = (1 + 96 * 68) * 2 * page
    ring = 4 * (1 + 96 * 18) * 2 * page
    assert round(full / 1e9, 2) == 1.71 and round(ring / 1e9, 2) == 1.81
