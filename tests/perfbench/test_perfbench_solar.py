"""The delta-rule linear-attention decoder's cell: its CPU rehearsal
through run.py's own ``execute``, the comparison that decides ``correct``
with its two controls (float8 operands; a bfloat16 state and nothing else
changed), the kernel-cost functions against hand counts at the published
widths, the ``solar_`` readers on hand-made records, and this PR's
declaration function ``check_declared(bench, root)``, which finds its
entries by NAME, on the real tree and on a copy with one more entry
appended."""

import copy
import json
import os
import time

import numpy as np
import pytest

import _perfbench_tiny as tiny
from _perfbench_solar_tiny import tiny_cell

from perfbench import harness, kernel_costs_solar as costs
from perfbench import metric_lib_solar as lib

CELL = "serve_solar_docreason"
LIMITS = ("logit_rel_l2", "expert_choice_diff_share",
          "expert_choice_margin_max", "state_rel_l2",
          "state_bf16_grid_share")


@pytest.fixture
def rehearse(tmp_path, monkeypatch):
    monkeypatch.setattr(tiny, "tiny_cell",
                        lambda name, root=tiny.ROOT: tiny_cell(name, root))
    return lambda trace: tiny.rehearse(CELL, tmp_path, trace=trace)


@pytest.mark.parametrize("trace", [0, 1])
def test_solar_cell_rehearsal(trace, rehearse, capsys):
    cell = rehearse(trace)
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    tiny.check_line(line, cell, trace)
    text = "\n".join(out[:-1])
    for key in LIMITS:
        assert "check %s" % key in text
    assert "check pool conserved after the run: True" in text
    assert "check: 6 slots live" in text
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


def _server(seed=3):
    import paddle_tpu as fluid

    from perfbench import serve_solar_common as common

    cell = tiny_cell()
    return common, cell, common.Server(
        cell, seed, fluid.CPUPlace(), harness.Setup(time.perf_counter()))


def test_both_controls_are_not_correct():
    """The reference in the program's place with float8 operands reads
    far above the program; with the state rounded to bfloat16 a token and
    NOTHING else changed it fails the state's own limits; the pools are
    drained after."""
    common, cell, server = _server()
    checker = common.Checker(cell, server)
    limits = cell.config["check"]["limits"]
    for seed in (3, 2 ** 31 + 5):
        sound = checker.numbers(seed)
        control = checker.control_numbers(seed)
        assert set(sound) == set(LIMITS)
        assert sound["logit_rel_l2"] < 2e-5
        assert sound["state_rel_l2"] < 2e-5
        assert sound["expert_choice_diff_share"] == 0.0
        assert control["logit_rel_l2"] > 1e-2
        assert control["state_rel_l2"] > 1e-2
        alone = {k: control[k + "_bf16_state_alone"] for k in LIMITS}
        assert alone["state_rel_l2"] > 1e-3
        # the state's own number: all of a bfloat16 state on bfloat16's
        # grid, next to nothing of a float32 one
        assert alone["state_bf16_grid_share"] == 1.0
        assert sound["state_bf16_grid_share"] < 1e-3
        assert control["state_bf16_grid_share"] < 1e-3
        assert common.verdict(sound, limits)
        assert not common.verdict({k: control[k] for k in LIMITS}, limits)
        assert not common.verdict(alone, limits)
    sess = server.session
    assert sess.pool_conserved and not sess.active_slots
    assert sess.pages_in_use == 0


def test_the_grid_share_tells_a_bfloat16_state_from_a_float32_one():
    from perfbench import serve_solar_common as common

    state = np.random.RandomState(0).standard_normal((2, 3, 4, 16, 16))
    assert common.bf16_grid_share(state) < 1e-3
    rounded = np.asarray(common.bf16_state(state.astype("float32")))
    assert common.bf16_grid_share(rounded) == 1.0
    # zeros (a slot never written) are not counted either way
    assert common.bf16_grid_share(np.zeros((2, 4))) == 0.0
    mixed = np.concatenate([rounded.ravel()[:100], state.ravel()[:300],
                            np.zeros(50)])
    assert common.bf16_grid_share(mixed) == pytest.approx(0.25, abs=0.01)


def test_kernel_costs_at_the_published_widths():
    cfg = harness.Cell(CELL).config
    count = costs.parameter_count(cfg)
    # ISSUE 42's arithmetic, from the config's keys
    want = {"linear_mixer": 137.7, "gqa_mixer": 109.1, "shared_expert": 15.7,
            "router": 1.3, "routed_expert": 15.7, "held_experts": 629.1,
            "linear_layer": 783.9, "gqa_layer": 755.2, "embedding": 100.7,
            "total": 3308.4}
    assert {k: round(count[k] / 1e6, 1) for k in want} == want
    assert round(2 * count["total"] / 1e9, 2) == 6.62
    # what the builder declares is what is counted
    from paddle_tpu.models.linear_attn_moe_decoder import parameter_shapes

    assert count["total"] == sum(
        int(np.prod(shape)) for shape, _dt in parameter_shapes(cfg).values())
    # a slot's state: 3 layers x (64 x 128 x 128 float32 + 3 x 24576 bf16)
    assert costs.state_bytes_per_slot(cfg) == 3 * (
        64 * 128 * 128 * 4 + 3 * 24576 * 2)
    assert round(96 * 3 * 64 * 128 * 128 * 4 / 1e9, 2) == 1.21
    assert costs.cached_bytes_per_token(cfg) == 2 * 8 * 128 * 2
    # a decode token step with every held expert hit: 6.4 GB of weights
    # (all but the embedding); with the 91% an even spread hits: 6.0
    assert round(costs.decode_step_bytes(cfg, 0, 0, 40) / 1e9, 1) == 6.4
    hit = 40 * (1.0 - (1.0 - 1.0 / 320) ** (96 * 8))
    assert round(hit / 40, 2) == 0.91
    assert round(costs.decode_step_bytes(cfg, 0, 0, hit) / 1e9, 1) == 6.0
    assert costs.decode_step_bytes(cfg, 96, 300_000, 8) \
        - costs.decode_step_bytes(cfg, 0, 0, 8) \
        == 2 * 96 * costs.state_bytes_per_slot(cfg) + 300_000 * 4096
    ops, moved = costs.state_update(cfg, 96)
    assert ops == 7 * 96 * 64 * 128 * 128
    assert moved == 2 * 96 * 64 * 128 * 128 * 4 \
        + 96 * 8192 * (3 * 2 + 2 * 4) + 96 * 64 * 4
    ops, moved = costs.chunk_prefill(cfg, [1000, 5000])
    assert ops == 6000 * 64 * (6 * 128 * 128 + 2 * 64 * 256)
    assert moved == 6000 * 64 * (3 * 128 * 2 + 2 * 128 * 4 + 4) \
        + 2 * 64 * 128 * 128 * 4
    # memory bound: 1.15 ms a layer for 8192 tokens against 0.35 ms of
    # products at the matrix unit's peak
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops, moved = costs.chunk_prefill(cfg, [8192])
    assert round(1e3 * ops / 197e12, 2) == 0.35
    assert round(1e3 * costs.least_seconds(ops, moved, peaks), 2) == 1.15
    ops, moved = costs.gqa_decode_attention(cfg, 300_000, 96)
    assert ops == 4 * 64 * 128 * 300_000
    assert moved == (2 * 300_000 * 1024 + 2 * 96 * 8192) * 2
    ops, moved = costs.prefill_attention(cfg, [1000, 5000])
    assert ops == 4 * 64 * 128 * (1000 * 1001 / 2 + 5000 * 5001 / 2)
    assert moved == 6000 * (2 * 64 + 2 * 8) * 128 * 2
    ops, moved = costs.expert_matmuls(cfg, 96, 36.4)
    assert ops == 2 * 96 * 3 * 4096 * 1280
    assert moved == (36.4 * 3 * 4096 * 1280 + 2 * 96 * 4096) * 2
    ops, moved = costs.causal_conv(cfg, 100)
    assert (ops, moved) == (2 * 100 * 4 * 24576, 2 * 100 * 24576 * 2)


def _records(cfg, **serve):
    return {"config": cfg, "serve": dict({"summary": {}}, **serve),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


# the per-layer entries PR 42 declared for this cell, by name: thirteen of
# its own and the decoder-only cells' shared ones
DECLARED = [
    "solar_decode_dispatch_device_ms", "solar_prefill_dispatch_device_ms",
    "solar_decode_hbm_roofline", "solar_state_update_roofline",
    "solar_chunk_prefill_roofline", "solar_gqa_decode_attention_roofline",
    "solar_prefill_attention_roofline", "solar_expert_matmul_roofline",
    "solar_linear_time_share", "solar_expert_time_share",
    "solar_state_bytes_share", "solar_held_expert_token_share",
    "solar_prefill_pad_share"] + tiny.DECODER_SHARED
HOST = {"step": [(0.0, 0.1, (96, 320_000)), (0.1, 0.2, (95, 310_000)),
                 (0.2, 0.3, (0, 0)), (5.0, 5.1, (96, 1))],
        "admit": [(0.05, 0.09, [(4096, [3000, 2500]), (8192, [6000])]),
                  (4.0, 4.1, [(1024, [600])])]}


def check_declared(bench, root):
    """Every name this PR declared is there, lists this cell, moves
    ``serve_tokens_per_s`` and sits in a layer PERF.md names; its reader
    gives no number on records without a device trace; the cell, its
    configuration and its traffic are the issue's; and the cell reports
    ``serve_tokens_per_s`` and ``trace_lower_s``."""
    cfg = harness.Cell(CELL, root=root).config
    tiny.check_cell_declares(bench, root, CELL, DECLARED, [
        _records(cfg, host=HOST, seconds=51.0, traced_s=3.0),
        {"config": cfg}])
    by_name = {m["name"]: m for m in bench["per_layer"]}
    with open(os.path.join(root, "PERF.md")) as f:
        perf = f.read()
    for name in DECLARED[:13]:
        entry = by_name[name]
        assert entry["workloads"][:1] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["layer"] in perf
        if name.endswith("_roofline"):
            assert entry["unit"] == "%" and entry["better"] == "higher"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar_open2_4l", "closed_120_docreason", 1)
    (config,) = [c for c in bench["configs"] if c["name"] == "solar_open2_4l"]
    assert config["source"] == cfg["source"]
    assert config["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
    (tokens,) = [m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s"]
    assert CELL in tokens["workloads"]


def test_declared_on_the_real_tree_and_on_a_copy_with_one_more_entry(
        tmp_path):
    """This PR's entries are found by NAME: a further per-layer entry
    appended at the END for this cell, and one for another, break
    nothing."""
    from test_perfbench_contract import (
        _append_metric,
        _copy_perfbench,
        _write,
    )
    import shutil

    bench = harness.load_json(os.path.join(tiny.ROOT, "BENCHMARK.json"))
    check_declared(bench, tiny.ROOT)
    assert len(bench["workloads"]) >= 8
    assert all(w["chips"] == 1 for w in bench["workloads"][:8])
    root = _copy_perfbench(tmp_path)
    shutil.copy(os.path.join(tiny.ROOT, "PERF.md"), root)
    new = copy.deepcopy(bench)
    for cell in (CELL, "serve_jamba_saturated"):
        name = _append_metric(root, new, cell)
        _write(root, new)
        check_declared(new, root)
        listed = [m["name"] for m in
                  harness.Cell(CELL, root=root).per_layer()]
        assert (name in listed) == (cell == CELL)


def test_solar_readers_on_hand_made_records(monkeypatch):
    from perfbench import metric_lib_glm

    # the rounds of this window, not those of a rehearsal that ran before
    # in this process: the held experts hit are what THEY counted
    hit = 37.0
    rounds = [{"id": i, "spans": [{"name": "round", "t0": 0.0, "t1": 0.1,
                                   "cpu": 0.0, "parent": None,
                                   "experts_held_hit": hit + i - 1}]}
              for i in range(3)]
    monkeypatch.setattr(metric_lib_glm, "read_rounds",
                        lambda records, stat, *a, **kw: stat(rounds, *a))
    cfg = harness.Cell(CELL).config
    step_ops = [lib.UPDATE_KERNEL, lib.CONV_STEP_KERNEL, lib.GQA_KERNEL,
                lib.EXPERT_KERNEL]
    runs = [{"name": "jit_multi", "start_s": 0.1 * i, "seconds": 0.08,
             "ops": {k: 0.001 for k in step_ops}} for i in range(3)]
    runs.append({"name": "jit_prefill", "start_s": 0.5, "seconds": 0.25,
                 "ops": {lib.CHUNK_KERNEL: 0.06, lib.PREFILL_KERNEL: 0.01,
                         lib.EXPERT_KERNEL: 0.02}})
    trace = {"window_s": 3.0, "busy_s": 2.8, "modules": runs, "ops": [
        ["%delta_rule_state_update.3 = (f32[96,64,128]{2,1,0}, "
         "f32[96,64,128,128]{3,2,1,0}) custom-call(", 0.5, 288],
        ["%delta_rule_chunk_prefill.2 = (f32[1,8192,8192]{2,1,0}, "
         "f32[1,64,128,128]) custom-call(", 0.3, 9],
        ["%ssm_causal_conv.5 = bf16[1,8192,24576] custom-call(", 0.02, 9],
        ["%ssm_conv_step.4 = (bf16[96,24576], bf16[3,96,24576]) "
         "custom-call(", 0.03, 288],
        ["%gqa_paged_decode_attention.7 = bf16[96,64,128] custom-call(",
         0.2, 96],
        ["%flash_attention_fwd.1 = bf16[1,64,8192,128] custom-call(", 0.05,
         3],
        ["%gmm.4 = f32[256,1280]{1,0} custom-call(", 0.6, 1200],
        ["%sort.9 = (s32[768]) sort(", 0.01, 400],
        ["%fusion.12 = bf16[96,4096]{1,0} fusion(", 0.3, 900]]}
    rec = _records(cfg, host=HOST, seconds=51.0, traced_s=3.0)
    rec["trace"] = trace
    assert lib.decode_dispatch_ms(rec) == pytest.approx(80.0)
    assert lib.prefill_dispatch_ms(rec) == pytest.approx(250.0)
    assert lib.experts_hit(rec) == hit
    want = [sum(costs.decode_step_bytes(cfg, live, rows + live * j, hit)
                for j in range(4)) / 819e9 / 0.08
            for _t0, _t1, (live, rows) in HOST["step"][:2]]
    hbm = lib.decode_hbm_roofline(rec)
    assert hbm == pytest.approx(100 * sum(want) / 2) and 50 < hbm < 70
    peaks = rec["peaks"]
    assert lib.state_update_roofline(rec) == pytest.approx(
        100 * 3 * 4 * sum(costs.least_seconds(
            *costs.state_update(cfg, live), peaks) for live in (96, 95))
        / 0.5)
    assert lib.chunk_prefill_roofline(rec) == pytest.approx(
        100 * 3 * sum(costs.least_seconds(
            *costs.chunk_prefill(cfg, lens), peaks)
            for lens in ([3000, 2500], [6000])) / 0.3)
    for read in (lib.state_update_roofline, lib.chunk_prefill_roofline,
                 lib.gqa_decode_attention_roofline,
                 lib.prefill_attention_roofline,
                 lib.expert_matmul_roofline):
        assert 0 < read(rec) < 100, read.__name__
    # both convolutions, the chunked prefill and the state update
    assert lib.linear_time_share(rec) == pytest.approx(
        100 * (0.5 + 0.3 + 0.02 + 0.03) / 2.8)
    assert lib.expert_time_share(rec) == pytest.approx(100 * 0.61 / 2.8)
    # rounds that do not count the experts hit: no number, and none
    # assumed in its place
    for r in rounds:
        del r["spans"][0]["experts_held_hit"]
    for read in (lib.experts_hit, lib.decode_hbm_roofline,
                 lib.expert_matmul_roofline):
        assert read(rec) is None, read.__name__
    # a program without the kernels (the parent): no number
    bare = dict(rec, trace=dict(trace, ops=trace["ops"][-1:], modules=[]))
    for read in (lib.decode_dispatch_ms, lib.prefill_dispatch_ms,
                 lib.decode_hbm_roofline, lib.state_update_roofline,
                 lib.chunk_prefill_roofline, lib.linear_time_share,
                 lib.expert_time_share, lib.gqa_decode_attention_roofline):
        assert read(bare) is None, read.__name__


def test_the_rounds_counters_give_the_three_shares(monkeypatch):
    from perfbench import metric_lib_glm, metric_lib_jamba

    cfg = harness.Cell(CELL).config
    per_slot = costs.state_bytes_per_slot(cfg)
    rounds = [{"id": i, "spans": [
        {"name": "round", "t0": 0.0, "t1": 0.1, "cpu": 0.03, "parent": None,
         "prefill_tokens": 5700, "prefill_pad_tokens": 2492,
         "prefill_chunks": 90, "prefill_chunks_padded": 38,
         "state_slots_live": 96, "state_bytes_live": 2 * 96 * per_slot,
         "kv_rows_visible": 320_000 + i,
         "experts_routed_tokens": 12288, "experts_held_tokens": 1500 + i,
         "experts_held_hit": 36.0 + i}]} for i in range(3)]
    monkeypatch.setattr(metric_lib_glm, "read_rounds",
                        lambda records, stat, *a, **kw: stat(rounds, *a))
    rec = {"config": cfg}
    share = lib.state_bytes_share(rec)
    whole = costs.decode_step_bytes(cfg, 96, 320_001, 37.0)
    assert share == pytest.approx(100.0 * 2 * 96 * per_slot / whole)
    assert 22 < share < 28
    assert lib.held_expert_token_share(rec) == pytest.approx(
        100.0 * 4503 / 36864)
    assert lib.read_prefill_pad_share(rec) == pytest.approx(
        metric_lib_jamba.prefill_pad_share(rounds))
    # a program that does not count them (the parent): nothing to read
    old = [{"id": 0, "spans": [{"name": "round", "t0": 0.0, "t1": 0.1,
                                "cpu": 0.0, "parent": None}]}]
    monkeypatch.setattr(metric_lib_glm, "read_rounds",
                        lambda records, stat, *a, **kw: stat(old, *a))
    for read in (lib.state_bytes_share, lib.held_expert_token_share,
                 lib.read_prefill_pad_share):
        assert read(rec) is None, read.__name__


def test_the_traffic_is_the_issues():
    cell = harness.Cell(CELL)
    t = cell.traffic
    assert cell.spec["traffic"] == "closed_120_docreason" and cell.chips == 1
    assert (t["loop"], t["clients"], t["stagger_s"]) == ("closed", 120, 4.0)
    assert t["src_len"] == {"dist": "lognormal", "median": 2048,
                            "sigma": 0.8, "min": 256, "max": 8192}
    assert t["trg_len"] == {"dist": "lognormal", "median": 512,
                            "sigma": 0.6, "min": 64, "max": 2048}
    assert (t["ramp_s"], t["drain_s"], t["trace_s"],
            t["client_timeout_s"]) == (25.0, 90.0, 3.0, 120.0)
    theirs = harness.Cell("serve_trinity_longctx").traffic
    assert set(t) == set(theirs)
    assert t["max_stream_backlog"] == theirs["max_stream_backlog"]
    # the plan: four requests a caller, mean prompt ~2.7 k, 4% at the
    # cap, mean output ~610
    from perfbench import loadgen

    plan = loadgen.make_plan(t, 2 ** 31 + 7, 51.0)
    src = plan["src_len"]
    assert len(src) == 480 and src.min() >= 256 and src.max() == 8192
    assert 2600 < src.mean() < 2750
    assert 0.17 < (src > 4096).mean() < 0.22
    assert 0.03 < (src == 8192).mean() < 0.05
    assert 580 < plan["trg_len"].mean() < 640
    assert plan["trg_len"].max() <= 2048


def test_the_configuration_states_every_published_key():
    """Every key of the catalog row's ``config`` under its own name and
    value but the four that the cut changes, each with its published
    value beside it; the pool's arithmetic; the check's five limits."""
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3, "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    cfg = harness.Cell(CELL).config
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["gqa_layers"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (4, [0], 40, 24576)
    assert cfg["expert_shard"] == {"of": 320, "first": 0}
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (48, 320, 196608)
    assert pub["gqa_layers"] == list(range(0, 48, 4))
    # the cut is the published layers 0-3: one whole period
    assert [i for i in pub["gqa_layers"] if i < 4] == cfg["gqa_layers"]
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert cfg["n_routed_experts"] * 8 == pub["n_routed_experts"]
    assert cfg["entry"] == "linear_decoder_frontend"
    assert cfg["dtype"] == "bfloat16" and cfg["state_dtype"] == "float32"
    pool = cfg["pool"]
    assert (pool["num_slots"], pool["max_prompt"], pool["max_new_tokens"],
            pool["page_size"], pool["tokens_per_dispatch"]) == (
                96, 8192, 2048, 128, 4)
    assert pool["prefill_buckets"] == [512, 1024, 2048, 4096, 8192]
    assert pool["prefill_token_budget"] == 8192
    assert pool["prefill_rungs"] is True
    # 80 pages a slot, 7681 pages a pool: 4.03 GB of K and V rows of ONE
    # layer; 1.21 GB of matrix state in three
    pages = 1 + 96 * -(-(8192 + 2048) // 128)
    assert pages == 7681
    assert round(2 * pages * 128 * 1024 * 2 / 1e9, 2) == 4.03
    check = cfg["check"]
    assert set(check["limits"]) == set(LIMITS)
    assert check["prompt_len_ranges"] == [[256, 1024], [4096, 8192]]
    assert check["positions"] == 32
    for key in ("what", "limits_why"):
        assert len(check[key]) > 200
    for key in ("kda_layer", "gate_rank", "value_heads", "conv_bias",
                "gqa_gate", "routing", "intermediate_size", "initialisers",
                "state_dtype"):
        assert key in cfg["assumed"], key
    assert "8 chips" in cfg["deployment"]
