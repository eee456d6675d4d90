"""The hybrid Mamba-2 decoder's cell: its CPU rehearsal through run.py's
own ``execute``, the comparison that decides ``correct`` with its two
controls (float8 operands; a bfloat16 state and nothing else changed), the
kernel-cost functions against hand counts at the published widths, the
``granite_`` readers on hand-made records, and this PR's declaration
function ``check_declared(bench, root)``, which finds its entries by NAME,
on the real tree and on a copy with one more entry appended."""

import copy
import json
import os
import time

import numpy as np
import pytest

import _perfbench_tiny as tiny
from _perfbench_granite_tiny import tiny_cell

from perfbench import harness, kernel_costs_granite as costs
from perfbench import metric_lib_granite as lib

CELL = "serve_granite_sessions"
LIMITS = ("logit_rel_l2", "expert_choice_diff_share",
          "expert_choice_margin_max", "state_rel_l2",
          "state_bf16_grid_share")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def rehearse(tmp_path, monkeypatch):
    monkeypatch.setattr(tiny, "tiny_cell",
                        lambda name, root=tiny.ROOT: tiny_cell(name, root))
    return lambda trace: tiny.rehearse(CELL, tmp_path, trace=trace)


@pytest.mark.parametrize("trace", [0, 1])
def test_granite_cell_rehearsal(trace, rehearse, capsys):
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
        # lateness (the host's clock), the rounds' counters, and nothing
        # of the device's
        assert set(line["metrics"]) >= {
            "build_s", "compile_s", "cache_misses", "trace_lower_s",
            "glm_loadgen_late_p99_ms"}
        assert not [name for name in line["metrics"]
                    if name.endswith("_roofline")
                    or name.endswith("_device_ms")
                    or name.endswith("_time_share")]
    else:
        assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def _server(seed=3):
    import paddle_tpu as fluid

    from perfbench import serve_granite_common as common

    cell = tiny_cell()
    return common, cell, common.Server(
        cell, seed, fluid.CPUPlace(), harness.Setup(time.perf_counter()))


def test_both_controls_are_not_correct():
    """The reference in the program's place with float8 operands reads
    far above the program; with the state rounded to bfloat16 a token and
    NOTHING else changed it fails the state's own limit; the pools are
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


def test_kernel_costs_at_the_published_widths():
    cfg = harness.Cell(CELL).config
    count = costs.parameter_count(cfg)
    # ISSUE 46's arithmetic, from the config's keys
    want = {"mamba_mixer": 102.3, "attention_mixer": 41.9,
            "shared_expert": 18.9, "router": 0.3, "routed_expert": 9.4,
            "held_experts": 339.7, "mamba_layer": 461.2,
            "attention_layer": 400.9, "embedding": 205.5, "total": 4757.2}
    assert {k: round(count[k] / 1e6, 1) for k in want} == want
    assert round(2 * count["total"] / 1e9, 2) == 9.51
    # what the builder declares is what is counted
    from paddle_tpu.models.ssd_moe_decoder import parameter_shapes

    assert count["total"] == sum(
        int(np.prod(shape)) for shape, _dt in parameter_shapes(cfg).values())
    # a slot's state: 9 layers x (128 x 64 x 128 float32 + 3 x 8448 bf16)
    assert costs.state_bytes_per_slot(cfg) == 9 * (
        128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert round(64 * 9 * 128 * 64 * 128 * 4 / 1e9, 2) == 2.42
    assert costs.cached_bytes_per_token(cfg) == 2 * 8 * 128 * 2
    # a decode token step with every held expert hit: 9.5 GB of weights
    # (the tied table is the head's operand), 6.8 GB of them experts
    assert round(costs.decode_step_bytes(cfg, 0, 0, 36) / 1e9, 1) == 9.5
    assert round(10 * 36 * 2 * costs.expert_parameters(cfg) / 1e9, 1) == 6.8
    assert costs.decode_step_bytes(cfg, 64, 100_000, 36) \
        - costs.decode_step_bytes(cfg, 0, 0, 36) \
        == 2 * 64 * costs.state_bytes_per_slot(cfg) + 100_000 * 4096
    # the state is a third of a step's bytes
    whole = costs.decode_step_bytes(cfg, 64, 100_000, 36)
    assert 0.31 < 2 * 64 * costs.state_bytes_per_slot(cfg) / whole < 0.35
    ops, moved = costs.state_update(cfg, 64)
    assert ops == 5 * 64 * 128 * 64 * 128
    assert moved == 2 * 64 * 128 * 64 * 128 * 4 \
        + 64 * (8448 * 2 + 128 * 4 + 8192 * 4)
    # memory bound: 0.66 ms a layer for 64 slots
    assert round(1e3 * costs.least_seconds(ops, moved, PEAKS), 2) == 0.66
    # a prompt of one whole chunk and 44 tokens of the next, and one of
    # 100: the pairs under the diagonal, chunk by chunk
    ops, moved = costs.chunk_prefill(cfg, [300, 100])
    pairs = 256 * 257 / 2 + 44 * 45 / 2 + 100 * 101 / 2
    assert ops == pairs * (2 * 128 + 128 * 2 * 64) \
        + 400 * 128 * 4 * 64 * 128
    assert moved == 400 * (8448 * 2 + 128 * 4 + 8192 * 4) \
        + 2 * 128 * 64 * 128 * 4
    # 4096 tokens: 6.3 MFLOP a token a layer, 0.13 ms at the matrix unit's
    # peak against 0.26 ms at the memory's
    ops, moved = costs.chunk_prefill(cfg, [4096])
    assert round(ops / 4096 / 1e6, 1) == 6.3
    assert round(1e3 * ops / 197e12, 2) == 0.13
    assert round(1e3 * costs.least_seconds(ops, moved, PEAKS), 2) == 0.26
    ops, moved = costs.expert_matmuls(cfg, 320, 35.5)
    assert ops == 2 * 320 * 3 * 4096 * 768
    assert moved == (35.5 * 3 * 4096 * 768 + 2 * 320 * 4096) * 2
    ops, moved = costs.causal_conv(cfg, 100)
    assert (ops, moved) == (2 * 100 * 4 * 8448, 2 * 100 * 8448 * 2)


def _records(cfg, **serve):
    return {"config": cfg, "serve": dict({"summary": {}}, **serve),
            "peaks": dict(PEAKS)}


# the per-layer entries PR 46 declared for this cell, by name: eleven of
# its own and the decoder-only cells' shared ones
DECLARED = [
    "granite_decode_dispatch_device_ms",
    "granite_prefill_dispatch_device_ms", "granite_decode_hbm_roofline",
    "granite_ssd_state_update_roofline",
    "granite_ssd_chunk_prefill_roofline", "granite_expert_matmul_roofline",
    "granite_ssm_time_share", "granite_expert_time_share",
    "granite_state_bytes_share", "granite_held_expert_token_share",
    "granite_prefill_pad_share"] + tiny.DECODER_SHARED
HOST = {"step": [(0.0, 0.1, (64, 100_000)), (0.1, 0.2, (63, 98_000)),
                 (0.2, 0.3, (0, 0)), (5.0, 5.1, (64, 1))],
        "admit": [(0.05, 0.09, [(2048, [1500, 1100]), (4096, [3000])]),
                  (4.0, 4.1, [(512, [300])])]}


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
    for name in DECLARED[:11]:
        entry = by_name[name]
        assert entry["workloads"][:1] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["layer"] in perf
        if name.endswith("_roofline"):
            assert entry["unit"] == "%" and entry["better"] == "higher"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite4_h_small_10l", "closed_80_sessions", 1)
    (config,) = [c for c in bench["configs"]
                 if c["name"] == "granite4_h_small_10l"]
    assert config["source"] == cfg["source"]
    assert config["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "num_local_experts",
        "vocab_size"]
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
    assert len(bench["workloads"]) >= 9
    assert all(w["chips"] == 1 for w in bench["workloads"][:9])
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


def test_granite_readers_on_hand_made_records(monkeypatch):
    from perfbench import metric_lib_glm

    # the rounds of this window, not those of a rehearsal that ran before
    # in this process: the held experts hit are what THEY counted
    hit = 35.0
    rounds = [{"id": i, "spans": [{"name": "round", "t0": 0.0, "t1": 0.1,
                                   "cpu": 0.0, "parent": None,
                                   "experts_held_hit": hit + i - 1}]}
              for i in range(3)]
    monkeypatch.setattr(metric_lib_glm, "read_rounds",
                        lambda records, stat, *a, **kw: stat(rounds, *a))
    cfg = harness.Cell(CELL).config
    step_ops = [lib.UPDATE_KERNEL, lib.CONV_STEP_KERNEL, lib.EXPERT_KERNEL]
    runs = [{"name": "jit_multi", "start_s": 0.1 * i, "seconds": 0.09,
             "ops": {k: 0.001 for k in step_ops}} for i in range(3)]
    runs.append({"name": "jit_prefill", "start_s": 0.5, "seconds": 0.15,
                 "ops": {lib.CHUNK_KERNEL: 0.03, lib.EXPERT_KERNEL: 0.02}})
    trace = {"window_s": 3.0, "busy_s": 2.8, "modules": runs, "ops": [
        ["%ssd_state_update.3 = (f32[64,2,64,64]{3,2,1,0}, "
         "f32[64,128,64,128]{3,2,1,0}) custom-call(", 0.9, 1080],
        ["%ssd_chunk_prefill.2 = (f32[1,4096,8192]{2,1,0}, "
         "f32[1,64,128,128]) custom-call(", 0.1, 27],
        ["%ssm_causal_conv.5 = bf16[1,4096,8448] custom-call(", 0.02, 27],
        ["%ssm_conv_step.4 = (bf16[64,8448], bf16[3,64,8448]) "
         "custom-call(", 0.03, 1080],
        ["%gmm.4 = f32[640,768]{1,0} custom-call(", 0.7, 3600],
        ["%sort.9 = (s32[640]) sort(", 0.01, 1200],
        ["%fusion.12 = bf16[64,4096]{1,0} fusion(", 0.3, 900]]}
    rec = _records(cfg, host=HOST, seconds=51.0, traced_s=3.0)
    rec["trace"] = trace
    assert lib.decode_dispatch_ms(rec) == pytest.approx(90.0)
    assert lib.prefill_dispatch_ms(rec) == pytest.approx(150.0)
    assert lib.experts_hit(rec) == hit
    want = [sum(costs.decode_step_bytes(cfg, live, rows + live * j, hit)
                for j in range(4)) / 819e9 / 0.09
            for _t0, _t1, (live, rows) in HOST["step"][:2]]
    hbm = lib.decode_hbm_roofline(rec)
    assert hbm == pytest.approx(100 * sum(want) / 2) and 60 < hbm < 90
    assert lib.state_update_roofline(rec) == pytest.approx(
        100 * 9 * 4 * sum(costs.least_seconds(
            *costs.state_update(cfg, live), PEAKS) for live in (64, 63))
        / 0.9)
    assert lib.chunk_prefill_roofline(rec) == pytest.approx(
        100 * 9 * sum(costs.least_seconds(
            *costs.chunk_prefill(cfg, lens), PEAKS)
            for lens in ([1500, 1100], [3000])) / 0.1)
    for read in (lib.state_update_roofline, lib.chunk_prefill_roofline,
                 lib.expert_matmul_roofline):
        assert 0 < read(rec) < 100, read.__name__
    # both convolutions, the chunked prefill and the state update
    assert lib.ssm_time_share(rec) == pytest.approx(
        100 * (0.9 + 0.1 + 0.02 + 0.03) / 2.8)
    assert lib.expert_time_share(rec) == pytest.approx(100 * 0.71 / 2.8)
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
                 lib.chunk_prefill_roofline, lib.ssm_time_share,
                 lib.expert_time_share, lib.expert_matmul_roofline):
        assert read(bare) is None, read.__name__


def test_the_rounds_counters_give_the_three_shares(monkeypatch):
    from perfbench import metric_lib_glm, metric_lib_jamba

    cfg = harness.Cell(CELL).config
    per_slot = costs.state_bytes_per_slot(cfg)
    rounds = [{"id": i, "spans": [
        {"name": "round", "t0": 0.0, "t1": 0.1, "cpu": 0.03, "parent": None,
         "prefill_tokens": 2600, "prefill_pad_tokens": 1496,
         "prefill_chunks": 12, "prefill_chunks_padded": 4,
         "state_slots_live": 64, "state_bytes_live": 2 * 64 * per_slot,
         "kv_rows_visible": 100_000 + i,
         "experts_routed_tokens": 25600, "experts_held_tokens": 12700 + i,
         "experts_held_hit": 35.0 + i}]} for i in range(3)]
    monkeypatch.setattr(metric_lib_glm, "read_rounds",
                        lambda records, stat, *a, **kw: stat(rounds, *a))
    rec = {"config": cfg}
    share = lib.state_bytes_share(rec)
    whole = costs.decode_step_bytes(cfg, 64, 100_001, 36.0)
    assert share == pytest.approx(100.0 * 2 * 64 * per_slot / whole)
    assert 31 < share < 35
    assert lib.held_expert_token_share(rec) == pytest.approx(
        100.0 * 38103 / 76800)
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
    assert cell.spec["traffic"] == "closed_80_sessions" and cell.chips == 1
    assert (t["loop"], t["clients"], t["stagger_s"]) == ("closed", 80, 4.0)
    assert t["src_len"] == {"dist": "lognormal", "median": 1024,
                            "sigma": 0.7, "min": 128, "max": 4096}
    assert t["trg_len"] == {"dist": "lognormal", "median": 320,
                            "sigma": 0.7, "min": 32, "max": 1024}
    assert t["trace_s"] == 3.0
    theirs = harness.Cell("serve_solar_docreason").traffic
    assert set(t) == set(theirs)
    assert set(t["assumed"]) == set(theirs["assumed"])
    assert t["max_stream_backlog"] == theirs["max_stream_backlog"]
    # the plan: four requests a caller, mean prompt ~1.3 k, mean output
    # ~400
    from perfbench import loadgen

    plan = loadgen.make_plan(t, 2 ** 31 + 7, 51.0)
    src = plan["src_len"]
    assert len(src) == 320 and src.min() >= 128 and src.max() == 4096
    assert 1200 < src.mean() < 1400
    assert 370 < plan["trg_len"].mean() < 430
    assert plan["trg_len"].max() <= 1024
    # callers over slots as the issue gives them
    assert t["clients"] * 4 == 5 * cell.config["pool"]["num_slots"]


def test_the_configuration_states_every_published_key():
    """Every key of the catalog row's ``config`` under its own name and
    value but the four that the cut changes, each with its published
    value beside it; the pool's arithmetic; the check's five limits."""
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_key_value_heads": 8,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True}
    cfg = harness.Cell(CELL).config
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (10, 36, 50176)
    assert cfg["expert_shard"] == {"of": 72, "first": 0}
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_local_experts"],
            pub["vocab_size"]) == (40, 72, 100352)
    assert [i for i, k in enumerate(pub["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    # the cut is the published layers 0-9: one whole period
    assert cfg["layer_types"] == pub["layer_types"][:10]
    assert cfg["layer_types"].count("mamba") == 9
    assert cfg["vocab_size"] * 2 == pub["vocab_size"]
    assert cfg["num_local_experts"] * 2 == pub["num_local_experts"]
    assert cfg["entry"] == "ssd_decoder_frontend"
    assert cfg["dtype"] == "bfloat16" and cfg["state_dtype"] == "float32"
    pool = cfg["pool"]
    assert (pool["num_slots"], pool["max_prompt"], pool["max_new_tokens"],
            pool["page_size"], pool["tokens_per_dispatch"]) == (
                64, 4096, 1024, 128, 4)
    assert pool["prefill_buckets"] == [256, 512, 1024, 2048, 4096]
    assert all(t % cfg["mamba_chunk_size"] == 0
               for t in pool["prefill_buckets"])
    assert pool["prefill_token_budget"] == 4096
    assert pool["prefill_rungs"] is True
    # 40 pages a slot, 2561 pages a pool: 1.34 GB of K and V rows of ONE
    # layer; 2.42 GB of matrix state in nine
    pages = 1 + 64 * -(-(4096 + 1024) // 128)
    assert pages == 2561
    assert round(2 * pages * 128 * 1024 * 2 / 1e9, 2) == 1.34
    check = cfg["check"]
    assert set(check["limits"]) == set(LIMITS)
    assert check["prompt_len_ranges"] == [[128, 1024], [2048, 4096]]
    assert check["positions"] == 32
    for key in ("what", "limits_why"):
        assert len(check[key]) > 200
    for key in ("head_dim", "mamba2_layer", "attention_scale", "routing",
                "multipliers", "initialisers", "state_dtype",
                "end_of_stream", "max_position_embeddings",
                "keys_nothing_reads"):
        assert key in cfg["assumed"], key
    assert "2 chips share each layer" in cfg["deployment"]
    assert "stage 0" in cfg["deployment"]
