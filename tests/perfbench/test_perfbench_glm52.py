"""The sparse-attention latent decoder's cell: its CPU rehearsal through
run.py's own ``execute``, the comparison that decides ``correct`` with its
two controls, the kernel-cost functions against hand counts at the
published widths, the ``glm52_`` readers on hand-made records, and this
PR's declaration function ``check_declared(bench, root)``, which finds its
entries by NAME, on the real tree and on a copy with one more entry
appended."""

import copy
import json
import os
import time

import pytest

import _perfbench_tiny as tiny
from _perfbench_glm52_tiny import tiny_cell

from perfbench import harness, kernel_costs_glm52 as costs
from perfbench import metric_lib_glm52 as lib

CELL = "serve_glm52_longctx"
LIMITS = ("logit_rel_l2", "expert_choice_diff_share",
          "expert_choice_margin_max", "index_choice_diff_share",
          "index_choice_margin_max")


@pytest.fixture
def rehearse(tmp_path, monkeypatch):
    monkeypatch.setattr(tiny, "tiny_cell",
                        lambda name, root=tiny.ROOT: tiny_cell(name, root))
    return lambda trace: tiny.rehearse(CELL, tmp_path, trace=trace)


@pytest.mark.parametrize("trace", [0, 1])
def test_glm52_cell_rehearsal(trace, rehearse, capsys):
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

    from perfbench import serve_glm52_common as common

    cell = tiny_cell()
    return common, cell, common.Server(
        cell, seed, fluid.CPUPlace(), harness.Setup(time.perf_counter()))


def test_sweep_decoder_holds_windows_under_one_server(tmp_path):
    """``sweep_decoder.py``'s loop, tiny on the CPU: two windows of the
    cell's own traffic behind ONE server found through the cell's entry,
    a pool override as a what-if, the server drained between windows."""
    import importlib

    import paddle_tpu as fluid

    from perfbench import sweep_decoder

    cell = tiny_cell()
    cell.root = str(tmp_path)
    entry = importlib.import_module(
        "perfbench.entries." + cell.config["entry"])
    rows = sweep_decoder.hold_windows(
        cell, entry.common, [2 ** 31 + 5, 7], 1, 1.5, 3, fluid.CPUPlace(),
        pool={"prefill_rungs": False})
    assert cell.config["pool"]["prefill_rungs"] is False
    assert [r["seed"] for r in rows] == [2 ** 31 + 5, 7]
    for row in rows:
        assert row["attempted"] > 0 and row["failed"] == 0
        assert row["tokens_per_s"] > 0
        assert row["prompts"] >= row["prefill_dispatches"] > 0
        assert row["pool_conserved"] and row["live_after"] == 0


def test_both_controls_are_not_correct():
    """The reference in the program's place with float8 operands reads
    far above the program; with NO selection the prompt longer than
    ``index_topk`` fails on its own; both pools are drained after."""
    common, cell, server = _server()
    checker = common.Checker(cell, server)
    limits = cell.config["check"]["limits"]
    for seed in (3, 2 ** 31 + 5):
        sound = checker.numbers(seed)
        control = checker.control_numbers(seed)
        assert sound["logit_rel_l2"] < 2e-5
        assert sound["expert_choice_diff_share"] == 0.0
        assert sound["index_choice_diff_share"] == 0.0
        assert control["logit_rel_l2"] > 1e-2
        assert control["index_choice_diff_share"] > 1e-2
        assert control["logit_rel_l2_dense_long_prompt"] > 1e-2
        assert control["logit_rel_l2_dense"] > 1e-2
        assert common.verdict(sound, limits)
        assert not common.verdict(control, limits)
        assert not common.verdict(
            {k: control[k + "_dense_long_prompt"] for k in LIMITS}, limits)
    sess = server.session
    assert sess.pool_conserved and not sess.active_slots
    assert sess.pages_in_use == 0


def test_index_choice_counts_what_the_reference_did_not_choose():
    import numpy as np

    from perfbench import serve_glm52_common as common

    scores = np.array([[5.0, 4.0, 3.0, 2.0, 1.0, -np.inf],
                       [1.0, 9.0, -np.inf, -np.inf, -np.inf, -np.inf]])
    assert common.own_positions(scores, 3).tolist() == [[0, 1, 2],
                                                       [1, 0, -1]]
    mine = np.array([[0, 1, 4], [0, 1, -1]])
    differ, total, margin = common.index_choice(scores, mine, 3)
    assert (differ, total) == (1, 5)
    # position 4 (score 1) lies 2 under the last chosen (3), in units of
    # the row's spread
    assert margin == pytest.approx(2.0 / np.std([5.0, 4, 3, 2, 1]))


def test_a_decode_dispatchs_record_says_what_was_selected():
    common, cell, server = _server()
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
    (_t0, _t1, (live, rows, chosen)), = server.host["step"]
    assert (live, rows, chosen) == (2, 31 + 6, 8 + 6)
    assert server.host["admit"][0][2] == [(8, [5]), (32, [30])]
    for slot in sess.active_slots:
        sess.cancel(slot)


def test_the_warm_up_runs_every_rung_of_every_bucket():
    """Nothing may compile inside the window: the warm-up dispatches each
    bucket's prefill at every rung of prompt rows the session can choose,
    and the decode program."""
    _common, _cell, server = _server()
    sess = server.session
    rungs = sess.geometry["prefill_rungs"]
    assert rungs == {8: [1, 2, 4, 8], 16: [1, 2, 4], 32: [1, 2]}
    seen = []
    run = server.tap.run

    def spy(program, feed=None, **kw):
        if feed and "prompt_ids" in feed:
            seen.append((len(feed["prompt_ids"]) // len(feed["prompt_len"]),
                         len(feed["prompt_len"])))
        return run(program, feed=feed, **kw)

    server.tap.run = spy
    server.warm()
    # 8 prompts of one bucket on 6 slots: the rung of 8 rows runs with 6
    assert sorted(seen) == sorted(
        [(t, r) for t, rows in rungs.items() for r in rows] + [(8, 2)])
    assert sess.steps_done and sess.pool_conserved


def test_kernel_costs_at_the_published_widths():
    cfg = harness.Cell(CELL).config
    count = costs.parameter_count(cfg)
    # ISSUE 39's arithmetic, from the config's keys
    want = {"attention": 165.0, "indexer": 9.4, "dense_ffn": 226.5,
            "shared_expert": 37.7, "router": 1.6, "routed_expert": 37.7,
            "held_experts": 604.0, "embedding": 118.9, "total": 3881.5}
    assert {k: round(count[k] / 1e6, 1) for k in want} == want
    assert round(2 * count["total"] / 1e9, 2) == 7.76
    # what the builder declares is what is counted
    from paddle_tpu.models.latent_moe_decoder import parameter_shapes
    import numpy as np

    assert count["total"] == sum(
        int(np.prod(shape)) for shape, _dt in parameter_shapes(cfg).values())
    # a decode token step with every held expert hit: 7.4 GB of weights
    # (all but the embedding); with 8 of 16: 5.1 GB
    assert round(costs.decode_step_bytes(cfg, 0, 0, 16) / 1e9, 1) == 7.5
    assert round(costs.decode_step_bytes(cfg, 0, 0, 8.6) / 1e9, 1) == 5.3
    assert costs.decode_step_bytes(cfg, 1000, 3000, 8) \
        - costs.decode_step_bytes(cfg, 0, 0, 8) \
        == 1000 * 5 * 576 * 2 + 3000 * 2 * 128 * 2
    assert round(costs.expected_experts_hit(cfg, 192), 1) == 8.5
    ops, moved = costs.index_score_decode(cfg, 100_000, 24)
    assert ops == 2 * 32 * 128 * 100_000
    assert moved == (100_000 * 128 + 24 * 32 * 128) * 2 + 100_000 * 4
    ops, moved = costs.sparse_decode_attention(cfg, 40_000, 24)
    assert ops == 2 * 64 * (576 + 512) * 40_000
    assert moved == (40_000 * 576 + 24 * 64 * (576 + 512)) * 2
    ops, moved = costs.prefill_attention(cfg, [1000, 5000])
    assert ops == 2 * 64 * 512 * (
        1000 * 1001 / 2 + 2048 * 2049 / 2 + (5000 - 2048) * 2048)
    assert moved == 6000 * 64 * 1024 * 2
    ops, moved = costs.expert_matmuls(cfg, 12, 8)
    assert ops == 2 * 12 * 3 * 6144 * 2048
    assert moved == (8 * 3 * 6144 * 2048 + 2 * 12 * 6144) * 2


def _records(cfg, **serve):
    return {"config": cfg, "serve": dict({"summary": {}}, **serve),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


# the per-layer entries PR 39 declared for this cell, by name: twelve of
# its own and the decoder-only cells' shared ones
DECLARED = [
    "glm52_decode_dispatch_device_ms", "glm52_prefill_dispatch_device_ms",
    "glm52_decode_hbm_roofline", "glm52_index_score_decode_roofline",
    "glm52_sparse_decode_attention_roofline",
    "glm52_prefill_attention_roofline", "glm52_expert_matmul_roofline",
    "glm52_sparse_attention_time_share", "glm52_expert_time_share",
    "glm52_selected_rows_share", "glm52_held_expert_token_share",
    "glm52_prefill_pad_share"] + tiny.DECODER_SHARED
HOST = {"step": [(0.0, 0.1, (24, 130_000, 45_000)),
                 (0.1, 0.2, (23, 120_000, 44_000)),
                 (0.2, 0.3, (0, 0, 0)), (5.0, 5.1, (24, 1, 1))],
        "admit": [(0.05, 0.09, [(4096, [3000, 2500]), (16384, [9000])]),
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
    for name in DECLARED[:12]:
        entry = by_name[name]
        assert entry["workloads"][:1] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["layer"] in perf
        if name.endswith("_roofline"):
            assert entry["unit"] == "%" and entry["better"] == "higher"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm52_dsa_5l", "closed_32_longdoc", 1)
    (config,) = [c for c in bench["configs"] if c["name"] == "glm52_dsa_5l"]
    assert config["source"] == cfg["source"]
    assert config["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "mlp_layer_types",
        "indexer_types", "n_routed_experts", "vocab_size"]
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
    root = _copy_perfbench(tmp_path)
    shutil.copy(os.path.join(tiny.ROOT, "PERF.md"), root)
    new = copy.deepcopy(bench)
    for cell in (CELL, "serve_glm_saturated"):
        name = _append_metric(root, new, cell)
        _write(root, new)
        check_declared(new, root)
        listed = [m["name"] for m in
                  harness.Cell(CELL, root=root).per_layer()]
        assert (name in listed) == (cell == CELL)


def test_glm52_readers_on_hand_made_records(monkeypatch):
    from perfbench import program_records

    # no rounds of a rehearsal that ran before in this process
    monkeypatch.setattr(program_records, "program_rounds", lambda: [])
    cfg = harness.Cell(CELL).config
    step_ops = [lib.SCORE_KERNEL, lib.DECODE_KERNEL, lib.EXPERT_KERNEL]
    runs = [{"name": "jit_multi", "start_s": 0.1 * i, "seconds": 0.05,
             "ops": {k: 0.001 for k in step_ops}} for i in range(3)]
    runs.append({"name": "jit_prefill", "start_s": 0.5, "seconds": 1.5,
                 "ops": {lib.PREFILL_KERNEL: 0.5, lib.EXPERT_KERNEL: 0.1}})
    trace = {"window_s": 3.0, "busy_s": 2.8, "modules": runs, "ops": [
        ["%index_score_decode.3 = f32[24,17,8,128]{3,2,1,0} custom-call(",
         0.010, 24],
        ["%sparse_latent_decode_attention.2 = bf16[24,64,512]{2,1,0} "
         "custom-call(", 0.020, 60],
        ["%sparse_latent_prefill_attention.5 = bf16[1,16384,16384] "
         "custom-call(", 0.9, 10],
        ["%gmm.4 = f32[256,2048]{1,0} custom-call(", 0.2, 300],
        ["%sort.9 = (s32[192]) sort(", 0.01, 100],
        ["%sort.3 = (f32[24,17408]{1,0}, s32[24,17408]) sort(", 0.03, 24],
        ["%fusion.5 = bf16[49152,640]{1,0:T(8,128)(2,1)S(1)} fusion(", 0.03,
         60],
        ["%fusion.6 = s32[49152]{0:T(1024)S(1)} fusion(", 0.01, 24],
        ["%fusion.77 = u32[128,16384]{1,0} fusion(", 0.05, 640],
        ["%fusion.12 = bf16[24,6144]{1,0} fusion(", 0.3, 900]]}
    rec = _records(cfg, host=HOST, seconds=51.0, traced_s=3.0)
    rec["trace"] = trace
    assert lib.decode_dispatch_ms(rec) == pytest.approx(50.0)
    assert lib.prefill_dispatch_ms(rec) == pytest.approx(1500.0)
    # no rounds here: the held experts hit are the even spread's
    hit = costs.expected_experts_hit(cfg, 23.5 * 8)
    assert lib.experts_hit(rec) == pytest.approx(hit)
    want = [sum(costs.decode_step_bytes(cfg, sel + live * j,
                                        rows + live * j, hit)
                for j in range(4)) / 819e9 / 0.05
            for _t0, _t1, (live, rows, sel) in HOST["step"][:2]]
    hbm = lib.decode_hbm_roofline(rec)
    assert hbm == pytest.approx(100 * sum(want) / 2) and 40 < hbm < 70
    for read in (lib.index_score_decode_roofline,
                 lib.sparse_decode_attention_roofline,
                 lib.prefill_attention_roofline,
                 lib.expert_matmul_roofline):
        assert 0 < read(rec) < 100, read.__name__
    # the decode attention's time is the gather's and the kernel's: a
    # program whose kernel fetched the rows itself reads three times this
    fused = dict(rec, trace=dict(trace, ops=[
        o for o in trace["ops"] if "[49152" not in o[0]]))
    assert lib.sparse_decode_attention_roofline(fused) == pytest.approx(
        lib.sparse_decode_attention_roofline(rec) * (0.020 + 0.04) / 0.020)
    # the three kernels, the selection's sort, the gather, the bisection
    assert lib.sparse_attention_time_share(rec) == pytest.approx(
        100 * (0.010 + 0.020 + 0.9 + 0.03 + 0.04 + 0.05) / 2.8)
    # the grouped products and the routing's sort, not the selection's
    assert lib.expert_time_share(rec) == pytest.approx(100 * 0.21 / 2.8)
    check_declared(harness.load_json(tiny.ROOT + "/BENCHMARK.json"),
                   tiny.ROOT)
    # a program without the kernels (the parent): no number
    bare = dict(rec, trace=dict(trace, ops=trace["ops"][-1:], modules=[]))
    for read in (lib.decode_dispatch_ms, lib.decode_hbm_roofline,
                 lib.sparse_attention_time_share, lib.expert_time_share,
                 lib.index_score_decode_roofline):
        assert read(bare) is None, read.__name__


def test_the_rounds_counters_give_the_three_shares():
    rounds = [{"id": i, "spans": [
        {"name": "round", "t0": 0.0, "t1": 0.1, "cpu": 0.03, "parent": None,
         "prefill_tokens": 5700, "prefill_pad_tokens": 2492,
         "latent_rows_resident": 130_000 + i, "latent_rows_selected": 45_000,
         "experts_routed_tokens": 3072, "experts_held_tokens": 190 + i,
         "experts_held_hit": 8.0 + i}]} for i in range(3)]
    assert lib._head_counter(rounds, "experts_held_hit") == [8.0, 9.0, 10.0]
    heads = [r["spans"][0] for r in rounds]
    assert sum(h["latent_rows_selected"] for h in heads) == 135_000
    # a program that does not count them (the parent): nothing to sum
    old = [{"id": 0, "spans": [{"name": "round", "t0": 0.0, "t1": 0.1,
                                "cpu": 0.0, "parent": None}]}]
    assert lib._head_counter(old, "latent_rows_resident") == []


def test_the_traffic_is_the_issues():
    cell = harness.Cell(CELL)
    t = cell.traffic
    assert cell.spec["traffic"] == "closed_32_longdoc" and cell.chips == 1
    assert (t["loop"], t["clients"], t["stagger_s"]) == ("closed", 32, 4.0)
    # sigma 0.5: the issue's own rule for a spread over 5% at 0.7
    assert t["src_len"] == {"dist": "lognormal", "median": 4096,
                            "sigma": 0.5, "min": 512, "max": 16384}
    assert t["trg_len"] == {"dist": "lognormal", "median": 256,
                            "sigma": 0.6, "min": 32, "max": 1024}
    assert (t["ramp_s"], t["drain_s"], t["trace_s"],
            t["client_timeout_s"]) == (20.0, 60.0, 3.0, 90.0)
    theirs = harness.Cell("serve_glm_saturated").traffic
    assert set(t) == set(theirs)
    # the plan: four requests a caller, mean prompt ~4.6 k, ~91% of them
    # and nearly all their tokens beyond index_topk, a twelfth past 8192
    from perfbench import loadgen

    plan = loadgen.make_plan(t, 2 ** 31 + 7, 51.0)
    src = plan["src_len"]
    assert len(src) == 128 and src.min() >= 512
    assert 8192 < src.max() <= 16384
    assert 4400 < src.mean() < 4900
    assert 0.88 < (src > 2048).mean() < 0.94
    assert src[src > 2048].sum() / float(src.sum()) > 0.95
    assert 0.06 < (src > 8192).mean() < 0.11
    assert 280 < plan["trg_len"].mean() < 320


def test_the_configuration_states_every_published_key():
    """Every key of the catalog row's ``config`` under its own name and
    value but the six that the cut changes, each with its published value
    beside it; the pool's arithmetic; the check's five limits."""
    published = {
        "attention_bias": False, "ep_size": 1, "head_dim": 192,
        "hidden_act": "silu", "hidden_size": 6144, "index_head_dim": 128,
        "index_n_heads": 32, "index_share_for_mtp_iteration": True,
        "index_skip_topk_offset": 3, "index_topk": 2048,
        "index_topk_freq": 4, "index_topk_pattern": None,
        "indexer_rope_interleave": True, "intermediate_size": 12288,
        "kv_lora_rank": 512, "max_position_embeddings": 1048576,
        "model_type": "glm_moe_dsa", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_key_value_heads": 64,
        "num_nextn_predict_layers": 1, "q_lora_rank": 2048,
        "qk_head_dim": 256, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_interleave": True,
        "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 256}
    cfg = harness.Cell(CELL).config
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 1, 16, 19360)
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["indexer_types"] == ["full", "shared", "shared", "shared",
                                    "full"]
    assert cfg["expert_shard"] == {"of": 256, "first": 0}
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["first_k_dense_replace"],
            pub["n_routed_experts"], pub["vocab_size"]) == (78, 3, 256,
                                                            154880)
    assert pub["mlp_layer_types"] == ["dense"] * 3 + ["sparse"] * 75
    assert pub["indexer_types"] == ["full"] * 3 + (
        ["shared"] * 3 + ["full"]) * 18 + ["shared"] * 3
    # the cut is the published layers 2-6
    assert pub["indexer_types"][2:7] == cfg["indexer_types"]
    assert pub["mlp_layer_types"][2:7] == cfg["mlp_layer_types"]
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert cfg["entry"] == "sparse_decoder_frontend"
    assert cfg["dtype"] == "bfloat16"
    pool = cfg["pool"]
    assert (pool["num_slots"], pool["max_prompt"], pool["max_new_tokens"],
            pool["page_size"], pool["tokens_per_dispatch"]) == (
                24, 16384, 1024, 128, 4)
    assert pool["prefill_buckets"] == [1024, 2048, 4096, 8192, 16384]
    # ISSUE 39's 16384 token places a dispatch, and a program a rung of
    # prompt rows under them (``assumed.prefill``)
    assert pool["prefill_token_budget"] == 16384
    assert pool["prefill_rungs"] is True
    assert pool["admit_token_budget"] == 16384
    # 136 pages a slot, 3265 pages a pool: 2.67 GB of latent rows in five
    # pools of 640-wide rows, 0.21 GB of narrow keys in two
    pages = 1 + 24 * -(-(16384 + 1024) // 128)
    assert pages == 3265
    assert round(5 * pages * 128 * 640 * 2 / 1e9, 2) == 2.67
    assert round(2 * pages * 128 * 128 * 2 / 1e9, 2) == 0.21
    check = cfg["check"]
    assert set(check["limits"]) == set(LIMITS)
    assert check["prompt_len_ranges"] == [[512, 2048], [8192, 16384]]
    assert check["positions"] == 32
    for key in ("what", "limits_why"):
        assert len(check[key]) > 200
    for key in ("hadamard", "indexer_k_norm", "indexer_weights",
                "multi_token_prediction", "rope"):
        assert key in cfg["assumed"], key
    assert "16 chips" in cfg["deployment"]
