"""The shortcut decoder's cell: its CPU rehearsal through run.py's own
``execute``, the comparison that decides ``correct`` with its three
controls (float8 operands; the identities' term left out; the expert block
fed sequentially), the kernel-cost functions against hand counts at the
published widths, the ``longcat_`` readers on hand-made records, and this
PR's declaration function ``check_declared(bench, root)``, which finds its
entries by NAME, on the real tree and on a copy with one more entry
appended."""

import copy
import json
import os
import time

import numpy as np
import pytest

import _perfbench_tiny as tiny
from _perfbench_longcat_tiny import tiny_cell

from perfbench import harness, kernel_costs_longcat as costs
from perfbench import metric_lib_longcat as lib

CELL = "serve_longcat_agentic"
LIMITS = ("logit_rel_l2", "expert_choice_diff_share",
          "expert_choice_margin_max")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def rehearse(tmp_path, monkeypatch):
    monkeypatch.setattr(tiny, "tiny_cell",
                        lambda name, root=tiny.ROOT: tiny_cell(name, root))
    return lambda trace: tiny.rehearse(CELL, tmp_path, trace=trace)


@pytest.mark.parametrize("trace", [0, 1])
def test_longcat_cell_rehearsal(trace, rehearse, capsys):
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

    from perfbench import serve_longcat_common as common

    cell = tiny_cell()
    return common, cell, common.Server(
        cell, seed, fluid.CPUPlace(), harness.Setup(time.perf_counter()))


def test_all_three_controls_are_not_correct():
    """The reference in the program's place with float8 operands reads far
    above the program; with the identities' term left out, and with the
    expert block fed from the second sub-block, it fails too; the pools
    are drained after."""
    common, cell, server = _server()
    checker = common.Checker(cell, server)
    limits = cell.config["check"]["limits"]
    for seed in (3, 2 ** 31 + 5):
        sound = checker.numbers(seed)
        control = checker.control_numbers(seed)
        assert set(sound) == set(LIMITS)
        assert sound["logit_rel_l2"] < 2e-5
        assert sound["expert_choice_diff_share"] == 0.0
        assert control["logit_rel_l2"] > 1e-2
        assert common.verdict(sound, limits)
        assert not common.verdict({k: control[k] for k in LIMITS}, limits)
        for suffix in ("_no_identities", "_sequential"):
            other = {k: control[k + suffix] for k in LIMITS}
            assert other["logit_rel_l2"] > 1e-3, suffix
            assert not common.verdict(other, limits), suffix
    sess = server.session
    assert sess.pool_conserved and not sess.active_slots
    assert sess.pages_in_use == 0
    # a layer owns two pools
    assert len(sess.geometry["state"]["page_pools"]) == 4


def test_kernel_costs_at_the_published_widths():
    cfg = harness.Cell(CELL).config
    count = costs.parameter_count(cfg)
    # ISSUE 49's arithmetic, from the config's keys
    want = {"attention_block": 90.57, "dense_ffn": 226.49, "router": 4.72,
            "layer_outside_experts": 638.87, "routed_expert": 37.75,
            "held_experts": 603.98, "embedding": 100.66, "total": 5172.75}
    assert {k: round(count[k] / 1e6, 2) for k in want} == want
    assert round(2 * count["total"] / 1e9, 2) == 10.35
    # the published model: 28 layers of 512 experts, the whole vocabulary
    whole = 28 * (count["layer_outside_experts"]
                  + 512 * count["routed_expert"]) + 2 * 131072 * 6144
    assert round(whole / 1e9, 1) == 560.7
    # what the builder declares is what is counted
    from paddle_tpu.models.shortcut_moe_decoder import parameter_shapes

    assert count["total"] == sum(
        int(np.prod(shape)) for shape, _dt in parameter_shapes(cfg).values())
    # two 576-wide rows a layer a token
    assert costs.cached_bytes_per_token(cfg) == 2 * 4 * 576 * 2
    # full occupancy: 64 slots x 5120 positions in eight pools of 640 lanes
    assert round(64 * 5120 * 8 * 640 * 2 / 1e9, 2) == 3.36
    # a decode token step: 5.1 GB of layer weights outside the experts,
    # 0.2 GB of head; 4.8 GB of held experts if every one is hit
    assert round(4 * 2 * count["layer_outside_experts"] / 1e9, 1) == 5.1
    assert round(2 * count["head"] / 1e9, 1) == 0.2
    assert round(4 * 2 * count["held_experts"] / 1e9, 1) == 4.8
    bare = costs.decode_step_bytes(cfg, 0, 0)
    assert bare == 2 * (4 * count["layer_outside_experts"] + count["head"]
                        + 6144)
    assert costs.decode_step_bytes(cfg, 130_000, 10.0) - bare \
        == 130_000 * 8 * 576 * 2 + 4 * 10 * 2 * count["routed_expert"]
    # the issue's reckoning: ~9.6 GB a step with 63% of the held experts
    # hit and ~130 k resident rows, 11.7 ms at the memory's rate
    step = costs.decode_step_bytes(cfg, 130_000, 0.63 * 16)
    assert round(step / 1e9, 1) == 9.6
    assert round(1e3 * step / 819e9, 1) == 11.7
    # an even spread of 64 x 12 choices over 768 outputs hits 63% of 16
    assert round(costs.expected_experts_hit(cfg, 64 * 12) / 16, 2) == 0.63
    ops, moved = costs.latent_decode_attention(cfg, 130_000, 64)
    assert ops == 2 * 64 * (576 + 512) * 130_000
    assert moved == (130_000 * 576 + 64 * 64 * (576 + 512)) * 2
    # memory bound: 0.19 ms a pool for ~130 k rows
    assert round(1e3 * costs.least_seconds(ops, moved, PEAKS), 2) == 0.19
    # the flash forward at 192 / 128: the two products at their own widths
    ops, moved = costs.prefill_attention(cfg, [300, 100])
    pairs = 300 * 301 / 2 + 100 * 101 / 2
    assert ops == 2 * 64 * pairs * (192 + 128)
    assert moved == 400 * 64 * (2 * 192 + 2 * 128) * 2
    # 4096 tokens: compute bound, 1.74 ms a block at the peak
    ops, moved = costs.prefill_attention(cfg, [4096])
    assert round(1e3 * costs.least_seconds(ops, moved, PEAKS), 2) == 1.74
    assert ops / 197e12 > moved / 819e9
    ops, moved = costs.expert_matmuls(cfg, 16.0, 10.1)
    assert ops == 2 * 16.0 * 3 * 6144 * 2048
    assert moved == (10.1 * 3 * 6144 * 2048 + 2 * 16.0 * 6144) * 2


def _records(cfg, **serve):
    return {"config": cfg, "serve": dict({"summary": {}}, **serve),
            "peaks": dict(PEAKS)}


# the per-layer entries PR 49 declared for this cell, by name: eleven of
# its own and the decoder-only cells' shared ones
DECLARED = [
    "longcat_decode_dispatch_device_ms",
    "longcat_prefill_dispatch_device_ms", "longcat_decode_hbm_roofline",
    "longcat_latent_decode_attention_roofline",
    "longcat_prefill_attention_roofline", "longcat_expert_matmul_roofline",
    "longcat_expert_time_share", "longcat_attention_time_share",
    "longcat_zero_expert_choice_share", "longcat_held_expert_token_share",
    "longcat_prefill_pad_share"] + tiny.DECODER_SHARED
HOST = {"step": [(0.0, 0.1, (64, 130_000)), (0.1, 0.2, (63, 128_000)),
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
        "longcat_flash_omni_4l", "closed_80_agentic", 1)
    (config,) = [c for c in bench["configs"]
                 if c["name"] == "longcat_flash_omni_4l"]
    assert config["source"] == cfg["source"]
    assert config["reduced"] == cfg["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
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
    assert len(bench["workloads"]) >= 10
    assert all(w["chips"] == 1 for w in bench["workloads"][:10])
    root = _copy_perfbench(tmp_path)
    shutil.copy(os.path.join(tiny.ROOT, "PERF.md"), root)
    new = copy.deepcopy(bench)
    for cell in (CELL, "serve_granite_sessions"):
        name = _append_metric(root, new, cell)
        _write(root, new)
        check_declared(new, root)
        listed = [m["name"] for m in
                  harness.Cell(CELL, root=root).per_layer()]
        assert (name in listed) == (cell == CELL)


def _rounds(**counters):
    return [{"id": i, "spans": [dict(
        {"name": "round", "t0": 0.0, "t1": 0.1, "cpu": 0.0, "parent": None},
        **{k: v + i - 1 for k, v in counters.items()})]} for i in range(3)]


def test_longcat_readers_on_hand_made_records(monkeypatch):
    from perfbench import metric_lib_glm

    # the rounds of this window, not those of a rehearsal that ran before
    # in this process: the held experts hit are what THEY counted
    hit = 10.0
    rounds = _rounds(experts_held_hit=hit, experts_routed_tokens=12288,
                     experts_held_tokens=256, experts_zero_tokens=4096)
    monkeypatch.setattr(metric_lib_glm, "read_rounds",
                        lambda records, stat, *a, **kw: stat(rounds, *a))
    cfg = harness.Cell(CELL).config
    runs = [{"name": "jit_multi", "start_s": 0.1 * i, "seconds": 0.08,
             "ops": {lib.DECODE_KERNEL: 0.001, lib.EXPERT_KERNEL: 0.001}}
            for i in range(3)]
    runs.append({"name": "jit_prefill", "start_s": 0.5, "seconds": 0.15,
                 "ops": {lib.PREFILL_KERNEL: 0.03,
                         lib.EXPERT_KERNEL: 0.02}})
    trace = {"window_s": 3.0, "busy_s": 2.8, "modules": runs, "ops": [
        ["%latent_paged_decode_attention.3 = bf16[64,64,512]{2,1,0} "
         "custom-call(", 0.3, 960],
        ["%flash_attention_fwd.2 = (bf16[1,64,4096,128]{3,2,1,0}, "
         "f32[1,64,1,4096]) custom-call(", 0.2, 72],
        ["%gmm.4 = f32[768,2048]{1,0} custom-call(", 0.25, 3600],
        ["%sort.9 = (s32[768]) sort(", 0.01, 1200],
        ["%fusion.12 = bf16[64,6144]{1,0} fusion(", 0.3, 900]]}
    rec = _records(cfg, host=HOST, seconds=51.0, traced_s=3.0)
    rec["trace"] = trace
    assert lib.decode_dispatch_ms(rec) == pytest.approx(80.0)
    assert lib.prefill_dispatch_ms(rec) == pytest.approx(150.0)
    assert lib.experts_hit(rec) == hit
    want = [sum(costs.decode_step_bytes(cfg, rows + live * j, hit)
                for j in range(4)) / 819e9 / 0.08
            for _t0, _t1, (live, rows) in HOST["step"][:2]]
    hbm = lib.decode_hbm_roofline(rec)
    assert hbm == pytest.approx(100 * sum(want) / 2) and 50 < hbm < 70
    # eight absorbed attentions a token step
    assert lib.latent_decode_attention_roofline(rec) == pytest.approx(
        100 * 8 * sum(costs.least_seconds(*costs.latent_decode_attention(
            cfg, rows + j * live, live), PEAKS)
            for live, rows in ((64, 130_000), (63, 128_000))
            for j in range(4)) / 0.3)
    # two flash forwards a layer a prefill dispatch
    assert lib.prefill_attention_roofline(rec) == pytest.approx(
        100 * 8 * sum(costs.least_seconds(
            *costs.prefill_attention(cfg, lens), PEAKS)
            for lens in ([1500, 1100], [3000])) / 0.2)
    held = 768.0 / 36864
    assert lib.expert_matmul_roofline(rec) == pytest.approx(
        100 * 4 * (sum(4 * costs.least_seconds(*costs.expert_matmuls(
            cfg, live * 12 * held, hit), PEAKS) for live in (64, 63))
            + sum(costs.least_seconds(*costs.expert_matmuls(
                cfg, n * 12 * held, 16), PEAKS) for n in (2600, 3000)))
        / 0.25)
    for read in (lib.latent_decode_attention_roofline,
                 lib.prefill_attention_roofline,
                 lib.expert_matmul_roofline):
        assert 0 < read(rec) < 100, read.__name__
    assert lib.attention_time_share(rec) == pytest.approx(
        100 * (0.3 + 0.2) / 2.8)
    assert lib.expert_time_share(rec) == pytest.approx(100 * 0.26 / 2.8)
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
                 lib.decode_hbm_roofline,
                 lib.latent_decode_attention_roofline,
                 lib.prefill_attention_roofline, lib.attention_time_share,
                 lib.expert_time_share, lib.expert_matmul_roofline):
        assert read(bare) is None, read.__name__


def test_the_rounds_counters_give_the_three_shares(monkeypatch):
    from perfbench import metric_lib_glm, metric_lib_jamba

    cfg = harness.Cell(CELL).config
    rounds = _rounds(prefill_tokens=2600, prefill_pad_tokens=1496,
                     experts_routed_tokens=12288, experts_held_tokens=257,
                     experts_zero_tokens=4097, experts_held_hit=10.0)
    monkeypatch.setattr(metric_lib_glm, "read_rounds",
                        lambda records, stat, *a, **kw: stat(rounds, *a))
    rec = {"config": cfg}
    assert lib.zero_expert_choice_share(rec) == pytest.approx(
        100.0 * 12291 / 36864)
    assert lib.held_expert_token_share(rec) == pytest.approx(
        100.0 * 771 / 36864)
    assert lib.prefill_pad_share(rec) == pytest.approx(
        metric_lib_jamba.prefill_pad_share(rounds))
    # a program that does not count them (the parent): nothing to read
    old = [{"id": 0, "spans": [{"name": "round", "t0": 0.0, "t1": 0.1,
                                "cpu": 0.0, "parent": None}]}]
    monkeypatch.setattr(metric_lib_glm, "read_rounds",
                        lambda records, stat, *a, **kw: stat(old, *a))
    for read in (lib.zero_expert_choice_share, lib.held_expert_token_share,
                 lib.prefill_pad_share):
        assert read(rec) is None, read.__name__


def test_the_traffic_is_the_issues():
    cell = harness.Cell(CELL)
    t = cell.traffic
    assert cell.spec["traffic"] == "closed_80_agentic" and cell.chips == 1
    assert (t["loop"], t["clients"], t["stagger_s"]) == ("closed", 80, 4.0)
    assert t["src_len"] == {"dist": "lognormal", "median": 1536,
                            "sigma": 0.6, "min": 256, "max": 4096}
    assert t["trg_len"] == {"dist": "lognormal", "median": 384,
                            "sigma": 0.7, "min": 32, "max": 1024}
    assert (t["ramp_s"], t["drain_s"], t["trace_s"],
            t["client_timeout_s"]) == (20.0, 60.0, 3.0, 90.0)
    theirs = harness.Cell("serve_granite_sessions").traffic
    assert set(t) == set(theirs)
    assert set(t["assumed"]) == set(theirs["assumed"])
    assert t["max_stream_backlog"] == theirs["max_stream_backlog"] == 4096
    # the plan: four requests a caller, mean prompt ~1.75 k, mean output
    # ~450
    from perfbench import loadgen

    plan = loadgen.make_plan(t, 2 ** 31 + 7, 51.0)
    src = plan["src_len"]
    assert len(src) == 320 and src.min() >= 256 and src.max() == 4096
    assert 1650 < src.mean() < 1850
    assert 420 < plan["trg_len"].mean() < 480
    assert plan["trg_len"].max() <= 1024
    # callers over slots as the issue gives them: 1.25 a slot
    assert t["clients"] * 4 == 5 * cell.config["pool"]["num_slots"]


def test_the_configuration_states_every_published_key():
    """Every key of the catalog row's ``config`` under its own name and
    value but the three that the cut changes, each with its published
    value beside it; the pool's arithmetic; the check's three limits."""
    published = {
        "attention_bias": False, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
        "rope_theta": 10000000, "attention_method": "MLA",
        "zero_expert_num": 256, "zero_expert_type": "identity",
        "moe_topk": 12}
    cfg = harness.Cell(CELL).config
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (4, 16, 16384)
    assert cfg["expert_shard"] == {"of": 512, "first": 0}
    assert cfg["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                "vocab_size": 131072}
    # the floors: four layers, at least 8 experts, an eighth of the rows
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] * 32 \
        == cfg["published"]["n_routed_experts"]
    assert cfg["entry"] == "shortcut_decoder_frontend"
    assert cfg["dtype"] == "bfloat16"
    pool = cfg["pool"]
    assert (pool["num_slots"], pool["max_prompt"], pool["max_new_tokens"],
            pool["page_size"], pool["tokens_per_dispatch"]) == (
                64, 4096, 1024, 128, 4)
    assert pool["prefill_buckets"] == [512, 1024, 2048, 4096]
    assert pool["prefill_token_budget"] == 4096
    assert pool["admit_token_budget"] == 4096
    assert pool["prefill_rungs"] is True
    # 40 pages a slot, 2561 pages a pool, EIGHT pools of 640 lanes
    pages = 1 + 64 * -(-(4096 + 1024) // 128)
    assert pages == 2561
    assert round(8 * pages * 128 * 640 * 2 / 1e9, 2) == 3.36
    check = cfg["check"]
    assert set(check["limits"]) == set(LIMITS)
    assert check["prompt_len_ranges"] == [[256, 1024], [2048, 4096]]
    assert check["positions"] == 32
    for key in ("what", "limits_why"):
        assert len(check[key]) > 200
    for key in ("tie_word_embeddings", "hidden_act", "norm_topk_prob",
                "router_bias", "rope", "mla_scales",
                "e_score_correction_bias", "router_gain", "encoders",
                "expert_shard", "vocab_size", "end_of_stream",
                "max_position_embeddings"):
        assert key in cfg["assumed"], key
    assert "32 chips share each layer" in cfg["deployment"]
    assert "nothing to overlap" in cfg["deployment"]
    # the description is this family's, and its builder takes it as it is
    from paddle_tpu.models import shortcut_moe_decoder as scd
    from paddle_tpu.models.decoder_programs import builder_for

    assert builder_for(cfg) is scd.build_shortcut_moe_decoder
    d = scd.decoder_dims(cfg)
    assert (d["q_scale"], round(d["kv_scale"], 4)) == (2.0, 3.4641)
    assert (d["Er"], d["Z"], d["E"], d["first"]) == (512, 256, 16, 0)
