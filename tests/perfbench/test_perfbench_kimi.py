"""The linear / latent decoder's cell: its CPU rehearsal through run.py's
own ``execute``, the comparison that decides ``correct`` with its three
controls (float8 operands; a bfloat16 state and nothing else changed; the
latent layer ROTATED), the kernel-cost functions against hand counts at
the published widths, the fifteen readers of ``metric_lib_kimi`` on hand-made records, and this
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
from _perfbench_kimi_tiny import tiny_cell

from perfbench import harness, kernel_costs_kimi as costs
from perfbench import metric_lib_kimi as lib

CELL = "serve_kimi_reasoning"
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
def test_kimi_cell_rehearsal(trace, rehearse, capsys):
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

    from perfbench import serve_kimi_common as common

    cell = tiny_cell()
    return common, cell, common.Server(
        cell, seed, fluid.CPUPlace(), harness.Setup(time.perf_counter()))


def test_all_three_controls_are_not_correct():
    """The reference in the program's place with float8 operands reads far
    above the program; with the state rounded to bfloat16 a token and
    NOTHING else changed it fails the state's own limits; with the latent
    layer's q_pe and k_pe ROTATED it fails ``logit_rel_l2``; the pools are
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
        assert alone["state_bf16_grid_share"] == 1.0
        assert sound["state_bf16_grid_share"] < 1e-3
        rotated = {k: control[k + "_rotated"] for k in LIMITS}
        assert rotated["logit_rel_l2"] > 1e-2
        # the layers BEFORE the latent one are untouched by the rotation,
        # the one after it is not: the state moves too
        assert rotated["state_rel_l2"] > 1e-4
        assert common.verdict(sound, limits)
        assert not common.verdict({k: control[k] for k in LIMITS}, limits)
        assert not common.verdict(alone, limits)
        assert not common.verdict(rotated, limits)
    sess = server.session
    assert sess.pool_conserved and not sess.active_slots
    assert sess.pages_in_use == 0
    # ONE latent pool beside four linear layers' arrays
    state = sess.geometry["state"]
    assert list(state["page_pools"]) == ["lad_pool_3"]
    assert len(server.state_arrays()) == 4


def test_kernel_costs_at_the_published_widths():
    cfg = harness.Cell(CELL).config
    count = costs.parameter_count(cfg)
    # ISSUE 53's arithmetic, from the config's keys
    want = {"linear_mixer": 39.52, "latent_mixer": 29.11,
            "routed_expert": 7.08, "dense_ffn": 63.70, "router": 0.59,
            "shared_expert": 7.08, "held_experts": 452.98,
            "embedding": 94.37, "total": 2282.27}
    assert {k: round(count[k] / 1e6, 2) for k in want} == want
    assert count["expert_layers"] == 4
    assert round(2 * count["total"] / 1e9, 2) == 4.56
    # the published model: 20 KDA + 7 MLA mixers, 26 x 257 experts, the
    # whole vocabulary: 49.1 B
    whole = (20 * count["linear_mixer"] + 7 * count["latent_mixer"]
             + 26 * (257 * count["routed_expert"] + count["router"])
             + count["dense_ffn"] + 2 * 163840 * 2304)
    assert round(whole / 1e9, 1) == 49.1
    # what the builder declares is what is counted
    from paddle_tpu.models.linear_attn_moe_decoder import parameter_shapes

    assert count["total"] == sum(
        int(np.prod(shape)) for shape, _dt in parameter_shapes(cfg).values())
    # a slot's state: 4 layers x (32 x 128 x 128 float32 + 3 x 12288 bf16)
    assert costs.state_bytes_per_slot(cfg) == 4 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert round(384 * 4 * 32 * 128 * 128 * 4 / 1e9, 2) == 3.22
    assert round(384 * 4 * 3 * 12288 * 2 / 1e9, 2) == 0.11
    assert costs.latent_row_bytes(cfg) == 576 * 2
    assert costs.latent_layers(cfg) == [3]
    # a decode token step with every held expert hit: 0.75 GB of weights
    # outside the experts (all but the embedding), 3.62 GB of experts
    parts = costs.decode_step_parts(cfg, 384, 384 * 1536, 64)
    assert {k: round(v / 1e9, 2) for k, v in parts.items()} == {
        "weights": 0.75, "experts": 3.62, "state": 6.67, "latent": 0.68}
    assert costs.decode_step_bytes(cfg, 384, 384 * 1536, 64) \
        == sum(parts.values())
    # the row as the pool holds it (640 lanes)
    wide = costs.decode_step_parts(cfg, 384, 384 * 1536, 64, 1280)
    assert wide["latent"] == 384 * 1536 * 1280
    assert {k: wide[k] for k in ("weights", "experts", "state")} \
        == {k: parts[k] for k in ("weights", "experts", "state")}
    assert costs.decode_step_bytes(cfg, 384, 500_000, 8) \
        - costs.decode_step_bytes(cfg, 0, 0, 8) \
        == 2 * 384 * costs.state_bytes_per_slot(cfg) + 500_000 * 1152
    ops, moved = costs.state_update(cfg, 384)
    assert ops == 7 * 384 * 32 * 128 * 128
    assert moved == 2 * 384 * 32 * 128 * 128 * 4 \
        + 384 * 4096 * (3 * 2 + 2 * 4) + 384 * 32 * 4
    # memory bound: 1.99 ms a layer a token for 384 slots
    assert round(1e3 * costs.least_seconds(ops, moved, PEAKS), 2) == 1.99
    ops, moved = costs.chunk_prefill(cfg, [1000, 3000])
    assert ops == 4000 * 32 * (6 * 128 * 128 + 2 * 64 * 256)
    assert moved == 4000 * 32 * (3 * 128 * 2 + 2 * 128 * 4 + 4) \
        + 2 * 32 * 128 * 128 * 4
    ops, moved = costs.latent_decode_attention(cfg, 500_000, 384)
    assert ops == 2 * 32 * (576 + 512) * 500_000
    assert moved == (500_000 * 576 + 384 * 32 * (576 + 512)) * 2
    # memory bound: 0.74 ms for ~500 k rows
    assert round(1e3 * costs.least_seconds(ops, moved, PEAKS), 2) == 0.74
    ops, moved = costs.prefill_attention(cfg, [300, 100])
    pairs = 300 * 301 / 2 + 100 * 101 / 2
    assert ops == 2 * 32 * pairs * (192 + 128)
    assert moved == 400 * 32 * (2 * 192 + 2 * 128) * 2
    ops, moved = costs.expert_matmuls(cfg, 768.0, 60.5)
    assert ops == 2 * 768.0 * 3 * 2304 * 1024
    assert moved == (60.5 * 3 * 2304 * 1024 + 2 * 768.0 * 2304) * 2


def _records(cfg, **serve):
    return {"config": cfg, "serve": dict({"summary": {}}, **serve),
            "peaks": dict(PEAKS)}


# the per-layer entries this cell lists, by name: the decoder-only cells'
# shared ones. Its own fifteen readers are FUNCTIONS of ``metric_lib_kimi``
# that no entry names: ``per_layer`` held its most, 128 entries, before
# this PR (PERF.md section 7, PR 53), and a PR may only add
DECLARED = list(tiny.DECODER_SHARED)
READERS = (
    "decode_dispatch_ms", "prefill_dispatch_ms", "decode_hbm_roofline",
    "state_update_roofline", "chunk_prefill_roofline",
    "latent_decode_attention_roofline", "prefill_attention_roofline",
    "expert_matmul_roofline", "linear_time_share", "latent_time_share",
    "expert_time_share", "state_bytes_share", "latent_bytes_share",
    "held_expert_token_share", "read_prefill_pad_share")
HOST = {"step": [(0.0, 0.1, (384, 600_000)), (0.1, 0.2, (383, 590_000)),
                 (0.2, 0.3, (0, 0)), (5.0, 5.1, (384, 1))],
        "admit": [(0.05, 0.09, [(2048, [1500, 1100]), (4096, [3000])]),
                  (4.0, 4.1, [(512, [300])])]}
GEOMETRY = {"latent_row_bytes": 1280}


def check_declared(bench, root):
    """The cell, its configuration and its traffic are the issue's; the
    cell reports ``serve_tokens_per_s``, ``trace_lower_s``, the three
    ``setup_`` readers the issue lists and the ten shared host-plane
    readers; ``per_layer`` is within the format's 128 entries and names
    no reader of this PR; and each of ``metric_lib_kimi``'s fifteen
    readers gives no number on records without a device trace."""
    cfg = harness.Cell(CELL, root=root).config
    bare = [_records(cfg, host=HOST, seconds=51.0, traced_s=3.0,
                     geometry=GEOMETRY), {"config": cfg}]
    tiny.check_cell_declares(bench, root, CELL, DECLARED, bare)
    for name in READERS:
        for records in bare:
            assert getattr(lib, name)(records) is None, name
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert not [name for name in by_name if name.startswith("kimi_")]
    for name in ("setup_spans_s", "setup_step_trace_lower_s",
                 "setup_shape_inference_s"):
        assert CELL in by_name[name]["workloads"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi_linear_5l", "closed_480_reasoning", 1)
    (config,) = [c for c in bench["configs"] if c["name"] == "kimi_linear_5l"]
    assert config["source"] == cfg["source"]
    assert config["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"]
    (tokens,) = [m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s"]
    assert CELL in tokens["workloads"]


def test_declared_on_the_real_tree_and_on_a_copy_with_one_more_entry(
        tmp_path):
    """This PR's entries are found by NAME, and break no declaration
    function that was there; a further per-layer entry appended at the END
    for this cell, and one for another, break nothing."""
    from test_perfbench_contract import (
        DECLARATIONS,
        _append_metric,
        _copy_perfbench,
        _write,
    )
    import shutil

    bench = harness.load_json(os.path.join(tiny.ROOT, "BENCHMARK.json"))
    check_declared(bench, tiny.ROOT)
    for check in DECLARATIONS.values():
        check(bench, tiny.ROOT)
    # the format's most: the list was full before this PR and still is
    assert len(bench["per_layer"]) == 128
    assert len(bench["workloads"]) >= 11
    assert all(w["chips"] == 1 for w in bench["workloads"][:11])
    # a later `benchmark` PR that has made room appends an entry for this
    # cell, or for another: neither breaks what is checked above
    root = _copy_perfbench(tmp_path)
    shutil.copy(os.path.join(tiny.ROOT, "PERF.md"), root)
    new = copy.deepcopy(bench)
    for cell in (CELL, "serve_solar_docreason"):
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


def test_kimi_readers_on_hand_made_records(monkeypatch):
    from perfbench import metric_lib_glm

    # the rounds of this window, not those of a rehearsal that ran before
    # in this process: the held experts hit are what THEY counted
    hit = 63.0
    rounds = _rounds(experts_held_hit=hit)
    monkeypatch.setattr(metric_lib_glm, "read_rounds",
                        lambda records, stat, *a, **kw: stat(rounds, *a))
    cfg = harness.Cell(CELL).config
    step_ops = [lib.UPDATE_KERNEL, "ssm_conv_step", lib.DECODE_KERNEL,
                lib.EXPERT_KERNEL]
    runs = [{"name": "jit_multi", "start_s": 0.1 * i, "seconds": 0.085,
             "ops": {k: 0.001 for k in step_ops}} for i in range(3)]
    runs.append({"name": "jit_prefill", "start_s": 0.5, "seconds": 0.25,
                 "ops": {lib.CHUNK_KERNEL: 0.06, lib.PREFILL_KERNEL: 0.01,
                         lib.EXPERT_KERNEL: 0.02}})
    trace = {"window_s": 3.0, "busy_s": 2.8, "modules": runs, "ops": [
        ["%delta_rule_state_update.3 = (f32[384,32,128]{2,1,0}, "
         "f32[384,32,128,128]{3,2,1,0}) custom-call(", 1.2, 384],
        ["%delta_rule_chunk_prefill.2 = (f32[2,4096,4096]{2,1,0}, "
         "f32[2,32,128,128]) custom-call(", 0.2, 12],
        ["%ssm_causal_conv.5 = bf16[2,4096,12288] custom-call(", 0.02, 12],
        ["%ssm_conv_step.4 = (bf16[384,12288], bf16[3,384,12288]) "
         "custom-call(", 0.03, 384],
        ["%latent_paged_decode_attention.7 = bf16[384,32,512] "
         "custom-call(", 0.2, 96],
        ["%flash_attention_fwd.1 = bf16[2,32,4096,128] custom-call(", 0.05,
         3],
        ["%gmm.4 = f32[3072,1024]{1,0} custom-call(", 0.5, 1200],
        ["%sort.9 = (s32[3072]) sort(", 0.01, 400],
        ["%fusion.12 = bf16[384,2304]{1,0} fusion(", 0.3, 900]]}
    rec = _records(cfg, host=HOST, seconds=51.0, traced_s=3.0,
                   geometry=GEOMETRY)
    rec["trace"] = trace
    assert lib.decode_dispatch_ms(rec) == pytest.approx(85.0)
    assert lib.prefill_dispatch_ms(rec) == pytest.approx(250.0)
    assert lib.experts_hit(rec) == hit
    want = [sum(costs.decode_step_bytes(cfg, live, rows + live * j, hit)
                for j in range(4)) / 819e9 / 0.085
            for _t0, _t1, (live, rows) in HOST["step"][:2]]
    hbm = lib.decode_hbm_roofline(rec)
    assert hbm == pytest.approx(100 * sum(want) / 2) and 60 < hbm < 75
    assert lib.state_update_roofline(rec) == pytest.approx(
        100 * 4 * 4 * sum(costs.least_seconds(
            *costs.state_update(cfg, live), PEAKS) for live in (384, 383))
        / 1.2)
    assert lib.chunk_prefill_roofline(rec) == pytest.approx(
        100 * 4 * sum(costs.least_seconds(
            *costs.chunk_prefill(cfg, lens), PEAKS)
            for lens in ([1500, 1100], [3000])) / 0.2)
    assert lib.latent_decode_attention_roofline(rec) == pytest.approx(
        100 * sum(costs.least_seconds(*costs.latent_decode_attention(
            cfg, rows + live * j, live), PEAKS)
            for live, rows in ((384, 600_000), (383, 590_000))
            for j in range(4)) / 0.2)
    assert lib.prefill_attention_roofline(rec) == pytest.approx(
        100 * sum(costs.least_seconds(
            *costs.prefill_attention(cfg, lens), PEAKS)
            for lens in ([1500, 1100], [3000])) / 0.05)
    for read in (lib.state_update_roofline, lib.chunk_prefill_roofline,
                 lib.latent_decode_attention_roofline,
                 lib.prefill_attention_roofline,
                 lib.expert_matmul_roofline):
        assert 0 < read(rec) < 100, read.__name__
    # both convolutions, the chunked prefill and the state update
    assert lib.linear_time_share(rec) == pytest.approx(
        100 * (1.2 + 0.2 + 0.02 + 0.03) / 2.8)
    assert lib.latent_time_share(rec) == pytest.approx(100 * 0.25 / 2.8)
    assert lib.expert_time_share(rec) == pytest.approx(100 * 0.51 / 2.8)
    # rounds that do not count the experts hit: no number, and none
    # assumed in its place
    for r in rounds:
        del r["spans"][0]["experts_held_hit"]
    for read in (lib.experts_hit, lib.decode_hbm_roofline,
                 lib.expert_matmul_roofline):
        assert read(rec) is None, read.__name__
    # a program without the kernels: no number
    bare = dict(rec, trace=dict(trace, ops=trace["ops"][-1:], modules=[]))
    for read in (lib.decode_dispatch_ms, lib.prefill_dispatch_ms,
                 lib.decode_hbm_roofline, lib.state_update_roofline,
                 lib.chunk_prefill_roofline, lib.linear_time_share,
                 lib.latent_time_share, lib.expert_time_share,
                 lib.latent_decode_attention_roofline,
                 lib.prefill_attention_roofline):
        assert read(bare) is None, read.__name__


def test_the_rounds_counters_give_the_four_shares(monkeypatch):
    from perfbench import metric_lib_glm, metric_lib_jamba

    cfg = harness.Cell(CELL).config
    per_slot = costs.state_bytes_per_slot(cfg)
    rounds = [{"id": i, "spans": [
        {"name": "round", "t0": 0.0, "t1": 0.1, "cpu": 0.03, "parent": None,
         "prefill_tokens": 5700, "prefill_pad_tokens": 2492,
         "prefill_chunks": 90, "prefill_chunks_padded": 38,
         "state_slots_live": 384, "state_bytes_live": 2 * 384 * per_slot,
         "kv_rows_visible": 600_000 + i,
         "experts_routed_tokens": 49152, "experts_held_tokens": 12000 + i,
         "experts_held_hit": 63.0 + 0.5 * i}]} for i in range(3)]
    monkeypatch.setattr(metric_lib_glm, "read_rounds",
                        lambda records, stat, *a, **kw: stat(rounds, *a))
    rec = {"config": cfg, "serve": {"geometry": GEOMETRY}}
    parts = costs.decode_step_parts(cfg, 384, 600_001, 63.5, 1280)
    whole = sum(parts.values())
    assert parts["state"] == 2 * 384 * per_slot
    state, latent = lib.state_bytes_share(rec), lib.latent_bytes_share(rec)
    assert state == pytest.approx(100.0 * parts["state"] / whole)
    assert latent == pytest.approx(100.0 * 600_001 * 1280 / whole)
    assert 52 < state < 58 and 5 < latent < 8
    assert lib.held_expert_token_share(rec) == pytest.approx(
        100.0 * 36003 / 147456)
    assert lib.read_prefill_pad_share(rec) == pytest.approx(
        metric_lib_jamba.prefill_pad_share(rounds))
    # a geometry without the row's bytes (a program before this PR): the
    # two byte shares read nothing
    for read in (lib.state_bytes_share, lib.latent_bytes_share):
        assert read({"config": cfg, "serve": {"geometry": {}}}) is None
        assert read({"config": cfg}) is None
    # a program that does not count them: nothing to read
    old = [{"id": 0, "spans": [{"name": "round", "t0": 0.0, "t1": 0.1,
                                "cpu": 0.0, "parent": None}]}]
    monkeypatch.setattr(metric_lib_glm, "read_rounds",
                        lambda records, stat, *a, **kw: stat(old, *a))
    for read in (lib.state_bytes_share, lib.latent_bytes_share,
                 lib.held_expert_token_share, lib.read_prefill_pad_share):
        assert read(rec) is None, read.__name__


def test_the_traffic_is_the_issues():
    cell = harness.Cell(CELL)
    t = cell.traffic
    assert cell.spec["traffic"] == "closed_480_reasoning" and cell.chips == 1
    assert (t["loop"], t["clients"], t["stagger_s"]) == ("closed", 480, 6.0)
    assert t["src_len"] == {"dist": "lognormal", "median": 768,
                            "sigma": 0.9, "min": 64, "max": 4096}
    assert t["trg_len"] == {"dist": "lognormal", "median": 1024,
                            "sigma": 0.6, "min": 128, "max": 4096}
    assert (t["ramp_s"], t["drain_s"], t["trace_s"],
            t["client_timeout_s"]) == (30.0, 150.0, 3.0, 240.0)
    theirs = harness.Cell("serve_solar_docreason").traffic
    assert set(t) == set(theirs)
    assert set(t["assumed"]) == set(theirs["assumed"])
    assert t["max_stream_backlog"] == theirs["max_stream_backlog"] == 4096
    # the plan: four requests a caller, mean prompt ~1.1 k with ~3% at the
    # cap, mean output ~1.2 k with ~1% at the cap: outputs LONGER than
    # prompts
    from perfbench import loadgen

    plan = loadgen.make_plan(t, 2 ** 31 + 7, 51.0)
    src, trg = plan["src_len"], plan["trg_len"]
    assert len(src) == 1920 and src.min() >= 64 and src.max() == 4096
    assert 1050 < src.mean() < 1120
    assert 0.025 < (src == 4096).mean() < 0.04
    assert 1180 < trg.mean() < 1250 and trg.min() >= 128
    assert 0.005 < (trg == 4096).mean() < 0.015
    assert trg.mean() > src.mean()
    # callers over slots as the issue gives them: 1.25 a slot
    assert t["clients"] * 4 == 5 * cell.config["pool"]["num_slots"]


def test_the_configuration_states_every_published_key():
    """Every key of the catalog row's ``config`` under its own name and
    value but the four that the cut changes, each with its published value
    beside it; the pool's arithmetic; the check's five limits."""
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216,
        "kv_lora_rank": 512, "mla_use_nope": True,
        "model_max_length": 1048576, "model_type": "kimi_linear",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1,
        "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
        "num_attention_heads": 32, "num_expert_group": 1,
        "num_experts_per_token": 8, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128}
    cfg = harness.Cell(CELL).config
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 64, 40960)
    lin = cfg["linear_attn_config"]
    assert lin == {"full_attn_layers": [4], "head_dim": 128,
                   "kda_layers": [1, 2, 3, 5], "num_heads": 32,
                   "short_conv_kernel_size": 4}
    assert cfg["expert_shard"] == {"of": 256, "first": 0}
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (27, 256, 163840)
    theirs = pub["linear_attn_config"]
    assert theirs["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert len(theirs["kda_layers"]) == 20
    # no width of the nested group moved; the cut is the published layers
    # 1-5: the leading dense layer and one whole period
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert lin[key] == theirs[key]
    assert [i for i in theirs["kda_layers"] if i <= 5] == lin["kda_layers"]
    assert [i for i in theirs["full_attn_layers"] if i <= 5] \
        == lin["full_attn_layers"]
    assert cfg["vocab_size"] * 4 == pub["vocab_size"]
    assert cfg["num_experts"] * 4 == pub["num_experts"]
    assert cfg["entry"] == "linear_latent_decoder_frontend"
    assert cfg["dtype"] == "bfloat16" and cfg["state_dtype"] == "float32"
    pool = cfg["pool"]
    assert (pool["num_slots"], pool["max_prompt"], pool["max_new_tokens"],
            pool["page_size"], pool["tokens_per_dispatch"]) == (
                384, 4096, 4096, 128, 4)
    assert pool["prefill_buckets"] == [512, 1024, 2048, 4096]
    assert pool["prefill_token_budget"] == 8192
    assert pool["admit_token_budget"] == 8192
    assert pool["prefill_rungs"] is True
    # 64 pages a slot, 24577 pages in ONE pool of 640 lanes: 4.03 GB
    pages = 1 + 384 * -(-(4096 + 4096) // 128)
    assert pages == 24577
    assert round(pages * 128 * 640 * 2 / 1e9, 2) == 4.03
    check = cfg["check"]
    assert set(check["limits"]) == set(LIMITS)
    assert check["prompt_len_ranges"] == [[256, 1024], [2048, 4096]]
    assert check["positions"] == 32
    assert check["control_rope_theta"] == cfg["rope_theta"]
    for key in ("what", "limits_why"):
        assert len(check[key]) > 200
    for key in ("kda_layer", "gate_rank", "layer_lists", "mla_use_nope",
                "q_lora_rank", "head_dim", "routing", "initialisers",
                "state_dtype", "expert_shard", "vocab_size", "pool",
                "end_of_stream", "model_max_length"):
        assert key in cfg["assumed"], key
    assert "4 chips share each layer" in cfg["deployment"]
    assert "20 to 7" in cfg["deployment"]
    # the description is the linear family's second naming, and its
    # builder takes the file as it is
    from paddle_tpu.models import linear_attn_moe_decoder as lad
    from paddle_tpu.models.decoder_programs import builder_for

    assert builder_for(cfg) is lad.build_linear_attn_moe_decoder
    d = lad.linear_dims(cfg)
    assert (d["Er"], d["E"], d["first"], d["k"], d["dense"]) \
        == (256, 64, 0, 8, 1)
    assert (d["W"], d["Wp"], d["beta_scale"], d["scale"]) \
        == (576, 640, 1.0, 2.446)
    assert lad.layer_kinds(cfg) == [lad.LINEAR] * 3 + [lad.LATENT,
                                                       lad.LINEAR]
