"""The Gated DeltaNet / multi-head decoder's cell: its CPU rehearsal through
run.py's own ``execute``, the comparison that decides ``correct`` with its
three controls (float8 operands; a bfloat16 state and nothing else changed;
the full layers ROTATED), the kernel-cost functions against hand counts at
the published widths, the twelve readers of ``metric_lib_olmo`` on hand-made
records, and this PR's declaration function ``check_declared(bench,
root)``, which finds its entries by NAME, on the real tree and on a copy
with one more entry appended."""

import copy
import json
import os
import time

import numpy as np
import pytest

import _perfbench_tiny as tiny
from _perfbench_olmo_tiny import tiny_cell

from perfbench import harness, kernel_costs_olmo as costs
from perfbench import metric_lib_olmo as lib

CELL = "serve_olmo_evalgen"
LIMITS = ("logit_rel_l2", "state_rel_l2", "state_bf16_grid_share")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def rehearse(tmp_path, monkeypatch):
    monkeypatch.setattr(tiny, "tiny_cell",
                        lambda name, root=tiny.ROOT: tiny_cell(name, root))
    return lambda trace: tiny.rehearse(CELL, tmp_path, trace=trace)


@pytest.mark.parametrize("trace", [0, 1])
def test_olmo_cell_rehearsal(trace, rehearse, capsys):
    cell = rehearse(trace)
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    tiny.check_line(line, cell, trace)
    text = "\n".join(out[:-1])
    for key in LIMITS:
        assert "check %s" % key in text
    assert "check expert" not in text
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

    from perfbench import serve_olmo_common as common

    cell = tiny_cell()
    return common, cell, common.Server(
        cell, seed, fluid.CPUPlace(), harness.Setup(time.perf_counter()))


def test_all_three_controls_are_not_correct():
    """The reference in the program's place with float8 operands reads far
    above the program; with the state rounded to bfloat16 a token and
    NOTHING else changed it fails the state's own limit; with the full
    layer's q and k ROTATED it fails ``logit_rel_l2`` and leaves the
    states of the layers before it where they were; the pools are drained
    after."""
    common, cell, server = _server()
    checker = common.Checker(cell, server)
    limits = cell.config["check"]["limits"]
    for seed in (3, 2 ** 31 + 5):
        sound = checker.numbers(seed)
        control = checker.control_numbers(seed)
        assert set(sound) == set(LIMITS)
        assert sound["logit_rel_l2"] < 2e-5
        assert sound["state_rel_l2"] < 2e-5
        assert control["logit_rel_l2"] > 1e-2
        assert control["state_rel_l2"] > 1e-2
        alone = {k: control[k + "_bf16_state_alone"] for k in LIMITS}
        assert alone["state_rel_l2"] > 1e-3
        assert alone["state_bf16_grid_share"] == 1.0
        assert sound["state_bf16_grid_share"] < 1e-3
        rotated = {k: control[k + "_rotated"] for k in LIMITS}
        assert rotated["logit_rel_l2"] > 1e-2
        # the tiny model's ONE full layer is its last: no linear layer
        # follows it, and the states do not see the rotation
        assert rotated["state_rel_l2"] < 1e-5
        assert common.verdict(sound, limits)
        assert not common.verdict({k: control[k] for k in LIMITS}, limits)
        assert not common.verdict(alone, limits)
        assert not common.verdict(rotated, limits)
    sess = server.session
    assert sess.pool_conserved and not sess.active_slots
    assert sess.pages_in_use == 0
    # K and V pools of the one full layer beside three linear layers'
    # arrays, whose tiles hold two heads each
    state = sess.geometry["state"]
    assert list(state["page_pools"]) == ["gdd_k_3", "gdd_v_3"]
    assert len(server.state_arrays()) == 3
    assert server.slot_states([0, 1]).shape == (2, 3, 6, 24, 64)


def test_the_servers_states_are_the_tiles_taken_apart():
    """``Server.slot_states`` undoes ``delta_rule.pack_heads``."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import delta_rule as dr

    _common, _cell, server = _server()
    rng = np.random.RandomState(0)
    plain = rng.standard_normal((6, 6, 24, 64)).astype("float32")
    for name in server.state_arrays():
        server.scope.var(name).set(dr.pack_heads(jnp.asarray(plain), 2))
    got = server.slot_states([4, 1])
    assert got.shape == (2, 3, 6, 24, 64)
    for layer in range(3):
        assert (got[:, layer] == plain[[4, 1]]).all()


def test_kernel_costs_at_the_published_widths():
    cfg = harness.Cell(CELL).config
    count = costs.parameter_count(cfg)
    # ISSUE 55's arithmetic, from the config's keys
    want = {"linear_mixer": 88.75, "full_mixer": 58.99, "ffn": 126.81,
            "linear_layer": 215.57, "full_layer": 185.81,
            "embedding": 385.35, "total": 2435.75}
    assert {k: round(count[k] / 1e6, 2) for k in want} == want
    assert round(2 * count["total"] / 1e9, 2) == 4.87
    assert round((count["total"] - 2 * count["embedding"]) / 1e6, 1) \
        == 1665.0
    # the published model: 24 linear + 8 full layers, the whole vocabulary
    whole = (24 * count["linear_layer"] + 8 * count["full_layer"]
             + 2 * count["embedding"] + 3840)
    assert round(whole / 1e9, 2) == 7.43
    # what the builder declares is what is counted
    from paddle_tpu.models.gated_delta_decoder import parameter_shapes

    assert count["total"] == sum(
        int(np.prod(shape)) for shape, _dt in parameter_shapes(cfg).values())
    assert costs.state_bytes_per_slot_layer(cfg) == 30 * 96 * 192 * 4
    assert costs.window_bytes_per_slot_layer(cfg) == 3 * 11520 * 2
    assert costs.kv_row_bytes(cfg) == 15360
    assert round(96 * 6 * 30 * 96 * 192 * 4 / 1e9, 2) == 1.27
    assert round(96 * 6 * 3 * 11520 * 2 / 1e9, 2) == 0.04
    # a decode token step of 96 slots at ~850 rows: 4.10 GB of weights
    # (all but the embedding), the state twice, the rows once
    parts = costs.decode_step_parts(cfg, 96, 96 * 850)
    assert {k: round(v / 1e9, 2) for k, v in parts.items()} == {
        "weights": 4.1, "state": 2.63, "rows": 2.51}
    assert costs.decode_step_bytes(cfg, 96, 96 * 850) == sum(parts.values())
    # 11.3 ms a token at 819 GB/s
    assert round(1e3 * sum(parts.values()) / 819e9, 1) == 11.3
    # a state held a head a tile (192 lanes laid out as 256): the array's
    # bytes, not the algorithm's
    wide = costs.decode_step_parts(cfg, 96, 96 * 850, 30 * 96 * 256 * 4)
    assert wide["state"] == 2 * 96 * 6 * (30 * 96 * 256 * 4 + 69120)
    assert wide["weights"] == parts["weights"]
    assert wide["rows"] == parts["rows"]
    ops, moved = costs.state_update(cfg, 96)
    assert ops == 7 * 96 * 30 * 96 * 192
    assert moved == 2 * 96 * 30 * 96 * 192 * 4 \
        + 96 * 30 * (2 * 96 + 192) * 2 + 96 * 30 * (2 + 192) * 4
    # memory bound: 0.52 ms a layer a token for 96 slots
    assert round(1e3 * costs.least_seconds(ops, moved, PEAKS), 2) == 0.52
    ops, moved = costs.chunk_prefill(cfg, [1000, 300])
    assert ops == 1300 * 30 * (6 * 96 * 192 + 2 * 64 * (96 + 192))
    assert moved == 1300 * 30 * ((2 * 96 + 192) * 2 + 2 * 4 + 192 * 4) \
        + 2 * 30 * 96 * 192 * 4
    ops, moved = costs.mha_decode_attention(cfg, 80_000, 96)
    assert ops == 4 * 3840 * 80_000
    assert moved == (2 * 80_000 + 2 * 96) * 3840 * 2
    # memory bound: 1.5 ms a layer a token for 80 k rows
    assert round(1e3 * costs.least_seconds(ops, moved, PEAKS), 2) == 1.5
    ops, moved = costs.prefill_attention(cfg, [300, 100])
    pairs = 300 * 301 / 2 + 100 * 101 / 2
    assert ops == 4 * 30 * pairs * 128
    assert moved == 400 * 4 * 3840 * 2
    ops, moved = costs.causal_conv(cfg, 1000)
    assert ops == 2 * 1000 * 4 * 11520 and moved == 2 * 1000 * 11520 * 2


def _records(cfg, **serve):
    return {"config": cfg, "serve": dict({"summary": {}}, **serve),
            "peaks": dict(PEAKS)}


# the per-layer entries this cell lists, by name: the decoder-only cells'
# shared ones. Its own twelve readers are FUNCTIONS of ``metric_lib_olmo``
# that no entry names: ``per_layer`` holds its most, 128 entries (PERF.md
# section 7, PR 53 (g) and PR 55), and a PR may only add
DECLARED = list(tiny.DECODER_SHARED)
HOST = {"step": [(0.0, 0.1, (96, 82_000)), (0.1, 0.2, (95, 80_000)),
                 (0.2, 0.3, (0, 0)), (5.0, 5.1, (96, 1))],
        "admit": [(0.05, 0.09, [(1024, [900, 700]), (512, [300])]),
                  (4.0, 4.1, [(256, [200])])]}
GEOMETRY = {"state_bytes_slot_layer": 2211840, "kv_row_bytes": 15360}


def check_declared(bench, root):
    """The cell, its configuration and its traffic are the issue's; the
    cell reports ``serve_tokens_per_s``, ``trace_lower_s``, the three
    ``setup_`` readers the issue lists and the ten shared host-plane
    readers; ``per_layer`` is within the format's 128 entries and names no
    reader of this PR; and each of ``metric_lib_olmo``'s twelve readers
    gives no number on records without a device trace."""
    cfg = harness.Cell(CELL, root=root).config
    bare = [_records(cfg, host=HOST, seconds=51.0, traced_s=3.0,
                     geometry=GEOMETRY), {"config": cfg}]
    tiny.check_cell_declares(bench, root, CELL, DECLARED, bare)
    assert len(lib.READERS) == 12
    for name, read in lib.READERS.items():
        assert name.startswith("olmo_")
        for records in bare:
            assert read(records) is None, name
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert not [name for name in by_name if name.startswith("olmo_")]
    for name in ("setup_spans_s", "setup_step_trace_lower_s",
                 "setup_shape_inference_s"):
        assert CELL in by_name[name]["workloads"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo_hybrid_8l", "closed_120_evalgen", 1)
    (config,) = [c for c in bench["configs"] if c["name"] == "olmo_hybrid_8l"]
    assert config["source"] == cfg["source"]
    assert config["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "layer_types"]
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
    assert len(bench["workloads"]) >= 12
    assert all(w["chips"] == 1 for w in bench["workloads"][:12])
    # a later `benchmark` PR that has made room appends an entry for this
    # cell, or for another: neither breaks what is checked above
    root = _copy_perfbench(tmp_path)
    shutil.copy(os.path.join(tiny.ROOT, "PERF.md"), root)
    new = copy.deepcopy(bench)
    for cell in (CELL, "serve_kimi_reasoning"):
        name = _append_metric(root, new, cell)
        _write(root, new)
        check_declared(new, root)
        listed = [m["name"] for m in
                  harness.Cell(CELL, root=root).per_layer()]
        assert (name in listed) == (cell == CELL)


def test_olmo_readers_on_hand_made_records():
    cfg = harness.Cell(CELL).config
    step_ops = [lib.UPDATE_KERNEL, "ssm_conv_step", lib.DECODE_KERNEL]
    runs = [{"name": "jit_multi", "start_s": 0.1 * i, "seconds": 0.066,
             "ops": {k: 0.001 for k in step_ops}} for i in range(3)]
    runs.append({"name": "jit_prefill", "start_s": 0.5, "seconds": 0.25,
                 "ops": {lib.CHUNK_KERNEL: 0.06, lib.PREFILL_KERNEL: 0.01}})
    trace = {"window_s": 3.0, "busy_s": 2.8, "modules": runs, "ops": [
        ["%delta_rule_state_update.3 = (f32[96,15,384]{2,1,0}, "
         "f32[96,15,96,384]{3,2,1,0}) custom-call(", 0.9, 576],
        ["%delta_rule_chunk_prefill.2 = (f32[8,30,1024,192]{3,2,1,0}, "
         "f32[8,30,96,192]) custom-call(", 0.2, 12],
        ["%ssm_causal_conv.5 = bf16[8,1024,11520] custom-call(", 0.02, 12],
        ["%ssm_conv_step.4 = (bf16[96,11520], bf16[3,96,11520]) "
         "custom-call(", 0.03, 576],
        ["%gqa_paged_decode_attention.7 = bf16[96,480,128] "
         "custom-call(", 0.5, 192],
        ["%flash_attention_fwd.1 = bf16[8,30,1024,128] custom-call(", 0.05,
         4],
        ["%fusion.12 = bf16[96,3840]{1,0} fusion(", 0.3, 900]]}
    rec = _records(cfg, host=HOST, seconds=51.0, traced_s=3.0,
                   geometry=GEOMETRY)
    rec["trace"] = trace
    assert lib.decode_dispatch_ms(rec) == pytest.approx(66.0)
    assert lib.prefill_dispatch_ms(rec) == pytest.approx(250.0)
    want = [sum(costs.decode_step_bytes(cfg, live, rows + live * j)
                for j in range(4)) / 819e9 / 0.066
            for _t0, _t1, (live, rows) in HOST["step"][:2]]
    hbm = lib.decode_hbm_roofline(rec)
    assert hbm == pytest.approx(100 * sum(want) / 2) and 60 < hbm < 75
    assert lib.state_update_roofline(rec) == pytest.approx(
        100 * 6 * 4 * sum(costs.least_seconds(
            *costs.state_update(cfg, live), PEAKS) for live in (96, 95))
        / 0.9)
    assert lib.chunk_prefill_roofline(rec) == pytest.approx(
        100 * 6 * sum(costs.least_seconds(
            *costs.chunk_prefill(cfg, lens), PEAKS)
            for lens in ([900, 700], [300])) / 0.2)
    assert lib.mha_decode_attention_roofline(rec) == pytest.approx(
        100 * 2 * sum(costs.least_seconds(*costs.mha_decode_attention(
            cfg, rows + live * j, live), PEAKS)
            for live, rows in ((96, 82_000), (95, 80_000))
            for j in range(4)) / 0.5)
    assert lib.prefill_attention_roofline(rec) == pytest.approx(
        100 * 2 * sum(costs.least_seconds(
            *costs.prefill_attention(cfg, lens), PEAKS)
            for lens in ([900, 700], [300])) / 0.05)
    for read in (lib.state_update_roofline, lib.chunk_prefill_roofline,
                 lib.mha_decode_attention_roofline,
                 lib.prefill_attention_roofline, lib.decode_hbm_roofline):
        assert 0 < read(rec) < 100, read.__name__
    # both convolutions, the chunked prefill and the state update
    assert lib.linear_time_share(rec) == pytest.approx(
        100 * (0.9 + 0.2 + 0.02 + 0.03) / 2.8)
    assert lib.attention_time_share(rec) == pytest.approx(100 * 0.55 / 2.8)
    # a program without the kernels: no number
    bare = dict(rec, trace=dict(trace, ops=trace["ops"][-1:], modules=[]))
    for read in (lib.decode_dispatch_ms, lib.prefill_dispatch_ms,
                 lib.decode_hbm_roofline, lib.state_update_roofline,
                 lib.chunk_prefill_roofline, lib.linear_time_share,
                 lib.attention_time_share,
                 lib.mha_decode_attention_roofline,
                 lib.prefill_attention_roofline):
        assert read(bare) is None, read.__name__


def test_the_rounds_counters_give_the_three_shares(monkeypatch):
    from perfbench import metric_lib_glm, metric_lib_jamba

    cfg = harness.Cell(CELL).config
    rounds = [{"id": i, "spans": [
        {"name": "round", "t0": 0.0, "t1": 0.1, "cpu": 0.03, "parent": None,
         "prefill_tokens": 1900, "prefill_pad_tokens": 660,
         "prefill_chunks": 31, "prefill_chunks_padded": 9,
         "state_slots_live": 96, "state_bytes_live": 2 * 96 * 6 * 2280960,
         "kv_rows_visible": 81_600 + i}]} for i in range(3)]
    monkeypatch.setattr(metric_lib_glm, "read_rounds",
                        lambda records, stat, *a, **kw: stat(rounds, *a))
    rec = {"config": cfg, "serve": {"geometry": GEOMETRY}}
    parts = costs.decode_step_parts(cfg, 96, 81_601)
    whole = sum(parts.values())
    # the program's counter counts what the cost model counts: the state
    # and the window of every linear layer, read and written
    assert parts["state"] == rounds[0]["spans"][0]["state_bytes_live"]
    state, rows = lib.state_bytes_share(rec), lib.kv_bytes_share(rec)
    assert state == pytest.approx(100.0 * parts["state"] / whole)
    assert rows == pytest.approx(100.0 * 81_601 * 2 * 15360 / whole)
    assert 26 < state < 30 and 25 < rows < 29
    assert lib.read_prefill_pad_share(rec) == pytest.approx(
        metric_lib_jamba.prefill_pad_share(rounds))
    # a state the array pads (a head a tile, 256 lanes for 192) reads as
    # a LARGER share of the step's bytes
    padded = {"config": cfg, "serve": {"geometry": dict(
        GEOMETRY, state_bytes_slot_layer=30 * 96 * 256 * 4)}}
    assert lib.state_bytes_share(padded) > state + 4
    # a geometry without the bytes (a program before this PR): the two
    # byte shares read nothing
    for read in (lib.state_bytes_share, lib.kv_bytes_share):
        assert read({"config": cfg, "serve": {"geometry": {}}}) is None
        assert read({"config": cfg}) is None
    # a program that does not count them: nothing to read
    old = [{"id": 0, "spans": [{"name": "round", "t0": 0.0, "t1": 0.1,
                                "cpu": 0.0, "parent": None}]}]
    monkeypatch.setattr(metric_lib_glm, "read_rounds",
                        lambda records, stat, *a, **kw: stat(old, *a))
    for read in (lib.state_bytes_share, lib.kv_bytes_share,
                 lib.read_prefill_pad_share):
        assert read(rec) is None, read.__name__


def test_the_traffic_is_the_issues():
    cell = harness.Cell(CELL)
    t = cell.traffic
    assert cell.spec["traffic"] == "closed_120_evalgen" and cell.chips == 1
    assert (t["loop"], t["clients"], t["stagger_s"]) == ("closed", 120, 4.0)
    assert t["src_len"] == {"dist": "lognormal", "median": 512,
                            "sigma": 0.6, "min": 64, "max": 1024}
    assert t["trg_len"] == {"dist": "lognormal", "median": 384,
                            "sigma": 0.6, "min": 32, "max": 1024}
    assert (t["ramp_s"], t["drain_s"], t["trace_s"]) == (20.0, 60.0, 3.0)
    theirs = harness.Cell("serve_kimi_reasoning").traffic
    assert set(t) == set(theirs)
    assert set(t["assumed"]) == set(theirs["assumed"])
    assert (t["client_timeout_s"], t["max_stream_backlog"]) == (
        theirs["client_timeout_s"], theirs["max_stream_backlog"])
    # the plan: four requests a caller; prompts longer than outputs
    from perfbench import loadgen

    plan = loadgen.make_plan(t, 2 ** 31 + 7, 51.0)
    src, trg = plan["src_len"], plan["trg_len"]
    assert len(src) == 480 and src.min() >= 64 and src.max() == 1024
    assert 540 < src.mean() < 600
    assert 0.08 < (src == 1024).mean() < 0.16
    assert 420 < trg.mean() < 470 and trg.min() >= 32
    assert 0.03 < (trg == 1024).mean() < 0.08
    # callers over slots as the issue gives them: 1.25 a slot
    assert t["clients"] * 4 == 5 * cell.config["pool"]["num_slots"]


def test_the_configuration_states_every_published_key():
    """Every key of the catalog row's ``config`` under its own name and
    value but the two that the cut changes, each with its published value
    beside it; the pool's arithmetic; the check's three limits."""
    period = ["linear_attention"] * 3 + ["full_attention"]
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "hidden_act": "silu", "max_position_embeddings": 65536,
        "attention_bias": False, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    cfg = harness.Cell(CELL).config
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"] == period * 2
    pub = cfg["published"]
    assert pub == {"num_hidden_layers": 32, "layer_types": period * 8}
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["entry"] == "gated_delta_decoder_frontend"
    assert cfg["dtype"] == "bfloat16" and cfg["state_dtype"] == "float32"
    pool = cfg["pool"]
    assert (pool["num_slots"], pool["max_prompt"], pool["max_new_tokens"],
            pool["page_size"], pool["tokens_per_dispatch"]) == (
                96, 1024, 1024, 128, 4)
    assert pool["prefill_buckets"] == [256, 512, 1024]
    assert pool["prefill_token_budget"] == 8192
    assert pool["admit_token_budget"] == 8192
    assert pool["prefill_rungs"] is True
    # 16 pages a slot, 1537 pages in each of 4 pools of 3840 lanes: 6.04 GB
    pages = 1 + 96 * -(-(1024 + 1024) // 128)
    assert pages == 1537
    assert round(4 * pages * 128 * 3840 * 2 / 1e9, 2) == 6.04
    check = cfg["check"]
    assert set(check["limits"]) == set(LIMITS)
    assert check["prompt_len_ranges"] == [[64, 256], [768, 1024]]
    assert check["positions"] == 32
    assert check["control_rope_theta"] == 500000.0
    for key in ("what", "limits_why"):
        assert len(check[key]) > 200
    for key in ("gdn_layer", "block", "full_layer", "rope_parameters",
                "ffn", "initialisers", "state_dtype", "vocab_size", "pool",
                "prefill", "end_of_stream", "max_position_embeddings"):
        assert key in cfg["assumed"], key
    assert "4 pipeline stages of 8 layers" in cfg["deployment"]
    assert "ONE chip holds each layer whole" in cfg["deployment"]
    # the description is served as the file has it
    from paddle_tpu.models import gated_delta_decoder as gdd
    from paddle_tpu.models.decoder_programs import builder_for

    assert builder_for(cfg) is gdd.build_gated_delta_decoder
    d = gdd.dims(cfg)
    assert (d["H"], d["dh"], d["Hl"], d["dk"], d["dv"], d["pack"]) \
        == (30, 128, 30, 96, 192, 2)
    assert (d["lw"], d["row"], d["beta_scale"]) == (11520, 3840, 2.0)
