"""The latent-attention, routed-expert decoder's cell: its CPU rehearsal
through run.py's own ``execute``, the comparison that decides ``correct``
with its control, the kernel-cost functions against hand counts at the
published widths, and the ``glm_`` readers on synthetic records."""

import json
import time

import pytest

import _perfbench_tiny as tiny
from _perfbench_glm_tiny import tiny_cell

from perfbench import harness, kernel_costs_glm as costs, loadgen
from perfbench import metric_lib_glm as lib

CELL = "serve_glm_saturated"


@pytest.fixture
def rehearse(tmp_path, monkeypatch):
    monkeypatch.setattr(tiny, "tiny_cell",
                        lambda name, root=tiny.ROOT: tiny_cell(name, root))
    return lambda trace: tiny.rehearse(CELL, tmp_path, trace=trace)


@pytest.mark.parametrize("trace", [0, 1])
def test_glm_cell_rehearsal(trace, rehearse, capsys):
    cell = rehearse(trace)
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    tiny.check_line(line, cell, trace)
    text = "\n".join(out[:-1])
    for key in ("logit_rel_l2", "expert_choice_diff_share",
                "expert_choice_margin_max"):
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
        # no device trace on the CPU: the set-up metrics (the counter's
        # reader is the one that was there: the cell lists itself), the
        # generator's lateness (the host's clock) and nothing of the
        # device's
        assert set(line["metrics"]) == {
            "build_s", "compile_s", "cache_misses", "trace_lower_s",
            "glm_loadgen_late_p99_ms"}
        assert line["metrics"]["trace_lower_s"]["value"] > 0
    else:
        assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_control_is_not_correct():
    """The reference in the program's place one precision down (float8
    operands) reads far above the program, on every number."""
    import paddle_tpu as fluid

    from perfbench import serve_glm_common as common

    cell = tiny_cell()
    server = common.Server(cell, 3, fluid.CPUPlace(),
                           harness.Setup(time.perf_counter()))
    checker = common.Checker(cell, server)
    for seed in (3, 2 ** 31 + 5):
        sound = checker.numbers(seed)
        control = checker.control_numbers(seed)
        assert sound["logit_rel_l2"] < 1e-5
        assert sound["expert_choice_diff_share"] == 0.0
        assert control["logit_rel_l2"] > 1e-2
        assert control["expert_choice_diff_share"] > 0.0
        assert control["expert_choice_margin_max"] > 0.0
        assert common.verdict(sound, cell.config["check"]["limits"])
        assert not common.verdict(control, cell.config["check"]["limits"])
    assert server.session.pool_conserved


def test_kernel_costs_at_the_published_widths():
    cfg = harness.Cell(CELL).config
    count = costs.parameter_count(cfg)
    # ISSUE 27's arithmetic, from the config's keys
    assert costs.attention_parameters(cfg) == 21_759_232
    assert costs.expert_parameters(cfg) == 9_437_184
    assert count["expert_layer_outside_experts"] == 31_331_648
    assert count["dense_layer"] == 84_677_888
    assert count["embedding"] + count["head"] == 634_388_480
    assert round(count["total"] / 1e6, 1) == 3895.6
    assert costs.cached_bytes_per_token(cfg) == 6912
    assert costs.cached_bytes_per_token(dict(cfg, num_hidden_layers=1)) \
        == 1152
    # a decode token step: every parameter but the embedding table
    assert round(costs.decode_step_bytes(cfg, 0) / 1e9, 2) == 7.16
    assert costs.decode_step_bytes(cfg, 160_000) \
        - costs.decode_step_bytes(cfg, 0) == 160_000 * 6912
    # 1024 pairs touch all 64 experts: 64 x 9.44 M x 2 bytes + the rows
    ops, moved = costs.expert_matmuls(cfg, 1024)
    assert ops == 2 * 1024 * 9_437_184
    assert moved == (64 * 9_437_184 + 2 * 1024 * 2048) * 2
    assert costs.expert_matmuls(cfg, 8)[1] < moved / 7     # 8 experts at most
    ops, moved = costs.latent_decode_attention(cfg, 1000, 10)
    assert ops == 2 * 20 * (576 + 512) * 1000
    assert moved == (1000 * 576 + 10 * 20 * 1088) * 2
    ops, moved = costs.prefill_attention(cfg, [4, 2])
    assert ops == 2 * 20 * (10 + 3) * 512
    assert moved == 6 * 20 * 1024 * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert costs.least_seconds(197e12, 1.0, peaks) == 1.0
    assert costs.least_seconds(1.0, 819e9 * 2, peaks) == 2.0


def _records(cfg, **serve):
    return {"config": cfg, "serve": dict({"summary": {}}, **serve),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


# the per-layer entries PR 27 declared for this cell, by name
DECLARED = [
    "glm_decode_dispatch_device_ms", "glm_prefill_dispatch_device_ms",
    "glm_decode_hbm_roofline", "glm_expert_matmul_roofline",
    "glm_latent_decode_attention_roofline", "glm_prefill_attention_roofline",
    "glm_expert_time_share"] + tiny.DECODER_SHARED
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


def test_glm_readers_on_synthetic_records():
    cfg = harness.Cell(CELL).config
    step_ops = ["latent_paged_decode_attention", "gmm"]
    runs = [{"name": "jit_multi", "start_s": 0.1 * i, "seconds": 0.08,
             "ops": {k: 0.01 for k in step_ops}} for i in range(3)]
    runs.append({"name": "jit_prefill", "start_s": 0.5, "seconds": 0.03,
                 "ops": {"flash_attention_fwd": 0.002,
                         "gmm": 0.01}})
    trace = {"window_s": 1.0, "busy_s": 0.6, "modules": runs, "ops": [
        ["%latent_paged_decode_attention.3 = bf16[256,32,512]{2,1,0} "
         "custom-call(...)", 0.03, 72],
        ["%gmm.7 = f32[1024,1536]{1,0} custom-call(", 0.09, 190],
        ["%sort.128 = (f32[256,64]{0,1}, s32[256,64]{0,1}) sort(", 0.001, 80],
        ["%flash_attention_fwd.2 = (bf16[2,20,1024,256]", 0.002, 6]]}
    rec = _records(cfg, host=HOST, seconds=51.0, traced_s=3.0)
    rec["trace"] = trace
    assert lib.decode_dispatches(rec) == [(250, 150_000), (252, 151_000)]
    assert lib.prefill_dispatches(rec) == [(512, [400, 300]), (128, [90])]
    assert lib.module_ms(rec, lib.DECODE_KERNEL) == pytest.approx(80.0)
    assert lib.module_ms(rec, lib.PREFILL_KERNEL) == pytest.approx(30.0)
    # 4 token steps of 7.16 GB + ~1.04 GB of rows at 819 GB/s, over 80 ms
    want = [sum(costs.decode_step_bytes(cfg, rows + live * j)
                for j in range(4)) / 819e9 / 0.08
            for live, rows in ((250, 150_000), (252, 151_000))]
    hbm = lib.decode_hbm_roofline(rec)
    assert hbm == pytest.approx(100 * sum(want) / 2) and 49 < hbm < 51
    assert lib.expert_time_share(rec) == pytest.approx(100 * 0.091 / 0.6)
    for read in (lib.expert_matmul_roofline,
                 lib.latent_decode_attention_roofline,
                 lib.prefill_attention_roofline):
        assert 0 < read(rec) < 100
    check_declared(harness.load_json(tiny.ROOT + "/BENCHMARK.json"),
                   tiny.ROOT)
    rounds = [{"id": 1, "spans": [
        {"name": "round", "t0": 0.0, "t1": 0.100, "cpu": 0.03,
         "parent": None, "prefill_prompts": 6, "prefill_dispatches": 2},
        {"name": "wait", "t0": 0.0, "t1": 0.010, "cpu": 0.0, "parent": 0},
        {"name": "admit", "t0": 0.010, "t1": 0.040, "cpu": None,
         "parent": 0},
        {"name": "prefill", "t0": 0.011, "t1": 0.039, "cpu": None,
         "parent": 2},
        {"name": "prefill.dispatch", "t0": 0.012, "t1": 0.037,
         "cpu": None, "parent": 3},
        {"name": "step", "t0": 0.040, "t1": 0.095, "cpu": 0.01,
         "parent": 0},
        {"name": "step.dispatch", "t0": 0.041, "t1": 0.091, "cpu": 0.001,
         "parent": 5}]}]
    assert lib.round_host_ms_p50(rounds) == pytest.approx(15.0)
    # no CPU time on the prefill's dispatch: no share; with it, the host
    # time's 15 ms less the thread's 30 - 0 - 1 - 20 = 9 ms of running
    assert lib.worker_offcpu_share(rounds) is None
    rounds[0]["spans"][4]["cpu"] = 0.020
    assert lib.worker_offcpu_share(rounds) == pytest.approx(40.0)
    assert lib.prefill_prompts_per_dispatch_p50(rounds) == 3.0


def test_an_idle_gap_is_laid_at_the_spans_it_overlaps():
    from perfbench import program_records as pr

    # one round: dispatch 0-50, handoff 50-70, admit 72-100 with a
    # prefill dispatch 80-100 inside; times in ns
    forest = pr.nest([["round", 0.0, 100.0], ["step.dispatch", 0.0, 50.0],
                      ["handoff", 50.0, 20.0], ["admit", 72.0, 28.0],
                      ["prefill.dispatch", 80.0, 20.0]])
    out = {}
    # the device idles from 45 to 89 and from 120 to 130 (no round)
    assert lib.lay_idle(forest, 45.0, 89.0, out) == 44.0
    assert lib.lay_idle(forest, 120.0, 130.0, out) == 0.0
    assert {k: round(v * 1e9, 6) for k, v in out.items()} == {
        "step.dispatch": 5.0, "handoff": 20.0, "round": 2.0, "admit": 8.0,
        "prefill.dispatch": 9.0}
    # the reader that was there lays all 44 at the bare round (no child
    # covers half of the gap)
    assert pr.idle_by_span([(45.0, 89.0)],
                           [[["round", 0.0, 100.0],
                             ["step.dispatch", 0.0, 50.0],
                             ["handoff", 50.0, 20.0], ["admit", 72.0, 28.0],
                             ["prefill.dispatch", 80.0, 20.0]]]) \
        == {"round": 4.4e-08}


def test_the_window_is_placed_though_one_stamp_lies_off():
    """The worker lost the lock between the benchmark's stamp and the
    program's span in ONE step of forty: the placement that was there
    reads every round, this cell's reads the window's."""
    from perfbench import program_records as pr

    def rnd(i, t0):
        return {"id": i, "spans": [
            {"name": "round", "t0": t0, "t1": t0 + 0.2, "cpu": 0.0,
             "parent": None},
            {"name": "step", "t0": t0 + 0.1, "t1": t0 + 0.19, "cpu": 0.0,
             "parent": 0}]}

    opening = 1000.0
    # 5 ramp rounds, 40 in a window of 8.6 s, 5 of the drain; no two
    # rounds as long as each other, as on a machine
    starts = [opening + 0.01 + 0.2 * (i - 5) + 0.0001 * i * i
              for i in range(50)]
    rounds = [rnd(i, t) for i, t in enumerate(starts)]
    inside = [r["spans"][1]["t0"] for r in rounds[5:45]]
    outside = [(t - opening - 2e-5, t - opening + 0.09, (256, 1))
               for t in inside]
    rec = _records({}, host={"step": list(outside), "admit": []},
                   seconds=starts[45] - opening - 0.05, traced_s=3.0)
    assert pr.window_rounds(rec, rounds) == rounds[5:45]
    assert lib.window_rounds(rec, rounds) == rounds[5:45]
    outside[17] = (outside[17][0] - 0.004,) + outside[17][1:]
    rec["serve"]["host"]["step"] = outside
    assert pr.window_rounds(rec, rounds) == rounds
    assert lib.window_rounds(rec, rounds) == rounds[5:45]
    # stamps that belong to other steps line up nowhere
    rec["serve"]["host"]["step"] = [(0.37 * i, 0.0, (1, 1))
                                    for i in range(40)]
    assert lib.window_rounds(rec, rounds) == rounds


def test_the_mix_is_the_generators_own():
    """``closed_320_chat`` has the keys of the closed mix that was there;
    prompts and outputs are quantiles of their own distributions in every
    seed, and the plan is four requests a caller."""
    cell = harness.Cell(CELL)
    assert set(cell.traffic) == set(
        harness.Cell("serve_base_saturated").traffic)
    for seed in (1, 2 ** 31 + 9):
        plan = loadgen.make_plan(cell.traffic, seed, 51)
        assert len(plan["src_len"]) == 1280
        assert sorted(plan["trg_len"]) == sorted(loadgen.draw_lengths(
            cell.traffic["trg_len"], 1280))
        assert 32 <= plan["src_len"].min() and plan["src_len"].max() == 1024
        assert 16 <= plan["trg_len"].min() and plan["trg_len"].max() == 512
        assert 440 < plan["src_len"].mean() < 470
        assert 180 < plan["trg_len"].mean() < 195
        assert (plan["src_len"] + plan["trg_len"]).max() <= 1536
