"""CPU rehearsals of the serving cells through run.py's own functions:
the paged session behind the frontend, the load generator's child process,
the comparison with the reference, and the result line."""

import json

import pytest

from _perfbench_tiny import check_line, rehearse, tiny_cell


@pytest.mark.parametrize("name,trace", [("serve_base_steady", 0),
                                        ("serve_base_steady", 1),
                                        ("serve_base_saturated", 0),
                                        ("serve_base_saturated", 1)])
def test_serve_cell_rehearsal(name, trace, tmp_path, capsys):
    cell = rehearse(name, tmp_path, trace=trace)
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    check_line(line, cell, trace)
    text = "\n".join(out[:-1])
    assert "check logit_rel_l2" in text and "(limit" in text
    assert "check pool conserved after the run: True" in text
    assert "ttft_ms over %d requests" % line["attempted"] in text
    assert "programs compiled inside the measured window: 0" in text
    for part in ("startup_init", "program_build", "reference_check",
                 "warmup_dispatches", "frontend_start", "ramp"):
        assert part in text
    if trace:
        host_side = {"build_s", "compile_s", "cache_misses",
                     "trace_lower_s",
                     "loadgen_late_p99_ms", "sat_loadgen_late_p99_ms",
                     "queue_wait_p50_ms", "admit_ms_p50",
                     "sat_admits_per_s", "sat_dispatch_gap_p50_ms"}
        assert set(line["metrics"]) <= host_side
        # a counter of the compile cache's statistics, not of the trace
        assert line["metrics"]["trace_lower_s"]["value"] > 0


def test_bf16_control_is_not_correct(tmp_path):
    """The control at a size a test run can hold: the reference itself in
    the program's place, in bfloat16, reads above the cell's limit. On the
    CPU the program's float32 is exact, so it sits on the HIGHEST
    reference and not on the stated-precision one the chip is judged
    against."""
    import time

    import paddle_tpu as fluid

    from perfbench import harness, serve_common

    cell = tiny_cell("serve_base_steady")
    server = serve_common.Server(cell, 3, fluid.CPUPlace(),
                                 harness.Setup(time.perf_counter()))
    checker = serve_common.Checker(cell.config, server)
    for seed in (3, 4, 2 ** 31 + 5):
        sound = checker.numbers(seed)
        control = checker.control_numbers(seed)
        limits = harness.Cell("serve_base_steady").config["check"]["limits"]
        assert sound["logit_rel_l2_vs_highest"] < 1e-5
        assert control["logit_rel_l2_vs_highest"] > 1e-3
        assert control["logit_rel_l2"] > limits["logit_rel_l2"]
        assert not serve_common.verdict(control, limits)
