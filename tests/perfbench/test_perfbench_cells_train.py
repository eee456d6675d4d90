"""CPU rehearsals of the training cells through run.py's own functions
(interpret-mode kernels, two seconds): control flow and the result line,
never a device number."""

import json

import pytest

from _perfbench_tiny import check_line, rehearse


@pytest.mark.parametrize("name,trace", [("train_big_1chip", 0),
                                        ("train_big_1chip", 1)])
def test_train_cell_rehearsal(name, trace, tmp_path, capsys):
    cell = rehearse(name, tmp_path, trace=trace)
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    check_line(line, cell, trace)
    text = "\n".join(out[:-1])
    assert "setup_s" in text and "taken apart" in text
    assert "check grad_rel_l2" in text and "(limit" in text
    assert "programs compiled inside the measured window: 0" in text
    assert "full collections (generation 2) inside the window:" in text
    if trace:
        # on the CPU no device metric is read: only host-side ones
        assert set(line["metrics"]) == {"build_s", "compile_s",
                                        "cache_misses", "trace_lower_s",
                                        "train_step_host_max_ms",
                                        "train_dispatch_host_ms"}
        assert "busy_s" not in line["device"]


@pytest.mark.parametrize("metric,train,want", [
    ("train_step_host_max_ms",
     {"step_seconds": [0.4, 0.5, 4.0, 0.3], "profiler_stop_step": 2}, 500.0),
    ("train_step_host_max_ms",
     {"step_seconds": [0.4, 0.5, 4.0], "profiler_stop_step": None}, 4000.0),
    ("train_step_host_max_ms", {"step_seconds": []}, None),
    ("train_dispatch_host_ms",
     {"dispatch_seconds": [0.006, 0.008, 0.010]}, 8.0),
    ("train_dispatch_host_ms", {"dispatch_seconds": []}, None),
])
def test_host_step_readers(metric, train, want):
    import os

    from perfbench import harness

    reader = harness.load_module(
        os.path.join(harness.ROOT, "perfbench", "layer_metrics",
                     metric + ".py"), "reader_" + metric)
    got = reader.read({"train": train})
    assert got == (want if want is None else pytest.approx(want))
    assert reader.read({}) is None
