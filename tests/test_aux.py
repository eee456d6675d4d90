"""Aux-subsystem tests: flags-from-env, check_nan_inf, memory_optimize
(remat), debugger dumps, profiler chrome trace (SURVEY.md §5 parity)."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags


def _simple_program(lr=0.05, seed=3):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=32, act="tanh")
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y)
        )
        fluid.optimizer.SGD(lr).minimize(loss)
    return main, startup, loss


def test_flags_env_parsing(monkeypatch):
    monkeypatch.setenv("FLAGS_check_nan_inf", "true")
    monkeypatch.setenv("FLAGS_checkpoint_interval_secs", "2.5")
    monkeypatch.setenv("FLAGS_checkpoint_max_to_keep", "1234")
    flags.refresh_from_env()
    try:
        assert flags.get("check_nan_inf") is True
        assert flags.get("checkpoint_interval_secs") == 2.5
        assert flags.get("checkpoint_max_to_keep") == 1234
        with pytest.raises(KeyError):
            flags.get("no_such_flag")
    finally:
        monkeypatch.delenv("FLAGS_check_nan_inf")
        monkeypatch.delenv("FLAGS_checkpoint_interval_secs")
        monkeypatch.delenv("FLAGS_checkpoint_max_to_keep")
        flags.refresh_from_env()
    assert flags.get("check_nan_inf") is False


def test_check_nan_inf_raises():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        out = fluid.layers.log(x)  # log of a negative -> NaN
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    bad = np.array([[-1.0, 1.0, 2.0, 3.0]], "float32")
    # Without the flag: NaN flows through silently (reference default).
    (res,) = exe.run(main, feed={"x": bad}, fetch_list=[out])
    assert np.isnan(np.asarray(res)).any()
    flags.set_flag("check_nan_inf", True)
    try:
        with pytest.raises(RuntimeError, match="NaN/Inf"):
            exe.run(main, feed={"x": bad}, fetch_list=[out])
    finally:
        flags.set_flag("check_nan_inf", False)


def test_memory_optimize_remat_preserves_numerics():
    rng = np.random.RandomState(0)
    data = [
        (
            rng.randn(16, 16).astype("float32"),
            rng.randn(16, 1).astype("float32"),
        )
        for _ in range(5)
    ]

    def run(optimized):
        with fluid.unique_name.guard():
            main, startup, loss = _simple_program()
        if optimized:
            n = fluid.memory_optimize(main, print_log=False)
            assert n > 0
            assert fluid.transpiler.release_memory(main) == 0
        exe = fluid.Executor(fluid.CPUPlace())
        from paddle_tpu.core.scope import Scope

        with fluid.scope_guard(Scope()):
            exe.run(startup)
            losses = []
            for xb, yb in data:
                (lv,) = exe.run(main, feed={"x": xb, "y": yb},
                                fetch_list=[loss])
                losses.append(float(np.asarray(lv).ravel()[0]))
        return losses

    base = run(optimized=False)
    remat = run(optimized=True)
    np.testing.assert_allclose(remat, base, rtol=1e-5, atol=1e-7)


def test_debugger_dumps(tmp_path):
    main, startup, loss = _simple_program()
    code = fluid.debugger.program_to_code(main)
    assert "mul(" in code and "sgd(" in code
    assert "param fc_" in code
    dot_path = str(tmp_path / "prog.dot")
    dot = fluid.debugger.draw_block_graphviz(
        main.global_block(), highlights=[loss.name], path=dot_path
    )
    assert dot.startswith("digraph G {") and dot.rstrip().endswith("}")
    assert os.path.exists(dot_path)
    assert loss.name.replace(".", "_") in dot  # highlighted node present


def test_profiler_report_and_chrome_trace(tmp_path, capsys):
    main, startup, loss = _simple_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    trace_path = str(tmp_path / "trace.json")
    rng = np.random.RandomState(1)
    # print_report=True: the report routes through logging by default so
    # pytest stays quiet; the stdout table is the opt-in escape hatch
    with fluid.profiler.profiler(profile_path=trace_path, print_report=True):
        for _ in range(3):
            with fluid.profiler.RecordEvent("train_step"):
                exe.run(
                    main,
                    feed={
                        "x": rng.randn(8, 16).astype("float32"),
                        "y": rng.randn(8, 1).astype("float32"),
                    },
                    fetch_list=[loss],
                )
    out = capsys.readouterr().out
    assert "Profiling Report" in out and "train_step" in out
    with open(trace_path) as f:
        trace = json.load(f)
    steps = [e for e in trace["traceEvents"] if e["name"] == "train_step"]
    assert len(steps) == 3
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in steps)


def test_weighted_average():
    from paddle_tpu.average import WeightedAverage

    wa = WeightedAverage()
    wa.add(2.0, 3)
    wa.add(np.array([4.0]), 1)
    assert wa.eval() == pytest.approx((2.0 * 3 + 4.0) / 4)
    wa.reset()
    with pytest.raises(ValueError):
        wa.eval()
    # element-wise matrix averaging, as upstream average.py supports
    wa.add(np.array([1.0, 3.0]), 1.0)
    wa.add(np.array([3.0, 5.0]), 3.0)
    np.testing.assert_allclose(wa.eval(), [2.5, 4.5])


def test_create_random_int_lodtensor():
    import paddle_tpu as fluid

    t = fluid.create_random_int_lodtensor(
        [[2, 3]], base_shape=[4], low=1, high=9)
    assert t.numpy().shape == (5, 4)
    assert t.recursive_sequence_lengths() == [[2, 3]]
    arr = t.numpy()
    assert arr.min() >= 1 and arr.max() <= 9
    assert arr.dtype == np.int64


def test_contrib_memory_usage_and_op_freq():
    """contrib utilities: memory band estimate + op frequency report
    (contrib/memory_usage_calc.py, contrib/op_frequence.py roles)."""
    from paddle_tpu import contrib

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [256])
        h = fluid.layers.fc(x, 128, act="relu")
        h = fluid.layers.fc(h, 128, act="relu")
        loss = fluid.layers.mean(h)
    low, high, unit = contrib.memory_usage(main, batch_size=64)
    assert 0 < low < high and unit in ("B", "KB", "MB", "GB")
    # doubling the batch cannot shrink the estimate
    low2, high2, unit2 = contrib.memory_usage(main, batch_size=128)
    bytes_for = {"B": 1, "KB": 2**10, "MB": 2**20, "GB": 2**30}
    assert high2 * bytes_for[unit2] > high * bytes_for[unit]

    uni, pairs = contrib.op_freq_statis(main)
    assert uni["mul"] == 2 and uni["relu"] == 2
    assert pairs.get("elementwise_add->relu") == 2  # fc bias -> act chain
    import pytest as _pytest
    with _pytest.raises(TypeError):
        contrib.memory_usage("not a program", 4)


def test_get_parameter_value():
    """io.get_parameter_value(_by_name): scope-backed parameter reads
    (io.py:818/:848 parity) including the not-initialized error."""
    import numpy as np
    import pytest
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[3], dtype="float32")
        pred = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    param = main.global_block().all_parameters()[0]
    with fluid.scope_guard(fluid.executor.Scope()):
        with pytest.raises(RuntimeError, match="startup"):
            fluid.io.get_parameter_value(param, exe)
        exe.run(startup)
        v = fluid.io.get_parameter_value(param, exe)
        assert v.shape == (3, 2)
        v2 = fluid.io.get_parameter_value_by_name(param.name, exe,
                                                  program=main)
        np.testing.assert_array_equal(v, v2)
    with pytest.raises(AssertionError, match="not a Parameter"):
        fluid.io.get_parameter_value(x, exe)
