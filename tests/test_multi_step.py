"""Compiled multi-step loop tests (SURVEY §7 hard part (c)): K steps in
one lax.scan executable must match K sequential Executor.run calls."""

import numpy as np

import paddle_tpu as fluid


def _build_sgd_program(seed=3):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4], stop_gradient=False)
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _feed(seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(8, 4).astype("float32")
    return {"x": x, "y": (x.sum(1, keepdims=True)).astype("float32")}


def test_multi_step_matches_sequential_runs():
    feed = _feed()
    k = 5

    main, startup, loss = _build_sgd_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope_a = fluid.core.scope.Scope()
    with fluid.scope_guard(scope_a):
        exe.run(startup)
        init = {n: np.array(scope_a.get_value(n))
                for n in scope_a.local_var_names()}
        seq_losses = [
            float(np.asarray(exe.run(main, feed=feed,
                                     fetch_list=[loss])[0]).reshape(-1)[0])
            for _ in range(k)
        ]
        w_name = [n for n in init if n.endswith("w_0")][0]
        w_seq = np.asarray(scope_a.get_value(w_name))

    # same program + identical initial weights, one scanned executable
    scope_b = fluid.core.scope.Scope()
    for n, v in init.items():
        scope_b.set_value(n, v)
    with fluid.scope_guard(scope_b):
        out = exe.run_multi_step(main, k, feed=feed, fetch_list=[loss])
        w_multi = np.asarray(scope_b.get_value(w_name))

    # identical deterministic math -> identical trained weights
    np.testing.assert_allclose(w_multi, w_seq, rtol=1e-5, atol=1e-6)
    # default fetch mode returns the LAST step's loss
    last = float(np.asarray(out[0]).reshape(-1)[0])
    np.testing.assert_allclose(last, seq_losses[-1], rtol=1e-5)


def test_multi_step_stacked_fetches_trajectory():
    feed = _feed(1)
    k = 4
    main, startup, loss = _build_sgd_program(seed=9)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        (traj,) = exe.run_multi_step(main, k, feed=feed, fetch_list=[loss],
                                     stack_fetches=True)
    traj = np.asarray(traj).reshape(k)
    assert np.isfinite(traj).all()
    # SGD on a fixed batch: strictly decreasing loss trajectory
    assert (np.diff(traj) < 0).all(), traj


def test_multi_step_with_in_graph_reader():
    """No feeds at all: input comes from the in-graph random reader."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 11
    startup.random_seed = 11
    with fluid.program_guard(main, startup):
        x, y = fluid.layers.random_data_generator(
            shapes=[[8, 4], [8, 1]], dtypes=["float32", "float32"])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        (traj,) = exe.run_multi_step(main, 6, fetch_list=[loss],
                                     stack_fetches=True)
    traj = np.asarray(traj).reshape(6)
    assert np.isfinite(traj).all()
    # random batches differ step to step: check the steps actually ran
    assert len(set(np.round(traj, 6))) > 1
