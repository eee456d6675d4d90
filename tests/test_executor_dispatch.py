"""The executor's warm dispatch path: the run's key is folded inside the
step's executable from (base key, run counter), the feeds go in as
arguments and the state gather keeps its holders, and none of it changes
a bit of what a run computes (ISSUE 25).

The key stream is checked against the step function called with
``fold_in(PRNGKey(seed), i)`` made the old, eager way.

``Executor.run``, ``run_async``, ``run_multi_step`` and
``ParallelExecutor.run`` reach the device through one function
(``executor._run_step``, ISSUE 29): what holds of one entry point is
checked of all four (``ENTRIES``).
"""

import time

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import flags
from paddle_tpu.core import exec_cache
from paddle_tpu.core.lowering import BlockLowerer, build_step_fn
from paddle_tpu.executor import global_scope
from paddle_tpu.observability import blackbox, memory, nan_provenance
from paddle_tpu.observability import step_profiler, telemetry
from paddle_tpu.parallel_executor import ParallelExecutor

ENTRIES = ["run", "run_async", "run_multi_step", "parallel"]
# the dispatch record's and telemetry's origin, and the black box's name
ORIGIN = {"run": "single", "run_async": "async",
          "run_multi_step": "multi_step", "parallel": "parallel"}
NAME = {"run": "Executor.run", "run_async": "Executor.run_async",
        "run_multi_step": "Executor.run_multi_step",
        "parallel": "ParallelExecutor.run"}

SEED = 1234
W = "w_scale.w_0"   # the name create_parameter gives
BATCH = 16


def _dropout_program(seed=SEED, train=True):
    """x -> fc -> dropout -> (mean -> SGD): the dropout output is what the
    step key decides; with ``train`` the parameters are donated and
    written back every run."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = 99
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(input=x, size=8) if train else x
        out = fluid.layers.dropout(h, dropout_prob=0.5)
        if train:
            loss = fluid.layers.mean(out)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, out


def _feed():
    return {"x": np.random.RandomState(0).rand(BATCH, 8).astype("float32")
            + 1.0}


def _old_key(seed, counter):
    return jax.random.fold_in(jax.random.PRNGKey(seed), counter)


class _Reference(object):
    """The program's own step function, jitted, threaded by hand."""

    def __init__(self, main, out, scope):
        names = set(scope.local_var_names())
        state_in, state_out = BlockLowerer(main, 0).analyze(names, {"x"})
        self.step = jax.jit(build_step_fn(
            main, ["x"], [out.name], state_in, state_out))
        self.state = {n: np.asarray(scope.get_value(n)) for n in state_in}

    def run(self, key):
        new_state, (fetched,) = self.step(self.state, _feed(), key)
        self.state.update(new_state)
        return np.asarray(fetched)


def _start(train=True, seed=SEED):
    main, startup, out = _dropout_program(seed, train=train)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    return main, out, exe


# -- the key stream -----------------------------------------------------------

@pytest.mark.parametrize("entry", ["run", "run_async"])
def test_key_stream_matches_the_eager_key(entry):
    main, out, exe = _start()
    ref = _Reference(main, out, global_scope())
    first = exe._run_counter
    for i in range(1, 6):
        if entry == "run":
            got, = exe.run(main, feed=_feed(), fetch_list=[out])
        else:
            got, = exe.run_async(main, feed=_feed(),
                                 fetch_list=[out]).result()
        want = ref.run(_old_key(SEED, first + i))
        assert exe._run_counter == first + i
        assert (got == 0).any() and (got != 0).any()   # a mask was drawn
        np.testing.assert_array_equal(got, want)


def test_key_stream_multi_step_matches_the_eager_key():
    steps = 3
    main, out, exe = _start()
    ref = _Reference(main, out, global_scope())
    first = exe._run_counter
    for c in range(1, 4):
        got, = exe.run_multi_step(main, steps, feed=_feed(),
                                  fetch_list=[out], stack_fetches=True)
        key = _old_key(SEED, first + c)
        want = np.stack([ref.run(jax.random.fold_in(key, i))
                         for i in range(steps)])
        np.testing.assert_array_equal(got, want)
        assert not (got[0] == got[1]).all()   # a key of its own each step
    # the last step's values ride the carry when nothing is stacked
    got, = exe.run_multi_step(main, steps, feed=_feed(), fetch_list=[out])
    key = _old_key(SEED, first + 4)
    want = [ref.run(jax.random.fold_in(key, i)) for i in range(steps)][-1]
    np.testing.assert_array_equal(got, want)


def test_key_stream_parallel_executor_matches_the_eager_key():
    main, out, _exe = _start(train=False)
    pe = ParallelExecutor(main_program=main, use_tpu=False)
    ref = _Reference(main, out, global_scope())
    for i in range(1, 5):
        got, = pe.run(fetch_list=[out.name], feed=_feed())
        assert pe._run_counter == i
        np.testing.assert_array_equal(got, ref.run(_old_key(SEED, i)))


def test_seedless_program_follows_the_executors_base_seed():
    main, out, exe = _start(seed=0)
    ref = _Reference(main, out, global_scope())
    first = exe._run_counter
    exe._base_seed = 424242
    for i in range(1, 4):
        got, = exe.run(main, feed=_feed(), fetch_list=[out])
        np.testing.assert_array_equal(
            got, ref.run(_old_key(424242, first + i)))
    exe._base_seed = 7   # another stream, with no new executable
    misses = exec_cache.stats()["trace_cache_misses"]
    got, = exe.run(main, feed=_feed(), fetch_list=[out])
    np.testing.assert_array_equal(got, ref.run(_old_key(7, first + 4)))
    assert exec_cache.stats()["trace_cache_misses"] == misses


def test_restored_seed_and_counter_continue_the_stream():
    """What a checkpoint carries (resilience/checkpoint.py) is enough."""
    main, out, exe = _start(train=False, seed=0)
    for _ in range(3):
        exe.run(main, feed=_feed(), fetch_list=[out])
    saved = exe._base_seed, exe._run_counter
    want = [exe.run(main, feed=_feed(), fetch_list=[out])[0]
            for _ in range(2)]
    resumed = fluid.Executor(fluid.CPUPlace())
    resumed._base_seed, resumed._run_counter = saved
    got = [resumed.run(main, feed=_feed(), fetch_list=[out])[0]
           for _ in range(2)]
    np.testing.assert_array_equal(got, want)
    assert not (want[0] == want[1]).all()


def test_executors_sharing_an_executable_keep_their_own_counters():
    main, out, a = _start(train=False)
    b = fluid.Executor(fluid.CPUPlace())
    for _ in range(3):
        a.run(main, feed=_feed(), fetch_list=[out])
    misses = exec_cache.stats()["trace_cache_misses"]
    a_start, b_start = a._run_counter, b._run_counter
    got_b, = b.run(main, feed=_feed(), fetch_list=[out])
    got_a, = a.run(main, feed=_feed(), fetch_list=[out])
    assert exec_cache.stats()["trace_cache_misses"] == misses   # shared
    assert (a._run_counter, b._run_counter) == (a_start + 1, b_start + 1)
    ref = _Reference(main, out, global_scope())
    np.testing.assert_array_equal(got_b, ref.run(_old_key(SEED, b_start + 1)))
    np.testing.assert_array_equal(got_a, ref.run(_old_key(SEED, a_start + 1)))


# -- the warm path makes no eager JAX call -------------------------------------

class _Calls(object):
    """Counts calls of ``jax.random.PRNGKey``, ``jax.random.fold_in`` and
    ``jax.device_put`` (the names the program looks up at call time)."""

    def __init__(self, monkeypatch):
        self.n = {"PRNGKey": 0, "fold_in": 0, "device_put": 0}
        for mod, name in ((jax.random, "PRNGKey"), (jax.random, "fold_in"),
                          (jax, "device_put")):
            monkeypatch.setattr(mod, name, self._counting(
                getattr(mod, name), name))

    def _counting(self, fn, name):
        def counted(*args, **kwargs):
            self.n[name] += 1
            return fn(*args, **kwargs)
        return counted

    def reset(self):
        for k in self.n:
            self.n[k] = 0


def _caller(entry, main, out, exe=None, feed=None):
    """``call()``: one more dispatch of ``main`` through ``entry``, and
    what it fetched of ``out``."""
    feed = feed or _feed()
    exe = exe or fluid.Executor(fluid.CPUPlace())
    if entry == "run":
        return lambda: exe.run(main, feed=feed, fetch_list=[out])[0]
    if entry == "run_async":
        return lambda: exe.run_async(
            main, feed=feed, fetch_list=[out]).result()[0]
    if entry == "run_multi_step":
        return lambda: exe.run_multi_step(
            main, 2, feed=feed, fetch_list=[out])[0]
    pe = ParallelExecutor(main_program=main, use_tpu=False)
    return lambda: pe.run(fetch_list=[out.name], feed=feed)[0]


@pytest.mark.parametrize("entry", ENTRIES)
def test_third_run_makes_no_eager_jax_call(entry, monkeypatch):
    main, out, exe = _start()
    call = _caller(entry, main, out, exe)
    calls = _Calls(monkeypatch)
    call()   # traces the fold, at most once
    call()
    calls.reset()
    before = exec_cache.stats()
    call()
    after = exec_cache.stats()
    assert calls.n["PRNGKey"] == 0
    assert calls.n["fold_in"] == 0
    assert calls.n["device_put"] <= 1
    assert after["gather_plan_hits"] == before["gather_plan_hits"] + 1
    assert after["gather_plan_rebuilds"] == before["gather_plan_rebuilds"]
    assert after["trace_cache_misses"] == before["trace_cache_misses"]


# -- one core, each observer hooked once ---------------------------------------

@pytest.mark.parametrize("entry", ENTRIES)
def test_each_call_leaves_one_dispatch_record_with_its_phases(entry):
    main, out, exe = _start()
    call = _caller(entry, main, out, exe)
    call()
    mark = time.time()
    for _ in range(3):
        call()
    mine = [r for r in step_profiler.dispatch_records(ORIGIN[entry])
            if r["t1"] >= mark]
    assert len(mine) == 3
    # the handle of run_async is made before the device is waited for
    waited = [] if entry == "run_async" else ["device"]
    for rec in mine:
        assert list(rec["phases"]) == [
            "feed", "compile", "dispatch", "fetch"] + waited + ["host"]
        assert rec["wall_s"] >= sum(rec["phases"].values()) * 0.999


def _multi_step_fetches(in_flight, return_numpy, calls=3):
    """A fresh start of the dropout program, then ``calls`` dispatches
    of two steps each with ``in_flight`` handed in: what each fetched,
    as the call returned it."""
    main, out, exe = _start()
    return [exe.run_multi_step(main, 2, feed=_feed(), fetch_list=[out],
                               return_numpy=return_numpy,
                               in_flight=in_flight)[0]
            for _ in range(calls)]


@pytest.mark.parametrize("traced", [False, True], ids=["plain", "traced"])
@pytest.mark.parametrize("return_numpy", [True, False])
def test_in_flight_runs_once_a_call_between_the_launch_and_the_wait(
        return_numpy, traced, monkeypatch):
    """``run_multi_step(in_flight=fn)``: ``fn`` is called exactly once a
    call, after the dispatch is launched and before anything waits for it
    (with ``return_numpy=False`` nothing in the call does, and the live
    arrays come back); the dispatch record keeps its phases, and the
    hook's seconds (30 ms asleep, 20 ms of CPU) are in its wall and in
    none of them, the residual ``host`` neither; what is fetched is
    bit-equal with and without it."""
    from paddle_tpu import executor as executor_mod
    from paddle_tpu.observability import tracing

    log = []
    dispatch = executor_mod.Executor._dispatch
    materialize = executor_mod._materialize_fetches
    monkeypatch.setattr(
        executor_mod.Executor, "_dispatch", staticmethod(
            lambda *a, **kw: (dispatch(*a, **kw), log.append("launch"))[0]))
    monkeypatch.setattr(
        executor_mod, "_materialize_fetches",
        lambda *a, **kw: (log.append("wait"), materialize(*a, **kw))[1])

    def hook():
        log.append("in_flight")
        time.sleep(0.03)
        until = time.thread_time() + 0.02
        while time.thread_time() < until:
            pass

    plain = _multi_step_fetches(None, return_numpy)
    del log[:]
    monkeypatch.setattr(tracing, "ENABLED", traced)
    mark = time.time()
    hooked = _multi_step_fetches(hook, return_numpy)
    monkeypatch.setattr(tracing, "ENABLED", False)
    # _start() runs the startup program: a launch and no hook
    waited = ["wait"] if return_numpy else []
    assert log == ["launch", "wait"] + 3 * (
        ["launch", "in_flight"] + waited)
    for got, want in zip(hooked, plain):
        assert isinstance(got, np.ndarray) == return_numpy
        assert np.array_equal(np.asarray(got), np.asarray(want))
    mine = [r for r in step_profiler.dispatch_records("multi_step")
            if r["t1"] >= mark]
    assert len(mine) == 3
    for rec in mine:
        assert list(rec["phases"]) == ["feed", "compile", "dispatch",
                                       "fetch"] + (
            ["device"] if return_numpy else []) + ["host"]
        assert rec["wall_s"] >= 0.05
        assert sum(rec["phases"].values()) <= rec["wall_s"] - 0.05 + 1e-4
        if traced:
            assert set(rec["cpu"]) == set(rec["phases"])
            assert sum(rec["cpu"].values()) < 0.02
        else:
            assert rec["cpu"] is None


@pytest.mark.parametrize("entry", ENTRIES)
def test_each_observer_records_one_event_per_call(entry, tmp_path):
    main, out, exe = _start()
    call = _caller(entry, main, out, exe)
    call()
    call()
    telemetry.reset()
    memory.reset()
    telemetry.enable(True)
    blackbox.enable(str(tmp_path / "box.json"), handlers=False)
    try:
        mark = time.time()
        call()
        steps = telemetry.step_records()
        events = [e for e in blackbox.events()
                  if e["kind"] == "dispatch" and e["ts"] >= mark]
        live = memory.live_by_kind()
    finally:
        blackbox.disable()
        telemetry.enable(False)
        telemetry.reset()
        memory.reset()
    record, = [r for r in step_profiler.dispatch_records(ORIGIN[entry])
               if r["t1"] >= mark]
    step, = steps
    assert step["executor"] == ORIGIN[entry]
    assert step["steps"] == (2 if entry == "run_multi_step" else 1)
    assert step["dispatch_only"] == (entry == "run_async")
    # one clock in every mode: telemetry's wall starts where the span's
    # does, feed and compile included, and ends after it
    assert step["wall_s"] >= record["wall_s"]
    assert 0.0 < step["h2d_seconds"] < step["wall_s"]
    assert step["feed_bytes"] == _feed()["x"].nbytes
    event, = events
    assert event["origin"] == NAME[entry]
    assert event["fetch_names"] == [out.name]
    # the ledger's pairs balance: the feeds and the fetched activations
    # have left, the state the step wrote back is booked
    assert "feed" not in live and "activation" not in live
    assert live.get("param", 0) > 0


def _nan_program():
    """x -> scale -> log -> mean: a zero fed makes op 1 (log) emit -inf
    from finite inputs."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [4])
        y = fluid.layers.log(fluid.layers.scale(x, scale=2.0))
        out = fluid.layers.mean(y)
    return main, out


@pytest.mark.parametrize("entry", ENTRIES)
def test_check_nan_inf_raises_with_blame(entry):
    main, out = _nan_program()
    x = np.ones([8, 4], "float32")
    x[3, 1] = 0.0
    call = _caller(entry, main, out, feed={"x": x})
    flags.set_flag("check_nan_inf", True)
    try:
        with pytest.raises(nan_provenance.NonFiniteError) as err:
            call()
    finally:
        flags.set_flag("check_nan_inf", False)
    assert "NaN/Inf detected" in str(err.value)
    assert err.value.diagnostic.op_type == "log"
    assert err.value.diagnostic.op_idx == 1
    assert np.isneginf(call())   # the flag is off


@pytest.mark.parametrize("entry", ENTRIES)
def test_verify_program_flag_gates_a_fresh_compile(entry):
    from paddle_tpu.analysis import ProgramVerifyError

    main = fluid.Program()
    block = main.global_block()
    block.create_var(name="a", shape=(8, 2), dtype="float32", is_data=True)
    block.create_var(name="o", shape=(8, 2), dtype="float32")
    block.append_op("relu", inputs={"X": ["missing_input"]},
                    outputs={"Out": ["o"]}, infer_shape=False)
    call = _caller(entry, main, block.vars["o"],
                   feed={"a": np.zeros([8, 2], "float32")})
    flags.set_flag("verify_program", True)
    try:
        with pytest.raises(ProgramVerifyError):
            call()
    finally:
        flags.set_flag("verify_program", False)


def test_second_executor_reuses_the_first_ones_multi_step_executable():
    main, out, first = _start()
    for _ in range(2):   # the first run's write-back may add names
        first.run_multi_step(main, 3, feed=_feed(), fetch_list=[out])
    before = exec_cache.stats()
    second = fluid.Executor(fluid.CPUPlace())
    second.run_multi_step(main, 3, feed=_feed(), fetch_list=[out])
    after = exec_cache.stats()
    assert after["fresh_compiles"] == before["fresh_compiles"]
    assert after["trace_cache_misses"] == before["trace_cache_misses"]
    # another scan length (one no other test has compiled) is another
    second.run_multi_step(main, 5, feed=_feed(), fetch_list=[out])
    assert (exec_cache.stats()["trace_cache_misses"]
            == before["trace_cache_misses"] + 1)


# -- the scope changes under a warm executable ---------------------------------

def _weight_program():
    """out = x * w with ``w`` a persistable the program only reads."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        w = fluid.layers.create_parameter(
            shape=[8], dtype="float32", name="w_scale",
            default_initializer=fluid.initializer.Constant(2.0))
        out = fluid.layers.elementwise_mul(x, w)
    return main, startup, out


def _set_numpy(scope):
    scope.set_value(W, np.full([8], 3.0, "float32"))
    return 3.0, 1


def _erase_and_recreate(scope):
    scope.erase([W])
    scope.set_value(W, jax.numpy.full([8], 5.0, "float32"))
    return 5.0, 0


def _other_device(scope):
    other = jax.devices("cpu")[1]
    scope.set_value(W, jax.device_put(
        np.full([8], 7.0, "float32"), other))
    return 7.0, 1


def _same_device(scope):
    scope.set_value(W, jax.numpy.full([8], 11.0, "float32"))
    return 11.0, 0


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("change", [
    _set_numpy, _erase_and_recreate, _other_device, _same_device],
    ids=lambda f: f.__name__.strip("_"))
def test_warm_run_sees_a_value_set_from_outside(change, entry, monkeypatch):
    main, startup, out = _weight_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = _feed()
    call = _caller(entry, main, out, exe, feed=feed)
    for _ in range(3):
        np.testing.assert_array_equal(call(), feed["x"] * 2.0)
    scale, moves = change(global_scope())
    calls = _Calls(monkeypatch)
    np.testing.assert_array_equal(call(), feed["x"] * scale)
    if entry == "parallel":
        # a mesh spreads a device's value, and takes a host value as it
        # is: its executable traces for the new kind of argument
        assert calls.n["device_put"] <= 1
    else:
        assert calls.n["device_put"] == moves   # moved once, or not at all
        assert calls.n["PRNGKey"] == calls.n["fold_in"] == 0
    # and the scope keeps what the caller put there
    kept = global_scope().get_value(W)
    np.testing.assert_array_equal(np.asarray(kept), np.full([8], scale))


@pytest.mark.parametrize("how", ["never_set", "emptied_when_warm"])
def test_uninitialised_persistable_raises_todays_error(how):
    main, startup, out = _weight_program()
    exe = fluid.Executor(fluid.CPUPlace())
    if how == "never_set":
        global_scope().var(W)   # a holder, and nothing in it
    else:
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=_feed(), fetch_list=[out])
        global_scope().set_value(W, None)
    with pytest.raises(RuntimeError) as err:
        exe.run(main, feed=_feed(), fetch_list=[out])
    assert str(err.value) == (
        "persistable variable 'w_scale.w_0' is not initialized in the scope "
        "(did you run the startup program?)")


def test_variable_created_later_in_a_child_scope_shadows_the_parents():
    main, startup, out = _weight_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    kid = global_scope().new_scope()
    feed = _feed()
    for _ in range(3):
        got, = exe.run(main, feed=feed, fetch_list=[out], scope=kid)
        np.testing.assert_array_equal(got, feed["x"] * 2.0)
    kid.set_value(W, jax.numpy.full([8], 4.0, "float32"))
    got, = exe.run(main, feed=feed, fetch_list=[out], scope=kid)
    np.testing.assert_array_equal(got, feed["x"] * 4.0)
    got, = exe.run(main, feed=feed, fetch_list=[out])   # the parent's own
    np.testing.assert_array_equal(got, feed["x"] * 2.0)


def test_an_erased_value_is_not_kept_alive_by_the_gather_plan():
    import gc
    import weakref

    main, startup, out = _weight_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    for _ in range(3):
        exe.run(main, feed=_feed(), fetch_list=[out])
    alive = weakref.ref(global_scope().get_value(W))
    global_scope().erase([W])
    gc.collect()
    assert alive() is None
    replaced = jax.numpy.full([8], 6.0, "float32")
    global_scope().set_value(W, replaced)
    exe.run(main, feed=_feed(), fetch_list=[out])
    alive = weakref.ref(replaced)
    global_scope().set_value(W, jax.numpy.full([8], 1.0, "float32"))
    del replaced
    gc.collect()
    assert alive() is None


def test_feeds_are_cast_to_the_declared_dtype_and_int64_still_feeds():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        ids = fluid.layers.data(name="ids", shape=[1], dtype="int64")
        out = fluid.layers.elementwise_add(
            x, fluid.layers.cast(ids, "float32"))
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.arange(8, dtype="float64").reshape(2, 4),   # cast
            "ids": np.array([[1], [2]], dtype="int32")}          # cast
    want = feed["x"].astype("float32") + feed["ids"].astype("float32")
    for _ in range(3):
        got, = exe.run(main, feed=feed, fetch_list=[out])
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, want)
    # a device array is taken as it is
    feed["x"] = jax.numpy.asarray(feed["x"], "float32")
    got, = exe.run(main, feed=feed, fetch_list=[out])
    np.testing.assert_array_equal(got, want)


def test_threads_share_a_gather_plan_while_a_value_is_replaced():
    """Serving clones run one executable on one scope from many threads;
    the plan they share must hand every run the scope's current value."""
    import sys
    import threading

    main, startup, out = _weight_program()
    fluid.Executor(fluid.CPUPlace()).run(startup)
    scope = global_scope()
    feed = _feed()
    want = feed["x"] * 2.0
    fluid.Executor(fluid.CPUPlace()).run(main, feed=feed, fetch_list=[out])
    wrong, stop = [], threading.Event()

    def serve():
        exe = fluid.Executor(fluid.CPUPlace())
        for _ in range(40):
            got, = exe.run(main, feed=feed, fetch_list=[out], scope=scope)
            if not (got == want).all():
                wrong.append(got)

    def replace():
        i = 0
        while not stop.is_set():
            two = np.full([8], 2.0, "float32")
            scope.set_value(W, two if i % 2 else jax.numpy.asarray(two))
            i += 1

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        servers = [threading.Thread(target=serve) for _ in range(16)]
        replacer = threading.Thread(target=replace)
        replacer.start()
        for t in servers:
            t.start()
        for t in servers:
            t.join(timeout=120)
        stop.set()
        replacer.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in servers + [replacer])
    assert wrong == []
