"""Executable-cache correctness: structural fingerprints, the process-
global registry, the persistent on-disk layers, and async dispatch.

Satellite coverage from the compile-tax PR: every trace flag toggle
recompiles, program mutation recompiles, structurally identical programs
share one executable, a corrupted on-disk entry degrades to a fresh
compile (asserted through the exec_cache stats counters), and
run_async(...).result() matches run(...) bit-for-bit. The disk layers are
exercised in-process by purging the in-memory registries between runs,
and across two PROCESSES by one case that runs a small child twice
against one cache directory (a plain and a sharded executable).
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

import paddle_tpu as fluid
from paddle_tpu import flags, unique_name
from paddle_tpu.core import exec_cache
from paddle_tpu.core.fingerprint import (
    TRACE_FLAGS,
    program_fingerprint,
)
import paddle_tpu.executor as executor_mod


def _build_mlp():
    unique_name.switch({})
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 11
    startup.random_seed = 11
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [6])
        hid = fluid.layers.fc(x, size=8, act="relu")
        out = fluid.layers.reduce_sum(fluid.layers.fc(hid, size=2))
    return main, startup, out


def _feed(bs=3):
    return {"x": np.arange(bs * 6, dtype="float32").reshape(bs, 6) / 10.0}


def _trace_misses():
    return exec_cache.stats()["trace_cache_misses"]


# -- fingerprint scheme ------------------------------------------------------

def test_fingerprint_stable_and_memoized():
    main, _, _ = _build_mlp()
    fp1 = program_fingerprint(main)
    fp2 = program_fingerprint(main)
    assert fp1 == fp2
    # memo is version-keyed: no structural change, no re-hash needed
    assert main._fingerprint_memo[0] == main._version


def test_fingerprint_identical_builds_match():
    m1, _, _ = _build_mlp()
    m2, _, _ = _build_mlp()
    assert m1 is not m2
    assert program_fingerprint(m1) == program_fingerprint(m2)


def test_fingerprint_changes_on_mutation():
    main, _, _ = _build_mlp()
    fp = program_fingerprint(main)
    op = main.global_block().ops[-1]
    op.set_attr("some_knob", 42)  # bumps _version through the framework API
    assert program_fingerprint(main) != fp


def test_fingerprint_differs_for_different_programs():
    m1, _, _ = _build_mlp()
    unique_name.switch({})
    m2, s2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(m2, s2):
        x = fluid.layers.data("x", [6])
        fluid.layers.reduce_sum(fluid.layers.fc(x, size=8))
    assert program_fingerprint(m1) != program_fingerprint(m2)


# -- in-memory executable sharing -------------------------------------------

def test_identical_programs_share_one_executable():
    m1, s1, o1 = _build_mlp()
    m2, _, o2 = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(s1)
    r1 = exe.run(m1, feed=_feed(), fetch_list=[o1])
    misses = _trace_misses()
    # same structure, same scope signature -> ZERO new traces, on either
    # the same executor or a brand-new instance
    r1b = exe.run(m2, feed=_feed(), fetch_list=[o2])
    exe2 = fluid.Executor(fluid.CPUPlace())
    r2 = exe2.run(m2, feed=_feed(), fetch_list=[o2])
    assert _trace_misses() == misses
    np.testing.assert_array_equal(np.asarray(r1[0]), np.asarray(r1b[0]))
    np.testing.assert_array_equal(np.asarray(r1[0]), np.asarray(r2[0]))


def test_program_mutation_recompiles():
    main, startup, out = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[out])
    misses = _trace_misses()
    with fluid.program_guard(main, startup):
        out2 = fluid.layers.scale(out, scale=2.0)  # graph surgery
    exe.run(main, feed=_feed(), fetch_list=[out2])
    assert _trace_misses() == misses + 1


def test_each_trace_flag_toggle_recompiles():
    main, startup, out = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[out])
    for name in TRACE_FLAGS:
        old = flags.get(name)
        flip = {"attention_impl": "reference",
                "flash_backward": "reference",
                "paged_attention": "reference",
                "tree_attention": "reference"}.get(name, True)
        assert flip != old, "flag %s: test flip value equals default" % name
        misses = _trace_misses()
        flags.set_flag(name, flip)
        try:
            exe.run(main, feed=_feed(), fetch_list=[out])
            assert _trace_misses() == misses + 1, (
                "toggling %s did not recompile" % name)
            # ...and toggling BACK is a pure cache hit, not a re-trace
            flags.set_flag(name, old)
            exe.run(main, feed=_feed(), fetch_list=[out])
            assert _trace_misses() == misses + 1
        finally:
            flags.set_flag(name, old)


def test_use_program_cache_false_retraces_without_evicting_others():
    main, startup, out = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[out])
    misses = _trace_misses()
    exe.run(main, feed=_feed(), fetch_list=[out], use_program_cache=False)
    assert _trace_misses() == misses + 1  # this run really re-traced
    # ...but the registry still serves everyone else (bypass, not purge)
    exe2 = fluid.Executor(fluid.CPUPlace())
    exe2.run(main, feed=_feed(), fetch_list=[out])
    assert _trace_misses() == misses + 1


# -- async dispatch ----------------------------------------------------------

def test_run_async_matches_run_bit_for_bit():
    main, startup, out = _build_mlp()
    main.random_seed = 5  # deterministic step keys across the two runs
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    (sync_out,) = exe.run(main, feed=_feed(), fetch_list=[out])
    handle = exe.run_async(main, feed=_feed(), fetch_list=[out])
    assert handle.fetch_names == [out.name]
    arrays = handle.arrays()
    assert len(arrays) == 1  # live device arrays, no host materialization
    handle.block_until_ready()
    assert handle.done()
    (async_out,) = handle.result()
    np.testing.assert_array_equal(np.asarray(sync_out), async_out)
    assert handle.result() is handle.result()  # memoized


def test_run_async_nan_check_survives_back_to_back_donation():
    """The deferred nan scan must be DISPATCHED at run_async time: a
    later step donates the very state buffers being checked, so a scan
    started lazily at .result() would read deleted arrays."""
    unique_name.switch({})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [6])
        loss = fluid.layers.mean(fluid.layers.fc(x, size=1))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    flags.set_flag("check_nan_inf", True)
    try:
        h1 = exe.run_async(main, feed=_feed(), fetch_list=[loss])
        h2 = exe.run_async(main, feed=_feed(), fetch_list=[loss])
        (l1,) = h1.result()  # h2's dispatch donated h1's checked state
        (l2,) = h2.result()
        assert np.isfinite(l1).all() and np.isfinite(l2).all()
    finally:
        flags.set_flag("check_nan_inf", False)


def test_run_async_nan_failure_raises_on_every_result_call():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("xr", [4])
        out = fluid.layers.log(x)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    bad = np.array([[-1.0, 1.0, 2.0, 3.0]], "float32")
    flags.set_flag("check_nan_inf", True)
    try:
        handle = exe.run_async(main, feed={"xr": bad}, fetch_list=[out])
        for _ in range(2):  # a retry must NOT silently return the NaNs
            with pytest.raises(RuntimeError, match="NaN/Inf"):
                handle.result()
    finally:
        flags.set_flag("check_nan_inf", False)


def test_run_async_defers_nan_check_to_result():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        out = fluid.layers.log(x)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    bad = np.array([[-1.0, 1.0, 2.0, 3.0]], "float32")
    flags.set_flag("check_nan_inf", True)
    try:
        handle = exe.run_async(main, feed={"x": bad}, fetch_list=[out])
        with pytest.raises(RuntimeError, match="NaN/Inf"):
            handle.result()
    finally:
        flags.set_flag("check_nan_inf", False)


def test_predictor_clone_shares_executable(tmp_path):
    main, startup, out = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.executor.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(
            str(tmp_path / "model"), ["x"], [out], exe, main_program=main)
    config = fluid.inference.NativeConfig(
        model_dir=str(tmp_path / "model"), use_tpu=False)
    pred = fluid.inference.create_paddle_predictor(config)
    r1 = pred.run([_feed()["x"]])
    misses = _trace_misses()
    clone = pred.clone()
    r2 = clone.run([_feed()["x"]])
    assert _trace_misses() == misses, "Clone() recompiled the model"
    np.testing.assert_array_equal(r1[0], r2[0])
    h = clone.run_async([_feed()["x"]])
    np.testing.assert_array_equal(r1[0], h.result()[0])


# -- persistent on-disk layers ----------------------------------------------

def _purge_in_memory():
    """Simulate a fresh process: drop every in-memory executable handle so
    the next run can only be served by the on-disk layers."""
    executor_mod._shared_executables.clear()
    compilation_cache.reset_cache()


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """Both layers under one tmp dir: the flag places the AOT images,
    the environment places JAX's cache (the only way to place it)."""
    d = str(tmp_path / "exec_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.path.join(d, "xla"))
    old = flags.get("exec_cache_dir")
    old_xla = jax.config.jax_compilation_cache_dir
    flags.set_flag("exec_cache_dir", d)
    exec_cache.configure()
    # executables compiled by EARLIER tests (while persistence was off)
    # share the same structural keys; drop them so this test's cold run
    # actually compiles and persists
    _purge_in_memory()
    try:
        yield d
    finally:
        flags.set_flag("exec_cache_dir", old)
        exec_cache.configure()
        # the library never moves or disables JAX's cache once on;
        # later tests want it as they found it
        jax.config.update("jax_compilation_cache_dir", old_xla)
        compilation_cache.reset_cache()


def test_warm_start_loads_aot_image(cache_dir):
    main, startup, out = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    (cold,) = exe.run(main, feed=_feed(), fetch_list=[out])
    aot_dir = os.path.join(cache_dir, "aot")
    assert os.listdir(aot_dir), "no AOT images written"
    _purge_in_memory()
    before = exec_cache.stats()["aot_hits"]
    m2, _, o2 = _build_mlp()
    exe2 = fluid.Executor(fluid.CPUPlace())
    (warm,) = exe2.run(m2, feed=_feed(), fetch_list=[o2])
    assert exec_cache.stats()["aot_hits"] > before, (
        "warm run did not deserialize the stored executable")
    # params untouched between runs -> identical math through the image
    np.testing.assert_array_equal(np.asarray(cold), np.asarray(warm))


def test_corrupted_cache_entry_degrades_to_fresh_compile(cache_dir):
    main, startup, out = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    (good,) = exe.run(main, feed=_feed(), fetch_list=[out])
    # trash EVERY on-disk entry in both layers
    for sub in ("aot", "xla"):
        root = os.path.join(cache_dir, sub)
        for dirpath, _, files in os.walk(root):
            for f in files:
                with open(os.path.join(dirpath, f), "wb") as fh:
                    fh.write(b"corrupt garbage, not an executable")
    _purge_in_memory()
    errors_before = exec_cache.stats()["aot_errors"]
    m2, _, o2 = _build_mlp()
    exe2 = fluid.Executor(fluid.CPUPlace())
    (recovered,) = exe2.run(m2, feed=_feed(), fetch_list=[o2])  # must not crash
    st = exec_cache.stats()
    assert st["aot_errors"] > errors_before, (
        "corrupt AOT image was not detected")
    np.testing.assert_array_equal(np.asarray(good), np.asarray(recovered))
    # the bad entries were QUARANTINED (kept for autopsy, never re-read)
    # and replaced by fresh ones on the way
    aot = os.path.join(cache_dir, "aot")
    for f in os.listdir(aot):
        path = os.path.join(aot, f)
        if not os.path.isfile(path):
            continue  # the quarantine subdir itself
        with open(path, "rb") as fh:
            assert fh.read(32) != b"corrupt garbage, not an executa"
    qdir = os.path.join(aot, "quarantine")
    assert os.path.isdir(qdir) and os.listdir(qdir), (
        "corrupt entries should be moved to quarantine/, not deleted")
    for f in os.listdir(qdir):
        with open(os.path.join(qdir, f), "rb") as fh:
            assert fh.read(32).startswith(b"corrupt garbage")


def test_cache_stats_exported_through_profiler(cache_dir):
    main, startup, out = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[out])
    st = fluid.profiler.exec_cache_stats()
    assert st["enabled"] and st["cache_dir"] == os.path.abspath(cache_dir)
    for k in ("fresh_compiles", "persistent_hits", "persistent_misses",
              "aot_hits", "aot_misses", "aot_errors",
              "compile_seconds_cold", "compile_seconds_warm"):
        assert k in st
    assert st["compile_seconds_cold"] + st["compile_seconds_warm"] >= 0


def test_trace_and_lower_seconds_grow_on_a_fresh_program_only():
    """Set-up's missing seconds: tracing the program to a jaxpr and
    lowering it are counted beside the backend compile, once per fresh
    executable; a cached dispatch adds nothing, and a jitted function
    traced inside another's trace is not counted twice."""
    main, startup, out = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    # earlier tests compiled this very program: drop their executables
    executor_mod._shared_executables.clear()
    before = exec_cache.stats()
    exe.run(main, feed=_feed(), fetch_list=[out])
    fresh = exec_cache.stats()
    assert fresh["trace_seconds"] > before["trace_seconds"]
    assert fresh["lower_seconds"] > before["lower_seconds"]
    for _ in range(3):
        exe.run(main, feed=_feed(), fetch_list=[out])
    cached = exec_cache.stats()
    assert cached["trace_seconds"] == fresh["trace_seconds"]
    assert cached["lower_seconds"] == fresh["lower_seconds"]

    import time

    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        time.sleep(0.05)   # runs while tracing only
        return x * 2.0

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1.0)

    t0 = time.perf_counter()
    outer(jnp.arange(7.0)).block_until_ready()
    wall = time.perf_counter() - t0
    traced = exec_cache.stats()["trace_seconds"] - cached["trace_seconds"]
    # inner is traced once (0.05 s asleep) inside outer's trace; a plain
    # sum of both reports would read it twice
    assert 0.05 <= traced <= wall
    exec_cache.reset_stats()
    assert exec_cache.stats()["trace_seconds"] == 0.0


# -- cache placement ---------------------------------------------------------

_PLACEMENT_PROBE = """
import json, sys
import jax, jax.numpy as jnp
from paddle_tpu.core import exec_cache

exec_cache.configure(sys.argv[1] or None)
if not sys.argv[1]:
    exec_cache.enable_xla_cache()  # what chip_smoke.py and perfbench do
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({
    "dir": jax.config.jax_compilation_cache_dir,
    "enabled": bool(jax.config.jax_enable_compilation_cache),
    "stats": exec_cache.stats(),
}))
"""


def _placement_probe(tmp_path, aot_dir="", script=_PLACEMENT_PROBE,
                     **env_overrides):
    """Run ``script`` with one argument in a child started from an
    unrelated cwd, the two cache variables as ``env_overrides`` say and
    not as this process has them; its last line of output, parsed."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "FLAGS_exec_cache_dir")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=repo, **env_overrides)
    out = subprocess.run(
        [sys.executable, "-c", script, aot_dir], env=env,
        cwd=str(tmp_path), capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_xla_cache_stays_where_the_environment_put_it(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the effective directory equals it
    — even with the AOT flag naming another directory — the cache is
    enabled after a compile, and the compile landed there."""
    outside = str(tmp_path / "placed_from_outside")
    got = _placement_probe(tmp_path, aot_dir=str(tmp_path / "aot_flag"),
                           JAX_COMPILATION_CACHE_DIR=outside)
    assert got["dir"] == outside and got["enabled"]
    assert got["stats"]["xla_cache_dir"] == outside
    assert got["stats"]["persistent_misses"] >= 1
    assert os.listdir(outside), "the compile was not cached there"
    assert os.listdir(str(tmp_path / "aot_flag")) == ["aot"]


def test_xla_cache_defaults_to_one_fixed_path_in_the_checkout(tmp_path):
    """Unset: two separate process starts (run from an unrelated cwd)
    resolve the same fixed in-checkout path, and the second start is
    served from what the first wrote."""
    first = _placement_probe(tmp_path)
    second = _placement_probe(tmp_path)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first["dir"] == second["dir"] == os.path.join(repo, ".jax_cache")
    assert first["enabled"] and second["enabled"]
    assert second["stats"]["persistent_hits"] >= 1
    assert second["stats"]["fresh_compiles"] == 0


# -- two processes, one cache directory ---------------------------------------

_WARM_START_CHILD = """
import json, sys
import numpy as np
import paddle_tpu as fluid
from paddle_tpu.core import exec_cache
from paddle_tpu.observability import REGISTRY

main, startup = fluid.Program(), fluid.Program()
main.random_seed = startup.random_seed = 3
with fluid.program_guard(main, startup):
    x = fluid.layers.data("x", [8])
    y = fluid.layers.data("y", [1])
    hid = fluid.layers.fc(x, size=16, act="relu")
    loss = fluid.layers.mean(
        fluid.layers.square_error_cost(fluid.layers.fc(hid, size=1), y))
    fluid.optimizer.SGD(0.1).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(startup)
feed = {"x": np.arange(64, dtype="float32").reshape(8, 8) / 64.0,
        "y": np.ones((8, 1), "float32")}
if sys.argv[1] == "sharded":
    pe = fluid.ParallelExecutor(loss_name=loss.name, main_program=main,
                                use_tpu=False, fsdp=2, tp=2)
    out = pe.run(fetch_list=[loss], feed=feed)
else:
    out = exe.run(main, feed=feed, fetch_list=[loss])
scrape = [l for l in REGISTRY.to_prometheus().splitlines()
          if l.startswith("paddle_tpu_fresh_compiles_total ")]
print(json.dumps({"loss": float(np.ravel(np.asarray(out[0]))[0]),
                  "scraped": float(scrape[0].split()[-1]),
                  "stats": exec_cache.stats()}))
"""


@pytest.mark.parametrize("kind", ["plain", "sharded"])
def test_a_second_process_runs_the_program_with_no_fresh_compile(
        tmp_path, kind):
    """Only the structural fingerprint connects the second process to the
    first one's executables: it must load them (AOT images) and compile
    nothing, by the cache's own count and by the metrics scrape, and
    compute the same loss."""
    cold, warm = [
        _placement_probe(
            tmp_path, kind, script=_WARM_START_CHILD,
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            FLAGS_exec_cache_dir=str(tmp_path / "cache"),
            JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
        for _ in range(2)]
    assert cold["stats"]["enabled"] and cold["stats"]["fresh_compiles"] > 0
    assert warm["stats"]["fresh_compiles"] == 0 == warm["scraped"], warm
    assert warm["stats"]["aot_hits"] >= 1, warm["stats"]
    assert warm["loss"] == cold["loss"]
