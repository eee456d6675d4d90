"""Native runtime (C++ libptpu_core) + PTPB program IR tests.

Covers: recordio round-trip + corruption detection through ctypes, the
C++ blocking queue under Python producer/consumer threads, NativeScope
host-tensor store, and — the lockstep guarantee — Python-serialized
programs parsing and re-serializing BYTE-IDENTICALLY in C++, then
deserializing back to an equivalent Python Program that still executes.
"""

import os
import subprocess
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import native
from paddle_tpu.core.program_bin import (
    deserialize_program,
    serialize_program,
)

pytestmark = pytest.mark.skipif(
    not native.available(),
    reason="native toolchain unavailable: %s" % native.last_error(),
)


def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "data.recordio")
    records = [b"alpha", b"", b"x" * 70000, np.arange(100).tobytes()]
    with native.RecordIOWriter(path) as w:
        for r in records:
            w.write(r)
    with native.RecordIOReader(path) as r:
        got = list(r)
    assert got == records

    # Flip a payload byte -> IOError on that record.
    blob = bytearray(open(path, "rb").read())
    blob[4 + 8 + 4 + 1] ^= 0xFF
    open(path, "wb").write(bytes(blob))
    with native.RecordIOReader(path) as r:
        with pytest.raises(IOError):
            next(r)


def test_native_queue_producer_consumer():
    q = native.NativeBlockingQueue(capacity=4)
    n_items = 200

    def producer():
        for i in range(n_items):
            q.push(b"item-%04d" % i)
        q.close()

    got = []
    t = threading.Thread(target=producer)
    t.start()
    while True:
        item = q.pop()
        if item is None:
            break
        got.append(item)
    t.join()
    assert len(got) == n_items
    assert got[0] == b"item-0000" and got[-1] == b"item-0199"
    assert q.is_closed()

    q.reopen()
    q.push(b"epoch2")
    assert q.pop(timeout_ms=1000) == b"epoch2"
    with pytest.raises(TimeoutError):
        q.pop(timeout_ms=50)


def test_native_scope():
    scope = native.NativeScope()
    w = np.arange(12, dtype="float32").reshape(3, 4)
    scope.set("w", w)
    scope.set("step", np.asarray([7], "int64"))
    child = scope.new_child()
    np.testing.assert_array_equal(child.get("w"), w)  # parent walk
    child.set("w", np.zeros((2,), "float32"))  # shadowing
    assert child.get("w").shape == (2,)
    assert scope.get("w").shape == (3, 4)
    assert scope.get("absent") is None
    assert set(scope.var_names()) == {"w", "step"}
    assert len(scope) == 2
    assert scope.erase("step")
    assert len(scope) == 1


def _build_sample_program():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y)
        )
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def test_ptpb_python_cpp_lockstep():
    """C++ parse + re-serialize must reproduce the Python bytes exactly."""
    main, _, _ = _build_sample_program()
    blob = serialize_program(main)
    nblocks, ops, reserialized = native.parse_program_bytes(blob)
    assert nblocks == len(main.blocks)
    assert ops[0] == len(main.global_block().ops)
    assert reserialized == blob


def test_ptpb_roundtrip_executes(tmp_path):
    """serialize -> C++ -> deserialize: the program still runs and matches
    the original's losses step for step."""
    main, startup, loss = _build_sample_program()
    blob = serialize_program(main)
    _, _, blob2 = native.parse_program_bytes(blob)
    restored = deserialize_program(blob2)

    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype("float32")
    y = rng.randn(16, 1).astype("float32")

    from paddle_tpu.core.scope import Scope

    results = []
    for prog in (main, restored):
        # Fresh Executor per run: the PRNG key folds in a per-executor run
        # counter, so determinism holds for identical run sequences.
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(Scope()):
            exe.run(startup)
            vals = []
            for _ in range(3):
                (lv,) = exe.run(prog, feed={"x": x, "y": y},
                                fetch_list=[loss.name])
                vals.append(float(np.asarray(lv).ravel()[0]))
            results.append(vals)
    np.testing.assert_allclose(results[0], results[1], rtol=1e-6)


def test_cpp_unit_suite_with_program_file(tmp_path):
    """Run the assert-based C++ suite end to end, feeding it a real
    Python-written PTPB file for its round-trip section."""
    main, _, _ = _build_sample_program()
    prog_path = str(tmp_path / "prog.ptpb")
    open(prog_path, "wb").write(serialize_program(main))
    test_bin = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "native", "build", "ptpu_native_test",
    )
    assert os.path.exists(test_bin), "build the native tests first"
    out = subprocess.run(
        [test_bin, prog_path], capture_output=True, text=True, timeout=120
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALL NATIVE TESTS PASSED" in out.stdout
    assert "program roundtrip ok" in out.stdout


def test_save_inference_model_uses_ptpb(tmp_path):
    """save_inference_model emits the language-neutral PTPB format (C++
    predictor loadable), not a Python pickle."""
    main, startup, loss = _build_sample_program()
    exe = fluid.Executor(fluid.CPUPlace())
    from paddle_tpu.core.scope import Scope

    with fluid.scope_guard(Scope()):
        exe.run(startup)
        block = main.global_block()
        pred = block.var("fc_1.tmp_1") if "fc_1.tmp_1" in block.vars else None
        target = pred if pred is not None else loss
        path = str(tmp_path / "model")
        fluid.io.save_inference_model(path, ["x", "y"], [target], exe,
                                      main_program=main)
        blob = open(os.path.join(path, "__model__"), "rb").read()
        assert blob[:4] == b"PTPB"
        prog, feeds, fetches = fluid.io.load_inference_model(path, exe)
        assert feeds == ["x", "y"] or set(feeds) <= {"x", "y"}
        assert fetches[0] is not None


def test_ptpb_lockstep_covers_fused_ops():
    """Programs rewritten by the fusion passes (fused ops with list/None
    attrs) still round-trip byte-exactly through the C++ PTPB parser."""
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        z = fluid.layers.relu(fluid.layers.elementwise_add(h, h))
        proj = fluid.layers.fc(input=fluid.layers.unsqueeze(z, axes=[1]),
                               size=4 * 6, num_flatten_dims=2)
        out, _ = fluid.layers.dynamic_lstm(input=proj, size=4 * 6)
    apply_pass(main, "fc_lstm_fuse")
    apply_pass(main, "fuse_elewise_add_act")
    apply_pass(main, "fc_fuse")
    types = [op.type for op in main.global_block().ops]
    assert "fusion_lstm" in types and "fused_elemwise_activation" in types
    blob = serialize_program(main)
    nblocks, ops, reserialized = native.parse_program_bytes(blob)
    assert reserialized == blob
    back = deserialize_program(blob)
    assert [op.type for op in back.global_block().ops] == types


def test_two_processes_build_an_empty_build_directory_at_once(tmp_path):
    """Six test workers import ``paddle_tpu.native`` at once on a fresh
    checkout; each must end up with a library it can load."""
    import shutil
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tree = str(tmp_path / "native")
    os.makedirs(tree)
    for sub in ("src", "include", "tests"):
        shutil.copytree(os.path.join(repo, "native", sub),
                        os.path.join(tree, sub))
    shutil.copy(os.path.join(repo, "native", "CMakeLists.txt"), tree)
    child = (
        "import os, sys\n"
        "sys.path.insert(0, %r)\n"
        "from paddle_tpu import native\n"
        "native._NATIVE_DIR = %r\n"
        "native._LIB_PATH = os.path.join(%r, 'build', 'libptpu_core.so')\n"
        "assert native.available(), native.last_error()\n"
        "q = native.NativeBlockingQueue(capacity=3)\n"
        "q.push(b'abc')\n"
        "print('loaded', q.size())\n" % (repo, tree, tree))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen([sys.executable, "-c", child], env=env,
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True) for _ in range(2)]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
        assert out.strip() == "loaded 1"
    left = os.listdir(os.path.join(tree, "build"))
    assert "libptpu_core.so" in left
    assert not [f for f in left if f.endswith(".tmp")]
