"""Forensics smoke (CI ``forensics`` stage): crash like production does,
then read the black box like an engineer would.

Two subprocess legs, both asserted from the parent:

1. **NaN leg** — the child runs a hand-built program whose ``log`` op
   goes non-finite under ``FLAGS_check_nan_inf=1``. The child must die
   non-zero, the black box must record the N001 diagnostic blaming the
   ``log`` op, and ``tools/blackbox_dump.py`` must exit 3 (its
   NaN-gate) on that dump.
2. **Signal leg** — the child SIGTERMs itself mid-run. The process must
   die BY the signal (not a clean exit), and the dump's last events
   must show the fatal signal arriving after the step dispatch.

Usage: python tools/forensics_smoke.py          # parent, runs both legs
       (child modes are internal)
"""

import json
import os
import signal
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _child_env(box):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", FLAGS_blackbox_path=box,
               FLAGS_check_nan_inf="1", FLAGS_nan_provenance="1")
    return env


def _build_and_run_nan():
    import numpy as np

    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        h = fluid.layers.scale(x, scale=2.0)
        y = fluid.layers.log(h)       # x contains a zero -> -inf here
        out = fluid.layers.mean(y)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"x": np.array([[1.0, 2.0, 0.0, 3.0]], dtype="float32")}
    exe.run(main, feed=feed, fetch_list=[out])  # raises NonFiniteError


def _run_then_sigterm():
    import numpy as np

    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        out = fluid.layers.mean(fluid.layers.scale(x, scale=2.0))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed={"x": np.ones((2, 4), "float32")},
            fetch_list=[out])
    os.kill(os.getpid(), signal.SIGTERM)  # handler dumps, then re-raises
    raise SystemExit("unreachable: SIGTERM should have killed us")


def _read_box(box):
    with open(box) as f:
        return json.load(f)


def _nan_leg(tmp):
    box = os.path.join(tmp, "nan.box.json")
    rc = subprocess.call(
        [sys.executable, os.path.abspath(__file__), "child-nan", box],
        env=_child_env(box))
    assert rc != 0, "NaN child should have died non-zero, got rc=0"
    snap = _read_box(box)
    diag = snap.get("nan_diagnostic")
    assert diag, "black box carries no nan_diagnostic: %s" % sorted(snap)
    assert diag["rule"] == "N001" and diag["op_type"] == "log", (
        "expected N001 blaming 'log', got %r" % diag)
    # the CLI gate: exit 3 when a NaN diagnostic is recorded
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "blackbox_dump.py"),
         box], capture_output=True, text=True)
    assert proc.returncode == 3, (
        "blackbox_dump should exit 3 on a NaN dump, got %d\n%s"
        % (proc.returncode, proc.stdout + proc.stderr))
    assert "N001" in proc.stdout and "log" in proc.stdout, proc.stdout
    print("forensics nan leg OK: N001 blamed op 'log'; dump CLI exits 3")


def _signal_leg(tmp):
    box = os.path.join(tmp, "sig.box.json")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "child-signal", box],
        env=_child_env(box))
    assert proc.returncode == -signal.SIGTERM, (
        "child should die BY SIGTERM (rc=-15), got rc=%d"
        % proc.returncode)
    snap = _read_box(box)
    kinds = [e["kind"] for e in snap["events"]]
    assert "fatal_signal" in kinds and "dispatch" in kinds, kinds
    assert snap["reason"].startswith("fatal_signal"), snap["reason"]
    assert snap.get("thread_stacks"), "signal dump must carry stacks"
    print("forensics signal leg OK: SIGTERM death left a readable box")


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "child-nan":
        _build_and_run_nan()
        return
    if len(sys.argv) > 1 and sys.argv[1] == "child-signal":
        _run_then_sigterm()
        return
    import tempfile

    with tempfile.TemporaryDirectory(prefix="forensics_") as tmp:
        _nan_leg(tmp)
        _signal_leg(tmp)
    print("forensics smoke OK")


if __name__ == "__main__":
    main()
