"""Rehearsal of ``chip_smoke.py`` on the CPU backend.

The script's phases are plain functions of a size and a place, so their
control flow — program build, AMP rewrite, steps, the oracle session,
the frontend and its streaming clients, the staggered admissions, the
record each phase returns — is rehearsed here at a tiny size. A
rehearsal is not a chip run: no kernel is in these programs (the CPU
backend takes the reference paths), the ``ok`` line is never printed,
and the script itself, run on this host, fails naming the missing
accelerator.
"""

import json
import os
import subprocess
import sys

import pytest

import paddle_tpu as fluid

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

# seq 64 at 2 tokens a dispatch: the first stream decodes for 32 rounds,
# long enough on a loaded host for the late ones' first tokens to fall
# inside it (at seq 16 the serve rehearsal lost that race one run in six)
TINY = dict(chip_smoke.FULL, n_layer=1, n_head=2, d_model=32, d_inner=64,
            vocab=64, seq=64, batch=4, train_steps=3, num_slots=2,
            page_size=16, decode_steps=2, src_lens=(64, 3, 9), late_after=1)


def test_train_phase_rehearsal():
    rec = chip_smoke.train_phase(TINY, fluid.CPUPlace())
    assert rec["device"]["platform"] == "cpu"
    assert len(rec["losses"]) == 3 and rec["losses"][-1] < rec["losses"][0]
    # no Mosaic call on this backend — which is why a chip run requires
    # them and a rehearsal cannot stand in for one
    assert set(rec["kernels"].values()) == {0}
    with pytest.raises(AssertionError, match="expected at least 3"):
        chip_smoke.require_kernels(rec, 3 * TINY["n_layer"])


def test_serve_phase_rehearsal():
    rec = chip_smoke.serve_phase(TINY, fluid.CPUPlace())
    assert rec["streams_complete"] == rec["requests"] == 3
    assert rec["tokens_equal_reference_oracle"] is True
    assert rec["admitted_mid_decode"], rec
    assert rec["pool"]["pages_per_slot"] == 4
    json.dumps(rec)  # every record is one JSON line


def test_script_fails_without_an_accelerator_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "NoAcceleratorError" in out.stderr
    assert "need 1 accelerator" in out.stderr
    assert out.stdout.strip() == "", out.stdout  # no phase line, no "ok"
