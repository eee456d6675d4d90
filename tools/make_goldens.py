"""Generate the committed golden-output regressions (tests/golden/).

For each registry model (tests/golden_models.py) this builds the serving
slice, materializes deterministic numpy parameters, runs the XLA oracle,
and writes tests/golden/<name>.npz = {expected output + feed arrays}.
tests/test_golden_cpp.py then asserts BOTH engines still reproduce the
committed bytes: the XLA path (catches lowering/numerics drift) and the
C++ interpreter (catches native-serving drift) — the zero-egress analog
of the reference's pretrained-model inference regressions
(paddle/fluid/inference/tests/api/, inference/test.cmake).

Regenerate deliberately after an intentional model/numerics change:
  JAX_PLATFORMS=cpu python tools/make_goldens.py
"""

import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu as fluid
    from golden_models import GOLDEN_MODELS, build_golden

    out_dir = os.path.join(ROOT, "tests", "golden")
    os.makedirs(out_dir, exist_ok=True)
    for name in sorted(GOLDEN_MODELS):
        with fluid.scope_guard(fluid.executor.Scope()):
            pruned, feed_names, fetch, feed, exe = build_golden(name)
            (want,) = exe.run(pruned, feed=feed, fetch_list=[fetch])
        expected = np.asarray(want)
        if not np.isfinite(expected).all():
            raise RuntimeError(
                "%s: oracle produced non-finite values — refusing to "
                "commit a garbage golden (param recipe bug?)" % name)
        payload = {"expected": expected}
        payload.update({"feed_" + k: v for k, v in feed.items()})
        path = os.path.join(out_dir, name + ".npz")
        np.savez_compressed(path, **payload)
        print("%s: expected %s -> %s (%d bytes)" % (
            name, payload["expected"].shape, os.path.basename(path),
            os.path.getsize(path)))


if __name__ == "__main__":
    main()
