"""The deterministic demo model and request stream the serving tests
share (``tests/test_serving.py``, ``test_frontend.py``, ``test_router.py``,
``test_serving_resilience.py``): a tiny softmax MLP trained and saved per
seed, a MIXED batch-size request list, and a closed-loop replay of it
against a ``BatchingServer``. Timings come from ``perfbench/`` on the
chip, which has a load generator of its own.
"""

import threading
import time

import numpy as np

__all__ = ["build_demo_model", "demo_requests", "replay",
           "DEMO_FEATURES", "DEMO_CLASSES"]

DEMO_FEATURES = 12
DEMO_CLASSES = 3
# request batch-size mix: deliberately NOT the bucket rungs — the point
# is that odd user sizes resolve to the finite ladder
DEMO_BATCH_MIX = (1, 2, 3, 5, 7, 8, 4, 6)


def build_demo_model(dirname, seed=3, train_steps=30):
    """Train + save the tiny softmax MLP the serving tests serve.
    Deterministic per seed (fixed program seeds, fresh name counters, a
    seeded data stream), so two builds agree on every cache key."""
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope

    with unique_name.guard({}):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = seed
        startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[DEMO_FEATURES],
                                  dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="int64")
            h = fluid.layers.fc(input=x, size=24, act="relu")
            pred = fluid.layers.fc(input=h, size=DEMO_CLASSES,
                                   act="softmax")
            loss = fluid.layers.mean(
                fluid.layers.cross_entropy(input=pred, label=y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = Scope()
        rng = np.random.RandomState(seed)
        base = rng.randn(DEMO_CLASSES, DEMO_FEATURES).astype("float32")
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(train_steps):
                lbl = rng.randint(0, DEMO_CLASSES, 32)
                xb = base[lbl] + 0.2 * rng.randn(
                    32, DEMO_FEATURES).astype("float32")
                exe.run(main, feed={"x": xb, "y": lbl.reshape(-1, 1)},
                        fetch_list=[loss])
            fluid.io.save_inference_model(dirname, ["x"], [pred], exe,
                                          main_program=main)
    return dirname


def demo_requests(n, seed=17):
    """``n`` deterministic requests with a mixed batch-size stream —
    every size in DEMO_BATCH_MIX appears, none above the default
    ladder top."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        rows = DEMO_BATCH_MIX[i % len(DEMO_BATCH_MIX)]
        out.append({"x": rng.randn(rows, DEMO_FEATURES).astype("float32")})
    return out


def replay(server, requests, concurrency=4):
    """Closed-loop replay: ``concurrency`` client threads round-robin
    the request list, each running its request synchronously (what a
    fleet of synchronous callers looks like, and what makes the
    dispatcher's coalescing window matter). Returns
    ``(wall_seconds, ok_count, error_list)``."""
    errors = []
    ok = [0] * concurrency

    def client(cid):
        for req in requests[cid::concurrency]:
            try:
                server.run(req)
                ok[cid] += 1
            except Exception as exc:  # noqa: BLE001 - collected
                errors.append(exc)

    threads = [threading.Thread(target=client, args=(i,),
                                name="paddle-tpu-loadgen-%d" % i)
               for i in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.perf_counter() - t0, sum(ok), errors
