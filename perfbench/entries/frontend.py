"""Entry ``frontend``: the serving operator's deployment. A paged
``SlotDecodeSession`` (``serving/generation.py``) behind a
``ServingFrontend`` (``serving/frontend.py``), clients over the wire."""

import time

from perfbench import harness, loadgen, serve_common
from perfbench.loadgen import percentile


def run(ctx):
    import paddle_tpu as fluid
    from paddle_tpu.observability import tracing

    cell, setup, cfg, traffic = ctx.cell, ctx.setup, ctx.cell.config, \
        ctx.cell.traffic
    devices = ctx.devices[:1]
    place = fluid.TPUPlace() if devices[0].platform != "cpu" \
        else fluid.CPUPlace()
    # the child imports and plans while the server builds
    client = serve_common.transformer_client(cell, traffic, ctx.seed,
                                             ctx.seconds, ctx.out_dir)
    try:
        server = serve_common.Server(cell, ctx.seed, place, setup)
        try:
            numbers = serve_common.Checker(cfg, server).numbers(ctx.seed)
            correct = serve_common.verdict(numbers, cfg["check"]["limits"])
            setup.part("reference_check")
            server.warm()
            ctx.steady()
            setup.part("warmup_dispatches")
            cache = ctx.cache_stats()
            server.instrument()
            if ctx.trace:
                tracing.enable(True)
            server.start(traffic.get("max_stream_backlog", 4096))
            setup.part("frontend_start")

            def opened(t_open):
                setup.part("ramp")
                ctx.window_opened(time.perf_counter()
                                  - (time.time() - t_open))

            summary, _records, host = serve_common.drive(
                server, traffic, ctx.seconds, client, on_open=opened,
                profiler=ctx.profiler if ctx.trace else None)
            traces = tracing.completed() if ctx.trace else []
        finally:
            tracing.enable(False)
            server.close()
    finally:
        client.kill()

    sess = server.session
    drained = bool(sess.pool_conserved)
    harness.log("check pool conserved after the run: %s (limit True) %s"
                % (drained, "ok" if drained else "NOT CORRECT"))
    n = summary["attempted"]
    ttft, tpot = summary["ttft_ms"], summary["tpot_ms"]
    e2e = {"serve_tokens_per_s": summary["tokens_per_s"],
           "ttft_p95_ms": percentile(ttft, 95),
           "tpot_p95_ms": percentile(tpot, 95)}
    harness.log("requests due in the window %d, failed %d; tokens "
                "delivered in the window %.1f -> serve_tokens_per_s %.1f"
                % (n, summary["failed"], summary["tokens_in_window"],
                   summary["tokens_per_s"]))
    for name, vals in (("ttft", ttft), ("tpot", tpot)):
        if vals:
            harness.log("%s_ms over %d requests: median %.3f, p95 %.3f "
                        "(%d beyond it), max %.3f"
                        % (name, len(vals), percentile(vals, 50),
                           percentile(vals, 95), len(vals) // 20,
                           max(vals)))
    late = summary["late_ms"]
    if late:
        harness.log("generator lateness over %d sends: median %.3f ms, p99 "
                    "%.3f ms" % (len(late), percentile(late, 50),
                                 percentile(late, 99)))
    plan = loadgen.make_plan(traffic, ctx.seed, ctx.seconds)
    # the profiler runs from the window's opening for ``trace_s``
    traced_s = ctx.profiler.window_s or 0.0
    traced = [(live, cfg["pool"]["tokens_per_dispatch"])
              for a, _b, live in host["step"] if 0.0 <= a < traced_s]
    return {
        "correct": bool(correct and drained),
        "attempted": n, "failed": summary["failed"],
        "end_to_end": e2e, "cache": cache, "devices": devices,
        "serve": {"summary": summary, "host": host, "traces": traces,
                  "traced_steps": traced, "seconds": ctx.seconds,
                  "length_weights": serve_common.length_weights(plan)},
    }


def make_checker(cell, devices):
    import paddle_tpu as fluid

    server = serve_common.Server(cell, 0, fluid.TPUPlace(),
                                 harness.Setup(time.perf_counter()))
    return serve_common.Checker(cell.config, server)
