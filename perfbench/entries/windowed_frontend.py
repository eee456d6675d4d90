"""Entry ``windowed_frontend``: a decoder of sliding-window and full
attention layers with routed experts behind the same ``ServingFrontend``,
wire and clients as entries ``frontend``, ``decoder_frontend`` and
``hybrid_frontend``: a ``DecoderOnlySession``
(``serving/decoder_session.py``) whose slots own a ring of K/V pages a
window layer beside a full layer's growing pages, prompts of up to 8192
tokens prefilled in buckets. The run is ``decoder_frontend``'s step for
step (that file and ``hybrid_frontend.py`` are their models' and are not
edited); the model's own parts are ``serve_trinity_common``'s."""

import os
import time

# the program's modules this entry needs, BEFORE anything is started: a
# program that lacks them (the parent of the PR that brought them) fails
# here, at once, and leaves no child process behind
from paddle_tpu.kernels import window_paged_attention  # noqa: F401
from paddle_tpu.models.windowed_moe_decoder import build_windowed_moe_decoder  # noqa: F401

from perfbench import harness, loadgen, serve_common
from perfbench import serve_trinity_common as common


def run(ctx):
    import paddle_tpu as fluid
    from paddle_tpu.observability import tracing

    cell, setup, cfg, traffic = ctx.cell, ctx.setup, ctx.cell.config, \
        ctx.cell.traffic
    devices = ctx.devices[:1]
    place = fluid.TPUPlace() if devices[0].platform != "cpu" \
        else fluid.CPUPlace()
    # the child imports and plans while the server builds
    client = common.Client(
        cell, traffic, ctx.seed, ctx.seconds,
        os.path.join(ctx.out_dir, "loadgen.json"))
    try:
        server = common.Server(cell, ctx.seed, place, setup)
        try:
            numbers = common.Checker(cell, server).numbers(ctx.seed)
            correct = common.verdict(numbers,
                                               cfg["check"]["limits"])
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
                server, cell, traffic, ctx.seed, ctx.seconds, ctx.out_dir,
                client=client, on_open=opened,
                profiler=ctx.profiler if ctx.trace else None)
        finally:
            tracing.enable(False)
            server.close()
    finally:
        client.kill()

    sess = server.session
    drained = bool(sess.pool_conserved and not sess.active_slots)
    harness.log("check pool conserved after the run: %s (limit True) %s"
                % (drained, "ok" if drained else "NOT CORRECT"))
    n = summary["attempted"]
    harness.log("requests due in the window %d, failed %d; tokens "
                "delivered in the window %.1f -> serve_tokens_per_s %.1f"
                % (n, summary["failed"], summary["tokens_in_window"],
                   summary["tokens_per_s"]))
    for name in ("ttft_ms", "tpot_ms"):
        vals = summary[name]
        if vals:
            harness.log("%s over %d requests (not judged above the knee): "
                        "median %.3f, p95 %.3f"
                        % (name, len(vals), loadgen.percentile(vals, 50),
                           loadgen.percentile(vals, 95)))
    steps = host["step"]
    if steps:
        harness.log("decode dispatches in the window %d: %d slots live in "
                    "the median, %d resident rows; prefill dispatches %d "
                    "for %d prompts"
                    % (len(steps),
                       loadgen.percentile([s[2][0] for s in steps], 50),
                       loadgen.percentile([s[2][1] for s in steps], 50),
                       sum(len(a[2]) for a in host["admit"]),
                       sum(len(p[1]) for a in host["admit"] for p in a[2])))
    return {
        "correct": bool(correct and drained),
        "attempted": n, "failed": summary["failed"],
        "end_to_end": {"serve_tokens_per_s": summary["tokens_per_s"]},
        "cache": cache, "devices": devices,
        "serve": {"summary": summary, "host": host,
                      "seconds": ctx.seconds,
                      "traced_s": ctx.profiler.window_s or 0.0,
                      "geometry": sess.geometry},
    }


def make_checker(cell, devices):
    import paddle_tpu as fluid

    server = common.Server(
        cell, 0, fluid.TPUPlace() if devices[0].platform != "cpu"
        else fluid.CPUPlace(), harness.Setup(time.perf_counter()))
    return common.Checker(cell, server)
