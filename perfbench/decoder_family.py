"""What every decoder-only family's entry and check share: ONE ``run``,
ONE ``make_checker`` and ONE comparison of served logits and choices of
experts with a reference. A ``DecoderOnlySession``
(``serving/decoder_session.py``) behind the same ``ServingFrontend``, wire
and clients as entry ``frontend``, prompts prefilled several a dispatch.

A family's own parts are its common module's (``serve_glm_common``,
``serve_jamba_common``, ``serve_trinity_common``): ``Server``, ``Checker``,
``verdict`` and ``client_sizes``. Its entry, ``entries/<entry>.py``, imports
the program's modules it needs BEFORE anything is started (a program that
lacks them, the parent of the PR that brought them, fails there at once and
leaves no child process behind) and hands the common module to ``run`` and
``make_checker``.
"""

import time

import numpy as np

from perfbench import harness, loadgen, serve_common


def _place(devices):
    import paddle_tpu as fluid

    return fluid.TPUPlace() if devices[0].platform != "cpu" \
        else fluid.CPUPlace()


def run(ctx, common):
    from paddle_tpu.observability import tracing

    cell, setup, cfg, traffic = ctx.cell, ctx.setup, ctx.cell.config, \
        ctx.cell.traffic
    devices = ctx.devices[:1]
    place = _place(devices)
    # the child imports and plans while the server builds
    vocab, longest = common.client_sizes(cfg)
    client = serve_common.Client(cell, traffic, ctx.seed, ctx.seconds,
                                 ctx.out_dir, vocab, longest)
    try:
        server = common.Server(cell, ctx.seed, place, setup)
        try:
            numbers = common.Checker(cell, server).numbers(ctx.seed)
            correct = common.verdict(numbers, cfg["check"]["limits"])
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


def make_checker(cell, devices, common):
    server = common.Server(cell, 0, _place(devices),
                           harness.Setup(time.perf_counter()))
    return common.Checker(cell, server)


def against_reference(reference, cfg, dense, tree, tokens, n_prompt, got,
                      chosen):
    """One served sequence against ``reference.forward`` following the
    program's choice of experts (``chosen``, a row a token for every
    expert layer; the ``dense`` leading layers have none): the squared
    error and norm of the logits at the last prompt position and the
    decoded ones, the (token, layer, rank) choices in which the
    reference's OWN choice differs and their count, and the largest
    margin by which a differing choice lies under the reference's last
    chosen in its biased scores."""
    import jax.numpy as jnp

    P = int(cfg["check"]["positions"])
    k = cfg["num_experts_per_tok"]
    # one compiled reference a range whatever the seed's lengths: the
    # sequence is padded to its range's end (causal: the padding changes
    # no position before it)
    total = next(hi for _lo, hi in cfg["check"]["prompt_len_ranges"]
                 if n_prompt < hi) + P
    pad = total - len(tokens)
    toks = np.concatenate([tokens, np.zeros(pad, "int64")])
    per_layer = [None] * dense + [
        jnp.asarray(np.concatenate(
            [c, np.zeros((pad, k), c.dtype)]), jnp.int32)
        for c in chosen]
    out = reference.forward(
        tree, toks, cfg, chosen=per_layer,
        logits_at=np.arange(n_prompt - 1, n_prompt + P))
    want = out["logits"]
    err = float(jnp.sum(jnp.square(got - want)))
    norm = float(jnp.sum(jnp.square(want)))
    differ, margin = 0, 0.0
    for mine, own, biased in zip(chosen, out["own"], out["biased"]):
        own = np.asarray(own)[:len(tokens)]
        biased = np.asarray(biased)[:len(tokens)]
        extra = ~(mine[:, :, None] == own[:, None, :]).any(-1)      # [T, k]
        differ += int(extra.sum())
        if extra.any():
            last = np.take_along_axis(biased, own, -1).min(-1)       # [T]
            gap = last[:, None] - np.take_along_axis(biased, mine, -1)
            margin = max(margin, float(gap[extra].max()))
    return err, norm, differ, len(tokens) * len(chosen) * k, margin
