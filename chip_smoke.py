"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4  # four chips: the sharded train step
                                    # against the same steps on one chip

One process (a chip belongs to one process at a time), the public API
only, at the full published width of the one model the repo both trains
and serves — Transformer base: 6 layers, 8 heads, d_model 512, d_inner
2048, vocab 32000, seq 256, batch 64, dropout 0.1, bf16 AMP, Adam —
with random weights made from a seed.

* **train**: ``Adam.minimize`` + ``rewrite_program_amp`` under
  ``program_guard``; ``Executor(TPUPlace())``; a few steps on one
  repeated seeded batch. Losses finite and falling, state and fetch on
  the TPU device, and the flash kernel's forward, dK/dV and dQ present
  as ``tpu_custom_call`` in the COMPILED step, once per attention.
* **serve**: same widths. A paged ``SlotDecodeSession`` behind an
  in-process ``ServingFrontend`` on a socket; ``ServingClient``s stream
  ``generate`` requests of mixed source lengths, some admitted while
  others are mid-decode. Every stream completes, tokens equal those of
  the same geometry decoded under ``FLAGS_paged_attention=reference``
  (the in-tree oracle), and the compiled decode step holds the paged
  kernel's ``tpu_custom_call``.
* **--chips 4**: the train program through ``ParallelExecutor`` on the
  planning mesh fsdp=2 x tp=2 (a data axis and a model axis, layouts
  derived by ``derive_sharding``), against the same steps from the same
  seed on one chip. No other phase.

There is no CPU branch: with no accelerator the script fails before any
phase and prints no result. Each phase is a plain function of a size
and a place, so ``tests/test_tpu_chip_smoke.py`` rehearses the control flow
at a tiny size on the CPU backend; a rehearsal never prints the ``ok``
line. Each phase prints one JSON line for the reader (times are host
clock around ``block_until_ready``; none is a claim). The last line is
``{"ok": true, "device": {...}}`` and nothing else.
"""

import argparse
import json
import re
import sys
import threading
import time

import numpy as np

# Transformer base (Vaswani et al. 2017, table 3 "base"), at its
# published widths. Nothing is cut for the one-chip run.
FULL = dict(n_layer=6, n_head=8, d_model=512, d_inner=2048, vocab=32000,
            seq=256, batch=64, train_steps=8,
            # serving pool geometry: page size 16 (one bf16 sublane
            # tile; Mosaic also accepts 8 and 128 at dh=64 — see
            # tests/test_tpu_lowering.py), 8 slots of 256/16 = 16 pages
            num_slots=8, page_size=16, decode_steps=4,
            src_lens=(256, 37, 129, 5, 200, 64), late_after=2)

MESH_STEPS = 4
# f32 master weights on both sides and bf16 AMP compute: the sharded
# step's matmuls and reductions split differently, so losses agree to
# bf16 rounding of a loss near ln(vocab), not to the bit
MESH_LOSS_RTOL = 2e-2


def emit(record):
    print(json.dumps(record), flush=True)


def device_info(devices):
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def cache_info():
    from paddle_tpu.core import exec_cache

    st = exec_cache.stats()
    return {"dir": st["xla_cache_dir"],
            "backend_compiles": st["backend_compiles"],
            "persistent_hits": st["persistent_hits"],
            "persistent_misses": st["persistent_misses"],
            "compile_seconds_cold": round(st["compile_seconds_cold"], 3),
            "compile_seconds_warm": round(st["compile_seconds_warm"], 3)}


def mosaic_calls(texts, names):
    """{kernel name: Mosaic custom calls of it} over compiled HLO texts
    — read from what the compiler produced, not from a flag. A call's
    ``op_name`` metadata carries the pallas_call's name (operands named
    after another kernel's results do not count)."""
    ops = [m.group(1) for text in texts for line in text.splitlines()
           if 'custom_call_target="tpu_custom_call"' in line
           for m in [re.search(r'op_name="([^"]*)"', line)] if m]
    return {name: sum(name in op for op in ops) for name in names}


def flash_kernel_names():
    import importlib

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    return (fa.FWD_KERNEL_NAME, fa.BWD_DKV_KERNEL_NAME,
            fa.BWD_DQ_KERNEL_NAME)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


# -- train --------------------------------------------------------------------

def build_train(cfg):
    """(main, startup, loss, feed): the config above, one seeded batch."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer
    from paddle_tpu.transpiler import rewrite_program_amp

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    # fresh name counters: parameter names are part of the model (the
    # decoder builders and the sharding plan look them up by name)
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss, _, _ = transformer.build(
            src_vocab_size=cfg["vocab"], trg_vocab_size=cfg["vocab"],
            max_length=cfg["seq"], n_layer=cfg["n_layer"],
            n_head=cfg["n_head"], d_model=cfg["d_model"],
            d_inner=cfg["d_inner"], dropout=0.1)
        fluid.optimizer.Adam(learning_rate=2e-4).minimize(loss)
    rewrite_program_amp(main, "bfloat16")
    rng = np.random.RandomState(11)
    bs, seq, vocab = cfg["batch"], cfg["seq"], cfg["vocab"]
    feed = {
        "src_word": rng.randint(1, vocab, (bs, seq)).astype("int64"),
        "src_len": np.full((bs, 1), seq, "int64"),
        "trg_word": rng.randint(1, vocab, (bs, seq)).astype("int64"),
        "trg_len": np.full((bs, 1), seq, "int64"),
        "label": rng.randint(1, vocab, (bs, seq)).astype("int64"),
    }
    return main, startup, loss, feed


def _timed_steps(run_step, steps):
    """Losses, per-step wall seconds (each step is waited for) and the
    last step's fetched array."""
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        out = run_step()
        out.block_until_ready()
        secs.append(time.perf_counter() - t0)
        losses.append(float(np.asarray(out).ravel()[0]))
    return losses, secs, out


def _check_losses(losses):
    check(all(np.isfinite(losses)), "non-finite loss: %r" % losses)
    check(losses[-1] < losses[0],
          "loss did not fall on a repeated batch: %r" % losses)


def train_phase(cfg, place):
    import paddle_tpu as fluid

    main, startup, loss, feed = build_train(cfg)
    device = place.jax_device()
    scope = fluid.Scope()
    exe = fluid.Executor(place)
    exe.run(startup, scope=scope)

    losses, secs, fetched = _timed_steps(lambda: exe.run(
        main, feed=feed, fetch_list=[loss], scope=scope,
        return_numpy=False)[0], cfg["train_steps"])
    _check_losses(losses)
    check(fetched.devices() == {device},
          "fetched loss lives on %s, not %s" % (fetched.devices(), device))
    state = [scope.find_var(n).value for n in scope.local_var_names()]
    stray = [v.devices() for v in state
             if hasattr(v, "devices") and v.devices() != {device}]
    check(state and not stray, "state off %s: %s" % (device, stray[:3]))
    t_text = time.perf_counter()
    texts = exe.compiled_text(main)
    kernels = mosaic_calls(texts, flash_kernel_names())
    text_seconds = time.perf_counter() - t_text
    return {
        "phase": "train", "device": device_info([device]),
        "config": {k: cfg[k] for k in ("n_layer", "n_head", "d_model",
                                       "d_inner", "vocab", "seq", "batch")},
        "amp": "bfloat16", "optimizer": "adam",
        "losses": [round(v, 4) for v in losses],
        "first_step_seconds": round(secs[0], 3),
        "step_ms": [round(1e3 * s, 2) for s in secs[1:]],
        "kernels": kernels, "attentions": 3 * cfg["n_layer"],
        "executables": len(texts),
        "compiled_text_seconds": round(text_seconds, 3),
        "cache": cache_info(),
    }


# -- serve --------------------------------------------------------------------

def _decoder_cfg(cfg):
    return dict(src_vocab_size=cfg["vocab"], trg_vocab_size=cfg["vocab"],
                n_layer=cfg["n_layer"], n_head=cfg["n_head"],
                d_inner=cfg["d_inner"])


def _session(cfg, exe, scope):
    from paddle_tpu.serving.generation import SlotDecodeSession

    return SlotDecodeSession(
        exe, num_slots=cfg["num_slots"], max_length=cfg["seq"],
        d_model=cfg["d_model"], paged=True, page_size=cfg["page_size"],
        steps=cfg["decode_steps"], scope=scope.new_scope(),
        **_decoder_cfg(cfg))


def _stream_all(address, src, src_lens, late_after):
    """One client thread per request. The first ``late_after`` start at
    once; the rest only after those have streamed their first tokens, so
    they are admitted while others are mid-decode. Returns per-request
    (rows, first-token time, end time, token events, tokens)."""
    from paddle_tpu.serving import ServingClient

    n = len(src_lens)
    results, errors = [None] * n, []
    streaming = [threading.Event() for _ in range(n)]  # first tokens seen

    def one(i):
        first = [None]
        events, tokens = [0], [0]

        def see(ev):
            if ev.get("event") == "tokens":
                events[0] += 1
                tokens[0] += len(ev["tokens"])
                if first[0] is None:
                    first[0] = time.perf_counter()
                    streaming[i].set()

        try:
            # a stream may wait behind another's first-use compile
            client = ServingClient(address, timeout_s=600.0)
            try:
                rows = client.generate_full(src[i], src_len=src_lens[i],
                                            on_event=see)
            finally:
                client.close()
            results[i] = (rows, first[0], time.perf_counter(), events[0],
                          tokens[0])
        except BaseException as exc:  # reported by the caller
            errors.append((i, exc))
        finally:
            streaming[i].set()  # never leave the caller waiting

    threads = [threading.Thread(target=one, args=(i,), daemon=True,
                                name="chip-smoke-client-%d" % i)
               for i in range(n)]
    t0 = time.perf_counter()
    for t in threads[:late_after]:
        t.start()
    for seen in streaming[:late_after]:
        seen.wait(timeout=600)
    for t in threads[late_after:]:
        t.start()
    for t in threads:
        t.join(timeout=600)
    check(not errors, "stream(s) failed: %r" % errors[:2])
    check(not any(t.is_alive() for t in threads), "a stream never ended")
    return t0, results


def serve_phase(cfg, place):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import flags
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.models import transformer
    from paddle_tpu.observability import REGISTRY
    from paddle_tpu.serving import ServingFrontend

    device = place.jax_device()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 13
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        transformer.build(dropout=0.0, label_smooth_eps=0.0,
                          max_length=cfg["seq"], d_model=cfg["d_model"],
                          **_decoder_cfg(cfg))
    scope = fluid.Scope()
    exe = fluid.Executor(place)
    exe.run(startup, scope=scope)

    lens = list(cfg["src_lens"])
    rng = np.random.RandomState(17)
    src = rng.randint(3, cfg["vocab"], (len(lens), cfg["seq"])).astype("int64")

    # Greedy streams from random weights turn on near-ties of 32000
    # logits. At the TPU's default f32 matmul precision (one bf16 pass)
    # any two attention implementations round differently and the
    # streams part at the first tie, so BOTH sessions run at HIGHEST:
    # what remains between them is f32 summation order.
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        flags.set_flag("paged_attention", "reference")
        try:
            want = _session(cfg, exe, scope).generate(src, lens)
        finally:
            flags.set_flag("paged_attention", "auto")

        # a server warms its executables before it takes traffic: one
        # request in process, which is also the kernel's first answer
        sess = _session(cfg, exe, scope)
        t_warm = time.perf_counter()
        warm = sess.generate(src[:1], lens[:1])
        warm_seconds = time.perf_counter() - t_warm
        check((warm == want[:1]).all(), "the kernel session's first "
              "stream differs from the oracle at token %d"
              % int(np.argmax(warm[0] != want[0])))
        fe = ServingFrontend(session=sess)
        try:
            t0, results = _stream_all(fe.address, src, lens,
                                      cfg["late_after"])
        finally:
            fe.close()
        step_texts = exe.compiled_text(sess.step_program)
    finally:
        jax.config.update("jax_default_matmul_precision", was)

    got = np.concatenate([r[0] for r in results], axis=0)
    check(got.shape == want.shape, "stream matrix %s vs oracle %s"
          % (got.shape, want.shape))
    same = (got == want).all(axis=1)
    check(same.all(), "streams %s differ from the reference-attention "
          "oracle (first at token %s)" % (
              np.flatnonzero(~same).tolist(),
              [int(np.argmax(got[i] != want[i]))
               for i in np.flatnonzero(~same)]))
    firsts = [r[1] for r in results]
    ends = [r[2] for r in results]
    late = range(cfg["late_after"], len(lens))
    overlapped = [i for i in late if any(
        firsts[j] < firsts[i] < ends[j] for j in range(cfg["late_after"]))]
    check(overlapped, "no request was admitted while another was "
          "mid-decode")
    check(sess.pool_conserved and sess.free_slots == cfg["num_slots"],
          "pool not drained: %d free slots, %d pages in use"
          % (sess.free_slots, sess.pages_in_use))
    check("paddle_tpu_kernel_fallbacks_total" not in REGISTRY.to_prometheus(),
          "a kernel fallback counter exists")
    token_events = sum(r[3] for r in results)
    return {
        "phase": "serve", "device": device_info([device]),
        "config": dict(_decoder_cfg(cfg), d_model=cfg["d_model"],
                       max_length=cfg["seq"]),
        "pool": {"num_slots": cfg["num_slots"],
                 "page_size": cfg["page_size"],
                 "pages_per_slot": pa.pages_for(cfg["seq"],
                                                cfg["page_size"]),
                 "tokens_per_dispatch": cfg["decode_steps"],
                 "page_dtype": "float32"},
        "matmul_precision": "highest",
        "warmup_seconds": round(warm_seconds, 3),
        "requests": len(lens), "src_lens": lens,
        "admitted_mid_decode": overlapped,
        "streams_complete": len(results),
        "tokens": sum(r[4] for r in results),
        "tokens_equal_reference_oracle": True,
        "ttft_ms": [round(1e3 * (f - t0), 1) for f in firsts],
        "ms_per_token_event": round(
            1e3 * (max(ends) - min(firsts)) / max(token_events, 1), 3),
        "kernels": mosaic_calls(step_texts, (pa.PAGED_KERNEL_NAME,)),
        # the oracle's reference-flag step and the served kernel step
        "executables": len(step_texts),
        "cache": cache_info(),
    }


# -- four chips -----------------------------------------------------------------

def mesh_phase(cfg, devices):
    """The train program through ``ParallelExecutor`` on the planning
    mesh data=1 x fsdp=2 x tp=2 over ``devices`` — the batch shards
    over fsdp (the data axis), parameters and Adam state over fsdp x tp
    (Megatron column/row splits on tp), all derived from the op graph
    by ``derive_sharding`` with no hand-written layout — against the
    same steps from the same seed on ``devices[0]`` alone."""
    import paddle_tpu as fluid

    steps = MESH_STEPS
    check(len(set(devices)) == 4, "need 4 distinct devices: %s" % (devices,))
    place = (fluid.CPUPlace() if devices[0].platform == "cpu"
             else fluid.TPUPlace())

    main, startup, loss, feed = build_train(cfg)
    scope = fluid.Scope()
    fluid.Executor(place).run(startup, scope=scope)
    pe = fluid.ParallelExecutor(
        loss_name=loss.name, main_program=main, scope=scope,
        use_tpu=devices[0].platform != "cpu", num_devices=4, fsdp=2, tp=2)
    mesh_devices = list(pe.mesh.devices.flat)
    check(set(mesh_devices) == set(devices) and len(mesh_devices) == 4,
          "mesh %s holds %s, not %s" % (dict(pe.mesh.shape), mesh_devices,
                                        devices))
    mesh_losses, mesh_secs, _ = _timed_steps(
        lambda: pe.run([loss], feed=feed, return_numpy=False)[0], steps)

    # parameter and optimizer-state shards addressable on all four
    plan = pe.sharding_plan(
        feed_shapes={n: tuple(v.shape) for n, v in feed.items()})
    sharded = sorted(plan.sharded_params())
    check(sharded, "the derived plan sharded no parameter")
    four_way = []
    for name in scope.local_var_names():
        value = scope.find_var(name).value
        shards = getattr(value, "addressable_shards", None)
        if not shards or not any(name.startswith(p) for p in sharded):
            continue
        if (len({s.device for s in shards}) == 4
                and shards[0].data.size * 4 == value.size):
            four_way.append(name)
    params = [n for n in four_way if n in sharded]
    opt_state = [n for n in four_way if "moment" in n]
    check(params and opt_state, "no parameter/Adam moment is split four "
          "ways across the mesh: params=%s opt_state=%s"
          % (params[:3], opt_state[:3]))
    text = "\n".join(pe.compiled_text())
    collectives = {op: text.count(" %s(" % op) + text.count(" %s-start(" % op)
                   for op in ("all-reduce", "all-gather", "reduce-scatter",
                              "all-to-all", "collective-permute")}
    check(collectives["all-reduce"] + collectives["reduce-scatter"] > 0
          and collectives["all-gather"] > 0,
          "expected gradient reductions and parameter all-gathers in the "
          "sharded step: %r" % collectives)

    # the same steps, same seed, one device
    main, startup, loss, feed = build_train(cfg)
    scope1 = fluid.Scope()
    exe = fluid.Executor(place)
    exe.run(startup, scope=scope1)
    one_losses, one_secs, _ = _timed_steps(lambda: exe.run(
        main, feed=feed, fetch_list=[loss], scope=scope1,
        return_numpy=False)[0], steps)

    _check_losses(mesh_losses)
    _check_losses(one_losses)
    check(np.allclose(mesh_losses, one_losses, rtol=MESH_LOSS_RTOL),
          "mesh losses %r vs one-chip %r beyond rtol %g"
          % (mesh_losses, one_losses, MESH_LOSS_RTOL))
    return {
        "phase": "mesh", "device": device_info(devices),
        "mesh": dict(pe.mesh.shape), "steps": steps,
        "mesh_losses": [round(v, 4) for v in mesh_losses],
        "one_chip_losses": [round(v, 4) for v in one_losses],
        "loss_rtol": MESH_LOSS_RTOL,
        "mesh_first_step_seconds": round(mesh_secs[0], 3),
        "mesh_step_ms": [round(1e3 * s, 2) for s in mesh_secs[1:]],
        "one_chip_step_ms": [round(1e3 * s, 2) for s in one_secs[1:]],
        "plan": plan.summary(),
        "four_way_sharded": {"params": len(params),
                             "opt_state": len(opt_state),
                             "example": params[0]},
        "collectives": collectives,
        "kernels": mosaic_calls([text], flash_kernel_names()),
        "attentions": 3 * cfg["n_layer"],
        "cache": cache_info(),
    }


# -- the chip run ---------------------------------------------------------------

def require_kernels(record, expect):
    """A chip run's claim that its kernels are really there: every named
    Mosaic call is in the compiled program(s) at least ``expect`` times
    — once per attention (the compiler may unroll a decode scan, so
    more is not a fault; fewer means a reference path stood in)."""
    for name, found in record["kernels"].items():
        check(found >= expect, "%s phase: %d x %s in the compiled "
              "program, expected at least %d"
              % (record["phase"], found, name, expect))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import paddle_tpu as fluid
    from paddle_tpu.core import exec_cache

    devices = fluid.require_accelerator(args.chips)
    if devices[0].platform != "tpu":
        raise fluid.NoAcceleratorError(
            "chip_smoke.py needs a TPU; JAX's accelerator here is %r"
            % devices[0].platform)
    import jax

    check(len(jax.devices()) == args.chips,
          "--chips %d but JAX reports %d devices"
          % (args.chips, len(jax.devices())))
    exec_cache.enable_xla_cache()
    emit({"phase": "start", "device": device_info(jax.devices()),
          "jax": jax.__version__, "cache_dir": exec_cache.xla_cache_dir()})
    # each record is printed before its kernels are required, so a
    # failed requirement still shows what was found
    if args.chips == 4:
        emit(rec := mesh_phase(FULL, devices))
        require_kernels(rec, 3 * FULL["n_layer"])
    else:
        place = fluid.TPUPlace()
        emit(rec := train_phase(FULL, place))
        require_kernels(rec, 3 * FULL["n_layer"])
        emit(rec := serve_phase(FULL, place))
        require_kernels(rec, FULL["n_layer"])
    print(json.dumps({"ok": True, "device": device_info(jax.devices())}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
