"""The training cell: the trainer's program from the
configuration, seeded batches on the device, the comparison with the plain
reference, and the measured loop.

A step is one ``run_step(feed)`` call whose loss is ready on the host; the
rate is taken over every step dispatched in the window and all the time
until the last of them has its loss on the host. The traffic's
``steps_in_flight`` says how many steps the loop keeps dispatched before it
reads the oldest one's loss (1: each loss is read before the next dispatch).
"""

import collections
import time

import numpy as np

from perfbench import harness, weights
from perfbench.reference import transformer as reference

def model_kwargs(cfg):
    return dict(src_vocab_size=cfg["src_vocab_size"],
                trg_vocab_size=cfg["trg_vocab_size"],
                max_length=cfg["max_length"], n_layer=cfg["n_layer"],
                n_head=cfg["n_head"], d_model=cfg["d_model"],
                d_inner=cfg["d_inner"])


def build_program(cfg, dropout):
    """(main, startup, loss) as a trainer writes it: the model, Adam and
    the bf16 AMP rewrite."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer
    from paddle_tpu.transpiler import rewrite_program_amp

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss, _, _ = transformer.build(
            dropout=dropout, label_smooth_eps=cfg["label_smooth_eps"],
            **model_kwargs(cfg))
        fluid.optimizer.Adam(
            learning_rate=cfg["optimizer"]["learning_rate"]).minimize(loss)
    if cfg.get("amp"):
        rewrite_program_amp(main, cfg["amp"])
    return main, startup, loss


def make_batches(cfg, seed, n, batch):
    """``n`` batches of full sequences (no padding), token ids drawn on the
    device from the seed in one jitted call."""
    import jax
    import jax.numpy as jnp

    T, V = cfg["max_length"], cfg["trg_vocab_size"]

    def draw(key):
        ks = jax.random.split(key, 3)
        ids = [jax.random.randint(k, (n, batch, T), 3, V, dtype=jnp.int32)
               for k in ks]
        return ids

    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             1000 + (seed >> 31))
    src, trg, label = jax.jit(draw)(key)
    full = jnp.full((batch, 1), T, jnp.int32)
    return [{"src_word": src[i], "src_len": full, "trg_word": trg[i],
             "trg_len": full, "label": label[i]} for i in range(n)]


def fp8(x):
    """The control's matrix-product operands: scaled per tensor to fp8's
    range (e4m3, largest 448), rounded to fp8 and scaled back; gradients
    pass straight through, as an fp8 training path would have them."""
    import jax
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def rel_l2(got, want):
    import jax.numpy as jnp

    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


def grad_names(cfg):
    """{program parameter: its path in the reference's tree} of the three
    gradients compared: the source embedding (only the rows the batch
    touches are non-zero), the first attention projection (the longest way
    back) and the last feed-forward weight."""
    last = cfg["n_layer"] - 1
    return {"src_emb": ("src_emb",),
            "enc_0_mha_q.w_0": ("enc", 0, "attn", "q"),
            "dec_%d_ffn_fc2.w_0" % last: ("dec", last, "ffn", "w2")}


def _pick(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def compare(loss, grads, ref_loss, ref_grads, cfg):
    """The numbers compared with the reference: the loss's relative error
    and the worst relative L2 error over the three gradients."""
    errs = {name: rel_l2(grads[name], _pick(ref_grads, path))
            for name, path in grad_names(cfg).items()}
    return {"loss_rel": abs(float(loss) - float(ref_loss))
            / abs(float(ref_loss)),
            "grad_rel_l2": max(errs.values()), "grads": errs}


class Checker(object):
    """The first step's loss and three gradients of the trainer's program
    (dropout off: the reference cannot replay its draws), at the published
    widths on ``check_batch`` seeded sequences, against the reference."""

    def __init__(self, cfg, place):
        import paddle_tpu as fluid

        self.cfg = cfg
        self.main, startup, self.loss = build_program(cfg, dropout=0.0)
        self.scope = fluid.Scope()
        self.exe = fluid.Executor(place)
        self.exe.run(startup, scope=self.scope)

    def reference(self, tree, batch, quant=None):
        import jax.numpy as jnp

        feed = {k: jnp.asarray(v) for k, v in batch.items()}
        return reference.loss_and_grads(
            tree, feed, self.cfg["n_head"], self.cfg["label_smooth_eps"],
            quant)

    def program(self, named, batch):
        weights.install(named, self.scope)
        names = list(grad_names(self.cfg))
        out = self.exe.run(self.main, feed=batch,
                           fetch_list=[self.loss] + [n + "@GRAD"
                                                     for n in names],
                           scope=self.scope, return_numpy=False)
        return float(np.asarray(out[0]).ravel()[0]), dict(zip(names,
                                                              out[1:]))

    def numbers(self, seed):
        cfg = self.cfg
        tree, named = weights.make(cfg, seed)
        batch = make_batches(cfg, seed, 1, cfg["check"]["batch"])[0]
        ref_loss, ref_grads = self.reference(tree, batch)
        loss, grads = self.program(named, batch)
        return compare(loss, grads, ref_loss, ref_grads, cfg)

    def control_numbers(self, seed):
        """The reference in the program's place, one precision below the
        configuration's bf16: every matrix-product operand through fp8
        (e4m3)."""
        import jax.numpy as jnp

        cfg = self.cfg
        tree, _ = weights.make(cfg, seed)
        batch = make_batches(cfg, seed, 1, cfg["check"]["batch"])[0]
        ref_loss, ref_grads = self.reference(tree, batch)

        loss, grads = self.reference(tree, batch, quant=fp8)
        picked = {name: _pick(grads, path)
                  for name, path in grad_names(cfg).items()}
        return compare(loss, picked, ref_loss, ref_grads, cfg)


def verdict(numbers, limits):
    """Print each number compared beside its limit; True when all hold."""
    ok = True
    for key, limit in limits.items():
        good = numbers[key] <= limit
        ok = ok and good
        harness.log("check %s = %.6g (limit %.6g) %s"
                    % (key, numbers[key], limit,
                       "ok" if good else "NOT CORRECT"))
    harness.log("loss_rel = %.6g (not judged: see the configuration's "
                "limits_why)" % numbers["loss_rel"])
    return ok


def run_cell(ctx, make_runner, devices):
    """The whole of a training cell. ``make_runner(main, startup, loss,
    named)`` returns ``run_step(feed) -> loss array`` on this entry's
    executor, with the benchmark's weights installed."""
    import jax

    import paddle_tpu as fluid

    cell, setup, cfg, traffic = ctx.cell, ctx.setup, ctx.cell.config, \
        ctx.cell.traffic
    place = fluid.TPUPlace() if devices[0].platform != "cpu" \
        else fluid.CPUPlace()
    chips = len(devices)
    batch = int(traffic["batch_per_chip"]) * chips
    tokens_per_step = batch * 2 * cfg["max_length"]

    main, startup, loss = build_program(cfg, dropout=cfg["dropout"])
    setup.part("program_build")
    _tree, named = weights.make(cfg, ctx.seed)
    del _tree
    run_step = make_runner(main, startup, loss, named, place)
    batches = make_batches(cfg, ctx.seed, int(traffic["batches"]), batch)
    setup.part("startup_init")

    checker = Checker(cfg, place)
    numbers = checker.numbers(ctx.seed)
    correct = verdict(numbers, cfg["check"]["limits"])
    del checker
    setup.part("reference_check")

    for i in range(int(traffic.get("warmup_steps", 2))):
        float(np.asarray(run_step(batches[i % len(batches)])).ravel()[0])
    ctx.steady()
    setup.part("warmup_dispatches")
    cache = ctx.cache_stats()

    prof = ctx.profiler
    trace_steps = int(traffic.get("trace_steps", 10))
    ahead = int(traffic["steps_in_flight"])
    flying = collections.deque()
    losses, step_s, dispatch_s, stop_step = [], [], [], None
    prof.start()
    t_open = time.perf_counter()
    ctx.window_opened(t_open)
    t_prev = t_open
    while t_prev - t_open < ctx.seconds or flying:
        with prof.annotate("pb:step"):
            # keep ``ahead`` steps dispatched while the window is open;
            # after it, the ones in flight are waited for and counted
            while len(flying) < ahead and t_prev - t_open < ctx.seconds:
                t0 = time.perf_counter()
                flying.append(run_step(
                    batches[len(dispatch_s) % len(batches)]))
                dispatch_s.append(time.perf_counter() - t0)
            losses.append(float(np.asarray(flying.popleft()).ravel()[0]))
        now = time.perf_counter()
        step_s.append(now - t_prev)
        t_prev = now
        if prof.running and len(losses) >= trace_steps:
            prof.stop()  # seconds of the host: they fall into the next step
            stop_step = len(step_s)
    prof.stop()
    elapsed = t_prev - t_open

    # the batches come round in turn: compare like with like, whole turns
    # where the window holds two, else the last step with the first step
    # on the same batch
    k = len(batches)
    finite = bool(np.all(np.isfinite(losses)))
    if len(losses) >= 2 * k:
        first, last = np.mean(losses[:k]), np.mean(losses[-k:])
    elif len(losses) > k:
        first, last = losses[(len(losses) - 1) % k], losses[-1]
    else:
        first = last = float("nan")
    falls = bool(last < first)
    harness.log("check loss over the window's %d steps: %.4f at its start, "
                "%.4f at its end (limit: lower), finite %s -> %s"
                % (len(losses), first, last, finite,
                   "ok" if (finite and falls) else "NOT CORRECT"))
    rate = len(losses) * tokens_per_step / elapsed
    harness.log("train_tokens_per_s %.1f over %d steps in %.3f s (median "
                "step %.2f ms, longest %.2f ms, %d tokens a step; %d in "
                "flight, the host %.2f ms a dispatch, longest %.2f ms)"
                % (rate, len(losses), elapsed,
                   1e3 * float(np.median(step_s)), 1e3 * max(step_s),
                   tokens_per_step, ahead, 1e3 * float(np.mean(dispatch_s)),
                   1e3 * max(dispatch_s)))
    return {
        "correct": bool(correct and finite and falls),
        "attempted": len(losses),
        "failed": int(np.sum(~np.isfinite(losses))),
        "end_to_end": {"train_tokens_per_s": rate},
        "cache": cache,
        "train": {"steps": len(losses), "tokens_per_step": tokens_per_step,
                  "step_seconds": step_s, "dispatch_seconds": dispatch_s,
                  "profiler_stop_step": stop_step,
                  "tokens_per_s": rate,
                  "batch": batch, "chips": chips},
        "devices": devices,
    }
