"""The serving cell: a paged ``SlotDecodeSession`` behind a
``ServingFrontend``, driven over the wire by the load generator's child
process; the comparison of the served decode path with the plain
reference; and the host-side records the per-layer metrics read.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

from perfbench import harness, loadgen, weights
from perfbench.reference import transformer as reference


def decoder_kwargs(cfg):
    return dict(src_vocab_size=cfg["src_vocab_size"],
                trg_vocab_size=cfg["trg_vocab_size"],
                n_layer=cfg["n_layer"], n_head=cfg["n_head"],
                d_inner=cfg["d_inner"])


class LogitsTap(object):
    """Stands between the session and its executor. Off, every call goes
    through untouched. On, the decode dispatch also fetches the output
    projection's logits (found in the step program by the sampler op's
    input), so the check reads the logits of the very session, pool and
    kernels the cell serves with."""

    def __init__(self, exe):
        self._exe = exe
        self.on = False
        self.logits_name = None
        self.taken = []   # per dispatch: (tokens [K,S,1], logits [K,S,1,V])

    def __getattr__(self, name):
        return getattr(self._exe, name)

    def find_logits(self, step_program):
        for op in step_program.global_block().ops:
            if op.type in ("slot_decode_sample", "slot_beam_search"):
                names = [n for n in op.input_arg_names()
                         if "proj_logits" in n]
                if names:
                    self.logits_name = names[0]
                    return
        raise RuntimeError("no proj_logits input of the sampler op in the "
                           "step program: the check cannot read logits")

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, **kw):
        if not self.on:
            return self._exe.run_multi_step(program, steps, feed=feed,
                                            fetch_list=fetch_list,
                                            scope=scope, **kw)
        kw["return_numpy"] = False
        out = self._exe.run_multi_step(
            program, steps, feed=feed,
            fetch_list=list(fetch_list) + [self.logits_name], scope=scope,
            **kw)
        self.taken.append((np.asarray(out[0]), out[-1]))
        return [np.asarray(o) for o in out[:-1]]


class Checker(object):
    """For two seeded requests: encoder forward at admission, then paged
    decode of ``positions`` positions through the session, its page pool
    and its kernels, against the reference's full forward over the same
    tokens. Logits are compared, not greedy tokens."""

    def __init__(self, cfg, server):
        self.cfg, self.server = cfg, server

    def _requests(self, seed):
        rng = np.random.RandomState((int(seed) + 7) % (2 ** 32))
        T, V = self.cfg["max_length"], self.cfg["src_vocab_size"]
        lens = [int(rng.randint(lo, hi))
                for lo, hi in self.cfg["check"]["src_len_ranges"]]
        src = np.zeros((2, T), "int64")
        for i, n in enumerate(lens):
            src[i, :n] = rng.randint(3, V, n)
        return src, lens

    def _decode(self, src, lens):
        """(tokens [2, P], logits [2, P, V]) from the served session."""
        sess, tap = self.server.session, self.server.tap
        P = int(self.cfg["check"]["positions"])
        slots = [sess.admit(src[i], lens[i]) for i in range(2)]
        tap.taken, tap.on = [], True
        try:
            for _ in range(P // self.cfg["pool"]["tokens_per_dispatch"]):
                sess.step()
        finally:
            tap.on = False
        for s in slots:
            sess.cancel(s)
        toks = np.concatenate([t for t, _l in tap.taken], axis=0)
        import jax.numpy as jnp

        logits = jnp.concatenate([l[:, jnp.asarray(slots)]
                                  for _t, l in tap.taken], axis=0)
        tap.taken = []
        # [P, 2, 1, V] -> [2, P, V]; [P, S, 1] -> [2, P]
        return (toks[:, slots, 0].T,
                jnp.transpose(logits[:, :, 0, :], (1, 0, 2)))

    @staticmethod
    def _rel_l2(got, want):
        import jax.numpy as jnp

        got, want = jnp.asarray(got, jnp.float32), jnp.asarray(
            want, jnp.float32)
        return float(jnp.linalg.norm((got - want).ravel())
                     / jnp.linalg.norm(want.ravel()))

    def _reference(self, tree, src, lens, toks, **kw):
        import jax.numpy as jnp

        bos = self.cfg["pool"]["bos_id"]
        trg = np.concatenate([np.full((2, 1), bos, "int64"),
                              toks[:, :-1]], axis=1)
        return reference.logits(tree, jnp.asarray(src),
                                jnp.asarray(lens), jnp.asarray(trg),
                                self.cfg["n_head"], **kw)

    def _served(self, seed):
        """The seed's weights into the served session, its two requests
        decoded there, and the reference over the same tokens twice: at the
        configuration's stated precision (float32, every product's operands
        rounded to bfloat16 once, as the v5e's DEFAULT precision computes
        it) and at float32 HIGHEST. Returns (tree, (src, lens, tokens),
        served logits, stated, exact)."""
        tree, named = weights.make(self.cfg, seed)
        weights.install(named, self.server.scope)
        src, lens = self._requests(seed)
        toks, got = self._decode(src, lens)
        args = (src, lens, toks)
        return (tree, args, got,
                self._reference(tree, *args, quant=one_bf16_pass),
                self._reference(tree, *args))

    def numbers(self, seed):
        """``logit_rel_l2`` is judged: the served logits against the
        reference at the stated precision. ``..._vs_highest`` is printed:
        the distance to exact float32, which the stated precision's own
        rounding dominates (0.5% on the chip) and a bfloat16 control would
        hide behind."""
        _tree, _args, got, stated, exact = self._served(seed)
        return {"logit_rel_l2": self._rel_l2(got, stated),
                "logit_rel_l2_vs_highest": self._rel_l2(got, exact)}

    def control_numbers(self, seed):
        """The reference in the program's place, one precision below the
        configuration's float32: weights and every activation in
        bfloat16."""
        import jax
        import jax.numpy as jnp

        tree, args, _got, stated, exact = self._served(seed)
        low = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), tree)
        got = self._reference(low, *args, act=_to_bf16)
        return {"logit_rel_l2": self._rel_l2(got, stated),
                "logit_rel_l2_vs_highest": self._rel_l2(got, exact)}


def one_bf16_pass(x):
    """A product operand as the DEFAULT matmul precision sees it."""
    import jax.numpy as jnp

    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _to_bf16(x):
    import jax.numpy as jnp

    return x.astype(jnp.bfloat16)


def verdict(numbers, limits):
    ok = True
    for key, limit in limits.items():
        good = numbers[key] <= limit
        ok = ok and good
        harness.log("check %s = %.6g (limit %.6g) %s"
                    % (key, numbers[key], limit,
                       "ok" if good else "NOT CORRECT"))
    harness.log("logit_rel_l2_vs_highest = %.6g (not judged: the stated "
                "precision's own rounding)"
                % numbers["logit_rel_l2_vs_highest"])
    return ok


class Server(object):
    """The system under test, built and warmed once."""

    def __init__(self, cell, seed, place, setup, profiler=None):
        import paddle_tpu as fluid
        from paddle_tpu.models import transformer
        from paddle_tpu.serving.generation import SlotDecodeSession

        cfg = self.cfg = cell.config
        pool = cfg["pool"]
        self.profiler = profiler
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 13
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            transformer.build(dropout=0.0, label_smooth_eps=0.0,
                              max_length=cfg["max_length"],
                              d_model=cfg["d_model"], **decoder_kwargs(cfg))
        self.scope = fluid.Scope()
        exe = fluid.Executor(place)
        exe.run(startup, scope=self.scope)
        _tree, named = weights.make(cfg, seed)
        weights.install(named, self.scope)
        del _tree
        setup.part("startup_init")
        self.tap = LogitsTap(exe)
        self.session = SlotDecodeSession(
            self.tap, num_slots=pool["num_slots"],
            max_length=cfg["max_length"], d_model=cfg["d_model"],
            bos_id=pool["bos_id"], eos_id=pool["eos_id"], paged=True,
            page_size=pool["page_size"],
            steps=pool["tokens_per_dispatch"],
            scope=self.scope.new_scope(), **decoder_kwargs(cfg))
        self.tap.find_logits(self.session.step_program)
        setup.part("program_build")
        self.host = {"admit": [], "step": []}
        self.frontend = None

    def warm(self):
        """Every program the traffic will use, once: admission, the decode
        dispatch, and the cancel's table rewrite."""
        sess, cfg = self.session, self.cfg
        src = np.full((1, cfg["max_length"]), 3, "int64")
        for _ in range(2):
            sess.enqueue(src, 16)
            sess.pump()
            sess.pump()
            for slot in list(range(cfg["pool"]["num_slots"])):
                sess.cancel(slot)
        if not (sess.pool_conserved
                and sess.free_slots == cfg["pool"]["num_slots"]):
            raise RuntimeError("pool not drained after the warm-up")

    def instrument(self):
        """Host spans around the two calls the decode worker makes into
        the session, on the host's clock and (as annotations) on the
        profiler's."""
        import jax

        sess, host = self.session, self.host
        admit_pending, step, cancel = (sess.admit_pending, sess.step,
                                       sess.cancel)

        def timed_admit():
            if not sess.pending_requests or not sess.free_slots:
                return admit_pending()
            t0 = time.time()
            with jax.profiler.TraceAnnotation("pb:admit"):
                out = admit_pending()
            host["admit"].append((t0, time.time(), len(out)))
            return out

        def timed_step():
            live = len(sess.active_slots)
            t0 = time.time()
            with jax.profiler.TraceAnnotation("pb:step"):
                out = step()
            host["step"].append((t0, time.time(), live))
            return out

        def timed_cancel(slot):
            with jax.profiler.TraceAnnotation("pb:cancel"):
                return cancel(slot)

        sess.admit_pending, sess.step = timed_admit, timed_step
        sess.cancel = timed_cancel

    def start(self, backlog):
        from paddle_tpu.serving import ServingFrontend

        self.frontend = ServingFrontend(session=self.session,
                                        max_stream_backlog=int(backlog))
        return self.frontend.address

    def close(self):
        if self.frontend is not None:
            self.frontend.close(drain=False, timeout=30.0)
            self.frontend = None


class Client(object):
    """The load generator's child process. ``vocab`` (token ids are drawn
    below it) and ``max_length`` (the longest request) are the caller's
    to say: a configuration's keys for them are its family's."""

    def __init__(self, cell, traffic, seed, seconds, out_dir, vocab,
                 max_length):
        self.out_path = os.path.join(out_dir, "loadgen.json")
        spec = {"traffic": traffic, "seed": int(seed),
                "seconds": float(seconds), "out": self.out_path,
                "vocab": int(vocab), "max_length": int(max_length)}
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
            [cell.root] + ([os.environ["PYTHONPATH"]]
                           if os.environ.get("PYTHONPATH") else [])))
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(cell.dir, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True, cwd=cell.root)
        self._send(spec)

    def _send(self, obj):
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def _expect(self, word):
        line = self.proc.stdout.readline().strip()
        if line != word:
            self.kill()
            raise RuntimeError("load generator said %r, not %r"
                               % (line, word))

    def ready(self):
        self._expect("ready")

    def go(self, address, t_open):
        self._send({"address": list(address), "t_open": t_open})

    def result(self):
        self._expect("done")
        self.proc.wait(timeout=60)
        with open(self.out_path) as f:
            return json.load(f)

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def length_weights(plan):
    """Mean context a decode slot-step attends over, weighting each
    request by the steps it lives: self-attention sees the positions
    decoded so far, cross attention the source."""
    lt = plan["trg_len"].astype(np.float64)
    ls = plan["src_len"].astype(np.float64)
    return {"mean_self_context": float(np.sum(lt * (lt + 1) / 2.0)
                                       / np.sum(lt)),
            "mean_cross_context": float(np.sum(ls * lt) / np.sum(lt))}


def transformer_client(cell, traffic, seed, seconds, out_dir):
    """The Transformer cells' client: source ids from the source
    vocabulary, requests up to ``max_length``."""
    return Client(cell, traffic, seed, seconds, out_dir,
                  cell.config["src_vocab_size"], cell.config["max_length"])


def drive(server, traffic, seconds, client, on_open=None, profiler=None):
    """One measured window of ``client``'s plan against a warm server.
    Returns the summary of the client's records and the host's."""
    try:
        client.ready()
        address = server.frontend.address
        t_open = time.time() + float(traffic["ramp_s"]) + 0.2
        client.go(address, t_open)
        # the ramp: requests flow, nothing is sampled yet
        time.sleep(max(0.0, t_open - time.time()))
        if on_open is not None:
            on_open(t_open)
        for key in server.host:
            del server.host[key][:]
        if profiler is not None:
            profiler.start()
            time.sleep(float(traffic.get("trace_s", 3.0)))
            profiler.stop()
        data = client.result()
    finally:
        client.kill()
    records = data["records"]
    summary = loadgen.summarize(records, seconds)
    host = {k: [(a - t_open, b - t_open, n) for a, b, n in v]
            for k, v in server.host.items()}
    return summary, records, host
