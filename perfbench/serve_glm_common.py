"""The serving cell of a decoder-only model with latent attention and
routed experts: a ``DecoderOnlySession`` (``serving/decoder_session.py``)
behind a ``ServingFrontend``, driven over the wire by the same load
generator's child process as the Transformer's cells
(``serve_common.drive``); the comparison of what the served session itself
produced with the plain reference; and the host-side records the ``glm_``
per-layer metrics read.
"""

import importlib
import time

import numpy as np

from perfbench import decoder_family, harness, weights_glm
from perfbench.reference import latent_moe_decoder as reference


def client_sizes(cfg):
    """What the load generator's child is told: the vocabulary its ids
    are drawn from and the longest prompt."""
    return cfg["vocab_size"], cfg["pool"]["max_prompt"]


def session_kwargs(cfg):
    pool = cfg["pool"]
    return dict(probe_rows=len(cfg["check"]["prompt_len_ranges"]),
                num_slots=pool["num_slots"], max_prompt=pool["max_prompt"],
                max_new_tokens=pool["max_new_tokens"],
                page_size=pool["page_size"],
                tokens_per_dispatch=pool["tokens_per_dispatch"],
                prefill_buckets=pool["prefill_buckets"],
                prefill_token_budget=pool["prefill_token_budget"],
                dtype=cfg.get("dtype", "bfloat16"))


class Tap(object):
    """Stands between the session and its executor, in the check and in
    the measured window alike, so that both drive the SAME executables:
    every prefill dispatch also fetches its logits and its choice of
    experts, every decode dispatch the logits of the session's
    ``probe_slots`` (what the builder's ``probe_rows`` adds to the step
    program) and every slot's choice. The extras stay on the
    device and are dropped, but for the prompts the check has ``marked``:
    what it compares is what the very session, pool and kernels the cell
    serves with computed, in dispatches shared with other prompts."""

    def __init__(self, exe, fetches):
        self._exe, self._f = exe, fetches
        self.marked = []      # the prompts whose prefill is kept
        self.on = False       # keep the decode dispatches' extras
        self.prefills, self.steps = {}, []

    def __getattr__(self, name):
        return getattr(self._exe, name)

    def run(self, program, feed=None, fetch_list=None, scope=None, **kw):
        if not feed or "prompt_ids" not in feed:
            return self._exe.run(program, feed=feed, fetch_list=fetch_list,
                                 scope=scope, **kw)
        out = self._exe.run(
            program, feed=feed, scope=scope, return_numpy=False,
            fetch_list=list(fetch_list) + [self._f["first_logits"],
                                           self._f["first_chosen"]], **kw)
        if self.marked:
            self._keep_marked(feed, out[-2], out[-1])
        return [np.asarray(o) for o in out[:-2]]

    def _keep_marked(self, feed, logits, chosen):
        """The rows of this dispatch that hold a marked prompt."""
        lens = np.asarray(feed["prompt_len"])
        ids = np.asarray(feed["prompt_ids"]).reshape(len(lens), -1)
        T = ids.shape[1]
        for i, prompt in enumerate(self.marked):
            n = len(prompt)
            for row in np.flatnonzero(lens == n):
                if i not in self.prefills and (ids[row, :n] == prompt).all():
                    # cut on the host: a slice of a device array is a
                    # program of its own for every length a seed draws
                    self.prefills[i] = {
                        "slot": int(feed["slot_idx"][row]),
                        "prompts": int((lens > 0).sum()),
                        "logits": np.asarray(logits)[row],       # [1, V]
                        "chosen": np.asarray(chosen)[
                            :, row * T:row * T + n]}             # [L, n, k]

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, **kw):
        kw["return_numpy"] = False
        out = self._exe.run_multi_step(
            program, steps, feed=feed, scope=scope,
            fetch_list=list(fetch_list) + [self._f["probe_logits"],
                                           self._f["chosen"]], **kw)
        if self.on:
            self.steps.append({
                "logits": out[-2],                            # [K, n, V]
                "chosen": np.asarray(out[-1])[:, :, feed["probe_slots"]]})
        return [np.asarray(o) for o in out[:-2]]


def fp8_operands(x):
    """A product operand one precision below bfloat16."""
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


class Checker(object):
    """For two seeded prompts: prefill and ``positions`` decoded positions
    through the served session, its pool and its kernels AS THE WINDOW
    DRIVES THEM, against the reference's full forward over the same
    tokens, computed from the SAME weights upcast a layer at a time.

    The session is filled as the window fills it: every slot is taken by
    a prompt of the traffic's own length distribution, queued and
    admitted by ``admit_pending`` (prefill dispatches of several prompts
    a bucket), half of them first, the other half, with the two checked
    prompts among them, beside the first half's live decode; the
    compared positions are decoded with every slot live over the one
    page pool, through the executables the window runs (``Tap``).

    ``logit_rel_l2``: the logits' relative L2 error, the reference
    following the PROGRAM's choice of experts (a near-tie that bfloat16
    turns would otherwise swamp it). The reference's OWN choice stands
    beside it: ``expert_choice_diff_share``, the share of (token, layer,
    rank) choices in which the two differ, and
    ``expert_choice_margin_max``, over the experts the program chose and
    the reference did not, how far each lies below the reference's last
    chosen in the reference's ``s + b``: a wrong router cannot hide
    behind the first number."""

    def __init__(self, cell, server):
        self.cfg, self.traffic, self.server = (cell.config, cell.traffic,
                                               server)

    def _prompts(self, seed):
        rng = np.random.RandomState((int(seed) + 7) % (2 ** 32))
        return [rng.randint(3, self.cfg["vocab_size"],
                            int(rng.randint(lo, hi))).astype("int64")
                for lo, hi in self.cfg["check"]["prompt_len_ranges"]]

    def _waves(self, seed, prompts):
        """Every slot's prompt, in two queues: fillers at the quantiles of
        the traffic's prompt lengths in a seeded order, the checked
        ``prompts`` at seeded places of the second."""
        from perfbench import loadgen

        rng = np.random.RandomState((int(seed) + 11) % (2 ** 32))
        S = self.cfg["pool"]["num_slots"]
        lengths = rng.permutation(loadgen.draw_lengths(
            self.traffic["src_len"], S - len(prompts)))
        lengths = np.minimum(lengths, self.cfg["pool"]["max_prompt"])
        fill = [rng.randint(3, self.cfg["vocab_size"], int(n))
                .astype("int64") for n in lengths]
        first, second = fill[:S // 2], fill[S // 2:]
        for p in prompts:
            second.insert(int(rng.randint(0, len(second) + 1)), p)
        # admit_pending cuts a bucket's prompts into dispatches in queue
        # order: a checked prompt that would be the odd one left over
        # changes places with the one before it in its bucket, so that it
        # shares its dispatch wherever the bucket holds another prompt
        sess = self.server.session
        for p in prompts:
            bucket = sess.bucket_of(len(p))
            same = [i for i, q in enumerate(second)
                    if sess.bucket_of(len(q)) == bucket]
            j = next(j for j, i in enumerate(same) if second[i] is p)
            per = sess.geometry["prompts_per_dispatch"][bucket]
            if j and j == len(same) - 1 and j % per == 0:
                at, before = same[j], same[j - 1]
                second[at], second[before] = second[before], second[at]
        return first, second

    def _serve(self, prompts, seed):
        """Per prompt: (tokens fed [n + P], logits [P + 1, V] at the last
        prompt position and the P decoded ones, the program's choice per
        expert layer [n + P, k])."""
        import jax.numpy as jnp

        sess, tap = self.server.session, self.server.tap
        P = int(self.cfg["check"]["positions"])
        K = self.cfg["pool"]["tokens_per_dispatch"]
        first, second = self._waves(seed, prompts)
        tap.prefills, tap.steps, tap.marked = {}, [], prompts
        try:
            for p in first:
                sess.enqueue(p)
            sess.admit_pending()
            sess.step()
            for p in second:
                sess.enqueue(p)
            sess.admit_pending()
            if sess.free_slots or sess.pending_requests \
                    or len(tap.prefills) != len(prompts):
                raise RuntimeError(
                    "the check's fill left %d slots free and %d requests "
                    "queued; %d of %d checked prompts were prefilled"
                    % (sess.free_slots, len(sess.pending_requests),
                       len(tap.prefills), len(prompts)))
            pre = [tap.prefills[i] for i in range(len(prompts))]
            slots = [p["slot"] for p in pre]
            sess.probe_slots[:] = slots
            tap.on = True
            for _ in range(P // K):
                sess.step()
        finally:
            tap.on, tap.marked = False, []
        harness.log("check: %d slots live over %d pages; the checked "
                    "prompts (%s tokens) were prefilled beside %s others "
                    "in their dispatches"
                    % (len(sess.active_slots), sess.pages_in_use,
                       ", ".join(str(len(p)) for p in prompts),
                       ", ".join(str(p["prompts"] - 1) for p in pre)))
        out = []
        for i, (prompt, slot) in enumerate(zip(prompts, slots)):
            toks = sess.tokens_of(slot)                      # P + 1 of them
            logits = jnp.concatenate(
                [jnp.asarray(pre[i]["logits"], jnp.float32)]
                + [s["logits"][:, i].astype(jnp.float32)
                   for s in tap.steps])
            chosen = np.concatenate(
                [pre[i]["chosen"]]
                + [np.transpose(s["chosen"][:, :, i], (1, 0, 2))
                   for s in tap.steps], axis=1)              # [L, n + P, k]
            out.append((np.concatenate([prompt, toks[:P]]), logits, chosen))
        for slot in sess.active_slots:
            sess.cancel(slot)
        tap.prefills, tap.steps = {}, []
        sess.probe_slots[:] = 0
        if not sess.pool_conserved or sess.pages_in_use:
            raise RuntimeError("pool not drained after the check")
        return out

    # the family's reference, and the configuration's key for its leading
    # dense layers, which choose no experts
    reference = reference
    dense_key = "first_k_dense_replace"

    def _against_reference(self, tree, tokens, n_prompt, got, chosen):
        """The three numbers' parts for one sequence."""
        return decoder_family.against_reference(
            self.reference, self.cfg, int(self.cfg.get(self.dense_key, 0)),
            tree, tokens, n_prompt, got, chosen)

    def _numbers(self, tree, served):
        err = norm = differ = choices = 0
        margin = 0.0
        n_of = [len(t) - int(self.cfg["check"]["positions"])
                for t, _l, _c in served]
        for (tokens, logits, chosen), n in zip(served, n_of):
            e, w, dif, cho, m = self._against_reference(
                tree, tokens, n, logits, chosen)
            err, norm, differ, choices = (err + e, norm + w, differ + dif,
                                          choices + cho)
            margin = max(margin, m)
        return {"logit_rel_l2": float(np.sqrt(err / norm)),
                "expert_choice_diff_share": differ / float(choices),
                "expert_choice_margin_max": margin}

    def numbers(self, seed):
        named = self.server.load_weights(seed)
        served = self._serve(self._prompts(seed), seed)
        return self._numbers(self.server.weights.tree(named, self.cfg),
                             served)

    def control_numbers(self, seed):
        """The reference itself in the program's place, one precision
        below the configuration's: every product's operands rounded to
        float8 (e4m3), its own choice of experts, over the tokens the
        program served."""
        import jax.numpy as jnp

        named = self.server.load_weights(seed)
        tree = weights_glm.tree(named, self.cfg)
        P = int(self.cfg["check"]["positions"])
        control = []
        for tokens, _logits, _chosen in self._serve(self._prompts(seed),
                                                    seed):
            n = len(tokens) - P
            out = reference.forward(
                tree, tokens, self.cfg, quant=fp8_operands,
                logits_at=np.arange(n - 1, n + P))
            control.append((tokens, out["logits"].astype(jnp.float32),
                            np.stack([np.asarray(o) for o in out["own"]])))
        return self._numbers(tree, control)


def verdict(numbers, limits):
    ok = True
    for key, limit in limits.items():
        good = numbers[key] <= limit
        ok = ok and good
        harness.log("check %s = %.6g (limit %.6g) %s"
                    % (key, numbers[key], limit,
                       "ok" if good else "NOT CORRECT"))
    return ok


class Server(object):
    """The system under test, built and warmed once."""

    # the family's builder module in the program (``parameter_shapes``,
    # ``load_parameters``) and its weights from the seed
    model = "paddle_tpu.models.latent_moe_decoder"
    weights = weights_glm

    def __init__(self, cell, seed, place, setup):
        import paddle_tpu as fluid
        from paddle_tpu.serving.decoder_session import DecoderOnlySession

        cfg = self.cfg = cell.config
        self.scope = fluid.Scope()
        self._seed = None
        self.load_weights(seed)
        setup.part("startup_init")
        exe = fluid.Executor(place)
        self.session = DecoderOnlySession(
            exe, cfg, scope=self.scope, **session_kwargs(cfg))
        self.tap = self.session._exe = Tap(exe, self.session._fetch)
        setup.part("program_build")
        self.host = {"admit": [], "step": []}
        self.frontend = None

    def load_weights(self, seed):
        """The seed's weights into the scope (the last seed's are dropped
        first: the chip does not hold two sets). Returns them by name."""
        model = importlib.import_module(self.model)
        dtype = self.cfg.get("dtype", "bfloat16")
        names = list(model.parameter_shapes(self.cfg, dtype))
        if self._seed != seed:
            self.scope.erase([n for n in names if self.scope.has(n)])
            model.load_parameters(self.scope,
                                  self.weights.make(self.cfg, seed, dtype),
                                  self.cfg, dtype)
            self._seed = seed
        return {n: self.scope.get_value(n) for n in names}

    def warm(self):
        """Every program the traffic will use, once: each bucket's prefill
        and the decode dispatch."""
        sess = self.session
        for bucket in sess.geometry["buckets"]:
            sess.enqueue(np.full((bucket,), 3, "int64"))
        for _ in range(2):
            sess.pump()
        for slot in sess.active_slots:
            sess.cancel(slot)
        if not (sess.pool_conserved and not sess.pending_requests
                and sess.free_slots == sess.geometry["num_slots"]):
            raise RuntimeError("pool not drained after the warm-up")

    def instrument(self):
        """Host spans around the calls the decode worker makes into the
        session, on the host's clock and (as annotations) on the
        profiler's, with what each dispatched."""
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
            host["admit"].append((t0, time.time(),
                                  list(sess.last_prefills)))
            return out

        def timed_step():
            t0 = time.time()
            with jax.profiler.TraceAnnotation("pb:step"):
                out = step()
            host["step"].append((t0, time.time(), sess.last_step))
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
