"""The serving cell of a hybrid state-space decoder: a
``DecoderOnlySession`` (``serving/decoder_session.py``) that the builder
``models/hybrid_ssm_decoder.py`` gives recurrent state beside K/V pages,
behind the same ``ServingFrontend``, wire, load generator and host-side
records as the latent-attention decoder's cell (``serve_glm_common.py``:
its ``Server``, ``client_sizes``, ``verdict`` and the way its ``Checker`` fills
every slot as the window does are used as they are); what is this model's
own is here: its weights, what the tap asks of a dispatch, and the
comparison with ``reference/hybrid_ssm_decoder.py``: logits AND the
recurrent state.
"""

import numpy as np

from perfbench import harness, serve_glm_common, weights_jamba
from perfbench.reference import hybrid_ssm_decoder as reference

verdict = serve_glm_common.verdict
client_sizes = serve_glm_common.client_sizes
fp8_operands = serve_glm_common.fp8_operands


def bf16_state(s):
    """The recurrent state kept one precision below float32: rounded to
    bfloat16's 8 bits of significand (``reduce_precision``: the compiler
    folds a float32 -> bfloat16 -> float32 pair of converts away)."""
    import jax

    return jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)


class Tap(object):
    """Stands between the session and its executor, in the check and in
    the measured window alike, so that both drive the SAME executables:
    every prefill dispatch also fetches its logits, every decode dispatch
    the logits of the session's ``probe_slots``. The extras stay on the
    device and are dropped, but for the prompts the check has ``marked``."""

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
            fetch_list=list(fetch_list) + [self._f["first_logits"]], **kw)
        lens = np.asarray(feed["prompt_len"])
        ids = np.asarray(feed["prompt_ids"]).reshape(len(lens), -1)
        for i, prompt in enumerate(self.marked):
            n = len(prompt)
            for row in np.flatnonzero(lens == n):
                if i not in self.prefills and (ids[row, :n] == prompt).all():
                    self.prefills[i] = {
                        "slot": int(feed["slot_idx"][row]),
                        "prompts": int((lens > 0).sum()),
                        "logits": np.asarray(out[-1])[row]}      # [1, V]
        return [np.asarray(o) for o in out[:-1]]

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, **kw):
        kw["return_numpy"] = False
        out = self._exe.run_multi_step(
            program, steps, feed=feed, scope=scope,
            fetch_list=list(fetch_list) + [self._f["probe_logits"]], **kw)
        if self.on:
            self.steps.append(out[-1])                        # [K, n, V]
        return [np.asarray(o) for o in out[:-1]]


class Server(serve_glm_common.Server):
    """The system under test, built and warmed once: the latent decoder's
    server with this model's weights and tap."""

    model = "paddle_tpu.models.hybrid_ssm_decoder"
    weights = weights_jamba

    def __init__(self, cell, seed, place, setup):
        super().__init__(cell, seed, place, setup)
        self.tap = self.session._exe = Tap(self.tap._exe,
                                           self.session._fetch)

    def slot_states(self, slots):
        """The recurrent state of ``slots`` as the served arrays hold it:
        [len(slots), state-space layers, n, d] float32, on the host."""
        import jax.numpy as jnp

        at = jnp.asarray(list(slots), jnp.int32)
        arrays = self.session.geometry["state"]["slot_arrays"]
        return np.stack(
            [np.asarray(self.scope.get_value(name)[at], "float32")
             for name in arrays if name.startswith("hsd_ssm_")], axis=1)


class Checker(serve_glm_common.Checker):
    """For two seeded prompts, served as ``serve_glm_common.Checker``
    serves them (every slot filled as the window fills it, the compared
    positions decoded with every slot live through the window's own
    executables), against the reference's full forward over the same
    tokens from the SAME weights upcast a layer at a time:

    ``logit_rel_l2``: the relative L2 error of the 33 logit rows of each
    prompt. ``state_rel_l2``: that of the compared slots' recurrent state
    ``s`` in every state-space layer, read from the served arrays after
    the prefill (so padding that leaked into the state, or a state
    installed for the wrong row, shows) and after the decoded tokens (so
    does a state the one-token update let drift)."""

    def _serve(self, prompts, seed):
        """Per prompt: (tokens fed [n + P], logits [P + 1, V], state
        [2, layers, n, d] after the prefill and after P decoded tokens)."""
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
            states = [self.server.slot_states(slots)]
            sess.probe_slots[:] = slots
            tap.on = True
            for _ in range(P // K):
                sess.step()
            states.append(self.server.slot_states(slots))
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
                + [s[:, i].astype(jnp.float32) for s in tap.steps])
            out.append((np.concatenate([prompt, toks[:P]]), logits,
                        np.stack([s[i] for s in states])))
        for slot in sess.active_slots:
            sess.cancel(slot)
        tap.prefills, tap.steps = {}, []
        sess.probe_slots[:] = 0
        if not sess.pool_conserved or sess.pages_in_use \
                or sess.active_slots:
            raise RuntimeError("pool not drained after the check")
        return out

    def _reference(self, tree, tokens, n_prompt, **control):
        """(logits [P + 1, V], state [2, layers, n, d]) of the reference
        over ``tokens``, padded to its range's end so that one compiled
        reference serves every seed (causal and recurrent: the padding
        changes nothing before it)."""
        cfg, P = self.cfg, int(self.cfg["check"]["positions"])
        total = next(hi for _lo, hi in cfg["check"]["prompt_len_ranges"]
                     if n_prompt < hi) + P
        toks = np.concatenate(
            [tokens, np.zeros(total - len(tokens), "int64")])
        out = reference.forward(
            tree, toks, cfg, logits_at=np.arange(n_prompt - 1, n_prompt + P),
            states_at=[n_prompt - 1, n_prompt + P - 1], **control)
        state = np.stack([np.transpose(np.asarray(s), (0, 2, 1))
                          for s in out["states"]], axis=1)
        return out["logits"], state

    def _numbers(self, tree, served):
        import jax.numpy as jnp

        P = int(self.cfg["check"]["positions"])
        err = norm = 0.0
        s_err = s_norm = 0.0           # [n]: by row of the state
        for tokens, logits, state in served:
            want, want_state = self._reference(tree, tokens,
                                               len(tokens) - P)
            err += float(jnp.sum(jnp.square(logits - want)))
            norm += float(jnp.sum(jnp.square(want)))
            want_state = want_state.astype("float64")
            s_err = s_err + np.sum(np.square(state - want_state),
                                   axis=(0, 1, 3))
            s_norm = s_norm + np.sum(np.square(want_state), axis=(0, 1, 3))
        harness.log("check: the state's relative error by row of n (A = -1 "
                    "... -n): %s" % " ".join(
                        "%.4f" % v for v in np.sqrt(s_err / s_norm)))
        return {"logit_rel_l2": float(np.sqrt(err / norm)),
                "state_rel_l2": float(np.sqrt(s_err.sum() / s_norm.sum())),
                "state_slow_rel_l2": float(np.sqrt(s_err[0] / s_norm[0]))}

    def control_numbers(self, seed):
        """The reference itself in the program's place, one precision
        below the configuration's: every product's operands rounded to
        float8 (e4m3) and the recurrent state kept in bfloat16, over the
        tokens the program served. ``state_rel_l2_bf16_state_alone`` is
        the same reading with the state's precision the ONLY change."""
        import jax.numpy as jnp

        named = self.server.load_weights(seed)
        tree = weights_jamba.tree(named, self.cfg)
        P = int(self.cfg["check"]["positions"])
        served = self._serve(self._prompts(seed), seed)

        def read(**control):
            rows = []
            for tokens, _logits, _state in served:
                logits, state = self._reference(
                    tree, tokens, len(tokens) - P, **control)
                rows.append((tokens, logits.astype(jnp.float32), state))
            return self._numbers(tree, rows)

        out = read(quant=fp8_operands, state_round=bf16_state)
        alone = read(state_round=bf16_state)
        for key, value in alone.items():
            out[key + "_bf16_state_alone"] = value
        return out
