"""The serving cell of a latent-attention decoder under LEARNED SPARSE
attention that holds a shard of its routed experts and a slice of its
vocabulary (``glm_moe_dsa``): ``models/latent_moe_decoder.py`` with
``index_topk`` and ``expert_shard`` in its description, behind the same
``DecoderOnlySession``, ``ServingFrontend``, wire, load generator and
host-side records as the dense latent decoder's cell
(``serve_glm_common.py``: its ``Server``, ``Tap`` and ``Checker`` are
extended, its ``verdict`` and ``client_sizes`` used as they are). What is
this model's own is here: its weights, the positions each indexer chose
beside the experts each router chose (fetched from the SAME executables,
in the check and in the window alike), what a decode dispatch's record
says of the selection, and the comparison with
``reference/sparse_latent_moe_decoder.py`` under the program's choice of
experts AND of positions.
"""

import time

import numpy as np

from perfbench import decoder_family, harness, serve_glm_common, weights_glm52
from perfbench.reference import sparse_latent_moe_decoder as reference

verdict = serve_glm_common.verdict
client_sizes = serve_glm_common.client_sizes
fp8_operands = serve_glm_common.fp8_operands


class Tap(serve_glm_common.Tap):
    """``serve_glm_common.Tap``, and every dispatch is also asked for the
    positions its indexers chose: a decode dispatch for every slot's
    ``[layers, S, index_topk]``, a prefill dispatch of a bucket longer
    than ``index_topk`` for each prompt's last row as a mask. They stay on
    the device and are dropped but for the prompts the check has marked."""

    def __init__(self, exe, fetches, topk):
        super().__init__(exe, fetches)
        self._topk = int(topk)
        self.first_selected, self.step_selected = {}, []

    def run(self, program, feed=None, fetch_list=None, scope=None, **kw):
        if not feed or "prompt_ids" not in feed:
            return self._exe.run(program, feed=feed, fetch_list=fetch_list,
                                 scope=scope, **kw)
        masked = (len(feed["prompt_ids"]) // len(feed["prompt_len"])
                  > self._topk)
        extra = [self._f["first_logits"], self._f["first_chosen"]] + (
            [self._f["first_selected"]] if masked else [])
        out = self._exe.run(
            program, feed=feed, scope=scope, return_numpy=False,
            fetch_list=list(fetch_list) + extra, **kw)
        kept, extras = out[:-len(extra)], out[-len(extra):]
        if self.marked:
            before = set(self.prefills)
            self._keep_marked(feed, extras[0], extras[1])
            for i in set(self.prefills) - before if masked else ():
                row = list(feed["slot_idx"]).index(self.prefills[i]["slot"])
                self.first_selected[i] = np.asarray(extras[2])[
                    :, row, :len(self.marked[i])] != 0
        return [np.asarray(o) for o in kept]

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, **kw):
        kw["return_numpy"] = False
        out = self._exe.run_multi_step(
            program, steps, feed=feed, scope=scope,
            fetch_list=list(fetch_list) + [self._f["probe_logits"],
                                           self._f["chosen"],
                                           self._f["selected"]], **kw)
        if self.on:
            probes = feed["probe_slots"]
            self.steps.append({
                "logits": out[-3],                            # [K, n, V]
                "chosen": np.asarray(out[-2])[:, :, probes]})
            # [K, layers, probes, topk]
            self.step_selected.append(np.asarray(out[-1])[:, :, probes])
        return [np.asarray(o) for o in out[:-3]]


class Server(serve_glm_common.Server):
    """The system under test, built and warmed once: the latent decoder's
    server with this model's weights and its tap."""

    weights = weights_glm52

    def __init__(self, cell, seed, place, setup):
        """``serve_glm_common.Server.__init__`` with the pool's
        ``prefill_rungs`` handed to the session (``session_kwargs`` names
        the keys the dense latent decoder's pool has) and this model's
        tap."""
        import paddle_tpu as fluid
        from paddle_tpu.serving.decoder_session import DecoderOnlySession

        cfg = self.cfg = cell.config
        self.scope = fluid.Scope()
        self._seed = None
        self.load_weights(seed)
        setup.part("startup_init")
        exe = fluid.Executor(place)
        self.session = DecoderOnlySession(
            exe, cfg, scope=self.scope,
            prefill_rungs=cfg["pool"]["prefill_rungs"],
            **serve_glm_common.session_kwargs(cfg))
        self.tap = self.session._exe = Tap(exe, self.session._fetch,
                                           cfg["index_topk"])
        setup.part("program_build")
        self.host = {"admit": [], "step": []}
        self.frontend = None

    def warm(self):
        """Every program the traffic will use, once: each bucket's
        prefill at EVERY rung of prompt rows, and the decode dispatch."""
        sess = self.session
        for bucket, rungs in sorted(sess.geometry["prefill_rungs"].items()):
            for rows in rungs:
                for _ in range(rows):
                    sess.enqueue(np.full((bucket,), 3, "int64"))
                while sess.pending_requests:
                    sess.pump()
                    for slot in sess.active_slots:
                        sess.cancel(slot)
        if not (sess.pool_conserved and not sess.pending_requests
                and sess.free_slots == sess.geometry["num_slots"]):
            raise RuntimeError("pool not drained after the warm-up")

    def start(self, backlog):
        """The frontend's worker admits under the pool's
        ``admit_token_budget`` (the check and the warm-up fill every slot
        in one call, as ``serve_glm_common`` drives them)."""
        self.session.admit_token_budget = self.cfg["pool"].get(
            "admit_token_budget")
        return super().start(backlog)

    def instrument(self):
        """``serve_glm_common.Server.instrument``, and a decode dispatch's
        record says what its slots' attention reads: ``(live slots,
        resident rows, selected rows)``."""
        import jax

        sess, host = self.session, self.host
        step = sess.step
        super().instrument()

        def timed_step():
            t0 = time.time()
            with jax.profiler.TraceAnnotation("pb:step"):
                out = step()
            host["step"].append((t0, time.time(),
                                 sess.last_step + (sess.last_selected_rows,)))
            return out

        sess.step = timed_step


class _Following(object):
    """``reference`` with the positions to follow bound to ``forward``
    (``decoder_family.against_reference`` hands it tokens and experts);
    keeps what the last forward returned."""

    def __init__(self, positions):
        self.positions, self.out = positions, None

    def forward(self, tree, tokens, cfg, **kw):
        self.out = reference.forward(tree, tokens, cfg,
                                     positions=self.positions, **kw)
        return self.out


def own_positions(scores, topk):
    """The reference's own choice from its index scores [n, T] (``-inf``
    above the diagonal): [n, topk] positions, ``-1`` where a row has
    fewer; ties to the lower position."""
    scores = np.asarray(scores, "float64")
    order = np.argsort(-scores, axis=-1, kind="stable")[:, :topk]
    return np.where(np.isfinite(np.take_along_axis(scores, order, -1)),
                    order, -1)


def index_choice(scores, mine, topk):
    """Of the positions the program chose for some rows (``mine`` [n,
    topk], ``-1``: none) against the reference's own choice from its
    ``scores`` [n, T]: (the program's positions the reference did not
    choose, all the program's positions, the largest margin by which a
    differing position lies under the reference's last chosen, in units of
    the row's spread of scores)."""
    scores = np.asarray(scores, "float64")
    own = own_positions(scores, topk)
    differ = total = 0
    margin = 0.0
    for row, (a, b) in enumerate(zip(mine, own)):
        a, b = a[a >= 0], b[b >= 0]
        extra = np.setdiff1d(a, b)
        differ += len(extra)
        total += len(a)
        if len(extra):
            seen = scores[row][np.isfinite(scores[row])]
            last = scores[row][b].min()
            margin = max(margin, float(
                (last - scores[row][extra].min()) / (seen.std() + 1e-30)))
    return differ, total, margin


class Checker(serve_glm_common.Checker):
    """``serve_glm_common.Checker`` for two seeded prompts, one short
    enough that every position is chosen (512-2048) and one several
    ``index_topk`` long (8192-16384), the reference following the
    program's choice of experts AND, for the compared rows (each prompt's
    last and the decoded positions), of positions: ``logit_rel_l2``,
    ``expert_choice_diff_share`` and ``expert_choice_margin_max`` as that
    checker defines them, and their twins for positions:
    ``index_choice_diff_share`` (the share of the positions the program's
    indexers chose for the compared rows that the reference's own indexer
    did not) and ``index_choice_margin_max`` (how far the worst of them
    lies under the reference's last chosen, in units of the row's spread
    of index scores)."""

    reference = reference

    def _serve(self, prompts, seed):
        """``serve_glm_common.Checker._serve``'s rows with the program's
        choice of positions as a fourth part: per ``full`` layer
        ``[P + 1, index_topk]`` (``-1``: none)."""
        tap = self.server.tap
        tap.first_selected, tap.step_selected = {}, []
        served = super()._serve(prompts, seed)
        topk = int(self.cfg["index_topk"])
        out = []
        for i, (tokens, logits, chosen) in enumerate(served):
            n = len(prompts[i])
            first = tap.first_selected.get(i)
            if first is None:
                # a bucket of at most index_topk rows: every position
                layers = tap.step_selected[0].shape[1]
                first = np.ones((layers, n), bool)
            rows = []
            for layer in range(first.shape[0]):
                at = np.flatnonzero(first[layer])
                last = np.full((1, topk), -1, "int64")
                last[0, :len(at)] = at
                rows.append(np.concatenate(
                    [last] + [s[:, layer, i] for s in tap.step_selected]))
            out.append((tokens, logits, chosen, rows))
        tap.first_selected, tap.step_selected = {}, []
        return out

    def _numbers(self, tree, served):
        err = norm = differ = choices = idx_differ = idx_total = 0
        margin = idx_margin = 0.0
        P = int(self.cfg["check"]["positions"])
        topk = int(self.cfg["index_topk"])
        dense = int(self.cfg.get(self.dense_key, 0))
        for tokens, logits, chosen, positions in served:
            follow = _Following(positions)
            e, w, dif, cho, m = decoder_family.against_reference(
                follow, self.cfg, dense, tree, tokens, len(tokens) - P,
                logits, chosen)
            err, norm, differ, choices = (err + e, norm + w, differ + dif,
                                          choices + cho)
            margin = max(margin, m)
            for scores, mine in zip(follow.out["index_scores"],
                                    positions or ()):
                d, t, im = index_choice(scores, mine, topk)
                idx_differ, idx_total = idx_differ + d, idx_total + t
                idx_margin = max(idx_margin, im)
        return {"logit_rel_l2": float(np.sqrt(err / norm)),
                "expert_choice_diff_share": differ / float(choices),
                "expert_choice_margin_max": margin,
                "index_choice_diff_share": idx_differ / float(
                    max(idx_total, 1)),
                "index_choice_margin_max": idx_margin}

    def control_numbers(self, seed):
        """The reference itself in the program's place, over the tokens
        the program served, its own choice of experts and of positions,
        twice: (A) one precision below the configuration's, every
        product's operands rounded to float8 (e4m3); (B) in float32 with
        NO selection (every earlier position attended: what dense latent
        attention would compute), under ``_dense``, and for the long
        prompt alone under ``_dense_long_prompt``: that one must fail, or
        the selection is not being checked."""
        import jax.numpy as jnp

        named = self.server.load_weights(seed)
        tree = weights_glm52.tree(named, self.cfg)
        P = int(self.cfg["check"]["positions"])
        topk = int(self.cfg["index_topk"])
        served = self._serve(self._prompts(seed), seed)

        def read(**control):
            rows = []
            for tokens, _logits, _chosen, _positions in served:
                n = len(tokens) - P
                out = reference.forward(
                    tree, tokens, self.cfg,
                    logits_at=np.arange(n - 1, n + P), **control)
                rows.append((tokens, out["logits"].astype(jnp.float32),
                             np.stack([np.asarray(o) for o in out["own"]]),
                             [own_positions(s, topk)
                              for s in out["index_scores"]] or None))
            return rows

        out = self._numbers(tree, read(quant=fp8_operands))
        dense = read(select=False)
        for suffix, rows in (("_dense", dense),
                             ("_dense_long_prompt", dense[-1:])):
            for key, value in self._numbers(tree, rows).items():
                out[key + suffix] = value
        harness.log("control: float8 operands, then no selection")
        return out
