"""The serving cell of the shortcut decoder (two latent-attention blocks
and two dense feed-forwards a layer, the expert block across them, a held
shard of its real experts beside its identities, a slice of its
vocabulary; HF ``longcat_flash``): ``models/shortcut_moe_decoder.py``
behind the same ``DecoderOnlySession``, ``ServingFrontend``, wire, load
generator and host-side records as the other decoder-only cells.
``serve_glm52_common.Server`` (prefill rungs, the admission budget, every
rung warmed) and ``serve_glm_common``'s ``Tap``, ``Checker``, ``verdict``
and ``client_sizes`` are extended or used as they are; what is this
model's own is here: its weights, its reference
(``reference/shortcut_moe_decoder.py``) and the three controls.
"""

import numpy as np

from perfbench import (
    decoder_family,
    harness,
    serve_glm52_common,
    serve_glm_common,
    weights_longcat,
)
from perfbench.reference import shortcut_moe_decoder as reference

verdict = serve_glm_common.verdict
client_sizes = serve_glm_common.client_sizes
fp8_operands = serve_glm_common.fp8_operands


class Server(serve_glm52_common.Server):
    """The system under test, built and warmed once: the decoder-only
    server with prefill rungs and an admission budget
    (``serve_glm52_common.Server``), this model's builder and weights, the
    dense latent decoder's tap (logits and the router's choice of every
    dispatch, from the executables the window runs)."""

    model = "paddle_tpu.models.shortcut_moe_decoder"
    weights = weights_longcat

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
            exe, cfg, scope=self.scope,
            prefill_rungs=cfg["pool"]["prefill_rungs"],
            **serve_glm_common.session_kwargs(cfg))
        self.tap = self.session._exe = serve_glm_common.Tap(
            exe, self.session._fetch)
        setup.part("program_build")
        self.host = {"admit": [], "step": []}
        self.frontend = None

    # a decode dispatch's record is (live slots, resident rows a pool)
    instrument = serve_glm_common.Server.instrument


class Checker(serve_glm_common.Checker):
    """``serve_glm_common.Checker`` for two seeded prompts (256-1024 and
    2048-4096 tokens), served as that checker serves them (every slot
    filled as the window fills it, the compared positions decoded with
    every slot live through the window's own executables), against THIS
    model's reference over the same tokens from the SAME weights:
    ``logit_rel_l2`` (the reference following the program's choice of
    router outputs, identities among them), ``expert_choice_diff_share``
    and ``expert_choice_margin_max`` (in the router's ``p + b`` over all
    768 outputs) as that checker defines them."""

    reference = reference

    def _against_reference(self, tree, tokens, n_prompt, got, chosen):
        # the shared comparison reads a token's choices under the other
        # families' key; this model's description calls them ``moe_topk``
        cfg = dict(self.cfg, num_experts_per_tok=self.cfg["moe_topk"])
        return decoder_family.against_reference(
            reference, cfg, 0, tree, tokens, n_prompt, got, chosen)

    def control_numbers(self, seed):
        """The reference itself in the program's place, over the tokens
        the program served and with its own choice of experts, three
        times: (A) one precision below the configuration's, every
        product's operands rounded to float8 (e4m3); (B) in float32 with
        the identities' term left out, under ``_no_identities``; (C) in
        float32 with the expert block fed from the SECOND sub-block's
        normed rows (a plain sequential layer, no shortcut), under
        ``_sequential``. Each must fail a limit, or a program that left
        the identities out, or put the expert block in the wrong place,
        would pass."""
        import jax.numpy as jnp

        named = self.server.load_weights(seed)
        tree = weights_longcat.tree(named, self.cfg)
        P = int(self.cfg["check"]["positions"])
        served = self._serve(self._prompts(seed), seed)

        def read(**control):
            rows = []
            for tokens, _logits, _chosen in served:
                n = len(tokens) - P
                out = reference.forward(
                    tree, tokens, self.cfg,
                    logits_at=np.arange(n - 1, n + P), **control)
                rows.append((tokens, out["logits"].astype(jnp.float32),
                             np.stack([np.asarray(o) for o in out["own"]])))
            return self._numbers(tree, rows)

        out = read(quant=fp8_operands)
        for suffix, control in (("_no_identities", dict(identities=False)),
                                ("_sequential", dict(sequential=True))):
            for key, value in read(**control).items():
                out[key + suffix] = value
        harness.log("control: float8 operands, then no identities, then "
                    "the expert block fed sequentially")
        return out
