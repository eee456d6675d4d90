"""The serving cell of a delta-rule linear-attention decoder whose
full-attention sibling is a NoPE latent layer, with a leading dense layer,
a held shard of its routed experts and a slice of its vocabulary
(``kimi_linear``): ``models/linear_attn_moe_decoder.py`` (its second
naming) behind the same ``DecoderOnlySession``, ``ServingFrontend``, wire,
load generator and host-side records as the other decoder-only cells.
``serve_solar_common``'s ``Server`` (rungs, the admission budget, the
matrix states read from the served arrays), ``Tap`` and the serving half
of its ``Checker`` are used as they are; what is this model's own is here:
its weights, its reference (``reference/linear_latent_moe_decoder.py``)
and the three controls.
"""

import numpy as np

from perfbench import (
    decoder_family,
    harness,
    serve_solar_common,
    weights_kimi,
)
from perfbench.reference import linear_latent_moe_decoder as reference
from perfbench.serve_jamba_common import bf16_state

verdict = serve_solar_common.verdict
client_sizes = serve_solar_common.client_sizes
fp8_operands = serve_solar_common.fp8_operands
bf16_grid_share = serve_solar_common.bf16_grid_share


class Server(serve_solar_common.Server):
    """``serve_solar_common.Server`` (the same builder module: the family
    reads this description's keys too) with this model's weights."""

    weights = weights_kimi


class _WithStates(object):
    """``reference`` with the positions whose state is returned bound to
    ``forward`` (``decoder_family.against_reference`` hands it tokens and
    experts); keeps what the last forward returned."""

    def __init__(self, states_at):
        self.states_at, self.out = states_at, None

    def forward(self, tree, tokens, cfg, **kw):
        self.out = reference.forward(tree, tokens, cfg,
                                     states_at=self.states_at, **kw)
        return self.out


class Checker(serve_solar_common.Checker):
    """``serve_solar_common.Checker`` (two seeded prompts among a full
    pool, 33 logit rows each and the compared slots' matrix states after
    the prefill and after the decoded tokens, through the window's own
    executables) against THIS model's reference: ``logit_rel_l2``,
    ``expert_choice_diff_share``, ``expert_choice_margin_max`` over the
    four expert layers (the leading layer is dense and chooses none),
    ``state_rel_l2`` over all four linear layers and
    ``state_bf16_grid_share``, as that checker defines them."""

    reference = reference

    def _numbers(self, tree, served):
        P = int(self.cfg["check"]["positions"])
        # the shared comparison reads a token's choices under the other
        # families' key; this model's description says
        # ``num_experts_per_token``
        cfg = dict(self.cfg,
                   num_experts_per_tok=self.cfg["num_experts_per_token"])
        dense = int(self.cfg["first_k_dense_replace"])
        err = norm = differ = choices = 0
        margin = 0.0
        s_err = s_norm = 0.0
        grid = []
        for tokens, logits, chosen, state in served:
            n = len(tokens) - P
            follow = _WithStates([n - 1, n + P - 1])
            e, w, dif, cho, m = decoder_family.against_reference(
                follow, cfg, dense, tree, tokens, n, logits, chosen)
            err, norm, differ, choices = (err + e, norm + w, differ + dif,
                                          choices + cho)
            margin = max(margin, m)
            grid.append(bf16_grid_share(state))
            for layer, want in enumerate(follow.out["states"]):
                want = np.asarray(want, "float64")     # [2, H, dk, dv]
                s_err += np.square(state[:, layer] - want).sum()
                s_norm += np.square(want).sum()
        return {"logit_rel_l2": float(np.sqrt(err / norm)),
                "expert_choice_diff_share": differ / float(choices),
                "expert_choice_margin_max": margin,
                "state_rel_l2": float(np.sqrt(s_err / s_norm)),
                "state_bf16_grid_share": max(grid)}

    def control_numbers(self, seed):
        """The reference itself in the program's place, over the tokens
        the program served and with its own choice of experts, three
        times: (A) one precision below the configuration's, every
        product's operands rounded to float8 (e4m3); (B) in float32 with
        the state ``S`` rounded to bfloat16 after every token and NOTHING
        else changed, under ``_bf16_state_alone``; (C) in float32 with the
        ROTATION applied to the latent layer's q_pe and k_pe at
        ``check.control_rope_theta``, under ``_rotated``: a program that
        rotates reads as this does, and must fail ``logit_rel_l2``."""
        import jax.numpy as jnp

        named = self.server.load_weights(seed)
        tree = weights_kimi.tree(named, self.cfg)
        P = int(self.cfg["check"]["positions"])
        served = self._serve(self._prompts(seed), seed)

        def read(**control):
            rows = []
            for tokens, _logits, _chosen, _state in served:
                n = len(tokens) - P
                out = reference.forward(
                    tree, tokens, self.cfg,
                    logits_at=np.arange(n - 1, n + P),
                    states_at=[n - 1, n + P - 1], **control)
                rows.append((
                    tokens, out["logits"].astype(jnp.float32),
                    np.stack([np.asarray(o) for o in out["own"]]),
                    np.stack([np.asarray(s) for s in out["states"]], 1)))
            return self._numbers(tree, rows)

        out = read(quant=fp8_operands)
        theta = float(self.cfg["check"]["control_rope_theta"])
        for suffix, control in (("_bf16_state_alone",
                                 dict(state_round=bf16_state)),
                                ("_rotated", dict(rotate=theta))):
            for key, value in read(**control).items():
                out[key + suffix] = value
        harness.log("control: float8 operands, then a bfloat16 state "
                    "alone, then the latent layer rotated")
        return out
