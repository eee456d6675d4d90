"""The serving cell of a hybrid Mamba-2 / grouped-query decoder that holds
a shard of its routed experts and a slice of its vocabulary
(``granitemoehybrid``): ``models/ssd_moe_decoder.py`` behind the same
``DecoderOnlySession``, ``ServingFrontend``, wire, load generator and
host-side records as the other decoder-only cells.
``serve_solar_common``'s ``Server`` (prefill rungs, the admission budget,
the matrix states of the compared slots read from the served arrays), its
``Tap`` and the way its ``Checker`` serves the compared prompts are used
as they are; what is this model's own is here: its weights and the
comparison with ``reference/ssd_moe_decoder.py``: logits under the
program's choice of experts, the reference's own choice beside them, AND
the state.
"""

import numpy as np

from perfbench import (
    decoder_family,
    harness,
    serve_glm_common,
    serve_solar_common,
    weights_granite,
)
from perfbench.reference import ssd_moe_decoder as reference
from perfbench.serve_jamba_common import bf16_state

verdict = serve_glm_common.verdict
client_sizes = serve_glm_common.client_sizes
fp8_operands = serve_glm_common.fp8_operands
bf16_grid_share = serve_solar_common.bf16_grid_share


class Server(serve_solar_common.Server):
    """The system under test, built and warmed once:
    ``serve_solar_common.Server`` with this model's builder and weights
    (its ``state_arrays`` are the 4-D per-slot arrays: here ``[slots, lane
    groups, d_state, group lanes]``, ``kernels/ssd.py``)."""

    model = "paddle_tpu.models.ssd_moe_decoder"
    weights = weights_granite

    def slot_states(self, slots):
        """The matrix state of ``slots`` as the served arrays hold it, laid
        out a head at a time as the reference returns it: [len(slots),
        Mamba-2 layers, heads, d_head, d_state] float32, on the host."""
        from paddle_tpu.kernels.ssd import to_heads

        return np.asarray(to_heads(super().slot_states(slots),
                                   self.cfg["mamba_n_heads"]))


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
    """``serve_solar_common.Checker`` for two seeded prompts (128-1024 and
    2048-4096 tokens), served as that checker serves them (every slot
    filled as the window fills it, the compared positions decoded with
    every slot live through the window's own executables), against THIS
    model's reference over the same tokens from the SAME weights:
    ``logit_rel_l2``, ``expert_choice_diff_share`` and
    ``expert_choice_margin_max`` (in the router's logits) as
    ``serve_glm_common.Checker`` defines them, and

    ``state_rel_l2``: the relative L2 error of the compared slots' matrix
    state ``s`` in every Mamba-2 layer, read from the served arrays after
    the prefill (so padding that leaked into the state, a chunk walked
    wrongly or a state installed for the wrong row shows) and after the
    decoded tokens (so does a state the one-token update let drift);
    ``state_bf16_grid_share``: the share of those states' nonzero elements
    that bfloat16 holds exactly: 2^-16 of a state kept in float32, all of
    one rounded to bfloat16 a token, whatever the decays and the prompts
    are (the state's ERROR tells the two apart only on heads that forget
    slowly, and how many of those a seed draws is the seed's)."""

    reference = reference

    def _numbers(self, tree, served):
        P = int(self.cfg["check"]["positions"])
        err = norm = differ = choices = 0
        margin = 0.0
        s_err = s_norm = 0.0
        grid = []
        for tokens, logits, chosen, state in served:
            n = len(tokens) - P
            follow = _WithStates([n - 1, n + P - 1])
            e, w, dif, cho, m = decoder_family.against_reference(
                follow, self.cfg, 0, tree, tokens, n, logits, chosen)
            err, norm, differ, choices = (err + e, norm + w, differ + dif,
                                          choices + cho)
            margin = max(margin, m)
            grid.append(bf16_grid_share(state))
            for layer, want in enumerate(follow.out["states"]):
                want = np.asarray(want, "float64")     # [2, H, P, N]
                s_err += np.square(state[:, layer] - want).sum()
                s_norm += np.square(want).sum()
        return {"logit_rel_l2": float(np.sqrt(err / norm)),
                "expert_choice_diff_share": differ / float(choices),
                "expert_choice_margin_max": margin,
                "state_rel_l2": float(np.sqrt(s_err / s_norm)),
                "state_bf16_grid_share": max(grid)}

    def control_numbers(self, seed):
        """The reference itself in the program's place, over the tokens
        the program served and with its own choice of experts, twice: (A)
        one precision below the configuration's, every product's operands
        rounded to float8 (e4m3); (B) in float32 with the state ``s``
        rounded to bfloat16 after every token and NOTHING else changed,
        under ``_bf16_state_alone``: that one must fail the state's own
        limit (``state_bf16_grid_share``), or a state kept in bfloat16
        would pass."""
        import jax.numpy as jnp

        named = self.server.load_weights(seed)
        tree = weights_granite.tree(named, self.cfg)
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
        for key, value in read(state_round=bf16_state).items():
            out[key + "_bf16_state_alone"] = value
        harness.log("control: float8 operands, then a bfloat16 state alone")
        return out
