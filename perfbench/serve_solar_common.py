"""The serving cell of a delta-rule linear-attention / grouped-query
decoder that holds a shard of its routed experts and a slice of its
vocabulary (``solar_open2``): ``models/linear_attn_moe_decoder.py`` behind
the same ``DecoderOnlySession``, ``ServingFrontend``, wire, load generator
and host-side records as the other decoder-only cells.
``serve_glm52_common.Server`` (prefill rungs, the admission budget) and
``serve_glm_common``'s ``Tap``, ``Checker``, ``verdict`` and
``client_sizes`` are extended or used as they are; what is this model's own
is here: its weights, the matrix state of the compared slots read from the
served arrays, and the comparison with
``reference/linear_attn_moe_decoder.py``: logits under the program's
choice of experts, the reference's own choice beside them, AND the state.
"""

import numpy as np

from perfbench import (
    decoder_family,
    harness,
    serve_glm52_common,
    serve_glm_common,
    weights_solar,
)
from perfbench.reference import linear_attn_moe_decoder as reference
from perfbench.serve_jamba_common import bf16_state

verdict = serve_glm_common.verdict
client_sizes = serve_glm_common.client_sizes
fp8_operands = serve_glm_common.fp8_operands

class Tap(serve_glm_common.Tap):
    """``serve_glm_common.Tap``, and before the first decode dispatch the
    check keeps it reads the compared slots' matrix state as the prefill
    left it (``read_states``: the server's)."""

    read_states = None

    def __init__(self, exe, fetches):
        super().__init__(exe, fetches)
        self.slots, self.before = [], None

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, **kw):
        if self.on and not self.steps:
            self.slots = [int(s) for s in feed["probe_slots"]]
            self.before = self.read_states(self.slots)
        return super().run_multi_step(program, steps, feed=feed,
                                      fetch_list=fetch_list, scope=scope,
                                      **kw)


class Server(serve_glm52_common.Server):
    """The system under test, built and warmed once: the decoder-only
    server with prefill rungs and an admission budget
    (``serve_glm52_common.Server``), this model's builder, weights and
    tap."""

    model = "paddle_tpu.models.linear_attn_moe_decoder"
    weights = weights_solar

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
        self.tap = self.session._exe = Tap(exe, self.session._fetch)
        self.tap.read_states = self.slot_states
        setup.part("program_build")
        self.host = {"admit": [], "step": []}
        self.frontend = None

    # a decode dispatch's record is (live slots, resident rows)
    instrument = serve_glm_common.Server.instrument

    def state_arrays(self):
        """The matrix states' names ([slots, heads, dk, dv]; the windows
        are [taps - 1, slots, width])."""
        return [name for name, a in
                self.session.geometry["state"]["slot_arrays"].items()
                if len(a["shape"]) == 4]

    def slot_states(self, slots):
        """The matrix state of ``slots`` as the served arrays hold it:
        [len(slots), linear layers, heads, dk, dv] float32, on the host."""
        import jax.numpy as jnp

        at = jnp.asarray(list(slots), jnp.int32)
        return np.stack(
            [np.asarray(self.scope.get_value(name)[at], "float32")
             for name in self.state_arrays()], axis=1)


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


def bf16_grid_share(state):
    """The share of a float32 state's nonzero elements that bfloat16
    holds exactly (the low 16 bits of the float32 are zero): ~2^-16 of a
    state kept in float32, all of one rounded to bfloat16 a token."""
    bits = np.ascontiguousarray(state, "float32").view(np.uint32)
    nonzero = (bits & 0x7FFFFFFF) != 0
    return float(((bits & 0xFFFF) == 0)[nonzero].mean()) \
        if nonzero.any() else 0.0


class Checker(serve_glm_common.Checker):
    """``serve_glm_common.Checker`` for two seeded prompts (256-1024 and
    4096-8192 tokens), served as that checker serves them (every slot
    filled as the window fills it, the compared positions decoded with
    every slot live through the window's own executables), against the
    reference's full forward over the same tokens from the SAME weights:
    ``logit_rel_l2``, ``expert_choice_diff_share`` and
    ``expert_choice_margin_max`` as that checker defines them, and

    ``state_rel_l2``: the relative L2 error of the compared slots' matrix
    state ``S`` in every linear layer, read from the served arrays after
    the prefill (so padding that leaked into the state, a chunk walked
    wrongly or a state installed for the wrong row shows) and after the
    decoded tokens (so does a state the one-token update let drift);
    ``state_bf16_grid_share``: the share of those states' elements that
    bfloat16 holds exactly. A state kept in bfloat16 reads 1 and a
    float32 one 2^-16; its ERROR does not tell them apart here, not even
    on the key channels of slowest decay: the rule's own correction ``I -
    beta k k^T`` overwrites a direction of ``S`` within ~dk / beta tokens
    whatever the decay, so a rounding a token adds ~0.011 where the
    program's bfloat16 activations already leave 0.009 (PERF.md section
    6, PR 42)."""

    reference = reference

    def _serve(self, prompts, seed):
        """``serve_glm_common.Checker._serve``'s rows with the compared
        slot's state as a fourth part: [2, layers, heads, dk, dv], after
        the prefill and after the decoded positions."""
        tap = self.server.tap
        served = super()._serve(prompts, seed)
        # a cancelled slot's rows stay as they are until it is reused
        after = self.server.slot_states(tap.slots)
        out = [(tokens, logits, chosen,
                np.stack([tap.before[i], after[i]]))
               for i, (tokens, logits, chosen) in enumerate(served)]
        tap.before = None
        return out

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
        the program served and with its own choice of experts, twice: (A)
        one precision below the configuration's, every product's operands
        rounded to float8 (e4m3); (B) in float32 with the state ``S``
        rounded to bfloat16 after every token and NOTHING else changed,
        under ``_bf16_state_alone``: that one must fail the state's own
        limit (``state_bf16_grid_share``), or a state kept in bfloat16
        would pass."""
        import jax.numpy as jnp

        named = self.server.load_weights(seed)
        tree = weights_solar.tree(named, self.cfg)
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
