"""The serving cell of a DENSE decoder of Gated DeltaNet linear-attention
layers beside multi-head attention layers (``olmo_hybrid``):
``models/gated_delta_decoder.py`` behind the same ``DecoderOnlySession``,
``ServingFrontend``, wire, load generator and host-side records as the
other decoder-only cells. ``serve_solar_common``'s ``Server`` (rungs, the
admission budget) and the serving half of its ``Checker`` (every slot
filled as the window fills it, the compared slots' matrix states read
before and after the decoded positions) are used as they are; what is this
model's own is here: its weights, a tap that asks no dispatch for a choice
of experts (no layer has a router), the state's tiles of two heads taken
apart, its reference (``reference/gated_delta_decoder.py``) and the three
controls.
"""

import numpy as np

from perfbench import harness, serve_solar_common, weights_olmo
from perfbench.reference import gated_delta_decoder as reference
from perfbench.serve_jamba_common import bf16_state

verdict = serve_solar_common.verdict
client_sizes = serve_solar_common.client_sizes
bf16_grid_share = serve_solar_common.bf16_grid_share


def fp8_operands(x):
    """A product operand one precision below bfloat16, float8 e4m3 with
    SATURATION at its largest number, 448: this model's feed-forward reads
    the residual stream as it is (no input norm), its ``silu(x Wg) * (x
    Wu)`` passes 448 at some token of a thousand, and e4m3fn has no
    infinity: the plain cast makes that element NaN and the whole control
    with it (my chip run, PR 55, call 1: ``logit_rel_l2`` NaN on both
    seeds)."""
    import jax.numpy as jnp

    return jnp.clip(x, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(
        jnp.float32)


def _no_experts(*shape):
    """What the shared checker reads as a choice of experts, for a model
    with no expert layer: no layer's."""
    return np.zeros(shape, "int32")


class Tap(serve_solar_common.Tap):
    """``serve_solar_common.Tap`` for a model with no router: a prefill
    dispatch is asked for its first logits and a decode dispatch for its
    ``probe_logits`` alone, and the check's rows hold an empty choice."""

    def run(self, program, feed=None, fetch_list=None, scope=None, **kw):
        if not feed or "prompt_ids" not in feed:
            return self._exe.run(program, feed=feed, fetch_list=fetch_list,
                                 scope=scope, **kw)
        out = self._exe.run(
            program, feed=feed, scope=scope, return_numpy=False,
            fetch_list=list(fetch_list) + [self._f["first_logits"]], **kw)
        if self.marked:
            self._keep_marked(feed, out[-1],
                              _no_experts(0, len(feed["prompt_ids"]), 1))
        return [np.asarray(o) for o in out[:-1]]

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, **kw):
        if self.on and not self.steps:
            self.slots = [int(s) for s in feed["probe_slots"]]
            self.before = self.read_states(self.slots)
        kw["return_numpy"] = False
        out = self._exe.run_multi_step(
            program, steps, feed=feed, scope=scope,
            fetch_list=list(fetch_list) + [self._f["probe_logits"]], **kw)
        if self.on:
            self.steps.append({
                "logits": out[-1],                            # [K, n, V]
                "chosen": _no_experts(steps, 0,
                                      len(feed["probe_slots"]), 1)})
        return [np.asarray(o) for o in out[:-1]]


class Server(serve_solar_common.Server):
    """``serve_solar_common.Server`` with this model's builder, weights
    and tap."""

    model = "paddle_tpu.models.gated_delta_decoder"
    weights = weights_olmo

    def __init__(self, cell, seed, place, setup):
        super().__init__(cell, seed, place, setup)
        self.tap = self.session._exe = Tap(self.tap._exe,
                                           self.session._fetch)
        self.tap.read_states = self.slot_states

    def slot_states(self, slots):
        """The matrix state of ``slots``: [len(slots), linear layers,
        heads, dk, dv] float32 on the host, the served tiles (``pack``
        heads' value lanes side by side) taken apart."""
        from paddle_tpu.kernels.delta_rule import unpack_heads

        x = super().slot_states(slots)       # [n, L, H / pack, dk, pack dv]
        heads = x.shape[2] * int(self.session.geometry["state_pack"])
        return np.asarray(unpack_heads(x, heads))


class Checker(serve_solar_common.Checker):
    """``serve_solar_common.Checker`` (two seeded prompts among a full
    pool, 33 logit rows each and the compared slots' matrix states after
    the prefill and after the decoded tokens, through the window's own
    executables) against THIS model's reference, without the expert
    numbers: ``logit_rel_l2``, ``state_rel_l2`` over all the linear layers
    and ``state_bf16_grid_share``, as that checker defines them."""

    reference = reference

    def _forward(self, tree, tokens, **control):
        """The reference over one served sequence, padded to its range's
        end (one compiled reference a range whatever the seed's lengths;
        causal: the padding changes no position before it): the logits at
        the last prompt position and the decoded ones, every linear
        layer's state after the prefill and after the decoded tokens."""
        P = int(self.cfg["check"]["positions"])
        n = len(tokens) - P
        total = next(hi for _lo, hi in self.cfg["check"]["prompt_len_ranges"]
                     if n < hi) + P
        toks = np.concatenate([tokens,
                               np.zeros(total - len(tokens), "int64")])
        return reference.forward(
            tree, toks, self.cfg, logits_at=np.arange(n - 1, n + P),
            states_at=[n - 1, n + P - 1], **control)

    def _numbers(self, tree, served):
        import jax.numpy as jnp

        err = norm = s_err = s_norm = 0.0
        grid = []
        for tokens, logits, _chosen, state in served:
            out = self._forward(tree, tokens)
            err += float(jnp.sum(jnp.square(logits - out["logits"])))
            norm += float(jnp.sum(jnp.square(out["logits"])))
            grid.append(bf16_grid_share(state))
            for layer, want in enumerate(out["states"]):
                want = np.asarray(want, "float64")     # [2, H, dk, dv]
                s_err += np.square(state[:, layer] - want).sum()
                s_norm += np.square(want).sum()
        return {"logit_rel_l2": float(np.sqrt(err / norm)),
                "state_rel_l2": float(np.sqrt(s_err / s_norm)),
                "state_bf16_grid_share": max(grid)}

    def control_numbers(self, seed):
        """The reference itself in the program's place, over the tokens
        the program served, three times: (A) one precision below the
        configuration's, every product's operands rounded to float8
        (e4m3); (B) in float32 with the state ``S`` rounded to bfloat16
        after every token and NOTHING else changed, under
        ``_bf16_state_alone``; (C) in float32 with the full layers' q and
        k ROTATED at ``check.control_rope_theta`` and nothing else
        changed, under ``_rotated``: a program that rotates reads as this
        does, and must fail ``logit_rel_l2``."""
        import jax.numpy as jnp

        named = self.server.load_weights(seed)
        tree = weights_olmo.tree(named, self.cfg)
        served = self._serve(self._prompts(seed), seed)

        def read(**control):
            rows = []
            for tokens, _logits, chosen, _state in served:
                out = self._forward(tree, tokens, **control)
                rows.append((
                    tokens, out["logits"].astype(jnp.float32), chosen,
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
                    "alone, then the full layers rotated")
        return out
