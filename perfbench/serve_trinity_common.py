"""The serving cell of a decoder of sliding-window and full attention
layers with routed experts: a ``DecoderOnlySession``
(``serving/decoder_session.py``) that the builder
``models/windowed_moe_decoder.py`` gives a ring of K/V pages a slot a
window layer beside a full layer's growing pools, behind the same
``ServingFrontend``, wire, load generator and host-side records as the
latent-attention decoder's cell (``serve_glm_common.py``: its ``Tap``,
``client_sizes``, ``verdict``, the way its ``Checker`` fills every slot as the
window does and what it makes of the experts' choices are used as they
are); what is this model's own is here: its weights, what a decode
dispatch's record says of the window, and the comparison with
``reference/afmoe_decoder.py``.
"""

import time

import numpy as np

from perfbench import serve_glm_common, weights_trinity
from perfbench.reference import afmoe_decoder as reference

verdict = serve_glm_common.verdict
client_sizes = serve_glm_common.client_sizes
fp8_operands = serve_glm_common.fp8_operands


class Server(serve_glm_common.Server):
    """The system under test, built and warmed once: the latent decoder's
    server with this model's weights."""

    model = "paddle_tpu.models.windowed_moe_decoder"
    weights = weights_trinity

    def start(self, backlog):
        """The frontend's worker admits under the pool's
        ``admit_token_budget`` (the check and the warm-up fill every slot
        in one call, as ``serve_glm_common`` drives them)."""
        self.session.admit_token_budget = self.cfg["pool"].get(
            "admit_token_budget")
        return super().start(backlog)

    def instrument(self):
        """``serve_glm_common.Server.instrument``, and a decode dispatch's
        record says what its slots could see in a window layer too:
        ``(live slots, resident rows, window rows)``."""
        import jax

        sess, host = self.session, self.host
        step = sess.step
        super().instrument()

        def timed_step():
            t0 = time.time()
            with jax.profiler.TraceAnnotation("pb:step"):
                out = step()
            host["step"].append((t0, time.time(),
                                 sess.last_step + (sess.last_window_rows,)))
            return out

        sess.step = timed_step


class Checker(serve_glm_common.Checker):
    """For two seeded prompts, one under the window and one several
    windows long, served as ``serve_glm_common.Checker`` serves them
    (every slot filled as the window fills it through ``admit_pending``,
    the compared positions decoded with every slot live through the
    window's own executables, both kinds of pool drained after), against
    the reference's full forward over the same tokens from the SAME
    weights upcast a layer, and in it an expert, at a time:
    ``logit_rel_l2`` under the program's choice of experts, with
    ``expert_choice_diff_share`` and ``expert_choice_margin_max`` of the
    reference's own choice, as that checker defines them."""

    reference = reference
    dense_key = "num_dense_layers"

    def control_numbers(self, seed):
        """The reference itself in the program's place, over the tokens
        the program served, its own choice of experts, twice: (A) one
        precision below the configuration's, every product's operands
        rounded to float8 (e4m3); (B) in float32 with the window layers'
        band LEFT OUT (every sliding layer attends all earlier positions:
        what a full cache read whole would compute), under ``_no_band``,
        and for the long prompt alone under ``_no_band_long_prompt``:
        that one must fail, or the window is not being checked."""
        import jax.numpy as jnp

        named = self.server.load_weights(seed)
        tree = weights_trinity.tree(named, self.cfg)
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
            return rows

        out = self._numbers(tree, read(quant=fp8_operands))
        no_band = read(band=False)
        for suffix, rows in (("_no_band", no_band),
                             ("_no_band_long_prompt", no_band[-1:])):
            for key, value in self._numbers(tree, rows).items():
                out[key + suffix] = value
        return out
