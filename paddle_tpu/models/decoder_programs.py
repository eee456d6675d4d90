"""The serving programs of a decoder-only language model, written ONCE: the
frame every family's builder fills (``models/latent_moe_decoder.py``,
``hybrid_ssm_decoder.py``, ``windowed_moe_decoder.py``,
``linear_attn_moe_decoder.py`` (two namings, one file),
``ssd_moe_decoder.py``, ``shortcut_moe_decoder.py``,
``gated_delta_decoder.py``). A family file
holds what its layers do and the state they keep (``DecoderFamily``); this
module holds what a
``serving.decoder_session.DecoderOnlySession`` dispatches, whatever the
model:

* ``init``: every declared pool and per-slot array zeroed, and the loop
  state ``<prefix>_tok`` / ``<prefix>_pos`` ``[S, 1]``.
* ``prefill_rungs[T][rows]``, for every length bucket ``T`` (a multiple of
  the page size): ``prompts_per_dispatch(T) = max(1, budget // T)`` prompts
  a dispatch and, with ``prefill_rungs``, also one program a RUNG of prompt
  rows under that (1, 2, 4, ...), so that a dispatch of few prompts walks
  their rows and not the budget's; ``prefill[T]`` is the fullest rung.
  Feeds ``prompt_ids [B*T]``, ``prompt_len [B]``, ``slot_idx [B]``
  (``num_slots`` for a row of padding: nothing is written for it),
  ``page_rows [B, pages_per_slot]``, a declared ring's ``window_rows
  [B, R]``, ``last_idx [B]`` (the flat index of each prompt's last token).
  The family's layers write the prompts' rows and install the slots'
  fixed-size state; the frame samples each prompt's first token from its
  last position's logits and installs ``tok`` / ``pos`` for its slot.
* ``step``: one decode token for every slot; ``Executor.run_multi_step``
  runs ``tokens_per_dispatch`` of them a dispatch. Feeds ``page_table
  [S, pages_per_slot]``, a ring's ``window_table [S, R]`` and ``live
  [S, 1]`` from the host's mirror (a slot that is not live has length 0,
  writes to the trash page, keeps its fixed-size state and is neither
  routed nor counted), so a cancel or a page grown costs no dispatch of
  its own. ``probe_rows`` > 0 adds the feed ``probe_slots [probe_rows]``
  and the fetch ``probe_logits [probe_rows, vocab]``: those slots' logits
  from the SAME executable that serves. It is not free: the gather makes
  the step write all slots' logits out where the program without it fuses
  the sampler's argmax into the head's product.

Parameters are declared by name (a family's ``parameter_shapes``) and come
from a checkpoint (``load_parameters``): there is no startup initialiser.

``builder_for(desc)`` chooses the family by the description's own keys.
The programs' fingerprints are held by ``tests/test_decoder_programs.py``.
"""

import collections
import contextlib
import importlib
import types

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.kernels.paged_attention import pages_for
from paddle_tpu.models.transformer import _sampler_attrs
from paddle_tpu.observability.explain import setup_span

__all__ = ["DecoderFamily", "build_decoder_programs", "builder_for",
           "load_named", "load_parameters"]


# (the description is this family's, its module in ``paddle_tpu.models``,
# its builder there), asked in order. A module's ``check_served``, where it
# has one, refuses by the key at fault before anything is built.
_FAMILIES = (
    # Mamba-2 has heads; a Mamba-1 description (the next row) has none
    (lambda desc: "mamba_n_heads" in desc,
     "ssd_moe_decoder", "build_ssd_moe_decoder"),
    (lambda desc: "mamba_d_state" in desc,
     "hybrid_ssm_decoder", "build_hybrid_ssm_decoder"),
    # two latent blocks a layer and zero-compute experts; such a
    # description has the next row's ``kv_lora_rank`` too
    (lambda desc: "zero_expert_num" in desc,
     "shortcut_moe_decoder", "build_shortcut_moe_decoder"),
    # delta-rule linear layers whose full-attention sibling is LATENT:
    # such a description has the next row's ``kv_lora_rank`` too
    (lambda desc: "linear_attn_config" in desc and "kv_lora_rank" in desc,
     "linear_attn_moe_decoder", "build_linear_attn_moe_decoder"),
    (lambda desc: "kv_lora_rank" in desc,
     "latent_moe_decoder", "build_latent_moe_decoder"),
    (lambda desc: "layer_types" in desc
     and desc.get("sliding_window") is not None,
     "windowed_moe_decoder", "build_windowed_moe_decoder"),
    (lambda desc: "linear_attn_config" in desc,
     "linear_attn_moe_decoder", "build_linear_attn_moe_decoder"),
    # Gated DeltaNet layers named by the ``linear_*`` keys: dense, its
    # ``layer_types`` has no ``sliding_window`` beside it
    (lambda desc: "linear_key_head_dim" in desc,
     "gated_delta_decoder", "build_gated_delta_decoder"),
)


def builder_for(desc):
    """The function that builds ``desc``'s serving programs, chosen by the
    description's own keys."""
    for is_family, module, builder in _FAMILIES:
        if is_family(desc):
            family = importlib.import_module("paddle_tpu.models." + module)
            getattr(family, "check_served", lambda desc: None)(desc)
            return getattr(family, builder)
    from paddle_tpu.serving.server import ServingError

    raise ServingError(
        "DecoderOnlySession knows no builder for this description (keys "
        "%s): it serves a hybrid Mamba-2 decoder with routed experts "
        "(mamba_n_heads), a hybrid state-space decoder (mamba_d_state), a "
        "decoder of two latent-attention blocks a layer with the expert "
        "block on a shortcut and zero-compute experts (zero_expert_num), a "
        "decoder of delta-rule linear-attention layers beside latent-"
        "attention layers (linear_attn_config with kv_lora_rank), a "
        "latent-attention decoder (kv_lora_rank), a decoder of window "
        "and full attention layers (layer_types with a sliding_window), "
        "a decoder of delta-rule linear-attention and grouped-query "
        "attention layers (linear_attn_config) or a dense decoder of "
        "Gated DeltaNet and multi-head attention layers "
        "(linear_key_head_dim)" % sorted(desc))


def load_named(scope, named, shapes=None):
    """Put ``named`` ({name: array}) into ``scope``; with ``shapes``
    ({name: (shape, dtype)}) every parameter must be there with its
    shape."""
    for name, (shape, _dt) in (shapes or {}).items():
        if name not in named:
            raise KeyError("the checkpoint has no parameter %r" % name)
        if tuple(named[name].shape) != shape:
            raise ValueError("%s: the model needs %s, the checkpoint has %s"
                             % (name, shape, tuple(named[name].shape)))
    for name, value in named.items():
        scope.var(name).set(value)


def load_parameters(parameter_shapes, scope, named, desc=None, dtype=None):
    """Put a checkpoint's arrays into ``scope`` under the programs' names.
    With ``desc`` every parameter must be there with its shape. A family's
    ``load_parameters`` is this with its own ``parameter_shapes``."""
    load_named(scope, named, desc and parameter_shapes(desc,
                                                       dtype or "bfloat16"))


class DecoderFamily(object):
    """What a family file hands ``build_decoder_programs``.

    prefix : of every name the programs declare (``lmd``: ``lmd_tok``).
    shapes : the family's ``parameter_shapes(desc, dtype)``; they hold
        ``<prefix>_embed`` and, unless ``head`` is given, ``<prefix>_head``.
    vocab : the width of the logits.
    state : ``state(S, P, page_size, pages_per_slot)`` -> what a slot owns,
        as ``geometry["state"]`` gives it to the session: ``page_pools``
        and ``slot_arrays`` ({name: {"shape", "dtype"[, "slot_axis"]}}, in
        order) and, where pools are rings, ``windowed`` (a ring:
        ``window``, ``pages_per_slot``, ``num_pages``, ``pools``,
        ``table_feed``, ``rows_feed``). A ring declared here gets its feed.
    prefill, step : ``hook(f, x)`` -> (the token rows after the family's
        layers and final norm, [(fetch key, per-layer parts)]): the layers
        on the embedded rows ``x`` of the program being built, ``f`` (below)
        its frame. The parts of a key are stacked into ONE variable
        ``<prefix>_<key>``, after the sampler; parts may be a callable, to
        append their ops there; a key with no parts fetches None.
    geometry : the family's own keys of ``geometry``.
    head : ``head(f, rows)`` -> float32 logits ``[rows, vocab]``; without
        one, the product with the family's own ``<prefix>_head``.
    mask : the prefill frame computes ``f.valid``, 1 for a prompt's real
        tokens ``[B*T]`` (what an expert layer routes).

    A frame ``f`` has ``w(name)`` (the parameter ``name`` declared in this
    program), ``state`` ({name: variable} of the pools and arrays), ``tok``
    and ``pos``, and ``valid``; in a prefill ``rows`` (B), ``bucket`` (T),
    ``lens``, ``slot_idx``, ``page_rows``, ``ring_rows`` (one a declared
    ring), ``last_idx``; in a step ``table``, ``ring_tables``, ``live`` (its
    ``valid``), ``lengths`` (resident rows after this step's write).
    """

    def __init__(self, prefix, shapes, vocab, state, prefill, step,
                 geometry, head=None, mask=True):
        self.prefix, self.shapes, self.vocab = prefix, shapes, int(vocab)
        self.state, self.prefill, self.step = state, prefill, step
        self.geometry, self.mask = geometry, mask
        self.head = head or self._own_head

    def _own_head(self, f, rows):
        return fluid.layers.dense_projection(
            rows, f.w(self.prefix + "_head"), out_dtype="float32")


def build_decoder_programs(family_of, desc, num_slots, max_positions,
                           page_size, prefill_buckets, num_pages=None,
                           prefill_token_budget=2048, sampler=None,
                           dtype="bfloat16", probe_rows=0,
                           tokens_per_dispatch=1, prefill_rungs=False):
    """Build the serving programs (module docstring) of the family
    ``family_of(desc, dtype, tokens_per_dispatch)``, a ``DecoderFamily``: a
    family file's ``build_<family>_decoder`` is this function with its own
    ``family_of``, and the rest is what the session passes. Returns a
    dict: ``init``, ``prefill`` ({bucket: program}), ``prefill_rungs``
    ({bucket: {prompt rows: program}}: the same programs, and with
    ``prefill_rungs`` one for every power of two of rows under a bucket's
    most), ``step``, ``fetches`` (the names to fetch: ``token``,
    ``first_token`` and, for checks, ``logits``, ``first_logits``,
    ``probe_logits``; ``expert_tokens`` and the family's other keys, None
    where no program has them) and ``geometry`` (slots, pages, buckets,
    prompts a dispatch, the rungs, ``state`` and the family's own keys)."""
    nn = fluid.layers
    family = family_of(desc, dtype, tokens_per_dispatch)
    shapes, V = family.shapes, family.vocab
    S, ps = int(num_slots), int(page_size)
    npp = pages_for(max_positions, ps)
    P = int(num_pages) if num_pages else 1 + S * npp
    buckets = sorted(int(t) for t in prefill_buckets)
    if any(t % ps for t in buckets):
        raise ValueError("every prefill bucket (%s) must be a multiple of "
                         "the page size %d: rows are written a page at a "
                         "time" % (buckets, ps))
    per_dispatch = {t: max(1, int(prefill_token_budget) // t)
                    for t in buckets}
    # the rows a bucket's programs are built for: the most a dispatch
    # takes and, with prefill_rungs, every power of two under it
    rungs = {t: [2 ** j for j in range((most - 1).bit_length())
                 if prefill_rungs] + [most]
             for t, most in per_dispatch.items()}
    state = family.state(S, P, ps, npp)
    specs = collections.OrderedDict(
        list(state["page_pools"].items())
        + list(state["slot_arrays"].items()))
    rings = state.get("windowed", ())
    sample = dict(eos_id=0, max_length=int(max_positions) + 2,
                  **_sampler_attrs(sampler))

    def name(key):
        return "%s_%s" % (family.prefix, key)

    fetches = {
        "token": name("step_tok"), "first_token": name("first_tok"),
        "logits": name("logits"), "first_logits": name("first_logits"),
        "probe_logits": name("probe_logits") if probe_rows else None,
        "expert_tokens": None}

    def declare(blk, var, shape, dt):
        return blk.create_var(name=var, shape=list(shape), dtype=dt,
                              persistable=True)

    def feed(var, shape):
        return nn.data(var, shape=shape, dtype="int64",
                       append_batch_size=False)

    @contextlib.contextmanager
    def program(label, **known):
        """A new program under construction and its frame, under the
        set-up span ``label`` (what the program's executables are called
        in the set-up ledger)."""
        prog = fluid.Program()
        with setup_span(label, prog), unique_name.guard({}), \
                fluid.program_guard(prog, fluid.Program()):
            blk = prog.global_block()
            yield prog, types.SimpleNamespace(
                w=lambda var: declare(blk, var, *shapes[var]),
                state={n: declare(blk, n, spec["shape"], spec["dtype"])
                       for n, spec in specs.items()},
                tok=declare(blk, name("tok"), (S, 1), "int64"),
                pos=declare(blk, name("pos"), (S, 1), "int64"), **known)

    def fetched(key, value, dt):
        """``value`` under the fetchable name of ``key``."""
        return nn.assign(value, output=fluid.default_main_program()
                         .global_block().create_var(name=name(key),
                                                    dtype=dt))

    def logits_of(f, rows, key, count):
        return fetched(key, nn.reshape(family.head(f, rows),
                                       shape=[count, 1, V]), "float32")

    def stacked(stacks):
        """Each key's per-layer parts as ONE fetchable variable."""
        for key, parts in stacks:
            parts = parts() if callable(parts) else parts
            fetches.setdefault(key, None)
            if parts:
                fetches[key] = name(key)
                fetched(key, nn.concat(
                    [nn.reshape(c, shape=[1] + list(c.shape))
                     for c in parts], axis=0), parts[0].dtype)

    with program("init") as (init, f):
        for n, var in f.state.items():
            nn.assign(nn.fill_constant(list(specs[n]["shape"]),
                                       specs[n]["dtype"], 0.0), output=var)
        for var in (f.tok, f.pos):
            nn.assign(nn.fill_constant([S, 1], "int64", 0), output=var)

    by_rows = {T: {} for T in buckets}
    for T, B in [(T, B) for T in buckets for B in rungs[T]]:
        # a rung under a bucket's most rows: prefill/<bucket>/<rows>
        label = "prefill/%d" % T + ("" if B == per_dispatch[T]
                                    else "/%d" % B)
        with program(label, rows=B, bucket=T) as (by_rows[T][B], f):
            ids = feed("prompt_ids", [B * T])
            f.lens = feed("prompt_len", [B])
            f.slot_idx = feed("slot_idx", [B])
            f.page_rows = feed("page_rows", [B, npp])
            f.ring_rows = [feed(ring["rows_feed"],
                                [B, ring["pages_per_slot"]])
                           for ring in rings]
            f.last_idx = feed("last_idx", [B])
            f.valid = nn.reshape(
                nn.sequence_mask(f.lens, maxlen=T, dtype="int64"),
                shape=[B * T]) if family.mask else None
            x, stacks = family.prefill(
                f, nn.embedding_rows(f.w(name("embed")), ids))
            logits = logits_of(f, nn.gather(x, f.last_idx),
                               "first_logits", B)
            lens2 = nn.reshape(f.lens, shape=[B, 1])
            first, _p, _d = nn.slot_decode_sample(logits, lens2, **sample)
            fetched("first_tok", first, "int64")
            nn.slot_rows_write(f.tok, f.slot_idx, first)
            nn.slot_rows_write(f.pos, f.slot_idx, lens2)
            stacked(stacks)

    with program("step") as (step, f):
        f.table = feed("page_table", [S, npp])
        f.ring_tables = [feed(ring["table_feed"],
                              [S, ring["pages_per_slot"]])
                         for ring in rings]
        f.live = f.valid = feed("live", [S, 1])
        # resident rows AFTER this step's write; 0 for an empty slot
        f.lengths = nn.elementwise_mul(
            nn.increment(f.pos, value=1, in_place=False), f.live)
        done = nn.elementwise_sub(
            nn.fill_constant([S, 1], "int64", 1), f.live)
        x, stacks = family.step(
            f, nn.embedding_rows(f.w(name("embed")), f.tok))
        logits = logits_of(f, x, "logits", S)
        if probe_rows:
            probe = feed("probe_slots", [int(probe_rows)])
            fetched("probe_logits", nn.gather(
                nn.reshape(logits, shape=[S, V]), probe), "float32")
        tok_new, pos_new, _done = nn.slot_decode_sample(
            logits, f.pos, done=done, **sample)
        fetched("step_tok", tok_new, "int64")
        stacked(stacks)
        nn.assign(tok_new, output=f.tok)
        nn.assign(pos_new, output=f.pos)

    return {
        "init": init, "step": step, "prefill_rungs": by_rows,
        "prefill": {T: by_rows[T][per_dispatch[T]] for T in buckets},
        "fetches": fetches,
        "geometry": dict(
            family.geometry, num_slots=S, page_size=ps, pages_per_slot=npp,
            num_pages=P, buckets=buckets, prompts_per_dispatch=per_dispatch,
            prefill_rungs=rungs,
            prefill_token_budget=int(prefill_token_budget), dtype=dtype,
            state=state),
    }
