"""The hybrid state-space / attention decoder, tiny on the CPU (hidden 64,
4 query heads on 1 key/value head, d_inner 128, d_state 4, dt_rank 8, two
periods of the layer pattern so that both kinds of layer and both offsets
occur, vocabulary 512): each new kernel in interpret mode against its
composed reference, grouped-query paged decode against the per-head paged
reference, prefill of prompts of different lengths in one bucket dispatch
and then decode through ``DecoderOnlySession`` against the plain
reference's full forward (logits AND the recurrent state), slots joining,
leaving and being reused, the session behind a real ``ServingFrontend``."""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.kernels import gqa_paged_attention as gq  # noqa: E402
from paddle_tpu.kernels import selective_scan as ss  # noqa: E402
from paddle_tpu.kernels.paged_attention import (  # noqa: E402
    paged_attention_reference,
)
from paddle_tpu.models import hybrid_ssm_decoder as hsd  # noqa: E402
from paddle_tpu.serving.decoder_session import (  # noqa: E402
    DecoderOnlySession,
    builder_for,
)
from paddle_tpu.serving.server import ServingError  # noqa: E402
from perfbench import weights_jamba  # noqa: E402
from perfbench.reference import hybrid_ssm_decoder as ref  # noqa: E402

DESC = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=1,
            intermediate_size=160, mamba_expand=2, mamba_d_state=4,
            mamba_d_conv=4, mamba_dt_rank=8, attn_layer_period=4,
            attn_layer_offset=2, num_hidden_layers=8, vocab_size=512,
            rms_norm_eps=1e-6, num_experts=1, mamba_conv_bias=True,
            mamba_proj_bias=False, tie_word_embeddings=True,
            sliding_window=None)
MAMBA = [i for i, k in enumerate(hsd.layer_kinds(DESC)) if k == "mamba"]


class Tap(object):
    """An executor that also fetches the logits of every dispatch (what
    the benchmark's check does on the chip)."""

    def __init__(self, exe, fetches):
        self._exe, self._f = exe, fetches
        self.prefill, self.steps = [], []

    def __getattr__(self, name):
        return getattr(self._exe, name)

    def run(self, program, feed=None, fetch_list=None, scope=None, **kw):
        if not feed or "prompt_ids" not in feed:
            return self._exe.run(program, feed=feed, fetch_list=fetch_list,
                                 scope=scope, **kw)
        out = self._exe.run(
            program, feed=feed, scope=scope, fetch_list=list(fetch_list) + [
                self._f["first_logits"]], **kw)
        self.prefill.append((feed, np.asarray(out[-1])))
        return out[:-1]

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, **kw):
        out = self._exe.run_multi_step(
            program, steps, feed=feed, scope=scope,
            fetch_list=list(fetch_list) + [self._f["logits"]], **kw)
        self.steps.append(np.asarray(out[-1]))           # [K, S, 1, V]
        return out[:-1]


def make_session(seed=3, num_slots=6, tap=False, desc=DESC, **kw):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    params = hsd.random_parameters(desc, seed, "float32")
    hsd.load_parameters(scope, params, desc, "float32")
    args = dict(num_slots=num_slots, max_prompt=32, max_new_tokens=16,
                page_size=8, tokens_per_dispatch=2,
                prefill_token_budget=64, scope=scope, dtype="float32")
    args.update(kw)
    sess = DecoderOnlySession(exe, desc, **args)
    if tap:
        sess._exe = Tap(exe, sess._fetch)
    return sess, weights_jamba.tree({k: jnp.asarray(v)
                                     for k, v in params.items()}, desc)


def rel(got, want):
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def prompts_of(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, DESC["vocab_size"], n) for n in lengths]


def slot_state(sess, slot):
    """(s [layers, n, d], window [layers, kw - 1, d]) of one slot, as the
    served arrays hold them."""
    get = sess._scope.get_value
    return (np.stack([np.asarray(get("hsd_ssm_%d" % i))[slot]
                      for i in MAMBA]),
            np.stack([np.asarray(get("hsd_win_%d" % i))[:, slot]
                      for i in MAMBA]))


def reference_state(out, k=0):
    return (np.stack([np.asarray(s[k]).T for s in out["states"]]),
            np.stack([np.asarray(w[k]) for w in out["windows"]]))


# -- the kernels in interpret mode against their composed references ----------

def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


# lengths that do and do not fill a 16-token group, a 128-token chunk and
# the bucket; (prompts, bucket, channels, d_state)
@pytest.mark.parametrize("B,T,d,n,lengths", [
    (3, 16, 128, 4, [16, 5, 1]),
    (2, 256, 1024, 16, [256, 130]),
    (3, 128, 512, 16, [128, 127, 17]),
    (2, 384, 256, 8, [129, 384]),
])
def test_prefill_kernels_match_their_references(B, T, d, n, lengths):
    rng = np.random.RandomState(T + d)
    x, w, bias = _rand(rng, B, T, d), _rand(rng, 4, d), _rand(rng, d)
    got = ss.causal_conv(x, w, bias, force_pallas=True)
    assert np.abs(got - ss.causal_conv_reference(x, w, bias)).max() < 1e-5
    dt = jax.nn.softplus(_rand(rng, B, T, d) - 3.0)
    b, c = _rand(rng, B, n, T), _rand(rng, B, n, T)
    a, skip = -jnp.exp(_rand(rng, n, d)), _rand(rng, d)
    lens = jnp.asarray(lengths)
    y, s = ss.prefill_scan(x, dt, b, c, a, skip, lens, force_pallas=True)
    y_ref, s_ref = ss.prefill_scan_reference(x, dt, b, c, a, skip, lens)
    assert np.abs(y_ref).max() > 0.1 and np.abs(s_ref).max() > 0.01
    assert np.abs(y - y_ref).max() < 1e-4 and np.abs(s - s_ref).max() < 1e-4
    # padding is NOTHING to the state, not a small thing: whatever stands
    # in the padded rows, the state is the same to the bit and y is 0 there
    pad = jnp.arange(T)[None, :, None] >= lens[:, None, None]
    y2, s2 = ss.prefill_scan(
        jnp.where(pad, 1e3, x), jnp.where(pad, 5.0, dt), b, c, a, skip, lens,
        force_pallas=True)
    assert (np.asarray(s2) == np.asarray(s)).all()
    assert (np.asarray(y2) == np.asarray(y)).all()
    assert not np.asarray(jnp.where(pad, y, 0.0)).any()


@pytest.mark.parametrize("S,d,n", [(12, 256, 4), (16, 2048, 16), (8, 128, 8)])
def test_one_token_kernels_match_their_references(S, d, n):
    rng = np.random.RandomState(S + d)
    win, x = _rand(rng, 3, S, d), _rand(rng, S, d)
    w, bias = _rand(rng, 4, d), _rand(rng, d)
    live = jnp.asarray(rng.randint(0, 2, size=S)).at[0].set(0).at[1].set(1)
    y, new = ss.conv_step(win, x, w, bias, live, force_pallas=True)
    y_ref, new_ref = ss.conv_step_reference(win, x, w, bias, live)
    assert np.abs(y - y_ref).max() < 1e-5 and (new == new_ref).all()
    state = _rand(rng, S, n, d)
    dt = jax.nn.softplus(_rand(rng, S, d) - 3.0)
    b, c = _rand(rng, S, n), _rand(rng, S, n)
    a, skip = -jnp.exp(_rand(rng, n, d)), _rand(rng, d)
    y, new = ss.state_update(state, x, dt, b, c, a, skip, live,
                             force_pallas=True)
    y_ref, new_ref = ss.state_update_reference(state, x, dt, b, c, a, skip,
                                               live)
    assert np.abs(y - y_ref).max() < 1e-5
    assert np.abs(new - new_ref).max() < 1e-5
    # a slot that is not live keeps its rows to the bit and reads 0
    dead = np.flatnonzero(np.asarray(live) == 0)
    assert (np.asarray(new)[dead] == np.asarray(state)[dead]).all()
    assert not np.asarray(y)[dead].any()
    # the one-token forms ARE the prefill forms a token at a time
    y1, s1 = ss.prefill_scan_reference(
        x[:, None], dt[:, None], b[:, :, None], c[:, :, None], a, skip,
        jnp.ones((S,), jnp.int32))
    y0, s0 = ss.state_update_reference(
        jnp.zeros_like(state), x, dt, b, c, a, skip, jnp.ones((S,)))
    assert np.abs(y1[:, 0] - y0).max() < 1e-5 and np.abs(s1 - s0).max() < 1e-6


@pytest.mark.parametrize("S,H,Hkv,dh,ps,npp", [
    (5, 20, 1, 128, 8, 4), (4, 8, 2, 16, 8, 3), (3, 4, 4, 32, 16, 2)])
def test_gqa_paged_decode_is_paged_attention_with_kv_repeated(
        S, H, Hkv, dh, ps, npp):
    rng = np.random.RandomState(H)
    P = 1 + S * npp
    q = _rand(rng, S, H, dh)
    k_pool, v_pool = _rand(rng, P, ps, Hkv * dh), _rand(rng, P, ps, Hkv * dh)
    table = jnp.asarray(rng.randint(1, P, size=(S, npp)))
    # an empty slot, a full one, lengths that do and do not fill a page
    lens = jnp.asarray(rng.randint(1, ps * npp, size=S)) \
        .at[0].set(0).at[1].set(ps * npp).at[2].set(ps)
    got = gq.gqa_paged_attention(q, k_pool, v_pool, table, lens,
                                 force_pallas=True)
    own = gq.gqa_paged_attention_reference(q, k_pool, v_pool, table, lens,
                                           dh ** -0.5)

    def repeated(pool):
        return jnp.repeat(pool.reshape(P, ps, Hkv, 1, dh), H // Hkv,
                          axis=3).reshape(P, ps, H * dh)

    want = paged_attention_reference(q, repeated(k_pool), repeated(v_pool),
                                     table, lens)
    assert np.abs(got - want).max() < 1e-5 and np.abs(own - want).max() < 1e-5
    assert not np.asarray(got)[0].any()
    with pytest.raises(ValueError, match="grouped-query page pool"):
        gq.gqa_paged_attention(q, k_pool[..., :-1], v_pool, table, lens)


# -- the layer order ----------------------------------------------------------

@pytest.mark.parametrize("period,offset,depth", [
    (14, 7, 28), (8, 4, 16), (4, 0, 8), (4, 2, 7), (3, 1, 5)])
def test_builder_reference_and_parameters_agree_on_the_layer_order(
        period, offset, depth):
    from perfbench import kernel_costs_jamba as costs

    desc = dict(DESC, attn_layer_period=period, attn_layer_offset=offset,
                num_hidden_layers=depth)
    kinds = hsd.layer_kinds(desc)
    want = [i for i in range(depth) if i % period == offset]
    assert [i for i, k in enumerate(kinds) if k == "attention"] == want
    assert ref.layer_kinds(desc) == kinds == costs.layer_kinds(desc)
    shapes = hsd.parameter_shapes(desc, "float32")
    for i, kind in enumerate(kinds):
        assert ("hsd_%d_q" % i in shapes) == (kind == "attention")
        assert ("hsd_%d_a_log" % i in shapes) == (kind == "mamba")
    built = hsd.build_hybrid_ssm_decoder(desc, 4, 48, 8, [8])
    state = built["geometry"]["state"]
    assert sorted(state["page_pools"]) == sorted(
        "hsd_%s_%d" % (part, i) for i in want for part in "kv")
    assert sorted(state["slot_arrays"]) == sorted(
        "hsd_%s_%d" % (part, i) for i in range(depth) if i not in want
        for part in ("ssm", "win"))
    assert built["geometry"]["layer_kinds"] == kinds
    # the weights module lays the tree out by the parameters' names alone
    tree = weights_jamba.tree(dict.fromkeys(shapes), desc)
    assert [("q" in layer["mixer"]) for layer in tree["layers"]] \
        == [k == "attention" for k in kinds]
    assert sum(int(np.prod(s)) for s, _dt in shapes.values()) \
        == costs.parameter_count(desc)["total"]


# -- the session against the reference ----------------------------------------

def test_prefill_of_different_lengths_in_one_dispatch_then_decode():
    """Prompts of 3, 5 and 8 tokens share ONE bucket dispatch (and 13 and
    30 have dispatches of their own): each slot's logits, recurrent state
    and window after the prefill are the reference's after the prompt's
    LAST REAL token, and after decode through the cache the reference's
    over the whole sequence. Float32 both sides: 1e-4 leaves a hundred
    times the rounding of 8 layers; a padded token leaking into the state,
    a state installed for the wrong row or a bfloat16 state (error ~4e-3
    a token) fails it."""
    sess, tree = make_session(tap=True)
    prompts = prompts_of([5, 8, 13, 3, 30])
    for p in prompts:
        sess.enqueue(p)
    slot_of = {rid: slot for slot, rid in sess.admit_pending().items()}
    assert sess.last_prefills == [(8, [5, 8, 3]), (16, [13]), (32, [30])]
    first = {}
    for feed, logits in sess._exe.prefill:
        for row, slot in enumerate(feed["slot_idx"]):
            first[int(slot)] = logits[row, 0]
    for rid, p in enumerate(prompts):
        n = len(p)
        out = ref.forward(tree, p, DESC, logits_at=[n - 1],
                          states_at=[n - 1])
        s, win = slot_state(sess, slot_of[rid])
        want_s, want_win = reference_state(out)
        assert rel(s, want_s) < 1e-4 and rel(win, want_win) < 1e-5, rid
        assert rel(first[slot_of[rid]], out["logits"][0]) < 1e-4
    for _ in range(4):
        sess.step()
    steps = np.concatenate(sess._exe.steps)[:, :, 0]         # [8, S, V]
    for rid, p in enumerate(prompts):
        slot, n = slot_of[rid], len(p)
        toks = sess.tokens_of(slot)                           # 9 of them
        seq = np.concatenate([p, toks[:-1]])
        out = ref.forward(tree, seq, DESC,
                          logits_at=np.arange(n, len(seq)),
                          states_at=[len(seq) - 1])
        assert rel(steps[:, slot], out["logits"]) < 1e-4
        s, win = slot_state(sess, slot)
        want_s, want_win = reference_state(out)
        assert rel(s, want_s) < 1e-4 and rel(win, want_win) < 1e-5
    # a bfloat16 state would not pass: the reference itself with s rounded
    # to bfloat16 a token lies 30 times the tolerance away
    p = prompts[4]
    sound = ref.forward(tree, p, DESC, states_at=[len(p) - 1])
    rounded = ref.forward(
        tree, p, DESC, states_at=[len(p) - 1],
        state_round=lambda s: s.astype(jnp.bfloat16).astype(jnp.float32))
    assert rel(reference_state(rounded)[0], reference_state(sound)[0]) > 1e-3


def test_a_slots_logits_do_not_depend_on_its_dispatch_mates():
    """Slots join and leave across dispatches of 2 tokens: a prompt served
    alone and the same prompt served among others, admitted later and
    beside cancelled neighbours, get the same logits."""
    prompt = prompts_of([11], seed=5)[0]
    alone, _tree = make_session(tap=True)
    slot = alone.admit(prompt)
    for _ in range(4):
        alone.step()
    want = np.concatenate(alone._exe.steps)[:, slot, 0]
    sess, _tree = make_session(tap=True)
    others = prompts_of([4, 29, 8, 16], seed=6)
    for p in others[:3]:
        sess.enqueue(p)
    sess.admit_pending()
    sess.step()                       # the others are a dispatch ahead
    sess.enqueue(prompt)
    (mine,) = sess.admit_pending()
    got = []
    for i in range(4):
        if i == 1:
            assert sess.cancel(sess.active_slots[0])       # one leaves
        if i == 2:
            sess.enqueue(others[3])                        # one joins
            sess.admit_pending()
        sess.step()
        got.append(sess._exe.steps[-1][:, mine, 0])
    assert rel(np.concatenate(got), want) < 1e-5
    assert (alone.tokens_of(slot) == sess.tokens_of(mine)).all()


def test_a_dispatch_runs_the_least_rung_of_rows_that_holds_its_prompts():
    """With ``prefill_rungs`` a bucket has a program a power of two of
    prompt rows under its most (budget 64: 8, 4 and 2 prompts of 8, 16 and
    32): one prompt is walked as one row, three as four, and tokens, logits
    and the state a prefill installs are those of the session that walks
    the whole budget."""
    def serve(**kw):
        sess, _tree = make_session(tap=True, **kw)
        for p in prompts_of([27, 5, 6, 7]):
            sess.enqueue(p)
        slot_of = {rid: slot for slot, rid in sess.admit_pending().items()}
        state = {rid: slot_state(sess, slot) for rid, slot in slot_of.items()}
        first = {int(slot): logits[row, 0]
                 for feed, logits in sess._exe.prefill
                 for row, slot in enumerate(feed["slot_idx"])}
        for _ in range(3):
            sess.step()
        steps = np.concatenate(sess._exe.steps)[:, :, 0]      # [6, S, V]
        return sess, {rid: (sess.tokens_of(slot), first[slot],
                            steps[:, slot]) + state[rid]
                      for rid, slot in slot_of.items()}

    plain, want = serve()
    sess, got = serve(prefill_rungs=True)
    assert plain.geometry["prefill_rungs"] == {8: [8], 16: [4], 32: [2]}
    assert sess.geometry["prefill_rungs"] == {
        8: [1, 2, 4, 8], 16: [1, 2, 4], 32: [1, 2]}
    assert sess.geometry["prompts_per_dispatch"] == {8: 8, 16: 4, 32: 2}
    places = lambda s: sorted(len(feed["prompt_ids"])  # noqa: E731
                              for feed, _first in s._exe.prefill)
    assert places(plain) == [64, 64] and places(sess) == [32, 32]
    assert sess.last_prefills == [(8, [5, 6, 7]), (32, [27])]
    for rid in range(4):
        assert (got[rid][0] == want[rid][0]).all()
        for mine, theirs in zip(got[rid][1:], want[rid][1:]):
            assert rel(mine, theirs) < 1e-5


def test_cancel_and_reuse_a_slot_starts_from_its_own_prefill():
    sess, tree = make_session(num_slots=3, tap=True)
    for p in prompts_of([9, 30, 3]):
        sess.enqueue(p)
    assert len(sess.admit_pending()) == 3 and sess.free_slots == 0
    for _ in range(2):
        sess.step()
    held = sess.pages_in_use
    before = slot_state(sess, 1)
    assert np.abs(before[0]).max() > 0
    assert sess.cancel(1) and not sess.cancel(1)
    assert sess.pool_conserved and sess.pages_in_use < held
    sess.step()
    # the freed slot's rows are dead weight the step walks: unchanged,
    # finite, and nobody's business
    after = slot_state(sess, 1)
    assert (after[0] == before[0]).all() and (after[1] == before[1]).all()
    assert np.isfinite(np.concatenate(sess._exe.steps)).all()
    # another prompt takes the slot: a fresh slot's logits and state
    prompt = prompts_of([12], seed=9)[0]
    rid = sess.enqueue(prompt)
    assert sess.admit_pending() == {1: rid}
    out = ref.forward(tree, prompt, DESC, logits_at=[11], states_at=[11])
    s, win = slot_state(sess, 1)
    want_s, want_win = reference_state(out)
    assert rel(s, want_s) < 1e-4 and rel(win, want_win) < 1e-5
    assert rel(sess._exe.prefill[-1][1][0, 0], out["logits"][0]) < 1e-4
    n0 = len(sess._exe.steps)
    sess.step()
    toks = sess.tokens_of(1)
    seq = np.concatenate([prompt, toks[:-1]])
    out = ref.forward(tree, seq, DESC, logits_at=[12, 13])
    assert rel(sess._exe.steps[n0][:, 1, 0], out["logits"]) < 1e-4
    done = {}
    while sess.active_slots:
        done.update(sess.pump())
    assert rid in done and len(done[rid]) == 17 and sess.pool_conserved
    assert sess.pages_in_use == 0 and sess.free_slots == 3
    for i in MAMBA:       # dead slots stay finite
        assert np.isfinite(np.asarray(
            sess._scope.get_value("hsd_ssm_%d" % i))).all()


def test_the_model_is_chosen_from_its_description():
    from paddle_tpu.models.latent_moe_decoder import build_latent_moe_decoder

    assert builder_for(DESC) is hsd.build_hybrid_ssm_decoder
    assert builder_for({"kv_lora_rank": 16}) is build_latent_moe_decoder
    with pytest.raises(ServingError, match="knows no builder"):
        builder_for({"hidden_size": 64})
    sess, _tree = make_session()
    state = sess.geometry["state"]
    assert len(state["page_pools"]) == 4 and len(state["slot_arrays"]) == 12
    assert state["slot_arrays"]["hsd_ssm_0"] == {
        "shape": (6, 4, 128), "dtype": "float32", "slot_axis": 0}
    assert state["slot_arrays"]["hsd_win_0"]["shape"] == (3, 6, 128)
    prompt = prompts_of([6])[0]
    for call in (lambda: sess.enqueue(prompt, prefix_tokens=[4, 5]),
                 lambda: sess.admit_group(prompt, n=2),
                 lambda: sess.admit_beam(prompt),
                 lambda: sess.enqueue(np.zeros(40, "int64"))):
        with pytest.raises(ServingError):
            call()
    for key, value in (("num_experts", 16), ("sliding_window", 4096),
                       ("tie_word_embeddings", False)):
        with pytest.raises(NotImplementedError):
            hsd.hybrid_dims(dict(DESC, **{key: value}))


def test_behind_a_real_frontend_two_clients_stream_at_once():
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import ServingClient, ServingFrontend

    # streams long enough that the clients end them, not max_new_tokens
    sess, tree = make_session(max_new_tokens=400)
    step = sess.step
    # a dispatch of the tiny model is faster than a stream's handler looks
    # for its client's cancel (between events, every stream_poll_s)
    sess.step = lambda: (time.sleep(0.1), step())[1]
    tracing.reset()
    tracing.enable(True)
    fe = ServingFrontend(session=sess, stream_poll_s=0.01)
    got, errors = {}, []

    def caller(i, prompt, want):
        try:
            client = ServingClient(fe.address, timeout_s=60)
            src = np.zeros(32, "int64")
            src[:len(prompt)] = prompt
            toks = []
            stream = client.generate(src, src_len=len(prompt))
            for ev in stream:
                if ev.get("event") == "tokens":
                    toks += [int(t) for t in ev["tokens"]]
                    if len(toks) >= want:
                        break
            stream.close()
            client.close()
            got[i] = toks[:want]
        except Exception as exc:  # noqa: BLE001 - reported below
            errors.append(repr(exc))

    prompts = prompts_of([7, 21], seed=4)
    threads = [threading.Thread(target=caller, args=(i, p, w))
               for i, (p, w) in enumerate(zip(prompts, (12, 9)))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        deadline = time.time() + 30
        while sess.active_slots and time.time() < deadline:
            time.sleep(0.02)
        with pytest.raises(ServingError, match="does not support"):
            list(ServingClient(fe.address).generate(
                np.zeros(32, "int64"), src_len=3, beam=True))
    finally:
        fe.close(drain=False, timeout=30)
        tracing.enable(False)
    # greedy streams are the reference's argmax over its own forward
    for i, prompt in enumerate(prompts):
        seq = np.concatenate([prompt, got[i][:-1]]).astype("int64")
        out = ref.forward(tree, jnp.asarray(seq), DESC,
                          logits_at=list(range(len(prompt) - 1, len(seq))))
        assert np.asarray(out["logits"]).argmax(-1).tolist() == got[i]
    assert len(got[0]) == 12 and len(got[1]) == 9
    assert sess.pool_conserved and not sess.active_slots

    # the worker's rounds carry the spans and the new counters
    names, counts, live = set(), {}, []
    for rd in tracing.rounds():
        spans = rd["spans"]
        names |= {s["name"] for s in spans}
        for key in ("prefill_prompts", "prefill_tokens",
                    "prefill_pad_tokens", "prefill_dispatches"):
            if key in spans[0]:
                counts[key] = counts.get(key, 0) + spans[0][key]
        if "state_slots_live" in spans[0]:
            live.append(spans[0]["state_slots_live"])
        assert "expert_max_over_mean" not in spans[0]
    assert {"admit", "prefill", "prefill.dispatch", "step",
            "step.dispatch", "cancel", "handoff"} <= names
    assert counts["prefill_prompts"] == 2
    assert counts["prefill_tokens"] == 7 + 21
    # a dispatch walks prompts_per_dispatch x bucket token places
    per = sess.geometry["prompts_per_dispatch"]
    assert counts["prefill_pad_tokens"] + 28 in (
        8 * per[8] + 32 * per[32], )
    assert live and set(live) <= {1, 2}
    tracing.reset()
