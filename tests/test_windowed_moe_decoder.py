"""The decoder of sliding-window and full attention layers with routed
experts, tiny on the CPU (hidden 64, 4 query heads on 2 key/value heads of
16, window 8 over pages of 4 so that a slot gives pages back many times, 8
experts top 2 beside a shared one, one dense layer then a whole period of
three window layers and a full one, vocabulary 512): the window decode
kernel in interpret mode against its composed reference and against plain
arithmetic, the ring's prefill write, prefill then decode through
``DecoderOnlySession`` (both kinds of pool) against the plain reference's
full forward for prompts under and over the window, what the comparison
is sensitive to, the accounting of both kinds of page, cancel and reuse,
``builder_for`` on the three descriptions, the session behind a real
``ServingFrontend``."""

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
from paddle_tpu.kernels import window_paged_attention as wp  # noqa: E402
from paddle_tpu.models import windowed_moe_decoder as wmd  # noqa: E402
from paddle_tpu.ops.window_ops import ring_row_prefill  # noqa: E402
from paddle_tpu.serving.decoder_session import (  # noqa: E402
    DecoderOnlySession,
    builder_for,
)
from paddle_tpu.serving.server import ServingError  # noqa: E402
from perfbench import weights_trinity  # noqa: E402
from perfbench.reference import afmoe_decoder as ref  # noqa: E402

WINDOW, PS, K = 8, 4, 2
DESC = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, intermediate_size=96, moe_intermediate_size=32,
            num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
            num_dense_layers=1, num_hidden_layers=5, vocab_size=512,
            layer_types=["sliding_attention"] * 4 + ["full_attention"],
            sliding_window=WINDOW, rms_norm_eps=1e-5, rope_theta=10000.0,
            route_norm=True, route_scale=2.826, score_func="sigmoid",
            mup_enabled=True, tie_word_embeddings=False, n_group=1,
            topk_group=1, rope_scaling=None)
RING = wmd.ring_pages_per_slot(WINDOW, K, PS)          # 4 pages a slot


class Tap(object):
    """An executor that also fetches the logits of every dispatch (what
    the benchmark's check does on the chip) and counts the dispatches."""

    def __init__(self, exe, fetches):
        self._exe, self._f = exe, fetches
        self.prefill, self.steps, self.calls = [], [], 0

    def __getattr__(self, name):
        return getattr(self._exe, name)

    def run(self, program, feed=None, fetch_list=None, scope=None, **kw):
        self.calls += 1
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
        self.calls += 1
        out = self._exe.run_multi_step(
            program, steps, feed=feed, scope=scope,
            fetch_list=list(fetch_list) + [self._f["logits"]], **kw)
        self.steps.append(np.asarray(out[-1]))           # [K, S, 1, V]
        return out[:-1]


def make_session(seed=3, num_slots=4, tap=True, desc=DESC, **kw):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    params = wmd.random_parameters(desc, seed, "float32")
    wmd.load_parameters(scope, params, desc, "float32")
    args = dict(num_slots=num_slots, max_prompt=32, max_new_tokens=16,
                page_size=PS, tokens_per_dispatch=K,
                prefill_token_budget=64, scope=scope, dtype="float32")
    args.update(kw)
    sess = DecoderOnlySession(exe, desc, **args)
    if tap:
        sess._exe = Tap(exe, sess._fetch)
    return sess, weights_trinity.tree({k: jnp.asarray(v)
                                       for k, v in params.items()}, desc)


def rel(got, want):
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def prompts_of(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, DESC["vocab_size"], n) for n in lengths]


def served_logits(sess, slot, prefill_at=None):
    """[1 + decoded, V]: the slot's prefill logits then every decode
    step's, from the tap."""
    tap = sess._exe
    feed, first = tap.prefill[-1 if prefill_at is None else prefill_at]
    row = list(feed["slot_idx"]).index(slot)
    rows = [first[row, 0]] + [s[k, slot, 0] for s in tap.steps
                              for k in range(s.shape[0])]
    return np.stack(rows)


# -- the kernel ---------------------------------------------------------------

def ring_table(lengths, window, ps, R, rng=None):
    """Every slot's ring over distinct pages (in a shuffled order: the
    entries of a ring are not in position order, nor are its pages)."""
    S = len(lengths)
    ids = np.arange(1, 1 + S * R)
    if rng is not None:
        rng.shuffle(ids)
    table, at = np.zeros((S, R), "int32"), 0
    for s, n in enumerate(lengths):
        if n:
            for j in range(max(n - window, 0) // ps, (n - 1) // ps + 1):
                table[s, j % R] = ids[at]
                at += 1
    return table


@pytest.mark.parametrize("S,H,Hkv,dh,ps,window,lengths", [
    (5, 4, 2, 16, 4, 9, [0, 3, 9, 14, 37]),
    (4, 8, 1, 32, 8, 16, [16, 17, 100, 1]),
    (3, 6, 3, 16, 4, 8, [8, 12, 31]),
])
def test_window_decode_kernel_matches_reference_and_plain_arithmetic(
        S, H, Hkv, dh, ps, window, lengths):
    rng = np.random.RandomState(S + H)
    R = wmd.ring_pages_per_slot(window, 1, ps)
    P = 1 + S * R
    k_pool = jnp.asarray(rng.randn(P, ps, Hkv * dh), jnp.float32)
    v_pool = jnp.asarray(rng.randn(P, ps, Hkv * dh), jnp.float32)
    q = jnp.asarray(rng.randn(S, H, dh), jnp.float32)
    table = ring_table(lengths, window, ps, R, rng)
    args = (q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(lengths),
            window)
    want = np.asarray(wp.window_paged_attention(*args,
                                                force_reference=True))
    got = np.asarray(wp.window_paged_attention(*args, force_pallas=True))
    np.testing.assert_allclose(got, want, atol=2e-6)
    for s, n in enumerate(lengths):
        if not n:
            assert not want[s].any()
            continue
        pos = np.arange(max(n - window, 0), n)

        def rows(pool):
            return np.stack([np.asarray(pool)[table[s, (p // ps) % R],
                                              p % ps] for p in pos]
                            ).reshape(len(pos), Hkv, dh)

        kk, vv = rows(k_pool), rows(v_pool)
        for h in range(H):
            g = h // (H // Hkv)
            sc = kk[:, g] @ np.asarray(q)[s, h] * dh ** -0.5
            w = np.exp(sc - sc.max())
            np.testing.assert_allclose(
                (w / w.sum()) @ vv[:, g], want[s, h], atol=1e-5)


def test_a_ring_too_short_for_its_window_is_refused():
    z = jnp.zeros
    with pytest.raises(ValueError, match="cannot hold a window"):
        wp.window_paged_attention(
            z((2, 4, 16)), z((9, 4, 32)), z((9, 4, 32)),
            z((2, 2), jnp.int32), z((2,), jnp.int32), 8)


@pytest.mark.parametrize("length", [0, 3, 8, 9, 16, 21, 32])
def test_prefill_writes_only_the_pages_the_ring_keeps(length):
    """Of a 32-token bucket, a prompt of ``length`` leaves the logical
    pages from that of row ``length - window + 1`` to its last in their
    ring columns, and nothing else anywhere but the trash page."""
    T, Wd = 32, 6
    pool = jnp.zeros((1 + RING, PS, Wd), jnp.float32)
    rows = jnp.asarray(np.arange(1, T * Wd + 1, dtype="float32")
                       .reshape(1, T, Wd))
    ring = np.arange(1, 1 + RING, dtype="int32")[None, :]
    out = np.asarray(ring_row_prefill(
        pool, rows, jnp.asarray(ring), jnp.asarray([length]), WINDOW))
    lo = max(length - WINDOW + 1, 0) // PS
    hi = (length - 1) // PS if length else -1
    kept = {j % RING: j for j in range(lo, hi + 1)}
    assert len(kept) <= RING
    for col in range(RING):
        page = out[1 + col]
        if col in kept:
            j = kept[col]
            np.testing.assert_array_equal(
                page, np.asarray(rows)[0, j * PS:(j + 1) * PS])
        else:
            assert not page.any()


# -- the session against the reference ----------------------------------------

# under the window, on its edge, a page boundary on the edge, over it, and
# several windows long
LENGTHS = [3, 8, 9, 12, 19, 30]


@pytest.fixture(scope="module")
def served():
    """Each prompt of ``LENGTHS`` prefilled (several a dispatch) and
    decoded 12 tokens through the session; per prompt its tokens and the
    logits of the last prompt position and of every decoded one."""
    sess, tree = make_session(num_slots=len(LENGTHS))
    prompts = prompts_of(LENGTHS, seed=1)
    for p in prompts:
        sess.enqueue(p)
    admitted = sess.admit_pending()
    assert len(admitted) == len(prompts)
    slot_of = {rid: slot for slot, rid in admitted.items()}
    for _ in range(6):
        sess.step()
    out = []
    for rid, p in enumerate(prompts):
        slot = slot_of[rid]
        at = next(i for i, (feed, _l) in enumerate(sess._exe.prefill)
                  if slot in list(feed["slot_idx"]))
        toks = sess.tokens_of(slot)
        out.append((np.concatenate([p, toks[:-1]]), len(p),
                    served_logits(sess, slot, at)[:len(toks)]))
    assert sess.pool_conserved
    return tree, out


def reference_logits(tree, seq, n, desc=DESC, **kw):
    return np.asarray(ref.forward(
        tree, seq, desc, logits_at=np.arange(n - 1, len(seq)), **kw)[
            "logits"])


@pytest.mark.parametrize("i", range(len(LENGTHS)),
                         ids=["prompt%d" % n for n in LENGTHS])
def test_prefill_then_decode_through_both_kinds_of_pool(served, i):
    tree, out = served
    seq, n, got = out[i]
    assert rel(got, reference_logits(tree, seq, n)) < 2e-5


def _without_qk_norms(monkeypatch):
    norm = ref.rms_norm
    monkeypatch.setattr(
        ref, "rms_norm",
        lambda x, scale, eps: x if x.ndim == 3 else norm(x, scale, eps))


def _rope_everywhere(monkeypatch):
    attention = ref.attention
    wide = DESC["sliding_window"] * 1000

    def on_full_layers_too(p, x, d, sliding, band=True, mm=jnp.matmul):
        d = d if sliding else dict(d, W=wide)
        return attention(p, x, d, True, band, mm)

    monkeypatch.setattr(ref, "attention", on_full_layers_too)


def _rope_nowhere(monkeypatch):
    monkeypatch.setattr(ref, "rope", lambda x, pos, theta: x)


@pytest.mark.parametrize("part", ["band", "qk_norms", "gate",
                                  "rope_on_window_layers",
                                  "no_rope_on_full_layers", "embed_scale"])
def test_the_comparison_fails_if_a_part_is_left_out(served, monkeypatch,
                                                    part):
    """The reference with one part of the mathematics left out lies far
    from what the session served (which agrees with the whole reference
    to 2e-5): the comparison above would fail on a program without it."""
    tree, out = served
    seq, n, got = out[-1]                     # 30 tokens: over the window
    kw, desc = {}, DESC
    if part == "band":
        kw["band"] = False
    elif part == "qk_norms":
        _without_qk_norms(monkeypatch)
    elif part == "gate":
        # sigmoid(0) = 1/2 on every column, which the norm after the
        # attention takes out again: the gate left out
        tree = dict(tree, layers=[
            dict(p, gate=jnp.zeros_like(p["gate"])) for p in tree["layers"]])
    elif part == "rope_on_window_layers":
        _rope_nowhere(monkeypatch)
    elif part == "no_rope_on_full_layers":
        _rope_everywhere(monkeypatch)
    else:
        desc = dict(DESC, mup_enabled=False)
    jax.clear_caches()        # ``ref.layer`` is jitted over what was patched
    try:
        assert rel(got, reference_logits(tree, seq, n, desc, **kw)) > 0.02
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_a_slots_logits_do_not_depend_on_its_dispatch_mates():
    alone, tree = make_session()
    prompt = prompts_of([19], seed=5)[0]
    slot = alone.admit(prompt)
    for _ in range(3):
        alone.step()
    want = served_logits(alone, slot)

    crowd, _ = make_session()
    for p in prompts_of([30, 5], seed=6):
        crowd.admit(p)
    crowd.step()
    crowd._exe.steps = []
    slot = crowd.admit(prompt)
    for _ in range(3):
        crowd.step()
    assert rel(served_logits(crowd, slot), want) < 1e-5


def test_a_dispatch_runs_the_least_rung_of_rows_that_holds_its_prompts():
    """With ``prefill_rungs`` a bucket has a program a power of two of
    prompt rows under its most (budget 64: 16, 8, 4 and 2 prompts of 4, 8,
    16 and 32), each with the ring's ``window_rows`` of as many rows: one prompt
    is walked as one row, three as four, and tokens and logits are those
    of the session that walks the whole budget."""
    def serve(**kw):
        sess, _tree = make_session(**kw)
        for p in prompts_of([27, 5, 6, 7]):
            sess.enqueue(p)
        slot_of = {rid: slot for slot, rid in sess.admit_pending().items()}
        for _ in range(3):
            sess.step()
        # the bucket of 8 is dispatched first, then the bucket of 32
        return sess, {rid: (sess.tokens_of(slot),
                            served_logits(sess, slot, prefill_at=rid == 0))
                      for rid, slot in slot_of.items()}

    plain, want = serve()
    sess, got = serve(prefill_rungs=True)
    assert plain.geometry["prefill_rungs"] == {
        4: [16], 8: [8], 16: [4], 32: [2]}
    assert sess.geometry["prefill_rungs"] == {
        4: [1, 2, 4, 8, 16], 8: [1, 2, 4, 8], 16: [1, 2, 4], 32: [1, 2]}
    assert sess.geometry["prompts_per_dispatch"] == {
        4: 16, 8: 8, 16: 4, 32: 2}
    feeds = lambda s: sorted(  # noqa: E731
        (len(feed["prompt_ids"]), feed["window_rows"].shape)
        for feed, _first in s._exe.prefill)
    assert feeds(plain) == [(64, (2, RING)), (64, (8, RING))]
    assert feeds(sess) == [(32, (1, RING)), (32, (4, RING))]
    assert sess.last_prefills == [(8, [5, 6, 7]), (32, [27])]
    for rid in range(4):
        assert (got[rid][0] == want[rid][0]).all()
        assert rel(got[rid][1], want[rid][1]) < 1e-5


# -- the accounting -----------------------------------------------------------

def test_a_ring_never_holds_more_than_its_pages_and_both_kinds_conserve():
    """Random admissions, decode to the end and cancels: a slot's ring
    holds exactly the pages of the rows the next dispatch can see or
    write, never more than ``RING``; its full pages grow with the
    sequence; both kinds conserve; a page behind the window goes back
    without a dispatch of its own."""
    sess, _tree = make_session(num_slots=5, max_new_tokens=20)
    npp = sess.geometry["pages_per_slot"]
    rng = np.random.RandomState(2)
    released = 0
    for rnd in range(40):
        if rng.rand() < 0.6:
            sess.enqueue(rng.randint(3, 512, int(rng.randint(1, 33))))
        before = sess._exe.calls
        sess.admit_pending()
        live = len(sess.active_slots)
        sess.step()
        assert sess._exe.calls - before <= 1 + len(sess.last_prefills)
        if live:
            c = sess.last_counters
            assert c["pages_in_use"] == (c["full_pages_in_use"]
                                         + c["window_pages_in_use"])
            released += c["window_pages_released"]
        for slot in sess.active_slots:
            st = sess._live[slot]
            at = st["len"] + st["n"] - 1          # the next row written
            (lo_f, full), (lo_w, ring) = sess.slot_pages(slot)
            assert lo_f == 0 and len(full) <= npp
            assert len(ring) <= RING
            # after a dispatch the ring still holds the pages that one
            # provisioned: from the first row ITS first query saw
            first = max(at - K - WINDOW + 1, 0) // PS
            assert lo_w == first
            assert lo_w + len(ring) == min(-(-at // PS), npp)
            table = sess._kinds[1].table[slot]
            assert sorted(table[table > 0]) == sorted(ring)
        assert sess.pool_conserved
        if sess.active_slots and rng.rand() < 0.25:
            victim = sess.active_slots[int(rng.randint(
                len(sess.active_slots)))]
            before = sess._exe.calls
            assert sess.cancel(victim)
            assert sess._exe.calls == before      # bookkeeping only
            assert sess.pool_conserved
    assert released > 20
    for slot in sess.active_slots:
        sess.cancel(slot)
    assert sess.pool_conserved and sess.pages_in_use == 0
    assert [k.reserved for k in sess._kinds] == [0, 0]


def test_admission_reserves_the_worst_case_in_every_kind():
    sess, _tree = make_session(num_slots=3)
    npp = sess.geometry["pages_per_slot"]             # 48 / 4 = 12
    sess.admit(prompts_of([5])[0])
    full, ring = sess._kinds
    assert full.reserved == -(-(5 + 16) // PS) and ring.reserved == RING
    sess.admit(prompts_of([32])[0])
    assert full.reserved == 6 + npp and ring.reserved == 2 * RING
    # a pool of full pages that cannot reserve a third worst case waits
    small, _ = make_session(num_slots=3, num_pages=1 + npp + 9)
    for n in (32, 20, 3):
        small.enqueue(prompts_of([n])[0])
    assert len(small.admit_pending()) == 2      # 12 + 9 pages; 5 more wait
    assert small.pending_requests == [2]


def test_cancel_in_mid_window_and_a_reused_slot_start_clean():
    sess, tree = make_session(num_slots=1)
    first = prompts_of([27], seed=7)[0]
    slot = sess.admit(first)
    for _ in range(3):
        sess.step()                  # pages behind the window have gone
    assert sess.slot_pages(slot)[1][0] > 0
    assert sess.cancel(slot)
    assert sess.pool_conserved and sess.pages_in_use == 0

    # the same slot, a shorter prompt: rows of its predecessor lie in the
    # pages it is given again, and nothing of them shows
    second = prompts_of([10], seed=8)[0]
    sess._exe.steps = []
    assert sess.admit(second) == slot
    for _ in range(4):
        sess.step()
    toks = sess.tokens_of(slot)
    seq = np.concatenate([second, toks[:-1]])
    got = served_logits(sess, slot)[:len(toks)]
    assert rel(got, reference_logits(tree, seq, len(second))) < 2e-5

    # a stream that runs to max_new_tokens frees both kinds of page
    done = {}
    while not done:
        done = sess.step()
    assert list(done) == [slot] and sess.pages_in_use == 0
    assert sess.pool_conserved and sess.free_slots == 1


# -- the builder --------------------------------------------------------------

def test_the_model_is_chosen_from_its_description():
    from paddle_tpu.models.hybrid_ssm_decoder import build_hybrid_ssm_decoder
    from paddle_tpu.models.latent_moe_decoder import build_latent_moe_decoder

    assert builder_for(DESC) is wmd.build_windowed_moe_decoder
    # the hybrid's published config carries a sliding_window key (null)
    jamba = {"mamba_d_state": 16, "sliding_window": None,
             "num_experts": 1}
    assert builder_for(jamba) is build_hybrid_ssm_decoder
    assert builder_for({"kv_lora_rank": 512}) is build_latent_moe_decoder
    for desc in ({"hidden_size": 64}, {"layer_types": ["full_attention"]},
                 {"layer_types": [], "sliding_window": None}):
        with pytest.raises(ServingError, match="knows no builder"):
            builder_for(desc)
    with pytest.raises(ValueError, match="layer_types"):
        wmd.windowed_dims(dict(DESC, num_hidden_layers=4))
    for key, value in (("score_func", "softmax"), ("n_group", 2),
                       ("tie_word_embeddings", True)):
        with pytest.raises(NotImplementedError):
            wmd.windowed_dims(dict(DESC, **{key: value}))


def test_geometry_declares_both_kinds_of_pool_and_parameters_agree():
    sess, tree = make_session(tap=False)
    state = sess.geometry["state"]
    (ring,) = state["windowed"]
    assert ring["window"] == WINDOW and ring["pages_per_slot"] == RING
    assert ring["num_pages"] == 1 + 4 * RING
    assert ring["pools"] == ["wmd_%s_%d" % (part, i) for i in range(4)
                             for part in "kv"]
    for name, spec in state["page_pools"].items():
        pages = ring["num_pages"] if name in ring["pools"] \
            else sess.geometry["num_pages"]
        assert spec["shape"] == (pages, PS, 2 * 16)
        assert sess._scope.get_value(name).shape == spec["shape"]
    assert not state["slot_arrays"]
    shapes = wmd.parameter_shapes(DESC)
    assert shapes["wmd_0_ffn_gate"][0] == (64, 96)
    assert "wmd_0_router" not in shapes
    assert shapes["wmd_4_experts_gate"][0] == (8, 64, 32)
    assert shapes["wmd_4_router_bias"] == ((8,), "float32")
    assert len(tree["layers"]) == 5 and "router" in tree["layers"][1]["ffn"]
    # the other two builders declare no ring, and take the same path
    assert len(sess._kinds) == 2 and sess._kinds[0].window is None


# -- behind the frontend ------------------------------------------------------

def test_behind_a_real_frontend_two_clients_stream_at_once():
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import ServingClient, ServingFrontend

    # streams long enough that the clients end them, not max_new_tokens
    sess, tree = make_session(max_new_tokens=400, tap=False)
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
               for i, (p, w) in enumerate(zip(prompts, (14, 9)))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        deadline = time.time() + 30
        while sess.active_slots and time.time() < deadline:
            time.sleep(0.02)
    finally:
        fe.close(drain=False, timeout=30)
        tracing.enable(False)
    # greedy streams are the reference's argmax over its own forward
    for i, prompt in enumerate(prompts):
        seq = np.concatenate([prompt, got[i][:-1]]).astype("int64")
        want = reference_logits(tree, seq, len(prompt))
        assert want.argmax(-1).tolist() == got[i]
    assert len(got[0]) == 14 and len(got[1]) == 9
    assert sess.pool_conserved and not sess.active_slots

    # the worker's rounds carry the spans and the counts by kind
    names, seen = set(), {}
    for rd in tracing.rounds():
        spans = rd["spans"]
        names |= {s["name"] for s in spans}
        for key in ("full_pages_in_use", "window_pages_in_use",
                    "window_pages_released", "full_rows_visible",
                    "window_rows_visible", "expert_max_over_mean",
                    "prefill_pad_tokens"):
            if key in spans[0]:
                seen.setdefault(key, []).append(spans[0][key])
    assert {"admit", "prefill", "prefill.dispatch", "step",
            "step.dispatch", "cancel"} <= names
    assert sum(seen["window_pages_released"]) >= 2
    assert max(seen["window_pages_in_use"]) <= 2 * RING
    assert max(seen["window_rows_visible"]) <= 2 * WINDOW
    assert max(seen["full_rows_visible"]) > 2 * WINDOW
    assert all(v >= 1.0 for v in seen["expert_max_over_mean"])
    assert sum(seen["prefill_pad_tokens"]) > 0
    tracing.reset()


def test_an_admit_token_budget_leaves_the_rest_to_the_next_round():
    """Six queued prompts under a budget of two dispatches of the longest
    bucket: a round admits what fits the budget (one request at least),
    decodes, and the next round admits more, in queue order."""
    sess, _tree = make_session(num_slots=6)
    for n in (30, 20, 5, 32, 9, 17):          # buckets 32 32 8 32 16 32
        sess.enqueue(prompts_of([n])[0])
    sess.admit_token_budget = 64
    assert sorted(sess.admit_pending().values()) == [0, 1]
    assert sess.pending_requests == [2, 3, 4, 5]
    sess.step()
    assert sorted(sess.admit_pending().values()) == [2, 3, 4]   # 8 + 32 + 16
    assert sorted(sess.admit_pending().values()) == [5]
    sess.admit_token_budget = 1                # one request at least
    sess.cancel(0), sess.cancel(1)
    for n in (30, 30):
        sess.enqueue(prompts_of([n])[0])
    assert len(sess.admit_pending()) == 1
    sess.admit_token_budget = None
    assert len(sess.admit_pending()) == 1
    assert sess.pool_conserved and not sess.pending_requests
