"""The decoder-only model with latent (MLA) attention and dropless routed
experts, tiny on the CPU, widths in the published RATIOS of its first
configuration (hidden 64, 4 heads of 12+4 / 16, ranks 24 / 16, 8 experts
top 2 beside a shared one, 1 dense + 2 expert layers, vocabulary 512):
each new op and the latent kernel against their references, prefill of
several prompts a dispatch and then decode through ``DecoderOnlySession``
and its pool against the plain reference's full forward (logits), the
session behind a real ``ServingFrontend``."""

import functools
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
from paddle_tpu.kernels import latent_attention as la  # noqa: E402
from paddle_tpu.models import latent_moe_decoder as lmd  # noqa: E402
from paddle_tpu.ops import decoder_ops, moe_ops  # noqa: E402
from paddle_tpu.serving.decoder_session import DecoderOnlySession  # noqa: E402
from paddle_tpu.serving.server import ServingError  # noqa: E402
from perfbench import weights_glm  # noqa: E402
from perfbench.reference import latent_moe_decoder as ref  # noqa: E402

DESC = dict(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=12,
            qk_rope_head_dim=4, v_head_dim=16, q_lora_rank=24,
            kv_lora_rank=16, intermediate_size=320,
            moe_intermediate_size=48, n_routed_experts=8,
            num_experts_per_tok=2, n_shared_experts=1,
            first_k_dense_replace=1, num_hidden_layers=3, vocab_size=512,
            rms_norm_eps=1e-5, rope_theta=1e6, routed_scaling_factor=1.8,
            norm_topk_prob=True, topk_method="noaux_tc", n_group=1,
            topk_group=1)


class Tap(object):
    """An executor that also fetches the logits and the chosen experts of
    every dispatch (what the benchmark's check does on the chip)."""

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
                self._f["first_logits"], self._f["first_chosen"]], **kw)
        self.prefill.append((feed, np.asarray(out[-2]), np.asarray(out[-1])))
        return out[:-2]

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, **kw):
        out = self._exe.run_multi_step(
            program, steps, feed=feed, scope=scope,
            fetch_list=list(fetch_list) + [self._f["logits"]], **kw)
        self.steps.append(np.asarray(out[-1]))
        return out[:-1]


def make_session(seed=3, num_slots=4, tap=False, **kw):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    params = lmd.random_parameters(DESC, seed, "float32")
    lmd.load_parameters(scope, params, DESC, "float32")
    args = dict(num_slots=num_slots, max_prompt=32, max_new_tokens=16,
                page_size=8, tokens_per_dispatch=2,
                prefill_token_budget=64, scope=scope, dtype="float32")
    args.update(kw)
    sess = DecoderOnlySession(exe, DESC, **args)
    if tap:
        sess._exe = Tap(exe, sess._fetch)
    return sess, weights_glm.tree({k: jnp.asarray(v)
                                   for k, v in params.items()}, DESC)


def rel(got, want):
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def prompts_of(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, DESC["vocab_size"], n) for n in lengths]


# -- ops against their references ---------------------------------------------

def test_rms_norm_and_gated_ffn_match_the_reference():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.standard_normal((7, 64)), jnp.float32)
    scale = jnp.asarray(1 + 0.1 * rng.standard_normal(64), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((64, 48)) / 8, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((48, 64)) / 7, jnp.float32)
    assert rel(decoder_ops.rms_norm(x, scale, 1e-5),
               ref.rms_norm(x, scale, 1e-5)) < 1e-6
    assert rel(decoder_ops.gated_ffn(x, wg, wu, wd),
               ref.swiglu(x, wg, wu, wd)) < 1e-5
    # statistics in float32 whatever the activations' dtype
    xb = x.astype(jnp.bfloat16)
    assert decoder_ops.rms_norm(xb, scale, 1e-5).dtype == jnp.bfloat16
    assert rel(decoder_ops.rms_norm(xb, scale, 1e-5),
               ref.rms_norm(xb.astype(jnp.float32), scale, 1e-5)) < 5e-3


def test_rope_is_the_references():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.standard_normal((9, 4, 8)), jnp.float32)
    pos = jnp.asarray([0, 1, 5, 17, 40, 41, 100, 1000, 1535])
    assert rel(decoder_ops.rope_rotate_half(x, pos, 1e6),
               ref.rope(x, pos, 1e6)) < 1e-5


def _moe_params(rng, E=8, D=64, F=48):
    def mat(*shape):
        return jnp.asarray(rng.standard_normal(shape) * shape[-2] ** -0.5,
                           jnp.float32)

    return {"router": mat(D, E),
            "router_bias": jnp.asarray(rng.uniform(-.01, .01, E),
                                       jnp.float32),
            "gate": mat(E, D, F), "up": mat(E, D, F), "down": mat(E, F, D),
            "shared_gate": mat(D, F), "shared_up": mat(D, F),
            "shared_down": mat(F, D)}


def _moe_op(p, x, valid=None, k=2):
    ins = {"X": [x], "RouterW": [p["router"]],
           "RouterBias": [p["router_bias"]], "ExpertWGate": [p["gate"]],
           "ExpertWUp": [p["up"]], "ExpertWDown": [p["down"]],
           "SharedWGate": [p["shared_gate"]], "SharedWUp": [p["shared_up"]],
           "SharedWDown": [p["shared_down"]]}
    if valid is not None:
        ins["Valid"] = [valid]
    return moe_ops._lower_dropless_moe_ffn(
        None, ins, {"top_k": k, "norm_topk": True, "scale": 1.8})


def test_dropless_experts_match_the_reference():
    rng = np.random.RandomState(2)
    p = _moe_params(rng)
    x = jnp.asarray(rng.standard_normal((37, 64)), jnp.float32)
    d = ref.dims(DESC)
    want, _biased, own = ref.experts_ffn(p, x, d)
    got = _moe_op(p, x)
    assert rel(got["Out"], want) < 1e-5
    assert (np.sort(got["Chosen"], -1) == np.sort(own, -1)).all()
    assert int(got["ExpertTokens"].sum()) == 37 * 2
    assert (np.asarray(got["ExpertTokens"])
            == np.bincount(np.asarray(own).ravel(), minlength=8)).all()


def test_dropless_means_dropless():
    """A batch routed WHOLLY to one pair of experts loses nothing: with a
    capacity every token over it would come back as the shared expert's
    output alone."""
    rng = np.random.RandomState(3)
    p = _moe_params(rng)
    p["router_bias"] = jnp.asarray([5.0, 4.0] + [0.0] * 6, jnp.float32)
    x = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    got = _moe_op(p, x)
    assert np.asarray(got["ExpertTokens"]).tolist() == [64, 64] + [0] * 6
    want, _b, own = ref.experts_ffn(p, x, ref.dims(DESC))
    assert (np.sort(own, -1) == [0, 1]).all()
    assert rel(got["Out"], want) < 1e-5
    shared = ref.swiglu(x, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
    routed = np.asarray(got["Out"]) - np.asarray(shared)
    assert (np.linalg.norm(routed, axis=1) > 1e-3).all()


def test_grouped_matmul_kernel_matches_ragged_dot():
    """The Pallas grouped product (interpret mode here) against
    ``jax.lax.ragged_dot``: groups of every size, empty ones, rows past
    the last group left out of the comparison."""
    from paddle_tpu.kernels import grouped_matmul as gm

    rng = np.random.RandomState(7)
    lhs = jnp.asarray(rng.standard_normal((200, 64)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((8, 64, 48)) / 8, jnp.float32)
    sizes = jnp.asarray([0, 1, 37, 0, 128, 3, 0, 11], jnp.int32)
    want = gm.grouped_matmul_reference(lhs, rhs, sizes)
    got = gm.grouped_matmul(lhs, rhs, sizes, force_pallas=True)
    n = int(sizes.sum())
    assert got.shape == (200, 48) and got.dtype == jnp.float32
    assert rel(got[:n], want[:n]) < 1e-5


def test_tokens_that_do_not_exist_are_neither_computed_nor_counted():
    rng = np.random.RandomState(4)
    p = _moe_params(rng)
    x = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    valid = jnp.asarray([1] * 5 + [0] * 4 + [1] * 3 + [0] * 4)
    got = _moe_op(p, x, valid)
    live = np.asarray(valid) > 0
    assert int(got["ExpertTokens"].sum()) == 2 * live.sum()
    want, _b, _o = ref.experts_ffn(p, x[live], ref.dims(DESC))
    assert rel(np.asarray(got["Out"])[live], want) < 1e-5


# -- the latent kernel --------------------------------------------------------

def _latent_case(dtype, seed=0):
    S, H, C, R, ps, npp = 6, 4, 16, 4, 8, 5
    P = 1 + S * npp
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    q_lat = jax.random.normal(k[0], (S, H, C)).astype(dtype)
    q_rope = jax.random.normal(k[1], (S, H, R)).astype(dtype)
    pool = jax.random.normal(k[2], (P, ps, C + R)).astype(dtype)
    table = 1 + np.random.RandomState(seed).permutation(P - 1).reshape(
        S, npp)
    # ragged, across page edges: empty, one row, a full page, one over...
    lengths = jnp.asarray([0, 1, 8, 9, 23, 40])
    return q_lat, q_rope, pool, jnp.asarray(table), lengths


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_latent_kernel_matches_its_reference(dtype, tol):
    args = _latent_case(dtype)
    want = la.latent_paged_attention_reference(*args, sm_scale=0.25)
    got = la.latent_paged_attention(*args, sm_scale=0.25,
                                    force_pallas=True)   # interpret mode
    assert got.shape == want.shape and got.dtype == dtype
    assert rel(got, want) < tol
    assert not np.asarray(got[0], "float32").any()       # length 0 -> 0


def _poisoned_case(lengths, npp, dtype, ps=8, seed=11):
    """Slots of ``lengths`` over a pool in which ONLY their resident pages
    hold rows: every other page is NaN, and a table's entries past a
    slot's resident pages name pages outside the pool. Returns the case
    and its clean twin (the tail on the trash page, the other pages zero)
    for the reference."""
    S, H, C, R = len(lengths), 3, 16, 4
    rng = np.random.RandomState(seed)
    P = 2 + S * npp                 # page 0 and the last page never resident
    ids = 1 + rng.permutation(P - 2)
    table = np.full((S, npp), P + 7, "int64")
    pool = np.full((P, ps, la.pool_width(C + R)), np.nan, "float32")
    clean = np.zeros_like(pool)
    at = 0
    for slot, n in enumerate(lengths):
        held = min(-(-n // ps), npp)
        table[slot, :held] = ids[at:at + held]
        at += held
    resident = table[table < P]
    clean[resident, :, :C + R] = rng.standard_normal(
        (resident.size, ps, C + R))
    pool[resident] = clean[resident]
    q_lat = jnp.asarray(rng.standard_normal((S, H, C)), dtype)
    q_rope = jnp.asarray(rng.standard_normal((S, H, R)), dtype)
    lengths = jnp.asarray(lengths)
    case = (q_lat, q_rope, jnp.asarray(pool, dtype), jnp.asarray(table),
            lengths)
    twin = (q_lat, q_rope, jnp.asarray(clean, dtype),
            jnp.asarray(np.where(table < P, table, 0)), lengths)
    return case, twin


@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_latent_kernel_reads_resident_pages_only(dtype, tol, group):
    """One grid step a slot, ``group`` pages a step of the walk: a slot's
    last step may be ragged (its rows past the last resident page are
    whatever the buffer held), a slot may be empty between two live ones,
    and nothing but resident pages and resident table entries may be
    read: everything else is NaN or out of range."""
    ps, npp = 8, 2 * group + 1
    lengths = [0, 1, ps, ps + 1, 0, group * ps - 1, group * ps,
               group * ps + 1, npp * ps, 0]
    case, twin = _poisoned_case(lengths, npp, dtype, ps=ps)
    want = la.latent_paged_attention_reference(*twin, sm_scale=0.25)
    got = la._latent_pallas(*case, sm_scale=0.25, interpret=True,
                            group=group)
    assert got.shape == want.shape and got.dtype == dtype
    got = np.asarray(got, "float32")
    assert np.isfinite(got).all()
    assert rel(got, want) < tol
    for slot, n in enumerate(lengths):
        if n:
            assert rel(got[slot], want[slot]) < tol
        else:
            assert not got[slot].any()                   # length 0 -> 0


@pytest.mark.parametrize("group", [1, 2, 4])
def test_a_length_over_the_table_stays_inside_the_row(group):
    """A length above ``pages_per_slot * page_size`` walks the slot's own
    row of the table and no entry of the next slot's, which here names
    pages outside the pool."""
    ps, npp = 8, 3
    lengths = [npp * ps + 5, 0, npp * ps + ps * group, 0]
    case, twin = _poisoned_case(lengths, npp, jnp.float32, ps=ps)
    want = la.latent_paged_attention_reference(*twin, sm_scale=0.25)
    got = np.asarray(la._latent_pallas(*case, sm_scale=0.25,
                                       interpret=True, group=group))
    assert np.isfinite(got).all() and rel(got, want) < 1e-5
    assert not got[1].any() and not got[3].any()


def test_pages_a_step_follow_the_shapes():
    """The wrapper's rule for the pages a step of the walk: one count at
    both served geometries, never more than the table holds, fewer where
    the walk's two halves would not fit the kernel's VMEM."""
    served = la._pages_per_step(128, 640, 2, 32, 12)
    assert served == la._pages_per_step(128, 640, 2, 64, 40) == 8
    assert la._pages_per_step(8, 20, 4, 16, 3) == 3
    assert la._pages_per_step(8, 20, 4, 16, 1) == 1
    assert la._pages_per_step(512, 640, 4, 64, 40) == 4


def test_latent_kernel_is_the_expanded_attention(monkeypatch):
    """Absorbed decode against the reference's EXPANDED attention: the
    last position of a sequence through ``attention`` equals the kernel's
    output (the Pallas kernel, in interpret mode) put through the value
    up-projection."""
    monkeypatch.setattr(la, "latent_paged_attention", functools.partial(
        la.latent_paged_attention, force_pallas=True))
    rng = np.random.RandomState(5)
    d = ref.dims(DESC)
    tree = weights_glm.tree(
        {k: jnp.asarray(v) for k, v in
         lmd.random_parameters(DESC, 1, "float32").items()}, DESC)
    p = tree["layers"][0]
    T = 19
    x = jnp.asarray(rng.standard_normal((T, 64)), jnp.float32)
    want = ref.attention(p, x, d)[-1]
    pos = jnp.arange(T)
    q = decoder_ops.rms_norm(x @ p["q_a"], p["q_norm"], 1e-5) @ p["q_b"]
    out = decoder_ops._lower_latent_rope_rows(
        None, {"Q": [q], "KVA": [x @ p["kv_a"]], "KVNorm": [p["kv_norm"]],
               "Positions": [pos]},
        dict(heads=4, nope_dim=12, rope_dim=4, theta=1e6))
    pool = jnp.zeros((4, 8, 20), jnp.float32)
    pool = la.latent_row_prefill(
        pool, jnp.pad(out["Row"], ((0, 5), (0, 0)))[None],
        jnp.asarray([[1, 2, 3]]), jnp.asarray([T]))
    att = decoder_ops._lower_latent_paged_attention(
        None, {"Q": [out["QOut"][-1:]], "KVB": [p["kv_b"]], "Pool": [pool],
               "PageTable": [jnp.asarray([[1, 2, 3]])],
               "Lengths": [jnp.asarray([T])]},
        {"nope_dim": 12})["Out"]
    assert rel(att[0] @ p["o"], want) < 1e-5


def test_row_writes_land_in_the_slots_pages_and_nowhere_else():
    pool = jnp.zeros((6, 4, 3), jnp.float32)
    table = jnp.asarray([[1, 2], [0, 0], [3, 4]])
    rows = jnp.asarray([[1.] * 3, [2.] * 3, [3.] * 3])
    out = np.asarray(la.latent_row_write(pool, rows, table,
                                         jnp.asarray([5, 2, 9])))
    assert (out[2, 1] == 1).all()        # slot 0, position 5
    assert (out[0, 2] == 2).all()        # an empty slot: the trash page
    assert (out[0, 1] == 3).all()        # past the table: the trash page
    assert out[[1, 3, 4, 5]].sum() == 0 and out[2].sum() == 3
    # prefill: whole pages, the page past the length goes to trash
    rows = jnp.arange(2 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 3)
    out = np.asarray(la.latent_row_prefill(
        pool, rows, jnp.asarray([[1, 2], [3, 3]]), jnp.asarray([6, 3])))
    assert (out[1] == rows[0, :4]).all() and (out[2] == rows[0, 4:]).all()
    assert (out[3] == rows[1, :4]).all() and out[4:].sum() == 0


# -- the session --------------------------------------------------------------

def test_batched_prefill_then_decode_match_the_reference():
    """3 prompts of different lengths in ONE prefill dispatch, then 12
    decoded positions through the session and its pool, against the
    reference's full forward over the same tokens: logits compared."""
    sess, tree = make_session(tap=True)
    lengths = [9, 16, 11]
    prompts = prompts_of(lengths)
    for p in prompts:
        sess.enqueue(p)
    admitted = sess.admit_pending()
    assert len(admitted) == 3 and sess.prefill_dispatches == 1
    assert sess.geometry["prompts_per_dispatch"][16] == 4
    slots = {rid: slot for slot, rid in admitted.items()}
    for _ in range(6):
        assert sess.step() == {}
    feed, first_logits, first_chosen = sess._exe.prefill[0]
    step_logits = np.concatenate(sess._exe.steps)          # [12, S, 1, V]
    for rid, prompt in enumerate(prompts):
        slot, n = slots[rid], len(prompt)
        toks = sess.tokens_of(slot)
        assert len(toks) == 13
        seq = np.concatenate([prompt, toks[:-1]])
        # the program's choice of experts, for the reference to follow
        row = list(feed["slot_idx"]).index(slot)
        out = ref.forward(tree, jnp.asarray(seq), DESC,
                          logits_at=list(range(n - 1, n + 12)))
        got = np.concatenate([first_logits[row], step_logits[:, slot, 0]])
        assert rel(got, out["logits"]) < 2e-5
        assert (got.argmax(-1) == toks).all()
        own = np.stack(out["own"])[:, :n]                  # [layers, n, k]
        mine = first_chosen[:, row * 16:row * 16 + n]
        assert (np.sort(own, -1) == np.sort(mine, -1)).all()
    assert sess.pool_conserved
    assert sess.pages_in_use == sum(-(-(n + 12) // 8)
                                    for n in lengths)


def test_probe_rows_are_the_probed_slots_logits():
    """``probe_rows`` adds the feed ``probe_slots`` and the fetch
    ``probe_logits`` to the step program: the named slots' rows of the
    logits every slot got, from the one executable (what a check of the
    served logits fetches every dispatch)."""
    sess, _tree = make_session(probe_rows=2)
    exe, seen = sess._exe, []

    class Both(object):
        def __getattr__(self, name):
            return getattr(exe, name)

        def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                           **kw):
            out = exe.run_multi_step(
                program, steps, feed=feed, fetch_list=list(fetch_list) + [
                    sess._fetch["probe_logits"], sess._fetch["logits"]],
                **kw)
            seen.append((np.asarray(out[-2]), np.asarray(out[-1])))
            return out[:-2]

    sess._exe = Both()
    for p in prompts_of([5, 20, 9]):
        sess.enqueue(p)
    slots = sorted(sess.admit_pending())
    sess.probe_slots[:] = [slots[2], slots[0]]
    sess.step()
    probe, logits = seen[0]                    # [K, 2, V], [K, S, 1, V]
    assert probe.shape == (2, 2, DESC["vocab_size"])
    assert (probe == logits[:, [slots[2], slots[0]], 0]).all()
    assert np.abs(probe[:, 0] - probe[:, 1]).max() > 1e-3
    # a session built without it has no such feed or fetch
    assert make_session()[0]._fetch["probe_logits"] is None


def test_a_stream_cancelled_mid_flight_returns_its_pages():
    sess, _tree = make_session()
    for p in prompts_of([9, 30, 3, 17]):
        sess.enqueue(p)
    assert len(sess.admit_pending()) == 4 and sess.free_slots == 0
    assert sess.prefill_dispatches == 3          # buckets 8 | 16 | 32 x 2
    sess.step()
    held = sess.pages_in_use
    assert sess.cancel(1) and not sess.cancel(1)
    assert sess.pool_conserved and sess.pages_in_use < held
    assert sess.free_slots == 1 and 1 not in sess.active_slots
    # the freed slot and pages serve the next request; the others decode on
    rid = sess.enqueue(prompts_of([12], seed=9)[0])
    assert list(sess.admit_pending().values()) == [rid]
    done = {}
    while sess.active_slots:
        done.update(sess.pump())
    assert rid in done and len(done[rid]) == 17 and sess.pool_conserved
    assert sess.pages_in_use == 0 and sess.free_slots == 4
    assert (sess.take_result(rid) == done[rid]).all()
    assert sess.take_result(rid) is None


def test_admission_order_and_pool_reservation():
    # room for two worst cases only: the third request waits its turn
    sess, _tree = make_session(num_pages=1 + 2 * 6)
    for p in prompts_of([32, 32, 4]):
        sess.enqueue(p)
    assert sorted(sess.admit_pending().values()) == [0, 1]
    assert sess.pending_requests == [2] and sess.free_slots == 2
    while 2 in sess.pending_requests:
        sess.pump()
    assert sess.pool_conserved


def test_what_the_session_does_not_do_raises_a_clear_error():
    sess, _tree = make_session()
    prompt = prompts_of([6])[0]
    for call in (lambda: sess.enqueue(prompt, prefix_tokens=[4, 5]),
                 lambda: sess.admit_group(prompt, n=2),
                 lambda: sess.admit_beam(prompt),
                 lambda: sess.enqueue(np.zeros(40, "int64")),
                 lambda: sess.enqueue(prompt, src_len=0)):
        with pytest.raises(ServingError):
            call()
    assert not sess.pending_requests and sess.pool_conserved
    with pytest.raises(ValueError, match="multiple of the page size"):
        make_session(prefill_buckets=[12, 32])
    with pytest.raises(NotImplementedError, match="group-limited"):
        lmd.decoder_dims(dict(DESC, n_group=2))


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
        # the closed streams are cancelled on the worker's next pass
        deadline = time.time() + 30
        while sess.active_slots and time.time() < deadline:
            time.sleep(0.02)
        # a beam request is refused on the wire, typed
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

    # the worker's rounds carry the new spans and counters
    names, counts = set(), {}
    for rd in tracing.rounds():
        spans = rd["spans"]
        names |= {s["name"] for s in spans}
        for key in ("prefill_prompts", "prefill_tokens",
                    "prefill_dispatches", "pages_in_use",
                    "expert_max_over_mean"):
            if key in spans[0]:
                counts[key] = counts.get(key, 0) + spans[0][key]
        for s in spans:
            if s["name"] == "prefill.dispatch":
                assert spans[s["parent"]]["name"] == "prefill"
                up = spans[spans[s["parent"]]["parent"]]
                assert up["name"] == "admit"
    assert {"admit", "prefill", "prefill.dispatch", "step",
            "step.dispatch", "cancel", "handoff"} <= names
    assert counts["prefill_prompts"] == 2
    assert counts["prefill_tokens"] == 7 + 21
    assert counts["expert_max_over_mean"] >= counts["prefill_dispatches"]
    tracing.reset()
