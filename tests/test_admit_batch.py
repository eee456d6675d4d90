"""Batched admission (``SlotDecodeSession.admit_pending``): the head run
of the queue goes through ONE encoder dispatch of a rung's rows, and
nothing a caller can see differs from admitting the same requests one
``admit()`` at a time:

* the same slots, groups and pages, the same device state, the same
  logits (the paged tests' tolerance) and the same greedy tokens, for a
  run of one, a run too short to be worth its rung's padding, a padded
  run, a whole rung and a run split above the top rung;
* an all-padding call (the ladder's warm-up) changes no ``pgd_`` array;
* a forced prefix in the queue splits the run and keeps the order; a
  pool or group reject defers to the front;
* a ``serve.admit`` fault under retry lands the batch in the same slots
  and pages; past the budget the batch is rolled back whole and the
  queue behind it is intact;
* a snapshot at the quiesce point after a batch holds every request,
  owned or pending.
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags
from paddle_tpu.executor import global_scope
from paddle_tpu.resilience import chaos
from paddle_tpu.serving.generation import SlotDecodeSession
from paddle_tpu.serving.snapshot import DecodeSnapshotManager

VOCAB, SEQ, D, S = 24, 8, 32, 36
CFG = dict(src_vocab_size=VOCAB, trg_vocab_size=VOCAB, n_layer=2,
           n_head=2, d_inner=64)
STATE = ("pgd_group_of", "pgd_table", "pgd_tok", "pgd_pos", "pgd_done",
         "pgd_src_mask")
CROSS = tuple("pgd_%scross_%d" % (kv, i) for kv in "kv" for i in range(2))


@pytest.fixture(scope="module")
def trained():
    """A tiny 2-layer transformer, trained a little so greedy decoding
    has clear winners, and 40 sources of mixed lengths."""
    from paddle_tpu.models import transformer

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 53
    startup.random_seed = 53
    scope = global_scope()
    with fluid.program_guard(main, startup):
        loss, _feeds, _extras = transformer.build(
            dropout=0.0, label_smooth_eps=0.0, max_length=SEQ, d_model=D,
            **CFG)
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(54)
    for _ in range(25):
        src = rng.randint(3, VOCAB, (16, SEQ)).astype("int64")
        trg = np.full_like(src, 1)
        trg[:, 1:] = src[:, :-1]
        exe.run(main, feed={
            "src_word": src, "src_len": np.full((16, 1), SEQ, "int64"),
            "trg_word": trg, "trg_len": np.full((16, 1), SEQ, "int64"),
            "label": src}, fetch_list=[loss])
    src = rng.randint(3, VOCAB, (40, SEQ)).astype("int64")
    src_len = rng.randint(2, SEQ + 1, 40).astype("int64")
    return {"exe": exe, "scope": scope, "src": src, "src_len": src_len}


@pytest.fixture(autouse=True)
def _clean_chaos_and_flags():
    yield
    chaos.disable()
    flags.set_flag("dispatch_retries", 0)


class _Tap(object):
    """Between a session and its executor: the decode dispatch also
    fetches the output projection's logits (the sampler op's input)."""

    def __init__(self, exe, step_program):
        self._exe = exe
        self.logits = []
        (self._name,) = [
            n for op in step_program.global_block().ops
            if op.type == "slot_decode_sample"
            for n in op.input_arg_names() if "proj_logits" in n]

    def __getattr__(self, name):
        return getattr(self._exe, name)

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, **kw):
        out = self._exe.run_multi_step(
            program, steps, feed=feed,
            fetch_list=list(fetch_list) + [self._name], scope=scope, **kw)
        self.logits.append(np.asarray(out[-1]))
        return out[:-1]


def _paged(trained, **kw):
    """Each session in a child scope of its own: parameters resolve
    through the parent, ``pgd_`` state is the child's."""
    args = dict(num_slots=S, max_length=SEQ, d_model=D, paged=True,
                page_size=4, steps=2, scope=trained["scope"].new_scope())
    args.update(CFG)
    args.update(kw)
    return SlotDecodeSession(trained["exe"], **args)


def _queue(sess, trained, rows, prefix_at=()):
    return [sess.enqueue(trained["src"][i], int(trained["src_len"][i]),
                         prefix_tokens=([5, 6] if i in prefix_at else None))
            for i in rows]


def _one_at_a_time(sess, trained, rows, prefix_at=()):
    return [sess.admit(trained["src"][i], int(trained["src_len"][i]),
                       prefix_tokens=([5, 6] if i in prefix_at else None))
            for i in rows]


def _books(sess):
    return (dict(sess._slot_group), {s: list(p) for s, p in
                                     sess._slot_pages.items()},
            sess._reserved_pages, sess.free_slots, sess.free_groups,
            sess.free_pages)


def _value(sess, name):
    return np.asarray(sess._scope.get_value(name))


def _assert_same_device_state(got, want, slots):
    for name in STATE:
        np.testing.assert_array_equal(_value(got, name),
                                      _value(want, name), err_msg=name)
    groups = [got._slot_group[s] for s in slots]
    for name in CROSS:
        np.testing.assert_allclose(
            _value(got, name)[groups], _value(want, name)[groups],
            rtol=2e-5, atol=2e-6, err_msg=name)


def _decode(sess, slots):
    """Step until every slot of ``slots`` has finished: its tokens and
    the logits of every dispatch."""
    sess._exe = tap = _Tap(sess._exe, sess.step_program)
    done = {}
    for _ in range(SEQ):
        done.update(sess.step())
        if set(slots) <= set(done):
            break
    return [done[s] for s in slots], tap.logits


# a run that would leave its rung less than an eighth full goes one at a
# time through rung 1: the 3, and the 2 left of the 34 after a whole rung
@pytest.mark.parametrize(
    "n,dispatches", [(1, 1), (3, 3), (5, 1), (32, 1), (34, 3), (36, 2)],
    ids=["one", "short_run", "padded", "whole_rung", "split_short", "split"])
def test_a_batch_admits_what_one_at_a_time_admits(trained, n, dispatches):
    batch, twin = _paged(trained), _paged(trained)
    assert batch._admit_rungs == (1, 32)
    rids = _queue(batch, trained, range(n))
    admitted = batch.admit_pending()
    slots = _one_at_a_time(twin, trained, range(n))
    assert [admitted[s] for s in slots] == rids      # order, slot by slot
    assert (batch.admit_dispatches, batch.admit_rows) == (dispatches, n)
    assert (twin.admit_dispatches, twin.admit_rows) == (n, n)
    assert _books(batch) == _books(twin)
    assert not batch.pending_requests
    _assert_same_device_state(batch, twin, slots)
    got_tokens, got_logits = _decode(batch, slots)
    want_tokens, want_logits = _decode(twin, slots)
    np.testing.assert_array_equal(got_tokens, want_tokens)
    assert len(got_logits) == len(want_logits)
    for got, want in zip(got_logits, want_logits):
        np.testing.assert_allclose(got[:, slots], want[:, slots],
                                   rtol=2e-5, atol=2e-6)
    assert batch.pool_conserved and batch.pages_in_use == 0


def test_an_all_padding_call_changes_no_state(trained):
    sess = _paged(trained)
    _queue(sess, trained, range(5))
    sess.admit_pending()
    sess.step()
    names = STATE + CROSS + ("pgd_kpool_0", "pgd_vpool_1")
    before = {n: _value(sess, n).tobytes() for n in names}
    for rung in sess._admit_rungs[1:]:
        sess._run(sess._admit_progs[rung], sess._admit_feed((), rung), [])
    for name in names:
        assert _value(sess, name).tobytes() == before[name], name


def test_a_forced_prefix_splits_the_run_and_keeps_the_order(trained):
    batch, twin = _paged(trained), _paged(trained)
    rids = _queue(batch, trained, range(11), prefix_at=(5,))
    admitted = batch.admit_pending()
    slots = _one_at_a_time(twin, trained, range(11), prefix_at=(5,))
    assert slots == list(range(11))
    assert [admitted[s] for s in slots] == rids
    # five plain requests, the forced prefix through admit(), five more
    assert (batch.admit_dispatches, batch.admit_rows) == (3, 11)
    assert _books(batch) == _books(twin)
    _assert_same_device_state(batch, twin, slots)
    got, _ = _decode(batch, slots)
    want, _ = _decode(twin, slots)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kw,fits", [
    (dict(num_pages=1 + 3 * 2), 3), (dict(num_groups=2), 2)],
    ids=["no_free_page", "no_free_group"])
def test_a_capacity_reject_defers_to_the_front(trained, kw, fits):
    sess = _paged(trained, num_slots=8, **kw)
    rids = _queue(sess, trained, range(5))
    admitted = sess.admit_pending()
    assert sorted(admitted) == list(range(fits))
    assert [admitted[s] for s in range(fits)] == rids[:fits]
    assert sess.pending_requests == rids[fits:]
    assert sess.admit_dispatches == 1
    assert sess.admit_pending() == {}               # still no room
    assert sess.pending_requests == rids[fits:]
    # the backlog drains in order as the first ones finish
    order = []
    for _ in range(4 * SEQ):
        order += list(sess.pump())
        if len(order) == 5:
            break
    assert sorted(order) == rids and sess.pool_conserved
    assert sess.pages_in_use == 0


def test_a_fault_under_retry_lands_in_the_same_slots_and_pages(trained):
    clean, sess = _paged(trained), _paged(trained)
    _queue(clean, trained, range(5))
    clean.admit_pending()
    chaos.configure("seed=3;io@site=serve.admit,n=1")
    flags.set_flag("dispatch_retries", 2)
    rids = _queue(sess, trained, range(5))
    admitted = sess.admit_pending()
    assert chaos.fires("serve.admit") == 1, "the fault never fired"
    assert [admitted[s] for s in range(5)] == rids
    assert _books(sess) == _books(clean)
    assert sess.pool_conserved and sess._leaked_pages == 0
    assert sess.admit_dispatches == 1       # the failed attempt ran none
    got, _ = _decode(sess, list(range(5)))
    want, _ = _decode(clean, list(range(5)))
    np.testing.assert_array_equal(got, want)


def test_past_the_budget_the_batch_is_rolled_back_whole(trained):
    sess = _paged(trained)
    fresh = _books(sess)
    rids = _queue(sess, trained, range(7), prefix_at=(5,))
    chaos.configure("io@site=serve.admit,n=1")
    with pytest.raises(IOError):
        sess.admit_pending()
    chaos.disable()
    # the five of the failed batch are gone, the rest is untouched
    assert sess.pending_requests == rids[5:]
    assert not sess._owner and not sess.active_slots
    assert _books(sess) == fresh and sess.pool_conserved
    assert sess.admit_dispatches == 0
    admitted = sess.admit_pending()
    assert [admitted[s] for s in (0, 1)] == rids[5:]


def test_a_snapshot_after_a_batch_holds_every_request(trained, tmp_path):
    sess = _paged(trained, num_slots=4)
    rids = _queue(sess, trained, range(6))
    seen = []
    sess._after_dispatch = lambda: seen.append(
        sorted(sess.pending_requests + list(sess._owner.values())))
    sess.admit_pending()
    sess._after_dispatch = None
    # one quiesce point, after the whole batch: nothing in neither view
    assert seen == [rids]
    mgr = DecodeSnapshotManager(sess, str(tmp_path / "snap"))
    mgr.save()
    mgr.close(save=False)
    restored = _paged(trained, num_slots=4)
    assert DecodeSnapshotManager(
        restored, str(tmp_path / "snap")).restore() is not None
    assert sorted(restored._owner.values()) == rids[:4]
    assert restored.pending_requests == rids[4:]
    done, want = {}, {}
    for _ in range(4 * SEQ):
        done.update(restored.pump())
        want.update(sess.pump())
        if len(done) == 6:
            break
    for rid in rids:
        np.testing.assert_array_equal(done[rid], want[rid])
