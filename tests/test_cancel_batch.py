"""Batched release (``SlotDecodeSession.cancel_many`` and every path that
gives slots up): the slots of ONE release are pointed at the trash page
by ONE table dispatch of a rung's rows, and nothing a caller can see
differs from cancelling the same slots one ``cancel()`` at a time:

* the same free list, group stack and ``_free`` order, the same device
  table, the survivors' tokens bit-identical through further steps, and
  the next admissions in the same slots, groups and pages -- for one
  slot, two, a whole rung and a rung and one;
* a lone cancel runs ``table_prog`` itself; a row of padding writes
  nothing;
* a repoint that fails past the retry budget leaks the pages of every
  slot of its dispatch, closes their books and lets the slots re-admit;
* a beam session releases a lane once, whichever of its slots are named;
* the finishers of one dispatch (``_consume_tokens``, ``_consume_spec``)
  and a rolled-back fork group cost one table dispatch.
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags
from paddle_tpu.executor import global_scope
from paddle_tpu.resilience import chaos
from paddle_tpu.resilience.chaos import ChaosTransientError
from paddle_tpu.serving.generation import SlotDecodeSession

VOCAB, SEQ, D, S = 24, 8, 32, 36
CFG = dict(src_vocab_size=VOCAB, trg_vocab_size=VOCAB, n_layer=2,
           n_head=2, d_inner=64)


@pytest.fixture(scope="module")
def trained():
    """A tiny 2-layer transformer, trained a little so greedy decoding
    has clear winners, and 80 sources of mixed lengths."""
    from paddle_tpu.models import transformer

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 57
    startup.random_seed = 57
    scope = global_scope()
    with fluid.program_guard(main, startup):
        loss, _feeds, _extras = transformer.build(
            dropout=0.0, label_smooth_eps=0.0, max_length=SEQ, d_model=D,
            **CFG)
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(58)
    for _ in range(25):
        src = rng.randint(3, VOCAB, (16, SEQ)).astype("int64")
        trg = np.full_like(src, 1)
        trg[:, 1:] = src[:, :-1]
        exe.run(main, feed={
            "src_word": src, "src_len": np.full((16, 1), SEQ, "int64"),
            "trg_word": trg, "trg_len": np.full((16, 1), SEQ, "int64"),
            "label": src}, fetch_list=[loss])
    src = rng.randint(3, VOCAB, (80, SEQ)).astype("int64")
    src_len = rng.randint(2, SEQ + 1, 80).astype("int64")
    return {"exe": exe, "scope": scope, "src": src, "src_len": src_len}


@pytest.fixture(autouse=True)
def _clean_chaos_and_flags():
    yield
    chaos.disable()
    flags.set_flag("dispatch_retries", 0)


def _paged(trained, **kw):
    """Each session in a child scope of its own: parameters resolve
    through the parent, ``pgd_`` state is the child's."""
    args = dict(num_slots=S, max_length=SEQ, d_model=D, paged=True,
                page_size=4, steps=2, scope=trained["scope"].new_scope())
    args.update(CFG)
    args.update(kw)
    return SlotDecodeSession(trained["exe"], **args)


def _admit(sess, trained, rows):
    rids = [sess.enqueue(trained["src"][i], int(trained["src_len"][i]))
            for i in rows]
    admitted = sess.admit_pending()
    return [slot for rid in rids
            for slot, owner in admitted.items() if owner == rid]


def _books(sess):
    """Everything a later admission is given from, in order."""
    return (list(sess._free), list(sess._free_groups),
            list(sess._pool._free), dict(sess._pool._ref),
            dict(sess._slot_group),
            {s: list(p) for s, p in sess._slot_pages.items()},
            {g: sorted(m) for g, m in sess._group_members.items()},
            sess._reserved_pages, sorted(sess._owner.items()))


def _table(sess):
    return np.asarray(sess._scope.get_value("pgd_table"))


class _Programs(object):
    """Between a session and its executor: the programs ``run`` is given,
    and a fault on chosen ones, raised AFTER the dispatch ran (it may or
    may not have landed, as far as the host can tell)."""

    def __init__(self, exe, fail=()):
        self._exe = exe
        self.ran = []
        self.fail = list(fail)

    def __getattr__(self, name):
        return getattr(self._exe, name)

    def run(self, prog, **kw):
        self.ran.append(prog)
        out = self._exe.run(prog, **kw)
        if any(prog is p for p in self.fail):
            raise ChaosTransientError("chaos: post-dispatch table fault")
        return out


def _finish(sess):
    done = {}
    for _ in range(2 * SEQ):
        if not sess.active_slots:
            break
        done.update(sess.step())
    assert not sess.active_slots
    return done


@pytest.mark.parametrize(
    "n,dispatches", [(1, 1), (2, 1), (32, 1), (33, 2)],
    ids=["one", "two", "whole_rung", "rung_and_one"])
def test_a_batch_releases_what_one_at_a_time_releases(trained, n,
                                                      dispatches):
    batch, twin = _paged(trained), _paged(trained)
    assert batch._rungs == (1, 32)
    for sess in (batch, twin):
        assert _admit(sess, trained, range(S)) == list(range(S))
        assert sess.step() == {}          # pages in use, nothing finished
    order = [int(s) for s in
             np.random.RandomState(n).permutation(S)[:n]]
    before = (batch.release_dispatches, batch.release_rows)
    assert batch.cancel_many(order) == order
    for slot in order:
        assert twin.cancel(slot) is True
    assert (batch.release_dispatches - before[0],
            batch.release_rows - before[1]) == (dispatches, n)
    assert batch.release_pad_rows == {1: 0, 2: 30, 32: 0, 33: 0}[n]
    assert (twin.release_dispatches, twin.release_rows) == (n, n)
    assert _books(batch) == _books(twin)
    assert batch.pool_conserved and twin.pool_conserved
    np.testing.assert_array_equal(_table(batch), _table(twin))
    assert not _table(batch)[order].any()        # the trash page
    assert batch.cancel_many(order) == []        # nothing live: no span
    assert batch.release_dispatches - before[0] == dispatches
    # the next admissions land in the same slots, groups and pages
    got = _admit(batch, trained, range(S, S + n))
    assert got == _admit(twin, trained, range(S, S + n))
    assert sorted(got) == sorted(order)
    assert _books(batch) == _books(twin)
    np.testing.assert_array_equal(_table(batch), _table(twin))
    # and every stream, survivor or newcomer, decodes the same tokens
    got, want = _finish(batch), _finish(twin)
    assert sorted(got) == sorted(want) == list(range(S))
    for slot in want:
        np.testing.assert_array_equal(got[slot], want[slot])
    assert _books(batch) == _books(twin)
    assert batch.pool_conserved and batch.pages_in_use == 0


def test_a_lone_cancel_runs_table_prog_itself(trained):
    sess = _paged(trained)
    _admit(sess, trained, range(6))
    sess._exe = seen = _Programs(sess._exe)
    assert sess.cancel(4) is True and sess.cancel(4) is False
    assert seen.ran == [sess._table_prog]
    assert sess._table_progs[1] is sess._table_prog
    del seen.ran[:]
    assert sess.cancel_many([0, 9, 2, 0]) == [0, 2]   # 9 is not live
    assert seen.ran == [sess._table_progs[32]]
    assert (sess.release_dispatches, sess.release_rows,
            sess.release_pad_rows) == (2, 3, 30)
    assert sess.active_slots == [1, 3, 5] and sess.pool_conserved


def test_a_padded_row_writes_nothing(trained):
    sess = _paged(trained)
    _admit(sess, trained, range(S))
    sess.step()
    before = _table(sess).copy()
    assert before.any(axis=1).all()               # every row holds pages
    for rung in sess._rungs[1:]:
        sess._run(sess._table_progs[rung], sess._trash_feed((), rung), [])
    np.testing.assert_array_equal(_table(sess), before)
    assert sess.cancel_many([7, S - 1]) == [7, S - 1]     # 30 rows of padding
    after = _table(sess)
    keep = [s for s in range(S) if s not in (7, S - 1)]
    np.testing.assert_array_equal(after[keep], before[keep])
    assert not after[[7, S - 1]].any()


def test_a_failed_repoint_leaks_every_slot_of_its_dispatch(trained):
    sess = _paged(trained)
    fresh_groups, fresh_free = sess.free_groups, sess.free_slots
    _admit(sess, trained, range(5))
    sess.step()
    pages = {s: list(sess._slot_pages[s]) for s in (3, 0, 4)}
    leaked = set().union(*pages.values())
    exe = sess._exe
    sess._exe = _Programs(exe, fail=sess._table_progs.values())
    assert sess.cancel_many([3, 0, 4]) == [3, 0, 4]    # absorbed, not raised
    sess._exe = exe
    assert sess._leaked_pages == len(leaked)
    assert sess._leaked_page_ids == leaked
    assert sess.release_dispatches == 0          # the one dispatch failed
    # the pages stay allocated, the books close, the slots are free again
    assert all(sess._pool.refcount(pg) == 1 for pg in leaked)
    assert sess.active_slots == [1, 2]
    assert sess.free_slots == fresh_free - 2
    assert sess.free_groups == fresh_groups - 2
    assert sess._reserved_pages == 2 * sess._npp
    assert not {3, 0, 4} & set(sess._slot_pages)
    assert sess.pool_conserved
    # ... and re-admit, in pages that are none of the leaked ones
    assert sorted(_admit(sess, trained, range(5, 8))) == [0, 3, 4]
    for slot in (0, 3, 4):
        assert not leaked & set(sess._slot_pages[slot])
    assert sorted(_finish(sess)) == [0, 1, 2, 3, 4]
    assert sess.pages_in_use == len(leaked) and sess.pool_conserved


def test_a_failed_chunk_leaks_its_own_slots_only(trained):
    """33 slots are a whole rung and a lone one: the rung's dispatch
    fails, the lone slot's (``table_prog``) lands."""
    sess = _paged(trained)
    _admit(sess, trained, range(S))
    order = list(range(33))
    first = set().union(*(sess._slot_pages[s] for s in order[:32]))
    sess._exe = _Programs(sess._exe, fail=[sess._table_progs[32]])
    assert sess.cancel_many(order) == order
    assert sess._leaked_page_ids == first
    assert (sess.release_dispatches, sess.release_rows) == (1, 1)
    assert sess.free_slots == 33 and sess.pool_conserved
    assert sess.pages_in_use == len(first) + sum(
        len(sess._slot_pages[s]) for s in (33, 34, 35))


def test_a_beam_lane_is_released_once(trained):
    K = 4
    sess = _paged(trained, num_slots=8, beam_width=K, steps=1)
    assert sess._rungs == (1, 8) and sess._admit_rungs == ()
    lanes = [sess.admit_beam(trained["src"][i], SEQ) for i in range(2)]
    rids = [sess.register_beam_owner(lane) for lane in lanes]
    for _ in range(3):
        sess.step()
    assert sess.active_beams == lanes
    a, b = (sess.beam_slots(lane) for lane in lanes)
    # two slots of one lane and one of the other: each lane once, all
    # eight rows in one dispatch
    assert sess.cancel_many([a[2], b[1], a[0]]) == [a[2], b[1]]
    assert (sess.release_dispatches, sess.release_rows,
            sess.release_pad_rows) == (1, 2 * K, 0)
    assert not sess.active_beams and sess.free_beams == 2
    assert sess.free_slots == 8 and not sess._beam_owner
    assert sess.pool_conserved and sess.pages_in_use == 0
    assert [sess.take_beam_result(rid) for rid in rids] == [None, None]
    # a lone cancel of a hypothesis: the lane's K rows, one dispatch
    lane = sess.admit_beam(trained["src"][2], SEQ)
    sess.step()
    assert sess.cancel(sess.beam_slots(lane)[3]) is True
    assert (sess.release_dispatches, sess.release_rows,
            sess.release_pad_rows) == (2, 3 * K, K)
    assert sess.cancel(sess._beam_width * lane) is False
    # a beam that runs to its end gives its lane up in one dispatch too
    tokens, _scores = sess.generate_beam(trained["src"][3], SEQ)
    assert tokens.shape == (K, SEQ)
    assert (sess.release_dispatches, sess.release_rows) == (3, 4 * K)
    assert sess.pool_conserved and sess.pages_in_use == 0


def test_the_finishers_of_one_dispatch_cost_one_table_dispatch(trained):
    sess = _paged(trained)
    slots = _admit(sess, trained, range(9))
    most = 0
    while sess.active_slots:
        before = (sess.release_dispatches, sess.release_rows)
        done = sess.step()
        assert sess.release_rows - before[1] == len(done)
        assert sess.release_dispatches - before[0] == (1 if done else 0)
        most = max(most, len(done))
    assert most > 1, "no dispatch finished two slots: the test shows nothing"
    assert sess.free_slots == S and sorted(sess._free[-9:]) == slots
    assert sess.pool_conserved and sess.pages_in_use == 0


def test_a_verify_dispatch_s_finishers_cost_one_table_dispatch(trained):
    sess = _paged(trained, num_slots=3, steps=1,
                  speculative={"k": 3, "drafter": "ngram"})
    assert sess._rungs == (1, 3)
    for i in range(3):
        sess.admit(trained["src"][i], int(trained["src_len"][i]))
    sess.step()
    # one verify dispatch's commits, by hand: slots 2 and 0 end (eos is
    # the second and the first committed token), slot 1 goes on
    tok_seq = np.full((3, sess._spec_nodes), 5, "int64")
    tok_seq[2, 1] = tok_seq[0, 0] = sess._eos
    done = sess._consume_spec(tok_seq, np.asarray([1, 1, 2]))
    assert sorted(done) == [0, 2] and sess.active_slots == [1]
    assert (sess.release_dispatches, sess.release_rows,
            sess.release_pad_rows) == (1, 2, 1)
    assert sess.pool_conserved
    assert sorted(sess._slot_pages) == [1]
    sess.cancel(1)
    assert sess.pages_in_use == 0 and sess.free_slots == 3


def test_a_rolled_back_fork_group_is_repointed_by_one_dispatch(trained):
    sess = _paged(trained, num_slots=8)
    fresh = _books(sess)
    chaos.configure("io@site=serve.admit,n=1")
    sess._exe = seen = _Programs(sess._exe)
    with pytest.raises(IOError):
        sess.admit_group(trained["src"][0], n=3, src_len=SEQ)
    chaos.disable()
    # the fault fires before the group's first dispatch: one member holds
    # pages, and its row goes back through table_prog
    assert seen.ran == [sess._table_prog]
    assert _books(sess) == fresh and sess.pool_conserved
    # a fault at the first join: both members' rows, one dispatch
    del seen.ran[:]
    seen.fail = [sess._join_prog]
    with pytest.raises(ChaosTransientError):
        sess.admit_group(trained["src"][0], n=3, src_len=SEQ)
    assert seen.ran[-1] is sess._table_progs[8]
    assert sum(p in sess._table_progs.values() for p in seen.ran) == 1
    # slots, groups and the reservation as they were; the members' pages
    # went back member by member, as they always have
    books = _books(sess)
    assert books[:2] == fresh[:2] and books[3:] == fresh[3:]
    assert sorted(books[2]) == sorted(fresh[2])
    assert sess.pool_conserved and sess._leaked_pages == 0


# -- a released slot is dead to the decode step --------------------------------

class _LiveTap(object):
    """Between a session and its executor: every decode dispatch also
    fetches the liveness the step program hands its cross-attention
    kernel (``[steps, S, 1]``, a row a token step)."""

    def __init__(self, exe):
        self._exe, self.live = exe, []

    def __getattr__(self, name):
        return getattr(self._exe, name)

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, **kw):
        names = {op.input("Live")[0] for op in program.global_block().ops
                 if op.type == "grouped_cross_attention"}
        (name,) = names   # every layer is handed the one vector
        out = self._exe.run_multi_step(
            program, steps, feed=feed, fetch_list=list(fetch_list) + [name],
            scope=scope, **kw)
        self.live.append(np.asarray(out[-1])[..., 0])
        return out[:-1]


def _traced_step(sess):
    """One ``step()`` inside a round of its own: (what it returned, the
    round's counts)."""
    from paddle_tpu.observability import tracing

    tracing.enable(True)
    try:
        rd = tracing.round_begin()
        try:
            done = sess.step()
        finally:
            tracing.round_end(rd, keep=False)
    finally:
        tracing.enable(False)
    return done, rd.spans[0]


def test_cancelled_slots_are_dead_in_the_next_step(trained):
    """After ``cancel_many`` the step program reads the cancelled slots
    as dead (their ``pgd_done`` is still 0: the table row says it), the
    host's mirror counts the same, nobody else's tokens move, and a
    re-admitted slot is live again at once."""
    n, calls = 10, CFG["n_layer"] * 1
    sess, twin = (_paged(trained, steps=1) for _ in range(2))
    for s in (sess, twin):
        assert _admit(s, trained, range(n)) == list(range(n))
        assert s.step() == {}
    sess._exe = tap = _LiveTap(sess._exe)
    _done, root = _traced_step(sess)
    # every admitted slot live, the never-occupied ones dead: a copy a
    # live slot (a group each), a grid step a slot, layer by layer
    np.testing.assert_array_equal(tap.live[-1][0], np.arange(S) < n)
    assert (root["cross_blocks_read"], root["cross_blocks_grid"]) \
        == (calls * n, calls * S)
    twin.step()
    gone = [2, 5, 7]
    assert sess.cancel_many(gone) == gone
    done_flags = np.asarray(sess._scope.get_value("pgd_done"))[:, 0]
    assert not done_flags[gone].any()   # cancelled, not done: the row
    assert not _table(sess)[gone].any()
    got, root = _traced_step(sess)
    want = twin.step()
    keep = [s for s in range(n) if s not in gone]
    np.testing.assert_array_equal(tap.live[-1][0],
                                  np.isin(np.arange(S), keep))
    assert (root["cross_blocks_read"], root["cross_blocks_grid"]) \
        == (calls * len(keep), calls * S)
    assert sorted(got) == sorted(s for s in want if s not in gone)
    # a cancelled slot's next occupant is live in the very next step
    back = _admit(sess, trained, range(n, n + 2))
    assert back == gone[:2]
    _traced_step(sess)
    np.testing.assert_array_equal(
        tap.live[-1][0], np.isin(np.arange(S), keep + back))
    # the survivors' streams are those of the run nobody was cancelled in
    got, want = _finish(sess), _finish(twin)
    for slot in keep:
        np.testing.assert_array_equal(got[slot], want[slot])
    assert sess.pool_conserved and sess.pages_in_use == 0


def test_a_finished_slot_is_dead_within_its_dispatch(trained):
    """Two token steps a dispatch: a stream that ends at the first is
    dead at the second (``pgd_done``), and after its release by the
    table row too."""
    sess = _paged(trained)
    _admit(sess, trained, range(6))
    sess._exe = tap = _LiveTap(sess._exe)
    was_live = set(sess.active_slots)
    while sess.active_slots:
        sess.step()
        first, second = tap.live[-1]
        assert set(np.flatnonzero(first)) == was_live
        assert set(np.flatnonzero(second)) <= was_live
        was_live = set(sess.active_slots)
        # whoever is still decoding was live at both steps
        assert was_live <= set(np.flatnonzero(second))
