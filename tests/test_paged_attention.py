"""Ragged paged-attention decode tests: kernel parity vs the composed
reference at ragged / non-page-multiple lengths, empty-slot safety,
O(page) pool writes, grid accounting proportional to RESIDENT pages,
and the paged SlotDecodeSession — staggered-admission greedy tokens
bit-identical to the dense slot decoder, page recycling across
release/readmit, pool-exhaustion admission control, seeded-sampler
replay determinism, and a zero-fresh-compile warm re-run of the
multi-token decode dispatch."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags
from paddle_tpu.core import exec_cache
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.serving.generation import (
    NoFreePageError,
    NoFreeSlotError,
    Sampler,
    SlotDecodeSession,
)

VOCAB, SEQ, D = 24, 8, 32
CFG = dict(src_vocab_size=VOCAB, trg_vocab_size=VOCAB, n_layer=1,
           n_head=2, d_inner=64)


# -- kernel ------------------------------------------------------------------

def _pools(rng, S, H, dh, ps, npp, lengths, tail="last"):
    """Random pools of whole token rows ``[P, ps, H * dh]`` + a ragged
    table: page 0 reserved (trash), each slot's tail aliased to its last
    valid page (``tail="trash"``: left on page 0)."""
    P = 1 + S * npp
    kp = rng.randn(P, ps, H * dh).astype("float32")
    vp = rng.randn(P, ps, H * dh).astype("float32")
    table = np.zeros((S, npp), np.int32)
    nxt = 1
    for s in range(S):
        n = pa.pages_for(lengths[s], ps)
        for p in range(n):
            table[s, p] = nxt
            nxt += 1
        if tail == "last":
            for p in range(n, npp):
                table[s, p] = table[s, max(n - 1, 0)]
    return kp, vp, table


def _per_head(pool, H):
    """The same pool as the old per-head layout ``[P, H, ps, dh]``."""
    P, ps, width = pool.shape
    return np.ascontiguousarray(
        pool.reshape(P, ps, H, width // H).transpose(0, 2, 1, 3))


def _oracle_per_head(q, kp_old, vp_old, table, lengths):
    """Plain numpy attention over per-head ``[P, H, ps, dh]`` pools: a
    slot's keys are its pages' rows in table order, cut at its length;
    an empty slot is 0."""
    S, H, dh = q.shape
    out = np.zeros((S, H, dh), "float64")
    for s in range(S):
        n = int(lengths[s])
        if n == 0:
            continue
        for h in range(H):
            k = np.concatenate([kp_old[p, h] for p in table[s]])[:n]
            v = np.concatenate([vp_old[p, h] for p in table[s]])[:n]
            sc = k.astype("float64") @ q[s, h].astype("float64") * dh ** -0.5
            w = np.exp(sc - sc.max())
            out[s, h] = (w / w.sum()) @ v.astype("float64")
    return out


def test_kernel_parity_ragged_non_multiple_lengths():
    """interpret-mode Pallas kernel == composed reference at per-slot
    lengths that are ragged AND off the page grid (including a full
    slot and a single-token slot)."""
    import jax.numpy as jnp

    S, H, dh, ps, npp = 5, 2, 16, 4, 8
    lengths = np.array([7, 1, 32, 13, 30], np.int32)
    rng = np.random.RandomState(3)
    q = rng.randn(S, H, dh).astype("float32")
    kp, vp, table = _pools(rng, S, H, dh, ps, npp, lengths)
    ref = pa.paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), jnp.asarray(lengths))
    ker = pa.paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), jnp.asarray(lengths), force_pallas=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


# heads x head width x page size x pages a slot, and the lengths of the
# slots: ragged and off the page grid, a full slot, one token, an empty
# slot first / in the middle / last, a length on a page boundary
@pytest.mark.parametrize("tail", ["last", "trash"])
@pytest.mark.parametrize("H,dh,ps,npp,lengths", [
    (2, 16, 4, 8, [7, 1, 32, 13, 30]),
    (8, 64, 16, 4, [0, 17, 64, 16, 0, 33]),
    (4, 128, 8, 3, [24, 8, 0, 9]),
    (16, 8, 8, 2, [3, 0]),
    (1, 32, 16, 2, [0, 31, 32]),
], ids=["2x16", "served_8x64", "dh_128", "16_heads", "one_head"])
def test_kernel_reference_and_per_head_oracle_agree(H, dh, ps, npp,
                                                    lengths, tail):
    """Three readings of one random pool: the interpret-mode kernel and
    the composed reference on whole token rows, and a numpy oracle on the
    SAME pool transposed to the old per-head pages. The table's tail is
    aliased to the slot's last page (what the session does) or left on
    the trash page; an empty slot is exactly 0 in all three."""
    import jax.numpy as jnp

    S = len(lengths)
    lengths = np.asarray(lengths, np.int32)
    rng = np.random.RandomState(H * 131 + dh)
    q = rng.randn(S, H, dh).astype("float32")
    kp, vp, table = _pools(rng, S, H, dh, ps, npp, lengths, tail=tail)
    want = _oracle_per_head(q, _per_head(kp, H), _per_head(vp, H), table,
                            lengths)
    args = [jnp.asarray(x) for x in (q, kp, vp, table, lengths)]
    ref = np.asarray(pa.paged_attention(*args, force_reference=True))
    ker = np.asarray(pa.paged_attention(*args, force_pallas=True))
    np.testing.assert_allclose(ref, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(ker, want, rtol=2e-5, atol=2e-6)
    for s in np.flatnonzero(lengths == 0):
        assert np.abs(ref[s]).max() == 0.0 and np.abs(ker[s]).max() == 0.0


def test_kernel_skips_pages_past_the_length():
    """A page past the slot's length is never computed on: NaN there
    (the trash page the table's tail sits on) changes nothing."""
    import jax.numpy as jnp

    S, H, dh, ps, npp = 2, 2, 8, 4, 3
    lengths = np.array([5, 4], np.int32)
    rng = np.random.RandomState(8)
    q = rng.randn(S, H, dh).astype("float32")
    kp, vp, table = _pools(rng, S, H, dh, ps, npp, lengths, tail="trash")
    want = np.asarray(pa.paged_attention(
        *[jnp.asarray(x) for x in (q, kp, vp, table, lengths)],
        force_pallas=True))
    kp[0] = vp[0] = np.nan
    got = np.asarray(pa.paged_attention(
        *[jnp.asarray(x) for x in (q, kp, vp, table, lengths)],
        force_pallas=True))
    np.testing.assert_array_equal(got, want)


# the slot walk's edges at pages of 4: the lengths of a call's slots, and
# what the table holds past a slot's resident pages
_WALK_LENGTHS = {
    "empty_first_and_last": [0, 9, 0],
    "one_token": [1, 1],
    "exactly_one_page": [4, 4, 4],
    "one_past_a_page": [5, 9, 13],
    "full_slots": [24, 24],
    "all_empty": [0, 0, 0, 0],
    "one_live_of_many": [0, 0, 0, 0, 0, 17, 0, 0],
    "every_slot_live": [3, 4, 5, 8, 12, 24, 1, 7],
    "runs_of_empty_between_live": [6, 0, 0, 11, 0, 2, 0, 0, 24],
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("lengths", list(_WALK_LENGTHS.values()),
                         ids=list(_WALK_LENGTHS))
def test_slot_walk_reads_only_resident_pages(lengths, dtype):
    """The kernel walks ``ceil(length / page_size)`` pages a slot inside
    its body: it agrees with the composed reference at every edge of the
    walk (no page, one row, a page boundary and one past it, a full slot,
    live slots next to each other and apart), an empty slot is exactly 0,
    and a table whose entries past a slot's resident pages are POISON (an
    id outside the pool) gives the same bits: nothing past a slot's
    resident pages is used (that no copy is even started for one is the
    next test's count)."""
    import jax.numpy as jnp

    S, H, dh, ps, npp = len(lengths), 4, 16, 4, 6
    lengths = np.asarray(lengths, np.int32)
    rng = np.random.RandomState(int(lengths.sum()) + S)
    kp, vp, table = _pools(rng, S, H, dh, ps, npp, lengths)
    q, kp, vp = (jnp.asarray(x, dtype) for x in
                 (rng.randn(S, H, dh), kp, vp))
    ref = np.asarray(pa.paged_attention_reference(
        q, kp, vp, jnp.asarray(table), jnp.asarray(lengths)), "float32")
    ker = np.asarray(pa.paged_attention(
        q, kp, vp, jnp.asarray(table), jnp.asarray(lengths),
        force_pallas=True), "float32")
    tol = 2e-6 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(ker, ref, rtol=tol, atol=tol)
    assert np.abs(ker[lengths == 0]).max(initial=0.0) == 0.0
    poisoned = table.copy()
    for s in range(S):
        poisoned[s, pa.pages_for(lengths[s], ps):] = 2 ** 30
    got = np.asarray(pa.paged_attention(
        q, kp, vp, jnp.asarray(poisoned), jnp.asarray(lengths),
        force_pallas=True), "float32")
    np.testing.assert_array_equal(got, ker)


def test_slot_walk_stops_at_the_end_of_a_table_row():
    """A length past what a table row covers reads the row's pages and
    no entry past them: the same bits as the full slot, and the
    reference's (it sees every position of the row as valid)."""
    import jax.numpy as jnp

    S, H, dh, ps, npp = 3, 2, 16, 4, 6
    full = np.array([npp * ps, 3, npp * ps], np.int32)
    rng = np.random.RandomState(6)
    kp, vp, table = _pools(rng, S, H, dh, ps, npp, full)
    args = [jnp.asarray(x) for x in
            (rng.randn(S, H, dh).astype("float32"), kp, vp, table)]
    want = np.asarray(pa.paged_attention(
        *args, jnp.asarray(full), force_pallas=True))
    past = jnp.asarray(np.array([npp * ps + 3, 3, 40], np.int32))
    got = np.asarray(pa.paged_attention(*args, past, force_pallas=True))
    np.testing.assert_array_equal(got, want)
    ref = np.asarray(pa.paged_attention_reference(*args, past))
    np.testing.assert_allclose(got, ref, rtol=2e-6, atol=2e-6)


def test_slot_walk_starts_two_copies_a_resident_page(monkeypatch):
    """Every copy the kernel starts is counted (interpret mode runs the
    body as JAX, so a callback beside ``start`` sees each one): a K and a
    V page a RESIDENT page, what ``grid_accounting`` calls
    ``page_walks``, whatever the slots before and after hold; none at
    all for a call of empty slots."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    started = []
    real = pltpu.make_async_copy

    class Counted(object):
        def __init__(self, copy):
            self.copy = copy

        def start(self):
            jax.debug.callback(lambda: started.append(1))
            self.copy.start()

        def wait(self):
            self.copy.wait()

    monkeypatch.setattr(pltpu, "make_async_copy",
                        lambda *a, **kw: Counted(real(*a, **kw)))
    S, H, dh, ps, npp = 9, 2, 16, 4, 6
    rng = np.random.RandomState(11)
    for lengths in ([6, 0, 0, 11, 0, 2, 0, 0, 24], [0] * S,
                    [3, 4, 5, 8, 12, 24, 1, 7, 9]):
        lengths = np.asarray(lengths, np.int32)
        kp, vp, table = _pools(rng, S, H, dh, ps, npp, lengths)
        del started[:]
        out = pa.paged_attention(
            *[jnp.asarray(x) for x in (rng.randn(S, H, dh).astype("float32"),
                                       kp, vp, table, lengths)],
            force_pallas=True)
        jax.block_until_ready(out)
        jax.effects_barrier()
        acct = pa.grid_accounting(lengths, ps, H, dh, npp * ps)
        assert len(started) == 2 * acct["page_walks"], (lengths, len(started))


def test_a_per_head_pool_is_refused_by_name():
    """The old ``[P, H, ps, dh]`` pool does not pass for whole token
    rows: a ValueError that says which shape a pool has."""
    import jax.numpy as jnp

    q = jnp.zeros((2, 2, 8), jnp.float32)
    pool = jnp.zeros((5, 2, 4, 8), jnp.float32)
    table = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="page_size, H \\* dh"):
        pa.paged_attention(q, pool, pool, table, jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError, match="page_size, H \\* dh"):
        pa.paged_tree_attention(
            jnp.zeros((2, 2, 3, 8), jnp.float32), pool, pool, table,
            jnp.zeros((2,), jnp.int32), jnp.ones((2, 3, 3), jnp.int32))


def test_kernel_empty_slots_are_zero_not_nan():
    """A slot with NO resident tokens returns exactly 0 from both
    paths — softmax over an all-masked row is never NaN bait (the
    flash kernel's fully-masked-row contract extended to decode)."""
    import jax.numpy as jnp

    S, H, dh, ps, npp = 3, 2, 8, 4, 2
    lengths = np.array([0, 5, 0], np.int32)
    rng = np.random.RandomState(4)
    q = rng.randn(S, H, dh).astype("float32")
    kp, vp, table = _pools(rng, S, H, dh, ps, npp, lengths)
    for force in ("pallas", "reference"):
        out = np.asarray(pa.paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(lengths),
            force_pallas=force == "pallas",
            force_reference=force == "reference"))
        assert np.isfinite(out).all()
        assert np.abs(out[0]).max() == 0.0 and np.abs(out[2]).max() == 0.0
        assert np.abs(out[1]).max() > 0.0


def test_paged_kv_write_lands_in_page_and_trash_is_isolated():
    """The O(page) write puts each slot's row at
    (table[s, pos//ps], pos%ps) and leaves every other bit of the pool
    untouched; slots parked on the trash page can never corrupt a live
    slot's page."""
    import jax.numpy as jnp

    S, H, dh, ps, npp = 3, 2, 4, 4, 2
    lengths = np.array([6, 3, 0], np.int32)
    rng = np.random.RandomState(5)
    kp, vp, table = _pools(rng, S, H, dh, ps, npp, lengths)
    knew = rng.randn(S, H, dh).astype("float32")
    vnew = rng.randn(S, H, dh).astype("float32")
    # slots 0/1 write at their current length; slot 2 is unoccupied and
    # parked on the trash page (row 0)
    pos = np.array([5, 2, 0], np.int32)
    k2, v2 = pa.paged_kv_write(
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(knew),
        jnp.asarray(vnew), jnp.asarray(table), jnp.asarray(pos))
    k2, v2 = np.asarray(k2), np.asarray(v2)
    for s, p in ((0, 5), (1, 2)):
        page, off = table[s, p // ps], p % ps
        np.testing.assert_array_equal(
            k2[page, off].reshape(H, dh), knew[s])
        np.testing.assert_array_equal(
            v2[page, off].reshape(H, dh), vnew[s])
    # everything else bit-identical (trash page 0 excepted)
    mask = np.ones_like(kp, bool)
    mask[0] = False
    for s, p in ((0, 5), (1, 2)):
        mask[table[s, p // ps], p % ps] = False
    np.testing.assert_array_equal(k2[mask], kp[mask])
    np.testing.assert_array_equal(v2[mask], vp[mask])


# write positions of the two live slots: the first and the last row of a
# page, the first row of a slot's next page, the last row a slot can hold
@pytest.mark.parametrize("pos", [(0, 3), (4, 7), (3, 4), (7, 0)],
                         ids=["0_3", "4_7", "3_4", "7_0"])
def test_paged_kv_write_at_page_boundaries_matches_per_head(pos):
    """A row written at a page boundary lands where the old per-head
    write put it (``pool[page, :, off, :]`` of the transposed pool), and
    a slot parked on the trash page writes only there."""
    import jax.numpy as jnp

    S, H, dh, ps, npp = 3, 2, 4, 4, 2
    lengths = np.array([8, 8, 0], np.int32)
    rng = np.random.RandomState(6)
    kp, vp, table = _pools(rng, S, H, dh, ps, npp, lengths)
    knew = rng.randn(S, H, dh).astype("float32")
    vnew = rng.randn(S, H, dh).astype("float32")
    pos = np.array(pos + (5,), np.int32)  # slot 2: any position, on trash
    k2, v2 = pa.paged_kv_write(
        *[jnp.asarray(x) for x in (kp, vp, knew, vnew, table, pos)])
    want_k, want_v = _per_head(kp, H), _per_head(vp, H)
    for s in range(S):
        page, off = table[s, pos[s] // ps], pos[s] % ps
        want_k[page, :, off, :] = knew[s]
        want_v[page, :, off, :] = vnew[s]
    assert table[2, pos[2] // ps] == 0
    np.testing.assert_array_equal(_per_head(np.asarray(k2), H), want_k)
    np.testing.assert_array_equal(_per_head(np.asarray(v2), H), want_v)


def test_prefill_and_copy_page_ops_write_whole_token_rows():
    """``paged_kv_prefill`` lands a prefix's ``[1, H, T, dh]`` rows as
    whole token rows (positions below ``write_from`` and the pad tail on
    the trash page), and ``paged_copy_page`` duplicates one page."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops

    H, dh, ps, npp, T = 2, 4, 4, 2, 8
    rng = np.random.RandomState(7)
    kp, vp, _ = _pools(rng, 1, H, dh, ps, npp, [T])
    knew = rng.randn(1, H, T, dh).astype("float32")
    vnew = rng.randn(1, H, T, dh).astype("float32")
    row = np.array([2, 1], np.int32)
    out = attention_ops._lower_paged_kv_prefill(None, {
        "KPool": [jnp.asarray(kp)], "VPool": [jnp.asarray(vp)],
        "KNew": [jnp.asarray(knew)], "VNew": [jnp.asarray(vnew)],
        "PageRow": [jnp.asarray(row)], "WriteFrom": [jnp.asarray([2])],
        "Len": [jnp.asarray([7])]}, {})
    k2, v2 = np.asarray(out["KOut"]), np.asarray(out["VOut"])
    want_k, want_v = kp.copy(), vp.copy()
    for t in range(2, 6):  # write_from <= t < len - 1
        want_k[row[t // ps], t % ps] = knew[0, :, t].reshape(-1)
        want_v[row[t // ps], t % ps] = vnew[0, :, t].reshape(-1)
    np.testing.assert_array_equal(k2[1:], want_k[1:])
    np.testing.assert_array_equal(v2[1:], want_v[1:])
    out = attention_ops._lower_paged_copy_page(None, {
        "KPool": [jnp.asarray(k2)], "VPool": [jnp.asarray(v2)],
        "Src": [jnp.asarray([2])], "Dst": [jnp.asarray([1])]}, {})
    k3 = np.asarray(out["KOut"])
    np.testing.assert_array_equal(k3[1], k2[2])
    np.testing.assert_array_equal(k3[2:], k2[2:])
    np.testing.assert_array_equal(np.asarray(out["VOut"])[1], v2[2])


# copy-on-write windows ``[(slot, src, dst)]`` over 4 slots of 2 pages (9
# pages: page 0 the trash page); ``(slot, 0, 0)`` is a rebind or padding
_COW_WINDOWS = {
    "one_pair": [(1, 3, 7)],
    "several_real_pairs": [(0, 1, 7), (2, 5, 8), (3, 6, 2), (1, 4, 3)],
    "padded_with_trash_self_copies":
        [(2, 5, 8), (0, 1, 7)] + [(2, 0, 0)] * 6,
    "a_slot_repeated_with_its_final_row":
        [(1, 3, 7), (1, 4, 8), (3, 0, 0), (1, 0, 0)],
    # a source shared by three pairs (N sharers, N - 1 copies) and a
    # source that is the trash page's neighbour in the feed's padding
    "a_source_copied_three_times":
        [(0, 5, 2), (1, 5, 7), (3, 5, 8), (2, 0, 0)],
}


def _cow_window_case(case):
    """Pools and a table of 4 slots, the window's feeds, and what a plain
    loop over its pairs leaves: every copy, then every slot's final row."""
    H, dh, ps, npp, S = 2, 4, 4, 2, 4
    rng = np.random.RandomState(11)
    kp, vp, table = _pools(rng, S, H, dh, ps, npp, [8] * S)
    window = _COW_WINDOWS[case]
    final_row = {slot: rng.randint(1, kp.shape[0], npp)
                 for slot, _src, _dst in window}
    feed = {
        "src_pages": np.asarray([w[1] for w in window], "int64"),
        "dst_pages": np.asarray([w[2] for w in window], "int64"),
        "slot_idxs": np.asarray([w[0] for w in window], "int64"),
        "page_rows": np.stack([final_row[w[0]] for w in window]
                              ).astype("int64"),
    }
    want_k, want_v, want_table = kp.copy(), vp.copy(), table.copy()
    for slot, src, dst in window:
        want_k[dst] = want_k[src]
        want_v[dst] = want_v[src]
    for slot, row in final_row.items():
        want_table[slot] = row
    return (H, dh, ps, npp, S), (kp, vp, table), feed, \
        (want_k, want_v, want_table)


@pytest.mark.parametrize("case", sorted(_COW_WINDOWS))
def test_copy_page_op_copies_a_window_in_order(case):
    """``paged_copy_page`` with ``Src`` / ``Dst`` of a whole window (no
    destination another pair's source or destination) equals a plain
    NumPy loop over the pairs, bit for bit; one pair is the case
    ``n = 1``."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops

    _geom, (kp, vp, _table), feed, (want_k, want_v, _t) = \
        _cow_window_case(case)
    out = attention_ops._lower_paged_copy_page(None, {
        "KPool": [jnp.asarray(kp)], "VPool": [jnp.asarray(vp)],
        "Src": [jnp.asarray(feed["src_pages"])],
        "Dst": [jnp.asarray(feed["dst_pages"])]}, {})
    np.testing.assert_array_equal(np.asarray(out["KOut"]), want_k)
    np.testing.assert_array_equal(np.asarray(out["VOut"]), want_v)


@pytest.mark.parametrize("case", sorted(_COW_WINDOWS))
def test_cow_batch_program_equals_a_loop_over_its_pairs(case):
    """``build_cow_batch_prog``'s program, run through the ``Executor`` on
    a scope that holds the pools and the table: every layer's pools and
    the table equal the plain loop's, bit for bit (all copies, then each
    slot's final row, a repeated slot's written more than once)."""
    import jax.numpy as jnp
    from paddle_tpu.models import transformer

    (H, dh, ps, npp, S), (kp, vp, table), feed, want = \
        _cow_window_case(case)
    L = 2
    prog = transformer.build_cow_batch_prog(
        S, npp * ps, L, H, H * dh, ps, kp.shape[0],
        len(feed["src_pages"]))
    scope = fluid.Scope()
    for i in range(L):
        scope.var("pgd_kpool_%d" % i).set(jnp.asarray(kp + i))
        scope.var("pgd_vpool_%d" % i).set(jnp.asarray(vp - i))
    scope.var("pgd_table").set(jnp.asarray(table))
    fluid.Executor(fluid.CPUPlace()).run(prog, feed=feed, fetch_list=[],
                                         scope=scope)
    want_k, want_v, want_table = want
    for i in range(L):
        # adding a constant commutes with copying whole pages
        np.testing.assert_array_equal(
            np.asarray(scope.find_var("pgd_kpool_%d" % i).get_tensor()),
            want_k + i)
        np.testing.assert_array_equal(
            np.asarray(scope.find_var("pgd_vpool_%d" % i).get_tensor()),
            want_v - i)
    np.testing.assert_array_equal(
        np.asarray(scope.find_var("pgd_table").get_tensor()), want_table)


def test_cow_batch_program_holds_the_same_operators_at_every_rung():
    """The program takes its window whole: a copy a layer and the row
    write, whatever the rung (it held 22 operators a pair unrolled)."""
    from paddle_tpu.models import transformer

    def ops(pairs):
        prog = transformer.build_cow_batch_prog(
            256, 256, 6, 8, 512, 16, 4097, pairs)
        return [op.type for op in prog.global_block().ops]

    assert ops(64) == ops(512) == ["paged_copy_page"] * 6 + ["scatter"]
    assert len(ops(64)) < 32
    assert ops(1) == ["paged_copy_page"] * 6 + ["dynamic_update_slice"]


@pytest.mark.parametrize("seed", range(4))
def test_copy_page_op_equals_the_loop_on_any_window_a_session_plans(seed):
    """The op gathers every source and then scatters: drawn windows that
    keep the session's rule (destinations fresh pages: distinct, none a
    source, none the trash page; sources shared freely; any number of
    trash self-copies anywhere) equal the loop over their pairs."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.serving.generation import _check_cow_window

    rng = np.random.RandomState(100 + seed)
    P, ps, width = 40, 4, 8
    kp = rng.randn(P, ps, width).astype("float32")
    vp = rng.randn(P, ps, width).astype("float32")
    for _ in range(8):
        pages = rng.permutation(np.arange(1, P))
        n_real = rng.randint(1, 12)
        dsts = pages[:n_real]
        srcs = rng.choice(pages[n_real:], n_real)   # repeats allowed
        pairs = list(zip(srcs, dsts)) + [(0, 0)] * rng.randint(0, 6)
        pairs = [pairs[i] for i in rng.permutation(len(pairs))]
        _check_cow_window([(0, s, d) for s, d in pairs if (s, d) != (0, 0)])
        want_k, want_v = kp.copy(), vp.copy()
        for s, d in pairs:
            want_k[d] = want_k[s]
            want_v[d] = want_v[s]
        out = attention_ops._lower_paged_copy_page(None, {
            "KPool": [jnp.asarray(kp)], "VPool": [jnp.asarray(vp)],
            "Src": [jnp.asarray([s for s, _d in pairs])],
            "Dst": [jnp.asarray([d for _s, d in pairs])]}, {})
        np.testing.assert_array_equal(np.asarray(out["KOut"]), want_k)
        np.testing.assert_array_equal(np.asarray(out["VOut"]), want_v)
        kp, vp = want_k, want_v


@pytest.mark.parametrize("copies", [
    [(0, 1, 7), (2, 7, 8)],      # a chain: a destination is a later source
    [(0, 7, 3), (2, 1, 7)],      # ... or an earlier one
    [(0, 1, 7), (2, 3, 7)],      # a destination written twice
    [(0, 1, 0)],                 # the trash page as a real destination
], ids=["chain", "source_overwritten", "repeated_destination",
        "trash_destination"])
def test_a_window_the_copy_program_cannot_take_in_one_pass_is_refused(
        copies):
    """What makes gather-then-scatter equal to copying in order is held
    where windows are dispatched: a window that breaks it raises before
    the program runs (the session treats that as a failed dispatch)."""
    from paddle_tpu.serving.generation import _check_cow_window

    with pytest.raises(RuntimeError, match="copy-on-write window"):
        _check_cow_window(copies)
    _check_cow_window([(0, 1, 7), (2, 1, 8), (3, 4, 5)])


def test_grid_accounting_scales_with_resident_pages():
    """The kernel's modeled HBM traffic follows pages actually
    RESIDENT, not S x max_length: half the resident tokens ~ half the
    KV bytes, and a low-occupancy pool moves a small fraction of the
    dense layout's traffic."""
    H, dh, ps, T = 2, 16, 4, 64
    lengths = [3, 17, 0, 0, 0, 0, 0, 0]
    acc = pa.grid_accounting(lengths, ps, H, dh, T)
    assert acc["valid_pages"] == pa.pages_for(3, ps) + pa.pages_for(17, ps)
    # the kernel's grid is a step a slot, its walk a turn a resident page
    assert acc["grid_steps"] == len(lengths)
    assert acc["page_walks"] == acc["valid_pages"] == 6
    assert acc["total_page_slots"] == 8 * 16
    # raggedness: 6 pages of 128 page-slots -> far under the dense bytes
    assert acc["hbm_bytes"] < 0.1 * acc["dense_hbm_bytes"]
    # proportionality in the KV term: doubling resident pages doubles
    # the page traffic exactly
    acc2 = pa.grid_accounting([3, 17, 3, 17, 0, 0, 0, 0], ps, H, dh, T)
    page_bytes = acc["page_bytes"]
    assert (acc2["hbm_bytes"] - acc2["valid_pages"] * 2 * page_bytes
            == acc["hbm_bytes"] - acc["valid_pages"] * 2 * page_bytes)
    assert acc2["valid_pages"] == 2 * acc["valid_pages"]
    # dense bytes are occupancy-blind — identical for both loads
    assert acc2["dense_hbm_bytes"] == acc["dense_hbm_bytes"]


# -- session -----------------------------------------------------------------

@pytest.fixture(scope="module")
def trained(request):
    """One tiny trained transformer shared by every session test; the
    greedy oracle is the PR 8 dense slot decoder."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 21
    startup.random_seed = 21
    from paddle_tpu.executor import global_scope
    from paddle_tpu.models import transformer

    # conftest swaps the global scope per test: capture THIS scope so
    # every test binds the same trained parameters through scope=...
    scope = global_scope()
    with fluid.program_guard(main, startup):
        loss, feeds, extras = transformer.build(
            dropout=0.0, label_smooth_eps=0.0, max_length=SEQ,
            d_model=D, **CFG)
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(22)
    for _ in range(30):
        src = rng.randint(3, VOCAB, (16, SEQ)).astype("int64")
        trg = np.full_like(src, 1)
        trg[:, 1:] = src[:, :-1]
        exe.run(main, feed={
            "src_word": src,
            "src_len": np.full((16, 1), SEQ, "int64"),
            "trg_word": trg,
            "trg_len": np.full((16, 1), SEQ, "int64"),
            "label": src,
        }, fetch_list=[loss])
    src = rng.randint(3, VOCAB, (5, SEQ)).astype("int64")
    src_len = np.asarray([[SEQ], [SEQ - 3], [SEQ - 1], [2], [SEQ]],
                         "int64")
    dense = SlotDecodeSession(exe, num_slots=3, max_length=SEQ,
                              d_model=D, scope=scope, **CFG)
    want = dense.generate(src, src_len)
    return {"exe": exe, "scope": scope, "src": src, "src_len": src_len,
            "want": want}


def _paged_session(trained, **kw):
    args = dict(num_slots=3, max_length=SEQ, d_model=D, paged=True,
                page_size=4, scope=trained["scope"])
    args.update(CFG)
    args.update(kw)
    return SlotDecodeSession(trained["exe"], **args)


def test_staggered_admissions_bit_identical_to_dense_decoder(trained):
    """The ORACLE: greedy tokens from the paged session under
    staggered mid-flight admissions are bit-identical to the PR 8
    dense slot decoder's."""
    sess = _paged_session(trained, steps=1)
    src, src_len, want = (trained["src"], trained["src_len"],
                          trained["want"])
    got = np.zeros_like(want)
    owner = {sess.admit(src[i], src_len[i]): i for i in range(3)}
    with pytest.raises(NoFreeSlotError):
        sess.admit(src[3], src_len[3])
    pending = [3, 4]
    steps = 0
    while owner or pending:
        while pending and sess.free_slots:
            i = pending.pop(0)
            owner[sess.admit(src[i], src_len[i])] = i
        for slot, tokens in sess.step().items():
            got[owner.pop(slot)] = tokens
        steps += 1
        assert steps < 100
    np.testing.assert_array_equal(got, want)
    assert sess.pages_in_use == 0  # everything recycled
    # and the scrape says so: the pool's gauge back at 0, a decode rate
    from paddle_tpu.observability import REGISTRY

    text = REGISTRY.to_prometheus()
    assert "paddle_tpu_serving_kv_pages_in_use 0" in text
    assert "paddle_tpu_serving_decode_tokens_per_sec" in text


def test_multi_token_dispatch_matches_and_reruns_warm(trained):
    """steps=K on-device scans produce the same tokens as per-token
    stepping, and a SECOND full batch through the warm session adds
    ZERO fresh compiles — the decode hot path is one cached multi-step
    executable plus the admit/table executables."""
    sess = _paged_session(trained, steps=4)
    got = sess.generate(trained["src"], trained["src_len"])
    np.testing.assert_array_equal(got, trained["want"])
    before = exec_cache.stats()["fresh_compiles"]
    again = sess.generate(trained["src"], trained["src_len"])
    np.testing.assert_array_equal(again, trained["want"])
    assert exec_cache.stats()["fresh_compiles"] == before, (
        "warm paged decode paid fresh compiles")


def test_pallas_kernel_in_session_matches_reference_impl(trained):
    """The whole session runs through the interpret-mode Pallas kernel
    (FLAGS_paged_attention=pallas) and produces the same greedy tokens
    as the composed-reference impl."""
    old = flags.get("paged_attention")
    flags.set_flag("paged_attention", "pallas")
    try:
        sess = _paged_session(trained, steps=2)
        got = sess.generate(trained["src"][:3], trained["src_len"][:3])
    finally:
        flags.set_flag("paged_attention", old)
    np.testing.assert_array_equal(got, trained["want"][:3])


def test_page_recycling_across_release_and_readmit(trained):
    """A pool sized for exactly the slot count keeps serving arbitrary
    request streams: completed sequences' pages are recycled into later
    admissions (B > slots > pages-at-once), and the free list returns
    to full when the pool drains."""
    sess = _paged_session(trained, steps=2,
                          num_pages=1 + 3 * pa.pages_for(SEQ, 4))
    total = sess.free_pages
    src = np.concatenate([trained["src"], trained["src"]], axis=0)
    src_len = np.concatenate([trained["src_len"], trained["src_len"]],
                             axis=0)
    want = np.concatenate([trained["want"], trained["want"]], axis=0)
    got = sess.generate(src, src_len)
    np.testing.assert_array_equal(got, want)
    assert sess.free_pages == total and sess.pages_in_use == 0


def test_pool_exhaustion_is_a_typed_admission_reject(trained):
    """An undersized pool rejects the admission whose WORST-CASE pages
    cannot be reserved (NoFreePageError), rolls the slot back, never
    wedges mid-flight (admitted sequences always provision), and the
    reservation is released on completion so a retry then succeeds."""
    # worst case is 2 pages per sequence; the pool holds exactly 2
    # allocatable — one sequence at a time, by reservation
    sess = _paged_session(trained, steps=1, num_pages=3)
    slot = sess.admit(trained["src"][0], trained["src_len"][0])
    free_before = sess.free_slots
    with pytest.raises(NoFreePageError):
        sess.admit(trained["src"][1], trained["src_len"][1])
    assert sess.free_slots == free_before  # rollback: slot not leaked
    out = {}
    while not out:
        out = sess.step()  # mid-flight provisioning must never raise
    np.testing.assert_array_equal(out[slot], trained["want"][0])
    assert sess.free_pages == 2  # pages recycled on completion
    # the reservation went with them: admission works again, and the
    # retried sequence decodes correctly through recycled pages
    slot2 = sess.admit(trained["src"][1], trained["src_len"][1])
    out = {}
    while not out:
        out = sess.step()
    np.testing.assert_array_equal(out[slot2], trained["want"][1])


def test_seeded_sampler_replay_is_bit_identical(trained):
    """Stochastic sampling (temperature / top-k) is deterministic
    under a fixed seed: a rebuilt session replays the exact token
    matrix, dispatch granularity notwithstanding (PRNG keys are
    (seed, slot, position), never the dispatch key)."""
    mk = lambda steps, strategy: _paged_session(
        trained, steps=steps,
        sampler=Sampler(strategy=strategy, temperature=0.8, top_k=3,
                        seed=11))
    a = mk(1, "top_k").generate(trained["src"], trained["src_len"])
    b = mk(4, "top_k").generate(trained["src"], trained["src_len"])
    np.testing.assert_array_equal(a, b)
    c = mk(4, "temperature").generate(trained["src"], trained["src_len"])
    d = mk(2, "temperature").generate(trained["src"], trained["src_len"])
    np.testing.assert_array_equal(c, d)
    # sampling actually happened (greedy and sampled streams differ)
    assert not np.array_equal(a, trained["want"]) or \
        not np.array_equal(c, trained["want"])
    # bos leads and every row terminates in the eos pad
    assert (a[:, 0] == 1).all()


def test_dense_fallback_fetches_token_ids_not_logits(trained):
    """Satellite: even the dense (reference-layout) session's step
    fetch is the [S, 1] device-selected token ids — the [S, 1, V]
    logits never cross the host boundary."""
    sess = SlotDecodeSession(trained["exe"], num_slots=2,
                             max_length=SEQ, d_model=D,
                             scope=trained["scope"], **CFG)
    sess.admit(trained["src"][0], trained["src_len"][0])
    fetched = sess._run(sess._step_prog, {
        "cur_tok": np.full((2, 1), 2, "int64"),
        "pe_row": np.zeros((2, 1, D), "float32"),
        "gen_pos": np.zeros((2, 1), "int64"),
    }, [sess._fetch_name])[0]
    assert np.asarray(fetched).shape == (2, 1)  # ids, not [S, 1, VOCAB]
    assert np.issubdtype(np.asarray(fetched).dtype, np.integer)
