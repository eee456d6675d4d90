"""The decode step's cross-attention kernel
(kernels/cross_attention_decode.py), interpreted on the CPU, against
``flash_attention_reference`` over explicitly gathered rows; its grid
accounting against a hand count; and a 2-layer ``SlotDecodeSession``
decoding the same requests with the kernel and with the reference.

The compile for the chip itself is tests/test_tpu_lowering.py's."""

import itertools

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import flags
from paddle_tpu.kernels import cross_attention_decode as cad
from paddle_tpu.kernels.flash_attention import flash_attention_reference
from paddle_tpu.kernels.paged_attention import KernelCompileError

import test_kv_reuse as kv
from test_kv_reuse import trained  # noqa: F401  (the module's fixture)


def _case(rng, S, G, H, N, T, dh, lengths, group_of):
    q = jnp.asarray(rng.randn(S, H, N, dh), jnp.float32)
    k = jnp.asarray(rng.randn(G, H, T, dh), jnp.float32)
    v = jnp.asarray(rng.randn(G, H, T, dh), jnp.float32)
    lengths = np.asarray(lengths)
    mask = (np.arange(T)[None, :] < lengths[:, None]).astype("float32")
    return q, k, v, jnp.asarray(group_of, jnp.int32), jnp.asarray(mask)


def _gathered_reference(q, k, v, gof, mask):
    m = np.asarray(mask)[np.asarray(gof)][:, None, None, :] > 0
    return flash_attention_reference(
        q, k[gof], v[gof], mask=jnp.asarray(m))


# (head width, source length, block): 64 is the served width, which the
# chip keeps with the source axis on the lanes (blocks of whole 128-lane
# tiles); at 128 the pools are row-major (blocks of whole sublane tiles)
_LAYOUTS = [(64, 256, 128), (128, 32, 8)]


@pytest.mark.parametrize("dh,T,block", _LAYOUTS,
                         ids=["source_on_lanes", "row_major"])
@pytest.mark.parametrize("H", [8, 16])
@pytest.mark.parametrize("N", [1, 4])
def test_kernel_matches_reference_over_gathered_rows(
        monkeypatch, N, H, dh, T, block):
    """Lengths 1 (an unadmitted row holds one valid key), a block edge,
    a block edge + 1 and the whole source; three slots on one group; a
    slot whose group has NO valid key returns exactly 0."""
    lane = cad.lanes_hold_source(dh)
    per_pos = 4 * H * 4 * (dh if lane else 128)
    monkeypatch.setattr(cad, "VMEM_BUDGET_BYTES", block * per_pos)
    assert cad.source_block(H, T, dh) == block
    lengths = [1, block, block + 1, T, 0]
    group_of = [3, 0, 2, 2, 1, 2, 4]
    rng = np.random.RandomState(H * 7 + N)
    q, k, v, gof, mask = _case(rng, len(group_of), len(lengths), H, N, T,
                               dh, lengths, group_of)
    got = np.asarray(cad.grouped_cross_attention(
        q, k, v, gof, mask, force_pallas=True))
    want = np.asarray(_gathered_reference(q, k, v, gof, mask))
    assert got.shape == (len(group_of), H, N, dh)
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=2e-5, atol=2e-5)
    assert np.all(got[-1] == 0.0)
    # [S, 1] group ids, as the decode programs hold them
    again = cad.grouped_cross_attention(
        q, k, v, gof[:, None], mask, force_pallas=True)
    np.testing.assert_array_equal(np.asarray(again), got)


def test_reference_route_is_the_composed_path():
    rng = np.random.RandomState(3)
    q, k, v, gof, mask = _case(rng, 3, 2, 2, 1, 8, 16, [8, 3], [1, 0, 1])
    got = cad.grouped_cross_attention(q, k, v, gof, mask,
                                      force_reference=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_gathered_reference(q, k, v, gof, mask)))
    # off the TPU and unforced, the reference is what runs
    np.testing.assert_array_equal(
        np.asarray(cad.grouped_cross_attention(q, k, v, gof, mask)),
        np.asarray(got))


@pytest.mark.parametrize("H,T,dh,want", [
    (8, 256, 64, 256),    # transformer_base: the whole row, 2 MiB of VMEM
    (16, 256, 64, 256),   # transformer_big's heads: 4 MiB, the budget
    (32, 256, 64, 128),   # twice that: half rows
    (8, 1024, 128, 256),  # row-major pools: [H, 256, 128] blocks
    (2, 8, 16, 8),        # the tests' tiny session: the whole axis
    (8, 200, 64, 200),    # no 128-lane divisor: the whole axis
])
def test_source_block_fits_the_budget(H, T, dh, want):
    assert cad.source_block(H, T, dh) == want


def test_a_refused_shape_raises_kernel_compile_error(monkeypatch):
    monkeypatch.setattr(cad, "VMEM_BUDGET_BYTES", 1024)
    rng = np.random.RandomState(5)
    q, k, v, gof, mask = _case(rng, 2, 2, 8, 1, 256, 64, [4, 9], [0, 1])
    with pytest.raises(KernelCompileError) as err:
        cad.grouped_cross_attention(q, k, v, gof, mask, force_pallas=True)
    assert err.value.kernel == cad.CROSS_DECODE_KERNEL_NAME
    assert ((2, 8, 1, 64), "float32") in err.value.shapes
    # the reference does not stand in: it runs only when asked for
    cad.grouped_cross_attention(q, k, v, gof, mask, force_reference=True)


def test_the_kernels_name_is_the_flash_forwards_stem_and_not_the_paged():
    """perfbench finds the kernel in a trace by the flash forward's name
    and a ``[S, H, N, dh]`` result; the paged kernel's reader sums every
    operation that holds ITS name."""
    assert "flash_attention_fwd" in cad.CROSS_DECODE_KERNEL_NAME
    assert "paged_decode_attention" not in cad.CROSS_DECODE_KERNEL_NAME


def test_grid_accounting_against_a_hand_count(monkeypatch):
    H, T, dh = 8, 256, 64
    monkeypatch.setattr(cad, "VMEM_BUDGET_BYTES", 128 * 4 * H * 4 * dh)
    # groups: 0 = 130 keys (two blocks), 1 = 5 keys, 2 = none, 3 = 256
    lengths = [130, 5, 0, 256]
    # slots 1 and 2 share group 1 back to back: one copy between them
    group_of = [0, 1, 1, 2, 3, 0]
    acct = cad.grid_accounting(group_of, lengths, H, T, dh)
    block_bytes = 2 * H * 128 * dh * 4
    # steps (group, block index): (0,0) (0,1) | (1,0) (1,0) | (1,0) (1,0)
    # | held (1,0) (1,0) | (3,0) (3,1) | (0,0) (0,1): a copy where it
    # changes, and the slot of the empty group 2 holds what slot 2 read
    assert acct["block"] == 128 and acct["grid_steps"] == 12
    assert acct["blocks_read"] == 2 + 1 + 0 + 0 + 2 + 2
    # compute skipped: second block of groups 1, 1, and both of group 2
    assert acct["blocks_skipped"] == 1 + 1 + 2
    qo = 2 * 6 * H * dh * 4
    assert acct["hbm_bytes"] == 7 * block_bytes + qo
    # whole rows: a step a slot, a copy a slot but where a group repeats
    # and where a slot has no key
    monkeypatch.setattr(cad, "VMEM_BUDGET_BYTES", 4 << 20)
    whole = cad.grid_accounting(group_of, lengths, H, T, dh, n_rows=4)
    assert (whole["block"], whole["grid_steps"], whole["blocks_read"],
            whole["blocks_skipped"]) == (256, 6, 4, 1)
    assert whole["hbm_bytes"] == 4 * 2 * block_bytes + 4 * qo


def test_grid_accounting_with_liveness_against_a_hand_count(monkeypatch):
    """Blocks read = the runs of distinct (group, block) the LIVE slots
    walk: a dead slot, wherever it stands and whatever its stale group,
    holds the index of the step before it."""
    H, T, dh = 8, 256, 64
    monkeypatch.setattr(cad, "VMEM_BUDGET_BYTES", 128 * 4 * H * 4 * dh)
    lengths = [130, 5, 256, 200]           # blocks: 2, 1, 2, 2
    group_of = [2, 0, 1, 1, 3, 1, 0, 2]
    live = [0, 1, 1, 0, 0, 1, 0, 1]
    acct = cad.grid_accounting(group_of, lengths, H, T, dh, live=live)
    # slot 0 (dead, ahead of every live one) holds slot 1's first block
    # (0,0), the copy slot 1 needs; then (0,0) (0,1) | (1,0) (1,0) |
    # held | held | (1,0) (1,0): the member of group 1 behind two dead
    # slots still shares slot 2's copy | held | (2,0) (2,1)
    assert acct["grid_steps"] == 16
    assert acct["blocks_read"] == 0 + 2 + 1 + 0 + 0 + 0 + 0 + 2
    assert acct["blocks_skipped"] == 2 + 0 + 1 + 2 + 2 + 1 + 2 + 0
    # nobody live: the pipeline's first step copies one block, no more
    none = cad.grid_accounting(group_of, lengths, H, T, dh, live=[0] * 8)
    assert (none["blocks_read"], none["blocks_skipped"]) == (1, 16)
    # everybody live is the count without the input
    assert cad.grid_accounting(
        group_of, lengths, H, T, dh, live=[1] * 8) == cad.grid_accounting(
        group_of, lengths, H, T, dh)


def _grid_indices(gof, slot_len, block, n_blocks):
    """The K/V block index of every grid step, in the grid's order, from
    the functions the kernel's index map is made of."""
    gof = jnp.asarray(gof, jnp.int32)
    slot_len = jnp.asarray(slot_len, jnp.int32)
    group, lo, hi = cad.steer_dead_slots(gof, slot_len, block)
    return [(int(group[s]), int(cad.kv_block_index(s, j, lo, hi)))
            for s in range(len(gof)) for j in range(n_blocks)]


# 16 slots over 8 groups: members of one group stand side by side (2 and
# 3, 9 to 11) and apart (group 6: slots 5 and 13)
_GROUP_OF = [0, 1, 2, 2, 3, 6, 4, 5, 7, 1, 1, 1, 3, 6, 0, 5]
_LIVE = {0: [], 1: [10], 8: [1, 2, 3, 6, 9, 10, 13, 15],
         16: list(range(16))}


@pytest.mark.parametrize("dh,T,block", _LAYOUTS,
                         ids=["source_on_lanes", "row_major"])
@pytest.mark.parametrize("N", [1, 5])
@pytest.mark.parametrize("n_live", sorted(_LIVE))
def test_dead_slots_read_nothing_and_live_slots_read_as_before(
        monkeypatch, n_live, N, dh, T, block):
    """With the step's liveness a live slot's rows are bit-equal to the
    op without it, a dead slot's are exactly 0, every dead slot's K/V
    index is the index of the grid step before it (no copy), side by
    side members of a group still share one, and ``grid_accounting``
    counts the copies the index map makes."""
    H = 2
    lane = cad.lanes_hold_source(dh)
    monkeypatch.setattr(cad, "VMEM_BUDGET_BYTES",
                        block * 4 * H * 4 * (dh if lane else 128))
    lengths = [1, block, block + 1, T, 3, T - 1, 2 * block, 7]
    S, n_blocks = len(_GROUP_OF), T // block
    rng = np.random.RandomState(100 * n_live + N)
    q, k, v, gof, mask = _case(rng, S, len(lengths), H, N, T, dh, lengths,
                               _GROUP_OF)
    live = np.zeros((S, 1), "int64")
    live[_LIVE[n_live]] = 1
    got = np.asarray(cad.grouped_cross_attention(
        q, k, v, gof, mask, force_pallas=True, live=jnp.asarray(live)))
    want = np.asarray(cad.grouped_cross_attention(
        q, k, v, gof, mask, force_pallas=True))
    alive = live[:, 0] > 0
    np.testing.assert_array_equal(got[alive], want[alive])
    assert np.all(got[~alive] == 0.0)
    np.testing.assert_array_equal(
        np.asarray(cad.grouped_cross_attention(
            q, k, v, gof, mask, force_reference=True,
            live=jnp.asarray(live)))[~alive], 0.0)
    slot_len = np.where(alive, np.asarray(lengths)[_GROUP_OF], 0)
    steps = _grid_indices(_GROUP_OF, slot_len, block, n_blocks)
    for s in np.flatnonzero(~alive):
        for j in range(n_blocks):
            at = s * n_blocks + j
            assert at == 0 or steps[at] == steps[at - 1], (s, j)
    if n_live == 16:
        # slots 2 and 3 (three blocks' worth of group 2) walk the same
        # blocks; slots 9 to 11 (group 1, one block) make ONE copy
        assert steps[2 * n_blocks:3 * n_blocks] \
            == steps[3 * n_blocks:4 * n_blocks]
        assert len(set(steps[9 * n_blocks:12 * n_blocks])) == 1
    copies = 1 + sum(a != b for a, b in zip(steps, steps[1:]))
    acct = cad.grid_accounting(_GROUP_OF, lengths, H, T, dh, n_rows=N,
                               live=live[:, 0])
    assert acct["block"] == block
    assert acct["blocks_read"] == copies
    runs = [x for x, _ in itertools.groupby(
        steps[at] for at in range(len(steps)) if alive[at // n_blocks])]
    assert copies == max(len(runs), 1)


# -- the session --------------------------------------------------------------

@pytest.fixture
def kernel_forced():
    """``attention_impl`` = ``pallas``: the op's ``impl="auto"`` runs the
    kernel, interpreted here (and the encoder's flash kernel with it)."""
    flags.set_flag("attention_impl", "pallas")
    try:
        yield
    finally:
        flags.set_flag("attention_impl", "auto")


class _Tap(object):
    """Between the session and its executor: every decode dispatch also
    fetches the output projection's logits (the sampler op's input)."""

    def __init__(self, exe):
        self._exe, self.name, self.logits = exe, None, []

    def __getattr__(self, name):
        return getattr(self._exe, name)

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, **kw):
        if self.name is None:
            (sampler,) = [op for op in program.global_block().ops
                          if op.type == "slot_decode_sample"]
            (self.name,) = [n for n in sampler.input_arg_names()
                            if "proj_logits" in n]
        out = self._exe.run_multi_step(
            program, steps, feed=feed,
            fetch_list=list(fetch_list) + [self.name], scope=scope, **kw)
        self.logits.append(np.asarray(out[-1]))
        return out[:-1]


def _decode(trained, impl):  # noqa: F811
    """Four sources of four lengths through a 2-layer paged session:
    (token rows, every dispatch's logits)."""
    flags.set_flag("attention_impl", impl)
    try:
        tap = _Tap(trained["exe"])
        sess = kv._paged(dict(trained, exe=tap))
        slots = [sess.admit(trained["src"][i], trained["src_len"][i])
                 for i in range(4)]
        outs = {}
        while len(outs) < 4:
            outs.update(sess.step())
        return np.stack([outs[s] for s in slots]), np.stack(tap.logits)
    finally:
        flags.set_flag("attention_impl", "auto")


def test_session_decodes_alike_with_the_kernel_and_the_reference(
        trained, monkeypatch):  # noqa: F811
    """The logits of every dispatch agree within the serving check's
    tolerance (0.006 of their norm, ``transformer_base.json``), and so
    far inside it that no greedy token turns: the streams equal the
    reference's and the dense oracle's."""
    traced = []
    kernel = cad._cross_decode_pallas
    monkeypatch.setattr(
        cad, "_cross_decode_pallas",
        lambda *a, **kw: traced.append(a[0].shape) or kernel(*a, **kw))
    got, got_logits = _decode(trained, "pallas")
    assert traced == [(4, 2, 1, 16)] * 2  # a call a layer, N = 1
    want, want_logits = _decode(trained, "reference")
    assert len(traced) == 2  # the reference never reaches the kernel
    assert got_logits.shape == want_logits.shape and got_logits.size
    err = (np.linalg.norm(got_logits - want_logits)
           / np.linalg.norm(want_logits))
    assert err < 0.006, err
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, trained["want"])


@pytest.mark.parametrize("case", [
    kv.test_group_greedy_member_bit_identical_to_solo_admit,
    kv.test_group_sampled_members_match_unshared_replay,
    kv.test_prefix_fork_shares_pages_until_cow_and_conserves,
    kv.test_cross_kv_pool_scales_with_groups_not_slots,
], ids=lambda f: f.__name__[5:])
def test_shared_group_cases_pass_with_the_kernel_forced(
        case, trained, kernel_forced):  # noqa: F811
    case(trained)
