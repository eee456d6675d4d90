"""The two grouped-query paged decode kernels (``kernels/gqa_paged_attention``
over a table in page order, ``kernels/window_paged_attention`` over a ring)
in interpret mode against their references: one grid step a slot, the slot's
resident pages walked inside the body several a step, nothing but resident
pages and resident table entries read."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import gqa_paged_attention as gq
from paddle_tpu.kernels import window_paged_attention as wp

PS, WINDOW = 8, 20
NPP, RING = 5, 4        # a full table of 40 positions; a ring for WINDOW

# name -> the slots' lengths. Every list puts an empty or a short slot
# beside a long one: the next slot's first pages start under this slot's
# last step.
_FULL = {
    "empty_and_one": [0, 1, 0, 2],
    "a_pages_edge": [PS, PS + 1, PS - 1, 2 * PS],
    "ragged_last_step": [3 * PS, 0, 3 * PS + 3, 2 * PS + 1],
    "full_table": [NPP * PS, NPP * PS - 1, 0, NPP * PS],
    "every_slot_empty": [0, 0, 0],
}
_RING = {
    "empty_and_one": [0, 1, 0, 2],
    "a_pages_edge": [PS, PS + 1, 2 * PS, 3 * PS],
    "shorter_than_the_window": [5, WINDOW - 1, WINDOW, 0],
    # first = length - WINDOW inside a page, the ring wrapped: 37 holds
    # pages 2..4 in columns 2, 3, 0; 65 fills all four columns (1, 2, 3, 0)
    "wrapped_first_inside_a_page": [WINDOW + 1, 37, 65, 100],
    "ragged_last_step": [64, 0, 37, 3],
    "every_slot_empty": [0, 0, 0],
}
_CASES = [("full", n) for n in _FULL] + [("ring", n) for n in _RING]


def rel(got, want):
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


def _poisoned_case(kind, lengths, dtype, H=8, Hkv=2, dh=16, seed=7):
    """Slots of ``lengths`` over K and V pools in which ONLY their resident
    pages hold rows: every other page is NaN, and every table entry that
    is not a resident page's names a page outside the pool. Returns the
    kernel's operands and their clean twin (those entries on the trash
    page, the other pages zero) for the reference."""
    S = len(lengths)
    width = NPP if kind == "full" else RING
    rng = np.random.RandomState(seed)
    P = 2 + S * width                # page 0 and the last page never resident
    ids = 1 + rng.permutation(P - 2)
    table = np.full((S, width), P + 7, "int64")
    at = 0
    for slot, n in enumerate(lengths):
        if n:
            first = max(n - WINDOW, 0) if kind == "ring" else 0
            for page in range(first // PS, (n - 1) // PS + 1):
                table[slot, page % width] = ids[at]
                at += 1
    resident = table[table < P]
    pools, cleans = [], []
    for _ in range(2):
        clean = np.zeros((P, PS, Hkv * dh), "float32")
        clean[resident] = rng.standard_normal((resident.size, PS, Hkv * dh))
        pool = np.full_like(clean, np.nan)
        pool[resident] = clean[resident]
        pools.append(jnp.asarray(pool, dtype))
        cleans.append(jnp.asarray(clean, dtype))
    q = jnp.asarray(rng.standard_normal((S, H, dh)), dtype)
    lengths = jnp.asarray(lengths)
    case = (q, pools[0], pools[1], jnp.asarray(table), lengths)
    twin = (q, cleans[0], cleans[1],
            jnp.asarray(np.where(table < P, table, 0)), lengths)
    return case, twin


def _run(kind, case, twin, group, sm_scale=0.25):
    """(kernel in interpret mode at ``group`` pages a step, reference)."""
    if kind == "ring":
        q, kp, vp, table, lens = case
        first = jnp.maximum(lens - WINDOW, 0)
        got = wp._window_pallas(q, kp, vp, table, first, lens, sm_scale,
                                interpret=True, group=group)
        want = wp.window_paged_attention_reference(*twin, WINDOW, sm_scale)
    else:
        got = gq._gqa_pallas(*case, sm_scale, interpret=True, group=group)
        want = gq.gqa_paged_attention_reference(*twin, sm_scale)
    return got, want


@pytest.mark.parametrize("group", [None, 1, 3],
                         ids=["rule", "1_page", "3_pages"])
@pytest.mark.parametrize("kind,name", _CASES,
                         ids=["%s-%s" % c for c in _CASES])
def test_decode_kernels_read_resident_pages_only(kind, name, group):
    """One grid step a slot, ``group`` pages a step of the walk (None: the
    wrapper's rule, here the whole table or ring in one ragged step):
    lengths 0 and 1, a page's edge, a ragged last step, a full table, a
    table whose other entries name pages outside the pool, every slot
    empty; for the ring a sequence shorter than the window and a wrapped
    ring whose first visible position lies inside a page. Everything that
    is not a resident page is NaN or out of range."""
    lengths = (_FULL if kind == "full" else _RING)[name]
    case, twin = _poisoned_case(kind, lengths, jnp.float32)
    got, want = _run(kind, case, twin, group)
    assert got.shape == want.shape and got.dtype == want.dtype
    got = np.asarray(got)
    assert np.isfinite(got).all()
    for slot, n in enumerate(lengths):
        if n:
            assert rel(got[slot], want[slot]) < 1e-5, (slot, n)
        else:
            assert not got[slot].any()                   # length 0 -> 0


@pytest.mark.parametrize("kind", ["full", "ring"])
def test_decode_kernels_in_bfloat16_with_a_padded_group(kind):
    """The served dtype, and a multi-query group that is not a whole
    sublane tile (20 heads on one key/value head, padded to 32)."""
    lengths = [0, 3, 2 * PS + 5, 65 if kind == "ring" else NPP * PS]
    case, twin = _poisoned_case(kind, lengths, jnp.bfloat16, H=20, Hkv=1,
                                dh=32)
    got, want = _run(kind, case, twin, 2)
    assert got.dtype == jnp.bfloat16
    got = np.asarray(got, "float32")
    assert np.isfinite(got).all() and rel(got, want) < 2e-2
    assert not got[0].any()


@pytest.mark.parametrize("group", [1, 2, 4])
def test_a_length_over_the_table_stays_inside_the_row(group):
    """A length above ``pages_per_slot * page_size`` walks the slot's own
    row of the table and no entry of the next slot's (which here names
    pages outside the pool), and sees the table's rows alone."""
    lengths = [NPP * PS + 5, 0, NPP * PS + PS * group, 0]
    case, twin = _poisoned_case("full", lengths, jnp.float32)
    got, want = _run("full", case, twin, group)
    got = np.asarray(got)
    assert np.isfinite(got).all() and rel(got, want) < 1e-5
    assert not got[1].any() and not got[3].any()


def test_pages_a_step_follow_the_shapes():
    """The wrappers' rule for the pages a step of the walk: a count from
    what the call can see (page size, row width, padded query rows, the
    table's width), never more than the table holds, fewer where both
    pools' two halves would not fit the kernel's VMEM."""
    served = {  # width of a bfloat16 row, padded query rows, table pages
        "solar": (1024, 16, 80), "trinity": (512, 16, 68),
        "trinity_ring": (512, 16, 18), "granite": (1024, 16, 40),
        "jamba": (128, 32, 12)}
    pages = {name: gq._pages_per_step(128, width, 2, rows, npp)
             for name, (width, rows, npp) in served.items()}
    # the table's pages spread evenly over the fewest steps of at most 8
    assert pages == {"solar": 8, "trinity": 8, "trinity_ring": 6,
                     "granite": 8, "jamba": 6}
    for name, (width, rows, npp) in served.items():
        assert 4 * pages[name] * 128 * width * 2 <= 12 << 20, name
    assert gq._pages_per_step(8, 32, 4, 16, 3) == 3
    assert gq._pages_per_step(8, 32, 4, 16, 1) == 1
    # a 2048-wide float32 row: eight pages would be 32 MB of halves
    assert gq._pages_per_step(128, 2048, 4, 16, 80) == 2


def test_kernel_bench_family_counts_what_the_walk_skips(monkeypatch, capsys):
    """``tools/kernel_bench.py --family gqa_decode`` tiny on the CPU: a
    row a kernel (the full table, and the ring of a configuration with a
    window) and walk, the grid's steps one a slot, the page walks under
    what a (slot, page) grid would step through, the kernel at its
    reference."""
    import importlib.util
    import os

    monkeypatch.setenv("BENCH_PLATFORM", "cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "kernel_bench", os.path.join(root, "tools", "kernel_bench.py"))
    kb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kb)
    rows = kb._bench_gqa_decode(
        [("trinity_mini_5l", "closed_120_longctx",
          ("kernel_costs_trinity", "decode_attention"),
          dict(num_slots=3, max_prompt=512, max_new_tokens=128,
               window=256))],
        ({}, dict(group=2)), calls=1, steps=1, warmup=0)
    capsys.readouterr()
    assert [(r["kernel"], r["walk"]) for r in rows] == [
        (gq.GQA_KERNEL_NAME, {}), (gq.GQA_KERNEL_NAME, {"group": 2}),
        (wp.WINDOW_KERNEL_NAME, {}), (wp.WINDOW_KERNEL_NAME, {"group": 2})]
    for r in rows:
        assert r["grid_steps"] == 3
        assert 0 < r["page_walks"] <= r["total_page_slots"]
        assert r["least_ms"] > 0 and r["pallas_ms"] > 0
        assert r["rel_l2"] < 2e-2
    assert rows[0]["total_page_slots"] == 3 * 5      # 640 positions
    assert rows[2]["total_page_slots"] == 3 * 4      # a ring for 256 + 3
    assert rows[2]["rows"] <= 3 * 256 < rows[0]["rows"]
