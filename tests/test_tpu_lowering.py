"""Every Pallas kernel, compiled by the chip's own compiler — no chip.

The TPU compiler ships with the installed libtpu and compiles for a chip
that is *described*, not attached (``topologies.get_topology_desc``), so
Mosaic's verdict on a kernel is available on the CPU test host. Each
case here runs the kernel entry point with ``interpret=False`` — the
entry points pick interpret mode from ``core.lowering.is_tpu_target()``,
which these tests steer by pinning the ambient compile platform to
"tpu", exactly what the executor does for a program placed on a TPU
device — lowers it for a described ``v5e`` chip, compiles it, and
asserts the Mosaic ``tpu_custom_call`` is in the compiled text. An
interpret-mode lowering (what ``force_pallas=True`` alone gives on this
host) holds no such call and proves nothing about the chip: the paged
decode kernel passed every interpret-mode test while Mosaic refused it
at every shape.

A passing compile is not a chip run: nothing executes, so these say
nothing about results or times.

Reference analogy: paddle/fluid/operators/math/jit_kernel_test.cc compiles
every JIT kernel variant in CI regardless of the deploy target.
"""

import contextlib
import importlib
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from paddle_tpu.core import lowering
from paddle_tpu.kernels import gru_cell, lstm_cell
from paddle_tpu.kernels import paged_attention as pa

# paddle_tpu.kernels re-exports the flash_attention FUNCTION, which
# shadows the submodule for every import-statement form; importlib
# resolves the module itself
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


@pytest.fixture(scope="module")
def v5e():
    """The described (not attached) v5e 2x2 host's devices."""
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu, or one that cannot describe it
        pytest.skip("cannot describe a v5e topology here: %s" % exc)
    return list(topo.devices)


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to JAX's persistent
    cache but cannot be read back without the chip (the next one warns
    and recompiles), so the cache is off around these tests."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@contextlib.contextmanager
def _tpu_target():
    """What CompiledProgram does for a program on a TPU Place: the
    kernel entry points under this see a TPU target, so interpret=False."""
    lowering._AMBIENT_PLATFORM.append("tpu")
    try:
        yield
    finally:
        lowering._AMBIENT_PLATFORM.pop()


def _compile_for(sharding, fn, *specs):
    """Trace ``fn`` as for a TPU target, compile it for the described
    chip(s), and return the compiled text with the Mosaic call count
    checked non-zero."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]

    def traced(*a):
        with _tpu_target():
            return fn(*a)

    text = jax.jit(traced).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, (
        "no Mosaic kernel in the compiled program (interpret-mode "
        "lowering or a reference path stood in)")
    return text


def _compile_v5e(v5e, fn, *specs):
    return _compile_for(SingleDeviceSharding(v5e[0]), fn, *specs)


def _assert_moves_no_pool(text, pool_elems):
    """No ``copy``, ``transpose`` or gather in the compiled ``text`` whose
    result holds a pool's elements or more."""
    import re

    moved = []
    for line in text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
            r"(copy|transpose|gather|copy-start)\(", line)
        if m and m.group(1):
            elems = 1
            for d in m.group(1).split(","):
                elems *= int(d)
            if elems >= pool_elems:
                moved.append(line.strip()[:160])
    assert not moved, "the program moves a whole pool:\n" + "\n".join(moved)


def _peak_bytes(compiled):
    """Arguments + temporaries + outputs that alias no argument."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def _pallas_grids(jaxpr):
    """``{kernel name: grid}`` of every ``pallas_call`` in ``jaxpr`` and
    the jaxprs nested in it (a scan's body, a cond's branches)."""
    grids = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids[eqn.params["name"]] = tuple(
                eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids.update(_pallas_grids(sub))
    return grids


F32, BF16 = jnp.float32, jnp.bfloat16

# the kernel_bench sweep's smallest shape plus a non-multiple batch that
# exercises the pad-to-block path
_RNN_SHAPES = [(32, 128, 256), (5, 16, 256)]


@pytest.mark.parametrize("bs,seq,d", _RNN_SHAPES)
def test_lstm_lowers_for_tpu(v5e, bs, seq, d):
    _compile_v5e(
        v5e,
        lambda xw, w_h, bias: lstm_cell.fused_lstm(
            xw, w_h, bias, force_pallas=True),
        ((bs, seq, 4 * d), F32), ((d, 4 * d), F32), ((4 * d,), F32))


def test_lstm_peephole_masked_lowers_for_tpu(v5e):
    bs, seq, d = 8, 16, 256

    def fn(xw, w_h, bias, p0, p1, p2, mask):
        return lstm_cell.fused_lstm(
            xw, w_h, bias, peephole=(p0, p1, p2), mask=mask,
            force_pallas=True)

    _compile_v5e(
        v5e, fn, ((bs, seq, 4 * d), F32), ((d, 4 * d), F32),
        ((4 * d,), F32), ((d,), F32), ((d,), F32), ((d,), F32),
        ((bs, seq), F32))


@pytest.mark.parametrize("bs,seq,d", _RNN_SHAPES)
def test_gru_lowers_for_tpu(v5e, bs, seq, d):
    _compile_v5e(
        v5e,
        lambda xw, wg, wc, b: gru_cell.fused_gru(
            xw, wg, wc, b, force_pallas=True),
        ((bs, seq, 3 * d), F32), ((d, 2 * d), F32), ((d, d), F32),
        ((3 * d,), F32))


def _flash_grad(**kw):
    def loss(q, k, v, *mask):
        masked = dict(kw, mask=mask[0] > 0) if mask else kw
        return fa.flash_attention(q, k, v, force_pallas=True,
                                  **masked).astype(F32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


def _assert_flash_fwd_bwd(text):
    """Forward, dK/dV and dQ are one named Mosaic call each."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in (fa.FWD_KERNEL_NAME, fa.BWD_DKV_KERNEL_NAME,
                 fa.BWD_DQ_KERNEL_NAME):
        assert any(name in line for line in calls), (
            "%s is not a Mosaic call of the compiled program" % name)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_bwd_lowers_for_tpu(v5e, causal):
    qkv = ((1, 2, 256, 64), F32)
    text = _compile_v5e(v5e, _flash_grad(causal=causal), qkv, qkv, qkv)
    _assert_flash_fwd_bwd(text)


def test_flash_gqa_window_lowers_for_tpu(v5e):
    # grouped-query (2 query heads per kv head) + sliding window + key
    # mask: the full feature set through fwd AND the FA2 backward
    q, kv = ((1, 4, 256, 64), F32), ((1, 2, 256, 64), F32)
    text = _compile_v5e(
        v5e, _flash_grad(causal=True, kv_group=2, window=128),
        q, kv, kv, ((1, 256), F32))
    _assert_flash_fwd_bwd(text)


def test_flash_uneven_tail_lowers_for_tpu(v5e):
    # T not a multiple of the default block: exercises the tail-tile path
    qkv = ((1, 2, 192, 64), F32)
    _assert_flash_fwd_bwd(_compile_v5e(v5e, _flash_grad(), qkv, qkv, qkv))


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_flash_train_width_lowers_for_tpu(v5e, dtype):
    """chip_smoke.py's train phase: batch 64 x 8 heads x seq 256 x
    dh 64, self-attention (causal) forward and backward; bf16 is what
    the AMP rewrite hands the kernel."""
    qkv = ((64, 8, 256, 64), dtype)
    text = _compile_v5e(v5e, _flash_grad(causal=True), qkv, qkv, qkv)
    _assert_flash_fwd_bwd(text)


@pytest.mark.parametrize("masked", [False, True],
                         ids=["causal", "key_mask"])
def test_flash_train_shape_grid_and_operand_dtype(v5e, masked):
    """``train_big_1chip``'s attention, bfloat16 [64, 16, 256, 64], forward
    and backward (decoder self-attention; encoder and cross attention
    under their key mask): each of the three Mosaic calls steps a grid of
    at most 256 (one tile a sequence, four heads or more a step; tiles of
    128 a head took 4096), and q, k, v and dO reach them as they arrive:
    no float32 array of their shape is made anywhere in the compiled
    program (a widened dO was read by both backward kernels)."""
    import re

    qkv = ((64, 16, 256, 64), BF16)
    specs = [qkv] * 3 + ([((64, 256), F32)] if masked else [])
    fn = _flash_grad(causal=not masked)
    text = _compile_v5e(v5e, fn, *specs)
    _assert_flash_fwd_bwd(text)

    def traced(*a):
        with _tpu_target():
            return fn(*a)

    grids = _pallas_grids(jax.make_jaxpr(traced)(*[
        jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in specs]).jaxpr)
    assert set(grids) == {fa.FWD_KERNEL_NAME, fa.BWD_DKV_KERNEL_NAME,
                          fa.BWD_DQ_KERNEL_NAME}
    for name, grid in grids.items():
        assert np.prod(grid) <= 256, (name, grid)
        # one tile a sequence: only batch and head blocks are stepped
        assert grid[0] == 64 and set(grid[2:]) == {1}, (name, grid)
    # an instruction of a fused computation lives in registers; every
    # other one's result is an array of the program
    widened, fused = [], False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            fused = head.group(1).startswith("fused_computation")
        elif not fused and re.match(
                r"\s*(?:ROOT )?%\S+ = f32\[64,16,256,64\]", line):
            widened.append(line.strip()[:160])
    assert not widened, "a float32 copy of an operand:\n" + "\n".join(widened)


def test_ring_flash_lowers_for_tpu(v5e):
    """Ring attention's shard_map + per-block Pallas engine compiles
    for the four described chips — guards the Mosaic x shard_map
    composition (sequence parallelism's hot path) without hardware."""
    from paddle_tpu.parallel.ring_attention import ring_attention

    mesh = Mesh(v5e[:4], ("data",))
    seq_sharded = NamedSharding(mesh, PartitionSpec(None, None, "data"))
    qkv = ((1, 2, 4 * 128, 64), F32)

    def loss(q, k, v):
        return ring_attention(q, k, v, mesh, axis_name="data",
                              causal=True, impl="flash").sum()

    _compile_for(seq_sharded, loss, qkv, qkv, qkv)


# chip_smoke.py's serve phase: Transformer-base heads (8 x dh 64) over
# the paged slot pool
_SERVE = dict(S=8, H=8, dh=64, T=256)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("page_size", [16, 128])
def test_paged_decode_lowers_for_tpu(v5e, page_size, dtype):
    S, H, dh, T = (_SERVE[k] for k in ("S", "H", "dh", "T"))
    npp = pa.pages_for(T, page_size)
    pool = ((1 + S * npp, page_size, H * dh), dtype)
    text = _compile_v5e(
        v5e,
        lambda q, k, v, t, n: pa.paged_attention(
            q, k, v, t, n, force_pallas=True),
        ((S, H, dh), dtype), pool, pool, ((S, npp), jnp.int32),
        ((S,), jnp.int32))
    assert pa.PAGED_KERNEL_NAME in text


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("n_nodes", [5, 8])
def test_tree_attention_lowers_for_tpu(v5e, n_nodes, dtype):
    """The speculative verify kernel at N = k + 1 nodes (k=4 is the
    drafter default, N=8 the widest tree the tests drive)."""
    S, H, dh, T = (_SERVE[k] for k in ("S", "H", "dh", "T"))
    page_size = 16
    npp = pa.pages_for(T, page_size)
    pool = ((1 + S * npp, page_size, H * dh), dtype)
    text = _compile_v5e(
        v5e,
        lambda q, k, v, t, b, anc: pa.paged_tree_attention(
            q, k, v, t, b, anc, max_length=T, force_pallas=True),
        ((S, H, n_nodes, dh), dtype), pool, pool, ((S, npp), jnp.int32),
        ((S,), jnp.int32), ((S, n_nodes, n_nodes), jnp.int32))
    assert pa.TREE_KERNEL_NAME in text


# the paged step as the session runs it at the served shapes (perfbench
# transformer_base: 4097 pages of 16, 8 heads of 64, 256 slots of 256
# positions, float32, 4 tokens a dispatch), then one thing changed a case
_STEP = dict(H=8, dh=64, ps=16, dtype=F32)
_STEP_CASES = {
    "served": {}, "16_heads": dict(H=16), "dh_128": dict(dh=128),
    "bf16": dict(dtype=BF16), "pages_of_128": dict(ps=128),
}


@pytest.mark.parametrize("n_nodes", [None, 5], ids=["step", "tree_5"])
@pytest.mark.parametrize("case", list(_STEP_CASES))
def test_paged_step_copies_no_pool(v5e, case, n_nodes):
    """Write + attention inside the multi-step scan, pools donated: the
    compiled program holds no ``copy``, ``transpose`` or gather of a
    pool-sized array, its temporaries are under 1/8 of one pool, and the
    pool keeps one layout with no padded lane from parameter to Mosaic
    operand. The per-head ``[P, H, page_size, dh]`` pool failed all
    three at ``dh`` 64: stored page-minor, scattered head-width-minor,
    read row-major, 1 + 4 + 1 copies a pool and dispatch and 1.6 GB of
    temporaries a layer."""
    import re

    cfg = dict(_STEP, **_STEP_CASES[case])
    H, dh, ps, dtype = (cfg[k] for k in ("H", "dh", "ps", "dtype"))
    S, T, steps = 256, 256, 4
    npp = pa.pages_for(T, ps)
    P = 1 + 4096 * 16 // ps
    pool_shape = (P, ps, H * dh)
    new_shape = (S, H, dh) if n_nodes is None else (S, H, n_nodes, dh)

    def step(kp, vp, q, kn, vn, table, pos):
        def body(carry, _):
            kp, vp, pos, acc = carry
            if n_nodes is None:
                kp, vp = pa.paged_kv_write(kp, vp, kn, vn, table, pos)
                out = pa.paged_attention(q, kp, vp, table, pos + 1)
            else:
                kp, vp = pa.paged_kv_write_block(
                    kp, vp, kn, vn, table,
                    pos[:, None] + jnp.arange(n_nodes)[None, :])
                out = pa.paged_tree_attention(
                    q, kp, vp, table, pos,
                    jnp.ones((S, n_nodes, n_nodes), jnp.int32),
                    max_length=T)
            return (kp, vp, pos + 1, acc + out), None

        with _tpu_target():
            carry, _ = jax.lax.scan(
                body, (kp, vp, pos, jnp.zeros_like(q)), None, length=steps)
        return carry[0], carry[1], carry[3]

    sharding = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
            for shape, dt in (
                (pool_shape, dtype), (pool_shape, dtype), (new_shape, dtype),
                (new_shape, dtype), (new_shape, dtype),
                ((S, npp), jnp.int32), ((S,), jnp.int32))]
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    kernel = pa.PAGED_KERNEL_NAME if n_nodes is None else pa.TREE_KERNEL_NAME
    assert kernel in text
    # a grid step is a slot: the resident pages are walked inside the body
    assert _pallas_grids(jax.make_jaxpr(step)(*args).jaxpr) == {kernel: (S,)}
    pool_elems = P * ps * H * dh
    _assert_moves_no_pool(text, pool_elems)
    pool_bytes = pool_elems * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8
    # one layout of the pool, row-major, the whole row on the lanes
    dims = "%d,%d,%d" % pool_shape
    layouts = set(re.findall(
        r"\[%s\]\{([\d,]+)" % dims, text))
    assert layouts == {"2,1,0"}, layouts
    assert not re.search(r"\[%d,%d,%d,%d\]" % (P, H, ps, dh), text)


def test_batched_admission_copies_no_cross_pool(v5e):
    """The top rung of ``SlotDecodeSession``'s admission ladder at the
    served shapes (perfbench transformer_base: 32 sources of 256
    positions into 256 groups of ``[8, 256, 64]`` float32 cross rows, 12
    pools of 134 MB): the compiled program scatters the batch's rows in
    place. It holds no ``copy``, ``transpose`` or gather of a pool's
    size, aliases all twelve cross pools, the source mask and the five
    per-slot state arrays onto their inputs, keeps each pool in the one
    layout the decode step's cross kernel reads, and its temporaries
    (the encoder's activations for 8192 token places) stay under one
    pool. The sibling of ``test_paged_step_copies_no_pool``."""
    import re

    from paddle_tpu.models import transformer

    rows, S, T, H, dh, L, ps = 32, 256, 256, 8, 64, 6, 16
    prog = transformer.build_admit_batch_prog(
        rows, S, src_vocab_size=32000, max_length=T, n_layer=L, n_head=H,
        d_model=H * dh, d_inner=2048, page_size=ps)
    state = {name: var for name, var in prog.global_block().vars.items()
             if var.persistable}
    npp = pa.pages_for(T, ps)
    feed_specs = {
        "src_word": ((rows, T), "int64"), "src_len": ((rows, 1), "int64"),
        "slot_idx": ((rows,), "int64"), "group_idx": ((rows,), "int64"),
        "page_row": ((rows, npp), "int64"),
        "start_tok": ((rows, 1), "int64"), "start_pos": ((rows, 1), "int64")}
    cp = lowering.CompiledProgram(prog, feed_specs, [], frozenset(state),
                                  is_test=prog._is_test, device=v5e[0])
    sharding = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    def of(names):
        return {n: spec(state[n].shape,
                        jnp.int32 if "int" in str(state[n].dtype) else F32)
                for n in names}

    pools = ["pgd_%scross_%d" % (kv, i) for kv in "kv" for i in range(L)]
    slot_state = ["pgd_group_of", "pgd_table", "pgd_tok", "pgd_pos",
                  "pgd_done"]
    assert set(cp.mutable_state) == set(pools + slot_state + ["pgd_src_mask"])
    compiled = cp.jitted.lower(
        of(cp.mutable_state), of(cp.frozen_state),
        {n: spec(shape, jnp.int32) for n, (shape, _d) in feed_specs.items()},
        (spec((2,), jnp.uint32), spec((), jnp.uint32))).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= L   # the encoder's flash forward
    pool_elems = S * H * T * dh
    _assert_moves_no_pool(text, pool_elems)
    memory = compiled.memory_analysis()
    mutable_bytes = sum(
        4 * int(np.prod(state[n].shape)) for n in cp.mutable_state)
    assert memory.alias_size_in_bytes == mutable_bytes, (
        memory.alias_size_in_bytes, mutable_bytes)
    head = text.split("entry_computation_layout", 1)[0]
    assert head.count("may-alias") + head.count("must-alias") == len(
        cp.mutable_state)
    print("batched admission, rung %d: temp_size_in_bytes %d"
          % (rows, memory.temp_size_in_bytes))
    assert memory.temp_size_in_bytes < 4 * pool_elems, \
        memory.temp_size_in_bytes
    layouts = set(re.findall(r"\[%d,%d,%d,%d\]\{([\d,]+)" % (S, H, T, dh),
                             text))
    assert layouts == {"2,3,1,0"}, layouts


def test_batched_table_repoint_writes_the_table_in_place(v5e):
    """The top rung of ``SlotDecodeSession``'s release ladder at the
    served shapes (perfbench transformer_base: 32 of 256 slots' rows of
    16 pages): the compiled program is one row scatter into the donated
    ``pgd_table``. The table is its only state and aliases its input;
    the program names no page pool and holds no op of a pool's size (the
    16 KB table itself the compiler may turn into the scatter's layout
    and back: two copies of 4096 words), and its temporaries stay within
    a few tables' bytes."""
    import re

    from paddle_tpu.models import transformer

    rows, S, T, ps = 32, 256, 256, 16
    prog = transformer.build_table_batch_prog(rows, S, max_length=T,
                                              page_size=ps)
    npp = pa.pages_for(T, ps)
    state = {name: var for name, var in prog.global_block().vars.items()
             if var.persistable}
    assert set(state) == {"pgd_table"}
    feed_specs = {"slot_idx": ((rows,), "int64"),
                  "page_row": ((rows, npp), "int64")}
    cp = lowering.CompiledProgram(prog, feed_specs, [], frozenset(state),
                                  is_test=prog._is_test, device=v5e[0])
    assert list(cp.mutable_state) == ["pgd_table"] and not cp.frozen_state
    sharding = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    compiled = cp.jitted.lower(
        {"pgd_table": spec((S, npp), jnp.int32)}, {},
        {n: spec(shape, jnp.int32) for n, (shape, _d) in feed_specs.items()},
        (spec((2,), jnp.uint32), spec((), jnp.uint32))).compile()
    text = compiled.as_text()
    assert "scatter" in text and "tpu_custom_call" not in text
    _assert_moves_no_pool(text, (1 + S * npp) * ps * 512)
    assert not re.search(r"\[%d,%d,\d+\]" % (1 + S * npp, ps), text)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 4 * S * npp, \
        memory.alias_size_in_bytes
    head = text.split("entry_computation_layout", 1)[0]
    assert head.count("may-alias") + head.count("must-alias") == 1
    assert memory.temp_size_in_bytes <= 4 * 4 * S * npp, \
        memory.temp_size_in_bytes


def test_copy_on_write_window_copies_no_pool(v5e):
    """The lowest rung of ``SlotDecodeSession``'s copy-on-write ladder at
    the served shapes (perfbench transformer_base: a window of 64 pairs
    over 256 slots' tables of 16 pages, 12 pools ``f32[4097,16,512]`` of
    134 MB): the compiled program is a gather of the window's 64 source
    pages and a scatter onto its destinations a pool, and one row scatter
    into the table, all in place. It holds no ``copy``, ``transpose`` or
    gather of a pool's size, aliases all twelve pools and the table onto
    their inputs, and its temporaries (the window's pages, 2 MB a pool)
    stay under one pool. The sibling of
    ``test_paged_step_copies_no_pool`` and of
    ``test_batched_admission_copies_no_cross_pool``."""
    import re

    import _described_compile as dc
    from paddle_tpu.models import transformer

    pairs, S, T, H, dh, L, ps = 64, 256, 256, 8, 64, 6, 16
    npp = pa.pages_for(T, ps)
    P = 1 + S * npp
    prog = transformer.build_cow_batch_prog(S, T, L, H, H * dh, ps, P, pairs)
    state = {name: (var.shape, var.dtype)
             for name, var in prog.global_block().vars.items()
             if var.persistable}
    pools = ["pgd_%spool_%d" % (kv, i) for kv in "kv" for i in range(L)]
    assert set(state) == set(pools + ["pgd_table"])
    assert all(tuple(state[n][0]) == (P, ps, H * dh) for n in pools)
    compiled = dc.compile_program(prog, v5e[0], state, {
        "src_pages": ((pairs,), "int64"), "dst_pages": ((pairs,), "int64"),
        "slot_idxs": ((pairs,), "int64"),
        "page_rows": ((pairs, npp), "int64")}, [])
    text = compiled.as_text()
    # the window whole: no loop over its pairs and no op a pair
    assert "while(" not in text and "tpu_custom_call" not in text
    assert len(re.findall(r"\[%d,%d,%d\]\S* gather\(" % (pairs, ps, H * dh),
                          text)) == 2 * L
    pool_elems = P * ps * H * dh
    _assert_moves_no_pool(text, pool_elems)
    memory = compiled.memory_analysis()
    mutable_bytes = 4 * (2 * L * pool_elems + S * npp)
    assert memory.alias_size_in_bytes == mutable_bytes, (
        memory.alias_size_in_bytes, mutable_bytes)
    head = text.split("entry_computation_layout", 1)[0]
    assert head.count("may-alias") + head.count("must-alias") == len(state)
    print("copy-on-write window, rung %d: temp_size_in_bytes %d"
          % (pairs, memory.temp_size_in_bytes))
    assert memory.temp_size_in_bytes < 4 * pool_elems, \
        memory.temp_size_in_bytes


# the decode step's cross attention at the served shapes (perfbench
# transformer_base: 256 slots and groups, 8 heads of 64, 256 source
# positions), the tree-verify program's N nodes, transformer_big's 16
# heads, and a head width of whole lane tiles (row-major pools)
@pytest.mark.parametrize("S,H,N,T,dh", [
    (256, 8, 1, 256, 64), (256, 8, 5, 256, 64), (64, 16, 1, 256, 64),
    (64, 8, 1, 512, 128)], ids=["served", "tree", "16_heads", "dh_128"])
def test_cross_decode_lowers_for_tpu_and_copies_no_pool(v5e, S, H, N, T, dh):
    """Mosaic takes the kernel, it goes by the flash forward's name, and
    the program holds no copy of a pool beside it: the ``[G, H, dh, T]``
    view of a pool the chip keeps source-minor is a bitcast (the old
    path gathered and transposed ~0.8 GB a call at the served shapes)."""
    from paddle_tpu.kernels import cross_attention_decode as cad

    pool = ((S, H, T, dh), F32)
    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=SingleDeviceSharding(v5e[0]))
            for shape, dtype in (((S, H, N, dh), F32), pool, pool,
                                 ((S,), jnp.int32), ((S, T), F32),
                                 ((S, 1), jnp.int32))]

    def traced(*a):
        with _tpu_target():
            return cad.grouped_cross_attention(*a[:5], live=a[5])

    compiled = jax.jit(traced).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%" + cad.CROSS_DECODE_KERNEL_NAME + "." in text
    assert pa.PAGED_KERNEL_NAME not in text
    _assert_moves_no_pool(text, S * H * T * dh)
    pool_bytes = S * H * T * dh * 4
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


def test_cross_decode_index_of_a_dead_slot_is_the_step_before_it():
    """What saves a dead slot's copy, at the served grid (256 slots, one
    block a slot) with 8 live: the pipeline copies a block only where the
    K/V index differs from the grid step before, and the index the
    kernel's map gives a dead slot, from the prefetch vectors the op
    computes, never does."""
    from paddle_tpu.kernels import cross_attention_decode as cad

    S, H, T, dh = 256, 8, 256, 64
    block = cad.source_block(H, T, dh)
    rng = np.random.RandomState(47)
    group_of = rng.permutation(S).astype("int32")   # stale groups too
    live = np.zeros(S, bool)
    live[rng.choice(S, 8, replace=False)] = True
    slot_len = np.where(live, rng.randint(1, T + 1, S), 0).astype("int32")
    group, lo, hi = cad.steer_dead_slots(
        jnp.asarray(group_of), jnp.asarray(slot_len), block)
    index = [(int(group[s]), int(cad.kv_block_index(s, j, lo, hi)))
             for s in range(S) for j in range(T // block)]
    first = int(np.flatnonzero(live)[0])
    for s in range(S):
        if live[s]:
            assert index[s] == (group_of[s], 0)     # its own row
        else:
            assert index[s] == (index[s - 1] if s else
                                (group_of[first], 0))
    assert 1 + sum(a != b for a, b in zip(index, index[1:])) == 8


def test_transformer_step_program_steers_once_and_copies_no_cross_pool(v5e):
    """The paged session's step program as the executor builds it, at the
    served widths (perfbench transformer_base; two layers of its six),
    four token steps a dispatch: both kernels are in it, no cross (or
    page) pool is copied, transposed or gathered, and every layer's
    cross-attention call of a token step takes the SAME four prefetch
    vectors (group, first and last block, length times liveness): the
    steering is computed once a step, not once a layer."""
    import re
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _described_compile as dc
    from paddle_tpu.kernels import cross_attention_decode as cad
    from paddle_tpu.models import transformer

    S, T, D, H, V, L = 256, 256, 512, 8, 32000, 2
    step, fetch = transformer.build_paged_slot_decoder(
        S, src_vocab_size=V, trg_vocab_size=V, max_length=T, n_layer=L,
        n_head=H, d_model=D, d_inner=2048, page_size=16)[5:7]
    state = {name: (var.shape, var.dtype)
             for name, var in step.global_block().vars.items()
             if var.persistable}
    with _tpu_target():
        text = dc.compile_program(step, v5e[0], state, {}, [fetch],
                                  steps=4).as_text()
    assert cad.CROSS_DECODE_KERNEL_NAME in text
    assert pa.PAGED_KERNEL_NAME in text
    _assert_moves_no_pool(text, S * H * T * (D // H))
    calls = re.findall(
        r"= f32\[%d,%d,1,%d\]\S* custom-call\(([^)]*)\), "
        r"custom_call_target=\"tpu_custom_call\"" % (S, H, D // H), text)
    assert len(calls) == L, len(calls)
    assert len({tuple(c.split(", ")[:4]) for c in calls}) == 1, calls


def _assert_one_slot_walk(v5e, fn, kernel, specs, pool_elems):
    """``fn`` compiles for the described chip to ONE Mosaic call named
    ``kernel`` whose grid step is a SLOT (the resident pages are walked
    inside the body, the pools left in HBM: D16's guard), with no copy of
    a pool-sized array around it. Returns the compiled text."""
    text = _compile_v5e(v5e, fn, *specs)
    assert kernel in text
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1

    def traced(*a):
        with _tpu_target():
            return fn(*a)

    args = [jax.ShapeDtypeStruct(shape, dt) for shape, dt in specs]
    S = specs[0][0][0]
    assert _pallas_grids(jax.make_jaxpr(traced)(*args).jaxpr) == {
        kernel: (S,)}
    _assert_moves_no_pool(text, pool_elems)
    return text


# the three latent-attention serving cells (perfbench glm47_flash_6l: 256
# slots, 20 heads, tables of 12 pages; longcat_flash_omni_4l: 64 slots, 64
# heads, tables of 40; kimi_linear_5l: 384 slots, 32 heads, tables of 64):
# a 512 + 64 wide row in a 640-wide bfloat16 pool of 128-row pages
@pytest.mark.parametrize("S,H,npp", [(256, 20, 12), (64, 64, 40),
                                     (384, 32, 64)],
                         ids=["glm47_flash", "longcat_flash", "kimi_linear"])
def test_latent_decode_lowers_for_tpu_at_the_served_widths(v5e, S, H, npp):
    """One Mosaic call whose grid step is a SLOT (the resident pages are
    walked inside the body, the pool left in HBM), and no copy of a
    pool-sized array around it."""
    from paddle_tpu.kernels import latent_attention as la

    C, R, ps = 512, 64, 128
    pool_shape = (1 + S * npp, ps, la.pool_width(C + R))
    specs = (((S, H, C), BF16), ((S, H, R), BF16), (pool_shape, BF16),
             ((S, npp), jnp.int32), ((S,), jnp.int32))
    _assert_one_slot_walk(
        v5e, lambda ql, qr, pool, t, n: la.latent_paged_attention(
            ql, qr, pool, t, n, sm_scale=0.0625, force_pallas=True),
        la.LATENT_KERNEL_NAME, specs, pool_shape[0] * ps * pool_shape[2])


def test_flash_prefill_width_lowers_for_tpu(v5e):
    """The same cell's prefill: causal forward at head width 256 in
    bfloat16, 2 prompts of the longest bucket."""
    qkv = ((2, 20, 1024, 256), BF16)
    text = _compile_v5e(
        v5e, lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        qkv, qkv, qkv)
    assert "flash_attention_fwd" in text


@pytest.mark.parametrize("rows", [1024, 8192], ids=["decode", "prefill"])
def test_grouped_matmul_lowers_for_tpu_at_the_served_widths(v5e, rows):
    """The same cell's routed experts: 64 groups over a decode step's 1024
    (token, expert) rows and a prefill dispatch's 8192, both products'
    shapes."""
    from paddle_tpu.kernels import grouped_matmul as gm

    for k, n in ((2048, 1536), (1536, 2048)):
        text = _compile_v5e(
            v5e, lambda a, b, g: gm.grouped_matmul(a, b, g, force_pallas=True),
            ((rows, k), BF16), ((64, k, n), BF16), ((64,), jnp.int32))
        assert gm.GROUPED_KERNEL_NAME in text


# the hybrid state-space decoder's serving cell (perfbench jamba2_3b): 256
# slots, d_inner 5120, d_state 16, 20 query heads on one 128-wide K/V row
_SSM = dict(S=256, d=5120, n=16)


@pytest.mark.parametrize("B,T", [(2, 1024), (16, 128)],
                         ids=["longest_bucket", "shortest_bucket"])
def test_ssm_prefill_kernels_lower_for_tpu_at_the_served_widths(v5e, B, T):
    from paddle_tpu.kernels import selective_scan as ss

    d, n = _SSM["d"], _SSM["n"]
    text = _compile_v5e(
        v5e, lambda x, w, b: ss.causal_conv(x, w, b, force_pallas=True),
        ((B, T, d), BF16), ((4, d), BF16), ((d,), BF16))
    assert ss.CONV_KERNEL_NAME in text
    text = _compile_v5e(
        v5e, lambda x, dt, b, c, a, skip, lens: ss.prefill_scan(
            x, dt, b, c, a, skip, lens, force_pallas=True),
        ((B, T, d), BF16), ((B, T, d), F32), ((B, n, T), F32),
        ((B, n, T), F32), ((n, d), F32), ((d,), F32), ((B,), jnp.int32))
    assert ss.SCAN_KERNEL_NAME in text


def test_ssm_one_token_kernels_update_the_state_in_place(v5e):
    """The decode step's two state-space kernels at the served sizes,
    state and window donated: Mosaic takes both, the compiled program
    holds no copy of either array and its temporaries are a small
    fraction of the 84 MB state of one layer."""
    import re

    from paddle_tpu.kernels import selective_scan as ss

    S, d, n = _SSM["S"], _SSM["d"], _SSM["n"]

    def step(state, window, x, dt, b, c, a, skip, w, bias, live):
        with _tpu_target():
            xc, window = ss.conv_step(window, x, w, bias, live)
            y, state = ss.state_update(state, xc, dt, b, c, a, skip, live)
        return state, window, y

    sharding = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
            for shape, dt in (
                ((S, n, d), F32), ((3, S, d), BF16), ((S, d), BF16),
                ((S, d), F32), ((S, n), F32), ((S, n), F32), ((n, d), F32),
                ((d,), F32), ((4, d), BF16), ((d,), BF16), ((S,), jnp.int32))]
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    assert ss.UPDATE_KERNEL_NAME in text and ss.CONV_STEP_KERNEL_NAME in text
    moved = [line.strip()[:160] for line in text.splitlines() if re.match(
        r"\s*(?:ROOT )?%%\S+ = \w+\[(%d,%d,%d|3,%d,%d)\]\S* "
        r"(copy|transpose|gather|copy-start)\(" % (S, n, d, S, d), line)]
    assert not moved, "the step moves a whole state array:\n" + "\n".join(moved)
    assert compiled.memory_analysis().temp_size_in_bytes < S * n * d * 4 // 8
    assert set(re.findall(r"\[%d,%d,%d\]\{([\d,]+)" % (S, n, d), text)) \
        == {"2,1,0"}


# the four served geometries of the grouped-query decode kernels
# (perfbench solar_open2_4l, trinity_mini_5l, granite4_h_small_10l,
# jamba2_3b): slots, query heads on key/value heads of 128, table pages
# of 128 rows, bfloat16
_GQA_SERVED = {
    "solar": (96, 64, 8, 80), "trinity": (96, 32, 4, 68),
    "granite": (64, 32, 8, 40), "jamba": (256, 20, 1, 12),
}


@pytest.mark.parametrize("cell", list(_GQA_SERVED))
def test_gqa_decode_lowers_for_tpu_at_the_served_widths(v5e, cell):
    from paddle_tpu.kernels import gqa_paged_attention as gq

    S, H, Hkv, npp = _GQA_SERVED[cell]
    dh = ps = 128
    pool = ((1 + S * npp, ps, Hkv * dh), BF16)
    _assert_one_slot_walk(
        v5e, lambda q, k, v, t, n: gq.gqa_paged_attention(
            q, k, v, t, n, force_pallas=True),
        gq.GQA_KERNEL_NAME,
        (((S, H, dh), BF16), pool, pool, ((S, npp), jnp.int32),
         ((S,), jnp.int32)), (1 + S * npp) * ps * Hkv * dh)


@pytest.mark.parametrize("cell", list(_GQA_SERVED))
def test_window_decode_lowers_for_tpu_at_the_served_widths(v5e, cell):
    """The window layers' decode at the served geometries' heads and
    slots (served: the 26B-A3B cell's, 32 query heads on 4 key/value heads
    of 128, 96 slots), a ring of 18 pages of 128 rows a slot under a
    window of 2048; its name holds no other kernel's."""
    from paddle_tpu.kernels import gqa_paged_attention as gq
    from paddle_tpu.kernels import window_paged_attention as wp

    S, H, Hkv, _ = _GQA_SERVED[cell]
    dh, ps, R = 128, 128, 18
    pool = ((1 + S * R, ps, Hkv * dh), BF16)
    text = _assert_one_slot_walk(
        v5e, lambda q, k, v, t, n: wp.window_paged_attention(
            q, k, v, t, n, 2048, force_pallas=True),
        wp.WINDOW_KERNEL_NAME,
        (((S, H, dh), BF16), pool, pool, ((S, R), jnp.int32),
         ((S,), jnp.int32)), (1 + S * R) * ps * Hkv * dh)
    assert gq.GQA_KERNEL_NAME not in text


def test_flash_window_prefill_width_lowers_for_tpu(v5e):
    """The same cell's longest prefill bucket on a window layer: one
    prompt of 8192, 32 heads on 4, a band of 2048, at the tiles
    ``ops/window_ops.py`` asks for."""
    q, kv = ((1, 32, 8192, 128), BF16), ((1, 4, 8192, 128), BF16)
    text = _compile_v5e(
        v5e, lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, kv_group=8, window=2048, block_q=512,
            block_k=512), q, kv, kv)
    assert "flash_attention_fwd" in text


# -- the delta-rule linear-attention decoder (kernels/delta_rule.py) ----------

@pytest.mark.parametrize("B,T,H", [(1, 8192, 64), (16, 512, 64),
                                   (2, 4096, 32), (16, 512, 32)])
def test_delta_rule_prefill_lowers_for_tpu_at_the_served_widths(v5e, B, T,
                                                                H):
    """The chunked delta rule of the 250B-A15B cell: 64 heads of 128 x
    128, one prompt of 8192 tokens and a bucket row of 16 of 512; and of
    the 48B-A3B cell: 32 heads, two prompts of 4096 and 16 of 512."""
    from paddle_tpu.kernels import delta_rule as dr

    d = 128
    text = _compile_v5e(
        v5e, lambda q, k, v, g, beta, lens: dr.chunk_prefill(
            q, k, v, g, beta, lens, force_pallas=True),
        ((B, T, H * d), BF16), ((B, T, H * d), BF16), ((B, T, H * d), BF16),
        ((B, T, H * d), F32), ((B, T, H), F32), ((B,), jnp.int32))
    assert dr.CHUNK_KERNEL_NAME in text


@pytest.mark.parametrize("S,H", [(96, 64), (384, 32)],
                         ids=["solar_open2", "kimi_linear"])
def test_delta_rule_update_updates_the_state_in_place(v5e, S, H):
    """The one-token update at the served sizes (96 slots of 64 heads, and
    384 slots of 32: a 128 x 128 float32 state a head), the state donated:
    Mosaic takes it, the compiled program holds no copy of the state,
    keeps its one layout (``dv`` on the lanes) and its temporaries are a
    small fraction of the 403 / 805 MB state of one layer."""
    import re

    from paddle_tpu.kernels import delta_rule as dr

    d = 128

    def step(state, q, k, v, g, beta, live):
        with _tpu_target():
            return dr.state_update(state, q, k, v, g, beta, live)

    sharding = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
            for shape, dt in (
                ((S, H, d, d), F32), ((S, H * d), BF16), ((S, H * d), BF16),
                ((S, H * d), BF16), ((S, H * d), F32), ((S, H), F32),
                ((S,), jnp.int32))]
    compiled = jax.jit(step, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert dr.STATE_KERNEL_NAME in text
    moved = [line.strip()[:160] for line in text.splitlines() if re.match(
        r"\s*(?:ROOT )?%%\S+ = \w+\[%d,%d,%d,%d\]\S* "
        r"(copy|transpose|gather|copy-start)\(" % (S, H, d, d), line)]
    assert not moved, "the step moves a whole state array:\n" + "\n".join(moved)
    assert compiled.memory_analysis().temp_size_in_bytes < S * H * d * d * 4 // 8
    assert set(re.findall(r"\[%d,%d,%d,%d\]\{([\d,]+)" % (S, H, d, d), text)) \
        == {"3,2,1,0"}


@pytest.mark.parametrize("B,T", [(8, 1024), (32, 256)])
def test_delta_rule_prefill_lowers_at_a_decay_a_head_and_odd_widths(v5e, B,
                                                                    T):
    """The chunked delta rule of the 7B hybrid's cell: 30 heads of 96 keys
    beside 192 values under ONE log decay a head, bucket rows of 8 prompts
    of 1024 and 32 of 256. Neither width is a lane multiple: the rows go
    in head-major."""
    from paddle_tpu.kernels import delta_rule as dr

    H, dk, dv = 30, 96, 192
    text = _compile_v5e(
        v5e, lambda q, k, v, g, beta, lens: dr.chunk_prefill(
            q, k, v, g, beta, lens, force_pallas=True),
        ((B, T, H * dk), BF16), ((B, T, H * dk), BF16),
        ((B, T, H * dv), BF16), ((B, T, H), F32), ((B, T, H), F32),
        ((B,), jnp.int32))
    assert dr.CHUNK_KERNEL_NAME in text


@pytest.mark.parametrize("pack", [2, 1])
def test_delta_rule_update_holds_the_state_at_its_published_size(v5e, pack):
    """The one-token update at 96 slots of 30 heads of 96 x 192 under a
    decay a head, the state donated. Two heads a tile (384 lanes: three
    whole tiles) the state's array is its elements, 212 MB, and the
    arguments are within 5% of that; a head a tile the 192 lanes are laid
    out as 256 and the array is a third larger. Mosaic takes both; neither
    program copies the state."""
    import re

    from paddle_tpu.kernels import delta_rule as dr

    S, H, dk, dv = 96, 30, 96, 192
    shape = (S, H // pack, dk, pack * dv)

    def step(state, q, k, v, g, beta, live):
        with _tpu_target():
            return dr.state_update(state, q, k, v, g, beta, live)

    sharding = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in (
                (shape, F32), ((S, H * dk), BF16), ((S, H * dk), BF16),
                ((S, H * dv), BF16), ((S, H), F32), ((S, H), F32),
                ((S,), jnp.int32))]
    compiled = jax.jit(step, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert dr.STATE_KERNEL_NAME in text
    moved = [line.strip()[:160] for line in text.splitlines() if re.match(
        r"\s*(?:ROOT )?%%\S+ = \w+\[%d,%d,%d,%d\]\S* "
        r"(copy|transpose|gather|copy-start)\(" % shape, line)]
    assert not moved, "the step moves a whole state array:\n" + "\n".join(moved)
    published = S * H * dk * dv * 4
    held = compiled.memory_analysis().argument_size_in_bytes
    if pack == 2:
        assert published < held < 1.05 * published, held
    else:
        assert held > 1.3 * published, held


def test_linear_decoder_step_copies_no_state_array_and_no_pool(v5e):
    """The whole decode program of the delta-rule decoder (an attention
    layer then three linear layers at the served head widths, 8 slots, 4
    token steps a dispatch) compiled for the described chip as the
    executor builds it: no copy, transpose or gather of a matrix state, a
    convolution window or a K/V pool."""
    import re
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _described_compile as dc
    from paddle_tpu.models import linear_attn_moe_decoder as lad

    S, positions, ps = 8, 1024, 128
    desc = dict(
        hidden_size=512, num_attention_heads=64, num_key_value_heads=8,
        head_dim=128, linear_attn_config=dict(
            short_conv_kernel_size=4, head_dim=128, num_heads=64,
            num_kv_heads=None),
        num_hidden_layers=4, gqa_layers=[0], vocab_size=2048,
        moe_intermediate_size=256, n_routed_experts=8,
        expert_shard={"of": 64, "first": 0}, n_shared_experts=1,
        num_experts_per_tok=8, norm_topk_prob=True, routed_scaling_factor=1,
        rms_norm_eps=1e-5, first_k_dense_replace=0, use_rope=False,
        use_gqa_gate=True, kda_use_full_proj=False,
        kda_allow_neg_eigval=True, tie_word_embeddings=False)
    built = lad.build_linear_attn_moe_decoder(
        desc, S, positions, ps, [512, 1024], prefill_token_budget=1024,
        tokens_per_dispatch=4)
    geo = built["geometry"]
    state = dict(lad.parameter_shapes(desc, "bfloat16"))
    moved_shapes = []
    for kind in ("page_pools", "slot_arrays"):
        for name, a in geo["state"][kind].items():
            state[name] = (a["shape"], a["dtype"])
            moved_shapes.append(",".join(str(n) for n in a["shape"]))
    state["lad_tok"] = state["lad_pos"] = ((S, 1), "int64")
    fetch = built["fetches"]
    with _tpu_target():
        text = dc.compile_program(
            built["step"], v5e[0], state,
            {"page_table": ((S, geo["pages_per_slot"]), "int64"),
             "live": ((S, 1), "int64")},
            [fetch["token"], fetch["expert_tokens"]], steps=4).as_text()
    from paddle_tpu.kernels import delta_rule as dr

    assert dr.STATE_KERNEL_NAME in text and "ssm_conv_step" in text
    assert "gqa_paged_decode_attention" in text
    moved = [line.strip()[:160] for line in text.splitlines() if re.match(
        r"\s*(?:ROOT )?%%\S+ = \w+\[(%s)\]\S* "
        r"(copy|transpose|gather|copy-start)\(" % "|".join(
            sorted(set(moved_shapes))), line)]
    assert not moved, "the step moves a state array or a pool:\n" \
        + "\n".join(moved)


# -- the Mamba-2 / expert decoder (kernels/ssd.py) ----------------------------

@pytest.mark.parametrize("B,T", [(1, 4096), (16, 256)])
def test_ssd_prefill_lowers_for_tpu_at_the_served_widths(v5e, B, T):
    """The chunked Mamba-2 prefill of the 32B-A9B cell: 128 heads of 64 on
    a state of 128, one prompt of 4096 tokens and a bucket row of 16 of
    256."""
    from paddle_tpu.kernels import ssd

    H, P, N = 128, 64, 128
    text = _compile_v5e(
        v5e, lambda x, dt, a, b, c, d, lens: ssd.chunk_prefill(
            x, dt, a, b, c, d, lens, force_pallas=True),
        ((B, T, H * P), BF16), ((B, T, H), F32), ((H,), F32),
        ((B, T, N), BF16), ((B, T, N), BF16), ((H,), F32),
        ((B,), jnp.int32))
    assert ssd.CHUNK_KERNEL_NAME in text


def test_ssd_update_updates_the_state_in_place(v5e):
    """The one-token update at the served sizes (64 slots, 128 heads, a
    64 x 128 float32 state a head, two heads a lane group), the state
    donated: Mosaic takes it, the compiled program holds no copy of the
    state, keeps its one layout and its temporaries are a small fraction
    of the 268 MB state of one layer."""
    import re

    from paddle_tpu.kernels import ssd

    # 64 slots, 128 heads of 64 x 128: [slots, lane groups, d_state, lanes]
    S, H, P, N = shape = ssd.state_shape(64, 128, 64, 128)
    assert shape == (64, 64, 128, 128)

    def step(state, x, dt, a, b, c, d, live):
        with _tpu_target():
            return ssd.state_update(state, x, dt, a, b, c, d, live)

    sharding = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
            for shape, dt in (
                ((S, H, P, N), F32), ((S, 8192), BF16), ((S, 128), F32),
                ((128,), F32), ((S, 128), BF16), ((S, 128), BF16),
                ((128,), F32), ((S,), jnp.int32))]
    compiled = jax.jit(step, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert ssd.STATE_KERNEL_NAME in text
    moved = [line.strip()[:160] for line in text.splitlines() if re.match(
        r"\s*(?:ROOT )?%%\S+ = \w+\[%d,%d,%d,%d\]\S* "
        r"(copy|transpose|gather|copy-start)\(" % (S, H, P, N), line)]
    assert not moved, "the step moves a whole state array:\n" + "\n".join(moved)
    assert compiled.memory_analysis().temp_size_in_bytes < S * H * P * N * 4 // 8
    assert set(re.findall(r"\[%d,%d,%d,%d\]\{([\d,]+)" % (S, H, P, N), text)) \
        == {"3,2,1,0"}


def test_ssd_convolutions_lower_at_the_8448_wide_row(v5e):
    """The ``x | B | C`` row is 8448 = 66 vectors wide, which 512 does not
    divide: the convolutions take the most lanes under 512 that do (384),
    not the whole row (33 MB of VMEM a block, which the compiler
    refuses)."""
    from paddle_tpu.kernels import selective_scan as ss

    d, S = 8448, 64
    assert ss._tile(d, 512) == 384
    text = _compile_v5e(
        v5e, lambda x, w, b: ss.causal_conv(x, w, b, force_pallas=True),
        ((1, 4096, d), BF16), ((4, d), BF16), ((d,), BF16))
    assert ss.CONV_KERNEL_NAME in text
    text = _compile_v5e(
        v5e, lambda win, x, w, b, live: ss.conv_step(
            win, x, w, b, live, force_pallas=True),
        ((3, S, d), BF16), ((S, d), BF16), ((4, d), BF16), ((d,), BF16),
        ((S,), jnp.int32))
    assert ss.CONV_STEP_KERNEL_NAME in text


# -- the trainer's loss head: what the compiled step stores of the logits ----

def _entry_ops(text):
    """[(result type, opcode, operands' result types, line)] of the compiled
    text's ENTRY computation (what runs as the device's ops)."""
    import re

    lines = text.splitlines()
    start = next(k for k, l in enumerate(lines) if l.startswith("ENTRY "))
    ops, types = [], {}
    for line in lines[start + 1:]:
        if line.startswith("}"):
            break
        m = re.match(
            r"\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\(([^)]*)\)", line)
        if m:
            types[m.group(1)] = m.group(2)
            ops.append((m.group(2), m.group(3),
                        re.findall(r"%([\w.-]+)", m.group(4)), line.strip()))
    return [(res, opc, [types.get(o, "") for o in operands], line)
            for res, opc, operands, line in ops]


def _arrays_of(type_text, elems):
    """(dtype, dims) of every array in an HLO type with ``elems`` elements
    or more."""
    import re

    found = []
    for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", type_text):
        if int(np.prod([int(d) for d in dims.split(",") if d])) >= elems:
            found.append((dtype, dims))
    return found


def _logits_traffic(text, elems):
    """(writes, reads, gathers) of logits-sized arrays by the ENTRY ops:
    ``writes`` the (dtype, dims, line) an op other than a parameter
    produces, ``reads`` the lines that take one as an operand, ``gathers``
    those of them that are a gather or a kCustom fusion (a gather's
    operand cannot be fused: it is stored whole first)."""
    writes, reads, gathers = [], [], []
    for res, opc, operand_types, line in _entry_ops(text):
        if opc in ("parameter", "get-tuple-element", "bitcast", "tuple"):
            continue
        writes += [(d, dims, line[:200]) for d, dims in _arrays_of(res, elems)]
        if any(_arrays_of(t, elems) for t in operand_types):
            reads.append(line[:200])
            if opc == "gather" or "kind=kCustom" in line:
                gathers.append(line[:200])
    return writes, reads, gathers


_TRAINER_STEP = {}


def _trainer_step(v5e):
    """The trainer's step (``perfbench/train_common.build_program``: the
    model at ``transformer_big``'s published widths, label smoothing 0.1,
    Adam, the bf16 AMP rewrite; ONE layer, batch 8) compiled for the
    described chip, once a process: ``(compiled, B, T, V)``."""
    import sys

    if _TRAINER_STEP:
        return _TRAINER_STEP["step"]
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _described_compile as dc
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer
    from paddle_tpu.transpiler import rewrite_program_amp

    B, T, V = 8, 256, 32000
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss, feeds, _ = transformer.build(
            src_vocab_size=V, trg_vocab_size=V, max_length=T, n_layer=1,
            n_head=16, d_model=1024, d_inner=4096, dropout=0.1,
            label_smooth_eps=0.1)
        fluid.optimizer.Adam(learning_rate=2e-4).minimize(loss)
    rewrite_program_amp(main, "bfloat16")
    state = {name: (var.shape, var.dtype)
             for name, var in main.global_block().vars.items()
             if var.persistable}
    feed = {var.name: ((B,) + tuple(var.shape[1:]), var.dtype)
            for var in feeds}
    with _tpu_target():
        compiled = dc.compile_program(main, v5e[0], state, feed, [loss.name],
                                      is_test=False)
    _TRAINER_STEP["step"] = (compiled, B, T, V)
    return _TRAINER_STEP["step"]


def test_training_head_stores_only_bfloat16_logits(v5e):
    """Of the trainer's step's ``[tokens, 32000]`` head only the bfloat16
    logits the projection writes are stored. The label's log-probability
    is a masked sum that fuses into a pass that reads them; a gather (as
    ``take_along_axis`` was) makes XLA store ``logits - lse`` whole in
    float32 first, twice at 2.1 GB a step in the benchmark's cell."""
    compiled, B, T, V = _trainer_step(v5e)
    writes, reads, gathers = _logits_traffic(compiled.as_text(), B * T * V)
    assert [(d, dims) for d, dims, _ in writes] in (
        [("bf16", "%d,%d,%d" % (B, T, V))], [("bf16", "%d,%d" % (B * T, V))]
    ), "the head stores more than its bfloat16 logits:\n" + "\n".join(
        "%s[%s] %s" % w for w in writes)
    # the two backward products, a row statistic, the bias gradient's pass
    assert 3 <= len(reads) <= 4, reads
    assert not gathers, "\n".join(gathers)
    # 431 949 312 bytes as repaired (the parent: 566 277 632), + 10%
    assert compiled.memory_analysis().temp_size_in_bytes < 476e6


def _fused_bodies(text):
    """{computation name: its lines} of the compiled text."""
    import re

    bodies, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"%?([\w.-]+) \(.*\) -> .* \{$", line)
        if m:
            cur = bodies.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return bodies


def test_ffn_second_product_weight_gradient_stands_alone(v5e):
    """In the trainer's step the gradient of ``*_ffn_fc2.w_0`` (``[4096,
    1024]``: the input is WIDER than the output) is a product of its own
    (``ops/math_ops.py``: ``_lone_weight_grad``; its result the bfloat16
    gradient) and the Adam update runs without a product inside and in the
    parameter's own layout: no ``copy`` of a float32 ``[4096, 1024]`` array
    in the ENTRY computation. The first product's ``[1024, 4096]`` update
    keeps its product, as ``jax.vjp`` forms it. Fused with its update and
    with the fusion that rebuilds ``dOut``, eleven of the cell's twelve
    such products ran at ~29% of the matrix unit's peak on the chip; alone
    and on a stored ``dOut`` they run at ~95% (PERF.md section 6, PR 54)."""
    import re

    text = _trainer_step(v5e)[0].as_text()
    bodies = _fused_bodies(text)

    def has_product(line):
        called = re.search(r"calls=%([\w.-]+)", line)
        return any(" convolution(" in b for b in bodies[called.group(1)])

    updates = {"4096,1024": [], "1024,4096": []}
    alone, copies = [], []
    for res, opc, _operands, line in _entry_ops(text):
        if opc == "fusion":
            for dims in updates:
                if "f32[%s]" % dims in res:
                    updates[dims].append(has_product(line))
            if re.match(r"bf16\[4096,1024\]", res) and has_product(line):
                alone.append(line[:160])
        if opc == "copy" and "f32[4096,1024]" in res:
            copies.append(line[:160])
    # an encoder and a decoder layer: two weights of each shape
    assert updates["4096,1024"] == [False, False], updates
    assert len(alone) == 2, alone
    assert updates["1024,4096"] == [True, True], updates
    assert not copies, "\n".join(copies)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_softmax_cross_entropy_writes_no_float32_copy_of_the_logits(v5e, dtype):
    """``softmax_with_cross_entropy`` + ``mean`` alone on fed logits
    ``[2048, 32000]`` (bfloat16 ones under the AMP rewrite, whose black
    list casts them up for the loss): the passes read the fed array and
    write nothing of its size."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _described_compile as dc
    import paddle_tpu as fluid
    from paddle_tpu.transpiler import rewrite_program_amp

    N, V = 2048, 32000
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        logits = fluid.layers.data("logits", shape=[V], dtype=dtype)
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
    if dtype == "bfloat16":
        rewrite_program_amp(main, "bfloat16")
    feed = {"logits": ((N, V), dtype), "label": ((N, 1), "int64")}
    compiled = dc.compile_program(main, v5e[0], {}, feed, [loss.name])
    writes, reads, gathers = _logits_traffic(compiled.as_text(), N * V)
    assert not writes, "\n".join("%s[%s] %s" % w for w in writes)
    assert reads and not gathers, (reads, gathers)


# -- the shortcut decoder (perfbench longcat_flash_omni_4l) --------------------

@pytest.mark.parametrize("B,T", [(1, 4096), (8, 512)])
def test_flash_forward_lowers_at_a_value_width_of_its_own(v5e, B, T):
    """The cell's prefill attention: 64 heads, queries and keys of 192
    beside values of 128 in bfloat16, one prompt of the longest bucket and
    eight of the shortest. Mosaic takes the forward with its second
    product, accumulator and output at the VALUES' width: no operand or
    result of the call is 192 (or 256) wide where a value is."""
    q = ((B, 64, T, 192), BF16)
    v = ((B, 64, T, 128), BF16)
    text = _compile_v5e(
        v5e, lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        q, q, v)
    (call,) = [ln for ln in text.splitlines()
               if "custom_call_target=\"tpu_custom_call\"" in ln
               and "flash_attention_fwd" in ln]
    assert "bf16[%d,64,%d,128]" % (B, T) in call.split(" custom-call(")[0]
    # two operands of 192 (q, k), one of 128 (v)
    operands = call.split(" custom-call(")[1]
    assert operands.count("bf16[%d,64,%d,192]" % (B, T)) == 2
    assert operands.count("bf16[%d,64,%d,128]" % (B, T)) == 1


def test_shortcut_decoder_programs_fit_the_chip_at_the_served_sizes(v5e):
    """The cell's own configuration (``perfbench/configs/
    longcat_flash_omni_4l.json``: 4 layers of two latent blocks, 16 of 512
    experts beside 256 identities, 64 slots of 5120 positions, eight
    pools) compiled for the described chip from shapes alone: the decode
    dispatch and the fullest prefill (4096 token places). Mosaic takes the
    kernels at these widths (64 heads over a 640-lane pool; 192 beside
    128), the instructions say which sub-block they are, the pools are
    updated in place, and arguments + temporaries stay under 15.0 GB of
    the chip's 17.18 (PERF.md, PR 49: 13.70 + 0.21 and 13.70 + 1.05)."""
    import json
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _described_compile as dc
    from paddle_tpu.models import shortcut_moe_decoder as scd

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs",
                           "longcat_flash_omni_4l.json")) as f:
        cfg = json.load(f)
    pool = cfg["pool"]
    S = pool["num_slots"]
    built = scd.build_shortcut_moe_decoder(
        cfg, S, pool["max_prompt"] + pool["max_new_tokens"],
        pool["page_size"], pool["prefill_buckets"],
        prefill_token_budget=pool["prefill_token_budget"],
        tokens_per_dispatch=pool["tokens_per_dispatch"],
        prefill_rungs=True, probe_rows=2)
    geo, f = built["geometry"], built["fetches"]
    npp = geo["pages_per_slot"]
    state = dict(scd.parameter_shapes(cfg, "bfloat16"))
    for name, a in geo["state"]["page_pools"].items():
        state[name] = (a["shape"], a["dtype"])
    assert len(geo["state"]["page_pools"]) == 2 * cfg["num_layers"]
    state["scd_tok"] = state["scd_pos"] = ((S, 1), "int64")

    with _tpu_target():
        step = dc.compile_program(
            built["step"], v5e[0], state,
            {"page_table": ((S, npp), "int64"), "live": ((S, 1), "int64"),
             "probe_slots": ((2,), "int64")},
            [f["token"], f["expert_tokens"], f["zero_tokens"],
             f["probe_logits"], f["chosen"]], steps=4)
        T = pool["prefill_buckets"][-1]
        prefill = dc.compile_program(
            built["prefill_rungs"][T][1], v5e[0], state,
            {"prompt_ids": ((T,), "int64"), "prompt_len": ((1,), "int64"),
             "slot_idx": ((1,), "int64"), "page_rows": ((1, npp), "int64"),
             "last_idx": ((1,), "int64")},
            [f["first_token"], f["first_logits"], f["first_chosen"]])
    text = step.as_text()
    for name in ("latent_paged_decode_attention", "gmm", "shortcut_moe",
                 "dense_ffn_0", "dense_ffn_1"):
        assert name in text, name
    pool_elems = int(np.prod(geo["state"]["page_pools"]["scd_pool_0"][
        "shape"]))
    _assert_moves_no_pool(text, pool_elems)
    assert "flash_attention_fwd" in prefill.as_text()
    assert step.memory_analysis().alias_size_in_bytes >= 8 * pool_elems * 2
    assert _peak_bytes(step) < 14.2e9, _peak_bytes(step)
    assert _peak_bytes(prefill) < 15.0e9, _peak_bytes(prefill)


# -- the linear / latent decoder (perfbench kimi_linear_5l) --------------------

def test_linear_latent_decoder_programs_fit_the_chip_at_the_served_sizes(v5e):
    """The cell's own configuration (``perfbench/configs/
    kimi_linear_5l.json``: four delta-rule layers and one NoPE latent
    layer, a leading dense layer, 64 of 256 experts, 384 slots of 8192
    positions) compiled for the described chip from shapes alone: the
    decode dispatch and the fullest prefill of the longest bucket. Mosaic
    takes the kernels at these shapes (the delta rule at ``[384, 32, 128,
    128]``, the absorbed decode at 32 heads over a 640-lane pool, the
    flash forward at 192 beside 128), the instructions say which sub-block
    they are, the matrix states and the latent pool are updated in place,
    and arguments + temporaries stay under 15.0 GB of the chip's 17.18
    (PERF.md, PR 53: 11.93 + 0.06 and 11.93 + 0.73)."""
    import json
    import re
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _described_compile as dc
    from paddle_tpu.kernels import delta_rule as dr
    from paddle_tpu.models import linear_attn_moe_decoder as lad

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs",
                           "kimi_linear_5l.json")) as f:
        cfg = json.load(f)
    pool = cfg["pool"]
    S = pool["num_slots"]
    built = lad.build_linear_attn_moe_decoder(
        cfg, S, pool["max_prompt"] + pool["max_new_tokens"],
        pool["page_size"], pool["prefill_buckets"],
        prefill_token_budget=pool["prefill_token_budget"],
        tokens_per_dispatch=pool["tokens_per_dispatch"],
        prefill_rungs=True, probe_rows=2)
    geo, f = built["geometry"], built["fetches"]
    npp = geo["pages_per_slot"]
    state = dict(lad.parameter_shapes(cfg, "bfloat16"))
    shapes = []
    for kind in ("page_pools", "slot_arrays"):
        for name, a in geo["state"][kind].items():
            state[name] = (a["shape"], a["dtype"])
            shapes.append(",".join(str(n) for n in a["shape"]))
    assert list(geo["state"]["page_pools"]) == ["lad_pool_3"]
    assert geo["state"]["slot_arrays"]["lad_s_4"]["shape"] \
        == (384, 32, 128, 128)
    state["lad_tok"] = state["lad_pos"] = ((S, 1), "int64")

    with _tpu_target():
        step = dc.compile_program(
            built["step"], v5e[0], state,
            {"page_table": ((S, npp), "int64"), "live": ((S, 1), "int64"),
             "probe_slots": ((2,), "int64")},
            [f["token"], f["expert_tokens"], f["probe_logits"],
             f["chosen"]], steps=4)
        T = pool["prefill_buckets"][-1]
        B = pool["prefill_token_budget"] // T
        prefill = dc.compile_program(
            built["prefill_rungs"][T][B], v5e[0], state,
            {"prompt_ids": ((B * T,), "int64"),
             "prompt_len": ((B,), "int64"), "slot_idx": ((B,), "int64"),
             "page_rows": ((B, npp), "int64"), "last_idx": ((B,), "int64")},
            [f["first_token"], f["first_logits"], f["first_chosen"]])
    text = step.as_text()
    for name in ("kda_mixer/" + dr.STATE_KERNEL_NAME,
                 "latent_attention/jit(_latent_pallas)", "gmm",
                 "latent_paged_decode_attention", "dense_ffn/", "moe/"):
        assert name in text, name
    moved = [line.strip()[:160] for line in text.splitlines() if re.match(
        r"\s*(?:ROOT )?%%\S+ = \w+\[(%s)\]\S* "
        r"(copy|transpose|gather|copy-start)\(" % "|".join(
            sorted(set(shapes))), line)]
    assert not moved, "the step moves a state array or the pool:\n" \
        + "\n".join(moved)
    pre = prefill.as_text()
    assert "flash_attention_fwd" in pre and dr.CHUNK_KERNEL_NAME in pre
    resident = sum(int(np.prod(a["shape"])) * (4 if a["dtype"] == "float32"
                                               else 2)
                   for kind in ("page_pools", "slot_arrays")
                   for a in geo["state"][kind].values())
    assert step.memory_analysis().alias_size_in_bytes >= resident
    assert _peak_bytes(step) < 12.5e9, _peak_bytes(step)
    assert _peak_bytes(prefill) < 15.0e9, _peak_bytes(prefill)


def test_gated_delta_decoder_programs_fit_the_chip_at_the_served_sizes(v5e):
    """The cell's own configuration (``perfbench/configs/
    olmo_hybrid_8l.json``: six Gated DeltaNet layers and two multi-head
    attention layers, every FFN dense, the whole vocabulary, 96 slots of
    2048 positions) compiled for the described chip from shapes alone: the
    decode dispatch and the fullest prefill of every bucket. Mosaic takes
    the kernels at these shapes (the delta rule at 30 heads of 96 x 192
    under a decay a head, two heads a tile of the state; the grouped-query
    decode at a group of ONE over 3840-wide rows; the flash forward at 30
    heads of 128), the instructions say which sub-block they are, the
    matrix states and the K/V pools are updated in place, a linear layer's
    state is held at its published 2.21 MB a slot, and arguments +
    temporaries stay under 15.0 GB of the chip's 17.18 (PERF.md, PR 55:
    12.23 + 0.01 and 12.23 + 0.92)."""
    import json
    import re
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _described_compile as dc
    from paddle_tpu.kernels import delta_rule as dr
    from paddle_tpu.models import gated_delta_decoder as gdd

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs",
                           "olmo_hybrid_8l.json")) as f:
        cfg = json.load(f)
    pool = cfg["pool"]
    S = pool["num_slots"]
    built = gdd.build_gated_delta_decoder(
        cfg, S, pool["max_prompt"] + pool["max_new_tokens"],
        pool["page_size"], pool["prefill_buckets"],
        prefill_token_budget=pool["prefill_token_budget"],
        tokens_per_dispatch=pool["tokens_per_dispatch"],
        prefill_rungs=True, probe_rows=2)
    geo, f = built["geometry"], built["fetches"]
    npp = geo["pages_per_slot"]
    state = dict(gdd.parameter_shapes(cfg, "bfloat16"))
    shapes = []
    for kind in ("page_pools", "slot_arrays"):
        for name, a in geo["state"][kind].items():
            state[name] = (a["shape"], a["dtype"])
            shapes.append(",".join(str(n) for n in a["shape"]))
    assert list(geo["state"]["page_pools"]) == [
        "gdd_k_3", "gdd_v_3", "gdd_k_7", "gdd_v_7"]
    assert geo["state"]["page_pools"]["gdd_k_3"]["shape"] \
        == (1537, 128, 3840)
    assert geo["state"]["slot_arrays"]["gdd_s_6"]["shape"] \
        == (96, 15, 96, 384)
    assert geo["state"]["slot_arrays"]["gdd_win_6"]["shape"] \
        == (3, 96, 11520)
    assert geo["state_bytes_slot_layer"] == 30 * 96 * 192 * 4 == 2211840
    assert geo["kv_row_bytes"] == 15360
    state["gdd_tok"] = state["gdd_pos"] = ((S, 1), "int64")

    with _tpu_target():
        step = dc.compile_program(
            built["step"], v5e[0], state,
            {"page_table": ((S, npp), "int64"), "live": ((S, 1), "int64"),
             "probe_slots": ((2,), "int64")},
            [f["token"], f["probe_logits"]], steps=4)
        prefills = []
        for T in pool["prefill_buckets"]:
            B = pool["prefill_token_budget"] // T
            prefills.append(dc.compile_program(
                built["prefill_rungs"][T][B], v5e[0], state,
                {"prompt_ids": ((B * T,), "int64"),
                 "prompt_len": ((B,), "int64"),
                 "slot_idx": ((B,), "int64"),
                 "page_rows": ((B, npp), "int64"),
                 "last_idx": ((B,), "int64")},
                [f["first_token"], f["first_logits"]]))
    text = step.as_text()
    for name in ("gdn_mixer/" + dr.STATE_KERNEL_NAME,
                 "gdn_mixer/ssm_conv_step", "mha_attention/",
                 "gqa_paged_decode_attention", "dense_ffn/"):
        assert name in text, name
    moved = [line.strip()[:160] for line in text.splitlines() if re.match(
        r"\s*(?:ROOT )?%%\S+ = \w+\[(%s)\]\S* "
        r"(copy|transpose|gather|copy-start)\(" % "|".join(
            sorted(set(shapes))), line)]
    assert not moved, "the step moves a state array or a pool:\n" \
        + "\n".join(moved)
    resident = sum(int(np.prod(a["shape"])) * (4 if a["dtype"] == "float32"
                                               else 2)
                   for kind in ("page_pools", "slot_arrays")
                   for a in geo["state"][kind].values())
    # 6.04 GB of rows, 1.27 GB of state, 0.04 GB of windows
    assert round(resident / 1e9, 2) == 7.36
    assert step.memory_analysis().alias_size_in_bytes >= resident
    assert _peak_bytes(step) < 12.5e9, _peak_bytes(step)
    for pre in prefills:
        text = pre.as_text()
        assert "flash_attention_fwd" in text
        assert dr.CHUNK_KERNEL_NAME in text
        assert _peak_bytes(pre) < 15.0e9, _peak_bytes(pre)
