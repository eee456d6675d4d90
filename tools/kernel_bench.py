"""Pallas-vs-XLA kernel microbench (VERDICT r2 item 3: measure the Pallas
kernels or delete them).

For each kernel family the hand-written Pallas path is timed against the
XLA-composed lowering it replaces, at >= 3 shapes, THROUGH the op layer
(the flags/attrs users flip), so the numbers reflect what the framework
actually runs. Prints one JSON line per (kernel, shape, impl) plus a
closing summary with the per-kernel speedup and a default recommendation.

Usage (TPU host):   python tools/kernel_bench.py
CPU smoke:          BENCH_PLATFORM=cpu python tools/kernel_bench.py --quick
(on CPU the Pallas paths run in interpreter mode and are expected to lose
badly; only the TPU numbers decide flag defaults.)

A kernel timed alone says what the kernel costs, not what the cell pays:
a share of a cell's step printed here is taken on the HOST's clock around
back-to-back calls, so it is a LOWER bound on the kernel's share in the
cell, where the kernel also waits on its neighbours and on the host. The
cell's own share comes from its traced run (``perfbench/run.py --trace 1``).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time_steps(fn, steps, warmup):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn()
    # sync on the last value
    import numpy as np

    np.asarray(out)
    return (time.perf_counter() - t0) / steps


def _bench_rnn(fluid, op_name, flag, shapes, steps, warmup):
    import numpy as np

    rows = []
    for bs, seq, hidden in shapes:
        times = {}
        for use_pallas in (False, True):
            fluid.flags.set_flag(flag, use_pallas)
            try:
                from paddle_tpu import unique_name

                unique_name.switch()
                main, startup = fluid.Program(), fluid.Program()
                main.random_seed = 3
                startup.random_seed = 3
                with fluid.program_guard(main, startup):
                    x = fluid.layers.data(
                        name="x", shape=[seq, 4 * hidden
                                         if op_name == "dynamic_lstm"
                                         else 3 * hidden],
                        dtype="float32")
                    if op_name == "dynamic_lstm":
                        out, _ = fluid.layers.dynamic_lstm(
                            input=x, size=4 * hidden)
                    else:
                        out = fluid.layers.dynamic_gru(
                            input=x, size=hidden)
                    loss = fluid.layers.reduce_mean(out)
                with fluid.scope_guard(fluid.executor.Scope()):
                    exe = fluid.Executor(fluid.TPUPlace()
                                         if _on_tpu() else fluid.CPUPlace())
                    exe.run(startup)
                    width = (4 if op_name == "dynamic_lstm" else 3) * hidden
                    feed = {"x": np.random.RandomState(0).rand(
                        bs, seq, width).astype("float32")}
                    dt = _time_steps(
                        lambda: exe.run(main, feed=feed,
                                        fetch_list=[loss])[0],
                        steps, warmup)
                times["pallas" if use_pallas else "xla"] = dt
            finally:
                fluid.flags.set_flag(flag, False)
        row = {"kernel": op_name, "shape": [bs, seq, hidden],
               "xla_ms": round(times["xla"] * 1e3, 3),
               "pallas_ms": round(times["pallas"] * 1e3, 3),
               "speedup": round(times["xla"] / times["pallas"], 3)}
        print(json.dumps(row))
        rows.append(row)
    return rows


def _bench_flash(fluid, shapes, steps, warmup, window=0):
    """window > 0 also times the sliding-window pruned kernel vs the
    windowed reference at the same shape — the O(window) wall-time
    proof interpret mode cannot provide."""
    import numpy as np

    rows = []
    for b, h, t, d in shapes:
        times = {}
        rng = np.random.RandomState(1)
        feed = {
            "q": rng.randn(b, h, t, d).astype("float32"),
            "k": rng.randn(b, h, t, d).astype("float32"),
            "v": rng.randn(b, h, t, d).astype("float32"),
        }
        for impl in ("reference", "pallas"):
            from paddle_tpu import unique_name

            unique_name.switch()
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                q = fluid.layers.data(name="q", shape=[h, t, d])
                kk = fluid.layers.data(name="k", shape=[h, t, d])
                v = fluid.layers.data(name="v", shape=[h, t, d])
                for var in (q, kk, v):
                    var.stop_gradient = False
                out = fluid.layers.scaled_dot_product_attention(
                    q, kk, v, causal=True, impl=impl, window=window)
                loss = fluid.layers.reduce_mean(out)
                # fwd+bwd: flash attention's win is the backward pass
                fluid.optimizer.SGD(learning_rate=0.0).minimize(
                    loss, parameter_list=[])
            with fluid.scope_guard(fluid.executor.Scope()):
                exe = fluid.Executor(fluid.TPUPlace()
                                     if _on_tpu() else fluid.CPUPlace())
                exe.run(startup)
                dt = _time_steps(
                    lambda: exe.run(main, feed=feed,
                                    fetch_list=[loss])[0],
                    steps, warmup)
            times[impl] = dt
        row = {"kernel": "flash_attention"
               + ("_w%d" % window if window else ""),
               "shape": [b, h, t, d],
               "xla_ms": round(times["reference"] * 1e3, 3),
               "pallas_ms": round(times["pallas"] * 1e3, 3),
               "speedup": round(times["reference"] / times["pallas"], 3)}
        print(json.dumps(row))
        rows.append(row)
    return rows


def _bench_paged_decode(shapes, calls, steps, warmup):
    """The paged decode kernel alone against the composed gather +
    softmax, at the served pool (pages of whole token rows) with some of
    the slots live: ``calls`` dependent calls inside one jit (a decode
    dispatch makes tokens x layers of them), so the time a call is the
    kernel's own and not a dispatch's. A live slot holds a uniform share
    of a target drawn as the serving cells draw theirs (lognormal source,
    median 24, sigma 0.6, x 0.9-1.3); the other slots are empty and sit
    on the trash page."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import paged_attention as pa

    rows = []
    for S, live, H, dh, ps, T in shapes:
        npp = pa.pages_for(T, ps)
        rng = np.random.RandomState(live)
        target = np.clip(
            np.exp(rng.normal(np.log(24.0), 0.6, S))
            * rng.uniform(0.9, 1.3, S), 4, T)
        lengths = np.ceil(target * rng.uniform(0.0, 1.0, S)).astype("int32")
        lengths[rng.permutation(S)[live:]] = 0
        table = np.zeros((S, npp), "int32")
        nxt = 1
        for slot in range(S):
            n = pa.pages_for(lengths[slot], ps)
            table[slot, :n] = np.arange(nxt, nxt + n)
            table[slot, n:] = table[slot, max(n - 1, 0)]
            nxt += n
        P = 1 + S * npp
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        kp = jax.random.normal(k1, (P, ps, H * dh), jnp.float32)
        vp = jax.random.normal(k2, (P, ps, H * dh), jnp.float32)
        q = jax.random.normal(k3, (S, H, dh), jnp.float32)
        table, lens = jnp.asarray(table), jnp.asarray(lengths)
        times = {}
        for impl in ("reference", "pallas"):
            def run(q, kp, vp, impl=impl):
                def body(q, _):
                    out = pa.paged_attention(
                        q, kp, vp, table, lens,
                        force_reference=impl == "reference",
                        force_pallas=impl == "pallas")
                    return q + 1e-3 * out, None

                return jax.lax.scan(body, q, None, length=calls)[0]

            fn = jax.jit(run)
            times[impl] = _time_steps(lambda: fn(q, kp, vp),
                                      steps, warmup) / calls
        acct = pa.grid_accounting(lengths, ps, H, dh, T)
        row = {"kernel": pa.PAGED_KERNEL_NAME,
               "shape": [S, live, H, dh, ps, T],
               "grid_steps": acct["grid_steps"],
               "page_walks": acct["page_walks"],
               "total_page_slots": acct["total_page_slots"],
               "xla_ms": round(times["reference"] * 1e3, 4),
               "pallas_ms": round(times["pallas"] * 1e3, 4),
               "speedup": round(times["reference"] / times["pallas"], 3)}
        print(json.dumps(row))
        rows.append(row)
    return rows


def _resident_lengths(traffic, slots, max_length, seed):
    """What ``slots`` slots hold above the knee under a traffic file: a
    slot is met inside a request for as long as its answer lasts, so the
    (prompt, answer) pairs are drawn in proportion to the answer and the
    slot holds the prompt and a uniform part of the answer."""
    import numpy as np

    from perfbench import loadgen

    rng = np.random.RandomState(seed)
    n = 4 * int(traffic["clients"])
    src = loadgen.draw_lengths(traffic["src_len"], n)[rng.permutation(n)]
    trg = loadgen.draw_lengths(traffic["trg_len"], n)
    took = rng.choice(n, slots, p=trg / float(trg.sum()))
    lengths = src[took] + np.floor(
        rng.uniform(0.0, 1.0, slots) * trg[took]).astype(np.int64)
    return np.minimum(lengths, max_length).astype("int32")


def _bench_latent_decode(cases, walks, steps, warmup, seed=0):
    """The absorbed latent decode kernel alone at a served cell's
    geometry (``perfbench/configs/<config>.json``: slots, heads, table
    width, the 640-wide bfloat16 pool of 128-row pages) with every slot
    holding what the cell's traffic leaves in it: ``calls`` dependent
    calls inside one jit (a decode dispatch makes tokens x pools of them).
    A row a walk (``group``: pages a step of the walk; ``{}`` is the
    wrapper's own rule): ms a call,
    the resident pages over the table's, the share of the cost model's
    least time that the call reaches, and one call's distance from
    ``latent_paged_attention_reference``."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import latent_attention as la

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench")
    with open(os.path.join(root, "peaks.json")) as f:
        peaks = json.load(f)["chips"][0]
    rows = []
    for config, traffic, costs, calls, resize in cases:
        with open(os.path.join(root, "configs", config + ".json")) as f:
            cfg = json.load(f)
        with open(os.path.join(root, "traffic", traffic + ".json")) as f:
            mix = json.load(f)
        costs = importlib.import_module("perfbench." + costs)
        pool_cfg = dict(cfg["pool"], **resize)
        S, ps = pool_cfg["num_slots"], pool_cfg["page_size"]
        H, C = cfg["num_attention_heads"], cfg["kv_lora_rank"]
        R = cfg["qk_rope_head_dim"]
        T = pool_cfg["max_prompt"] + pool_cfg["max_new_tokens"]
        npp = -(-T // ps)
        lengths = _resident_lengths(mix, S, T, seed)
        pages = -(-lengths // ps)
        table = np.zeros((S, npp), "int32")
        order = 1 + np.random.RandomState(seed).permutation(int(pages.sum()))
        at = 0
        for slot in range(S):
            table[slot, :pages[slot]] = order[at:at + pages[slot]]
            at += pages[slot]
        dtype = jnp.dtype(cfg["dtype"])
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        pool = jax.random.normal(
            k1, (1 + S * npp, ps, la.pool_width(C + R)), dtype)
        q_lat = jax.random.normal(k2, (S, H, C), dtype)
        q_rope = jax.random.normal(k3, (S, H, R), dtype)
        table, lens = jnp.asarray(table), jnp.asarray(lengths)
        sm_scale = (cfg["qk_nope_head_dim"] + R) ** -0.5
        least = costs.least_seconds(*costs.latent_decode_attention(
            cfg, int(lengths.sum()), S), peaks)
        # the reference gathers a slot's whole table in float32: a few
        # slots at a time, so that 384 slots of 64 pages fit beside the pool
        part = max(1, (1 << 28) // (npp * ps * C))
        want = np.concatenate([np.asarray(
            la.latent_paged_attention_reference(
                q_lat[at:at + part], q_rope[at:at + part], pool,
                table[at:at + part], lens[at:at + part], sm_scale),
            "float32") for at in range(0, S, part)])
        for walk in walks:
            got = np.asarray(la._latent_pallas(
                q_lat, q_rope, pool, table, lens, sm_scale,
                interpret=not _on_tpu(), **walk), "float32")

            def run(q_lat, q_rope, pool, walk=walk):
                def body(q, _):
                    out = la._latent_pallas(
                        q, q_rope, pool, table, lens, sm_scale,
                        interpret=not _on_tpu(), **walk)
                    return q + (1e-3 * out).astype(q.dtype), None

                return jax.lax.scan(body, q_lat, None, length=calls)[0]

            fn = jax.jit(run)
            secs = _time_steps(lambda: fn(q_lat, q_rope, pool), steps,
                               warmup) / calls
            row = {"kernel": la.LATENT_KERNEL_NAME, "config": config,
                   "traffic": traffic, "shape": [S, H, npp, ps],
                   "walk": walk, "rows": int(lengths.sum()),
                   "resident_pages": int(pages.sum()),
                   "table_pages": S * npp,
                   "pallas_ms": round(secs * 1e3, 4),
                   "least_ms": round(least * 1e3, 4),
                   "roofline_share": round(least / secs, 4),
                   "rel_l2": float(np.linalg.norm(got - want)
                                   / np.linalg.norm(want))}
            print(json.dumps(row))
            rows.append(row)
    return rows


def _bench_gqa_decode(cases, walks, calls, steps, warmup, seed=0):
    """The grouped-query decode kernels alone at a served cell's geometry
    (``perfbench/configs/<config>.json``: slots, query heads on key/value
    heads, the table's width, bfloat16 pools of 128-row pages) with every
    slot holding what the cell's traffic leaves in it: ``calls`` dependent
    calls inside one jit. A configuration with a ``sliding_window`` also
    runs the ring kernel over the same slots. A row a
    kernel and walk (``group``: pages a step of the walk; ``{}`` is the
    wrapper's own rule): ms a call, the grid's steps, the page walks and
    what a (slot, page) grid would step through, the share of the cost
    model's least time that the call reaches, and one call's distance
    from the kernel's reference."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import gqa_paged_attention as gq
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.kernels import window_paged_attention as wp
    from paddle_tpu.models import windowed_moe_decoder as wmd

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench")
    with open(os.path.join(root, "peaks.json")) as f:
        peaks = json.load(f)["chips"][0]
    rows = []
    for config, traffic, costs, resize in cases:
        with open(os.path.join(root, "configs", config + ".json")) as f:
            cfg = json.load(f)
        with open(os.path.join(root, "traffic", traffic + ".json")) as f:
            mix = json.load(f)
        pool_cfg = dict(cfg["pool"], **resize)
        S, ps = pool_cfg["num_slots"], pool_cfg["page_size"]
        H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        dh = cfg.get("head_dim") or cfg["hidden_size"] // H
        T = pool_cfg["max_prompt"] + pool_cfg["max_new_tokens"]
        lengths = _resident_lengths(mix, S, T, seed)
        dtype = jnp.dtype(cfg["dtype"])
        sm_scale = dh ** -0.5
        rng = np.random.RandomState(seed)
        k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(k3[2], (S, H, dh), dtype)
        lens = jnp.asarray(lengths)

        def least(visible):
            # kernel_costs_*.gqa_decode_attention's arithmetic (the
            # Mamba-2 cell's module has none): each visible K and V row
            # read once a key/value head, queries read, outputs written
            if costs:
                module, fn = costs
                module = importlib.import_module("perfbench." + module)
                return module.least_seconds(
                    *getattr(module, fn)(cfg, visible, S), peaks)
            return max(4.0 * H * dh * visible / peaks["bf16_flops_per_s"],
                       (2 * visible * Hkv + 2 * S * H) * dh * 2
                       / peaks["hbm_bytes_per_s"])

        def pools(width_pages):
            shape = (1 + S * width_pages, ps, Hkv * dh)
            return (jax.random.normal(k3[0], shape, dtype),
                    jax.random.normal(k3[1], shape, dtype))

        def table_of(first_page, last_page, width, ring):
            """A slot's resident logical pages ``first_page .. last_page``
            over distinct shuffled page ids, in column ``page % width``; a
            full table's tail holds the slot's last valid id (the host's
            fill), a ring's other columns the trash page."""
            table = np.zeros((S, width), "int32")
            held = np.where(lengths > 0, last_page - first_page + 1, 0)
            ids, at = 1 + rng.permutation(int(held.sum())), 0
            for slot in range(S):
                n = int(held[slot])
                cols = (int(first_page[slot]) + np.arange(n)) % width
                table[slot, cols] = ids[at:at + n]
                if n and not ring:
                    table[slot, n:] = table[slot, n - 1]
                at += n
            return table, held

        last = np.maximum(lengths - 1, 0) // ps
        kernels = [("full", -(-T // ps), np.zeros(S, "int64"), lengths)]
        if H == Hkv:
            # multi-head: the Transformer's kernel (every head on the
            # lanes of one row) takes these pools too; one row, its rule
            kernels.append(("paged", -(-T // ps), np.zeros(S, "int64"),
                            lengths))
        window = pool_cfg.get("window", cfg.get("sliding_window"))
        if window:
            first = np.maximum(lengths - window, 0)
            kernels.append(("ring", wmd.ring_pages_per_slot(
                window, pool_cfg["tokens_per_dispatch"], ps), first // ps,
                lengths - first))
        for kind, width, first_page, visible in kernels:
            table, held = table_of(first_page, last, width, kind == "ring")
            table = jnp.asarray(table)
            kp, vp = pools(width)
            if kind == "ring":
                first = jnp.maximum(lens - window, 0)
                name = wp.WINDOW_KERNEL_NAME

                def kernel(q, kp, vp, **walk):
                    return wp._window_pallas(
                        q, kp, vp, table, first, lens, sm_scale,
                        interpret=not _on_tpu(), **walk)

                def reference(rows):
                    return wp.window_paged_attention_reference(
                        q[rows], kp, vp, table[rows], lens[rows], window,
                        sm_scale)
            else:
                name = gq.GQA_KERNEL_NAME

                def kernel(q, kp, vp, **walk):
                    return gq._gqa_pallas(
                        q, kp, vp, table, lens, sm_scale,
                        interpret=not _on_tpu(), **walk)

                if kind == "paged":
                    name = pa.PAGED_KERNEL_NAME

                    def kernel(q, kp, vp):
                        return pa._paged_pallas(
                            q, kp, vp, table, lens, sm_scale,
                            interpret=not _on_tpu())

                def reference(rows):
                    return gq.gqa_paged_attention_reference(
                        q[rows], kp, vp, table[rows], lens[rows], sm_scale)

            # the reference gathers a slot's whole table in float32: eight
            # slots at a time
            want = np.concatenate([
                np.asarray(reference(slice(at, at + 8)), "float32")
                for at in range(0, S, 8)])
            floor = least(int(visible.sum()))
            for walk in (walks if kind != "paged" else ({},)):
                try:
                    got = np.asarray(kernel(q, kp, vp, **walk), "float32")
                except Exception as exc:    # a walk Mosaic refuses: a row
                    row = {"kernel": name, "config": config, "walk": walk,
                           "refused": str(exc).splitlines()[0][:240]}
                    print(json.dumps(row))
                    rows.append(row)
                    continue

                def run(q, kp, vp, walk=walk):
                    def body(q, _):
                        out = kernel(q, kp, vp, **walk)
                        return q + (1e-3 * out).astype(q.dtype), None

                    return jax.lax.scan(body, q, None, length=calls)[0]

                fn = jax.jit(run)
                secs = _time_steps(lambda: fn(q, kp, vp), steps,
                                   warmup) / calls
                row = {"kernel": name, "config": config, "traffic": traffic,
                       "shape": [S, H, Hkv, dh, width, ps], "walk": walk,
                       "rows": int(visible.sum()), "grid_steps": S,
                       "page_walks": int(held.sum()),
                       "total_page_slots": S * width,
                       "pallas_ms": round(secs * 1e3, 4),
                       "least_ms": round(floor * 1e3, 4),
                       "roofline_share": round(floor / secs, 4),
                       "rel_l2": float(np.linalg.norm(got - want)
                                       / np.linalg.norm(want))}
                print(json.dumps(row))
                rows.append(row)
    return rows


def _bench_state_kernels(names, update_cases, prefill_cases, calls, steps,
                         warmup):
    """A per-slot-state family's two kernels alone against their composed
    forms. ``update_cases``: (shape, state bytes moved a call, the zero
    state's shape, ``update(state, impl) -> state``): ``calls`` dependent
    calls inside one jit with the state donated (a decode dispatch makes
    tokens x layers of them). ``prefill_cases``: (shape, chunk-heads
    walked, operands, ``prefill(*operands) -> (out, state)``). Beside the
    times, the
    largest difference of the kernel's state and output from the composed
    form's over the largest element."""
    import functools

    import jax
    import jax.numpy as jnp

    def worst(got, want):
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    def forced(impl):
        return dict(force_reference=impl == "reference",
                    force_pallas=impl == "pallas")

    rows = []
    for shape, moved, zero, update in update_cases:
        times, last = {}, {}
        for impl in ("reference", "pallas"):
            def run(state, impl=impl):
                return jax.lax.scan(
                    lambda st, _: (update(st, **forced(impl)), None), state,
                    None, length=calls)[0]

            fn = jax.jit(run, donate_argnums=0)
            box = [jnp.zeros(zero, jnp.float32)]

            def once(fn=fn, box=box):
                box[0] = fn(box[0])
                return box[0][0, 0, 0]

            times[impl] = _time_steps(once, steps, warmup) / calls
            last[impl] = jax.jit(run)(jnp.zeros(zero, jnp.float32))
        row = {"kernel": names[0], "shape": list(shape),
               "xla_ms": round(times["reference"] * 1e3, 4),
               "pallas_ms": round(times["pallas"] * 1e3, 4),
               "speedup": round(times["reference"] / times["pallas"], 3),
               "state_gbytes_per_s": round(moved / times["pallas"] / 1e9, 1),
               "state_max_err": worst(last["pallas"], last["reference"])}
        print(json.dumps(row), flush=True)
        rows.append(row)
    for shape, chunk_heads, operands, prefill in prefill_cases:
        times, last = {}, {}
        for impl in ("reference", "pallas"):
            fn = jax.jit(functools.partial(prefill, **forced(impl)))
            last[impl] = fn(*operands)
            times[impl] = _time_steps(
                lambda fn=fn: fn(*operands)[1][0, 0, 0],
                max(steps // 4, 1), 1)
        row = {"kernel": names[1], "shape": list(shape),
               "xla_ms": round(times["reference"] * 1e3, 3),
               "pallas_ms": round(times["pallas"] * 1e3, 3),
               "speedup": round(times["reference"] / times["pallas"], 3),
               "us_per_chunk_head": round(
                   times["pallas"] * 1e6 / chunk_heads, 3),
               "state_max_err": worst(last["pallas"][1],
                                      last["reference"][1]),
               "out_max_err": worst(last["pallas"][0],
                                    last["reference"][0])}
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def _bench_delta_rule(update_shapes, prefill_shapes, calls, steps, warmup):
    """The two delta-rule kernels (``kernels/delta_rule.py``): the
    one-token update of every slot's matrix state and the chunked prefill
    of one prompt against the plain loop over its tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import delta_rule as dr

    def inputs(key, rows, H, dk, dv, pack=0):
        """``pack`` > 0: the log decay a HEAD (Gated DeltaNet), else a key
        channel."""
        ks = jax.random.split(key, 5)
        q, k = (jax.random.normal(ks[i], rows + (H * dk,), jnp.bfloat16)
                for i in range(2))
        v = jax.random.normal(ks[2], rows + (H * dv,), jnp.bfloat16)
        g = -jnp.exp(jax.random.uniform(
            ks[3], rows + (H if pack else H * dk,), jnp.float32,
            np.log(1e-3), np.log(4.0)))
        beta = jax.random.uniform(ks[4], rows + (H,), jnp.float32, 0.0, 2.0)
        return q, k, v, g, beta

    # a shape's trailing ``pack`` (heads a tile of the state, 1 or 2) marks
    # a decay a head; the prefill's is only that mark
    updates, prefills = [], []
    for S, live, H, dk, dv, *pack in update_shapes:
        pack = pack[0] if pack else 0
        args = inputs(jax.random.PRNGKey(S), (S,), H, dk, dv, pack) + (
            jnp.asarray(np.arange(S) < live, jnp.int32),)
        tiles = max(pack, 1)
        updates.append((
            (S, live, H, dk, dv) + ((pack,) if pack else ()),
            2 * live * H * dk * dv * 4, (S, H // tiles, dk, tiles * dv),
            lambda state, args=args, **kw: dr.state_update(
                state, *args, **kw)[1]))
    for B, T, n, H, dk, dv, *pack in prefill_shapes:
        args = inputs(jax.random.PRNGKey(T), (B, T), H, dk, dv,
                      pack[0] if pack else 0) + (
            jnp.full((B,), n, jnp.int32),)
        prefills.append(((B, T, n, H, dk, dv) + tuple(pack),
                         B * H * -(-n // dr.CHUNK), args, dr.chunk_prefill))
    return _bench_state_kernels(
        (dr.STATE_KERNEL_NAME, dr.CHUNK_KERNEL_NAME), updates, prefills,
        calls, steps, warmup)


def _bench_ssd(update_shapes, prefill_shapes, calls, steps, warmup):
    """The two Mamba-2 kernels (``kernels/ssd.py``): the one-token update
    of every slot's matrix state and the chunked prefill of the prompts of
    one bucket against the composed chunked form."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import ssd

    def inputs(key, rows, H, P, N):
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], rows + (H * P,), jnp.bfloat16)
        dt = jax.nn.softplus(
            jax.random.normal(ks[1], rows + (H,), jnp.float32) - 2.0)
        a = -jax.random.uniform(ks[2], (H,), jnp.float32, 1.0, 16.0)
        b, c = (jax.random.normal(ks[i], rows + (N,), jnp.bfloat16)
                for i in (3, 4))
        return x, dt, a, b, c, jnp.ones((H,), jnp.float32)

    updates, prefills = [], []
    for S, live, H, P, N in update_shapes:
        args = inputs(jax.random.PRNGKey(S), (S,), H, P, N) + (
            jnp.asarray(np.arange(S) < live, jnp.int32),)
        updates.append((
            (S, live, H, P, N), 2 * live * H * P * N * 4,
            ssd.state_shape(S, H, P, N),
            lambda state, args=args, **kw: ssd.state_update(
                state, *args, **kw)[1]))
    for B, T, n, H, P, N in prefill_shapes:
        args = inputs(jax.random.PRNGKey(T), (B, T), H, P, N) + (
            jnp.full((B,), n, jnp.int32),)
        prefills.append(((B, T, n, H, P, N), B * H * -(-n // ssd.CHUNK),
                         args, ssd.chunk_prefill))
    return _bench_state_kernels(
        (ssd.STATE_KERNEL_NAME, ssd.CHUNK_KERNEL_NAME), updates, prefills,
        calls, steps, warmup)


def _flash_tile_cases(quick):
    """(label, q shape, kv heads, dtype, kwargs, backward?, tiles) of the
    sweep: the training cell's self/cross attention and the decoder-only
    cells' prefill widths, each under the rule (``None``) and under the
    ``(block_q, block_k, heads_per_step)`` it is swept against."""
    if quick:
        return [("quick", (1, 4, 32, 16), 4, "float32",
                 dict(causal=True), True, [None, (16, 16, 2)])]
    train = (64, 16, 256, 64)
    swept = [None] + [(t, t, hb) for t in (128, 256)
                      for hb in (1, 2, 4, 8, 16)]
    cases = [("train_causal", train, 16, "bfloat16", dict(causal=True),
              True, swept),
             ("train_key_mask", train, 16, "bfloat16", dict(mask=True),
              True, [None, (128, 128, 1), (256, 256, 1), (256, 256, 4),
                     (256, 256, 8)])]
    for T in (256, 1024):     # glm47_flash_6l: 20 heads of 256
        cases.append(("glm_prefill", (2, 20, T, 256), 20, "bfloat16",
                      dict(causal=True), False,
                      [None, (128, 128, 1), (min(T, 512),) * 2 + (1,)]))
    for T in (256, 1024):     # jamba2_3b: 20 heads of 128 on ONE kv head
        cases.append(("jamba_prefill", (2, 20, T, 128), 1, "bfloat16",
                      dict(causal=True, kv_group=20), False,
                      [None, (128, 128, 1), (min(T, 512),) * 2 + (1,)]))
    for T in (512, 2048):     # solar_open2_4l: 64 heads of 128 on 8
        cases.append(("solar_prefill", (1, 64, T, 128), 8, "bfloat16",
                      dict(causal=True, kv_group=8), False,
                      [None, (128, 128, 1), (512, 512, 1)]))
    # trinity_mini_5l's window layer at ops/window_ops.py's tiles
    cases.append(("trinity_prefill", (1, 32, 8192, 128), 4, "bfloat16",
                  dict(causal=True, kv_group=8, window=2048), False,
                  [(512, 512, 1), (512, 512, 2), (512, 512, 4)]))
    return cases


def _traced_device_ops(call, steps):
    """``[name, start_ns, duration_ns]`` of every device op of ``steps``
    runs of ``call()`` (which blocks until its result is ready), from a
    profiler trace; ``[]`` where the trace holds no device plane (off the
    chip)."""
    import shutil
    import tempfile

    import jax

    from perfbench import trace_reduce

    trace_dir = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(steps):
                call()
        path = [os.path.join(r, f) for r, _d, fs in os.walk(trace_dir)
                for f in fs if f.endswith(".xplane.pb")][0]
        devices = trace_reduce.flatten(path)["devices"]
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return next(iter(devices.values()))["ops"] if devices else []


def _bench_flash_tiles(cases, steps, fa=None):
    """The three flash kernels alone, by their device time in a profiler
    trace (``perfbench/trace_reduce.py``; found by their pallas_call
    names): the tile rule of ``kernels/flash_attention.py`` against the
    tiles and heads a grid step it is swept over. ``_choose_tiles`` is
    replaced for a swept row and nothing else of the kernel is."""
    import importlib

    import jax
    import jax.numpy as jnp

    from perfbench import trace_reduce

    fa = fa or importlib.import_module("paddle_tpu.kernels.flash_attention")
    names = (fa.FWD_KERNEL_NAME, fa.BWD_DKV_KERNEL_NAME,
             fa.BWD_DQ_KERNEL_NAME)
    rule = getattr(fa, "_choose_tiles", None)
    rows = []
    for label, qshape, kvh, dtype, kw, backward, swept in cases:
        B, H, T, d = qshape
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(keys[0], qshape, jnp.float32).astype(dtype)
        k, v = (jax.random.normal(kk, (B, kvh, T, d),
                                  jnp.float32).astype(dtype)
                for kk in keys[1:3])
        w = jax.random.normal(keys[3], qshape, jnp.float32).astype(dtype)
        kw = dict(kw)
        if kw.pop("mask", False):
            kw["mask"] = jnp.ones((B, T), bool)
        for tiles in swept:
            if tiles is not None and rule is not None:
                fa._choose_tiles = lambda *a, **k_: tiles
            elif tiles is not None:   # a tree without the rule: its tiles
                kw = dict(kw, block_q=tiles[0], block_k=tiles[1])

            def attend(q_, k_, v_):
                return fa.flash_attention(q_, k_, v_, **kw)

            def loss(q_, k_, v_):
                return (attend(q_, k_, v_) * w).astype(jnp.float32).sum()

            fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2))
                         if backward else attend)
            try:
                jax.block_until_ready(fn(q, k, v))
                ops = _traced_device_ops(
                    lambda: jax.block_until_ready(fn(q, k, v)), steps)
                if not ops:
                    raise RuntimeError("no device plane in the trace")
                ms = {n: 0.0 for n in names}
                for name, _t0, dur in ops:
                    # under jax.grad the calls are jvp_<name>_ and
                    # transpose_jvp_<name>__
                    stem = trace_reduce.op_name(name)
                    for n in names:
                        if n in stem:
                            ms[n] += dur / 1e6 / steps
                row = {"kernel": "flash_tiles", "case": label,
                       "shape": list(qshape), "kv_heads": kvh,
                       "dtype": dtype,
                       "tiles": "rule" if tiles is None else list(tiles),
                       "fwd_ms": round(ms[names[0]], 4)}
                if backward:
                    row.update(dkv_ms=round(ms[names[1]], 4),
                               dq_ms=round(ms[names[2]], 4))
            except Exception as exc:  # a refused tile is a row, not the end
                row = {"kernel": "flash_tiles", "case": label,
                       "shape": list(qshape), "tiles": list(tiles or ()),
                       "error": (str(exc) or repr(exc))[-300:]}
            finally:
                if rule is not None:
                    fa._choose_tiles = rule
            print(json.dumps(row), flush=True)
            rows.append(row)
    return rows


def _bench_weight_grad(shapes, steps):
    """A dense layer's weight gradient ``x^T dOut`` with an Adam-like update
    behind it, alone and on PLAIN operands, in three forms: ``vjp`` as
    ``jax.vjp`` of ``x @ w`` forms it (the update fused behind the product),
    ``alone`` as ``mul_grad`` forms it for an ``x`` wider than ``dOut``
    (``ops/math_ops.py``: ``_lone_weight_grad``: the same operand order, the
    product and the update each alone) and ``turned`` (``dOut^T x`` alone,
    one relayout of the gradient, the update alone in the parameter's
    layout: the other operand order, which PR 54 tried first). bfloat16
    operands and gradient, float32 parameter and moments, all donated;
    device time of a call from a profiler trace, the share of the chip's
    bfloat16 peak that the product's ``2 M K N`` operations reach over the
    WHOLE call, and the call's largest ops. These rows are the floor a
    step's program can reach (PR 54: ~88% of peak fused and ~74% turned at
    every shape, whichever operand is the wide one: the orientation costs
    nothing); inside a step the operands come from fusions that may rebuild
    them a window, which only the cell's trace shows."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import math_ops
    from perfbench import trace_reduce

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench")
    with open(os.path.join(root, "peaks.json")) as f:
        peak = json.load(f)["chips"][0]["bf16_flops_per_s"]
    rows_of = (((0,), (0,)), ((), ()))

    def turned(dout, x):
        barrier = jax.lax.optimization_barrier
        dyt = barrier(jax.lax.dot_general(dout, x, rows_of))
        # flat: one dimension has one layout, so the update keeps its own
        return jnp.reshape(barrier(jnp.reshape(dyt.T, (-1,))),
                           dyt.shape[::-1])

    forms = {
        "vjp": lambda dout, x: jax.lax.dot_general(x, dout, rows_of),
        "alone": lambda dout, x: math_ops._lone_weight_grad(x, dout),
        "turned": turned,
    }
    rows = []
    for M, K, N in shapes:
        kx, kd = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(kx, (M, K), jnp.float32).astype(jnp.bfloat16)
        dout = jax.random.normal(kd, (M, N), jnp.float32).astype(
            jnp.bfloat16)
        for form, product in forms.items():

            def step(x_, dout_, p, m1, m2, product=product):
                g = product(dout_, x_).astype(jnp.float32)
                m1 = 0.9 * m1 + 0.1 * g
                m2 = 0.999 * m2 + 0.001 * g * g
                return p - 2e-4 * m1 / (jnp.sqrt(m2) + 1e-8), m1, m2

            fn = jax.jit(step, donate_argnums=(2, 3, 4))
            state = tuple(jnp.zeros((K, N), jnp.float32) for _ in range(3))
            state = [jax.block_until_ready(fn(x, dout, *state))]

            def call():
                state[0] = jax.block_until_ready(fn(x, dout, *state[0]))

            ops = _traced_device_ops(call, steps)
            if not ops:   # the CPU smoke: the forms ran, nothing timed
                row = {"kernel": "weight_grad", "x": [M, K], "dout": [M, N],
                       "form": form, "error": "no device plane in the trace"}
                print(json.dumps(row), flush=True)
                rows.append(row)
                continue
            by_op = {}
            for name, _t0, dur in ops:
                shape = trace_reduce.first_shape(name)
                label = "%s %s%s" % (
                    trace_reduce.op_name(name),
                    shape[0] if shape else "", list(shape[1]) if shape
                    else "")
                by_op[label] = by_op.get(label, 0.0) + dur / 1e6 / steps
            ms = sum(by_op.values())
            row = {"kernel": "weight_grad", "x": [M, K], "dout": [M, N],
                   "form": form, "ms": round(ms, 4),
                   "pct_of_peak": round(
                       100 * 2.0 * M * K * N / (ms * 1e-3) / peak, 1),
                   "ops_ms": {k: round(v, 4) for k, v in sorted(
                       by_op.items(), key=lambda kv: -kv[1])[:4]}}
            print(json.dumps(row), flush=True)
            rows.append(row)
    return rows


def _on_tpu():
    return os.environ.get("BENCH_PLATFORM") != "cpu"


_FAMILIES = ("dynamic_lstm", "dynamic_gru", "flash_attention",
             "flash_tiles", "paged_decode", "latent_decode", "gqa_decode",
             "delta_rule", "ssd", "weight_grad")


def _orchestrate(args):
    """Run each kernel family in its OWN subprocess under a deadline
    (this parent stays off JAX — a chip belongs to one process at a
    time): a crash OR a hang costs one family, and rows a child printed
    before dying still reach the log and the summary."""
    import subprocess
    import sys

    all_rows, failed = [], []
    for fam in _FAMILIES:
        # -u: unbuffered child stdout, so rows printed before a hang
        # survive the SIGKILL (a pipe is block-buffered by default)
        cmd = [sys.executable, "-u", os.path.abspath(__file__),
               "--family", fam]
        if args.quick:
            cmd.append("--quick")
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True,
                timeout=int(os.environ.get("KERNEL_BENCH_FAMILY_TIMEOUT",
                                           "900")))
            stderr, rc = proc.stderr, proc.returncode
            stdout = proc.stdout
        except subprocess.TimeoutExpired as e:
            stdout = (e.stdout or b"").decode() if isinstance(
                e.stdout, bytes) else (e.stdout or "")
            stderr = "family timed out"
            rc = -1
        for line in stdout.splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            print(line)
            try:
                all_rows.append(json.loads(line))
            except ValueError:
                pass
        if rc != 0:
            failed.append(fam)
            sys.stderr.write(stderr[-6000:] + "\n")
            print(json.dumps({"kernel": fam,
                              "error": "family rc=%s; stderr tail above"
                              % rc}))
    return all_rows, failed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny shapes + few steps (CPU smoke)")
    ap.add_argument("--family", choices=_FAMILIES,
                    help="run ONE family, in this process")
    ap.add_argument("--config", default="",
                    help="families delta_rule and gqa_decode: only the "
                         "rows of this perfbench configuration")
    ap.add_argument("--pages", default="1,2,4,8",
                    help="gqa_decode: the pages a step of the walk to "
                         "sweep beside the kernels' own rule")
    args = ap.parse_args()

    if args.family is None:
        all_rows, failed = _orchestrate(args)
        _print_verdicts(all_rows)
        sys.exit(1 if failed else 0)

    import jax

    import paddle_tpu as fluid

    # the accelerator or fail; BENCH_PLATFORM=cpu is the explicit
    # interpreter-mode smoke, whose timings decide nothing
    if os.environ.get("BENCH_PLATFORM") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    else:
        fluid.require_accelerator()

    if args.quick:
        steps, warmup = 3, 1
        rnn_shapes = [(4, 16, 32)]
        fa_shapes = [(1, 2, 128, 32)]
        paged_shapes, paged_calls = [(8, 3, 2, 16, 8, 32)], 2
        # the second of each: a decay a head, two heads a tile
        update_shapes, prefill_shapes = \
            [(4, 3, 2, 16, 16), (4, 3, 4, 24, 64, 2)], \
            [(1, 128, 100, 2, 16, 16), (1, 128, 100, 3, 24, 40, 1)]
        ssd_update, ssd_prefill = [(4, 3, 4, 8, 16)], \
            [(2, 64, 40, 4, 8, 16)]
        latent_cases = [("glm47_flash_6l", "closed_320_chat",
                         "kernel_costs_glm", 2,
                         dict(num_slots=4, max_prompt=256,
                              max_new_tokens=128))]
        latent_walks = ({}, dict(group=2))
        gqa_cases = [("trinity_mini_5l", "closed_120_longctx",
                      ("kernel_costs_trinity", "decode_attention"),
                      dict(num_slots=4, max_prompt=768, max_new_tokens=256,
                           window=256))]
    else:
        steps, warmup = 20, 5
        rnn_shapes = [(32, 128, 256), (64, 256, 512), (16, 512, 1024)]
        fa_shapes = [(8, 8, 1024, 64), (4, 8, 2048, 64), (2, 8, 4096, 128)]
        # perfbench transformer_base: 256 slots of 256 positions, 8 heads
        # of 64, pages of 16; 8 slots live in the steady cell (32 before
        # PR 30), all 256 above the knee; 24 calls a dispatch (4 tokens x
        # 6 layers)
        paged_shapes = [(256, live, 8, 64, 16, 256) for live in (8, 32, 256)]
        paged_calls = 24
        # perfbench glm47_flash_6l under closed_320_chat (256 slots x 20
        # heads x 12 pages, 24 calls a dispatch: 4 tokens x 6 layers) and
        # longcat_flash_omni_4l under closed_80_agentic (64 x 64 x 40, 32
        # calls: 4 tokens x 8 pools); the wrapper's rule, then the sweep
        latent_cases = [
            ("glm47_flash_6l", "closed_320_chat", "kernel_costs_glm", 24,
             {}),
            ("longcat_flash_omni_4l", "closed_80_agentic",
             "kernel_costs_longcat", 32, {}),
            # kimi_linear_5l under closed_480_reasoning (384 slots x 32
            # heads x 64 pages, 4 calls a dispatch: 4 tokens x ONE pool)
            ("kimi_linear_5l", "closed_480_reasoning",
             "kernel_costs_kimi", 4, {})]
        latent_walks = ({},) + tuple(dict(group=g) for g in (1, 2, 4, 8))
        # the four cells that serve the grouped-query kernel, each under
        # its own traffic: solar_open2_4l (96 slots, 64 heads on 8, tables
        # of 80 pages), trinity_mini_5l (96, 32 on 4, 68; its four window
        # layers a ring of 18 under a window of 2048),
        # granite4_h_small_10l (64, 32 on 8, 40), jamba2_3b (256, 20 on 1,
        # 12)
        gqa_cases = [
            ("solar_open2_4l", "closed_120_docreason",
             ("kernel_costs_solar", "gqa_decode_attention"), {}),
            ("trinity_mini_5l", "closed_120_longctx",
             ("kernel_costs_trinity", "decode_attention"), {}),
            ("granite4_h_small_10l", "closed_80_sessions", None, {}),
            ("jamba2_3b", "closed_320_chat",
             ("kernel_costs_jamba", "gqa_decode_attention"), {})]
        # perfbench solar_open2_4l: 96 slots (all live, and two thirds)
        # of 64 heads of 128 x 128, 12 calls a dispatch (4 tokens x 3
        # linear layers); one 8192-token prompt, whole and ended inside a
        # chunk a third of the way
        update_shapes = [(96, live, 64, 128, 128) for live in (96, 64)]
        prefill_shapes = [(1, 8192, n, 64, 128, 128) for n in (8192, 2700)]
        # perfbench kimi_linear_5l: 384 slots (all live, and two thirds)
        # of 32 heads of 128 x 128, 16 calls a dispatch (4 tokens x 4
        # linear layers); two 4096-token prompts and a bucket row of 8 of
        # 1024 that end inside a chunk
        update_shapes += [(384, live, 32, 128, 128) for live in (384, 256)]
        prefill_shapes += [(2, 4096, 4096, 32, 128, 128),
                           (8, 1024, 700, 32, 128, 128)]
        # perfbench olmo_hybrid_8l: 96 slots (all live, and two thirds) of
        # 30 heads of 96 x 192 under a decay a HEAD, two heads a tile of
        # the state (and one, the layout that pads 192 lanes to 256), 24
        # calls a dispatch (4 tokens x 6 linear layers); bucket rows of 8
        # prompts of 1024 and 32 of 256 that end inside a chunk
        olmo_update = [(96, live, 30, 96, 192, pack)
                       for pack in (2, 1) for live in (96, 64)]
        olmo_prefill = [(8, 1024, 700, 30, 96, 192, 2),
                        (32, 256, 200, 30, 96, 192, 2)]
        if args.config == "olmo_hybrid_8l":
            update_shapes, prefill_shapes = olmo_update, olmo_prefill
        elif not args.config:
            update_shapes += olmo_update
            prefill_shapes += olmo_prefill
        # olmo_hybrid_8l's full layers: 96 slots, 30 heads on 30 (a group
        # of ONE), tables of 16 pages of 3840-wide rows; the Transformer's
        # kernel at 30 x 128 beside the grouped-query one
        gqa_cases.append(("olmo_hybrid_8l", "closed_120_evalgen",
                          ("kernel_costs_olmo", "mha_decode_attention"),
                          {}))
        if args.config:
            gqa_cases = [c for c in gqa_cases if c[0] == args.config]
        # perfbench granite4_h_small_10l: 64 slots (all live, and three
        # quarters) of 128 heads of 64 x 128, 36 calls a dispatch (4 tokens
        # x 9 Mamba-2 layers); one 4096-token prompt, whole and ended a
        # third of the way, and bucket rows of 4 prompts of 1024 and 16 of 256
        ssd_update = [(64, live, 128, 64, 128) for live in (64, 48)]
        ssd_prefill = [(1, 4096, 4096, 128, 64, 128),
                       (1, 4096, 1300, 128, 64, 128),
                       (4, 1024, 700, 128, 64, 128),
                       (16, 256, 200, 128, 64, 128)]

    # child mode: exactly one family, crash loudly (the parent records
    # the traceback from stderr and keeps the other families)
    if args.family == "dynamic_lstm":
        _bench_rnn(fluid, "dynamic_lstm", "use_pallas_lstm", rnn_shapes,
                   steps, warmup)
    elif args.family == "dynamic_gru":
        _bench_rnn(fluid, "dynamic_gru", "use_pallas_gru", rnn_shapes,
                   steps, warmup)
    elif args.family == "paged_decode":
        _bench_paged_decode(paged_shapes, paged_calls, steps, warmup)
    elif args.family == "latent_decode":
        _bench_latent_decode(latent_cases, latent_walks, steps, warmup)
    elif args.family == "gqa_decode":
        _bench_gqa_decode(
            gqa_cases, ({},) + tuple(
                dict(group=int(g)) for g in args.pages.split(",") if g),
            2 if args.quick else 24, steps, warmup)
    elif args.family == "delta_rule":
        _bench_delta_rule(update_shapes, prefill_shapes,
                          2 if args.quick else 12, steps, warmup)
    elif args.family == "ssd":
        _bench_ssd(ssd_update, ssd_prefill, 2 if args.quick else 36, steps,
                   warmup)
    elif args.family == "weight_grad":
        # transformer_big's step (64 x 256 tokens): the FFN's second and
        # first product, an attention projection, the 32000-wide head
        _bench_weight_grad(
            [(64, 48, 16), (64, 16, 48)] if args.quick else
            [(16384, 4096, 1024), (16384, 1024, 4096), (16384, 1024, 1024),
             (16384, 1024, 32000)], 2 if args.quick else 10)
    elif args.family == "flash_tiles":
        _bench_flash_tiles(_flash_tile_cases(args.quick),
                           2 if args.quick else 5)
    else:
        _bench_flash(fluid, fa_shapes, steps, warmup)
        # sliding-window leg: same longest shape, window = seq/8 — the
        # pruned-kernel wall-time proof (the tiles the window keeps
        # predict ~seq/(2*window)x on the flash side). Scaled with the
        # shape so the --quick smoke (seq 128) still exercises a window
        # that actually prunes.
        t_last = fa_shapes[-1][2]
        _bench_flash(fluid, fa_shapes[-1:], steps, warmup,
                     window=max(t_last // 8, 16))


def _print_verdicts(all_rows):
    import numpy as np

    summary = {}
    for row in all_rows:
        if "speedup" in row:
            summary.setdefault(row["kernel"], []).append(row["speedup"])
    verdicts = {
        k: {"geomean_speedup": round(
            float(np.prod(v)) ** (1.0 / len(v)), 3),
            "recommend_default": "pallas"
            if all(s > 1.05 for s in v) else "xla"}
        for k, v in summary.items()
    }
    # verdicts from the CPU smoke must be distinguishable — only chip
    # numbers set flag defaults (module docstring); without
    # BENCH_PLATFORM=cpu every family required the accelerator
    print(json.dumps({"on_tpu": _on_tpu(), "verdicts": verdicts}))


if __name__ == "__main__":
    main()
