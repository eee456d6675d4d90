"""fused_label_smooth_ce: the MFU lever-#1 op must be
algebraically identical to the composed head it replaces
(softmax_with_cross_entropy + log_softmax smoothing term,
models/transformer.py), in loss AND in gradients."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags


def _build_head(fused, eps, n, v, seed):
    # reset the name counter so both engines' programs name the fc
    # params identically (head_fc.w_0) regardless of build order
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        logits = fluid.layers.fc(x, size=v, name="head_fc")
        if fused:
            cost = fluid.layers.fused_label_smooth_ce(
                logits, label, epsilon=eps)
        else:
            cost = fluid.layers.softmax_with_cross_entropy(logits, label)
            if eps:
                neg_sum_logp = fluid.layers.scale(
                    fluid.layers.reduce_sum(
                        fluid.layers.log_softmax(logits), dim=-1,
                        keep_dim=True),
                    scale=-1.0)
                cost = fluid.layers.elementwise_add(
                    fluid.layers.scale(cost, scale=1.0 - eps),
                    fluid.layers.scale(neg_sum_logp, scale=eps / v))
        loss = fluid.layers.mean(cost)
        fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
    return main, startup, loss


def _run_steps(fused, eps, steps=3, n=6, v=11, seed=3):
    rng = np.random.RandomState(7)
    xs = rng.randn(steps, n, 4).astype("float32")
    ys = rng.randint(0, v, (steps, n, 1)).astype("int64")
    with fluid.scope_guard(fluid.executor.Scope()):
        main, startup, loss = _build_head(fused, eps, n, v, seed)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = []
        for i in range(steps):
            (lv,) = exe.run(main, feed={"x": xs[i], "label": ys[i]},
                            fetch_list=[loss])
            losses.append(float(np.ravel(lv)[0]))
        w = np.asarray(fluid.executor.global_scope()
                       .find_var("head_fc.w_0").value)
    return losses, w


@pytest.mark.parametrize("eps", [0.0, 0.1])
def test_fused_matches_composed_head(eps):
    """Same seeds, same feeds: per-step losses identical (the loss
    values drive nothing, so equality at step k also proves the
    gradient/update parity of steps < k) and final weights identical."""
    l_ref, w_ref = _run_steps(fused=False, eps=eps)
    l_fused, w_fused = _run_steps(fused=True, eps=eps)
    np.testing.assert_allclose(l_fused, l_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(w_fused, w_ref, rtol=1e-4, atol=1e-5,
                               err_msg="weight trajectories diverged — "
                                       "fused backward is not the "
                                       "composed head's gradient")


def test_fused_ce_grad_formula():
    """Direct check of dL/dx = softmax - eps/V - (1-eps)*onehot against
    numeric differentiation through the op's own lowering."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.loss_ops import _lower_fused_label_smooth_ce

    rng = np.random.RandomState(0)
    x = rng.randn(5, 9).astype("float32")
    lbl = rng.randint(0, 9, (5, 1)).astype("int64")
    eps = 0.1

    def f(xx):
        out = _lower_fused_label_smooth_ce(
            None, {"Logits": [xx], "Label": [jnp.asarray(lbl)]},
            {"epsilon": eps})
        return jnp.sum(out["Loss"])

    got = jax.grad(f)(jnp.asarray(x))
    # analytic expectation
    e = np.exp(x - x.max(-1, keepdims=True))
    sm = e / e.sum(-1, keepdims=True)
    onehot = np.eye(9)[lbl[:, 0]]
    want = sm - eps / 9 - (1 - eps) * onehot
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


def test_fused_ce_flag_switches_transformer_head():
    from paddle_tpu.models import transformer

    def ops_of(prog):
        return {op.type for op in prog.global_block().ops}

    old = flags.get("fused_ce")
    try:
        flags.set_flag("fused_ce", True)
        with fluid.scope_guard(fluid.executor.Scope()):
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                transformer.build(src_vocab_size=40, trg_vocab_size=40,
                                  max_length=8, n_layer=1, n_head=2,
                                  d_model=16, d_inner=32, dropout=0.0)
            assert "fused_label_smooth_ce" in ops_of(main)
            assert "log_softmax" not in ops_of(main)
    finally:
        flags.set_flag("fused_ce", old)


def test_fused_ce_bf16_logits_stay_bf16():
    """Under AMP the fused op must accept bf16 logits without a
    blacklist upcast: the [N, V] softmax/grad tensors are the lever."""
    import jax.numpy as jnp
    from paddle_tpu.ops.loss_ops import _lower_fused_label_smooth_ce

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(4, 33).astype("float32")).astype(jnp.bfloat16)
    lbl = jnp.asarray(rng.randint(0, 33, (4, 1)))
    out = _lower_fused_label_smooth_ce(
        None, {"Logits": [x], "Label": [lbl]}, {"epsilon": 0.1})
    loss = np.asarray(out["Loss"]).astype("float64")
    # f32 reference on the same (bf16-rounded) logits
    xf = np.asarray(x.astype(jnp.float32)).astype("float64")
    m = xf.max(-1, keepdims=True)
    lse = m + np.log(np.exp(xf - m).sum(-1, keepdims=True))
    xy = np.take_along_axis(xf, np.asarray(lbl), axis=-1)
    want = lse - 0.9 * xy - (0.1 / 33) * xf.sum(-1, keepdims=True)
    np.testing.assert_allclose(loss, want, rtol=2e-2, atol=2e-2)
    assert out["Loss"].dtype == jnp.float32


def test_fused_ce_full_transformer_trajectory():
    """End to end on the real model: transformer.build under
    FLAGS_fused_ce must produce the same 3-step loss trajectory as the
    composed head (same seeds, same feeds) — pins the model wiring, not
    just the op."""
    from paddle_tpu.models import transformer

    def run(fused):
        old = flags.get("fused_ce")
        flags.set_flag("fused_ce", fused)
        try:
            fluid.unique_name.switch()
            with fluid.scope_guard(fluid.executor.Scope()):
                main, startup = fluid.Program(), fluid.Program()
                main.random_seed = startup.random_seed = 11
                with fluid.program_guard(main, startup):
                    loss, feeds, _ = transformer.build(
                        src_vocab_size=60, trg_vocab_size=60,
                        max_length=8, n_layer=1, n_head=2, d_model=16,
                        d_inner=32, dropout=0.0)
                    fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
                # the flag must actually switch the head, or the A/B
                # below compares the composed head against itself
                has_fused = any(op.type == "fused_label_smooth_ce"
                                for op in main.global_block().ops)
                assert has_fused == fused, (
                    "FLAGS_fused_ce plumbing broken: fused=%r but "
                    "program has_fused=%r" % (fused, has_fused))
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                rng = np.random.RandomState(3)
                losses = []
                for _ in range(3):
                    feed = {
                        "src_word": rng.randint(1, 60, (2, 8)).astype("int64"),
                        "src_len": np.full((2, 1), 8, "int64"),
                        "trg_word": rng.randint(1, 60, (2, 8)).astype("int64"),
                        "trg_len": np.full((2, 1), 8, "int64"),
                        "label": rng.randint(1, 60, (2, 8)).astype("int64"),
                    }
                    (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
                    losses.append(float(np.ravel(lv)[0]))
            return losses
        finally:
            flags.set_flag("fused_ce", old)

    ref = run(False)
    fused = run(True)
    np.testing.assert_allclose(fused, ref, rtol=1e-5, atol=1e-6,
                               err_msg="full-model fused-CE trajectory "
                                       "diverged from the composed head")
    assert ref[-1] < ref[0], "training did not reduce the loss"
