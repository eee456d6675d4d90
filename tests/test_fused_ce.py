"""fused_label_smooth_ce, the Transformer's loss head, must be
algebraically identical to the head composed from
softmax_with_cross_entropy + a log_softmax smoothing term (what
models/transformer.py built before the chip settled the two, PERF.md
section 6, PR 48), in loss AND in gradients."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags


def _composed_cost(logits, label, eps, v):
    """The per-row loss ``transformer.build`` composed before PR 48, from
    ``softmax_with_cross_entropy`` and a ``log_softmax`` smoothing term."""
    cost = fluid.layers.softmax_with_cross_entropy(logits, label)
    if eps:
        neg_sum_logp = fluid.layers.scale(
            fluid.layers.reduce_sum(
                fluid.layers.log_softmax(logits), dim=-1, keep_dim=True),
            scale=-1.0)
        cost = fluid.layers.elementwise_add(
            fluid.layers.scale(cost, scale=1.0 - eps),
            fluid.layers.scale(neg_sum_logp, scale=eps / v))
    return cost


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
            cost = _composed_cost(logits, label, eps, v)
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


def test_transformer_head_is_the_fused_op():
    """``transformer.build`` has one head (the chip settled it, PERF.md
    section 6, PR 48): no flag, no keyword and no composed branch."""
    from paddle_tpu.models import transformer

    with fluid.scope_guard(fluid.executor.Scope()):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            transformer.build(src_vocab_size=40, trg_vocab_size=40,
                              max_length=8, n_layer=1, n_head=2,
                              d_model=16, d_inner=32, dropout=0.0)
    ops = {op.type for op in main.global_block().ops}
    assert "fused_label_smooth_ce" in ops
    assert not ops & {"log_softmax", "softmax_with_cross_entropy"}
    with pytest.raises(KeyError):
        flags.get("fused_ce")


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


def _composed_head(logits, label, trg_len, eps, vocab, max_length):
    """``_composed_cost`` on the Transformer's logits, masked and averaged
    over the real target positions as ``transformer.build`` does."""
    cost = _composed_cost(
        fluid.layers.reshape(logits, shape=[-1, vocab]),
        fluid.layers.reshape(label, shape=[-1, 1]), eps, vocab)
    mask = fluid.layers.sequence_mask(trg_len, maxlen=max_length,
                                      dtype="float32")
    cost = fluid.layers.reshape(cost, shape=[-1, max_length])
    return fluid.layers.elementwise_div(
        fluid.layers.reduce_sum(fluid.layers.elementwise_mul(cost, mask)),
        fluid.layers.reduce_sum(mask))


def _tiny_transformer_losses(composed, trg_len):
    """Three SGD steps of the tiny Transformer on seeded feeds: the losses
    of ``transformer.build``'s own head, or of ``_composed_head`` on its
    logits."""
    from paddle_tpu.models import transformer

    fluid.unique_name.switch()
    with fluid.scope_guard(fluid.executor.Scope()):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        with fluid.program_guard(main, startup):
            loss, feeds, extras = transformer.build(
                src_vocab_size=60, trg_vocab_size=60, max_length=8,
                n_layer=1, n_head=2, d_model=16, d_inner=32, dropout=0.0)
            if composed:
                by_name = {v.name: v for v in feeds}
                loss = _composed_head(
                    extras["logits"], by_name["label"], by_name["trg_len"],
                    0.1, 60, 8)
            fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(3)
        losses = []
        for _ in range(3):
            feed = {
                "src_word": rng.randint(1, 60, (2, 8)).astype("int64"),
                "src_len": np.full((2, 1), 8, "int64"),
                "trg_word": rng.randint(1, 60, (2, 8)).astype("int64"),
                "trg_len": np.asarray(trg_len, "int64").reshape(2, 1),
                "label": rng.randint(1, 60, (2, 8)).astype("int64"),
            }
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
            losses.append(float(np.ravel(lv)[0]))
    return losses


def test_transformer_head_matches_a_composed_head_trajectory():
    """End to end on the real model: ``transformer.build``'s loss gives
    the same 3-step trajectory as a head composed here on its logits from
    ``softmax_with_cross_entropy`` and ``log_softmax`` (same seeds, same
    feeds; padded targets, so the mask is exercised) — pins the model
    wiring, not just the op."""
    ref = _tiny_transformer_losses(composed=True, trg_len=[8, 5])
    built = _tiny_transformer_losses(composed=False, trg_len=[8, 5])
    np.testing.assert_allclose(built, ref, rtol=1e-5, atol=1e-6,
                               err_msg="transformer.build's head diverged "
                                       "from the composed head")


def test_tiny_transformer_trajectory_is_the_one_before_pr_48():
    """Three SGD steps of the tiny model, full-length targets, give the
    losses the tree before PR 48 gave with its composed head and its
    gather (read there on this backend: 4.332022190093994,
    4.37652063369751, 4.219750881195068; the fused head with the masked
    sum reads them to the last float32 place: ...2667, ...1110, the third
    equal)."""
    losses = _tiny_transformer_losses(composed=False, trg_len=[8, 8])
    np.testing.assert_allclose(
        losses, [4.332022190093994, 4.37652063369751, 4.219750881195068],
        rtol=1e-6)
    assert losses[-1] < losses[0], "training did not reduce the loss"
