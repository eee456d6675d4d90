"""softmax_with_cross_entropy's hard-label pick is a masked sum over the
class axis (``ops/loss_ops._pick_label``), not a gather: the same numbers
as the gather it replaced, bit for bit in float32, and a label outside
``[0, classes)`` is an ignored one. What the compiled step stores of the
logits because of it: ``tests/test_tpu_lowering.py``; the Transformer's
trajectory before and after: ``tests/test_fused_ce.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import backward


def _gather_reference(logits, label, weight, ignore_index):
    """The lowering as it stood before the masked sum (``take_along_axis``
    on ``logits - lse``): (loss, d(sum(loss * weight)) / d(logits))."""
    lbl = jnp.asarray(label.reshape(logits.shape[:-1]), jnp.int32)

    def loss_of(x):
        lse = jax.scipy.special.logsumexp(x, axis=-1, keepdims=True)
        nll = -jnp.take_along_axis(x - lse, lbl[..., None], axis=-1)
        if ignore_index >= 0:
            nll = jnp.where((lbl == ignore_index)[..., None],
                            jnp.zeros_like(nll), nll)
        return nll

    x = jnp.asarray(logits)
    grad = jax.grad(lambda v: jnp.sum(loss_of(v) * jnp.asarray(weight)))(x)
    return np.asarray(loss_of(x)), np.asarray(grad)


def _run_op(logits, label, weight, ignore_index=-100, dtype="float32"):
    """(Loss, Softmax, Logits@GRAD of sum(Loss * weight)) through a
    program and the executor."""
    with fluid.scope_guard(fluid.executor.Scope()):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data("x", shape=list(logits.shape[1:]),
                                  dtype=dtype)
            x.stop_gradient = False
            y = fluid.layers.data("y", shape=list(label.shape[1:]),
                                  dtype="int64")
            w = fluid.layers.data("w", shape=list(weight.shape[1:]),
                                  dtype="float32")
            loss, softmax = fluid.layers.softmax_with_cross_entropy(
                x, y, ignore_index=ignore_index, return_softmax=True)
            total = fluid.layers.reduce_sum(
                fluid.layers.elementwise_mul(loss, w))
            (grad,) = backward.calc_gradient(total, x)
        exe = fluid.Executor(fluid.CPUPlace())
        return [np.asarray(v) for v in exe.run(
            main, feed={"x": logits, "y": label, "w": weight},
            fetch_list=[loss, softmax, grad])]


def _case(lead, label_tail, classes=37, seed=5):
    rng = np.random.RandomState(seed)
    logits = (3.0 * rng.randn(*lead, classes)).astype("float32")
    label = rng.randint(0, classes, lead + label_tail).astype("int64")
    weight = rng.rand(*lead, 1).astype("float32") + 0.5
    return logits, label, weight


@pytest.mark.parametrize("ignore_index", [-100, 3])
@pytest.mark.parametrize("lead,label_tail", [((12,), ()), ((12,), (1,)),
                                             ((3, 4), (1,))],
                         ids=["label_N", "label_N_1", "label_B_T_1"])
def test_masked_pick_is_the_gather_bit_for_bit(lead, label_tail, ignore_index):
    logits, label, weight = _case(lead, label_tail)
    label.reshape(-1)[:3] = 3  # some rows hold the class that 3 ignores
    loss, _, grad = _run_op(logits, label, weight, ignore_index)
    want_loss, want_grad = _gather_reference(
        logits, label, weight, ignore_index)
    assert loss.dtype == np.float32 and loss.shape == lead + (1,)
    np.testing.assert_array_equal(loss, want_loss)
    np.testing.assert_array_equal(grad, want_grad)
    ignored = (label.reshape(lead) == ignore_index)
    assert ignored.sum() >= (3 if ignore_index >= 0 else 0)
    assert not loss[ignored].any()


def test_a_label_outside_the_classes_is_ignored():
    """The default ``ignore_index=-100`` fed as a label: a loss of zero and
    a gradient row of zeros (the gather wrapped round to another class and
    trained on it); the rows beside it are untouched."""
    logits, label, weight = _case((6,), (1,))
    clean = _run_op(logits, label, weight)
    label[2, 0], label[4, 0] = -100, 37
    loss, softmax, grad = _run_op(logits, label, weight)
    for row in (2, 4):
        assert loss[row, 0] == 0.0 and not grad[row].any()
    kept = [0, 1, 3, 5]
    np.testing.assert_array_equal(loss[kept], clean[0][kept])
    np.testing.assert_array_equal(grad[kept], clean[2][kept])
    np.testing.assert_array_equal(softmax, clean[1])


def test_softmax_output_is_unchanged():
    logits, label, weight = _case((3, 4), (1,))
    _, softmax, _ = _run_op(logits, label, weight)
    lse = jax.scipy.special.logsumexp(jnp.asarray(logits), axis=-1,
                                      keepdims=True)
    np.testing.assert_array_equal(softmax, np.asarray(jnp.exp(logits - lse)))
    np.testing.assert_allclose(softmax.sum(-1), 1.0, rtol=1e-6)


def test_soft_labels_are_unchanged():
    from paddle_tpu.ops.loss_ops import _lower_softmax_xent

    logits, _, _ = _case((5,), (1,))
    rng = np.random.RandomState(9)
    soft = rng.dirichlet(np.ones(37), 5).astype("float32")
    out = _lower_softmax_xent(
        None, {"Logits": [jnp.asarray(logits)], "Label": [jnp.asarray(soft)]},
        {"soft_label": True})
    want = -(soft * np.asarray(jax.nn.log_softmax(logits))).sum(-1)
    np.testing.assert_allclose(np.asarray(out["Loss"])[:, 0], want, rtol=1e-6)


def test_bfloat16_logits_keep_their_dtype_and_pick_in_float32():
    """Fed bfloat16 logits with no AMP rewrite: the loss comes back in the
    logits' dtype as before, and is the float32 loss of the same (rounded)
    logits to bfloat16's last place."""
    from paddle_tpu.ops.loss_ops import _lower_softmax_xent

    logits, label, _ = _case((8,), (1,))
    x = jnp.asarray(logits).astype(jnp.bfloat16)
    out = _lower_softmax_xent(
        None, {"Logits": [x], "Label": [jnp.asarray(label)]}, {})
    assert out["Loss"].dtype == jnp.bfloat16
    want, _ = _gather_reference(
        np.asarray(x.astype(jnp.float32)), label, np.ones((8, 1), "float32"),
        -100)
    np.testing.assert_allclose(
        np.asarray(out["Loss"].astype(jnp.float32)), want, rtol=2e-2,
        atol=2e-2)
