"""``mul_grad`` (ops/math_ops.py: ``_lower_mul_grad``) against ``jax.vjp``
of the plain product. The op forms ``Y@GRAD`` as a product of its own on a
stored ``Out@GRAD`` when ``X`` is wider than ``Out`` (the same product: the
barriers move no number); every other gradient is the vjp's own."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.backward import calc_gradient
from paddle_tpu.ops import math_ops
from paddle_tpu.transpiler import rewrite_program_amp

_ROWS = (4, 8)           # x_num_col_dims 2 feeds [4, 8, K]; 1 feeds [32, K]
_WIDTHS = {"x_wider": (48, 16), "x_narrower": (16, 48), "as_wide": (32, 32)}
_CASES = list(itertools.product(
    _WIDTHS, (1, 2), ("float32", "bfloat16"), ("X", "Y", "XY")))


def _program(x_shape, y_shape, out_shape, xn, dtype, wants):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        block = main.global_block()
        x, y, dout = (
            block.create_var(name=name, shape=shape, dtype="float32",
                             stop_gradient=stop)
            for name, shape, stop in (("x", x_shape, "X" not in wants),
                                     ("y", y_shape, "Y" not in wants),
                                     ("dout", out_shape, True)))
        out = block.create_var(name="out", shape=out_shape, dtype="float32",
                               stop_gradient=False)
        block.append_op(type="mul", inputs={"X": ["x"], "Y": ["y"]},
                        outputs={"Out": ["out"]},
                        attrs={"x_num_col_dims": xn, "y_num_col_dims": 1})
        grads = calc_gradient(out, [v for v, w in ((x, "X"), (y, "Y"))
                                    if w in wants], target_gradients=[dout])
    if dtype == "bfloat16":
        # the trainer's path: the AMP rewrite hands mul_grad bfloat16
        # operands and takes bfloat16 gradients from it
        rewrite_program_amp(main, "bfloat16")
    return main, grads


@pytest.mark.parametrize(
    "widths,xn,dtype,wants", _CASES,
    ids=["-".join(map(str, c)) for c in _CASES])
def test_mul_grad_is_the_vjp_of_the_plain_product(widths, xn, dtype, wants,
                                                  monkeypatch):
    K, N = _WIDTHS[widths]
    x_shape = (_ROWS if xn == 2 else (_ROWS[0] * _ROWS[1],)) + (K,)
    out_shape = x_shape[:-1] + (N,)
    rng = np.random.RandomState(K + xn)
    feed = {"x": rng.randn(*x_shape).astype("float32"),
            "y": rng.randn(K, N).astype("float32"),
            "dout": rng.randn(*out_shape).astype("float32")}
    main, grads = _program(x_shape, (K, N), out_shape, xn, dtype, wants)
    assert [op.type for op in main.global_block().ops] == ["mul", "mul_grad"]
    alone = []
    rule = math_ops._lone_weight_grad
    monkeypatch.setattr(math_ops, "_lone_weight_grad",
                        lambda *a: alone.append(1) or rule(*a))
    got = fluid.Executor(fluid.CPUPlace()).run(
        main, feed=feed, fetch_list=grads, return_numpy=False)

    def plain(x, y):
        return jnp.reshape(jnp.reshape(x, (-1, K)) @ y, out_shape)

    @jax.jit
    def reference(x, y, dout):
        x, y, dout = (a.astype(dtype) for a in (x, y, dout))
        return jax.vjp(plain, x, y)[1](dout)

    assert len(alone) == (widths == "x_wider" and "Y" in wants)
    want = dict(zip("XY", reference(feed["x"], feed["y"], feed["dout"])))
    for slot, g in zip(wants, got):
        g, w = np.asarray(g), np.asarray(want[slot])
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


def test_kernel_bench_family_times_every_form(monkeypatch, capsys):
    """``tools/kernel_bench.py --family weight_grad`` tiny on the CPU: a
    row a shape and form; off the chip the trace holds no device plane, so
    a row says so and carries no time."""
    import importlib.util
    import os

    monkeypatch.setenv("BENCH_PLATFORM", "cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "kernel_bench", os.path.join(root, "tools", "kernel_bench.py"))
    kb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kb)
    rows = kb._bench_weight_grad([(64, 48, 16), (64, 16, 48)], steps=1)
    capsys.readouterr()
    assert [(r["x"], r["dout"], r["form"]) for r in rows] == [
        (x, dout, form) for x, dout in (([64, 48], [64, 16]),
                                        ([64, 16], [64, 48]))
        for form in ("vjp", "alone", "turned")]
    assert all("ms" not in r and "device plane" in r["error"] for r in rows)
