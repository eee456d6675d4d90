"""Book-style convergence tests for the conv model zoo on tiny synthetic
data (tests/book/test_{recognize_digits,image_classification}.py parity)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import mnist, resnet, vgg


def _synthetic_images(n, shape, classes, seed=0):
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, classes, n).astype("int64")
    base = rng.randn(classes, *shape).astype("float32")
    x = base[labels] + 0.25 * rng.randn(n, *shape).astype("float32")
    return x, labels.reshape(-1, 1)


def _train(build_fn, kwargs, n=64, bs=16, steps=25, lr=0.001, classes=4,
           optimizer=None):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss, feeds, extras = build_fn(**kwargs)
        opt = optimizer or fluid.optimizer.Adam(learning_rate=lr)
        opt.minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    shape = tuple(int(d) for d in feeds[0].shape[1:])
    x, y = _synthetic_images(n, shape, classes)
    losses = []
    for step in range(steps):
        i = (step * bs) % n
        lv, = exe.run(
            main,
            feed={feeds[0].name: x[i : i + bs], feeds[1].name: y[i : i + bs]},
            fetch_list=[loss],
        )
        losses.append(float(lv[0]))
        assert np.isfinite(losses[-1]), "loss diverged at step %d" % step
    return losses


def test_mnist_conv_converges():
    losses = _train(
        mnist.build,
        {"img_shape": (1, 28, 28), "class_num": 4},
        steps=30,
    )
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.6, losses


def test_resnet_cifar_converges():
    losses = _train(
        resnet.build,
        {"img_shape": (3, 16, 16), "class_num": 4, "depth": 8,
         "variant": "cifar10"},
        steps=30,
    )
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_vgg_builds_and_steps():
    # Full VGG-16 is heavy for CI; 32x32 input, few steps, finite loss.
    losses = _train(
        vgg.build,
        {"img_shape": (3, 32, 32), "class_num": 4},
        n=16,
        bs=8,
        steps=4,
    )
    assert all(np.isfinite(losses))


def test_resnet50_imagenet_builds():
    """ResNet-50 graph builds and infers shapes (its train step is too
    heavy for unit CI)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loss, feeds, extras = resnet.build(
            img_shape=(3, 64, 64), class_num=10, depth=50
        )
        fluid.optimizer.Momentum(0.1, 0.9).minimize(loss)
    n_params = len(main.global_block().all_parameters())
    assert n_params > 100  # 53 convs + BN scales/biases
    assert loss.shape == (1,)


def test_se_resnext_builds_and_steps():
    from paddle_tpu.models import se_resnext

    losses = _train(
        se_resnext.build,
        {"img_shape": (3, 32, 32), "class_num": 4, "depth": 50},
        n=8,
        bs=4,
        steps=3,
    )
    assert all(np.isfinite(losses))


def test_googlenet_builds_and_steps():
    from paddle_tpu.models import googlenet

    losses = _train(
        googlenet.build,
        {"img_shape": (3, 64, 64), "class_num": 4},
        n=8,
        bs=4,
        steps=3,
    )
    assert all(np.isfinite(losses))


def test_alexnet_converges():
    from paddle_tpu.models import alexnet

    losses = _train(
        alexnet.build,
        {"img_shape": (3, 63, 63), "class_num": 4},
        n=32,
        bs=8,
        steps=20,
    )
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_conv_nhwc_flag_parity():
    """FLAGS_conv_nhwc (the MFU layout experiment) must be a pure layout
    change: identical losses, forward and backward, vs the NCHW default."""
    from paddle_tpu import flags, unique_name

    def run():
        unique_name.switch()
        np.random.seed(0)
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 21
        startup.random_seed = 21
        with fluid.program_guard(main, startup):
            loss, feeds, _ = mnist.build(class_num=4)
            fluid.optimizer.Momentum(learning_rate=0.01,
                                     momentum=0.9).minimize(loss)
        with fluid.scope_guard(fluid.executor.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            shape = tuple(int(d) for d in feeds[0].shape[1:])
            x, y = _synthetic_images(32, shape, 4)
            out = []
            for step in range(6):
                lv, = exe.run(
                    main,
                    feed={feeds[0].name: x[:16], feeds[1].name: y[:16]},
                    fetch_list=[loss])
                out.append(float(lv[0]))
            return out

    base = run()
    flags.set_flag("conv_nhwc", True)
    try:
        nhwc = run()
    finally:
        flags.set_flag("conv_nhwc", False)
    np.testing.assert_allclose(nhwc, base, rtol=1e-5, atol=1e-6)
