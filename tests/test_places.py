"""Places resolve to the device they name, or fail saying why.

``Place.jax_device()`` used to take ``device_id % len(pool)``, so
``TPUPlace(3)`` on a one-chip host was chip 0 without a word; and the
helper behind every measuring entry point (``require_accelerator``) is
what keeps a CPU number from ever being written under a device metric.
"""

import jax
import pytest

import paddle_tpu as fluid


@pytest.mark.parametrize("place_cls", [fluid.TPUPlace, fluid.CPUPlace])
def test_device_id_beyond_the_pool_raises(place_cls):
    n = len(jax.local_devices())  # the forced-CPU backend's virtual pool
    assert place_cls(n - 1).jax_device() == jax.local_devices()[n - 1]
    for bad in (n, n + 3, -1):
        with pytest.raises(ValueError) as err:
            place_cls(bad).jax_device()
        msg = str(err.value)
        assert "%s(%d)" % (place_cls.__name__, bad) in msg
        assert "owns %d" % n in msg


def test_executor_on_out_of_range_place_fails_at_run():
    exe = fluid.Executor(fluid.TPUPlace(len(jax.local_devices())))
    with pytest.raises(ValueError, match="out of range"):
        exe.run(fluid.default_startup_program())


def test_tpuplace_without_a_chip_is_a_cpu_device_and_says_so():
    # today's contract for the hundreds of tests that build TPUPlace():
    # no accelerator -> the CPU pool; the docstring states it plainly
    assert fluid.TPUPlace().jax_device().platform == "cpu"
    assert "statement of preference" in fluid.TPUPlace.__doc__


def test_require_accelerator_names_the_missing_device():
    with pytest.raises(fluid.NoAcceleratorError) as err:
        fluid.require_accelerator()
    msg = str(err.value)
    assert "need 1 accelerator" in msg and "cpu:0" in msg
    assert "JAX_PLATFORMS='cpu'" in msg
