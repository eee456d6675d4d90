"""Compile a serving builder's programs for a DESCRIBED v5e (no chip):
what ``Executor`` would build for a program placed on that device
(``CompiledProgram`` / ``MultiStepProgram``), lowered from shapes alone.
Used by ``tests/test_sparse_latent_decoder.py`` at small sizes, by
``tests/test_tpu_lowering.py`` (the trainer's loss head among them) and by
a builder's scratch script at the served ones."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from paddle_tpu.core import lowering
from paddle_tpu.core.types import np_dtype


def compile_program(program, device, state, feeds, fetches, steps=None,
                    is_test=True):
    """``state`` / ``feeds``: {name: (shape, dtype)}. Returns the compiled
    executable (``as_text()``, ``memory_analysis()``). ``is_test=False``
    for a training program (dropout draws, the optimizer's updates)."""
    sharding = SingleDeviceSharding(device)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), np_dtype(dtype),
                                    sharding=sharding)

    # x64 is off: an int64 feed or state array reaches the step as int32
    def narrow(dtype):
        return "int32" if str(dtype) == "int64" else dtype

    feed_specs = {n: (tuple(s), narrow(d)) for n, (s, d) in feeds.items()}
    if steps:
        cp = lowering.MultiStepProgram(
            program, steps, feed_specs, fetches, list(state),
            is_test=is_test, device=device, stack_fetches=True)
    else:
        cp = lowering.CompiledProgram(
            program, feed_specs, fetches, list(state), is_test=is_test,
            device=device)
    mut = {n: spec(state[n][0], narrow(state[n][1]))
           for n in cp.mutable_state}
    frz = {n: spec(state[n][0], narrow(state[n][1]))
           for n in cp.frozen_state}
    fd = {n: spec(s, d) for n, (s, d) in feed_specs.items()}
    key = (jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding),
           np.uint32(0))
    return cp.jitted.lower(mut, frz, fd, key).compile()


def session_shapes(built, parameter_shapes, num_slots):
    """{name: (shape, dtype)} of everything a session's scope holds."""
    state = dict(parameter_shapes)
    for name, pool in built["geometry"]["state"]["page_pools"].items():
        state[name] = (pool["shape"], pool["dtype"])
    state["lmd_tok"] = ((num_slots, 1), "int64")
    state["lmd_pos"] = ((num_slots, 1), "int64")
    return state
