"""The Mamba-2 kernels (``kernels/ssd.py``) tiny on the CPU: the chunked
prefill and the one-token update, each in its Pallas form (interpret mode)
and its composed ``jax.numpy`` form, against the recurrence walked a token
at a time (``ssd.token_loop``): prompts of unequal length in one bucket,
padding that leaves the state bit for bit, a dead slot that keeps its
rows, the update in place, and decode continuing a prefill's state."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.kernels import ssd  # noqa: E402

FORMS = {"pallas": dict(force_pallas=True),
         "composed": dict(force_reference=True)}


def rel(got, want):
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def inputs(rng, rows, H, P, N, rate=(1.0, 16.0), dtype=jnp.float32):
    """x, Delta (softplus of a normal: 1e-2 .. 3), A = -U(rate), B, C, D."""
    x = jnp.asarray(rng.standard_normal(rows + (H * P,)), dtype)
    dt = jnp.asarray(np.log1p(np.exp(rng.standard_normal(rows + (H,)) - 1)),
                     jnp.float32)
    a = -jnp.asarray(rng.uniform(rate[0], rate[1], (H,)), jnp.float32)
    b, c = (jnp.asarray(rng.standard_normal(rows + (N,)), dtype)
            for _ in range(2))
    d = jnp.asarray(rng.standard_normal((H,)), jnp.float32)
    return x, dt, a, b, c, d


# a prompt that ends inside a chunk, on a chunk's edge, after one token,
# over several chunks; heads that fill a lane group (8 x 16, 2 x 64), that
# do not (3 x 8) and a head block of its own (16 x 64); decays near 1 and
# near 0 (e^-48 a token)
CASES = [
    (3, 32, 4, 8, 16, [32, 19, 1], (1.0, 16.0)),
    (2, 512, 2, 64, 128, [512, 300], (1.0, 16.0)),
    (3, 768, 8, 16, 32, [700, 256, 257], (1e-3, 1e-1)),
    (2, 64, 3, 8, 16, [64, 7], (1.0, 16.0)),
    (1, 512, 16, 64, 128, [411], (0.5, 4.0)),
]


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("B,T,H,P,N,lengths,rate", CASES)
def test_chunked_prefill_matches_the_token_loop(form, B, T, H, P, N,
                                                lengths, rate):
    rng = np.random.RandomState(T + H)
    x, dt, a, b, c, d = inputs(rng, (B, T), H, P, N, rate)
    lens = jnp.asarray(lengths, jnp.int32)
    want_y, want_s = ssd.token_loop(x, dt, a, b, c, d, lens)
    got_y, got_s = ssd.chunk_prefill(x, dt, a, b, c, d, lens, **FORMS[form])
    assert got_s.shape == ssd.state_shape(B, H, P, N)
    got_s = ssd.to_heads(got_s, H)
    assert np.isfinite(np.asarray(got_s)).all()
    assert rel(got_s, want_s) < 5e-5
    assert rel(got_y, want_y) < 5e-5
    # padding reads 0
    for row, n in enumerate(lengths):
        assert not np.asarray(got_y[row, n:]).any()


@pytest.mark.parametrize("form", sorted(FORMS))
def test_padding_leaves_the_state_bit_for_bit(form):
    """What follows a prompt in its bucket row (other tokens, not zeros)
    and how long the bucket is change NOTHING: Delta = 0 past the length,
    and ``exp(0) s + 0`` is ``s``. The same prompt in a bucket of 256, in
    one of 768 and with other tokens behind it gives the same bits."""
    rng = np.random.RandomState(5)
    x, dt, a, b, c, d = inputs(rng, (1, 768), 4, 16, 32)
    lens = jnp.asarray([200], jnp.int32)
    _y, long = ssd.chunk_prefill(x, dt, a, b, c, d, lens, **FORMS[form])
    _y, short = ssd.chunk_prefill(x[:, :256], dt[:, :256], a, b[:, :256],
                                  c[:, :256], d, lens, **FORMS[form])
    other = [jnp.concatenate([v[:, :200], 3.0 * v[:, 200:] + 1.0], axis=1)
             for v in (x, dt, b, c)]
    _y, behind = ssd.chunk_prefill(other[0], other[1], a, other[2],
                                   other[3], d, lens, **FORMS[form])
    assert (np.asarray(long) == np.asarray(short)).all()
    assert (np.asarray(long) == np.asarray(behind)).all()


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("S,H,P,N", [(4, 4, 8, 16), (3, 128, 16, 32),
                                     (2, 2, 64, 128)])
def test_state_update_matches_one_token_of_the_loop(form, S, H, P, N):
    rng = np.random.RandomState(S + H)
    x, dt, a, b, c, d = inputs(rng, (S,), H, P, N)
    state = jnp.asarray(rng.standard_normal((S, H, P, N)), jnp.float32)
    live = jnp.asarray([1, 0, 1, 1][:S], jnp.int32)
    y, new = ssd.token_step(state, x.reshape(S, H, P), dt, a, b, c)
    y = y + d[:, None] * x.reshape(S, H, P)
    served = ssd.from_heads(state)
    assert served.shape == ssd.state_shape(S, H, P, N)
    got_y, got_s = ssd.state_update(served, x, dt, a, b, c, d, live,
                                    **FORMS[form])
    keep = np.asarray(live) > 0
    assert rel(np.asarray(ssd.to_heads(got_s, H))[keep],
               np.asarray(new)[keep]) < 1e-6
    assert rel(np.asarray(got_y)[keep],
               np.asarray(y).reshape(S, -1)[keep]) < 1e-6
    # a slot that is not live keeps its rows, bit for bit, and reads 0
    assert (np.asarray(got_s[1]) == np.asarray(served[1])).all()
    assert not np.asarray(got_y[1]).any()


@pytest.mark.parametrize("H,P,N", [(4, 8, 16), (128, 64, 128), (3, 8, 16)])
def test_the_served_layout_goes_to_heads_and_back(H, P, N):
    """``[slots, G, N, g P]``: d_state on the sublanes, the channels of a
    lane group's heads side by side on the lanes in ``x``'s own order."""
    state = jnp.arange(2 * H * P * N, dtype=jnp.float32).reshape(2, H, P, N)
    served = ssd.from_heads(state)
    g = ssd.lane_group(H, P)
    assert g * P <= 128 and H % g == 0
    assert served.shape == ssd.state_shape(2, H, P, N) == (
        2, H // g, N, g * P)
    assert (np.asarray(ssd.to_heads(served, H)) == np.asarray(state)).all()
    # element (head h, channel p, state n) sits at lane (h % g) P + p of
    # row n of group h // g
    h, p, n = H - 1, P - 3, N - 2
    assert served[1, h // g, n, (h % g) * P + p] == state[1, h, p, n]


def test_the_update_is_in_place():
    """The Pallas call aliases the state to its output: under ``jit`` with
    the state donated the lowered module carries the alias, and no second
    state array is made."""
    S, H, P, N = 2, 4, 8, 16
    rng = np.random.RandomState(0)
    x, dt, a, b, c, d = inputs(rng, (S,), H, P, N)
    live = jnp.ones((S,), jnp.int32)

    def step(state):
        return ssd.state_update(state, x, dt, a, b, c, d, live,
                                force_pallas=True)

    jaxpr = jax.make_jaxpr(step)(
        jnp.zeros(ssd.state_shape(S, H, P, N), jnp.float32))
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == ssd.STATE_KERNEL_NAME
    # operand 0 is the scalar-prefetched live mask, operand 1 the state
    assert tuple(call.params["input_output_aliases"]) == ((1, 1),)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_decode_continues_a_prefills_state(form):
    """A prompt prefilled to token n and then updated a token at a time
    reaches the state, and reads the outputs, of the same tokens prefilled
    whole."""
    rng = np.random.RandomState(9)
    B, T, n, H, P, N = 2, 48, 40, 4, 8, 16
    x, dt, a, b, c, d = inputs(rng, (B, T), H, P, N)
    whole_y, whole_s = ssd.token_loop(x, dt, a, b, c, d,
                                      jnp.asarray([T, T], jnp.int32))
    _y, s = ssd.chunk_prefill(x, dt, a, b, c, d,
                              jnp.asarray([n, n], jnp.int32), **FORMS[form])
    live = jnp.ones((B,), jnp.int32)
    for t in range(n, T):
        y, s = ssd.state_update(s, x[:, t], dt[:, t], a, b[:, t], c[:, t], d,
                                live, **FORMS[form])
        assert rel(y, whole_y[:, t]) < 5e-5
    assert rel(ssd.to_heads(s, H), whole_s) < 5e-5


@pytest.mark.parametrize("form", sorted(FORMS))
def test_bfloat16_operands_take_one_pass_and_stay_near(form):
    """Serving's dtype: x, B and C in bfloat16, the products' operands
    bfloat16 with float32 accumulation, the state float32: within a few
    bfloat16 roundings of the float32 loop over the same bfloat16 inputs."""
    rng = np.random.RandomState(11)
    x, dt, a, b, c, d = inputs(rng, (2, 512), 4, 16, 32, (0.5, 4.0),
                               jnp.bfloat16)
    lens = jnp.asarray([512, 301], jnp.int32)
    want_y, want_s = ssd.token_loop(x, dt, a, b, c, d, lens)
    got_y, got_s = ssd.chunk_prefill(x, dt, a, b, c, d, lens, **FORMS[form])
    assert got_s.dtype == jnp.float32 and got_y.dtype == jnp.float32
    assert rel(ssd.to_heads(got_s, 4), want_s) < 6e-3
    assert rel(got_y, want_y) < 6e-3
