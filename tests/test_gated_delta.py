"""``ops/gated_delta.py``: the chunked form of the gated delta rule
against the token-by-token recurrence, the carried state and convolution
tail across calls, and tokens that must not advance the state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu.ops.gated_delta import (
    CHUNK,
    causal_conv,
    gated_delta_chunked,
    gated_delta_step,
    l2_normalize,
)

H, DK, DV = 3, 8, 16


def _inputs(seed, B, T, beta_max=2.0):
    r = np.random.default_rng(seed)
    q = l2_normalize(jnp.asarray(r.normal(size=(B, T, H, DK)), jnp.float32))
    k = l2_normalize(jnp.asarray(r.normal(size=(B, T, H, DK)), jnp.float32))
    v = jnp.asarray(r.normal(size=(B, T, H, DV)), jnp.float32)
    g = -jnp.asarray(r.uniform(0.0, 1.5, size=(B, T, H)), jnp.float32)
    beta = jnp.asarray(r.uniform(0.0, beta_max, size=(B, T, H)), jnp.float32)
    state = jnp.asarray(r.normal(size=(B, H, DK, DV)), jnp.float32)
    return q * DK ** -0.5, k, v, g, beta, state


def _token_by_token(q, k, v, g, beta, state):
    """The recurrence as written, in float64 numpy."""
    q, k, v, g, beta, s = (np.asarray(a, np.float64)
                           for a in (q, k, v, g, beta, state))
    B, T = q.shape[:2]
    out = np.zeros((B, T, H, DV))
    for t in range(T):
        s = s * np.exp(g[:, t])[..., None, None]
        kv = np.einsum("bhk,bhkv->bhv", k[:, t], s)
        u = beta[:, t][..., None] * (v[:, t] - kv)
        s = s + k[:, t][..., None] * u[..., None, :]
        out[:, t] = np.einsum("bhk,bhkv->bhv", q[:, t], s)
    return out, s


@pytest.mark.parametrize("T", [1, 5, CHUNK, CHUNK + 1, 3 * CHUNK - 7, 200])
def test_chunked_equals_the_recurrence(T):
    args = _inputs(T, 2, T)
    want_o, want_s = _token_by_token(*args)
    o, s = jax.jit(gated_delta_chunked)(*args)
    assert float(args[4].max()) > 1.0 or T == 1     # beta up to 2
    np.testing.assert_allclose(o, want_o, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s, want_s, atol=2e-4, rtol=2e-4)


def test_step_equals_the_recurrence():
    q, k, v, g, beta, state = _inputs(7, 2, 9)
    want_o, want_s = _token_by_token(q, k, v, g, beta, state)
    s = state
    for t in range(9):
        o, s = gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                beta[:, t], s)
        np.testing.assert_allclose(o, want_o[:, t], atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s, want_s, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("cuts", [(1,), (64,), (17, 100), (3, 64, 65, 190)])
def test_one_sequence_split_into_calls_that_carry_the_state(cuts):
    """Recurrence and convolution over one sequence in pieces, the state
    and the tail handed on, against the whole sequence in one call."""
    T, C, K = 200, H * (2 * DK + DV), 4
    r = np.random.default_rng(11)
    x = jnp.asarray(r.normal(size=(2, T, C)), jnp.float32)
    kernel = jnp.asarray(r.uniform(-0.5, 0.5, size=(K, C)), jnp.float32)
    _, _, _, g, beta, state = _inputs(12, 2, T)

    def run(x, g, beta, state, tail):
        y, tail = causal_conv(x, tail, kernel)
        y = jax.nn.silu(y).reshape(x.shape[0], x.shape[1], H, 2 * DK + DV)
        q = l2_normalize(y[..., :DK]) * DK ** -0.5
        k = l2_normalize(y[..., DK:2 * DK])
        o, state = gated_delta_chunked(q, k, y[..., 2 * DK:], g, beta, state)
        return o, state, tail

    tail0 = jnp.zeros((2, K - 1, C), jnp.float32)
    want_o, want_s, want_tail = run(x, g, beta, state, tail0)
    outs, s, tail = [], state, tail0
    for a, b in zip((0,) + cuts, cuts + (T,)):
        o, s, tail = run(x[:, a:b], g[:, a:b], beta[:, a:b], s, tail)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want_o,
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s, want_s, atol=2e-4, rtol=2e-4)
    np.testing.assert_array_equal(tail, want_tail)


@pytest.mark.parametrize("form", ["chunked", "step"])
def test_a_pad_row_leaves_state_and_tail_bit_identical(form):
    q, k, v, g, beta, state = _inputs(3, 3, 70)
    row = jnp.array([True, False, True])
    if form == "step":
        _, s = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], state, mask=row)
    else:
        _, s = gated_delta_chunked(q, k, v, g, beta, state,
                                   mask=jnp.broadcast_to(row[:, None],
                                                         (3, 70)))
    np.testing.assert_array_equal(s[1], state[1])
    assert not np.array_equal(s[0], state[0])
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 70, 5)),
                    jnp.float32)
    tail = jnp.asarray(np.random.default_rng(1).normal(size=(3, 3, 5)),
                       jnp.bfloat16)
    _, new = causal_conv(x, tail, jnp.ones((4, 5)),
                         n_real=jnp.array([70, 0, 2]))
    np.testing.assert_array_equal(new[1], tail[1])
    np.testing.assert_array_equal(new[0], x[0, -3:].astype(jnp.bfloat16))
    np.testing.assert_array_equal(
        new[2], jnp.concatenate([tail[2, 2:], x[2, :2].astype(jnp.bfloat16)]))


@pytest.mark.parametrize("n_real", [1, 40, CHUNK, 100])
def test_a_pad_tail_does_not_advance_the_state(n_real):
    """What the pad tail holds reaches nothing, bit for bit, and the state
    is the one after the real tokens."""
    T = 128
    q, k, v, g, beta, state = _inputs(5, 2, T)
    mask = jnp.broadcast_to(jnp.arange(T)[None] < n_real, (2, T))
    o, s = gated_delta_chunked(q, k, v, g, beta, state, mask=mask)
    other = _inputs(6, 2, T)

    def spliced(a, b):
        m = mask.reshape(mask.shape + (1,) * (a.ndim - 2))
        return jnp.where(m, a, b)

    o2, s2 = gated_delta_chunked(
        *(spliced(a, b) for a, b in zip((q, k, v, g, beta), other[:5])),
        state, mask=mask)
    np.testing.assert_array_equal(s, s2)
    np.testing.assert_array_equal(o[:, :n_real], o2[:, :n_real])
    _, want = gated_delta_chunked(q[:, :n_real], k[:, :n_real],
                                  v[:, :n_real], g[:, :n_real],
                                  beta[:, :n_real], state)
    np.testing.assert_allclose(s, want, atol=1e-5, rtol=1e-5)
