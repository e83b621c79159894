"""The fused one-token step of the gated delta rule
(``ops/pallas_gated_delta.py``, ISSUE 36) against the jnp form it
replaces on a TPU (``ops/gated_delta.py::gated_delta_step``), in
interpret mode on the CPU: the same mathematics on the state as the
kernel carries it (heads side by side where that fills whole lane tiles),
a masked row's state bit for bit, the packing and its inverse, steps of
the kernel against one call of the chunked form through the packed
shape, and the rule that says which form a process runs. Then the fused
CHUNK kernel against the chunked XLA form and against steps
of the jnp step, pad tails and whole masked rows, its rule, and a
gradient through the mixer on the kernel path. (Both kernels compiled for
the v5e are in ``tests/test_pallas_latent_attention.py``: one file
describes the topology.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
    olmo_hybrid as O,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
    pallas_gated_delta as K,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops.gated_delta import (
    gated_delta_chunked,
    gated_delta_step,
    l2_normalize,
)

# (B, H, dk, dv): the cell's heads at a few slots, whole lane tiles as they
# are, the tiny test models', an odd H of the cell's heads (cannot pair)
SHAPES = [(3, 30, 96, 192), (2, 4, 64, 128), (2, 2, 8, 16), (2, 5, 96, 192)]
IDS = ["cell_h30_96x192", "h4_64x128", "tiny_h2_8x16", "odd_h5_96x192"]
LAYOUTS = [(15, 96, 384), (4, 64, 128), (2, 8, 16), (5, 96, 192)]


def _inputs(seed, B, H, dk, dv, T=None):
    r = np.random.default_rng(seed)
    t = () if T is None else (T,)

    def normal(*shape):
        return jnp.asarray(r.normal(size=shape), jnp.float32)

    q = l2_normalize(normal(B, *t, H, dk)) * dk ** -0.5
    k = l2_normalize(normal(B, *t, H, dk))
    v = normal(B, *t, H, dv)
    g = -jnp.asarray(r.uniform(0.0, 1.5, size=(B, *t, H)), jnp.float32)
    beta = jnp.asarray(r.uniform(0.0, 2.0, size=(B, *t, H)), jnp.float32)
    return q, k, v, g, beta, normal(B, H, dk, dv)


@pytest.mark.parametrize("shape,layout", zip(SHAPES, LAYOUTS), ids=IDS)
def test_the_layout_is_a_function_of_the_shapes(shape, layout):
    B, H, dk, dv = shape
    assert K.state_layout(H, dk, dv) == layout
    assert int(np.prod(layout)) == H * dk * dv
    state = _inputs(0, *shape)[-1]
    packed = K.pack(state)
    assert packed.shape == (B,) + layout
    np.testing.assert_array_equal(K.unpack(packed, H), state)
    G = K.heads_per_row(H, dv)
    # head G j + i of a row lies in lanes [i dv, (i + 1) dv) of packed row j
    np.testing.assert_array_equal(
        packed[:, (H - 1) // G, :, (H - 1) % G * dv:], state[:, H - 1])


@pytest.mark.parametrize("block", [None, 1])
@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_kernel_is_the_jnp_step(shape, block):
    """``o`` and ``S`` to float32 rounding (the kernel adds a column of
    ``dk`` products in another order); the row that is masked keeps its
    state bit for bit."""
    B, H, dk, dv = shape
    q, k, v, g, beta, state = _inputs(1, *shape)
    mask = jnp.arange(B) != 1
    want_o, want_s = gated_delta_step(q, k, v, g, beta, state, mask)
    o, s = K.gated_delta_step_packed(q, k, v, g, beta, K.pack(state), mask,
                                     block=block, interpret=True)
    assert o.shape == (B, H, dv) and s.shape == (B,) + K.state_layout(
        H, dk, dv)
    s = K.unpack(s, H)
    np.testing.assert_allclose(o, want_o, atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(s, want_s, atol=2e-6, rtol=2e-6)
    np.testing.assert_array_equal(s[1], state[1])
    assert float(jnp.abs(s[0] - state[0]).max()) > 1e-2    # the others moved


def test_kernel_takes_a_state_left_unpacked():
    """``[B, H, dk, dv]`` is a packing too (one head a row): what the
    microbenchmark times as the padded pool."""
    q, k, v, g, beta, state = _inputs(2, 2, 30, 96, 192)
    want_o, want_s = gated_delta_step(q, k, v, g, beta, state)
    o, s = K.gated_delta_step_packed(q, k, v, g, beta, state, interpret=True)
    np.testing.assert_allclose(o, want_o, atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(s, want_s, atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("shape", SHAPES[:3], ids=IDS[:3])
def test_steps_of_the_kernel_are_one_chunked_call(shape):
    """``T`` kernel steps, the packed state handed on, against one
    ``gated_delta_chunked`` call that unpacks the rows and packs them
    again, as the mixer runs a prefill chunk."""
    B, H, dk, dv = shape
    T = 5
    q, k, v, g, beta, state = _inputs(3, *shape, T=T)
    mask = jnp.ones((B, T), bool).at[1, 3:].set(False)
    want_o, want_s = gated_delta_chunked(
        q, k, v, g, beta, K.unpack(K.pack(state), H), mask)
    want_s = K.pack(want_s)
    s, out = K.pack(state), []
    for t in range(T):
        o, s = K.gated_delta_step_packed(
            q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s, mask[:, t],
            interpret=True)
        out.append(o)
    real = np.asarray(mask)
    np.testing.assert_allclose(np.stack(out, 1)[real],
                               np.asarray(want_o)[real], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=2e-5)


def test_a_state_that_is_no_packing_is_refused_by_name():
    q, k, v, g, beta, state = _inputs(4, 2, 4, 8, 64)
    with pytest.raises(ValueError, match="no packing"):
        K.gated_delta_step_packed(q, k, v, g, beta,
                                  state.reshape(2, 4, 64, 8), interpret=True)
    with pytest.raises(ValueError, match="does not divide"):
        K.gated_delta_step_packed(q, k, v, g, beta, state, block=3,
                                  interpret=True)


@pytest.mark.parametrize("seen,want", [
    (dict(H=30, dk=96, dv=192, platform="tpu"), "kernel"),
    (dict(H=30, dk=96, dv=192, platform="cpu"), "xla"),
    (dict(H=4, dk=64, dv=128, platform="tpu"), "kernel"),
    (dict(H=2, dk=8, dv=16, platform="tpu"), "xla"),      # 16 lanes a row
    (dict(H=5, dk=96, dv=192, platform="tpu"), "xla"),    # cannot pair
    (dict(H=16, dk=8, dv=16, platform="tpu"), "kernel"),  # eight a row
    (dict(H=16, dk=4, dv=16, platform="tpu"), "xla"),     # half a sublane tile
], ids=["cell_tpu", "cell_cpu", "whole_tiles", "tiny", "odd", "eight_a_row",
        "short_rows"])
def test_the_step_is_a_function_of_what_the_code_sees(seen, want):
    assert K.state_step(**seen) == want
    assert want in K.STATE_STEPS


def test_the_mixer_declares_the_kernels_layout_and_runs_xla_here():
    """The published heads on this CPU: the state is carried packed
    whatever the backend, the one-token call is the jnp step through an
    unpack and a pack, and a run of tokens ends on the same state."""
    hf = {"model_type": "olmo_hybrid", "vocab_size": 64, "hidden_size": 32,
          "intermediate_size": 48, "num_hidden_layers": 1,
          "num_attention_heads": 2, "num_key_value_heads": 2,
          "layer_types": [O.LINEAR], "linear_num_key_heads": 30,
          "linear_num_value_heads": 30, "linear_key_head_dim": 96,
          "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
          "linear_allow_neg_eigval": True,
          "rope_parameters": {"rope_theta": None},
          "max_position_embeddings": 64, "tie_word_embeddings": False}
    cfg = O.olmo_hybrid_config_from_hf(hf)
    model = O.OlmoHybridForCausalLM(cfg)
    assert O.state_step(cfg) == model.state_step() == "xla"
    assert O.state_chunk(cfg) == model.state_chunk() == "xla"
    ids = jnp.asarray(np.random.default_rng(5).integers(3, 60, (2, 6)),
                      jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    def run(cache, tokens):
        lg, mut = model.apply({"params": params, "cache": cache}, tokens,
                              decode=True, mutable=["cache"])
        return lg, mut["cache"]

    empty = jax.tree_util.tree_map(jnp.zeros_like, model.apply(
        {"params": params}, ids, decode=True, mutable=["cache"])[1]["cache"])
    leaf = empty["backbone"]["layers_0"]["linear_attn"]["recurrent_state"]
    assert leaf.shape == (2, 15, 96, 384) and leaf.dtype == jnp.float32
    whole_lg, whole = run(empty, ids)
    cache = empty
    for t in range(ids.shape[1]):
        lg, cache = run(cache, ids[:, t:t + 1])
    np.testing.assert_allclose(lg[:, 0], whole_lg[:, -1], atol=2e-4)
    got = cache["backbone"]["layers_0"]["linear_attn"]["recurrent_state"]
    want = whole["backbone"]["layers_0"]["linear_attn"]["recurrent_state"]
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(got).max()) > 0


# -- a run of tokens: the fused chunk kernel ------------------------------------

# (H, dk, dv) packed: the cell's heads two a row, and a small shape of two a
# row (64 values a head: two fill a lane tile)
CHUNK_SHAPES = [(30, 96, 192), (4, 8, 64)]
CHUNK_IDS = ["cell_h30_96x192", "small_h4_8x64"]


def _chunk_inputs(seed, B, T, H, dk, dv, n_real):
    """Operands of a run of ``T`` tokens and a mask whose row ``b`` is
    real up to ``n_real[b]`` (the pad tail after it; 0: a row masked
    whole), from a non-zero state."""
    q, k, v, g, beta, state = _inputs(seed, B, H, dk, dv, T=T)
    mask = jnp.arange(T)[None, :] < jnp.asarray(n_real)[:, None]
    return q, k, v, g, beta, state * 0.3, mask


@pytest.mark.parametrize("B,T,n_real", [
    (1, 512, [512]), (4, 64, [64, 30, 0, 64]), (4, 100, [100, 77, 0, 40])],
    ids=["b1_t512", "b4_t64", "b4_t100_ragged"])
@pytest.mark.parametrize("shape", CHUNK_SHAPES, ids=CHUNK_IDS)
def test_chunk_kernel_is_the_xla_form(shape, B, T, n_real):
    """``o`` and ``S`` to float32 rounding against ``gated_delta_chunked``
    through the packing; a pad tail mid-chunk (rows of 30, 77 and 40
    real tokens) advances nothing, and a row masked whole keeps its state
    bit for bit."""
    H, dk, dv = shape
    q, k, v, g, beta, state, mask = _chunk_inputs(11, B, T, H, dk, dv,
                                                  n_real)
    want_o, want_s = gated_delta_chunked(q, k, v, g, beta, state, mask)
    o, s = K.gated_delta_chunked_packed(q, k, v, g, beta, K.pack(state),
                                        mask, interpret=True)
    assert o.shape == (B, T, H, dv) and s.shape == (B,) + K.state_layout(
        H, dk, dv)
    s = K.unpack(s, H)
    np.testing.assert_allclose(o, want_o, atol=2e-6, rtol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-6, rtol=2e-5)
    for b, n in enumerate(n_real):
        if n == 0:
            np.testing.assert_array_equal(s[b], state[b])
        else:
            assert float(jnp.abs(s[b] - state[b]).max()) > 1e-2


@pytest.mark.parametrize("shape", CHUNK_SHAPES, ids=CHUNK_IDS)
def test_the_pad_tail_advances_nothing(shape):
    """A row of 77 real tokens ends on the state that its 77 tokens alone
    make: the tail's operands reach nothing."""
    H, dk, dv = shape
    q, k, v, g, beta, state, mask = _chunk_inputs(12, 1, 100, H, dk, dv,
                                                  [77])
    _, s = K.gated_delta_chunked_packed(q, k, v, g, beta, K.pack(state),
                                        mask, interpret=True)
    cut = [a[:, :77] for a in (q, k, v, g, beta)]
    _, alone = K.gated_delta_chunked_packed(*cut, K.pack(state),
                                            interpret=True)
    np.testing.assert_allclose(s, alone, atol=1e-6, rtol=1e-6)


def test_the_chunk_kernel_is_steps_of_the_step():
    """One kernel call over 70 tokens against 70 calls of the jnp step."""
    H, dk, dv = 4, 8, 64
    B, T = 2, 70
    q, k, v, g, beta, state, mask = _chunk_inputs(13, B, T, H, dk, dv,
                                                  [70, 45])
    o, s = K.gated_delta_chunked_packed(q, k, v, g, beta, K.pack(state),
                                        mask, interpret=True)
    want_s, out = state, []
    for t in range(T):
        o_t, want_s = gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                       beta[:, t], want_s, mask[:, t])
        out.append(o_t)
    real = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(o)[real],
                               np.stack(out, 1)[real], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(K.unpack(s, H), want_s, atol=2e-5, rtol=2e-5)


def test_a_chunk_on_a_state_that_is_no_packing_is_refused_by_name():
    q, k, v, g, beta, state, mask = _chunk_inputs(14, 2, 8, 4, 8, 64, [8, 8])
    with pytest.raises(ValueError, match="no packing"):
        K.gated_delta_chunked_packed(q, k, v, g, beta,
                                     state.reshape(2, 4, 64, 8),
                                     interpret=True)
    with pytest.raises(ValueError, match="power of two"):
        K.gated_delta_chunked_packed(q, k, v, g, beta, K.pack(state),
                                     chunk=48, interpret=True)


@pytest.mark.parametrize("seen,want", [
    (dict(H=30, dk=96, dv=192, platform="tpu"), "kernel"),
    (dict(H=30, dk=96, dv=192, platform="cpu"), "xla"),
    (dict(H=4, dk=64, dv=128, platform="tpu"), "kernel"),
    (dict(H=4, dk=8, dv=64, platform="tpu"), "kernel"),
    (dict(H=4, dk=8, dv=64, platform="cpu"), "xla"),
    (dict(H=2, dk=8, dv=16, platform="tpu"), "xla"),      # 16 lanes a row
    (dict(H=5, dk=96, dv=192, platform="tpu"), "xla"),    # cannot pair
    (dict(H=16, dk=4, dv=16, platform="tpu"), "xla"),     # half a sublane tile
], ids=["cell_tpu", "cell_cpu", "whole_tiles", "small_tpu", "small_cpu",
        "tiny", "odd", "short_rows"])
def test_the_chunk_form_is_a_function_of_what_the_code_sees(seen, want):
    assert K.state_chunk(**seen) == want
    assert want in K.STATE_STEPS


def test_a_gradient_through_the_mixer_on_the_kernel_path_is_the_xla_forms(
        monkeypatch):
    """The plain forward of a tiny model whose heads pack (four of ``8 x
    64``, two a row), its loss differentiated with respect to every
    parameter: through the kernel (steered as a TPU would see it; in the
    test, the program has no option for it) and through the XLA form, the
    same gradient, and the same logits."""
    hf = {"model_type": "olmo_hybrid", "vocab_size": 64, "hidden_size": 32,
          "intermediate_size": 48, "num_hidden_layers": 2,
          "num_attention_heads": 2, "num_key_value_heads": 2,
          "layer_types": [O.LINEAR, O.FULL], "linear_num_key_heads": 4,
          "linear_num_value_heads": 4, "linear_key_head_dim": 8,
          "linear_value_head_dim": 64, "linear_conv_kernel_dim": 4,
          "linear_allow_neg_eigval": True,
          "rope_parameters": {"rope_theta": None},
          "max_position_embeddings": 128, "tie_word_embeddings": False}
    cfg = O.olmo_hybrid_config_from_hf(hf)
    model = O.OlmoHybridForCausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(15).integers(3, 60, (2, 70)),
                      jnp.int32)
    attn = jnp.ones((2, 70), jnp.int32).at[1, 50:].set(0)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    def grad():              # a new function each call: a trace of its own
        def loss(p):
            lg = model.apply({"params": p}, ids, attn)
            return jnp.sum(lg[:, :50] ** 2) * 1e-3, lg
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)

    assert O.state_chunk(cfg) == model.state_chunk() == "xla"
    (want_l, want_lg), want_g = grad()
    monkeypatch.setattr(O, "state_chunk", lambda cfg: K.state_chunk(
        cfg.linear_num_heads, cfg.linear_key_head_dim,
        cfg.linear_value_head_dim, platform="tpu"))
    assert model.state_chunk() == "kernel"
    (got_l, got_lg), got_g = grad()
    np.testing.assert_allclose(got_lg, want_lg, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_l, want_l, rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))
    assert float(jnp.abs(got_g["backbone"]["layers_0"]["linear_attn"][
        "A_log"]).max()) > 0
