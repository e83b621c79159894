"""Each plain reference against the system at tiny size on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec


def _config(name):
    import os

    cfg = spec.load_json(os.path.join(spec.HERE, "configs", name + ".json"))
    return spec.merge(cfg, cfg["rehearsal"])


def test_bert_reference_agrees_with_the_program():
    from chipbench.families import bert as family
    from chipbench.reference import bert as ref

    cfg = _config("bert-large-uncased-wwm")
    model, params = family.build(cfg, 3, attention_impl="xla",
                                 dtype="float32")
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, cfg["vocab_size"], (4, 24)), jnp.int32)
    mask = jnp.asarray(np.array([[1] * 24, [1] * 20 + [0] * 4,
                                 [1] * 9 + [0] * 15, [1] * 24]), jnp.int32)
    tt = jnp.asarray(rng.integers(0, 2, (4, 24)), jnp.int32)
    got = model.apply({"params": params}, ids, mask, tt, deterministic=True)
    want = ref.forward(params, cfg, ids, mask, tt)
    assert want.shape == (4, 2)
    # float32 on both sides: only the order of summation differs
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the tolerance is tight enough to catch a dropped mask
    wrong = ref.forward(params, cfg, ids, jnp.ones_like(mask), tt)
    assert float(jnp.max(jnp.abs(wrong - want))) > 5e-5


@pytest.mark.parametrize("seq", [40, 1024])
def test_llama_reference_agrees_with_the_program(seq):
    from chipbench.families import llama as family
    from chipbench.reference import llama as ref

    cfg = _config("qwen2.5-3b")
    cfg["max_position_embeddings"] = 2048
    model, params = family.build(cfg, 5, dtype="float32")
    # q/k/v biases start at zero: give them values, or the test would
    # not notice a reference that leaves them out
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    key = jax.random.PRNGKey(1)
    leaves = []
    for path, leaf in flat:
        if "bias" in jax.tree_util.keystr(path):
            key, k = jax.random.split(key)
            leaf = 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
        leaves.append(leaf)
    params = jax.tree_util.tree_unflatten(treedef, leaves)
    tokens = jnp.asarray(np.random.default_rng(seq).integers(
        3, cfg["vocab_size"], (seq,)), jnp.int32)
    rows = jnp.arange(seq - 8, seq)
    got = model.apply({"params": params}, tokens[None],
                      jnp.ones((1, seq), jnp.int32))[0, seq - 8:]
    want = ref.logits(params, cfg, tokens, rows)
    np.testing.assert_allclose(got, want, atol=5e-4)
    # a shifted position (what a wrong cache offset would give) fails
    shifted = ref.logits(params, cfg, jnp.roll(tokens, 1), rows)
    assert float(jnp.max(jnp.abs(shifted - want))) > 1e-2
