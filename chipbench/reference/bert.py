"""BERT for sequence classification, forward pass, as published
(Devlin et al. 2018; HF ``BertForSequenceClassification``): plain
``jax.numpy`` in float32 under ``highest`` matmul precision, no kernels.
Reads the program's parameter tree by name and nothing else of it.
Dropout is off (inference), the only departure from the training graph.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def forward(params, config: dict, input_ids, attention_mask, token_type_ids):
    """Classifier logits ``[B, num_labels]`` in float32."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        eps = config["layer_norm_eps"]
        heads = config["num_attention_heads"]
        emb = p["backbone"]["embeddings"]
        b, s = input_ids.shape
        x = (emb["word_embeddings"]["embedding"][input_ids]
             + emb["position_embeddings"]["embedding"][jnp.arange(s)][None]
             + emb["token_type_embeddings"]["embedding"][token_type_ids])
        x = _ln(x, emb["embeddings_ln"], eps)
        bias = (1.0 - attention_mask[:, None, None, :].astype(jnp.float32)) * -1e9
        d = x.shape[-1] // heads

        def split(t):
            return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

        for i in range(config["num_hidden_layers"]):
            lp = p["backbone"]["encoder"][f"layer_{i}"]
            a = lp["attention"]
            q, k, v = (split(_dense(x, a[n])) for n in ("query", "key", "value"))
            scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(float(d)) + bias
            ctx = jax.nn.softmax(scores, axis=-1) @ v
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
            x = _ln(x + _dense(ctx, a["attention_out"]), lp["attention_ln"], eps)
            h = jax.nn.gelu(_dense(x, lp["ffn"]["intermediate"]),
                            approximate=False)
            x = _ln(x + _dense(h, lp["ffn"]["ffn_out"]), lp["ffn_ln"], eps)
        pooled = jnp.tanh(_dense(x[:, 0], p["backbone"]["pooler"]["pooler"]))
        return _dense(pooled, p["classifier"])
