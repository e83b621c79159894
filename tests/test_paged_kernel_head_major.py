"""The fused paged decode kernel over pools whose pages the v5e stores
HEAD-major (``pallas_paged_attention.head_major_rows``: kv head counts
that are no multiple of 8, Olmo-Hybrid's 30 among them): rows taken as
(kv head, key) pairs, in interpret mode against the gather path."""

import jax.numpy as jnp
import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
    paged_attention,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_paged_attention import (
    head_major_rows,
    paged_decode_attention,
)


def test_the_rule_is_the_v5es_default_layouts():
    """``[N, 16, H, 128]``, bf16 and float32 alike (rehearsal compiles for
    the v5e, PR 33): pages head-major for 12 and 30, key-major for every
    count PR 29 measured the kernel at."""
    assert [h for h in (1, 2, 4, 8, 12, 16, 24, 30, 32, 40)
            if head_major_rows(h)] == [12, 30]


@pytest.mark.parametrize("Hkv, G, window", [(30, 1, None), (12, 2, None),
                                            (6, 1, 40), (3, 2, None)])
def test_head_major_rows_match_xla(Hkv, G, window):
    assert head_major_rows(Hkv)
    rng = np.random.RandomState(Hkv)
    S, D, bs, nb = 4, 128, 16, 8
    N = 1 + S * nb
    pk, pv = (jnp.asarray(rng.randn(N, bs, Hkv, D).astype(np.float32))
              for _ in range(2))
    tables = jnp.asarray(rng.permutation(np.arange(1, N))
                         .reshape(S, nb).astype(np.int32))
    q = jnp.asarray(rng.randn(S, Hkv * G, D).astype(np.float32) * 0.3)
    ctx = jnp.asarray(np.array([65, 128, 0, 17], np.int32))
    got = np.asarray(paged_decode_attention(q, pk, pv, tables, ctx,
                                            width=128, window=window))
    want = np.asarray(paged_attention(q, pk, pv, tables, ctx, width=128,
                                      impl="xla", window=window))
    act = np.asarray(ctx) > 0
    np.testing.assert_allclose(got[act], want[act], rtol=1e-5, atol=1e-5)
    assert np.all(got[~act] == 0.0)           # an empty slot reads zeros
