"""The fused paged decode kernel for latent attention
(``ops/pallas_paged_latent_attention.py``) against the absorbed form over
a gathered cache (``models/deepseek_v2.py::attend_absorbed``), in
interpret mode on a CPU (ISSUE 34): every edge of a slot's walk in one
batch of unequal contexts, both buckets' widths, bf16 and float32 pools,
shuffled block tables, an inactive row, and pages the contexts do not
reach never read. The rehearsal compile for the v5e at the cell's shape
is in ``tests/test_pallas_latent_attention.py`` (one file describes the
topology)."""

import jax.numpy as jnp
import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
    deepseek_v2 as D,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
    pallas_paged_attention,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
    gather_paged_kv,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_paged_latent_attention import (
    paged_latent_decode_attention,
)

# doc-sat's row cut small: 4 heads against rows of 128 + 64 values in 256
# lanes, pages of 16, block tables that span 128 keys, a compute block of
# TWO pages (32 keys) so that a table holds several
H, RANK, ROPE, NOPE, VD, ROW, BS, SPAN = 4, 128, 64, 16, 16, 256, 16, 128
SCALE = 0.1


@pytest.fixture
def two_page_blocks(monkeypatch):
    monkeypatch.setattr(pallas_paged_attention, "_BLOCK_KEYS", 2 * BS)


def _contexts(width):
    """An empty slot, one key, exactly one page, exactly one compute
    block, one key past it, and the bucket's full width."""
    return np.array([0, 1, BS, 2 * BS, 2 * BS + 1, width], np.int32)


def _case(seed, ctx, dtype):
    """(q_nope, q_pe, w_kvb, pool, tables) for ``len(ctx)`` slots: rows
    ``c | k_pe | zeros`` in a pool whose pages lie scattered."""
    rng = np.random.RandomState(seed)
    S, nb = len(ctx), SPAN // BS
    N = 1 + S * nb
    pool = np.zeros((N, BS, ROW), np.float32)
    pool[..., :RANK + ROPE] = rng.randn(N, BS, RANK + ROPE)
    tables = rng.permutation(np.arange(1, N)).reshape(S, nb)
    return (jnp.asarray(rng.randn(S, 1, H, NOPE) * 0.3, dtype),
            jnp.asarray(rng.randn(S, 1, H, ROPE) * 0.3, dtype),
            jnp.asarray(rng.randn(RANK, H, NOPE + VD) * 0.1, dtype),
            jnp.asarray(pool, dtype), jnp.asarray(tables.astype(np.int32)))


def _reference(q_nope, q_pe, w_kvb, pool, tables, ctx, width):
    """``attend_absorbed`` over the bucket's rows gathered by the tables,
    keys past each slot's context masked."""
    latent = gather_paged_kv(pool[:, :, None, :], tables, width=width)[:, 0]
    bias = jnp.where(jnp.arange(width)[None, None, :]
                     < jnp.asarray(ctx)[:, None, None], 0.0,
                     D.NEG_INF).astype(jnp.float32)
    return D.attend_absorbed(q_nope, q_pe, latent, bias, w_kvb, rank=RANK,
                             scale=SCALE)


def _kernel(q_nope, q_pe, w_kvb, pool, tables, ctx, width):
    """The model's paged branch, from the absorbed query to ``W_uv``."""
    q = D.absorbed_query(q_nope, q_pe, w_kvb, ROW, pool.dtype)[:, 0]
    o_lat = paged_latent_decode_attention(
        q, pool, tables[:, :width // BS], jnp.asarray(ctx), rank=RANK,
        scale=SCALE)
    assert o_lat.shape == (len(ctx), H, RANK) and o_lat.dtype == pool.dtype
    return D.absorbed_values(o_lat[:, None], w_kvb, NOPE), o_lat


def _assert_close(got, want, ctx, dtype):
    """Active rows to the precision of the pool's type (a bf16 weight is
    rounded at another point of the same sum in the two forms: before the
    division by the softmax's denominator in the kernel, after it in the
    reference)."""
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    act = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(got, np.float32)[act],
                               np.asarray(want, np.float32)[act],
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("width", [64, 128], ids=["b64", "b128"])
def test_kernel_is_the_absorbed_form_at_every_edge_of_a_walk(
        width, dtype, two_page_blocks):
    ctx = _contexts(width)
    case = _case(7, ctx, dtype)
    got, o_lat = _kernel(*case, ctx, width)
    _assert_close(got, _reference(*case, ctx, width), ctx, dtype)
    # the inactive row walked no page: exact zeros
    assert np.all(np.asarray(o_lat, np.float32)[0] == 0.0)


@pytest.mark.parametrize("ctx", [
    (0, 0, 0), (1, 1, 1), (128, 128, 128), (127, 3, 64), (5, 0, 97, 0, 33)],
    ids=["all-empty", "one-key", "full", "ragged", "empty-between"])
def test_unequal_contexts_at_the_shipped_block(ctx):
    """The block as it ships (512 keys: one ragged block a slot here) and
    the prefetch across slots, over empty slots too."""
    ctx = np.array(ctx, np.int32)
    case = _case(11, ctx, jnp.float32)
    got, o_lat = _kernel(*case, ctx, SPAN)
    _assert_close(got, _reference(*case, ctx, SPAN), ctx, jnp.float32)
    assert np.all(np.asarray(o_lat)[ctx == 0] == 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_pages_past_the_context_are_not_read(dtype, two_page_blocks):
    """NaN in every page a slot's context does not reach, the null page
    among them, changes nothing: the walk ends at the context's last
    page and an empty slot fetches nothing."""
    ctx = _contexts(SPAN)
    q_nope, q_pe, w_kvb, pool, tables = _case(8, ctx, dtype)
    want = _reference(q_nope, q_pe, w_kvb, pool, tables, ctx, SPAN)
    reached = np.zeros((pool.shape[0],), bool)
    for row, n in zip(np.asarray(tables), ctx):
        reached[row[:-(-int(n) // BS)]] = True
    junk = jnp.where(jnp.asarray(~reached)[:, None, None], jnp.nan, pool)
    got, _ = _kernel(q_nope, q_pe, w_kvb, junk, tables, ctx, SPAN)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    _assert_close(got, want, ctx, dtype)


def test_kernel_validates_its_inputs():
    ctx = np.array([4, 9], np.int32)
    q_nope, q_pe, w_kvb, pool, tables = _case(2, ctx, jnp.float32)
    q = D.absorbed_query(q_nope, q_pe, w_kvb, ROW, pool.dtype)[:, 0]
    with pytest.raises(ValueError, match="not the pool's row width"):
        paged_latent_decode_attention(q[..., :128], pool, tables,
                                      jnp.asarray(ctx), rank=RANK,
                                      scale=SCALE)
    with pytest.raises(ValueError, match="first 512 lanes"):
        paged_latent_decode_attention(q, pool, tables, jnp.asarray(ctx),
                                      rank=512, scale=SCALE)
