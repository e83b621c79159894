"""The fused latent-prefill kernel (``ops/pallas_latent_attention.py``,
ISSUE 32) against the XLA key-block loop it replaces on a TPU
(``models/deepseek_v2.py::attend_expanded``), in interpret mode on the
CPU: the same mathematics, the same mask from ``start`` and
``key_valid``, and the skip a row at a time. Then the chooser
(``expanded_form``: a pure function of platform, mesh, shapes and type),
the model's plain forward by either form, the gradient through the
kernel form, and the kernel compiled for the v5e at the published
widths, with the paged DECODE kernel of the absorbed form
(``ops/pallas_paged_latent_attention.py``, ISSUE 34) beside it at the
cell's shape, and the fused one-token step of the gated delta rule
(``ops/pallas_gated_delta.py``, ISSUE 36) alone and inside Olmo-Hybrid's
whole decode step, and a prefill dispatch's write of whole pages
(``ops/attention.py::scatter_paged_blocks``, ISSUE 38) alone and inside
Qwen's prefill programs: one file describes the topology."""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
    deepseek_v2 as D,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
    pallas_latent_attention as K,
)

# small widths, a row of one lane tile as the cache stores it
RANK, NOPE, ROT, VD, ROW, HEADS, BLOCK = 16, 16, 8, 16, 128, 2, 16
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2e-2}


def _operands(dtype, B, S, W, seed=3):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    q_nope = jax.random.normal(k[0], (B, S, HEADS, NOPE)).astype(dtype)
    q_pe = jax.random.normal(k[1], (B, S, HEADS, ROT)).astype(dtype)
    latent = jnp.pad(jax.random.normal(k[2], (B, W, RANK + ROT)),
                     [(0, 0), (0, 0), (0, ROW - RANK - ROT)]).astype(dtype)
    w = (jax.random.normal(k[3], (RANK, HEADS, NOPE + VD)) * 0.3).astype(dtype)
    return q_nope, q_pe, latent, w


def _both(ops, start, valid, S, W, **kw):
    """(XLA loop, kernel[, steps]) on the same operands and mask."""
    q_nope, q_pe, latent, w = ops
    start = jnp.asarray(start, jnp.int32)
    want = D.attend_expanded(
        q_nope, q_pe, latent, D.mask_bias(start, S, valid, W), w,
        rank=RANK, scale=0.2, key_block=BLOCK)
    got = K.latent_prefill_attention(
        q_nope, q_pe, latent, w, start, valid, rank=RANK, scale=0.2,
        block=BLOCK, **kw)
    return want, got


def _engine_valid(start, S, W):
    """``_prefill_chunk``'s key_valid: the keys below ``start + C``."""
    return jnp.arange(W)[None, :] < jnp.asarray(start)[:, None] + S


# (rows' starts, queries, bucket, key_valid): every edge of a dispatch
_CASES = {
    "one_row": ([32], 16, 64, "engine"),
    "four_rows_four_starts": ([0, 16, 32, 48], 16, 64, "engine"),
    # a prefix-cache hit leaves a start that is no multiple of the block:
    # two blocks straddle the diagonal
    "starts_off_the_block": ([4, 20, 41, 7], 16, 64, "engine"),
    # a prompt's last chunk: real tokens, then a pad tail whose keys sit
    # behind every real query; nothing but the mask says so
    "ragged_last_chunk": ([16, 48], 16, 64, "engine"),
    # a pad row rides start 0 against the null table's zeros
    "pad_row": ([48, 0], 16, 64, "pad"),
    "bucket_four_times_the_context": ([0, 16], 16, 128, "engine"),
    # the plain forward: no cache, start 0, two query blocks, a padding
    # mask with holes
    "plain_forward_with_holes": ([0, 0], 32, 32, "holes"),
    "plain_forward_no_mask": ([0, 0, 0], 32, 32, None),
}


@pytest.mark.parametrize("dtype,q_rows", [
    (jnp.float32, None), (jnp.bfloat16, None), (jnp.float32, 8)],
    ids=["float32", "bfloat16", "float32_two_passes"])
@pytest.mark.parametrize("case", list(_CASES))
def test_kernel_is_the_xla_form(case, dtype, q_rows, monkeypatch):
    """``q_rows``: the queries a pass of a step attends, cut to half a
    block so that a step makes two passes as it does on the chip (512
    queries, 256 a pass)."""
    if q_rows:
        monkeypatch.setattr(K, "_Q_ROWS", q_rows)
    start, S, W, mask = _CASES[case]
    ops = _operands(dtype, len(start), S, W)
    if mask is None:
        valid = None
    elif mask == "holes":
        holes = jax.random.uniform(jax.random.PRNGKey(9), (len(start), W))
        valid = (holes > 0.3).at[:, 0].set(True)
    else:
        valid = _engine_valid(start, S, W)
        if mask == "pad":
            ops = (*ops[:2], ops[2].at[-1].set(0), ops[3])
    want, got = _both(ops, start, valid, S, W)
    assert got.shape == want.shape == (len(start), S, HEADS, VD)
    assert got.dtype == want.dtype == dtype
    err = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max()
    assert float(err) <= TOL[dtype], f"{case}: {float(err)}"


@pytest.mark.parametrize("start,S,W", [
    ([0, 16, 32, 48], 16, 128),        # a bucket twice to eight times
    ([4, 20, 41, 7], 16, 128),         # the context, starts off the block
    ([0, 40], 32, 128),                # two query blocks a row
], ids=["aligned", "off_the_block", "two_query_blocks"])
def test_a_row_runs_the_blocks_its_own_context_needs(start, S, W):
    """The skip is per row: a (row, head, query block) runs the key
    blocks up to its last query's and no other, whatever the bucket and
    whatever the other rows hold (the XLA form runs a block when any row
    sees it). What is skipped is not read either: NaN rows behind every
    row's last needed block leave the result finite and unchanged."""
    q_nope, q_pe, latent, w = _operands(jnp.float32, len(start), S, W)
    valid = _engine_valid(start, S, W)
    want, (got, steps) = _both((q_nope, q_pe, latent, w), start, valid, S, W,
                               count_steps=True)
    nq = S // BLOCK
    expect = [[(s + (iq + 1) * BLOCK - 1) // BLOCK + 1 for iq in range(nq)]
              for s in start]
    assert steps.shape == (len(start), HEADS, nq)
    for h in range(HEADS):
        assert np.asarray(steps[:, h]).tolist() == expect
    assert int(steps.sum()) < len(start) * HEADS * nq * (W // BLOCK)
    np.testing.assert_allclose(got, want, atol=2e-6)
    past = (jnp.arange(W)[None, :]
            >= jnp.asarray([e[-1] * BLOCK for e in expect])[:, None])
    poisoned = jnp.where(past[:, :, None], jnp.nan, latent)
    again = K.latent_prefill_attention(
        q_nope, q_pe, poisoned, w, jnp.asarray(start, jnp.int32), valid,
        rank=RANK, scale=0.2, block=BLOCK)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_shapes_off_the_block_are_refused_by_name():
    ops = _operands(jnp.float32, 1, 12, 64)
    with pytest.raises(ValueError, match="by the XLA form"):
        K.latent_prefill_attention(*ops, rank=RANK, scale=0.2, block=BLOCK)


def test_a_gradient_through_the_kernel_form_is_the_xla_forms():
    """The kernel has no backward pass; ``attend_expanded_kernel``
    recomputes one through the XLA form, so a plain forward that took the
    kernel still trains."""
    q_nope, q_pe, latent, w = _operands(jnp.float32, 2, 16, 32)
    start = jnp.zeros((2,), jnp.int32)
    valid = jnp.ones((2, 32), bool).at[1, 5].set(False)
    bias = D.mask_bias(start, 16, valid, 32)

    def loss(form):
        def f(qn, qp, lat, w):
            out = (D.attend_expanded_kernel(qn, qp, lat, w, start, valid,
                                            RANK, 0.2, BLOCK)
                   if form == "kernel" else
                   D.attend_expanded(qn, qp, lat, bias, w, rank=RANK,
                                     scale=0.2, key_block=BLOCK))
            return (out ** 2).sum()
        return jax.grad(f, argnums=(0, 1, 2, 3))(q_nope, q_pe, latent, w)

    for a, b in zip(loss("kernel"), loss("xla_loop")):
        np.testing.assert_allclose(a, b, atol=1e-5)


# -- the chooser ---------------------------------------------------------------

_PUBLISHED = D.DeepseekV2Config(dtype=jnp.bfloat16)
_SEEN = dict(cfg=_PUBLISHED, q_len=512, width=8192, platform="tpu",
             mesh=False)


@pytest.mark.parametrize("seen,want", [
    # the engine's three prefill programs on the chip ...
    ({}, "kernel"),
    ({"width": 2048}, "kernel"),
    ({"q_len": 1024, "width": 1024}, "kernel"),
    ({"cfg": dataclasses.replace(_PUBLISHED, dtype=jnp.float32)}, "kernel"),
    # ... and the XLA loop for anything else the code can see
    ({"platform": "cpu"}, "xla_loop"),
    ({"platform": "gpu"}, "xla_loop"),
    ({"mesh": True}, "xla_loop"),
    ({"q_len": 8, "width": 8}, "xla_loop"),            # model.init's dummy
    ({"q_len": 40, "width": 8192}, "xla_loop"),
    ({"width": 8192 + 256}, "xla_loop"),
    ({"cfg": dataclasses.replace(_PUBLISHED, dtype=jnp.float16)},
     "xla_loop"),
    ({"cfg": dataclasses.replace(_PUBLISHED, kv_lora_rank=448)},
     "xla_loop"),
    ({"cfg": dataclasses.replace(_PUBLISHED, qk_nope_head_dim=64)},
     "xla_loop"),
    ({"cfg": dataclasses.replace(_PUBLISHED, v_head_dim=192)}, "xla_loop"),
])
def test_the_form_is_a_function_of_what_the_code_sees(seen, want):
    kw = {**_SEEN, **seen}
    assert D.expanded_form(kw.pop("cfg"), kw.pop("q_len"), kw.pop("width"),
                           **kw) == want
    assert want in D.EXPANDED_FORMS


def test_on_this_cpu_every_call_is_the_xla_loop():
    model = D.DeepseekV2ForCausalLM(_PUBLISHED)
    assert model.expanded_form(512, 8192) == "xla_loop"
    # and the path stays two-valued: HOW a chunk attends is another answer
    assert {D.latent_path(n) for n in (1, 2, 512)} == {"absorbed",
                                                       "expanded"}


# -- the model by either form --------------------------------------------------

@pytest.mark.parametrize("masked", [False, True], ids=["whole", "padded"])
def test_the_plain_forward_by_the_kernel_is_the_one_by_the_loop(
        lane_latent, seen_as_tpu, monkeypatch, masked):
    _cfg, model, params = lane_latent
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 3, 120)
    mask = (jnp.ones((2, 24), jnp.int32).at[1, 17:].set(0) if masked
            else None)
    assert model.expanded_form(24, 24) == "kernel"
    assert model.expanded_form(20, 20) == "xla_loop"
    ran = []
    kernel = K.latent_prefill_attention
    monkeypatch.setattr(K, "latent_prefill_attention",
                        lambda *a, **kw: ran.append(1) or kernel(*a, **kw))
    forward = lambda: jax.jit(                              # noqa: E731
        lambda p: model.apply({"params": p}, ids, mask))(params)
    got = forward()
    assert len(ran) == 2                      # once a layer
    monkeypatch.undo()
    jax.clear_caches()
    assert model.expanded_form(24, 24) == "xla_loop"
    want = forward()
    real = np.ones((2, 24), bool) if mask is None else np.asarray(mask) > 0
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               atol=2e-5)


# -- compiled for the chip -----------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows,width,dtype", [
    (4, 8192, jnp.bfloat16), (1, 2048, jnp.bfloat16), (4, 2048, jnp.float32)],
    ids=["g4_w8192_bf16", "g1_w2048_bf16", "g4_w2048_f32"])
def test_the_kernel_compiles_for_the_v5e_at_published_widths(
        one_chip, rows, width, dtype):
    """Mosaic's tile rules and the 16 MiB of VMEM a kernel gets are not
    seen in interpret mode: compile doc-sat's dispatches for the chip
    that is described, not attached. No score block is among the
    program's buffers."""
    cfg = dataclasses.replace(_PUBLISHED, dtype=dtype)
    H, C = cfg.num_heads, 512
    assert D.expanded_form(cfg, C, width, platform="tpu",
                           mesh=False) == "kernel"

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(
        lambda qn, qp, lat, w, st, kv: K.latent_prefill_attention(
            qn, qp, lat, w, st, kv, rank=cfg.kv_lora_rank,
            scale=cfg.softmax_scale, block=D.KEY_BLOCK, interpret=False)
    ).lower(sds((rows, C, H, cfg.qk_nope_head_dim)),
            sds((rows, C, H, cfg.qk_rope_head_dim)),
            sds((rows, width, D.latent_width(cfg))),
            sds((cfg.kv_lora_rank, H, cfg.qk_nope_head_dim + cfg.v_head_dim)),
            sds((rows,), jnp.int32), sds((rows, width), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"f32[{rows},{H},{C},{D.KEY_BLOCK}]" not in text
    # queries head-major and padded, and nothing else of any size
    q_bytes = rows * H * C * 256 * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes <= 2.1 * q_bytes


@pytest.mark.parametrize("slots", [32, 64])
def test_the_paged_decode_kernel_compiles_for_the_v5e_at_the_cells_shape(
        one_chip, slots):
    """The fused paged DECODE kernel of the absorbed form
    (``ops/pallas_paged_latent_attention.py``, ISSUE 34; its parity
    tests are ``tests/test_paged_latent_kernel.py``) at doc-sat's shape:
    32 slots of 128 heads against a pool of 19,531 pages of ``[16,
    640]`` bf16 through tables of the 8,192 bucket, the step's row
    written first as the model's paged branch writes it. The pool is
    written where it lies and read by the kernel from there: no copy of
    it is in the program, and nothing else of any size. Twice the slots
    compile too: a slot's query and output pass through the kernel's
    fast memory one at a time."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        scatter_paged_kv,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_paged_latent_attention import (
        paged_latent_decode_attention,
    )

    cfg = _PUBLISHED
    row, pages = D.latent_width(cfg), 19531

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(q, pool, tables, ctx, new):
        pool = scatter_paged_kv(pool, tables, ctx, new)
        return paged_latent_decode_attention(
            q, pool, tables, ctx + 1, rank=cfg.kv_lora_rank,
            scale=cfg.softmax_scale, interpret=False), pool

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        sds((slots, cfg.num_heads, row)), sds((pages, 16, row)),
        sds((slots, 8192 // 16), jnp.int32), sds((slots,), jnp.int32),
        sds((slots, row))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    pool_ops = [line for line in text.splitlines()
                if f"= bf16[{pages},16,{row}]" in line]
    assert pool_ops and not any(" copy(" in line for line in pool_ops)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= 1 << 20
    assert memory.alias_size_in_bytes >= pages * 16 * row * 2


# -- the gated delta rule's one-token step (ISSUE 36) ---------------------------

_STATE = dict(B=64, H=30, dk=96, dv=192)      # olmo-hybrid-7b-pp2-gen-sat


@pytest.mark.parametrize("packed,pool_bytes", [
    (True, 141_557_760), (False, 188_743_680)], ids=["whole_tile", "plain"])
def test_the_state_step_kernel_compiles_for_the_v5e_at_the_cells_shape(
        one_chip, packed, pool_bytes):
    """The kernel at 64 slots of 30 heads of ``96 x 192``. The donated
    pool is written where it lies (its bytes are the program's aliased
    bytes, and nothing else of any size is allocated), and on the chip
    the whole-tile pool ``[64, 15, 96, 384]`` is the 141.6 MB its shape
    counts where ``[64, 30, 96, 192]`` is 188.7 MB: 192 lanes are padded
    to 256."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
        pallas_gated_delta as G,
    )

    B, H, dk, dv = (_STATE[k] for k in ("B", "H", "dk", "dv"))
    assert G.state_step(H, dk, dv, platform="tpu") == "kernel"
    shape = (B,) + (G.state_layout(H, dk, dv) if packed else (H, dk, dv))

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(
        lambda q, k, v, g, b, s, m: G.gated_delta_step_packed(
            q, k, v, g, b, s, m, interpret=False), donate_argnums=5,
    ).lower(sds((B, H, dk)), sds((B, H, dk)), sds((B, H, dv)), sds((B, H)),
            sds((B, H)), sds(shape), sds((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gated_delta_step" in text
    dims = ",".join(str(d) for d in shape)
    assert not [line for line in text.splitlines()
                if f"= f32[{dims}]" in line and " copy(" in line]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == pool_bytes
    assert memory.temp_size_in_bytes <= 1 << 20


def test_olmo_hybrids_decode_step_holds_the_kernel_and_no_pass_over_the_state(
        one_chip, monkeypatch):
    """The whole ``_paged_decode_step`` of the published configuration as
    a TPU runs it: twelve calls of the kernel stand where the jnp step's
    two fusions a layer were, no reduction runs over a state-shaped
    operand, no state pool is copied, and the state pools are aliased at
    the bytes their shapes count (12 x 141.6 MB, not 12 x 188.7 MB)."""
    from chipbench import spec
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.olmo_hybrid import (
        OlmoHybridForCausalLM,
        olmo_hybrid_config_from_hf,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve import engine

    # the kernels' wrappers ask jax.devices() whether to interpret: steer
    # them here, in the test, to lower for the TPU
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: list(one_chip.device_set))
    cfg = spec.load_json(os.path.join(spec.HERE, "configs",
                                      "olmo-hybrid-7b-pp2.json"))
    dep = cfg["deployment"]
    model = OlmoHybridForCausalLM(olmo_hybrid_config_from_hf(
        cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    assert model.state_step() == "kernel"
    dummy = jnp.ones((1, 8), jnp.int32)
    pshape = jax.eval_shape(
        lambda k: model.init(k, dummy, dummy)["params"], jax.random.PRNGKey(0))
    plan, pool_shapes = engine.build_cache_plan(model, pshape,
                                                dep["max_model_len"])
    assert plan.state_shapes.count(((15, 96, 384), "float32")) == 12
    token_bytes = sum(h * d * np.dtype(t).itemsize for h, d, t in pool_shapes)
    blocks = 1 + dep["kv_pool_bytes"] // (dep["block_size"] * token_bytes)
    n, nb = dep["num_slots"], dep["max_model_len"] // dep["block_size"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    step = jax.jit(
        lambda p, pools, states, *a: engine._paged_decode_step(
            model, p, pools, *a, plan, 1024, False, states),
        donate_argnums=(1, 2))
    pools = [sds(shape, t) for shape, (_h, _d, t) in zip(
        engine.pool_dims(plan, pool_shapes, blocks, dep["block_size"]),
        pool_shapes)]
    states = [sds((n,) + shape, jnp.dtype(t))
              for shape, t in plan.state_shapes]
    compiled = step.lower(
        jax.tree_util.tree_map(lambda l: sds(l.shape, l.dtype), pshape),
        pools, states, sds((n,), jnp.int32), sds((n, nb), jnp.int32), sds((n,), jnp.int32),
        sds((n,), jnp.bool_), sds((n,), jnp.float32), sds((n,), jnp.int32),
        sds((n,), jnp.float32), sds((n, 2), jnp.uint32),
        sds((n,), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "gated_delta_step" in line]
    assert len(calls) == 12
    state = r"f32\[64,(15,96,384|30,96,192)\]"
    for line in text.splitlines():
        if re.search(state, line):
            assert not re.search(state + r"\S* (copy|slice)\(", line), line
            assert "multiply_reduce_fusion" not in line, line
            assert "multiply_add_fusion" not in line, line
    donated = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in pools + states)
    # (padded pools would be 566 MB more)
    aliased = compiled.memory_analysis().alias_size_in_bytes
    assert donated <= aliased <= 1.01 * donated


# -- a prefill dispatch's pages, written where they lie (ISSUE 38) ---------------

def _pool_ops(text: str, blocks: int) -> set:
    """The opcodes of the instructions whose result has a pool's full
    shape (``blocks`` leading rows) in a compiled program."""
    return {m.group(1) for m in re.finditer(
        rf"= \S*\[{blocks},[^ ]* ([\w\-]+)\(", text)}


# what a pool may be in a program that writes it in place: the argument,
# free views of it, the scatter (inside its fusion) and the fusion itself
_IN_PLACE = {"parameter", "bitcast", "scatter", "fusion", "get-tuple-element"}


@pytest.mark.parametrize("shape,rows", [
    ((7630, 16, 2, 128), 4), ((7630, 16, 2, 128), 1),
    ((3561, 16, 30, 128), 4), ((19532, 16, 640), 4)],
    ids=["qwen_g4", "qwen_g1", "olmo_g4", "latent_g4"])
def test_the_block_write_is_in_place_on_the_v5e(one_chip, shape, rows):
    """Key-major pages of two heads (as ``[N, 32, 128]`` rows: through
    the four axes the compiler re-lays the whole pool out around a
    block-windowed scatter), head-major pages of thirty, latent pages: a
    dispatch's 32 blocks a row go into the donated pool with no
    instruction of the pool's shape but the write."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        scatter_paged_blocks,
    )

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    heads = shape[2] if len(shape) == 4 else 1
    compiled = jax.jit(scatter_paged_blocks, donate_argnums=(0,)).lower(
        sds(shape), sds((rows, 256), jnp.int32), sds((rows,), jnp.int32),
        sds((rows, heads, 512, shape[-1]))).compile()
    assert _pool_ops(compiled.as_text(), shape[0]) <= _IN_PLACE
    pool_bytes = int(np.prod(shape)) * 2
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < 0.1 * pool_bytes


@pytest.mark.parametrize("rows", [1, 4])
def test_qwens_prefill_programs_hold_no_operation_of_a_pools_shape(one_chip,
                                                                  rows):
    """chat-sat's one-row and four-row ``prefill_chunk`` programs at the
    cell's pool geometry (two layers of the 36: four pools): nothing
    copies a pool. Before ISSUE 38 the one-row program re-laid every pool
    out to feed its gather (``copy bf16[7630,16,2,128]{3,1,2,0}``, 7.1 ms a
    dispatch over 72 pools), and both scattered a row a token."""
    from chipbench import spec
    from chipbench.families.llama import LlamaForCausalLM, llama_config_from_hf
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve import engine

    cfg = spec.load_json(os.path.join(spec.HERE, "configs", "qwen2.5-3b.json"))
    dep = cfg["deployment"]
    model = LlamaForCausalLM(llama_config_from_hf(
        dict(cfg, num_hidden_layers=2), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16))
    dummy = jnp.ones((1, 8), jnp.int32)
    pshape = jax.eval_shape(
        lambda k: model.init(k, dummy, dummy)["params"], jax.random.PRNGKey(0))
    plan, pool_shapes = engine.build_cache_plan(model, pshape,
                                                dep["max_model_len"])
    # the blocks the whole model's pools have: 36 layers' bytes a token
    blocks = 1 + dep["kv_pool_bytes"] // (dep["block_size"] * 36_864)
    assert blocks == 7630

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    g, c, bs = rows, dep["prefill_chunk"], dep["block_size"]
    assert engine.prefill_write_path(c, bs) == "pages"
    step = jax.jit(
        lambda p, pools, *a: engine._prefill_chunk(
            model, p, pools, *a, plan, False, 2048),
        donate_argnums=(1,))
    compiled = step.lower(
        jax.tree_util.tree_map(lambda l: sds(l.shape, l.dtype), pshape),
        [sds((blocks, bs, h, d), t) for h, d, t in pool_shapes],
        sds((g, c), jnp.int32), sds((g, dep["max_model_len"] // bs), jnp.int32),
        sds((g,), jnp.int32), sds((g,), jnp.int32), sds((g,), jnp.float32),
        sds((g,), jnp.int32), sds((g,), jnp.float32),
        sds((g, 2), jnp.uint32), sds((g,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "copy(%pools_" not in text
    assert _pool_ops(text, blocks) <= _IN_PLACE | {"tuple"}
