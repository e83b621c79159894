"""A prefill dispatch writes the pages of its chunk
(``ops.attention.scatter_paged_blocks``, ISSUE 38): one update a whole
block where the chunk is a multiple of the block size, the same values in
the same pool rows as ``scatter_paged_kv``'s one update a token; the row
form for any other chunk. The engine's token-identity tests hold the
whole path (``test_serve.py``, ``test_serve_latent.py``,
``test_serve_state.py``, ``test_serve_xing4.py``,
``test_paged_kernel_head_major.py``); here the write alone, which form a
lowered program holds, and the ``write_path`` that says so."""

import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu import obs

BS, C, NB = 4, 8, 10          # block, chunk (two blocks), table width

# pool shape without the block axes: (heads, head_dim), None = no heads axis
_POOLS = {
    "key_major_h2": (2, 8),       # chat-sat's pages: [bs, H, D]
    "head_major_h30": (30, 8),    # Olmo's: stored [H, bs, D]
    "head_major_h12": (12, 8),
    "latent": (None, 24),         # [N, bs, D]
    "int8_scale": (2, 1),         # [N, bs, H, 1] float32
}

# rows of one dispatch: (start in chunks, real?) a row
_DISPATCHES = {
    # every start the grid has, in one dispatch
    "start_0_and_3C": [(0, True), (3, True), (1, True), (3, True)],
    # an unused row rides the null table and writes block 0
    "pad_row": [(0, True), (0, False), (2, True), (0, False)],
    # a final chunk: the tail of its values is pad junk, written into the
    # row's own last blocks all the same (the scheduler trims them after)
    "pad_tail": [(4, True)],
}


def _case(pool_kind, dispatch, seed=0):
    import jax.numpy as jnp

    heads, dim = _POOLS[pool_kind]
    rows = _DISPATCHES[dispatch]
    G, N = len(rows), 1 + len(rows) * NB
    rng = np.random.RandomState(seed)
    shape = (N, BS, dim) if heads is None else (N, BS, heads, dim)
    pool = jnp.asarray(rng.randn(*shape).astype(np.float32))
    free = rng.permutation(np.arange(1, N))
    tables = np.zeros((G, NB), np.int32)
    for g, (_, real) in enumerate(rows):
        if real:
            tables[g] = free[g * NB:(g + 1) * NB]
    start = np.asarray([c * C for c, _ in rows], np.int32)
    values = jnp.asarray(
        rng.randn(G, heads or 1, C, dim).astype(np.float32))
    return pool, jnp.asarray(tables), jnp.asarray(start), values


def _by_rows(pool, tables, start, values):
    """The parent's write: one ``scatter_paged_kv`` row a token."""
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        scatter_paged_kv,
    )

    G, H, _, D = values.shape
    positions = (start[:, None] + jnp.arange(C)[None, :]).reshape(-1)
    rows = values.transpose(0, 2, 1, 3).reshape(G * C, H, D)
    return scatter_paged_kv(pool, jnp.repeat(tables, C, axis=0), positions,
                            rows[:, 0] if pool.ndim == 3 else rows)


@pytest.mark.parametrize("dispatch", list(_DISPATCHES))
@pytest.mark.parametrize("pool_kind", list(_POOLS))
def test_block_write_equals_the_row_scatter(pool_kind, dispatch):
    """Bit for bit on every block but the null one: the blocks a real
    table names hold what the row form wrote, every other block what it
    held."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        scatter_paged_blocks,
    )

    pool, tables, start, values = _case(pool_kind, dispatch)
    got = np.asarray(scatter_paged_blocks(pool, tables, start, values))
    want = np.asarray(_by_rows(pool, tables, start, values))
    np.testing.assert_array_equal(got[1:], want[1:])
    written = {int(tables[g, int(start[g]) // BS + j])
               for g in range(tables.shape[0]) for j in range(C // BS)} - {0}
    assert written, "the case writes no real block"
    for b in written:
        assert not np.array_equal(got[b], np.asarray(pool)[b])
    for b in set(range(1, pool.shape[0])) - written:
        np.testing.assert_array_equal(got[b], np.asarray(pool)[b])


def test_block_write_carries_a_sharded_heads_axis(devices8):
    """Under a tensor-parallel mesh a key-major page is written as ``[bs,
    H, D]``, the heads axis whole (the merged ``bs * H`` rows could not
    carry its sharding): the same bits."""
    import jax

    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        _key_major_page_rows,
        scatter_paged_blocks,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        MeshConfig,
        build_mesh,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
        use_mesh,
    )

    pool, tables, start, values = _case("key_major_h2", "start_0_and_3C")
    assert _key_major_page_rows(pool).shape == (pool.shape[0], BS * 2, 8)
    with use_mesh(build_mesh(MeshConfig(tp=2), devices=devices8[:2])):
        assert _key_major_page_rows(pool) is None
        got = jax.jit(scatter_paged_blocks)(pool, tables, start, values)
    np.testing.assert_array_equal(
        np.asarray(got)[1:],
        np.asarray(_by_rows(pool, tables, start, values))[1:])


def test_the_rule_is_the_chunk_on_the_block_grid():
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        prefill_write_path,
    )

    assert prefill_write_path(512, 16) == "pages"
    assert prefill_write_path(16, 16) == "pages"
    assert prefill_write_path(8, 16) == "rows"       # half a block
    assert prefill_write_path(12, 8) == "rows"


_TRACE = [(5, 7), (9, 3), (13, 6), (6, 4)]


def _serve(model, params, **kw):
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    rng = np.random.RandomState(7)
    trace = [(rng.randint(1, 120, (p,)).astype(np.int32), m)
             for p, m in _TRACE]
    eng = ServeEngine(model, params, num_slots=3, num_blocks=40,
                      max_model_len=48, **kw)
    reqs = [eng.submit(p, m) for p, m in trace]
    eng.run()
    return eng, [[int(t) for t in eng.output_ids(r)] for r in reqs]


def test_a_chunk_off_the_block_grid_takes_the_row_form_and_is_right(
        gpt2_setup):
    """``prefill_chunk`` 6 over blocks of 4: a chunk starts and ends inside
    blocks, so it is written a row a token, and serves the tokens an engine
    on the block grid serves."""
    _cfg, model, params = gpt2_setup
    rows, got = _serve(model, params, block_size=4, prefill_chunk=6)
    pages, want = _serve(model, params, block_size=4, prefill_chunk=8)
    assert rows.stats().write_path == "rows"
    assert pages.stats().write_path == "pages"
    assert got == want


def _pool_scatters(jaxpr, num_blocks: int):
    """``(indices, operand shape)`` of every scatter into an array of
    ``num_blocks`` leading rows (a pool, or a view of one) in a traced
    program, nested calls included."""
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name.startswith("scatter")
                and eqn.invars[0].aval.shape[0] == num_blocks):
            yield eqn.invars[1].aval.shape[0], eqn.invars[0].aval.shape
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pool_scatters(inner, num_blocks)


@pytest.mark.parametrize("kv_cache_dtype", ["fp", "int8"])
@pytest.mark.parametrize("chunk, path", [(8, "pages"), (6, "rows")])
def test_the_traced_program_scatters_blocks_not_tokens(gpt2_setup, chunk,
                                                       path, kv_cache_dtype):
    """The four-row ``prefill_chunk`` program of an engine on the block
    grid holds one scatter a pool (an int8 engine's scale pools too) of
    ``G * C / bs`` indices, a whole page each, and none of ``G * C`` (a
    token a row) or ``G * C * H``; off the grid it holds the row form's."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    _cfg, model, params = gpt2_setup
    bs, G, H, N = 4, 4, 2, 40
    eng = ServeEngine(model, params, num_slots=3, block_size=bs,
                      num_blocks=N, prefill_chunk=chunk, max_model_len=48,
                      prefill_batch=G, kv_cache_dtype=kv_cache_dtype)
    assert eng.write_path == path
    zf, zi = np.zeros((G,), np.float32), np.zeros((G,), np.int32)
    traced = eng._prefill_fn.trace(
        eng.model, eng.params, eng._pools, np.zeros((G, chunk), np.int32),
        np.zeros((G, 48 // bs), np.int32), zi, np.full((G,), -1, np.int32),
        zf, zi, zf, np.zeros((G, 2), np.uint32), zi, eng._plan, False, 48)
    found = list(_pool_scatters(traced.jaxpr.jaxpr, N))
    assert len(found) == len(eng._pools) == (
        8 if kv_cache_dtype == "int8" else 4)
    counts = {n for n, _shape in found}
    if path == "pages":
        assert counts == {G * chunk // bs}
        # a page's bs * H (key, head) rows at once
        assert {shape[1] for _n, shape in found} == {bs * H}
    else:
        assert counts <= {G * chunk, G * chunk * H}


def test_write_path_is_on_the_span_and_in_the_report(gpt2_setup, tmp_path):
    import json

    _cfg, model, params = gpt2_setup
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        eng, _ = _serve(model, params, block_size=4, prefill_chunk=8)
        obs.flush()
        with open(tmp_path / "telemetry" / "events.jsonl") as f:
            events = [json.loads(line) for line in f]
    finally:
        obs.reset(enabled=False)
    assert eng.stats().write_path == "pages"
    spans = [e for e in events if e.get("type") == "span"
             and e["name"] == "serve/prefill_chunk"]
    assert len(spans) == eng.prefill_dispatches > 0
    assert {e["args"]["write_path"] for e in spans} == {"pages"}
    report = [e for e in events if e.get("event") == "report"][-1]
    assert report["write_path"] == "pages"
