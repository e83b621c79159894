"""Decode throughput (bench.py --generate): tokens/s/chip per mode.

The reference's model objects carry ``generate`` via HF ``transformers``
(SURVEY.md D7; the reference itself only fine-tunes,
reference ``scripts/train.py:145``) — round 2 proved our decode paths
token-exact against HF; this mode measures them, one line each:

- ``gpt2_greedy``      GPT-2 (124M shape) prefill + jitted-scan greedy
                       continuation — the decoder-only path.
- ``gpt2_greedy_int8`` same, int8 weight-only dense kernels
                       (models/quant.py) — the HBM-bandwidth story:
                       decode re-reads all weights per token, so 1/4
                       the kernel bytes should show up as tokens/s.
- ``llama_greedy``     TinyLlama-1.1B shape (22L/2048H/32q/4kv heads,
                       GQA) prefill + cached greedy — the modern
                       decoder family at a real size (2.2 GB bf16).
- ``llama_greedy_int8`` same, int8 dense kernels.
- ``llama_greedy_b1``  same model at batch 1 — the baseline the
                       speculative line compares against (batch 1 is
                       the latency-bound single-stream case; the
                       speculative API itself batches).
- ``llama_self_spec_b1`` batch-1 greedy via layer-skip self-speculation
                       (draft = the model's own first ~1/5 layers,
                       k=4; models/generate.py::self_draft). Random
                       weights are the acceptance WORST CASE — real
                       checkpoints only accept more per window.
- ``bart_greedy``      BART-base encoder once + cached greedy decode —
                       the encoder-decoder path.
- ``bart_beam4``       same, beam search at 4 beams (beams flattened
                       into the batch dim, so the chip sees batch×beams).

tokens/s/chip counts GENERATED tokens only (batch × max_new_tokens ÷
wall; prefill/encoder cost is inside the wall clock, amortized over the
continuation — the standard way decode throughput is quoted). Each mode
runs once to compile, then the timed repeat; completion is forced by
``jax.device_get`` of the output ids (a host fetch of the real buffer).

``vs_baseline`` is 0.0: the reference publishes no decode numbers
(BASELINE.md) and there is no literature anchor at these exact shapes.

Off-TPU the models shrink to smoke-test size (the mode must stay
runnable in the CPU gate); TPU runs use the real 124M/139M shapes.
"""

from __future__ import annotations

import json
import time


def _bench_one(run, n_new_tokens: int, batch: int) -> float:
    """tokens/s for one decode config: compile pass, then timed pass."""
    import jax

    jax.device_get(run())          # compile + warm
    t0 = time.perf_counter()
    jax.device_get(run())          # real buffers fetched → fully done
    wall = time.perf_counter() - t0
    return batch * n_new_tokens / wall


def bench_generate() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import _on_tpu
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import init_params
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.bart import (
        BartConfig,
        BartForConditionalGeneration,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
        beam_search_generate,
        generate,
        generate_causal,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.gpt2 import (
        Gpt2Config,
        Gpt2LMHeadModel,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )

    on_tpu = _on_tpu()
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.RandomState(0)

    if on_tpu:
        batch, prompt_len, new_tokens = 16, 128, 128
        gpt2_cfg = Gpt2Config(dtype=dtype)                  # 124M
        bart_cfg = BartConfig(dtype=dtype)                  # base, 139M
        llama_cfg = LlamaConfig(                            # TinyLlama-1.1B
            vocab_size=32000, hidden_size=2048, num_layers=22,
            num_heads=32, num_kv_heads=4, intermediate_size=5632,
            max_position_embeddings=2048, dtype=dtype)
    else:
        batch, prompt_len, new_tokens = 4, 16, 16
        gpt2_cfg = Gpt2Config(vocab_size=512, hidden_size=64, num_layers=2,
                              num_heads=4, intermediate_size=128,
                              max_position_embeddings=256, dtype=dtype)
        llama_cfg = LlamaConfig(vocab_size=512, hidden_size=64,
                                num_layers=2, num_heads=4, num_kv_heads=2,
                                intermediate_size=128,
                                max_position_embeddings=256, dtype=dtype)
        bart_cfg = BartConfig(vocab_size=512, d_model=64, encoder_layers=2,
                              decoder_layers=2, encoder_attention_heads=4,
                              decoder_attention_heads=4, encoder_ffn_dim=128,
                              decoder_ffn_dim=128, max_position_embeddings=256,
                              dtype=dtype)

    results = {}

    gpt2 = Gpt2LMHeadModel(gpt2_cfg)
    gpt2_params = init_params(gpt2, gpt2_cfg, seed=0)
    prompt = jnp.asarray(
        rng.randint(0, gpt2_cfg.vocab_size, (batch, prompt_len)), jnp.int32)
    results["gpt2_greedy"] = _bench_one(
        lambda: generate_causal(gpt2, gpt2_params, prompt,
                                max_new_tokens=new_tokens),
        new_tokens, batch)

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.quant import (
        quantize_for_generation,
    )
    q_gpt2, q_params, _ = quantize_for_generation(gpt2, gpt2_params)
    results["gpt2_greedy_int8"] = _bench_one(
        lambda: generate_causal(q_gpt2, q_params, prompt,
                                max_new_tokens=new_tokens),
        new_tokens, batch)

    llama = LlamaForCausalLM(llama_cfg)
    llama_params = init_params(llama, llama_cfg, seed=0)
    l_prompt = jnp.asarray(
        rng.randint(3, llama_cfg.vocab_size, (batch, prompt_len)), jnp.int32)
    results["llama_greedy"] = _bench_one(
        lambda: generate_causal(llama, llama_params, l_prompt,
                                max_new_tokens=new_tokens),
        new_tokens, batch)
    q_llama, ql_params, _ = quantize_for_generation(llama, llama_params)
    results["llama_greedy_int8"] = _bench_one(
        lambda: generate_causal(q_llama, ql_params, l_prompt,
                                max_new_tokens=new_tokens),
        new_tokens, batch)

    # self-speculative decode measured DELIBERATELY at batch 1 (the
    # classic latency-bound single-stream case; the API itself batches,
    # rows advancing independently) against a batch-1 greedy baseline
    # so the comparison is apples-to-apples. Random weights give a
    # WORST-CASE acceptance floor — real checkpoints accept more,
    # never fewer, tokens/window.
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
        generate_speculative,
        self_draft,
    )

    draft_layers = max(1, llama_cfg.num_layers // 5)
    draft, d_params = self_draft(llama, llama_params, draft_layers)
    spec_prompt = l_prompt[:1]
    results["llama_greedy_b1"] = _bench_one(
        lambda: generate_causal(llama, llama_params, spec_prompt,
                                max_new_tokens=new_tokens),
        new_tokens, 1)
    results["llama_self_spec_b1"] = _bench_one(
        lambda: generate_speculative(llama, llama_params, draft, d_params,
                                     spec_prompt,
                                     max_new_tokens=new_tokens,
                                     speculate_k=4),
        new_tokens, 1)
    _, spec_stats = generate_speculative(
        llama, llama_params, draft, d_params, spec_prompt,
        max_new_tokens=new_tokens, speculate_k=4, return_stats=True)
    extra_detail = {
        "llama_greedy_b1": {"batch": 1},
        "llama_self_spec_b1": {
            "batch": 1,
            "accepted_per_window": spec_stats["accepted_per_window"],
            "window_ceiling": spec_stats["window_ceiling"],
            "draft_layers": draft_layers},
    }

    bart = BartForConditionalGeneration(bart_cfg)
    bart_params = init_params(bart, bart_cfg, seed=0)
    src = jnp.asarray(
        rng.randint(3, bart_cfg.vocab_size, (batch, prompt_len)), jnp.int32)
    results["bart_greedy"] = _bench_one(
        lambda: generate(bart, bart_params, src, max_new_tokens=new_tokens),
        new_tokens, batch)
    results["bart_beam4"] = _bench_one(
        lambda: beam_search_generate(bart, bart_params, src, num_beams=4,
                                     max_new_tokens=new_tokens),
        new_tokens, batch)

    n_chips = len(jax.devices())
    from huggingface_sagemaker_tensorflow_distributed_tpu import obs

    for mode, tok_s in results.items():
        # mirror every stdout line into the telemetry stream so bench
        # JSONL and events.jsonl carry the same series names
        obs.scalar(f"bench/generate_{mode}_tokens_per_sec_per_chip",
                   tok_s / n_chips)
        print(json.dumps({
            "metric": f"generate_{mode}_tokens_per_sec_per_chip",
            "value": round(tok_s / n_chips, 1),
            "unit": "tokens/sec/chip",
            "vs_baseline": 0.0,  # no reference decode number (BASELINE.md)
            "detail": {"batch": batch, "prompt_len": prompt_len,
                       "new_tokens": new_tokens,
                       "model_scale": "real" if on_tpu else "smoke",
                       **extra_detail.get(mode, {})},
        }))


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))  # repo root, for `from bench import ...`
    bench_generate()
