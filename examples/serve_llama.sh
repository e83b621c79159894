#!/bin/bash
# The serving tier on one exported checkpoint: plain greedy, layer-skip
# self-speculation, int8 KV cache, chunked prefill — the generated
# tokens are IDENTICAL across all four (speculation/quantized-cache/
# chunking change speed and memory, never tokens).
set -eu
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"
OUT=${OUT:-/tmp/ex_serve}
rm -rf "$OUT"
python - << 'PY'
from transformers import LlamaConfig
LlamaConfig(vocab_size=256, hidden_size=32, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=64,
            max_position_embeddings=64).save_pretrained("/tmp/ex_serve_cfg")
PY
python scripts/train.py \
  --dataset synthetic --task causal-lm --from_scratch true \
  --model_name_or_path /tmp/ex_serve_cfg \
  --epochs 1 --train_batch_size 8 --dtype float32 \
  --max_seq_length 32 --max_train_samples 64 --max_eval_samples 32 \
  --learning_rate 1e-3 --scale_lr_by_world_size false \
  --output_data_dir "$OUT/out" --model_dir "$OUT/model" \
  --checkpoint_dir "$OUT/ckpt"
P="python scripts/predict.py --model_dir $OUT/model --task causal-lm \
   --text 'once upon a time' --max_new_tokens 8"
echo "--- greedy:";            eval "$P"
echo "--- self-speculative:";  eval "$P --self_speculate_layers 1"
echo "--- int8 KV cache:";     eval "$P --kv_cache int8"
echo "--- chunked prefill:";   eval "$P --prefill_chunk 4"
# beam search picks the best-scoring hypothesis, so its tokens may
# legitimately differ from greedy; sampled speculation is distribution
# -exact (seeded, so reproducible) rather than token-exact
echo "--- beam search (4 beams, HF-exact scorer):"
eval "$P --num_beams 4"
echo "--- sampled speculation (temperature 0.8, rejection-exact):"
eval "$P --self_speculate_layers 1 --temperature 0.8"
