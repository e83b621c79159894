#!/bin/bash
# LoRA fine-tuning of a Llama checkpoint: the frozen base carries no
# Adam state or gradient tree (adapters + task head only), then serve
# directly from the adapter sidecar — no merged export needed.
set -eu
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"
OUT=${OUT:-/tmp/ex_lora}
rm -rf "$OUT"
python - << 'PY'
from transformers import LlamaConfig
LlamaConfig(vocab_size=256, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=64,
            max_position_embeddings=64).save_pretrained("/tmp/ex_llama_cfg")
PY
python scripts/train.py \
  --dataset synthetic --task causal-lm --from_scratch true \
  --model_name_or_path /tmp/ex_llama_cfg \
  --epochs 1 --train_batch_size 8 --dtype float32 \
  --max_seq_length 32 --max_train_samples 64 --max_eval_samples 32 \
  --learning_rate 1e-3 --scale_lr_by_world_size false \
  --lora_rank 4 --lora_targets attention \
  --output_data_dir "$OUT/out" --model_dir "$OUT/model" \
  --checkpoint_dir "$OUT/ckpt"
echo "--- adapter sidecar next to the merged export:"
ls "$OUT/model"
echo "--- serve from base + adapter (no merged weights needed):"
python scripts/predict.py --model_dir "$OUT/model" --task causal-lm \
  --adapter "$OUT/model/adapter" --text "hello world" --max_new_tokens 6
