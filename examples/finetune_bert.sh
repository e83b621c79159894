#!/bin/bash
# The reference workload end to end: BERT-family seq-cls fine-tune →
# eval → HF-layout export + `key = value` results files.
set -eu
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"
OUT=${OUT:-/tmp/ex_bert}
rm -rf "$OUT"
python - << 'PY'
from transformers import BertConfig
BertConfig(vocab_size=256, hidden_size=64, num_hidden_layers=2,
           num_attention_heads=4, intermediate_size=128,
           max_position_embeddings=128).save_pretrained("/tmp/ex_bert_cfg")
PY
python scripts/train.py \
  --dataset synthetic --from_scratch true \
  --model_name_or_path /tmp/ex_bert_cfg \
  --epochs 2 --train_batch_size 8 --dtype float32 \
  --max_seq_length 64 --max_train_samples 256 --max_eval_samples 64 \
  --learning_rate 1e-3 --scale_lr_by_world_size false \
  --output_data_dir "$OUT/out" --model_dir "$OUT/model" \
  --checkpoint_dir "$OUT/ckpt"
echo "--- results files (the reference's contract):"
cat "$OUT/out/train_results.txt" "$OUT/out/eval_results.txt"
echo "--- exported checkpoint:"
ls "$OUT/model"
