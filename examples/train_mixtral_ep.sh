#!/bin/bash
# Mixtral (sparse MoE in the Llama family) causal-LM training with
# expert parallelism: experts shard over the `expert` mesh axis, token
# dispatch rides XLA all-to-alls, checkpoint exports in HF's native
# block_sparse_moe layout (loadable by transformers).
set -eu
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"
OUT=${OUT:-/tmp/ex_mixtral}
rm -rf "$OUT"
python - << 'PY'
from transformers import MixtralConfig
MixtralConfig(vocab_size=256, hidden_size=32, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2,
              intermediate_size=64, max_position_embeddings=64,
              num_local_experts=4, num_experts_per_tok=2,
              sliding_window=None).save_pretrained("/tmp/ex_mixtral_cfg")
PY
python scripts/train.py \
  --dataset synthetic --task causal-lm --from_scratch true \
  --model_name_or_path /tmp/ex_mixtral_cfg \
  --epochs 1 --train_batch_size 8 --dtype float32 \
  --max_seq_length 32 --max_train_samples 64 --max_eval_samples 32 \
  --learning_rate 1e-3 --scale_lr_by_world_size false \
  --num_experts 4 --ep 2 --tp 2 \
  --output_data_dir "$OUT/out" --model_dir "$OUT/model" \
  --checkpoint_dir "$OUT/ckpt"
python - << 'PY'
import json
c = json.load(open("/tmp/ex_mixtral/model/config.json"))
print("exported model_type:", c["model_type"],
      "num_local_experts:", c["num_local_experts"])
PY
