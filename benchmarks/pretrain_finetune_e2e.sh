#!/bin/bash
# Pretrained → fine-tune end-to-end evidence.
#
# The reference's main path is from_pretrained → fine-tune
# (reference scripts/train.py:117). The hub is unreachable here, so the
# framework manufactures its own pretrained checkpoint: MLM-pretrain a
# small BERT on the vendored corpus text, export HF layout, reload via
# from_pretrained, fine-tune seq-cls — exercising the full
# convert/export/reload cycle UNDER TRAINING, not just logits parity.
#
# Runs (all on the virtual 8-device CPU mesh, dp8):
#   A  MLM pretrain 6 epochs from scratch          -> $WORK/mlm_model
#   B  seq-cls fine-tune 1 epoch FROM A            -> eval_results.txt
#   C  seq-cls from scratch 1 epoch (control)      -> eval_results.txt
#   D  LoRA r=8 fine-tune 1 epoch FROM A           -> eval_results.txt
#      (frozen backbone + adapters/head at 10x lr — the PEFT lr
#      convention; exercises the LoRA path end to end incl. the
#      adapter sidecar export)
# Expected: B beats C under the 1-epoch budget; D stays near chance ON
# THIS CORPUS — it is constructed to defeat frozen-feature probes (the
# label depends on clause ORDER, and a linear probe on the frozen
# backbone's CLS features measures only 0.553), so parameter-efficient
# tuning needs a backbone that already encodes the task, which a 1.8M
# -param 6-epoch MLM pretrain does not provide. See EVAL_REALDATA.md
# ("LoRA under a tiny pretraining budget").
set -euo pipefail

WORK=${WORK:-/tmp/pt_ft_e2e}
rm -rf "$WORK"; mkdir -p "$WORK"

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"

WORK="$WORK" python - <<'EOF'
import os
from transformers import BertConfig
BertConfig(vocab_size=8192, hidden_size=128, num_hidden_layers=4,
           num_attention_heads=4, intermediate_size=512,
           max_position_embeddings=128).save_pretrained(
    os.path.join(os.environ["WORK"], "smallbert"))
EOF

COMMON="--dataset vendored_reviews --train_batch_size 4 --dtype float32
  --max_seq_length 128 --scale_lr_by_world_size false"

echo "=== A: MLM pretrain (6 epochs, from scratch) ==="
python scripts/train.py $COMMON --task mlm --from_scratch true \
  --model_name_or_path "$WORK/smallbert" --epochs 6 --learning_rate 3e-4 \
  --output_data_dir "$WORK/mlm_out" --model_dir "$WORK/mlm_model" \
  --checkpoint_dir "$WORK/mlm_ckpt"

echo "=== B: fine-tune seq-cls 1 epoch FROM the MLM export ==="
python scripts/train.py $COMMON --task seq-cls \
  --model_name_or_path "$WORK/mlm_model" --epochs 1 --learning_rate 3e-4 \
  --output_data_dir "$WORK/ft_out" --model_dir "$WORK/ft_model" \
  --checkpoint_dir "$WORK/ft_ckpt"

echo "=== C: control — seq-cls 1 epoch from scratch ==="
python scripts/train.py $COMMON --task seq-cls --from_scratch true \
  --model_name_or_path "$WORK/smallbert" --epochs 1 --learning_rate 3e-4 \
  --output_data_dir "$WORK/scratch_out" --model_dir "$WORK/scratch_model" \
  --checkpoint_dir "$WORK/scratch_ckpt"

echo "=== D: LoRA r=8 fine-tune 1 epoch FROM the MLM export ==="
python scripts/train.py $COMMON --task seq-cls \
  --model_name_or_path "$WORK/mlm_model" --epochs 1 --learning_rate 3e-3 \
  --lora_rank 8 \
  --output_data_dir "$WORK/lora_out" --model_dir "$WORK/lora_model" \
  --checkpoint_dir "$WORK/lora_ckpt"

echo "=== results ==="
echo "--- B (pretrained, 1 epoch):"; cat "$WORK/ft_out/eval_results.txt"
echo "--- C (scratch, 1 epoch):"; cat "$WORK/scratch_out/eval_results.txt"
echo "--- D (pretrained + LoRA r=8, 1 epoch):"; cat "$WORK/lora_out/eval_results.txt"
