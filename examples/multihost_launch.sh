#!/bin/bash
# The estimator-style launcher: a 2-host job as two REAL processes with
# a JAX distributed coordinator (the local stand-in for one process per
# TPU host), artifact collection under the job dir, rank-death safety.
# On a real slice the TPUVMBackend builds the equivalent
# `gcloud compute tpus tpu-vm ssh --worker=all` command.
set -eu
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
python - << 'PY'
from transformers import BertConfig
BertConfig(vocab_size=256, hidden_size=32, num_hidden_layers=2,
           num_attention_heads=4, intermediate_size=64,
           max_position_embeddings=64).save_pretrained("/tmp/ex_mh_cfg")

from huggingface_sagemaker_tensorflow_distributed_tpu.launch import TPUJob
job = TPUJob(
    entry_point="scripts/train.py", source_dir=".",
    slice_spec="cpu-4", num_hosts=2,
    hyperparameters={
        "dataset": "synthetic", "from_scratch": "true",
        "model_name_or_path": "/tmp/ex_mh_cfg",
        "epochs": 1, "train_batch_size": 4, "dtype": "float32",
        "max_seq_length": 32, "max_train_samples": 32,
        "max_eval_samples": 16, "learning_rate": "1e-3",
        "scale_lr_by_world_size": "false",
    },
    job_root="/tmp/ex_mh_jobs")
handle = job.fit(wait=True)
print("job dir:", handle.job_dir)
import os
print("artifacts:", sorted(os.listdir(handle.output_data_dir)))
PY
