"""Operations and bytes computed from shapes, and the table of peaks.

Copies of the program's sound arithmetic (``obs/flops.py`` FLOP
convention, the ``kv_bytes_read_per_step`` arithmetic of
``serve/engine.py``), kept here so that no later PR can move the
yardstick. Matmul FLOPs only; training = 3 x forward; recomputation
does not count.
"""

from __future__ import annotations

import functools
import os

from chipbench.spec import HERE, load_json

TRAIN_FACTOR = 3.0


@functools.lru_cache(maxsize=1)
def _peaks_table() -> dict:
    return load_json(os.path.join(HERE, "peaks.json"))["devices"]


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip, by exact ``device_kind``. A device
    that is not in the table is an error, not a default."""
    table = _peaks_table()
    if device_kind not in table:
        raise LookupError(
            f"device_kind {device_kind!r} is not in chipbench/peaks.json "
            f"(have {sorted(table)}): add it with its source")
    return table[device_kind]


def layer_fwd_flops_per_token(hidden: int, intermediate: int, kv_len: int,
                              kv_ratio: float = 1.0,
                              gated: bool = False) -> float:
    """One dense transformer layer, per token at context ``kv_len``:
    q/k/v/o projections (k, v scaled by the GQA ratio), QK^T + PV, and
    the MLP (2 matmuls, 3 when gated)."""
    qkvo = 2 * hidden * hidden * (2 + 2 * kv_ratio)
    attn = 4 * kv_len * hidden
    mlp = (6 if gated else 4) * hidden * intermediate
    return qkvo + attn + mlp


def encoder_train_flops_per_sample(hidden: int, intermediate: int,
                                   layers: int, seq_len: int) -> float:
    """Training FLOPs of one ``seq_len``-token sample through an
    encoder with a classification head (the head is negligible)."""
    return TRAIN_FACTOR * seq_len * layers * layer_fwd_flops_per_token(
        hidden, intermediate, seq_len)


def flash_flops_bytes(batch: int, heads: int, seq: int, head_dim: int,
                      itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes the three flash kernels need for one
    non-causal attention forward + backward over ``[B, H, S, D]``.
    Forward: QK^T, PV (2 matmuls). ``bwd_dq``: S, dP, dQ (3).
    ``bwd_dkv``: S, dP, dV, dK (4). The score recomputation inside the
    backward kernels is part of the algorithm, so it counts. Bytes are
    each operand read once and each result written once."""
    unit = 2.0 * batch * heads * seq * seq * head_dim   # one matmul
    tensor = batch * heads * seq * head_dim * itemsize  # one [B,H,S,D]
    return {
        "flash_fwd": {"flops": 2 * unit, "bytes": 4 * tensor},
        "flash_bwd_dq": {"flops": 3 * unit, "bytes": 6 * tensor},
        "flash_bwd_dkv": {"flops": 4 * unit, "bytes": 6 * tensor},
    }


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> dict:
    """The least time the chip could take and which peak bounds it."""
    t_flops = flops / (peak["bf16_tflops"] * 1e12)
    t_bytes = nbytes / (peak["hbm_gbytes_per_s"] * 1e9)
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


def kv_bytes_read_per_step(num_slots: int, bucket: int,
                           token_bytes: int) -> int:
    """Pool bytes one decode dispatch reads: every slot gathers the
    whole ``bucket`` width whatever its context (``serve/engine.py``:
    ``num_slots * bucket * token_bytes``)."""
    return int(num_slots) * int(bucket) * int(token_bytes)


def decode_step_bytes(param_bytes: int, num_slots: int, bucket: int,
                      token_bytes: int) -> int:
    """Bytes one decode step NEEDS: the weights once and the KV of the
    bucket width for every slot, read once."""
    return int(param_bytes) + kv_bytes_read_per_step(num_slots, bucket,
                                                     token_bytes)
