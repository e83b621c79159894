"""Record the small trace the reduction's tests read
(``tests/chipbench/data``): a few steps of a matmul and a flash-attention
forward + backward on one chip, under the benchmark's own host spans.
Run on the chip; the CPU gives no device plane.

    python3 -m chipbench.tools.record_sample_trace <out dir>
"""

from __future__ import annotations

import sys


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import device
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_attention import (
        flash_attention,
    )

    x = jnp.ones((256, 512), jnp.bfloat16)
    q = jnp.ones((2, 4, 512, 64), jnp.bfloat16)

    @jax.jit
    def sample_step(x, q):
        y = x @ jnp.ones((512, 512), x.dtype)
        g = jax.grad(lambda q: flash_attention(q, q, q).astype(
            jnp.float32).sum())(q)
        return y.astype(jnp.float32).sum() + g.astype(jnp.float32).sum()

    jax.block_until_ready(sample_step(x, q))
    jax.profiler.start_trace(out_dir)
    for _ in range(3):
        with device.annotate("sample_step"):
            jax.block_until_ready(sample_step(x, q))
        with device.annotate("sample_pause"):
            import time
            time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
