"""``serve``: continuous-batching inference engine (ISSUE 3 + the
ISSUE 5 decode fast path: width-bucketed KV gather, batched prefill,
per-slot seeded sampling).

- :mod:`~.paged_kv` — block-pool KV allocation + gather read-waste
  accounting (host-side policy).
- :mod:`~.scheduler` — iteration-level admission/preemption over fixed
  decode slots, per-iteration max-context + tokens-per-dispatch
  prefill budget.
- :mod:`~.engine` — the jitted prefill/decode step functions (compiled
  per gather bucket) and the driving loop (``scripts/serve.py`` is the
  CLI; ``chipbench/`` the measurement).
- :mod:`~.router` — N engine replicas behind one facade (ISSUE 14):
  round-robin / least-loaded / prefix-affinity / length-aware
  placement, replica drain/restart with requeue-to-siblings and live
  resident migration, disaggregated prefill/decode roles (ISSUE 18).
- :mod:`~.transport` — cross-engine KV block-set migration (ISSUE 18):
  one primitive moves a live request between engines with zero
  re-prefill, token-exactly.
- :mod:`~.policy` — goodput-aware admission control (ISSUE 20):
  pluggable scheduler ordering (fifo | slo), per-tenant token-bucket
  rate limits, structured rejections. Host-side by contract
  (graftlint R7).
"""

from huggingface_sagemaker_tensorflow_distributed_tpu.serve.paged_kv import (  # noqa: F401
    BlockManager,
    PoolExhausted,
    prefix_chain_keys,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.policy import (  # noqa: F401
    POLICIES,
    RateLimited,
    SloPolicy,
    TokenBucket,
    parse_aging_s,
    parse_policy,
    parse_rate_limit,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.scheduler import (  # noqa: F401
    Request,
    Scheduler,
)


def __getattr__(name):
    # ServeEngine pulls in jax; keep `import ...serve` cheap for
    # host-only consumers (scheduler/block-manager tests)
    if name in ("ServeEngine", "EngineStats", "CachePlan",
                "build_cache_plan", "parse_gather_buckets",
                "parse_prefix_cache", "parse_tp"):
        from huggingface_sagemaker_tensorflow_distributed_tpu.serve import (
            engine,
        )
        return getattr(engine, name)
    if name in ("Router", "parse_replicas", "parse_placement",
                "parse_roles"):
        from huggingface_sagemaker_tensorflow_distributed_tpu.serve import (
            router,
        )
        return getattr(router, name)
    if name in ("TransportError", "migrate_request", "can_accept",
                "pool_signature"):
        from huggingface_sagemaker_tensorflow_distributed_tpu.serve import (
            transport,
        )
        return getattr(transport, name)
    raise AttributeError(name)
