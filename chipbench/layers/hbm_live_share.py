"""What a serving cell really holds in device memory, over the device's
limit: the weights plus the KV blocks that held data at the fullest
moment of the run (blocks of running requests and blocks parked in the
prefix cache). The pool is reserved whole at start-up, so the runtime's
peak (``device.memory_peak_bytes``) reads weights + reservation whatever
the traffic; this reads what the traffic put there. Source: the
program's block manager (counts), sampled after every step."""


def read(o):
    c = o.counters
    if not o.memory_limit_bytes or c.get("kv_held_blocks_peak") is None:
        return None
    held = c["param_bytes"] + c["kv_held_blocks_peak"] * c["block_bytes"]
    return 100.0 * held / o.memory_limit_bytes
