"""Prompt tokens served out of the prefix cache over prompt tokens
admitted. Source: the program's per-request counters (a count)."""


def read(o):
    total = o.counters.get("prompt_tokens")
    if not total:
        return None
    return 100.0 * o.counters["prefix_cached_tokens"] / total
