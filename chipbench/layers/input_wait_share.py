"""Seconds the train loop waited for input over the window. Source: the
program's ``data/consumer_wait_s`` metric events (one per prefetch
iterator, written when it closes)."""


def read(o):
    waits = [e["value"] for e in o.events
             if e.get("type") == "metric"
             and e.get("name") == "data/consumer_wait_s"
             and e.get("value") is not None]
    if not waits or o.window_s <= 0:
        return None
    return 100.0 * sum(waits) / o.window_s
