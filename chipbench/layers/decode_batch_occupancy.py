"""Mean decode slots in use over ``num_slots``, over the iterations that
decoded. Source: the program's ``iteration_ledger`` events."""


def read(o):
    slots = [e["decode_slots"] for e in o.events
             if e.get("type") == "serve"
             and e.get("event") == "iteration_ledger"
             and e.get("decode_slots")]
    if not slots:
        return None
    return 100.0 * (sum(slots) / len(slots)) / o.counters["num_slots"]
