"""Seconds inside ``ServeEngine.warmup()``: the ``serve/warmup`` span,
recorded before telemetry had a directory and replayed when it got one
(a life-cycle span, ``obs.lifecycle_span``). Its children name the
programs it warmed (``serve/warmup/prefill_g4``, ...). None from a
program that records no such span."""


def read(o):
    durs = [e["dur"] for e in o.events
            if e.get("type") == "span" and e.get("name") == "serve/warmup"]
    return sum(durs) if durs else None
