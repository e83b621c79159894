"""The longest pause of the host inside an engine iteration: the longest
``host_pause`` event (a wake-up of the program's 10 ms ticker thread
that came more than 20 ms late, ``obs/watchdog.py``) that overlaps a
``serve/step`` span; 0 when none does. The benchmark's own work between
laps (building an engine, collecting garbage) holds the interpreter
too, which is why only pauses inside an iteration count. None from a
program without the meter or the span."""


def longest_pause_ms(events: list, span_name: str, hull: bool = False):
    """Milliseconds of the longest ``host_pause`` that overlaps a span
    named ``span_name`` (with ``hull``: that lies between the first
    such span's start and the last one's end); 0.0 when none does, None
    where the meter did not run or no such span was recorded."""
    ran = any(e.get("type") == "metric"
              and e.get("name") == "host/pause_max_s" for e in events)
    spans = [(e["mono"], e["mono"] + e["dur"]) for e in events
             if e.get("type") == "span" and e.get("name") == span_name]
    if not ran or not spans:
        return None
    if hull:
        spans = [(min(s for s, _ in spans), max(e for _, e in spans))]
    inside = [p["dur"] for p in events if p.get("type") == "host_pause"
              and any(p["mono"] < e and p["mono"] + p["dur"] > s
                      for s, e in spans)]
    return 1e3 * max(inside, default=0.0)


def read(o):
    return longest_pause_ms(o.events, "serve/step")
