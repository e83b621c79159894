"""Host milliseconds of one engine iteration that decoded, the wait for
the device taken out: the median over such ``iteration_ledger`` lines of
``dur_s - fetch_wait_s``. A decode step shorter than this and the host
sets the pace. Source: the engine's own account of each iteration's
wall time (``stage_s`` / ``dispatch_s`` / ``fetch_wait_s`` /
``commit_s``, ISSUE 25); None from a program whose ledger lacks it."""

from chipbench import stats


def ledger_lines(o, decoded: bool = False) -> list:
    """The window's ``iteration_ledger`` lines that carry the
    iteration's account of its wall time; with ``decoded``, those of
    iterations that committed a decode step."""
    return [e for e in o.events
            if e.get("type") == "serve"
            and e.get("event") == "iteration_ledger"
            and e.get("fetch_wait_s") is not None
            and (e.get("decode_slots") or not decoded)]


def read(o):
    host = [e["dur_s"] - e["fetch_wait_s"] for e in ledger_lines(o, True)]
    return 1e3 * stats.median(host) if host else None
