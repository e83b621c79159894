"""The longest the host was blocked on the device inside ONE engine
iteration of the window (``fetch_wait_s``): a prefill dispatch and the
decode step behind it in a quiet window; a stall of the device or the
runtime shows here and not in ``host_pause_max_ms``. Source: as
``engine_host_ms_per_step``."""

from chipbench.layers.engine_host_ms_per_step import ledger_lines


def read(o):
    waits = [e["fetch_wait_s"] for e in ledger_lines(o)]
    return 1e3 * max(waits) if waits else None
