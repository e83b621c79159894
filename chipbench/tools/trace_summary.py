"""Look at one trace by hand: planes, lines, and the names that took
most time on each line.

    python3 -m chipbench.tools.trace_summary <trace dir or .xplane.pb> [n]
"""

from __future__ import annotations

import os
import sys


def summarize(path: str, n: int = 25) -> str:
    from jax.profiler import ProfileData

    from chipbench import reduce

    if os.path.isdir(path):
        path = reduce.find_xplane(path) or path
    data = ProfileData.from_file(path)
    out = [f"{path} ({os.path.getsize(path)} bytes)"]
    for plane in data.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            total: dict = {}
            count = 0
            for ev in line.events:
                count += 1
                key = (reduce.parse_op(ev.name)[1]
                       if line.name == reduce.OPS_LINE else ev.name)
                total[key] = total.get(key, 0.0) + ev.duration_ns * 1e-9
            if not count:
                continue
            out.append(f"  LINE {line.name!r}: {count} events")
            for name, secs in sorted(total.items(), key=lambda kv: -kv[1])[:n]:
                out.append(f"    {secs:12.6f} s  {name[:140]}")
    return "\n".join(out)


if __name__ == "__main__":
    print(summarize(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25))
