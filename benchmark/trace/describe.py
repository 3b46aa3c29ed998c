"""Look at a trace by hand before trusting a reduction of it:
``python -m benchmark.trace.describe FILE.xplane.pb`` prints every plane and
line with its event count and, per line, the names that took most time
(with the keys of their first event's stats)."""

from __future__ import annotations

import sys
from collections import defaultdict


def describe(path: str, top: int = 12) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} line(s)")
        for line in lines:
            total: dict[str, float] = defaultdict(float)
            count: dict[str, int] = defaultdict(int)
            stats: dict[str, dict] = {}
            n = 0
            for event in line.events:
                n += 1
                total[event.name] += event.duration_ns
                count[event.name] += 1
                if event.name not in stats:
                    stats[event.name] = {k: str(v)[:80] for k, v in event.stats}
            print(f"  LINE {line.name!r}: {n} event(s), {len(total)} name(s)")
            for name in sorted(total, key=total.get, reverse=True)[:top]:
                print(
                    f"    {total[name] / 1e6:10.3f} ms  x{count[name]:<6} {name[:90]}"
                    f"  stats={stats[name]}"
                )


if __name__ == "__main__":
    describe(sys.argv[1], *(int(a) for a in sys.argv[2:]))
