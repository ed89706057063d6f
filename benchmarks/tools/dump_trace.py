#!/usr/bin/env python3
"""Look at a profiler trace by hand: planes, lines, sample events with
their stats, and the heaviest names of each device line.

    python3 benchmarks/tools/dump_trace.py <log_dir or .xplane.pb> [top]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from jax.profiler import ProfileData
    from harness.trace import find_xplane
    path = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 25
    if os.path.isdir(path):
        path = find_xplane(path)
    print("file", path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            total, by, sample = 0.0, {}, {}
            n = 0
            for ev in line.events:
                n += 1
                total += ev.duration_ns
                by[ev.name] = by.get(ev.name, 0.0) + ev.duration_ns
                if ev.name not in sample:
                    sample[ev.name] = (ev.start_ns, ev.duration_ns,
                                       dict(ev.stats))
            print(f"  LINE {line.name!r}: {n} events, {total / 1e9:.6f} s, "
                  f"{len(by)} names")
            if plane.name.startswith("/device") or n < 50:
                for name, ns in sorted(by.items(),
                                       key=lambda kv: -kv[1])[:top]:
                    s0, d0, stats = sample[name]
                    stats = {k: (v if len(str(v)) < 300 else str(v)[:300])
                             for k, v in stats.items()}
                    print(f"    {ns / 1e9:10.6f} s  {name[:120]}  "
                          f"first@{s0:.0f}+{d0:.0f}ns  {stats}")


if __name__ == "__main__":
    main()
