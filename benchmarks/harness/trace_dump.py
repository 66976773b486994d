"""Print what a trace holds, for reading by hand before a matcher is
written: planes, lines, how many events, the names that took most time
and the stats a few events carry.

    python benchmarks/harness/trace_dump.py <file.xplane.pb> [top]
"""

import sys


def main(path: str, top: int = 40):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            total = {}
            for e in events:
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns
            lo = min(e.start_ns for e in events)
            hi = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{len(total)} names, span {(hi - lo) / 1e9:.4f}s")
            for name, ns in sorted(total.items(), key=lambda kv: -kv[1])[:top]:
                sample = next(e for e in events if e.name == name)
                n = sum(1 for e in events if e.name == name)
                stats = {k: (str(v)[:100]) for k, v in sample.stats}
                print(f"    {ns / 1e9:10.6f}s x{n:<6d} {name[:80]!r} {stats}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 40)
