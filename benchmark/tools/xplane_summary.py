"""Print what a ``.xplane.pb`` holds, for reading a trace by hand: every
plane, every line with its event count and busiest event names.

    python3 benchmark/tools/xplane_summary.py <file.xplane.pb> [top]
"""
import collections
import sys


def main(path, top=12):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            total = collections.Counter()
            count = collections.Counter()
            for e in events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
            lo = min(e.start_ns for e in events)
            hi = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events over "
                  f"{(hi - lo) / 1e6:.1f} ms")
            for name, ns in total.most_common(top):
                print(f"      {ns / 1e6:10.3f} ms  x{count[name]:<6} "
                      f"{name[:110]}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12)
