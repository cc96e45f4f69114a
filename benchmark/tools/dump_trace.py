"""Print what an .xplane.pb holds: planes, lines, event counts, a few events
with their stats. For the look by hand that comes before any reduction.

    python3 benchmark/tools/dump_trace.py <file.xplane.pb | trace dir> [events per line]
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import trace_reduce  # noqa: E402


def main():
    import jax.profiler

    path = sys.argv[1]
    if os.path.isdir(path):
        path = trace_reduce.newest_xplane(path)
    show = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    print(path, os.path.getsize(path), "bytes")
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:show]:
                print(f"    {e.name[:80]!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} stats={list(e.stats)[:6]}")


if __name__ == "__main__":
    main()
