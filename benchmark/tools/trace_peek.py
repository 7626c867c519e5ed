"""Print the structure of a recorded trace: planes, lines, and the most
frequent event names of each line. A tool for whoever writes a reader
against a new kind of trace; the benchmark does not run it.

    python3 benchmark/tools/trace_peek.py <trace dir or .xplane.pb>
"""
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(path):
    from jax.profiler import ProfileData
    from benchmark import trace_reduce
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            span = (min(e.start_ns for e in events),
                    max(e.start_ns + e.duration_ns for e in events)) \
                if events else (0, 0)
            print(f"  LINE {line.name!r}: {len(events)} events over "
                  f"{(span[1] - span[0]) * 1e-9:.3f} s; "
                  f"{names.most_common(12)}")


if __name__ == "__main__":
    main(sys.argv[1])
