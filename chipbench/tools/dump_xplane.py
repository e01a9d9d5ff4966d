"""Print what a recorded ``.xplane.pb`` holds: planes, lines, event
counts and a few names each — the look by hand that comes before code
against a trace.  ``python3 chipbench/tools/dump_xplane.py <file> [n]``"""

import sys

from jax.profiler import ProfileData


def main(path, n=6):
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:n]:
                print(f"    {ev.name[:100]!r} start_ns={ev.start_ns:.0f} "
                      f"dur_ns={ev.duration_ns:.0f}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 6)
