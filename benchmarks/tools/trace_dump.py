"""Dump the structure of a JAX profiler trace: planes, lines, event names.

Look at a trace by hand before writing a reader against it:

    python3 benchmarks/tools/trace_dump.py <dir-or-xplane.pb> [--events N]

Prints every plane with its lines and event counts, then, per line of a
device plane, the first N events with all their stats.  Needs JAX only.
"""

from __future__ import annotations

import argparse
import glob
import os
import sys


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        sys.exit(f"no .xplane.pb under {path}")
    return found[-1]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--events", type=int, default=12)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(args.path))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            if not plane.name.startswith("/device:"):
                names = {}
                for e in events:
                    names[e.name] = names.get(e.name, 0.0) + e.duration_ns
                top = sorted(names.items(), key=lambda kv: -kv[1])[:args.events]
                for name, ns in top:
                    print(f"      {ns / 1e6:12.3f} ms  {name}")
                continue
            for e in events[:args.events]:
                stats = {k: v for k, v in e.stats}
                print(f"      {e.start_ns:14.0f} +{e.duration_ns:10.0f} ns "
                      f"{e.name!r} {stats}")


if __name__ == "__main__":
    main()
