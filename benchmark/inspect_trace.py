"""Look at a trace by hand before writing a reader against it:
``python benchmark/inspect_trace.py <trace_dir or .xplane.pb> [out.txt]``
lists every plane and line with its number of events, and for the lines
that hold most events the names that took most time, with one event's
statistics."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def describe(path: str, top: int = 40) -> str:
    from jax.profiler import ProfileData

    from benchmark.trace_reduce import find_xplane

    if os.path.isdir(path):
        path = find_xplane(path)
    out = [path]
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            by_name: dict[str, list] = {}
            for e in events:
                if e.name.startswith("$"):
                    continue    # python frames
                slot = by_name.setdefault(e.name, [0.0, 0, e])
                slot[0] += e.duration_ns * 1e-9
                slot[1] += 1
            ranked = sorted(by_name.items(), key=lambda kv: -kv[1][0])
            for name, (seconds, count, sample) in ranked[:top]:
                stats = {k: v for k, v in sample.stats}
                out.append(f"    {seconds * 1e3:10.3f} ms {count:6d} x "
                           f"{name}  {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    text = describe(sys.argv[1])
    if len(sys.argv) > 2:
        os.makedirs(os.path.dirname(os.path.abspath(sys.argv[2])),
                    exist_ok=True)
        with open(sys.argv[2], "w") as f:
            f.write(text)
    else:
        print(text)
