"""Look at a trace by hand: planes, lines, the commonest events and a few
events with all their stats. ``python benchmark/tools/dump_trace.py DIR``
where DIR holds an ``.xplane.pb`` (and ``step.hlo.txt``), as a traced run
leaves under ``.bench_out/trace/<cell>/``."""

import collections
import os
import sys

import jax


def main(out_dir):
    paths = [os.path.join(d, n) for d, _, ns in os.walk(out_dir)
             for n in ns if n.endswith(".xplane.pb")]
    prof = jax.profiler.ProfileData.from_file(paths[0])
    for plane in prof.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            names = collections.Counter(e.name for e in evs)
            dur = collections.Counter()
            for e in evs:
                dur[e.name] += e.duration_ns
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{len(names)} names; first start {evs[0].start_ns}")
            if plane.name.startswith("/device") or "bench/" in "".join(names):
                for n, d in dur.most_common(25):
                    print(f"      {d / 1e6:10.3f} ms  x{names[n]:<5} {n[:100]}")
                for e in evs[:3] + evs[len(evs) // 2:len(evs) // 2 + 3]:
                    st = {k: (str(v)[:160]) for k, v in e.stats}
                    print(f"      EVENT {e.name[:80]!r} start {e.start_ns} "
                          f"dur {e.duration_ns} stats {st}")
    hlo = os.path.join(out_dir, "step.hlo.txt")
    if os.path.exists(hlo):
        for line in open(hlo):
            if "custom_call_target" in line or "custom-call" in line[:60]:
                print("HLO", line[:400].rstrip(), "...", line[-300:].rstrip())


if __name__ == "__main__":
    main(sys.argv[1])
