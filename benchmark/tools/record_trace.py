"""Cut a few steps out of a traced run's ``.xplane.pb`` into a small JSON
that the tests replay: the device's lines of operations and programs, the
harness's ``bench/`` host spans, and the op_name of every instruction seen
(from ``step.hlo.txt``). Event names are cut to 200 characters.

    python benchmark/tools/record_trace.py TRACE_DIR OUT.json.gz FIRST LAST
"""

import gzip
import json
import os
import sys

import jax

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from benchlib import xtrace  # noqa: E402


def main(trace_dir, out, first, last):
    pb = [os.path.join(d, n) for d, _, ns in os.walk(trace_dir)
          for n in ns if n.endswith(".xplane.pb")][0]
    prof = jax.profiler.ProfileData.from_file(pb)
    hlo = xtrace.hlo_paths(open(os.path.join(trace_dir, "step.hlo.txt")).read())
    full = xtrace.read(prof, hlo, 1)
    runs = full.chips[0].step_runs(full.window)
    lo, hi = runs[first][0] - 1e-6, runs[last][1] + 1e-6
    planes, seen = [], set()
    for plane in prof.planes:
        lines = []
        for line in plane.lines:
            keep_line = (plane.name.startswith("/device:TPU:")
                         and line.name in ("XLA Ops", "XLA Modules"))
            evs = []
            for ev in line.events:
                s = ev.start_ns * 1e-9
                if not lo <= s < hi:
                    continue
                if keep_line or ev.name.startswith("bench/"):
                    evs.append([ev.name[:200], ev.start_ns, ev.duration_ns])
                    m = xtrace._HLO_LINE.match(ev.name)
                    if m:
                        seen.add(m.group(1))
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    # the window of the excerpt, as the harness would have annotated it
    planes.append({"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench/window", lo * 1e9, (hi - lo) * 1e9]]}]})
    rec = {"planes": planes, "steps": last - first + 1,
           "hlo": {k: [hlo[k][0], hlo[k][1][:900]] for k in seen if k in hlo}}
    with gzip.open(out, "wt") as f:
        json.dump(rec, f)
    print(out, os.path.getsize(out), "bytes;", len(seen), "instructions")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
