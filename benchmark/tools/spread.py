"""Spreads of a cell's two sets of runs, as the builder's contract reads
them: for each metric the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) over the median, a set; the wider of
the two; how far the second set's median lies from the first's; and the
driver's rule for tightness (``tight``): in a set, the run farthest from the
set's median left out, the largest less the smallest of the rest over the
median, which has to stay under half the metric's bound.

    python benchmark/tools/spread.py SET1.jsonl SET2.jsonl

Each file holds the result lines of one set (one JSON object a line).
"""

import json
import statistics
import sys


def load(path):
    runs = [json.loads(l) for l in open(path) if l.startswith("{")]
    out = {}
    for r in runs:
        for name, m in r["metrics"].items():
            out.setdefault(name, []).append(m["value"])
    return out, runs


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def tight(values):
    med = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - med))[:-1]
    return (max(rest) - min(rest)) / med


def main(a, b):
    sa, ra = load(a)
    sb, rb = load(b)
    print(f"runs {len(ra)}+{len(rb)}; correct "
          f"{sum(r['correct'] for r in ra + rb)} of {len(ra) + len(rb)}; "
          f"memory_peak_bytes {sorted({r['device']['memory_peak_bytes'] for r in ra + rb})}")
    for name in sa:
        va, vb = sa[name], sb[name]
        if name == "setup_s":      # each side's first run compiles
            va, vb = va[1:], vb[1:]
        ma, mb = statistics.median(va), statistics.median(vb)
        print(f"{name:16s} median {ma:.6g} / {mb:.6g}  second-vs-first "
              f"{(mb - ma) / ma:+.4%}  spread {spread(va):.4%} / "
              f"{spread(vb):.4%}  tight {tight(va):.4%} / {tight(vb):.4%}"
              f"  -> five times the wider "
              f"{5 * max(spread(va), spread(vb)):.4%}  values {[round(v, 4) for v in sa[name]]} {[round(v, 4) for v in sb[name]]}")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
