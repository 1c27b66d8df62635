"""Spreads of a cell's runs, as the bounds are set from them.

    python3 benchmarks/tools/spread.py chiprun_out/sets/<cell>.set1.jsonl \
        chiprun_out/sets/<cell>.set2.jsonl

Each file holds the result lines (the last line of standard output) of one
set of runs, in run order.  For every metric: each set's median and
spread (distance between the quartiles of ``statistics.quantiles(n=4)`` as
a share of the median), the wider spread, five times it, and how far the
second set's median lies from the first's.  The first run of the first file
is the one that may have compiled; its ``setup_s`` is listed apart.
"""

import json
import statistics
import sys

from os.path import abspath, dirname
sys.path.insert(0, dirname(dirname(dirname(abspath(__file__)))))

from benchmarks.lib.stats import quartile_spread  # noqa: E402


def load(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.startswith("{")]


def main(paths):
    sets = [load(p) for p in paths]
    names = sorted(sets[0][0]["metrics"])
    print(f"runs per set {[len(s) for s in sets]} correct "
          f"{[all(r['correct'] for r in s) for s in sets]} peak_gb "
          f"{max(r['device']['memory_peak_bytes'] for s in sets for r in s) / 1e9:.3f}")
    for name in names:
        vals = [[r["metrics"][name]["value"] for r in s] for s in sets]
        if name == "setup_s":
            print(f"setup_s first run {vals[0][0]:.3f}")
            vals[0] = vals[0][1:]
        meds = [statistics.median(v) for v in vals]
        spreads = [quartile_spread(v) for v in vals]
        shift = (meds[-1] - meds[0]) / meds[0] if len(meds) > 1 else 0.0
        print(f"{name}: medians {[round(m, 4) for m in meds]} spreads "
              f"{[round(s, 5) for s in spreads]} widest {max(spreads):.5f} "
              f"x5 {5 * max(spreads):.4f} second-vs-first {shift:+.5f}")
        print("   values", [[round(x, 3) for x in v] for v in vals])


if __name__ == "__main__":
    main(sys.argv[1:])
