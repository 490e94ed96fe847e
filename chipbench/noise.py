"""The noise study: what windows of other lengths would have read from
runs recorded at the longest one, and how far the runs then spread.

    python -m chipbench.noise <dump directory> [10 20 30 40 51]

reads the files `python -m chipbench.run --dump <dir>` wrote (per-chunk
completion times of a training cell, per-request latencies of a serving
cell) and prints, for each window, each run's reading and the spread
(Q3 - Q1) / median of the runs as `statistics.quantiles(n=4)` gives it:
the spread the driver reads. `run_seconds` was chosen from this table
(PERF.md).
"""

import glob
import json
import os
import sys

from chipbench import timeline


def study(directory, windows):
    out = {}
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        with open(path) as f:
            d = json.load(f)
        cell = out.setdefault(d["workload"], {})
        if "done_s" in d:
            done = d["done_s"]
            for w in windows:
                r = timeline.train_reading(done, d["items_per_chunk"],
                                           done[0], w)
                if r:
                    cell.setdefault(("median_chunk_items_per_s", w), []).append(
                        r["median_items_per_s"])
                    cell.setdefault(("mean_items_per_s", w), []).append(
                        r["mean_items_per_s"])
        else:
            due, lat = d["due_s"], d["lat_ms"]
            done = [None if v is None else a + v / 1000.0
                    for a, v in zip(due, lat)]
            for w in windows:
                r = timeline.serve_reading(due, due, done, d["seconds"] + 5,
                                           1e9, 1.0, float(w))
                if r:
                    cell.setdefault(("p50_ms", w), []).append(r["p50_ms"])
                    cell.setdefault(("p95_ms", w), []).append(r["p95_ms"])
    return out


def main(argv):
    directory = argv[0]
    windows = [int(v) for v in argv[1:]] or [10, 20, 30, 40, 51]
    for cell, table in study(directory, windows).items():
        print(cell)
        for (name, w), values in sorted(table.items()):
            spread = timeline.quartile_spread(values)
            print(f"  {name:>18} window {w:>3}s  runs {len(values)}  "
                  f"spread {100 * spread if spread is not None else -1:6.3f}%"
                  f"  values {[round(v, 3) for v in values]}")


if __name__ == "__main__":
    main(sys.argv[1:])
