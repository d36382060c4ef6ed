"""Spread of each end-to-end metric over the runs that ``series.py`` kept:
per cell and set, the median and the distance between the first and third
quartiles (``statistics.quantiles(values, n=4)``) as a share of it.

    python3 bench/tools/spread.py OUT.jsonl [OUT.jsonl ...]

A run's set is the sixth field of its spec (``series.py`` ignores fields
after the fifth only where the fifth names a plant, so pass ``-`` there).
"""
import collections
import json
import statistics
import sys


def main(paths):
    values = collections.defaultdict(list)
    for path in paths:
        with open(path) as f:
            for line in f:
                rec = json.loads(line)
                parts = rec["spec"].split()
                r = rec["result"]
                if not r or parts[3] != "0":
                    continue
                tag = parts[5] if len(parts) > 5 else "-"
                for name, m in r["metrics"].items():
                    values[(parts[0], tag, name)].append(m["value"])
    for (cell, tag, name), v in sorted(values.items()):
        med = statistics.median(v)
        q = statistics.quantiles(v, n=4) if len(v) > 1 else [med, med, med]
        print(f"{cell} set {tag} {name}: n {len(v)} median {med!r} "
              f"spread {(q[2] - q[0]) / med!r} min {min(v)!r} max {max(v)!r}")


if __name__ == "__main__":
    main(sys.argv[1:])
