"""Median and spread of each metric over a set of runs, as the bounds in
``BENCHMARK.json`` are set from them.

    python potbench/spread.py RESULT_FILE...

Each file holds one run's standard output; its last line is the result.
The spread is the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) over the median.
"""

import json
import statistics
import sys


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths: list[str]) -> int:
    by_metric: dict[str, list[float]] = {}
    for path in paths:
        with open(path) as f:
            line = f.read().strip().splitlines()[-1]
        out = json.loads(line)
        if not out["correct"]:
            print(f"{path}: not correct", file=sys.stderr)
        for name, m in out["metrics"].items():
            by_metric.setdefault(name, []).append(m["value"])
    for name, vals in sorted(by_metric.items()):
        print(json.dumps({"metric": name, "runs": len(vals),
                          "median": statistics.median(vals),
                          "spread": spread(vals) if len(vals) > 1 else None,
                          "values": vals}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
