"""Read, in one warm process, the numbers a cell's ``correct`` compares: the program's over
many seeds and the control's (the reference in fp8) over a few. The limits in
``benchmark/limits/<cell>.json`` are set from these two readings (PERF.md section 2).

    python -m benchmark.tools.read_limits --workload <cell> --seeds 12 --control-seeds 3 --seconds 2
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=2.0)
    parser.add_argument("--first-seed", type=int, default=2_147_500_000)
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--out", default=os.path.join("chiprun_out", "limits"))
    options = parser.parse_args(argv)

    from benchmark.run import execute

    rows = []
    for i in range(options.seeds):
        seed = options.first_seed + 7919 * i
        line, checks = execute(options.workload, seed, options.seconds, False, options.tiny, control=i < options.control_seeds)
        rows.append({"seed": seed, "correct": line["correct"], "checks": {c.name: c.value for c in checks}})
        print("READ " + json.dumps(rows[-1]), flush=True)
    names = sorted({name for row in rows for name in row["checks"]})
    summary = {}
    for name in names:
        values = [row["checks"][name] for row in rows if name in row["checks"]]
        summary[name] = {"n": len(values), "min": min(values), "max": max(values)}
        print(f"SUMMARY {name}: n {len(values)}, min {min(values)!r}, max {max(values)!r}", flush=True)
    os.makedirs(options.out, exist_ok=True)
    with open(os.path.join(options.out, options.workload + ".json"), "w") as f:
        json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
