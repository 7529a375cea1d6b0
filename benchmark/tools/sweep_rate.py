"""Find the knee of a serving cell once: run the cell's traffic at several fixed arrival
rates, each in a fresh engine in this one process, and print for each the tails, the output
rate against the offered one and the backlog at the window's close. The knee is the highest
rate at which the backlog does not grow and the time to first token stays flat; the cell's
traffic file then states four fifths of it.

    python -m benchmark.tools.sweep_rate --workload <serving cell> --rates 1,2,3,4,5,6 --seconds 25
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True, help="comma-separated requests per second")
    parser.add_argument("--seconds", type=float, default=25.0)
    parser.add_argument("--seed", type=int, default=2_147_700_001)
    parser.add_argument("--tiny", action="store_true")
    options = parser.parse_args(argv)

    from benchmark.run import execute

    for i, rate in enumerate(float(r) for r in options.rates.split(",")):
        line, _ = execute(options.workload, options.seed + i, options.seconds, False, options.tiny,
                          traffic_overrides={"rate_per_s": rate}, skip_check=True)
        metrics = {k: v["value"] for k, v in line["metrics"].items()}
        print("SWEEP " + json.dumps({"rate_per_s": rate, "attempted": line["attempted"], "failed": line["failed"], **metrics}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
