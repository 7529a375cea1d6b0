"""Run one traced run of a cell and print what named per-layer readers read there — readers
under ``benchmark/layer_metrics/`` that ``BENCHMARK.json`` has no entry for yet, on any cell:

    python -m benchmark.tools.read_layers --workload <cell> --seed <n> --seconds 45 \\
        --reader idle_in_step_ms.train --reader idle_between_programs_ms.train [--keep-trace]

The cell's driver runs as the command runs it (``benchmark.run.execute``, ``--trace 1``);
the readers are called on its result before the run's directory is removed. No driver,
configuration or accepted reader is touched: the driver is wrapped, as ``read_limits`` wraps
the command. The values and the result line go to ``<out>/<cell>.json``; ``--keep-trace``
also copies the run's ``.xplane.pb`` there, gzipped, and its telemetry records.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
from dataclasses import dataclass, field
from types import SimpleNamespace

from benchmark.spec import Spec


@dataclass
class ReadingSpec(Spec):
    """A :class:`Spec` whose drivers also call ``readers`` on what they return."""

    readers: tuple = ()
    keep_trace_in: str | None = None
    read: dict = field(default_factory=dict)  # reader -> what it returned

    def driver(self, traffic: dict):
        module = super().driver(traffic)

        def run(ctx):
            result = module.run(ctx)
            for name in self.readers:
                self.read[name] = self.layer_metric(name).read(result, ctx)
                print(f"READ {name} = {self.read[name]!r}", flush=True)
            if self.keep_trace_in is not None:
                from benchmark.reduce_trace import find_xplane

                os.makedirs(self.keep_trace_in, exist_ok=True)
                with open(find_xplane(os.path.join(ctx.out_dir, "trace")), "rb") as source:
                    with gzip.open(os.path.join(self.keep_trace_in, ctx.cell.name + ".xplane.pb.gz"), "wb") as target:
                        shutil.copyfileobj(source, target)
                with open(os.path.join(self.keep_trace_in, ctx.cell.name + ".telemetry.jsonl"), "w") as f:
                    f.writelines(json.dumps(record) + "\n" for record in result.telemetry)
            return result

        return SimpleNamespace(run=run)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=45.0)
    parser.add_argument("--reader", action="append", default=[], help="a file under layer_metrics/, without .py")
    parser.add_argument("--keep-trace", action="store_true")
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--out", default=os.path.join("chiprun_out", "layers"))
    options = parser.parse_args(argv)

    from benchmark.run import execute

    spec = ReadingSpec.load()
    spec.readers = tuple(options.reader)
    spec.keep_trace_in = options.out if options.keep_trace else None
    line, _ = execute(options.workload, options.seed, options.seconds, True, options.tiny, spec=spec)
    os.makedirs(options.out, exist_ok=True)
    with open(os.path.join(options.out, options.workload + ".json"), "w") as f:
        json.dump({"seed": options.seed, "read": spec.read, "line": line}, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
