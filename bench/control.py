#!/usr/bin/env python3
"""Read a cell's checks over several seeds in one process, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20 \\
        [--set integrity=false] [--set integrity_backend=host]

``--set`` overrides a service setting of the cell's configuration: with
``integrity=false`` this is the control, the configuration's integrity
guarantee switched off, which has to come out as not correct. Without it,
it reads the program's own runs. The benchmark's runs never run this.
Prints, for each seed, one JSON line with the seed, ``correct`` and the
numbers compared.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE")
    args = ap.parse_args(argv)
    cell = harness.resolve_cell(args.workload)
    for kv in args.set:
        key, value = kv.split("=", 1)
        cell.config["service"][key] = json.loads(value) if value in ("true", "false") \
            or value.lstrip("-").isdigit() else value
    try:
        device, peaks = harness.open_chip(cell.chips)
    except RuntimeError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = harness.run_cell(cell, seed, args.seconds, False, device=device,
                               peaks=peaks, work=harness.work_dir(cell.name),
                               log=lambda m: print(m, flush=True), t_start=t)
        print(json.dumps({"seed": seed, "set": args.set, "correct": out["correct"],
                          "metrics": out["metrics"],
                          "checks": {k: c["value"] for k, c in out["checks"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
