#!/usr/bin/env python3
"""Readings of the comparison that decides ``correct``, over many seeds, to
set each cell's limits (``bench/limits/<workload>.json``).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 [--control]
                               [--fault half_batch] [--out file.jsonl]

For each seed: build the cell, run set-up until the recorder holds the
samplers' recorded fragments and three learner steps (no measured window),
free the program, and print one JSON line of readings: the program against
the float32 reference, and with ``--control`` the bfloat16 reference in the
program's place.  ``--fault`` plants a fault of ``bench/faults.py`` first.
The benchmark's own runs never run this.  Each seed logs the device's bytes
in use before the build, after set-up and once the program is freed; give
one seed per process all the same, as the benchmark's runs start afresh.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    from bench import faults, harness

    cell = harness.Cell(harness.load_json(ROOT / "BENCHMARK.json"), args.workload)
    harness.configure_jax()
    harness.device_info(cell.chips)
    fault = faults.FAULTS[args.fault] if args.fault else None
    cell.traffic = dict(cell.traffic, warmup_iters=0)  # set-up stops once recorded
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        algo, ws, rec, _ = harness.setup(cell, seed, fault)
        rec.memory["after_setup"] = harness.bytes_in_use(cell.chips)
        harness.stop(algo, ws, rec)
        del algo, ws
        harness.free(cell.chips, rec.memory, f"seed {seed}")
        line = {"workload": cell.name, "seed": seed, "fault": args.fault or None,
                "memory_bytes_in_use": rec.memory,
                "program": harness.readings(cell, seed, rec, detail=True)}
        if args.control:
            line["control"] = harness.readings(cell, seed, rec, control=True)
        line["seconds"] = time.perf_counter() - t
        del rec
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
