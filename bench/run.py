#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` records a
profiler trace of the window and prints the per-layer metrics read from it.
The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ...); the last lines of
standard error are the numbers compared for ``correct``, each beside its
limit.  Anything but a TPU, or fewer chips than the cell asks for, exits
non-zero with no result.  See ``bench/harness.py`` and ``PERF.md``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# Import the benchmark as the package ``bench`` (its ``trace`` module must not
# shadow the standard library's) and the system under test from ``src``.
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness

    bench_file = ROOT / "BENCHMARK.json"
    if not bench_file.is_file() or not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("bench: run from a checkout holding BENCHMARK.json and src/repro")
    cell = harness.Cell(harness.load_json(bench_file), args.workload)
    harness.configure_jax()
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    for name, c in result["checks"].items():
        harness.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
