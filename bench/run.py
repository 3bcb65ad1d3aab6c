"""Run one cell of the benchmark named in ``BENCHMARK.json``.

    python3 bench/run.py --workload q40.reexec --seed 7 --seconds 10 --trace 0

Needs a TPU: with no accelerator, or fewer chips than the cell asks for,
it exits non-zero and prints no result.  Prints the numbers that decide
``correct`` beside their limits as the last lines of standard error, and
one JSON object as the last line of standard output.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import NoAccelerator, load_cell, run_cell

    cell = load_cell(ROOT, args.workload)
    try:
        outcome = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t0=T0, root=ROOT)
    except NoAccelerator as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return 2
    print(f"host {json.dumps(outcome.host)}", file=sys.stderr)
    for name, c in outcome.line["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(outcome.line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
