"""Readings that the limits of ``correct`` are set from.

    python3 bench/limits.py --workload q40.reexec --seconds 20 --seeds 101 102 103

For each seed, one whole run of the cell (a short window at the cell's
own load, the check's own sample) in one process, printing one JSON line
with the program's readings and ``correct``, and the control's: the plain
reference computed in float32 put in the program's place and judged by
the same comparison and limits.  The benchmark's own runs never run the
control.  Needs a TPU, as ``bench/run.py`` does.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench.harness import NoAccelerator, load_cell, run_cell

    cell = load_cell(ROOT, args.workload)
    t0 = T0
    for seed in args.seeds:
        try:
            out = run_cell(cell, seed, args.seconds, False, t0=t0, root=ROOT, control=True)
        except NoAccelerator as exc:
            print(f"no result: {exc}", file=sys.stderr)
            return 2
        print(json.dumps({"seed": seed, "correct": out.line["correct"],
                          "attempted": out.line["attempted"],
                          "metrics": {k: v["value"] for k, v in out.line["metrics"].items()},
                          "readings": out.readings,
                          "control_correct": out.control["correct"],
                          "control_readings": out.control["readings"],
                          "host": out.host}), flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
