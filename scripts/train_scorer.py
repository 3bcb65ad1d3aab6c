"""Train the learned search-guidance scorer from a seeded window corpus.

Harvests labeled windows by replaying ``SessionGenerator`` sessions through
an observer-instrumented Veer⁺ (positives *and* negatives — the certificate
corpus alone only sees winning windows), optionally mixes in existing
JSONL corpora (``session_bench --dump-windows`` output), trains the window
and per-EV logistic scorers, prints calibration stats, and writes the JSON
artifact ``VeerConfig(guidance="model")`` loads.

Usage (from the repo root):

    python scripts/train_scorer.py                       # refresh the
                                                         #   committed artifact
                                                         #   src/repro/learn/pretrained.json
    python scripts/train_scorer.py --smoke --out /tmp/g.json
                                                         # CI: tiny corpus,
                                                         #   fast train
    python scripts/train_scorer.py --corpus windows.jsonl --out my.json
    python scripts/train_scorer.py --dump-corpus corpus.jsonl
                                                         # also keep the
                                                         #   harvested corpus
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

from repro.learn import PRETRAINED_PATH, harvest, load_guidance, train_guidance  # noqa: E402
from repro.workload import dump_windows, load_windows  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sessions", type=int, default=10,
                    help="sessions to harvest (default 10)")
    ap.add_argument("--chain", type=int, default=12,
                    help="versions per session (default 12)")
    ap.add_argument("--budget", type=int, default=200,
                    help="max decompositions per harvested pair")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny harvest + fast train (the CI guided-smoke job)")
    ap.add_argument("--corpus", action="append", default=[], metavar="JSONL",
                    help="mix in an existing labeled-window corpus "
                         "(repeatable; session_bench --dump-windows output)")
    ap.add_argument("--no-harvest", action="store_true",
                    help="train from --corpus files only")
    ap.add_argument("--dump-corpus", metavar="PATH",
                    help="also write the harvested+mixed corpus as JSONL")
    ap.add_argument("--out", metavar="PATH", default=str(PRETRAINED_PATH),
                    help="artifact path (default: the committed "
                         "src/repro/learn/pretrained.json)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the calibration stats as JSON")
    args = ap.parse_args()

    sessions = 4 if args.smoke else args.sessions
    chain = 6 if args.smoke else args.chain

    examples = []
    if not args.no_harvest:
        t0 = time.perf_counter()
        examples = harvest(
            seed=args.seed,
            sessions=sessions,
            chain_length=chain,
            max_decompositions=args.budget,
        )
        print(
            f"harvested {len(examples)} labeled windows from {sessions} "
            f"sessions x {chain} versions in {time.perf_counter() - t0:.1f}s"
        )
    for path in args.corpus:
        with open(path) as fh:
            extra = list(load_windows(fh))
        print(f"loaded {len(extra)} examples from {path}")
        examples.extend(extra)
    if not examples:
        raise SystemExit("no training examples (use --corpus or drop --no-harvest)")

    if args.dump_corpus:
        with open(args.dump_corpus, "w") as fh:
            report = dump_windows(examples, fh)
        print(f"wrote corpus to {args.dump_corpus}: {report.summary()}")

    model, stats = train_guidance(examples, seed=args.seed)
    cal = stats["window"]
    print(
        f"trained on {stats['trainable']}/{stats['deduped']} deduped windows "
        f"(labels {stats['label_counts']}): "
        f"accuracy {cal['accuracy']:.3f}, brier {cal['brier']:.3f}, "
        f"base rate {cal['base_rate']:.3f}"
    )
    for row in cal["reliability"]:
        print(
            f"  calib {row['bin']}: n={row['n']:>5} "
            f"pred={row['mean_pred']:.2f} actual={row['frac_true']:.2f}"
        )
    for name, c in stats["evs"].items():
        print(f"  ev {name}: {c['wins']}/{c['attempts']} attempts won")

    model.save(args.out)
    print(f"wrote guidance artifact to {args.out}")
    load_guidance(args.out)  # round-trip + feature-contract check
    if args.json:
        pathlib.Path(args.json).write_text(json.dumps(stats, indent=2) + "\n")
        print(f"wrote stats to {args.json}")


if __name__ == "__main__":
    main()
