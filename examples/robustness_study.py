"""Full reproduction of the paper's Figures 1-6 (robustness of scheduling
algorithms to processing-rate estimation errors).

    PYTHONPATH=src python examples/robustness_study.py [--full]

Writes experiments/figures/robustness_study.csv and prints the per-figure
summaries plus the headline-claims check.  --full uses paper-scale horizons
(slow on one CPU core); the default is a reduced but qualitatively faithful
sweep.
"""

import argparse
import csv
import sys
from pathlib import Path

# `benchmarks` lives at the repo root, which is not on sys.path when this
# file is run as a script (sys.path[0] is examples/).
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    fast = not args.full

    from benchmarks import figures
    rows = []
    for name, fn in (("fig1", figures.fig1_precise),
                     ("fig2", figures.fig2_highload),
                     ("fig3/4", figures.fig34_under),
                     ("fig5/6", figures.fig56_over)):
        out = fn(fast)
        rows.extend(out)
        print(f"-- {name}: {len(out)} points")
        algos = sorted({r["algo"] for r in out})
        for algo in algos:
            sub = [r for r in out if r["algo"] == algo]
            worst = max(r["mean_delay"] for r in sub)
            sens = max((abs(r.get("sensitivity", 0.0)) for r in sub),
                       default=0.0)
            print(f"   {algo:16s} worst delay {worst:8.2f} slots"
                  f"   max sensitivity {sens:6.1%}")
    claims = figures.headline_claims(rows)
    print("headline claims:", claims)

    outdir = Path("experiments/figures")
    outdir.mkdir(parents=True, exist_ok=True)
    keys = sorted({k for r in rows for k in r})
    with open(outdir / "robustness_study.csv", "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=keys)
        w.writeheader()
        w.writerows(rows)
    print(f"wrote {outdir / 'robustness_study.csv'}")


if __name__ == "__main__":
    from repro.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    main()
