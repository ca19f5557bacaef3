"""Benchmark driver for the paper reproductions: Fig. 2 (connectivity
statistics), Table 2 (days to 40% top-1) and Fig. 7 (staleness and
idleness, from the Table-2 runs). Prints ``name,us_per_call,derived``
CSV rows (plus human-readable sections).

Default mode is CPU-budget-friendly: Fig. 2 full-scale, Table 2 at a
reduced horizon (sync capped; full runs live in results/table2.json via
``python -m benchmarks.table2_training_time``). Speed is measured on the
chip by ``bench/run.py``, not here.

    PYTHONPATH=src python -m benchmarks.run [--full]
"""
from __future__ import annotations

import argparse
import time


def section(title):
    print(f"\n# === {title} ===", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="run the full Table-2 horizon (slow)")
    args = ap.parse_args()

    t0 = time.time()
    print("name,us_per_call,derived")

    # ------------------------------------------------ Fig. 2
    section("Fig2: connectivity statistics (191 sats, 12 GS)")
    from benchmarks import fig2_connectivity
    t = time.time()
    out = fig2_connectivity.run(days=5.0)
    print(f"fig2_connectivity,{(time.time() - t) * 1e6:.0f},"
          f"ci[{out['ci_min']}..{out['ci_max']}]_nk[{out['nk_min']:.0f}.."
          f"{out['nk_max']:.0f}]")

    # ------------------------------------------------ Table 2 / Fig 6 / 7
    section("Table2: days to 40% top-1 (reduced horizon; full in "
            "results/table2.json)")
    from benchmarks.table2_training_time import run_table2
    t = time.time()
    max_days = 20.0 if args.full else 6.0
    schemes = (["sync", "async", "fedbuff", "fedspace"] if args.full
               else ["async", "fedbuff", "fedspace"])
    rows, _ = run_table2(["noniid"], schemes, max_days=max_days)
    for r in rows:
        d = r["days_to_target"]
        print(f"table2_{r['setting']}_{r['scheme']},"
              f"{r['wall_s'] * 1e6:.0f},"
              f"days_to_40pct={d if d is not None else 'FAIL'}")

    # ------------------------------------------------ Fig. 7 summary
    section("Fig7: staleness/idleness distribution (from Table-2 runs)")
    for r in rows:
        print(f"fig7_{r['scheme']},0,hist={r['staleness_hist']}"
              f"_idle={r['idle_connections']}of{r['total_connections']}")

    print(f"\n# total bench time: {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
