"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig12]

Prints ``name,us_per_call,derived`` CSV rows (plus a header comment per
suite). Figure/table mapping:
    table1_comm        — Table 1 / §3: O(1) vs Theta(log p) comm volumes
    table7_efficiency  — Table 7: compute efficiency vs #accelerators
    fig10_11_speedup   — Figs 10-11: measured gossip-vs-AGD step speedup
    fig12_14_accuracy  — Figs 12-14: convergence equivalence (final loss)
    fig16_loss_vs_time — Fig 16: loss after a fixed wall-time budget
    fig17_every_logp   — Fig 17: gossip vs every-log(p) all-reduce
    ablation_robustness— beyond-paper: grad-vs-model gossip, dropped
                         exchanges, staleness-k convergence
"""
import argparse
import sys
import traceback

SUITES = [
    "table1_comm",
    "table7_efficiency",
    "fig10_11_speedup",
    "fig12_14_accuracy",
    "fig16_loss_vs_time",
    "fig17_every_logp",
    "ablation_robustness",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    failed = []
    print("name,us_per_call,derived")
    for name in SUITES:
        if args.only and args.only not in name:
            continue
        print(f"# suite: {name}", flush=True)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["rows"])
            for row_name, us, derived in mod.rows():
                print(f"{row_name},{us:.2f},{derived}", flush=True)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"# FAILED suites: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
