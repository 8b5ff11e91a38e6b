"""Benchmark entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV blocks:
  1. Partition quality        (paper Tables 4.3–4.6 + Table 4.7 synthesis)
  2. PMVC phase decomposition (paper Figures 4.16–4.55), batch-swept

Section 2 also writes ``BENCH_pmvc.json`` at the repo root (per-cell
timings + phase costs) so the perf trajectory is tracked across PRs.
"""
from pathlib import Path

from benchmarks import bench_partition, bench_pmvc
from repro.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    print("# === 1. partition quality (Tables 4.3-4.6) ===")
    rows = bench_partition.run()
    print("\n# === Table 4.7 analogue: win rates per combo ===")
    for combo, w in bench_partition.summary(rows).items():
        print(f"{combo}," + ",".join(f"{k}={v:.2f}" for k, v in w.items()))

    print("\n# === 1b. planning time at scale (DESIGN.md §10) ===")
    bench_partition.plan_at_scale()

    print("\n# === 2. PMVC phase decomposition (Figures 4.16-4.55) ===")
    bench_pmvc.run(json_path=str(Path(__file__).resolve().parent.parent / "BENCH_pmvc.json"))


if __name__ == "__main__":
    main()
