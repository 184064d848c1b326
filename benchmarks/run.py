"""Benchmark harness: one section per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV (value column is GOPS / cycles /
microseconds as the name indicates).

    PYTHONPATH=src python -m benchmarks.run [--skip-e2e]
"""

from __future__ import annotations

import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-e2e", action="store_true")
    ap.add_argument(
        "--json-out",
        default=None,
        help="path for the machine-readable kernel benchmark dump "
        "(default: BENCH_kernel.json, or $BENCH_KERNEL_JSON)",
    )
    args = ap.parse_args()

    from benchmarks import cycles, kernel_bench, throughput_model

    os.environ.setdefault("XLA_FLAGS", kernel_bench.VIRTUAL_CPU_DEVICES)

    sections = [
        ("paper tables II/III/IV + fig6", throughput_model.run),
        ("cycle scaling eq6 vs eq8", cycles.run),
        (
            "bit-serial matmul kernels",
            lambda: kernel_bench.run(json_path=args.json_out),
        ),
    ]
    if not args.skip_e2e:
        from benchmarks import e2e_bench

        sections.append(("end-to-end train/serve", e2e_bench.run))

    print("name,us_per_call,derived")
    failures = 0
    for title, fn in sections:
        print(f"# --- {title} ---")
        try:
            for name, val, derived in fn():
                print(f"{name},{val},{derived}")
        except AssertionError as e:
            failures += 1
            print(f"# SECTION FAILED ({title}): {e}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
