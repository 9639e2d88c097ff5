"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines and writes one
schema-versioned detail record per lane (see ``common.emit``) under
results/bench/ — or under ``--json-dir`` to consolidate a run's JSON in
one place (the CI artifact step and local A/B comparisons both point it
at a fresh directory). REPRO_BENCH_SIZE=medium scales the proxy
datasets to benchmark-grade sizes.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="CEAZ benchmark harness (all lanes)")
    ap.add_argument("--json-dir", default=None, metavar="DIR",
                    help="write every lane's detail JSON under DIR "
                         "(default: results/bench or $REPRO_BENCH_OUT)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import init_compile_cache
    init_compile_cache()
    from . import common
    if args.json_dir:
        common.OUT_DIR = args.json_dir
    from . import (chi_thresholds, fixed_ratio, fused_decode,
                   fused_pipeline, kernel_microbench, offline_codewords,
                   parallel_io, ratio_distortion, roofline_report,
                   serving_latency, single_pass, sort_latency,
                   symbol_hist, throughput, update_size)
    suites = [
        ("sort_latency(Fig6/Alg1)", sort_latency.run),
        ("symbol_hist(Fig7)", symbol_hist.run),
        ("offline_codewords(Fig10)", offline_codewords.run),
        ("update_size(Fig11)", update_size.run),
        ("chi_thresholds(Fig12)", chi_thresholds.run),
        ("fixed_ratio(Fig13)", fixed_ratio.run),
        ("fixed_ratio_speculation(gate)", fixed_ratio.run_speculation),
        ("single_pass(gate)", single_pass.run),
        ("ratio_distortion(Fig14/T4/T5)", ratio_distortion.run),
        ("throughput(Fig15/16,T6/T7)", throughput.run),
        ("fused_pipeline(Fig4)", fused_pipeline.run),
        ("fused_decode(Fig4-read)", fused_decode.run),
        ("serving_latency(paging)", serving_latency.run),
        ("kernel_microbench(dispatch)", kernel_microbench.run),
        ("parallel_io(Fig17)", parallel_io.run),
        ("roofline_report(dry-run)", roofline_report.run),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        try:
            fn()
        except Exception as e:
            failures += 1
            print(f"{name},0,FAILED:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
