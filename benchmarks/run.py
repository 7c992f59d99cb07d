"""Benchmark driver — one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (paper Fig. 7, Fig. 8, Fig. 9,
Appendix D, Appendix E.1), then the roofline summary pointer, and
writes a machine-readable ``BENCH_<timestamp>.json`` next to the CSV
output so the perf trajectory is trackable across PRs.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--skip-skew]
    PYTHONPATH=src python -m benchmarks.run --trajectory   # summarize
"""
import argparse
import glob
import json
import os
import sys
import time
import traceback

from benchmarks import common


def trajectory(out_dir: str) -> None:
    """Summarize the BENCH_<timestamp>.json series already on disk:
    one line per (section, benchmark) with its us_per_call across
    runs, oldest -> newest, so cross-PR drift is visible at a
    glance."""
    paths = sorted(glob.glob(os.path.join(out_dir, "BENCH_*.json")))
    if not paths:
        print(f"# no BENCH_*.json under {out_dir}")
        return
    runs = []
    for p in paths:
        try:
            with open(p) as f:
                runs.append(json.load(f))
        except (OSError, ValueError):
            print(f"# skipping unreadable {p}")
    stamps = [r.get("timestamp", "?") for r in runs]
    print(f"# {len(runs)} runs: {stamps[0]} .. {stamps[-1]}")
    series = {}        # (section, name) -> [us or None per run]
    for i, r in enumerate(runs):
        for sec, names in r.get("sections", {}).items():
            for name, rec in names.items():
                series.setdefault((sec, name),
                                  [None] * len(runs))[i] = rec
    print("section,name,us_per_call_series,"
          "p50_ms_series,p95_ms_series,p99_ms_series,latest_extras")
    for (sec, name), recs in sorted(series.items()):
        us = ["-" if rec is None else f"{rec.get('us_per_call', 0):g}"
              for rec in recs]
        last = next(rec for rec in reversed(recs) if rec is not None)

        def pseries(key):
            # latency-percentile drift, same oldest->newest shape as
            # us_per_call; benchmarks that don't emit them show "-"
            vals = ["-" if rec is None or key not in rec
                    else f"{rec[key]:g}" for rec in recs]
            return "->".join(vals) if any(v != "-" for v in vals) \
                else "-"
        extras = ";".join(f"{k}={v}" for k, v in sorted(last.items())
                          if k not in ("us_per_call", "derived",
                                       "p50_ms", "p95_ms", "p99_ms"))
        print(f"{sec},{name},{'->'.join(us)},{pseries('p50_ms')},"
              f"{pseries('p95_ms')},{pseries('p99_ms')},{extras}")
    failed = [(r.get("timestamp"), r.get("failed_sections"))
              for r in runs if r.get("failed_sections")]
    if failed:
        print(f"# runs with failed sections: {failed}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-skew", action="store_true",
                    help="skip the 8-virtual-device subprocess benchmark")
    ap.add_argument("--out-dir", default=".",
                    help="directory for BENCH_<timestamp>.json")
    ap.add_argument("--trajectory", action="store_true",
                    help="don't run anything: summarize the existing "
                         "BENCH_*.json series in --out-dir")
    args = ap.parse_args()
    if args.trajectory:
        trajectory(args.out_dir)
        return
    # fail fast on an unwritable destination, not after the full run
    os.makedirs(args.out_dir, exist_ok=True)
    from repro import compile_cache
    compile_cache.enable(os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))

    print("name,us_per_call,derived")
    sections = []
    from benchmarks import (biomedical, representation, serving, storage,
                            succinct, tpch_nested)
    sections.append(("tpch_nested (Fig.7)",
                     lambda: tpch_nested.run(scale=30 if args.quick else 60)))
    sections.append(("serving (plan-cache query service)",
                     lambda: serving.run(
                         n_orders=300 if args.quick else 2000,
                         invocations=20 if args.quick else 50)))
    def storage_section():
        storage.run(n_orders=300 if args.quick else 2000,
                    n_parts=128 if args.quick else 512,
                    chunk_rows=32 if args.quick else 64)
        # compression ratio / decode GB/s / morsel-stream records ride
        # in the same trajectory file
        if args.quick:
            storage.run_compression(n_orders=1200, fanout=40,
                                    chunk_rows=8192, iters=3,
                                    smoke=True)
            storage.run_streamed(n_orders=400, n_parts=128,
                                 chunk_rows=32)
        else:
            storage.run_compression()
            storage.run_streamed()
    sections.append(("storage (persisted shredded datasets)",
                     storage_section))
    sections.append(("biomedical E2E (Fig.9)",
                     lambda: biomedical.run(n_samples=6 if args.quick else 10)))
    sections.append(("succinct (App.D)", succinct.run))
    sections.append(("representation (App.E.1)",
                     lambda: representation.run(
                         n=5000 if args.quick else 20000)))
    if not args.skip_skew:
        from benchmarks import cost, hypercube, skew
        sections.append(("skew (Fig.8)", skew.run))
        sections.append(("hypercube (one-round multiway join)",
                         lambda: hypercube.run(smoke=args.quick)))
        sections.append(("cost (cost-based optimizer)",
                         lambda: cost.run(smoke=args.quick)))

    failed = []
    for name, fn in sections:
        print(f"# --- {name} ---", flush=True)
        common.set_section(name)
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        finally:
            common.set_section(None)
    print("# --- roofline (assignment) ---")
    print("# see: PYTHONPATH=src python -m benchmarks.roofline")

    stamp = time.strftime("%Y%m%d_%H%M%S")
    by_section = {}
    for rec in common.RECORDS:
        # keep every emitted field (us_per_call, derived, and the
        # compile_ms/warm_ms split) in the perf-trajectory file
        payload_rec = {k: v for k, v in rec.items()
                       if k not in ("section", "name")}
        by_section.setdefault(rec["section"] or "unsectioned", {})[
            rec["name"]] = payload_rec
    payload = {"timestamp": stamp, "quick": args.quick,
               "failed_sections": failed, "sections": by_section}
    out_path = f"{args.out_dir}/BENCH_{stamp}.json"
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out_path}")

    if failed:
        print(f"# FAILED sections: {failed}")
        sys.exit(1)
    print("# all benchmark sections completed")


if __name__ == '__main__':
    main()
