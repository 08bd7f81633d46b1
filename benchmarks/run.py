"""Benchmark entry point: one function per paper table/figure, plus the
quantized-serving sweep (``--only quant`` → quant_sweep, writing
``BENCH_quant.json``) and the filter sweep (``--only filter`` →
filter_sweep, writing ``BENCH_filters.json``).

``PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] [--n N]``
Prints ``benchmark,name,metric,value`` CSV rows; artifacts land in
artifacts/bench/. ``--n`` overrides the dataset size on benchmarks that
take one (CI smoke runs use a tiny value). The roofline report
(§Roofline) is separate: ``python -m benchmarks.roofline``.
"""
import argparse
import inspect
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale-ish sizes (slower)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--n", type=int, default=0,
                    help="dataset-size override for benchmarks accepting n")
    ap.add_argument("--partitions", type=int, default=0,
                    help="partition-count override for benchmarks accepting "
                         "partitions (scale_sweep; CI smoke uses 8)")
    ap.add_argument("--skew", type=float, default=0.0,
                    help="Zipf query-popularity exponent for benchmarks "
                         "accepting skew (serve_sweep; 0 = uniform)")
    args = ap.parse_args()

    from benchmarks import paper_benchmarks as pb
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    fns = pb.ALL
    if args.only:
        fns = [f for f in fns if args.only in f.__name__]
        if not fns:
            raise SystemExit(f"no benchmark matches {args.only!r}")
    t_start = time.time()
    for fn in fns:
        kw = {}
        sig = inspect.signature(fn).parameters
        if args.n and "n" in sig:
            kw["n"] = args.n
        if args.partitions and "partitions" in sig:
            kw["partitions"] = args.partitions
        if args.skew and "skew" in sig:
            kw["skew"] = args.skew
        print(f"=== {fn.__name__} ===", flush=True)
        t0 = time.time()
        fn(fast=not args.full, **kw)
        print(f"=== {fn.__name__} done in {time.time()-t0:.1f}s ===", flush=True)
    print(f"ALL BENCHMARKS DONE in {time.time()-t_start:.1f}s")


if __name__ == "__main__":
    main()
