"""Serving driver: the batched exact-inference engine (``repro.serve``).

A mixed stream of joint/marginal/conditional LL, sampling and MPE requests
is coalesced into padded per-kind micro-batches and executed through the
compiled-program cache; warm-up (compilation) and steady-state throughput
are reported separately, against the direct one-call-at-a-time baseline.

  PYTHONPATH=src python -m repro.launch.serve --arch einet_rat --requests 64
  PYTHONPATH=src python -m repro.launch.serve --smoke --trace /tmp/trace.json
"""

from __future__ import annotations

import argparse

import jax

from repro import obs
from repro import serve as serve_lib
from repro.configs import EinetConfig, get_config
from repro.launch import cells as dr

# CI trace-smoke profile: the same tiny all-grouping RAT shape as
# benchmarks/bench_serve.py (32 vars = the smallest RAT whose scopes don't
# collide across repetitions, so the smoke serves the grouped plan); kept
# local because the launch CLIs only see src/ on PYTHONPATH
SMOKE_CONFIG = EinetConfig(
    name="einet-rat-serve-smoke",
    structure="rat",
    num_vars=32,
    depth=2,
    num_repetitions=2,
    num_sums=4,
    batch_size=64,
)


# engine vs direct, per value: |engine - direct| <= PARITY_RTOL *
# (1 + |direct|), about 84 float32 ulps.  An absolute bound cannot hold on TPU, where the
# bucketed and batch-1 programs associate their reductions differently: for
# einet-pd-svhn on a v5e they differed by 3.9e-3 absolute.
PARITY_RTOL = 1e-5


def serve_einet(cfg, args):
    model = dr.build_einet(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n = args.requests
    reqs = serve_lib.mixed_requests(model.num_vars, n, seed=0)
    report = serve_lib.run_benchmark(
        model, params, reqs, max_batch=args.max_batch, reps=args.reps
    )
    print(serve_lib.format_report(report))
    if report["parity_max_rel_diff"] > PARITY_RTOL:
        raise SystemExit(
            "engine/direct parity violated: "
            f"{report['parity_max_rel_diff']:.2e} relative "
            f"(max abs {report['parity_max_abs_diff']:.2e})"
        )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny built-in arch + short stream (the CI "
                         "trace-smoke profile); --arch not required")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=0,
                    help="engine micro-batch cap (0 = min(32, requests))")
    ap.add_argument("--reps", type=int, default=3,
                    help="steady-state measurement repetitions")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="collect obs tracing spans and export a "
                         "Chrome-trace JSON to this path at exit")
    ap.add_argument("--metrics", default=None, metavar="OUT.json",
                    help="write the METRICS.snapshot() JSON to this path "
                         "at exit")
    args = ap.parse_args()
    if not args.smoke and args.arch is None:
        ap.error("--arch is required (or pass --smoke)")
    obs.cli_begin(args.trace)
    cfg = SMOKE_CONFIG if args.smoke else get_config(args.arch)
    serve_einet(cfg, args)
    obs.cli_end(args.trace, args.metrics)


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
