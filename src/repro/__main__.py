"""``python -m repro`` - the one CLI over the one experiment API.

Subcommands:
  * ``sweep`` - batched experiment grids (the former
    ``python -m repro.sweep``, flags unchanged; results in the sweep
    store).
  * ``serve`` - fleet capacity planning: replay a synthetic serving
    request stream through the batched DVBP engine (``repro.api``
    serving_requests workload) and compare policies against the host
    fleet baselines.  With ``--traffic {poisson,diurnal}`` it instead
    drives the live batched front end (admission queue -> double-buffered
    block dispatch) and reports throughput + placement latency.
  * ``bench`` - the benchmark harness (``benchmarks.run``; requires the
    repo root on sys.path, i.e. run from a checkout).
  * ``obs`` - summarize a JSONL observability run log (spans + counters),
    optionally converting it to Chrome/Perfetto trace_event JSON.
  * ``validate`` - input validation / quarantine dry run: build the suite
    instances and report rows the sanitizer would quarantine (NaN sizes,
    non-positive durations, departure < arrival, oversize, duplicate
    ids); exits non-zero when anything is bad.

    PYTHONPATH=src python -m repro sweep --suites azure --n-instances 12
    PYTHONPATH=src python -m repro serve --requests 2000 --sigma 0.5
    PYTHONPATH=src python -m repro serve --traffic poisson --rate 5e4 \
        --tps 1.2e5 --requests 2000
    PYTHONPATH=src python -m repro bench --fast
    PYTHONPATH=src python -m repro obs run.obs.jsonl --perfetto trace.json
    PYTHONPATH=src python -m repro validate --suites azure huawei
"""
from __future__ import annotations

import argparse
import sys
from typing import List, Optional


def _serve(argv: Optional[List[str]]) -> None:
    ap = argparse.ArgumentParser(
        prog="python -m repro serve",
        description="Fleet capacity planning: DVBP policies over a "
                    "request stream via the batched replay engine.")
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--tps", type=float, default=50.0,
                    help="decode tokens per second per slot")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sigma", type=float, default=0.0,
                    help="log-normal decode-length prediction error "
                         "(0 = clairvoyant predictions)")
    ap.add_argument("--policies",
                    default="first_fit,best_fit_linf,greedy,"
                            "nrt_prioritized",
                    help="comma list of scan policies to plan with")
    ap.add_argument("--setting", default="predicted",
                    choices=["nonclairvoyant", "clairvoyant", "predicted"],
                    help="information regime (predicted replays the "
                         "attached request predictions)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--kv-tokens", type=int, default=65536)
    ap.add_argument("--prefill-budget", type=float, default=262144)
    ap.add_argument("--backend", default=None,
                    choices=["auto", "jnp", "pallas", "pallas_interpret"])
    ap.add_argument("--store", default="",
                    help="persist records to this sweep-store directory")
    ap.add_argument("--baselines", action="store_true",
                    help="also run the host round_robin / pack_all fleet "
                         "baselines for reference")
    ap.add_argument("--traffic", default=None,
                    choices=["poisson", "diurnal"],
                    help="run the live batched front end (admission queue "
                         "-> double-buffered block dispatch) under this "
                         "synthetic traffic instead of capacity planning")
    ap.add_argument("--batch-max", type=int, default=256,
                    help="admission batch size for --traffic mode")
    args = ap.parse_args(argv)

    from . import api
    from .serving.fleet import attach_predictions, synth_requests
    from .serving.scheduler import ReplicaCapacity

    if args.traffic:
        from .serving.dispatch import serve_traffic
        from .serving.traffic import make_traffic
        caps = ReplicaCapacity(args.slots, args.kv_tokens,
                               args.prefill_budget)
        reqs = make_traffic(args.traffic, args.requests, rate=args.rate,
                            seed=args.seed, sigma_pred=args.sigma)
        print(f"{'policy':<18} {'req/s':>10} {'p50_ms':>8} {'p99_ms':>8} "
              f"{'replica_s':>12} {'opened':>7} {'shed':>6}")
        for pol in args.policies.split(","):
            rep = serve_traffic(reqs, pol, caps, tps=args.tps,
                                batch_max=args.batch_max,
                                impl=args.backend or "auto")
            p50, p99 = rep.latency_quantiles()
            print(f"{pol:<18} {rep.throughput:>10.0f} {p50 * 1e3:>8.2f} "
                  f"{p99 * 1e3:>8.2f} {rep.replica_seconds:>12.1f} "
                  f"{rep.replicas_opened:>7d} {rep.shed:>6d}")
        return

    reqs = synth_requests(args.requests, seed=args.seed, rate=args.rate,
                          tps=args.tps)
    setting = args.setting
    if setting == "predicted":
        reqs = attach_predictions(reqs, args.sigma, seed=args.seed)
        setting = api.Setting.predicted()
    caps = ReplicaCapacity(args.slots, args.kv_tokens, args.prefill_budget)
    wl = api.serving_requests(reqs, caps=caps, tps=args.tps,
                              name=f"synth{args.requests}r{args.seed}")
    exp = api.Experiment(wl, policies=tuple(args.policies.split(",")),
                         settings=(setting,))
    res = exp.run(store=args.store or None, backend=args.backend,
                  progress=lambda m: print(f"# {m}", flush=True))
    print(f"{'policy':<18} {'setting':<22} {'replica_s':>12} "
          f"{'opened':>7} {'ratio':>8}")
    for r in res.rows():
        print(f"{r['policy']:<18} {r['setting']:<22} "
              f"{r['usage_time']:>12.1f} {r['n_bins_opened']:>7d} "
              f"{r['ratio']:>8.4f}")
    if args.baselines:
        from .serving.fleet import simulate_fleet
        for pol in ("round_robin", "pack_all"):
            b = simulate_fleet(reqs, pol, caps, args.tps)
            print(f"{pol:<18} {'(host baseline)':<22} "
                  f"{b['replica_seconds']:>12.1f} "
                  f"{b['replicas_opened']:>7d} {'-':>8}")


def _bench(argv: Optional[List[str]]) -> None:
    try:
        from benchmarks.run import main as bench_main
    except ImportError as e:
        raise SystemExit(
            "python -m repro bench needs the repo checkout on sys.path "
            f"(run from the repo root): {e}")
    bench_main(argv)


def main(argv: Optional[List[str]] = None) -> None:
    argv = sys.argv[1:] if argv is None else list(argv)
    ap = argparse.ArgumentParser(
        prog="python -m repro",
        description=__doc__.splitlines()[0],
        usage="python -m repro {sweep,serve,bench,obs,validate} ...")
    ap.add_argument("command",
                    choices=["sweep", "serve", "bench", "obs", "validate"])
    args, rest = ap.parse_known_args(argv)
    from .compile_cache import enable
    enable()
    if args.command == "sweep":
        from .sweep.__main__ import main as sweep_main
        sweep_main(rest)
    elif args.command == "serve":
        _serve(rest)
    elif args.command == "obs":
        from .obs.cli import main as obs_main
        obs_main(rest)
    elif args.command == "validate":
        from .resilience.validate import main as validate_main
        validate_main(rest)
    else:
        _bench(rest)


if __name__ == "__main__":
    main()
