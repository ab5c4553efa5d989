"""CLI for batched experiment sweeps.

Promoted to ``python -m repro sweep`` (same flags); this module remains
the legacy ``python -m repro.sweep`` entry and forwards unchanged.

Examples:
    # clairvoyant azure grid, all on-device policies, results persisted
    PYTHONPATH=src python -m repro sweep --suites azure --n-instances 12

    # prediction-noise sweep over three sigmas, five seeds
    PYTHONPATH=src python -m repro sweep --preds clairvoyant \
        lognormal:0.5 lognormal:2.0 --seeds 0,1,2,3,4

    # incremental: re-running the same spec only computes missing groups
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

from .grid import PredModel, SuiteSpec, SweepSpec, run_sweep, summarize_sweep
from .store import SweepStore
from ..consolidate import ConsolidationSpec
from ..core.jaxsim import SCAN_POLICIES

SUITE_DEFAULT_SEED = {"azure": 2026, "huawei": 77, "azure_trace": 0}


def _pred(token: str) -> PredModel:
    kind, _, param = token.partition(":")
    if kind in ("lognormal", "uniform") and not param:
        unit = "SIGMA" if kind == "lognormal" else "EPS"
        raise SystemExit(f"--preds {kind} needs a parameter: {kind}:{unit}")
    return PredModel(kind, float(param) if param else 0.0)


def _platform() -> str:
    """The default JAX platform, asked in a short-lived child so that this
    process never starts a backend (and never holds a chip its workers
    would need)."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        return "cpu"
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.default_backend())"],
        capture_output=True, text=True, timeout=300)
    return out.stdout.strip().splitlines()[-1] if out.stdout.strip() \
        else "unknown"


def main(argv=None, prog: str = "python -m repro sweep") -> None:
    ap = argparse.ArgumentParser(
        prog=prog,
        description="Evaluate a DVBP experiment grid in batched device runs.")
    ap.add_argument("--suites", nargs="+", default=["azure"],
                    choices=["azure", "huawei", "azure_trace"])
    ap.add_argument("--n-instances", type=int, default=6)
    ap.add_argument("--n-items", type=int, default=500)
    ap.add_argument("--suite-seed", type=int, default=None,
                    help="instance-generator seed (default: family-specific)")
    ap.add_argument("--trace-root", default="data/azure",
                    help="Azure Packing2020 dump directory (azure_trace)")
    ap.add_argument("--policies", default="all",
                    help=f"comma list from {','.join(SCAN_POLICIES)} "
                         "or 'all' (parametric names like cbd_beta4 / "
                         "cbdt_rho3600 parse too)")
    ap.add_argument("--preds", nargs="+", default=["clairvoyant"],
                    help="prediction models: none | clairvoyant | "
                         "lognormal:SIGMA | uniform:EPS")
    ap.add_argument("--seeds", default="0",
                    help="comma list of seeds for noisy prediction models")
    ap.add_argument("--max-bins", type=int, default=64)
    ap.add_argument("--max-bins-cap", type=int, default=8192)
    ap.add_argument("--consolidate", nargs="+", default=["none"],
                    help="consolidation scenario axis: none | "
                         "underload[:THRESHOLD[:BUDGET]] | "
                         "periodic:DT[:THRESHOLD[:BUDGET]] (tagged knobs "
                         "t/b/e/c/dt accepted, e.g. underload:t0.25:b64); "
                         "each value adds a grid column")
    ap.add_argument("--store", default="experiments/sweeps",
                    help="result-store directory")
    ap.add_argument("--no-store", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="recompute even if the store has results")
    ap.add_argument("--backend", default=None,
                    choices=["auto", "jnp", "pallas", "pallas_interpret"],
                    help="scoring engine (default auto: Pallas kernel on "
                         "TPU, inline jnp elsewhere)")
    ap.add_argument("--shard", default="auto",
                    choices=["auto", "never", "always"],
                    help="shard the lane axis over local devices")
    ap.add_argument("--block-events", type=int, default=0,
                    help="kernel backends: events per megakernel "
                         "invocation (0/1 = per-event replay); execution "
                         "knob only, never changes results")
    ap.add_argument("--resume", action="store_true",
                    help="checkpoint every replay under "
                         "STORE/checkpoints and resume a killed sweep "
                         "bit-identically (sugar for --checkpoint-dir "
                         "STORE/checkpoints)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="snapshot the scan carry here at event-block "
                         "boundaries; a rerun resumes from the last "
                         "checkpoint")
    ap.add_argument("--checkpoint-every", type=int, default=2048,
                    help="events between checkpoint snapshots")
    ap.add_argument("--hosts", type=int, default=0,
                    help="launch N worker processes, each running a "
                         "1/N slice of the (suite, pred, policy, "
                         "consolidation) grid against the shared store "
                         "(journal-merged; the final records equal a "
                         "single-process run)")
    ap.add_argument("--host-index", type=int, default=None,
                    help="run only this host's grid slice (normally set "
                         "by --hosts, or via REPRO_HOST_INDEX)")
    ap.add_argument("--host-count", type=int, default=None,
                    help="total hosts sharding the grid (with "
                         "--host-index, or via REPRO_HOST_COUNT)")
    args = ap.parse_args(argv)

    if args.hosts and args.hosts > 1:
        # process-per-host launcher: re-exec this CLI once per slice with
        # the slice pinned via environment, then let the store's
        # journal+lock merging produce the single combined record set
        if args.no_store:
            raise SystemExit("--hosts needs a store to merge results into")
        if _platform() == "tpu":
            raise SystemExit(
                "--hosts starts one process per slice, and a TPU chip "
                "belongs to one process: drop --hosts and let one process "
                "drive every local chip (--shard auto shards the lanes "
                "over them)")
        base, skip = [], False
        for a in (argv if argv is not None else sys.argv[1:]):
            if skip:
                skip = False
                continue
            if a == "--hosts":
                skip = True
                continue
            if a.startswith("--hosts="):
                continue
            base.append(a)
        procs = []
        for i in range(args.hosts):
            env = dict(os.environ, REPRO_HOST_INDEX=str(i),
                       REPRO_HOST_COUNT=str(args.hosts))
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "repro", "sweep"] + base, env=env))
        rcs = [p.wait() for p in procs]
        if any(rcs):
            raise SystemExit(f"worker processes failed: rc={rcs}")
        # fall through host-free: every group is now cached, so the parent
        # re-runs the grid as pure store reads and prints the merged
        # summary (workers already honored --force on their slices)
        args = ap.parse_args(base)
        args.force = False

    host_index = args.host_index if args.host_index is not None else \
        int(os.environ.get("REPRO_HOST_INDEX", "0"))
    host_count = args.host_count if args.host_count is not None else \
        (int(os.environ["REPRO_HOST_COUNT"])
         if "REPRO_HOST_COUNT" in os.environ else None)

    policies = tuple(SCAN_POLICIES) if args.policies == "all" else \
        tuple(args.policies.split(","))
    suites = tuple(
        SuiteSpec(fam, args.n_instances, args.n_items,
                  args.suite_seed if args.suite_seed is not None
                  else SUITE_DEFAULT_SEED[fam], trace_root=args.trace_root)
        for fam in args.suites)
    spec = SweepSpec(
        suites=suites, policies=policies,
        predictions=tuple(_pred(t) for t in args.preds),
        seeds=tuple(int(s) for s in args.seeds.split(",")),
        max_bins=args.max_bins, max_bins_cap=args.max_bins_cap,
        consolidations=tuple(ConsolidationSpec.parse(t)
                             for t in args.consolidate))

    store = None if args.no_store else SweepStore(args.store)
    ckpt_dir = args.checkpoint_dir
    if args.resume and ckpt_dir is None:
        ckpt_dir = os.path.join(args.store, "checkpoints")
    who = f" host {host_index}/{host_count}" if host_count else ""
    print(f"# sweep {spec.spec_hash()}{who} -> "
          f"{store.path(spec) if store else '(not stored)'}")
    records = run_sweep(spec, store=store, force=args.force,
                        progress=lambda m: print(f"# {m}", flush=True),
                        backend=args.backend, shard=args.shard,
                        block_events=args.block_events,
                        checkpoint_dir=ckpt_dir,
                        checkpoint_every=args.checkpoint_every,
                        host_index=host_index, host_count=host_count)

    print(f"{'policy':<18} {'pred':<14} {'n':>4} {'mean':>8} {'median':>8} "
          f"{'q1':>8} {'q3':>8}")
    for (policy, pred), st in summarize_sweep(records).items():
        print(f"{policy:<18} {pred:<14} {st.n:>4} {st.mean:>8.4f} "
              f"{st.median:>8.4f} {st.q1:>8.4f} {st.q3:>8.4f}")


if __name__ == "__main__":
    from ..api._migration import warn_legacy
    warn_legacy("python -m repro.sweep", "python -m repro sweep")
    main(prog="python -m repro.sweep")
