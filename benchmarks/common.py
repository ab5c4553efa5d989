"""Shared benchmark machinery.

Every paper figure gets one function returning rows
(name, us_per_call, derived) where ``derived`` is the paper's metric - the
mean performance ratio over the instance suite (usage time / Eq.(1) lower
bound).  Scale knobs: BENCH_INSTANCES (default 12), BENCH_ITEMS (default
2500), BENCH_REPEATS (default 1), read at each call (``scale``), so a run or
a test sets them before its first figure - the paper uses 28 Azure
instances; raise the knobs to reproduce at full scale.  If the real Azure
trace is present under data/azure/, it is used instead of the synthetic
family.

Policies in ``jaxsim.SCAN_POLICIES`` - the score-based Any Fit family AND
the category-structured families (hybrid, RCP/PPE, CBD/CBDT, lifetime
alignment, adaptive) - are driven through the batched sweep runner
(``repro.sweep``): the whole suite - and, for noise sweeps, all seeds -
replays as one lane-batched scan per policy.  The rest (next_fit,
rr_next_fit) run on the host oracle engine.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import (BoxStats, get_algorithm, lognormal_predictions,
                        lower_bound, run, uniform_predictions)
from repro.data import load_azure_csv, make_azure_like_suite, \
    make_huawei_like_suite

def scale() -> Tuple[int, int, int]:
    """(instances, items per instance, prediction seeds) of this run."""
    return (int(os.environ.get("BENCH_INSTANCES", "12")),
            int(os.environ.get("BENCH_ITEMS", "2500")),
            int(os.environ.get("BENCH_REPEATS", "1")))


def repeat_seeds() -> Tuple[int, ...]:
    """The prediction seeds of the noise figures: BENCH_REPEATS of them."""
    return tuple(range(scale()[2]))


@functools.lru_cache()
def _suite_at(suite_name: str, n_instances: int, n_items: int):
    if suite_name == "huawei":
        return tuple(make_huawei_like_suite(n_instances=min(n_instances, 9),
                                            n_items=max(n_items // 2, 500)))
    real = load_azure_csv()
    if real is not None:
        print("# using REAL Azure trace", flush=True)
        return tuple(real)
    return tuple(make_azure_like_suite(n_instances=n_instances,
                                       n_items=n_items))


def _suite(suite_name: str):
    return _suite_at(suite_name, *scale()[:2])


def azure_suite():
    return _suite("azure")


def _jaxsim_policy(name: str, kw: Dict) -> Optional[str]:
    """jaxsim scan-policy string for (registry name, kwargs), or None if
    the combination has no batched lane (next_fit / rr_next_fit and exotic
    kwargs stay on the host oracle).  Thin delegate: the mapping itself is
    ``repro.api.Policy.from_registry`` so the figures cannot drift from
    the sweep path."""
    from repro.api import Policy
    p = Policy.from_registry(name, **kw)
    return None if p is None or not p.scan else p.name


def alg(name: str, **kw):
    f = lambda: get_algorithm(name, **kw)
    f.jaxsim_policy = _jaxsim_policy(name, kw)
    return f


def _workload(suite_name: str):
    """The bench suite wrapped as an api workload (registered by content
    digest, so the facade reuses the packed batch across figure calls)."""
    from repro.api import instances
    return instances(list(_suite(suite_name)), name=f"bench-{suite_name}")


def _evaluate_batched(policy: str, suite: str, sigma: Optional[float],
                      eps: Optional[float], seeds: Sequence[int]
                      ) -> Tuple[List[float], float]:
    """Batched evaluation through the ``repro.api`` facade: one
    ``Experiment`` cell per (policy, setting), per-instance mean ratios
    out of the tidy records."""
    from repro.api import Experiment, Setting
    if sigma is not None:
        setting = Setting.predicted("lognormal", sigma)
    elif eps is not None:
        setting = Setting.predicted("uniform", eps)
    else:
        setting = Setting.clairvoyant()
    wl = _workload(suite)
    exp = Experiment(wl, policies=(policy,), settings=(setting,),
                     seeds=tuple(seeds))
    from repro.sweep.grid import _built_suite
    _built_suite(wl.suite())   # one-time suite prep outside the timing
    #                            (prediction sampling stays inside: it is
    #                            work the cell genuinely re-does per seed)
    t0 = time.time()
    res = exp.run()
    rows = res.rows()
    secs = (time.time() - t0) / max(len(rows), 1)
    by_inst: Dict[str, List[float]] = {}
    for r in rows:
        by_inst.setdefault(r["instance"], []).append(r["ratio"])
    ratios = [float(np.mean(by_inst[i.name])) for i in _suite(suite)]
    return ratios, secs


def evaluate(algorithm_factory, *, suite: str = "azure",
             sigma: Optional[float] = None, eps: Optional[float] = None,
             seeds: Sequence[int] = (0,)) -> Tuple[List[float], float]:
    """Run a factory()-fresh algorithm over the suite.

    Returns (per-instance mean ratios, wall seconds per run)."""
    policy = getattr(algorithm_factory, "jaxsim_policy", None)
    if policy is not None:
        return _evaluate_batched(policy, suite, sigma, eps, seeds)
    insts = _suite(suite)
    ratios = []
    t0 = time.time()
    n_runs = 0
    for inst in insts:
        lb = lower_bound(inst)
        per_seed = []
        for s in seeds:
            pdur = None
            if sigma is not None:
                pdur = lognormal_predictions(inst, sigma, seed=s)
            elif eps is not None:
                pdur = uniform_predictions(inst, eps, seed=s)
            r = run(inst, algorithm_factory(), predicted_durations=pdur)
            per_seed.append(r.ratio(lb))
            n_runs += 1
        ratios.append(float(np.mean(per_seed)))
    return ratios, (time.time() - t0) / max(n_runs, 1)


def row(name: str, secs_per_call: float, derived: float) -> str:
    return f"{name},{secs_per_call*1e6:.0f},{derived:.4f}"


def box_row(name: str, ratios: List[float], secs: float) -> str:
    st = BoxStats.from_ratios(ratios)
    return (f"{name},{secs*1e6:.0f},{st.mean:.4f}  "
            f"# median={st.median:.3f} q1={st.q1:.3f} q3={st.q3:.3f}")
