"""Seeded traffic for the benchmark: DVBP instances and predicted durations.

The arithmetic is a copy of the program's generators, kept here so that a
change to the program cannot move the yardstick:

  * ``vm_type_table`` / ``azure_items``: ``data.traces._vm_type_table`` and
    ``data.traces._one_instance`` (log-normal lifetimes, 14-day horizon,
    Zipf VM-type popularity, diurnal arrivals);
  * ``huawei_items``: the per-instance body of
    ``data.traces.make_huawei_like_suite`` (d=2, nine PM capacities);
  * ``lognormal_durations``: ``core.predictions.lognormal_predictions``
    (Pdur = Rdur * exp(N(0, sigma))).

What differs from the program is only what the configuration fixes and
what ``--seed`` draws.  The configuration fixes the fleet: its machine
types and request counts, and, from its ``fleet_seed``, each machine
type's VM-type table, popularity and diurnal phase.  ``--seed`` draws the
requests (VM types, arrivals, lifetimes) and the prediction noise, so
every seed replays the same kind of cluster.  Every size and time is put
on the grid the configuration states (``size_grid``, ``time_grid_s``), on
which float32 sums are exact (see ``PERF.md``).  An instance is a plain
dict of numpy arrays; the runners turn it into the program's input type.
"""
from __future__ import annotations

import numpy as np

DAY = 86400.0
HORIZON = 14 * DAY


def rng_for(seed: int, *keys: int) -> np.random.Generator:
    """One generator per (run seed, stream keys); any whole ``seed``."""
    return np.random.default_rng([int(seed) % (1 << 64)] +
                                 [int(k) for k in keys])


def vm_type_table(rng, n_types: int, d: int, pm_cores: int) -> np.ndarray:
    max_exp = int(np.log2(pm_cores))
    core_exp = rng.integers(0, max_exp, n_types)
    cores = 2.0 ** core_exp
    gb_per_core = rng.choice([1.0, 2.0, 4.0, 8.0], n_types,
                             p=[0.15, 0.35, 0.35, 0.15])
    pm_mem = pm_cores * 4.0
    mem = cores * gb_per_core
    ssd = cores / pm_cores * rng.uniform(0.3, 1.5, n_types)
    nic = cores / pm_cores * rng.uniform(0.2, 1.2, n_types)
    cols = [cores / pm_cores, mem / pm_mem, ssd, nic]
    if d == 5:
        cols.append(cores / pm_cores * rng.uniform(0.0, 1.0, n_types))
    return np.clip(np.stack(cols[:d], axis=1), 1e-4, 1.0)


def azure_items(fleet, rng, n_items: int, d: int, pm_cores: int,
                med_lifetime: float, sigma_lifetime: float):
    """(sizes, arrivals, departures) of one Azure-like instance, sorted by
    arrival, before the grid is applied.  ``fleet`` draws the machine
    type's VM-type table, popularity and phase; ``rng`` the requests."""
    n_types = int(fleet.integers(8, 30))
    table = vm_type_table(fleet, n_types, d, pm_cores)
    pop = 1.0 / np.arange(1, n_types + 1) ** fleet.uniform(0.8, 1.6)
    pop /= pop.sum()
    phase = fleet.uniform(0, 2 * np.pi)
    sizes = table[rng.choice(n_types, n_items, p=pop)]
    proposals = rng.uniform(0, HORIZON, n_items * 2)
    accept = rng.random(n_items * 2) < \
        0.55 + 0.45 * np.sin(2 * np.pi * proposals / DAY + phase)
    arrivals = np.sort(proposals[accept][:n_items])
    if len(arrivals) < n_items:
        extra = rng.uniform(0, HORIZON, n_items - len(arrivals))
        arrivals = np.sort(np.concatenate([arrivals, extra]))
    life = rng.lognormal(np.log(med_lifetime), sigma_lifetime, n_items)
    life = np.clip(life, 30.0, None)
    life = np.minimum(life, np.maximum(HORIZON - arrivals, 60.0))
    life = np.minimum(life, HORIZON - arrivals + 1e-3)
    return sizes, arrivals, arrivals + life


def huawei_items(fleet, rng, n_items: int, cpu_cap: float, mem_cap: float):
    n_types = int(fleet.integers(6, 20))
    cores = 2.0 ** fleet.integers(0, 7, n_types)
    mem = cores * fleet.choice([1.0, 2.0, 4.0], n_types)
    table = np.clip(np.stack([cores / cpu_cap, mem / mem_cap], axis=1),
                    1e-4, 1.0)
    pop = 1.0 / np.arange(1, n_types + 1) ** 1.2
    pop /= pop.sum()
    sizes = table[rng.choice(n_types, n_items, p=pop)]
    arrivals = np.sort(rng.uniform(0, HORIZON, n_items))
    life = np.clip(rng.lognormal(np.log(1800.0), 1.8, n_items), 30.0, None)
    life = np.minimum(life, HORIZON - arrivals + 1e-3)
    return sizes, arrivals, arrivals + life


def on_grid(sizes, arrivals, departures, size_grid: int, time_grid: float):
    """Snap an instance to the configuration's grid: sizes to multiples of
    ``1 / size_grid`` (at least one step, at most 1), times to multiples of
    ``time_grid`` with every lifetime at least one step.  Keeps arrival
    order (stable sort)."""
    sizes = np.clip(np.round(sizes * size_grid), 1, size_grid) / size_grid
    arr = np.round(arrivals / time_grid) * time_grid
    dep = np.maximum(np.round(departures / time_grid) * time_grid,
                     arr + time_grid)
    order = np.argsort(arr, kind="stable")
    return sizes[order], arr[order], dep[order]


def lognormal_durations(rng, durations, sigma: float, time_grid: float):
    """Predicted durations Rdur * exp(N(0, sigma)), on the time grid and at
    least one step long."""
    delta = np.exp(rng.normal(0.0, sigma, len(durations)))
    pdur = np.round(durations * delta / time_grid) * time_grid
    return np.maximum(pdur, time_grid)


def instance(cfg: dict, k: int, seed: int, n_items: int,
             prefix: int = 0, stream: int = 0) -> dict:
    """Machine type ``k`` of the configuration's fleet with ``n_items``
    requests drawn from (``seed``, ``stream``); ``prefix > 0`` keeps its
    first ``prefix`` arrivals.  Returns {"name", "sizes", "arrivals",
    "departures"}."""
    mt = cfg["machine_types"][k]
    fleet = rng_for(cfg["fleet_seed"], k)
    rng = rng_for(seed, k, stream)
    if cfg["family"] == "azure":
        s, a, dp = azure_items(fleet, rng, n_items, mt["d"], mt["pm_cores"],
                               mt["med_lifetime_s"], mt["sigma_lifetime"])
    elif cfg["family"] == "huawei":
        s, a, dp = huawei_items(fleet, rng, n_items, mt["cpu_cap"],
                                mt["mem_cap"])
    else:
        raise ValueError(f"unknown fleet family {cfg['family']!r}")
    s, a, dp = on_grid(s, a, dp, cfg["size_grid"], cfg["time_grid_s"])
    if prefix:
        s, a, dp = s[:prefix], a[:prefix], dp[:prefix]
    return {"name": f"{cfg['name']}_{k:02d}_{stream}", "sizes": s,
            "arrivals": a, "departures": dp}


def requests(cfg: dict, k: int) -> int:
    """Requests of machine type ``k`` over the whole horizon: the
    configuration's per-instance count times the type's load factor."""
    return int(round(cfg["requests_per_instance"] *
                     cfg["machine_types"][k].get("load_factor", 1.0)))


def predictions(inst: dict, setting: dict, seed: int, k: int,
                time_grid: float, stream: int = 0):
    """Predicted durations of one lane: the real ones for
    ``{"kind": "clairvoyant"}``, log-normal noise for
    ``{"kind": "lognormal", "sigma": s}``."""
    dur = inst["departures"] - inst["arrivals"]
    if setting["kind"] == "clairvoyant":
        return dur
    if setting["kind"] == "lognormal":
        return lognormal_durations(rng_for(seed, k, stream, 1), dur,
                                   setting["sigma"], time_grid)
    raise ValueError(f"unknown prediction setting {setting!r}")
