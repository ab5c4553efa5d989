"""The plain reference: a sequential event-driven replay of one DVBP lane.

A copy of the program's host oracle (``core/engine.py``, ``core/bins.py``
and the policies of ``core/algorithms/`` that the cells run), kept here so
that the yardstick cannot move with the program, and stripped to what the
cells need: ``best_fit_linf``, ``hybrid`` and ``adaptive``.  It imports
nothing of the program and nothing of JAX, so it runs in worker processes
while the parent holds the chip.

``dtype`` is the precision of every stored quantity and every sum: loads,
sizes, times, aggregates, errors and the usage total.  ``float64`` is the
reference; ``bfloat16`` is the control (the next precision below the
float32 the program computes in), which the comparison has to refuse.
"""
from __future__ import annotations

import heapq
import math

import numpy as np

EPS = 1e-9          # feasibility tolerance: exact fits are accepted


def _dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


class Pool:
    """Open-bin state over absolute bin indices (a closed index is never
    reused); open bins are kept in opening order."""

    def __init__(self, d: int, dt):
        self.dt = dt
        self.cap = 64
        self.used = np.zeros((self.cap, d), dt)
        self.n_active = np.zeros(self.cap, np.int64)
        self.access_seq = np.full(self.cap, -1, np.int64)
        self.indicated_close = np.full(self.cap, -np.inf)
        self.tag = np.full(self.cap, -1, np.int64)
        self.n_bins = 0
        self.seq = 0
        self.open_list = []

    def _grow(self):
        for name, fill in (("used", 0), ("n_active", 0), ("access_seq", -1),
                           ("indicated_close", -np.inf), ("tag", -1)):
            a = getattr(self, name)
            new = np.full((2 * self.cap,) + a.shape[1:], fill, a.dtype)
            new[:self.cap] = a
            setattr(self, name, new)
        self.cap *= 2

    def open_bin(self) -> int:
        if self.n_bins == self.cap:
            self._grow()
        idx = self.n_bins
        self.n_bins += 1
        self.used[idx] = 0
        self.n_active[idx] = 0
        self.tag[idx] = -1
        self.open_list.append(idx)
        return idx

    def close_bin(self, idx: int):
        self.open_list.remove(idx)

    def place(self, idx: int, size, pdep, now: float):
        self.used[idx] = self.used[idx] + size
        self.n_active[idx] += 1
        self.access_seq[idx] = self.seq
        self.seq += 1
        if pdep is not None:
            self.indicated_close[idx] = max(self.indicated_close[idx], pdep,
                                            now)

    def remove(self, idx: int, size):
        self.used[idx] = self.used[idx] - size
        self.n_active[idx] -= 1
        if self.n_active[idx] == 0:
            self.used[idx] = 0

    def open_indices(self) -> np.ndarray:
        return np.asarray(self.open_list, np.int64)

    def fits_mask(self, idx: np.ndarray, size) -> np.ndarray:
        if len(idx) == 0:
            return np.zeros(0, bool)
        rem = (1.0 - self.used[idx]).astype(self.dt)
        return np.all(size <= rem + EPS, axis=1)

    def feasible(self, size) -> np.ndarray:
        idx = self.open_indices()
        return idx[self.fits_mask(idx, size)]

    def effective_close(self, idx: np.ndarray, now: float) -> np.ndarray:
        return np.maximum(self.indicated_close[idx], now)


# ---------------------------------------------------------------- policies

class BestFitLinf:
    """Least l_inf leftover after placement among feasible open bins."""

    requires_predictions = False

    def bind(self, pool, lane):
        self.pool = pool

    def select(self, i, size, now, pdep) -> int:
        feas = self.pool.feasible(size)
        if not len(feas):
            return -1
        rem = ((1.0 - self.pool.used[feas]).astype(self.pool.dt) -
               size).astype(self.pool.dt)
        return int(feas[np.argmin(rem.max(axis=1))])

    def placed(self, i, size, idx, opened):
        pass

    def departed(self, i, size, idx):
        pass


def _dur_exponent(dur: float) -> int:
    """j with dur in [2^(j-1), 2^j), exact via frexp."""
    return int(np.frexp(max(dur, 1e-12))[1])


class Hybrid:
    """Azar & Vainstein's Hybrid with the l_inf adaptation: categories by
    (duration class, arrival window); an item joins the general pool of
    its class while its category's aggregate stays under 1/(2 sqrt(i))."""

    requires_predictions = True

    def bind(self, pool, lane):
        self.pool = pool
        dur = lane["departures"] - lane["arrivals"]
        self.z = _dur_exponent(float(dur.min())) if len(dur) else 0
        self.tag_ids = {}
        self.agg = {}
        self.state = {}

    def _tag(self, key) -> int:
        return self.tag_ids.setdefault(key, len(self.tag_ids))

    def _first_fit(self, size, tag) -> int:
        idx = self.pool.open_indices()
        same = idx[self.pool.tag[idx] == tag]
        feas = same[self.pool.fits_mask(same, size)]
        return int(feas[0]) if len(feas) else -1

    def select(self, i, size, now, pdep) -> int:
        dt = self.pool.dt
        j = _dur_exponent(pdep - now)
        ci = max(j - self.z + 1, 1)
        key = (ci, int(math.floor(now / 2.0 ** j)))
        agg = self.agg.get(key)
        after = size if agg is None else (agg + size).astype(dt)
        thr = dt.type(1.0 / (2.0 * math.sqrt(ci)))
        if float(after.max()) <= float(thr) + EPS:
            self.dest = ("G", key)
            return self._first_fit(size, self._tag(("G",)))
        self.dest = ("C", key)
        return self._first_fit(size, self._tag(("C", key)))

    def placed(self, i, size, idx, opened):
        kind, key = self.dest
        if opened:
            self.pool.tag[idx] = self._tag(("G",) if kind == "G"
                                           else ("C", key))
        if kind == "G":
            prev = self.agg.get(key, np.zeros(len(size), self.pool.dt))
            self.agg[key] = (prev + size).astype(self.pool.dt)
        self.state[i] = (key, kind == "G")

    def departed(self, i, size, idx):
        key, general = self.state.pop(i)
        if general:
            self.agg[key] = np.maximum(
                (self.agg[key] - size).astype(self.pool.dt), 0)


class Adaptive:
    """Switch on the running max multiplicative prediction error over
    departed items: < low -> prioritized NRT, < high -> Greedy, else First
    Fit."""

    requires_predictions = True

    def __init__(self, low: float = 2.0, high: float = 16.0):
        self.low, self.high = low, high

    def bind(self, pool, lane):
        self.pool = pool
        self.lane = lane
        self.err = 1.0
        self.pdur = np.zeros(max(len(lane["arrivals"]), 1))

    def select(self, i, size, now, pdep) -> int:
        self.pdur[i] = max(pdep - now, 1e-12)
        feas = self.pool.feasible(size)
        if not len(feas):
            return -1
        if self.err < self.low:                       # prioritized NRT
            gap = self.pool.effective_close(feas, now) - pdep
            ok = gap >= 0
            if ok.any():
                return int(feas[ok][np.argmin(gap[ok])])
            return int(feas[np.argmax(gap)])
        if self.err < self.high:                      # Greedy
            return int(feas[np.argmax(self.pool.effective_close(feas,
                                                                now))])
        return int(feas[0])                           # First Fit

    def placed(self, i, size, idx, opened):
        pass

    def departed(self, i, size, idx):
        dt = self.pool.dt
        lane = self.lane
        rdur = float(dt.type(max(lane["departures"][i] - lane["arrivals"][i],
                                 1e-12)))
        pdur = float(dt.type(max(self.pdur[i], 1e-12)))
        e = float(dt.type(max(rdur / pdur, pdur / rdur)))
        self.err = max(self.err, e)


POLICIES = {"best_fit_linf": BestFitLinf, "hybrid": Hybrid,
            "adaptive": Adaptive}


# ------------------------------------------------------------------ engine

def replay(policy: str, lane: dict, pdur=None, dtype: str = "float64"):
    """Replay one lane under ``policy``.

    ``lane``: {"sizes" (n, d), "arrivals" (n,), "departures" (n,)}, sorted
    by arrival.  ``pdur``: predicted durations, or None for the real ones.
    Departures at time t go before arrivals at t (half-open intervals);
    equal-time departures in item order.  Returns (usage_time,
    n_bins_opened, peak_open_bins)."""
    dt = _dtype(dtype)
    low = (lambda a: np.asarray(a, np.float64).astype(dt).astype(np.float64))
    sizes = np.asarray(lane["sizes"]).astype(dt)
    arrivals = low(lane["arrivals"])
    departures = low(lane["departures"])
    pdeps = departures if pdur is None else low(arrivals + low(pdur))
    lane = {"arrivals": arrivals, "departures": departures}
    algo = POLICIES[policy]()
    reveal = algo.requires_predictions or pdur is not None
    pool = Pool(sizes.shape[1], dt)
    algo.bind(pool, lane)
    n = len(arrivals)
    opened_at = {}
    usage = dt.type(0)
    peak = 0
    heap = []
    i = 0
    while i < n or heap:
        next_arr = arrivals[i] if i < n else np.inf
        if heap and heap[0][0] <= next_arr:
            t, item, idx = heapq.heappop(heap)
            pool.remove(idx, sizes[item])
            algo.departed(item, sizes[item], idx)
            if pool.n_active[idx] == 0:
                usage = dt.type(usage + dt.type(t - opened_at.pop(idx)))
                pool.close_bin(idx)
            continue
        now = float(arrivals[i])
        pdep = float(pdeps[i]) if reveal else None
        idx = algo.select(i, sizes[i], now, pdep)
        opened = idx < 0
        if opened:
            idx = pool.open_bin()
            opened_at[idx] = now
        pool.place(idx, sizes[i], pdep, now)
        algo.placed(i, sizes[i], idx, opened)
        heapq.heappush(heap, (float(departures[i]), i, idx))
        peak = max(peak, len(pool.open_list))
        i += 1
    return float(usage), int(pool.n_bins), int(peak)


def replay_task(args):
    """``replay`` for a process pool: args = (policy, lane, pdur, dtype)."""
    return replay(*args)
