"""Stream cells: one unit of work is one whole request stream replayed
through the program's entry point, ``stream.replay_stream``, from an
in-memory source (``stream.InstanceSource``) to its record (usage time,
bins opened).

Streams run back to back; stream j is a fresh instance of the
configuration's stream machine type drawn from (seed, j), generated inside
the call (about a tenth of a second for 200k requests, a fraction of a
percent of the call).  The call passes only what the deployment fixes: the
source, the policy, the cluster's slot pool (``max_bins``) and the
alive-VM row pool (``item_rows``); no execution option.

A traced run cuts its stream: the source stops after the traffic's
``trace_requests`` requests, the replay drains the departures still
pending and returns, and the record is judged against the reference replay
of the requests it was given.  A whole stream's device trace would hold
tens of millions of operations and overflow the profiler's buffers.
"""
from __future__ import annotations

from bench import gen


def _source(raw: dict, cut):
    from repro.core.types import Instance
    from repro.stream import InstanceSource

    class Cut(InstanceSource):
        """The program's in-memory source, stopped after ``cut``
        requests."""

        given = 0

        def records(self):
            for rec in super().records():
                if self.given == cut:
                    return
                self.given += 1
                yield rec

    return Cut(Instance(raw["sizes"], raw["arrivals"], raw["departures"],
                        raw["name"]))


def _replay(state: dict, raw: dict, cut=None):
    from repro.stream import replay_stream
    src = _source(raw, cut)
    res = replay_stream(src, state["policy"], max_bins=state["max_bins"],
                        item_rows=state["item_rows"])
    n = src.given
    return res, {k: v[:n] for k, v in raw.items() if k != "name"}


def lanes(cfg: dict, traffic: dict, seed: int):
    """[(key, instance, None)] of the first stream of ``seed`` (the
    clairvoyant setting: the stream replays real departures)."""
    st = cfg["stream"]
    return [(0, gen.instance(cfg, st["machine_type"], seed, st["requests"],
                             stream=1), None)]


def setup(cfg: dict, traffic: dict, seed: int, trace: bool) -> dict:
    st = cfg["stream"]
    state = {"cfg": cfg, "k": st["machine_type"], "seed": seed,
             "policy": traffic["policy"], "family": traffic["family"],
             "requests": st["requests"], "max_bins": st["max_bins"],
             "item_rows": st["item_rows"], "lanes": {},
             "cut": traffic["trace_requests"] if trace else None}
    # warm-up: a short stream of the same geometry compiles every program
    _replay(state, gen.instance(cfg, state["k"], seed, st["requests"],
                                prefix=st["warm_requests"], stream=0))
    return state


def call(state: dict, j: int) -> dict:
    raw = gen.instance(state["cfg"], state["k"], state["seed"],
                       state["requests"], stream=j + 1)   # as lanes() does
    res, lane = _replay(state, raw, state["cut"])
    state["lanes"][j] = lane
    state["given"] = state.get("given", 0) + len(lane["arrivals"])
    return {"events": 2 * len(lane["arrivals"]), "expected": [j],
            "records": {j: (float(res.usage), int(res.opened))}}


def reference_tasks(state: dict, calls):
    keys = sorted(state["lanes"])
    return keys, [(state["policy"], state["lanes"][j], None, "float64")
                  for j in keys]


def work(state: dict) -> dict:
    """Per call: the mean number of requests a call was given."""
    d = state["cfg"]["machine_types"][state["k"]]["d"]
    n = state.get("given", 0) / max(len(state["lanes"]), 1)
    return {"family": state["family"], "lanes": 1, "arrivals": n,
            "departures": n, "max_bins": state["max_bins"], "d": d,
            "items": state["item_rows"]}


def release(state: dict) -> None:
    pass
