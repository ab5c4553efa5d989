"""Guarded dispatch: retry-with-backoff plus the degradation ladder.

Two layers, composed by ``run_ladder``:

  * ``guarded_call`` retries *transient* device failures (RESOURCE_
    EXHAUSTED / UNAVAILABLE / DEADLINE_EXCEEDED / ABORTED - the status
    markers ``jax.errors.JaxRuntimeError`` carries) on the same plan, with
    deterministic jittered exponential backoff.  Counted as
    ``resilience.retry``.
  * When retries exhaust (or the failure is non-transient but still a
    *device* failure - ``is_degradable``), execution moves DOWN an
    explicit ladder of equivalent plans: blocked megakernel -> per-event
    kernel, sharded -> single-device, kernel backend -> jnp reference.
    Every rung replays the identical decision sequence (the backends are
    bit-identical on fp32-exact instances; tests/test_resilience.py
    asserts usage equality under injected faults), so degrading trades
    throughput, never results.  Each step is counted as
    ``resilience.degrade_<from>_<to>``.

Failures that are neither transient nor degradable (assertion errors,
shape errors, Pallas lowering and compile errors, KeyboardInterrupt)
propagate immediately - the ladder exists for *device* trouble, not for
bugs, so a kernel the chip's compiler refuses never runs silently in jnp.

Backoff sleeps scale with env ``REPRO_RESILIENCE_BACKOFF_SCALE`` (tests
set 0 to run the retry logic without the waiting).
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import time
from typing import Callable, List, Tuple

from .. import obs
from .faults import InjectedFault

# status markers of failures worth retrying on the same plan
TRANSIENT_MARKERS = ("RESOURCE_EXHAUSTED", "DEADLINE_EXCEEDED",
                     "UNAVAILABLE", "ABORTED")
# markers of a program the compiler refused (Mosaic kernel compile, VMEM or
# HBM overflow at compile time): a bug in the program, never device trouble
COMPILE_MARKERS = ("Mosaic", "compile", "vmem", "Scoped allocation")


def is_compile_error(exc: BaseException) -> bool:
    """The compiler refused the program: no rung below may hide that."""
    return any(m in str(exc) for m in COMPILE_MARKERS)


def is_transient(exc: BaseException) -> bool:
    """Worth retrying on the same execution plan."""
    return isinstance(exc, Exception) and not is_compile_error(exc) and \
        any(m in str(exc) for m in TRANSIENT_MARKERS)


def is_degradable(exc: BaseException) -> bool:
    """A device/runtime failure a lower ladder rung can route around: a
    scripted fault, or a ``jax.errors.JaxRuntimeError`` raised while the
    compiled program ran.  Lowering and compile errors are bugs and
    propagate."""
    import jax
    if isinstance(exc, InjectedFault):
        return True
    return isinstance(exc, jax.errors.JaxRuntimeError) and \
        not is_compile_error(exc)


def backoff_delay(site: str, attempt: int, base: float = 0.05,
                  factor: float = 2.0, seed: int = 0) -> float:
    """Exponential backoff with deterministic jitter in [0.5, 1.5) -
    reproducible chaos runs, no synchronized retry herds."""
    h = hashlib.blake2b(f"{seed}:{site}:{attempt}".encode(),
                       digest_size=4).digest()
    jitter = 0.5 + int.from_bytes(h, "big") / 0x100000000
    scale = float(os.environ.get("REPRO_RESILIENCE_BACKOFF_SCALE", "1"))
    return base * (factor ** (attempt - 1)) * jitter * scale


def guarded_call(fn: Callable, *, site: str, retries: int = 2,
                 base_delay: float = 0.05, seed: int = 0):
    """Call ``fn()``; retry transient failures up to ``retries`` times
    with jittered exponential backoff.  Non-transient failures (and the
    last transient one) propagate to the caller - typically a ladder."""
    attempt = 0
    while True:
        try:
            return fn()
        except Exception as e:
            if attempt >= retries or not is_transient(e):
                raise
            attempt += 1
            obs.counter_add("resilience.retry")
            obs.instant("resilience.retry", site=site, attempt=attempt,
                        error=str(e)[:200])
            time.sleep(backoff_delay(site, attempt, base_delay, seed=seed))


# ------------------------------------------------------------- the ladder

@dataclasses.dataclass(frozen=True)
class Rung:
    """One execution plan on the replay degradation ladder."""

    label: str
    backend: str
    block_events: int
    ndev: int


def rung_label(backend: str, block_events: int, ndev: int) -> str:
    if block_events and block_events > 1:
        lab = "blocked"
    elif backend != "jnp":
        lab = "perevent"
    else:
        lab = "jnp"
    return lab + ("_sharded" if ndev > 1 else "")


def replay_rungs(backend: str, block_events: int, ndev: int) -> List[Rung]:
    """The ladder for one replay dispatch, degrading one axis per rung:
    drop the event-blocked megakernel first (keep the kernel), then lane
    sharding, then the kernel backend itself (jnp is the reference twin -
    the floor, never degraded past)."""
    cfgs = [(backend, block_events, ndev)]
    be, T, nd = backend, block_events, ndev
    if T and T > 1:
        T = 0
        cfgs.append((be, T, nd))
    if nd > 1:
        nd = 1
        cfgs.append((be, T, nd))
    if be != "jnp":
        be = "jnp"
        cfgs.append((be, T, nd))
    return [Rung(rung_label(*c), *c) for c in cfgs]


def transition_name(a: Rung, b: Rung) -> Tuple[str, str]:
    """(from, to) labels for the one axis a ladder step degrades."""
    if (a.block_events or 0) != (b.block_events or 0):
        return ("blocked", "perevent")
    if a.ndev != b.ndev:
        return ("sharded", "single")
    return (a.backend, b.backend)


def run_ladder(attempt: Callable[[Rung], object], rungs: List[Rung], *,
               site: str, retries: int = 2, base_delay: float = 0.05):
    """Run ``attempt(rung)`` down the ladder: each rung is retried for
    transient failures (``guarded_call``); a degradable failure moves to
    the next rung with a ``resilience.degrade_<from>_<to>`` counter.
    Returns ``(rung, result)`` for the rung that served.  The last rung's
    failure - or any non-degradable one - propagates."""
    for i, rung in enumerate(rungs):
        try:
            return rung, guarded_call(lambda: attempt(rung), site=site,
                                      retries=retries,
                                      base_delay=base_delay)
        except Exception as e:
            if i + 1 >= len(rungs) or not is_degradable(e):
                raise
            frm, to = transition_name(rung, rungs[i + 1])
            obs.counter_add(f"resilience.degrade_{frm}_{to}")
            obs.instant("resilience.degrade", site=site, frm=frm, to=to,
                        error=str(e)[:200])
    raise AssertionError("unreachable: empty ladder")
