"""Cells of ``BENCHMARK.json`` cut to a size the CPU replays in seconds."""
import copy
import os

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.resolve(ROOT, name)
    cfg = copy.deepcopy(cell.config)
    if cell.traffic["runner"] == "sweep":
        cfg["machine_types"] = cfg["machine_types"][:3]
        cfg["requests_per_instance"] = min(cfg["requests_per_instance"], 60)
        cfg["sweep"].update(prefix_arrivals=0, max_bins=64)
    else:
        cfg["stream"].update(requests=1500, warm_requests=300, max_bins=128,
                             item_rows=2048)
    cell.config = cfg
    return cell


def run(cell, seed: int = 2 ** 31 + 7, trace: bool = False):
    import io
    import time

    import jax
    return harness.run_cell(cell, seed, 0.1, trace, time.perf_counter(),
                            jax.devices(), ref_workers=0, log=io.StringIO())
