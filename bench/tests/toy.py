"""Toy-size versions of the benchmark's configurations, for CPU tests.

The engine geometry, the drive count and the LBA space shrink; the
traffic, the platform costs and the model do not. ``CASES`` holds every
configuration under ``bench/configs/`` with its traffic: the cells of
BENCHMARK.json, and the 16-drive array sharded over chips, which waits
in Open questions (PERF.md) for a run on four chips.
"""
from __future__ import annotations

import dataclasses

from bench import harness

TOY_ENGINE = {"num_sqs": 8, "sq_depth": 64, "fetch_width": 16,
              "num_units": 4, "num_bufs": 64}
TOY_SSD = {"n_instances": 16, "t_max_iops": 4e6}


CASES = {
    "d40m.randread_qd256": ("d40m_local", "randread_qd256_96r", 1),
    "array16.randread_qd256": ("array16_d40m_4chip", "randread_qd256_6r", 4),
}


def toy_cell(name: str) -> harness.Cell:
    cell = harness.make_cell(name, *CASES[name])
    cfg = dict(cell.config)
    cfg["engine"] = {**cfg["engine"], **TOY_ENGINE}
    cfg["ssd"] = {**cfg["ssd"], **TOY_SSD}
    cfg["num_blocks"] = 1024
    cfg["block_words"] = 8
    cfg["drives"] = min(cfg["drives"], 4)
    traffic = {**cell.traffic, "io_depth": 8, "rounds_per_call": 4}
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def run_toy(name: str, seed: int = 2**31 + 5, seconds: float = 0.3):
    """One toy run on the first CPU device; returns the result dict."""
    import jax

    return harness.run_cell(
        toy_cell(name), seed, seconds, trace=False,
        t_process=0.0, devices=jax.devices()[:1],
    )
