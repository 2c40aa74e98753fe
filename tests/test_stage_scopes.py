"""Every stage of the engine round runs under a ``stage.<name>`` scope.

XLA keeps the scope in each compiled instruction's ``op_name``; the
benchmark maps a device trace's operations to stages through it
(``bench/stages.py``). For each runner maker, the compiled runner of a
toy configuration must carry every scope that configuration exercises
and no other, and nearly every instruction of the scan's body must map
to a stage.
"""
import functools
import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from bench import stages
from repro.core import engine
from repro.core.types import (
    CacheConfig,
    EngineConfig,
    FabricConfig,
    PlatformModel,
    SSDConfig,
    WorkloadConfig,
)

SSD = SSDConfig(num_blocks=1024)
PLAT = PlatformModel()
WL = WorkloadConfig(io_depth=16, read_frac=0.8)
SMALL = dict(num_sqs=8, sq_depth=64, fetch_width=16, num_units=4,
             num_bufs=64, emulate_data=True)
ROUNDS = 8
DRIVES = 2

LOCAL = {"fetch", "lock", "timing", "datapath", "flash", "cq", "account",
         "data_read", "data_write", "resubmit"}
CONFIGS = {
    "local": (EngineConfig(**SMALL), LOCAL),
    "remote_cached": (
        EngineConfig(
            fabric=FabricConfig(
                remote=True, tx_bytes_per_us=10_000.0,
                rx_bytes_per_us=10_000.0, rtt_us=2.0,
                switch_bytes_per_us=20_000.0, switch_fanin=4,
            ),
            cache=CacheConfig(enabled=True, num_sets=8, ways=2, chase=2),
            **SMALL,
        ),
        LOCAL | {"fabric_tx", "fabric_rx", "cache"},
    ),
    "sanitized": (EngineConfig(sanitize=True, **SMALL), LOCAL | {"sanitize"}),
}
MAKERS = ("make_runner", "make_array_runner", "make_sharded_array_runner")
# Instructions that move no data of their own: the body's parameter,
# its tuple plumbing and constants.
PLUMBING = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast"}


@functools.cache
def compiled_hlo(maker: str, config: str) -> str:
    cfg = CONFIGS[config][0]
    if maker == "make_runner":
        call = engine.make_runner(cfg, SSD, WL, PLAT, ROUNDS)
        state = engine.init_state(cfg, SSD, WL)
    else:
        state = engine.init_array_state(cfg, SSD, WL, DRIVES)
        if maker == "make_array_runner":
            call = engine.make_array_runner(cfg, SSD, WL, PLAT, ROUNDS)
        else:
            mesh = Mesh(np.asarray(jax.devices()[:1]), ("dev",))
            call = engine.make_sharded_array_runner(
                cfg, SSD, WL, PLAT, ROUNDS, mesh=mesh
            )
    return call.lower(state).compile().as_text()


def scan_body(hlo: str) -> dict[str, str]:
    """{instruction: opcode} of the largest while body: the round."""
    bodies = {}
    for name in re.findall(r"\bbody=%?([^\s,}]+)", hlo):
        m = re.search(r"^%?" + re.escape(name) + r" [^\n]*\{\n(.*?)^\}",
                      hlo, re.M | re.S)
        bodies[name] = dict(re.findall(
            r"^\s+(?:ROOT\s+)?%?([^\s=]+) = .*?\s([a-z][a-z0-9-]*)\(",
            m.group(1), re.M))
    return max(bodies.values(), key=len)


# The sharded runner has no checkify path, so no sanitized case.
CASES = [(m, c) for m in MAKERS for c in sorted(CONFIGS)
         if (m, c) != ("make_sharded_array_runner", "sanitized")]


@pytest.mark.parametrize("maker,config", CASES)
def test_compiled_runner_carries_every_stage_scope(maker, config):
    found = set(stages.op_scopes(compiled_hlo(maker, config)).values())
    assert found == CONFIGS[config][1]


@pytest.mark.parametrize("maker", MAKERS)
def test_scan_body_instructions_map_to_stages(maker):
    hlo = compiled_hlo(maker, "local")
    scopes = stages.op_scopes(hlo)
    work = [n for n, op in scan_body(hlo).items() if op not in PLUMBING]
    mapped = [n for n in work if n in scopes]
    assert len(work) > 50
    assert len(mapped) >= 0.9 * len(work), sorted(set(work) - set(mapped))


def test_runner_module_is_named_in_the_hlo():
    hlo = compiled_hlo("make_runner", "local")
    assert stages.module_name(hlo) == "jit__run"
