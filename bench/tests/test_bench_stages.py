"""Self time per stage (``bench/stages.py``) and the epoch's valid share.

The traces are built in the profiler's own format, as in
``test_bench_trace.py``, with intervals chosen so that each stage's self
time is worked out by hand below.
"""
import gzip

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import harness, stages, trace
from bench.stage_trace import cut_trace
from bench.tests import test_bench_trace as recorded
from bench.tests.toy import toy_cell

# A compiled runner in HLO text, cut to what the map reads: an op_name
# naming the stage (the sort, under vmap), a fusion that names none
# itself while its fused instructions do, the reduce-window steps of a
# cumulative sum that carry no scope path and take their operand's
# stage, and the loop itself, its counter and a copy of the carried
# state, which name no stage.
HLO = """\
HloModule jit__run, is_scheduled=true

%fused_computation (param_0: s32[16]) -> s32[16] {
  %param_0 = s32[16]{0} parameter(0)
  %add.1 = s32[16]{0} add(%param_0, %param_0), metadata={op_name="jit(_run)/while/body/stage.fetch/add"}
  ROOT %mul.2 = s32[16]{0} multiply(%add.1, %add.1), metadata={op_name="jit(_run)/while/body/stage.fetch/mul"}
}

%wrapped_computation (param_1: s32[16]) -> s32[16] {
  %param_1 = s32[16]{0} parameter(0)
  ROOT %reduce-window.11 = s32[16]{0} reduce-window(%param_1), metadata={op_name="reduce_window_sum"}
}

%region_0 (arg_tuple: (s32[], s32[16])) -> (s32[], s32[16]) {
  %arg_tuple = (s32[], s32[16]{0}) parameter(0)
  %get-tuple-element.3 = s32[16]{0} get-tuple-element(%arg_tuple), index=1
  %get-tuple-element.4 = s32[] get-tuple-element(%arg_tuple), index=0
  %copy.5 = s32[16]{0} copy(%get-tuple-element.3)
  %fusion.6 = s32[16]{0} fusion(%copy.5), kind=kLoop, calls=%fused_computation
  %sort.7 = s32[16]{0} sort(%fusion.6), dimensions={0}, metadata={op_name="jit(_run)/vmap()/while/body/stage.lock/jit(sort)/sort"}
  %wrapped_reduce-window.8 = s32[16]{0} fusion(%sort.7), kind=kLoop, calls=%wrapped_computation
  %reduce-window.9 = s32[16]{0} reduce-window(%wrapped_reduce-window.8), metadata={op_name="reduce_window_sum"}
  %wrapped_add = s32[] add(%get-tuple-element.4, %get-tuple-element.4), metadata={op_name="jit(_run)/while/body/add"}
  ROOT %tuple.10 = (s32[], s32[16]{0}) tuple(%wrapped_add, %reduce-window.9)
}

ENTRY %main (p: s32[16]) -> s32[16] {
  %p = s32[16]{0} parameter(0)
  %fusion.12 = s32[16]{0} fusion(%p), kind=kLoop, calls=%fused_computation
  %tuple.13 = (s32[], s32[16]{0}) tuple(%fusion.12, %fusion.12)
  %while.14 = (s32[], s32[16]{0}) while(%tuple.13), condition=%region_0, body=%region_0, metadata={op_name="jit(_run)/while"}
  ROOT %get-tuple-element.15 = s32[16]{0} get-tuple-element(%while.14), index=1
}
"""


def test_op_scopes_from_hlo_text():
    assert stages.module_name(HLO) == "jit__run"
    scopes = stages.op_scopes(HLO)
    assert scopes["fusion.6"] == "fetch"          # its fused instructions
    assert scopes["sort.7"] == "lock"             # its own op_name
    assert scopes["wrapped_reduce-window.8"] == "lock"   # its operand's
    assert scopes["reduce-window.9"] == "lock"
    for unscoped in ("copy.5", "wrapped_add", "arg_tuple", "while.14"):
        assert unscoped not in scopes
    assert stages.scope_of("a/stage.x/b/stage.data_read/c") == "data_read"
    assert stages.scope_of("jit(_run)/while/body/add") is None


def test_op_key_reads_instruction_names():
    assert stages.op_key("fusion.468") == "fusion.468"
    assert stages.op_key("%fusion.468 = f32[8]{0} fusion(%p)") == "fusion.468"


# Times in microseconds. Two calls of the runner, a ``mark`` program
# between them whose ``fusion.1`` shares a name with the runner's, and
# the tail of an earlier runner call that reaches into the window.
HOST = [("bench.call", 100, 400), ("bench.call", 450, 750),
        ("bench.wait", 120, 400), ("bench.wait", 470, 750)]
MODULES = [("jit__run(7)", 80, 104), ("jit__run(7)", 105, 395),
           ("jit__lambda(9)", 396, 399), ("jit__run(7)", 455, 745)]
OPS = [
    ("fusion.4", 90, 104),     # clipped at 100: data_write 4
    ("while.9", 105, 395),     # self 5 + 10 + 5 = 20, unscoped
    ("fusion.1", 110, 200),    # fetch 90
    ("sort.2", 200, 300),      # lock 100
    ("copy.3", 300, 320),      # unscoped 20
    ("fusion.4", 330, 390),    # data_write 60
    ("fusion.1", 396, 399),    # the mark program's: unscoped 3
    ("while.9", 455, 745),     # self 5 + 5 = 10, unscoped
    ("fusion.1", 460, 560),    # fetch 100
    ("sort.2", 560, 600),      # lock 40
    ("fusion.4", 600, 740),    # data_write 140
]
SCOPES = {"fusion.1": "fetch", "sort.2": "lock", "fusion.4": "data_write"}
EXPECTED_US = {"fetch": 190, "lock": 140, "data_write": 204,
               "unscoped": 20 + 20 + 10 + 3}
BUSY_US = 4 + 290 + 3 + 290


@pytest.fixture(scope="module")
def profile():
    names = ["jit__run(7)", "jit__lambda(9)", "while.9", "fusion.1",
             "sort.2", "copy.3", "fusion.4"]
    text = recorded._plane(1, "/host:CPU", [("python", HOST)],
                           ["bench.call", "bench.wait"])
    text += recorded._plane(2, "/device:TPU:0",
                            [("XLA Modules", MODULES), ("XLA Ops", OPS)],
                            names)
    return ProfileData.from_text_proto(text)


def test_self_time_per_stage_by_hand(profile):
    got = stages.self_times(profile, SCOPES, "jit__run")
    assert got == {k: pytest.approx(v / 1e6) for k, v in EXPECTED_US.items()}
    assert sum(EXPECTED_US.values()) == BUSY_US
    s = trace.reduce(profile)
    assert s.busy_s == [pytest.approx(BUSY_US / 1e6)]
    assert sum(got.values()) == pytest.approx(s.busy_mean_s)


def test_overlapping_ops_go_to_the_later_one():
    got = dict(stages.self_intervals(
        [(0, 10, "a"), (5, 15, "b"), (5, 8, "c"), (20, 30, "d")]))
    assert got == {"a": 5, "c": 3, "b": 7, "d": 10}


def test_recorded_fixture_reads_as_before_and_partitions_busy():
    # The fixture of test_bench_trace.py: round time, idle share and the
    # breakdown read what they read before the stages were named, and the
    # stage self times (a map of two of its three ops) sum to its busy.
    text = recorded._plane(1, "/host:CPU", [("python", recorded.HOST)],
                           ["bench.call", "bench.wait"])
    text += recorded._plane(2, "/device:TPU:0",
                            [("XLA Modules", [("jit_run", 0, 2000)]),
                             ("XLA Ops", recorded.OPS)],
                            ["jit_run", "fusion.1", "sort.2", "scatter.3"])
    prof = ProfileData.from_text_proto(text)
    s = trace.reduce(prof)
    run = harness.Run(setup_s=1.0, window_s=1.0, retired=1.0,
                      rounds_per_call=recorded.ROUNDS_PER_CALL,
                      peak_bytes=1, trace=s)
    assert harness.metric_reader("round_device_ms")(run) == pytest.approx(
        710 / 1e3 / 72)
    assert harness.metric_reader("device_idle_share")(run) == pytest.approx(
        100 * (1 - 710 / 1000))
    assert dict(s.device_ops) == {"fusion.1": pytest.approx(510e-6),
                                  "sort.2": pytest.approx(160e-6),
                                  "scatter.3": pytest.approx(90e-6)}
    got = stages.self_times(prof, {"fusion.1": "fetch", "sort.2": "lock"},
                            "jit_run")
    assert got == {"fetch": pytest.approx(460e-6),
                   "lock": pytest.approx(160e-6),
                   "unscoped": pytest.approx(90e-6)}


def test_cut_trace_keeps_what_the_reductions_read(profile):
    cut = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            cut_trace(profile)))
    assert trace.reduce(cut) == trace.reduce(profile)
    assert stages.self_times(cut, SCOPES, "jit__run") == stages.self_times(
        profile, SCOPES, "jit__run")


def test_epoch_valid_share_from_a_toy_runs_counters():
    # The program's fetched counter over the calls, against the rows the
    # rounds processed; the plain reference counts the same requests.
    from bench.data import drive_keys
    from bench.reference import Reference, model_from

    cell = toy_cell("d40m.randread_qd256")
    seed, calls = 2**31 + 11, 3
    prog = harness.program(cell)
    state = harness.build_state(cell, prog, seed, jax.devices()[:1])
    call = harness.make_call(cell, prog, jax.devices()[:1])
    before = float(np.sum(state.metrics.fetched))
    for _ in range(calls):
        state = call(state)
    fetched = float(np.sum(state.metrics.fetched)) - before
    rounds = calls * cell.traffic["rounds_per_call"]
    share = stages.epoch_valid_share(cell, fetched, rounds)

    ref = Reference(model_from(cell.config, cell.traffic),
                    drive_keys(seed, cell.config["drives"])[0])
    ref.run(rounds)
    e = cell.config["engine"]
    rows = rounds * e["num_sqs"] * e["fetch_width"]
    assert 0 < share <= 100
    assert share == pytest.approx(100 * float(np.sum(ref.completed)) / rows)


DATA = harness.BENCH / "tests" / "data"
LOCAL_STAGES = {"fetch", "lock", "timing", "datapath", "flash", "cq",
                "account", "data_read", "data_write", "resubmit"}


@pytest.fixture(scope="module")
def v5e():
    """A trace of the d40m.randread_qd256 cell's runner recorded on a TPU
    v5e by ``bench/stage_trace.py --rounds 2 --fixture``, cut to what the
    reductions read, with the op map of the same compiled runner and
    what the script printed then."""
    import json

    profile = trace.load(DATA / "v5e_randread_qd256.xplane.pb.gz")
    rec = json.loads(gzip.decompress(
        (DATA / "v5e_randread_qd256.op_scopes.json.gz").read_bytes()))
    return profile, rec


def test_v5e_trace_layout(v5e):
    # One TPU plane; the ops carry their instruction's HLO text as their
    # name, and run inside module events named "<module>(<fingerprint>)".
    profile, rec = v5e
    plane = next(p for p in profile.planes
                 if p.name.startswith(trace.DEVICE_PREFIX))
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    assert set(lines) == {trace.OPS_LINE, stages.MODULES_LINE}
    assert {e.name.split("(")[0] for e in lines[stages.MODULES_LINE]} == {
        rec["module"], "jit__lambda"}
    ops = lines[trace.OPS_LINE]
    assert all(e.name.startswith("%") and " = " in e.name for e in ops)
    loop = max(ops, key=lambda e: e.duration_ns)
    assert stages.op_key(loop.name).startswith("while.")
    assert stages.op_key(loop.name) not in rec["scopes"]


def test_v5e_trace_reads_by_stage(v5e):
    profile, rec = v5e
    s = trace.reduce(profile)
    assert s.calls == rec["result"]["calls"]
    rounds = s.calls * rec["rounds_per_call"]
    assert s.busy_mean_s / rounds * 1e3 == pytest.approx(
        rec["result"]["round_device_ms"])
    got = stages.self_times(profile, rec["scopes"], rec["module"])
    assert set(got) == LOCAL_STAGES | {stages.UNSCOPED}
    assert sum(got.values()) == pytest.approx(s.busy_mean_s, rel=1e-9)
    assert got[stages.UNSCOPED] < 0.05 * s.busy_mean_s
    assert {k: v / rounds * 1e3 for k, v in got.items()} == pytest.approx(
        rec["result"]["stage_ms"])
