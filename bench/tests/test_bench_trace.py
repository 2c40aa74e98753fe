"""The trace-to-metrics reduction (``bench/trace.py``) and its readers.

The trace here is built in the profiler's own format (an XSpace, from
its text form) with known intervals: three calls of the harness's host
spans, and device operations on the ``XLA Ops`` line of one TPU plane,
two of them overlapping and one reaching past the window, plus a line
the reduction must ignore. The expected busy union, idle share and busy
time per round are worked out by hand below.
"""
import pytest

from bench import harness, trace

ROUNDS_PER_CALL = 24
US = 1_000_000  # picoseconds per microsecond


def _plane(pid, name, lines, names):
    meta = "".join(
        f"event_metadata {{ key: {i} value {{ id: {i} name: \"{n}\" }} }}\n"
        for i, n in enumerate(names, 1)
    )
    body = ""
    for lid, (lname, events) in enumerate(lines, 1):
        evs = "".join(
            f"events {{ metadata_id: {names.index(n) + 1} "
            f"offset_ps: {s * US} duration_ps: {(e - s) * US} }}\n"
            for n, s, e in events
        )
        body += f"lines {{ id: {lid} name: \"{lname}\" timestamp_ns: 0\n{evs}}}\n"
    return f"planes {{ id: {pid} name: \"{name}\"\n{body}{meta}}}\n"


# Times in microseconds. Calls: [100, 400), [450, 750), [800, 1100).
CALLS = [(100, 400), (450, 750), (800, 1100)]
HOST = [("bench.call", s, e) for s, e in CALLS] + [
    ("bench.wait", s + 20, e) for s, e in CALLS]
OPS = [
    ("fusion.1", 90, 200),    # starts before the window: 100..200
    ("sort.2", 150, 260),     # overlaps fusion.1: union 100..260
    ("scatter.3", 300, 390),  # 90
    ("fusion.1", 470, 700),   # 230
    ("fusion.1", 820, 1000),  # 180
    ("sort.2", 1050, 1200),   # clipped at 1100: 50
]
BUSY_US = 160 + 90 + 230 + 180 + 50
WINDOW_US = 1100 - 100


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    text = _plane(1, "/host:CPU", [("python", HOST)],
                  ["bench.call", "bench.wait"])
    text += _plane(2, "/device:TPU:0",
                   [("XLA Modules", [("jit_run", 0, 2000)]),
                    ("XLA Ops", OPS)],
                   ["jit_run", "fusion.1", "sort.2", "scatter.3"])
    return ProfileData.from_text_proto(text)


def test_busy_union_idle_share_and_round_time(profile):
    s = trace.reduce(profile)
    assert s.calls == 3
    assert s.window_s == pytest.approx(WINDOW_US / 1e6)
    assert s.busy_s == [pytest.approx(BUSY_US / 1e6)]
    assert s.idle_share_max == pytest.approx(1 - BUSY_US / WINDOW_US)

    run = harness.Run(setup_s=1.0, window_s=1.0, retired=1.0,
                      rounds_per_call=ROUNDS_PER_CALL, peak_bytes=1,
                      trace=s)
    round_ms = harness.metric_reader("round_device_ms")(run)
    idle = harness.metric_reader("device_idle_share")(run)
    assert round_ms == pytest.approx(BUSY_US / 1e3 / (3 * ROUNDS_PER_CALL))
    assert idle == pytest.approx(100 * (1 - BUSY_US / WINDOW_US))


def test_breakdown_names_ops_and_host_activity(profile):
    s = trace.reduce(profile)
    ops = dict(s.device_ops)
    assert ops["fusion.1"] == pytest.approx((100 + 230 + 180) / 1e6)
    assert ops["sort.2"] == pytest.approx((110 + 50) / 1e6)
    # Idle stretches 260..300 and 1000..1050 fall inside a call's wait
    # span; 390..470 and 700..820 fall between calls (midpoints 430, 760).
    assert s.idle_gaps == [
        ["host_between_calls", pytest.approx(120e-6)],
        ["host_between_calls", pytest.approx(80e-6)],
        ["bench.wait", pytest.approx(50e-6)],
        ["bench.wait", pytest.approx(40e-6)],
    ]


def test_no_call_span_reads_nothing():
    class Empty:
        planes = []

    assert trace.reduce(Empty()) is None
    run = harness.Run(setup_s=1.0, window_s=1.0, retired=1.0,
                      rounds_per_call=1, peak_bytes=1)
    assert harness.metric_reader("round_device_ms")(run) is None
    assert harness.metric_reader("device_idle_share")(run) is None


def test_calls_dispatched_ahead_count_by_their_waits():
    # Three calls sent up front; the trace holds the waits of two, so the
    # window ends with the second wait and the device ran two calls in it.
    from jax.profiler import ProfileData

    host = [("bench.call", 100, 110), ("bench.call", 120, 130),
            ("bench.call", 140, 150), ("bench.wait", 150, 420),
            ("bench.wait", 420, 700)]
    ops = [("fusion.1", 115, 415), ("fusion.1", 420, 695),
           ("fusion.1", 700, 990)]
    text = _plane(1, "/host:CPU", [("python", host)],
                  ["bench.call", "bench.wait"])
    text += _plane(2, "/device:TPU:0", [("XLA Ops", ops)], ["fusion.1"])
    s = trace.reduce(ProfileData.from_text_proto(text))
    assert s.calls == 2
    assert s.window_s == pytest.approx(600e-6)
    assert s.busy_s == [pytest.approx(575e-6)]
