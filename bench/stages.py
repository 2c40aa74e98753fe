"""Device self time of the engine round's stages, from one profiler trace.

The program runs each stage of its round under a named scope
``stage.<name>`` (``core/engine.py``, ``core/device.py``). XLA keeps the
scope in the ``op_name`` metadata of each instruction it compiles, and
the device trace names each operation by its instruction. So:

- ``op_scopes`` maps the instruction names of the runner's compiled HLO
  text to the innermost ``stage.*`` scope of their ``op_name``. A fusion
  whose own ``op_name`` names no stage takes the stage that most of the
  instructions fused into it name.
- ``epoch_valid_share`` is the share of the rows the rounds processed
  that held a request, from the program's own ``Metrics.fetched``.
- ``self_times`` reduces a trace over the same window, devices and
  operations as ``bench/trace.py``. On each device an operation's self
  time is the part of its duration in which no operation that started
  after it (nested inside it on the same line: the scan's ``while``
  holds the whole round) is running. Self times partition the busy
  time. Each goes to the stage of its instruction when it runs inside
  one of the runner module's ``XLA Modules`` events, and to
  ``unscoped`` otherwise: XLA-inserted copies, the loop's own control,
  and the operations of other programs.
"""
from __future__ import annotations

import re
from collections import Counter

from bench.trace import (
    CALL_SPAN, DEVICE_PREFIX, HOST_PLANE, OPS_LINE, WAIT_SPAN, _events,
)

MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"
STAGE = re.compile(r"\bstage\.(\w+)")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,}]+)")
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_REF = re.compile(r"%([^\s,)]+)")


def scope_of(op_name: str) -> str | None:
    """The innermost ``stage.*`` scope named in an ``op_name``."""
    found = STAGE.findall(op_name)
    return found[-1] if found else None


def module_name(hlo_text: str) -> str:
    """The name of the module in compiled HLO text (``HloModule <name>``)."""
    m = _MODULE.match(hlo_text)
    if m is None:
        raise ValueError("not HLO text: no 'HloModule' header")
    return m.group(1)


def _operands(rest: str) -> list[str]:
    """The operand names of an instruction, from the text after its '='."""
    m = _OPCODE.search(rest)
    if m is None:
        return []
    depth, end = 0, len(rest)
    for k in range(m.end() - 1, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[k], 0)
        if depth == 0:
            end = k
            break
    return _REF.findall(rest[m.end():end])


def _parse(hlo_text: str):
    """{computation: [(instruction, own scope, called computation,
    operands)]}, each computation's instructions in the text's order;
    the operands only of instructions without a scope path."""
    comps: dict[str, list] = {}
    current = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c and not line.startswith(" "):
            current = comps.setdefault(c.group(1), [])
            continue
        i = _INSTRUCTION.match(line)
        if i and current is not None:
            op = _OP_NAME.search(line)
            calls = _CALLS.search(line)
            # Only an instruction without a scope path of its own may
            # take its operand's stage (see op_scopes).
            pathless = op is None or "/" not in op.group(1)
            current.append((i.group(1), scope_of(op.group(1)) if op else None,
                            calls.group(1) if calls else None,
                            _operands(line[i.end():]) if pathless else []))
    return comps


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> stage, for every instruction that has one.

    An instruction's stage is the one its own ``op_name`` names; else,
    for a fusion, the one most of its fused instructions name; else,
    where its ``op_name`` holds no scope path at all, the stage of its
    first operand that has one. The last rule covers the parts of one
    lowered primitive that JAX emits without the path (the
    ``reduce-window`` steps of a cumulative sum carry only
    ``op_name="reduce_window_sum"``) and XLA's copies of a stage's
    values. Copies of the loop's carried state, and instructions whose
    path names no stage (the ``while`` itself, its counter), stay
    without one."""
    comps = _parse(hlo_text)
    memo: dict[str, str | None] = {}
    out: dict[str, str] = {}

    def stage(name, own, calls, operands) -> str | None:
        s = own or (fused(calls) if calls else None)
        if s is None:
            s = next((out[o] for o in operands if o in out), None)
        if s is not None:
            out[name] = s
        return s

    def fused(comp: str) -> str | None:
        # The stage most of a called computation's instructions name;
        # a tie goes to the one named last, nearest the root.
        if comp not in memo:
            memo[comp] = None          # a call cycle names nothing
            named = [stage(*ins) for ins in comps.get(comp, [])]
            votes = Counter(s for s in named if s)
            if votes:
                best = max(votes.values())
                memo[comp] = [s for s in named if votes[s] == best][-1]
        return memo[comp]

    for instrs in comps.values():
        for ins in instrs:
            if ins[0] not in out:
                stage(*ins)
    return out


def op_key(event_name: str) -> str:
    """The instruction name of an ``XLA Ops`` event."""
    return event_name.lstrip("%").split(" ", 1)[0].split("=", 1)[0]


def self_intervals(ops):
    """[(start, end, key)] -> [(key, self time)]: each instant goes to the
    latest-started operation running then, so the self times sum to the
    union of the intervals."""
    out = []
    stack: list[list] = []     # [end, key, self time]; innermost on top
    t = 0.0

    def advance(until):
        nonlocal t
        while stack:
            top = stack[-1]
            upto = min(top[0], until)
            if upto > t:
                top[2] += upto - t
                t = upto
            if top[0] > until:
                return
            out.append((top[1], top[2]))
            stack.pop()

    for s, e, key in sorted(ops, key=lambda o: (o[0], -o[1])):
        advance(s)
        t = s
        stack.append([e, key, 0.0])
    advance(float("inf"))
    return out


def op_self_times(profile, module: str) -> dict | None:
    """{(instruction, in the runner module): seconds of device self time,
    the mean over devices} in the window of ``bench.trace.reduce``; None
    where that reads nothing. ``module`` names the runner's program."""
    host, devices = [], []
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            host += [(ev.start_ns, ev.end_ns, ev.name) for ev in _events(plane)
                     if ev.name in (CALL_SPAN, WAIT_SPAN)]
        elif plane.name.startswith(DEVICE_PREFIX):
            ops = [(ev.start_ns, ev.end_ns, ev.name)
                   for ev in _events(plane, OPS_LINE)]
            mods = [(ev.start_ns, ev.end_ns)
                    for ev in _events(plane, MODULES_LINE)
                    if ev.name == module or ev.name.startswith(module + "(")]
            if ops:
                devices.append((ops, mods))
    calls = [s for s, _, n in host if n == CALL_SPAN]
    waits = [e for _, e, n in host if n == WAIT_SPAN]
    if not calls or not waits or not devices:
        return None
    lo, hi = min(calls), max(waits)
    total: Counter = Counter()
    for ops, mods in devices:
        clipped = [(max(s, lo), min(e, hi), (s, name)) for s, e, name in ops
                   if min(e, hi) > max(s, lo)]
        for (start, name), ns in self_intervals(clipped):
            inside = any(ms <= start < me for ms, me in mods)
            total[op_key(name), inside] += ns / 1e9
    return {k: v / len(devices) for k, v in total.items()}


def self_times(profile, scopes: dict[str, str], module: str) -> dict | None:
    """Seconds of device self time per stage (``UNSCOPED`` for the rest),
    the mean over devices; ``scopes`` is ``op_scopes`` of the runner's
    compiled HLO text and ``module`` its ``module_name``."""
    ops = op_self_times(profile, module)
    if ops is None:
        return None
    out: Counter = Counter()
    for (name, inside), sec in ops.items():
        out[scopes.get(name, UNSCOPED) if inside else UNSCOPED] += sec
    return dict(out)


def epoch_valid_share(cell, fetched: float, rounds: int) -> float:
    """% of the epoch rows that ``rounds`` rounds of every drive processed
    (``num_sqs`` x ``fetch_width`` each) that held a valid request, from
    the growth of ``Metrics.fetched`` over those rounds."""
    e = cell.config["engine"]
    rows = rounds * cell.config["drives"] * e["num_sqs"] * e["fetch_width"]
    return 100.0 * fetched / rows
