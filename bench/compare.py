"""The comparison that decides ``correct``: the program's final state
against the plain reference's, after the same number of rounds.

Every number is a gap between the two; each has a limit of its own in
``bench/limits/<config>.json`` (see PERF.md for the readings each limit
was set from):

- ``discrete_mismatches``: ring entries (request id, LBA, opcode, buffer,
  size, tenant), ring heads and tails, CQ request ids, heads and tails,
  the round-robin cursor, the request counter, the op counter and the
  completion counts that differ. Which requests were fetched, completed
  and resubmitted, in which order: exact.
- ``bad_buffer_rows``: I/O buffer rows that are not, bit for bit, the
  seeded image row of the LBA whose read last filled them.
- ``time_gap_us``: the largest difference of a virtual time the closed
  loop feeds on: SQ submit times, CQ completion times, instance,
  dispatcher and lock busy-until cursors, the clock, the last submit
  per SQ, the first submit and the last completion.
- ``hist_l1``: requests in different latency buckets (all drives).
- ``stat_rel_gap``: the largest relative gap of the latency sums (end to
  end, timing model, data path) and of the DSA busy-until cursors, which
  the program accumulates in float32 in an order of its own.
- ``tenant_sum_rel_gap``: the relative gap of the per-tenant end-to-end
  latency sum, which the program accumulates by a scatter-add.
"""
from __future__ import annotations

import numpy as np

from bench.data import image_rows
from bench.reference import Reference

NAMES = (
    "discrete_mismatches", "bad_buffer_rows", "time_gap_us", "hist_l1",
    "stat_rel_gap", "tenant_sum_rel_gap",
)


def _mismatches(a, b) -> int:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return max(a.size, b.size)
    return int(np.sum(a != b))


def _time_gap(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    both = (a == b)  # equal FAR sentinels and equal times
    diff = np.where(both, 0.0, np.abs(a - b))
    return float(np.max(diff, initial=0.0)) if np.all(np.isfinite(diff)) \
        else float("inf")


def _rel_gap(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    scale = np.maximum(np.abs(b), 1e-30)
    gap = np.abs(a - b) / scale
    return float(np.max(gap)) if np.all(np.isfinite(gap)) else float("inf")


def gaps(state, ref: Reference, image_keys, block_words: int) -> dict:
    """The numbers compared. ``state`` is the program's final
    ``EngineState`` on the host with a leading drive axis on every leaf
    (its ``flash`` may be None)."""
    r, sq, cq, dev = ref, state.rings, state.cq, state.device
    met = state.metrics
    d = ref.clock.shape[0]

    discrete = sum((
        _mismatches(sq.head, r.sq_head), _mismatches(sq.tail, r.sq_tail),
        _mismatches(sq.req_id, r.sq_req), _mismatches(sq.lba, r.sq_lba),
        _mismatches(sq.opcode, r.sq_op), _mismatches(sq.buf_id, r.sq_buf),
        _mismatches(sq.nblocks, r.sq_nblocks),
        _mismatches(sq.tenant, r.sq_tenant),
        _mismatches(cq.head, r.cq_tail), _mismatches(cq.tail, r.cq_tail),
        _mismatches(cq.req_id, r.cq_req),
        _mismatches(dev.tstate.rr, r.rr),
        _mismatches(state.req_counter, r.req_counter),
        _mismatches(dev.flash.io_seq, r.io_seq),
        _mismatches(met.completed, r.completed),
        _mismatches(met.fetched, r.completed),
        _mismatches(met.tenant_completed[:, 0], r.completed),
    ))

    bufs = np.asarray(state.bufs)
    bad_rows = 0
    for di in range(d):
        lba = r.bufs_lba[di]
        filled = lba >= 0
        want = np.zeros_like(bufs[di])
        want[filled] = image_rows(lba[filled], block_words, int(image_keys[di]))
        bad_rows += int(np.sum(np.any(
            bufs[di].view(np.uint32) != want.view(np.uint32), axis=1
        )))

    time_gap = max(
        _time_gap(sq.submit_time, r.sq_time),
        _time_gap(cq.done_time, r.cq_time),
        _time_gap(cq.visible_time, r.cq_time),
        _time_gap(dev.tstate.busy_until, r.busy),
        _time_gap(dev.disp_time, r.disp),
        _time_gap(dev.lock_time, r.lock),
        _time_gap(state.clock, r.clock),
        _time_gap(state.last_submit, r.last_submit),
        _time_gap(met.first_submit, r.first_submit),
        _time_gap(met.last_completion, r.last_completion),
    )

    hist = np.asarray(met.lat_hist, np.float64)
    hist_l1 = float(np.sum(np.abs(hist - r.lat_hist))) + float(np.sum(
        np.abs(np.asarray(met.tenant_lat_hist, np.float64)[:, 0]
               - r.lat_hist)
    ))

    stat = max(
        _rel_gap(met.sum_e2e, r.sum_e2e),
        _rel_gap(met.sum_target, r.sum_target),
        _rel_gap(met.sum_proc, r.sum_proc),
        _rel_gap(dev.dsa_time, r.dsa),
    )
    return {
        "discrete_mismatches": float(discrete),
        "bad_buffer_rows": float(bad_rows),
        "time_gap_us": time_gap,
        "hist_l1": hist_l1,
        "stat_rel_gap": stat,
        "tenant_sum_rel_gap": _rel_gap(met.tenant_sum_e2e[:, 0], r.sum_e2e),
    }


def verdict(numbers: dict, limits: dict) -> bool:
    """Every number at or under its limit (a NaN fails)."""
    return all(numbers[k] <= limits[k] for k in NAMES)
