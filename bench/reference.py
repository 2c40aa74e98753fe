"""Plain NumPy reference of the emulator for the benchmark's cells.

It follows the model as the paper and the program's module docstrings
state it, one engine round at a time, for every drive of a cell at once
(leading drive axis ``D``), and imports nothing of the program:

1. frontend (SwarmIO distributed, coalesced, DSA fetch): an SQ entry is
   visible once its submit time is at or before the round's clock; each
   active service unit fetches up to ``fetch_width`` visible head entries
   from each of its SQs in one pass, at ``dsa_coal_base_us + n * (SQE
   bytes / DSA bandwidth)`` per SQ (or ``n * dsa_sqe_fetch_us`` where
   that is less, and one doorbell poll for an empty SQ); a unit whose
   previous pass is still running skips the round;
2. the global timing lock, taken by the units in index order at
   ``lock_per_batch_us`` per non-empty batch; a request arrives at the
   timing model at ``max(fetch done, its unit's lock grant)``;
3. the aggregated NVMeVirt timing model: the p-th request of the round
   goes to instance ``(rr + p) % K``; on instance k with busy-until
   ``B`` the j-th request (rank j in this round) starts at
   ``b_j + j*Sched`` where ``b_j = max(b_{j-1}, arrival_j - j*Sched)``,
   ``b_{-1} = B``, and completes at ``max(start + Sched, arrival +
   L_min)``; ``B' = b_last + m*Sched``;
4. the DSA data path: one pipelined engine per unit, issue cost
   ``dsa_desc_issue_us + dsa_batch_setup_us / 16``, ``bytes / bandwidth
   + 0.01`` us per copy, plus the fetched SQE bytes; every read copies
   its flash block into the request's buffer;
5. the flash backend is idle for reads that hit the mapping table, and
   the CQ is neutral: each completion is posted to the CQ of its SQ and
   reaped at its completion time;
6. the closed loop: every completed slot submits its next request
   ``resubmit_delay_us`` after the completion, to the same SQ, with the
   same buffer, request id ``counter + row``, and a uniform LBA hashed
   from the id and the drive's salt; each SQ's new entries are posted in
   submit-time order; the clock advances by the poll quantum, or jumps
   to the earliest pending submission.

Virtual time is kept in ``time_dtype`` (float32 as the configuration
states, or a lower precision for the control). The DSA times, whose
sums the program associates freely, and the metric sums are kept in
float64 and compared with a tolerance.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.data import hash_np

FAR = 3e38
HIST_BUCKETS = 64
HIST_PER_DECADE = 64 / 5.0  # 64 log buckets over 5 decades from 1 us
DSA_BATCH = 16              # requests per DSA batch descriptor
DSA_DESC_US = 0.01          # engine time per copy descriptor

# The model as far as this reference implements it; a configuration
# that asks for anything else is refused rather than compared wrongly.
SUPPORTED = {
    "engine": {
        "frontend": "distributed", "mode": "aggregated", "coalesced": True,
        "dsa_fetch": True, "batched_datapath": True,
        "timing_scope": "global", "lock_order": "program",
        "transport": "p2p", "emulate_data": True,
    },
    "ssd": {"routing": "round_robin", "block_bytes": 512},
    "traffic": {"kind": "closed_loop", "read_frac": 1.0,
                "mapping_hit_rate": 1.0},
}


@dataclasses.dataclass(frozen=True)
class Model:
    num_sqs: int
    sq_depth: int
    fetch_width: int
    num_units: int
    num_bufs: int
    poll_quantum_us: float
    n_instances: int
    sched_us: float
    l_min_us: float
    block_bytes: int
    num_blocks: int
    io_depth: int
    resubmit_delay_us: float
    sqe_bytes: int
    dsa_coal_base_us: float
    dsa_sqe_fetch_us: float
    dsa_bytes_per_us: float
    dsa_issue_us: float
    doorbell_poll_us: float
    lock_per_batch_us: float


def model_from(config: dict, traffic: dict) -> Model:
    """The reference's model of a cell, from its configuration and traffic
    files. Raises ``NotImplementedError`` for what it does not model."""
    eng, ssd, plat = config["engine"], config["ssd"], config["platform"]
    for group, want in SUPPORTED.items():
        have = {"engine": eng, "ssd": ssd, "traffic": traffic}[group]
        for key, val in want.items():
            if have.get(key, val) != val:
                raise NotImplementedError(
                    f"reference does not model {group}.{key}="
                    f"{have.get(key)!r}"
                )
    for key in ("cache", "fabric", "qp"):
        if eng.get(key):
            raise NotImplementedError(f"reference does not model {key}")
    return Model(
        num_sqs=eng["num_sqs"], sq_depth=eng["sq_depth"],
        fetch_width=eng["fetch_width"], num_units=eng["num_units"],
        num_bufs=eng["num_bufs"], poll_quantum_us=eng["poll_quantum_us"],
        n_instances=ssd["n_instances"],
        sched_us=ssd["n_instances"] / ssd["t_max_iops"] * 1e6,
        l_min_us=ssd["l_min_us"], block_bytes=ssd["block_bytes"],
        num_blocks=config["num_blocks"], io_depth=traffic["io_depth"],
        resubmit_delay_us=traffic["resubmit_delay_us"],
        sqe_bytes=plat["sqe_bytes"],
        dsa_coal_base_us=plat["dsa_coal_base_us"],
        dsa_sqe_fetch_us=plat["dsa_sqe_fetch_us"],
        dsa_bytes_per_us=plat["dsa_bytes_per_us"],
        dsa_issue_us=plat["dsa_desc_issue_us"]
        + plat["dsa_batch_setup_us"] / DSA_BATCH,
        doorbell_poll_us=plat["doorbell_poll_us"],
        lock_per_batch_us=plat["lock_per_batch_us"],
    )


def request_key(req_id: np.ndarray, salt: np.ndarray, stream: int):
    """The closed loop's per-request hash: request id, drive salt and
    stream (0 = address, 1 = opcode), workload seed 0."""
    with np.errstate(over="ignore"):
        base = (
            req_id.astype(np.uint32)
            + salt.astype(np.uint32) * np.uint32(0x632BE5AB)
            + np.uint32(stream) * np.uint32(7919)
        )
    return hash_np(base)


def address(req_id, salt, num_blocks: int) -> np.ndarray:
    return (request_key(req_id, salt, 0) % np.uint32(num_blocks)).astype(
        np.int32
    )


def opcode(req_id, salt) -> np.ndarray:
    """Read (0) or write (1); every request is a read at ``read_frac`` 1."""
    h = request_key(req_id, salt, 1)
    return ((h % np.uint32(1000)).astype(np.float32) >= 1000.0).astype(
        np.int32
    )


def latency_bucket(lat_us: np.ndarray) -> np.ndarray:
    lg = np.log10(np.maximum(lat_us.astype(np.float64), 1e-6))
    return np.clip(lg * HIST_PER_DECADE, 0, HIST_BUCKETS - 1).astype(
        np.int64
    )


class Reference:
    """The state of D drives and the round that advances it."""

    def __init__(self, m: Model, salts: np.ndarray, time_dtype=np.float32):
        self.m, self.T = m, time_dtype
        T = self.T
        d = len(salts)
        q, dep, u = m.num_sqs, m.sq_depth, m.num_units
        self.salt = np.asarray(salts, np.uint32).astype(np.int32)
        self.far = T(FAR)
        # SQ rings.
        self.sq_time = np.full((d, q, dep), self.far, T)
        self.sq_op = np.zeros((d, q, dep), np.int32)
        self.sq_lba = np.zeros((d, q, dep), np.int32)
        self.sq_nblocks = np.ones((d, q, dep), np.int32)
        self.sq_buf = np.zeros((d, q, dep), np.int32)
        self.sq_req = np.zeros((d, q, dep), np.int32)
        self.sq_tenant = np.zeros((d, q, dep), np.int32)
        self.sq_head = np.zeros((d, q), np.int64)
        self.sq_tail = np.zeros((d, q), np.int64)
        # CQ rings.
        self.cq_time = np.full((d, q, dep), self.far, T)
        self.cq_req = np.zeros((d, q, dep), np.int32)
        self.cq_tail = np.zeros((d, q), np.int64)
        # Device.
        self.busy = np.zeros((d, m.n_instances), T)
        self.rr = np.zeros(d, np.int64)
        self.disp = np.zeros((d, u), T)
        self.lock = np.zeros(d, T)
        self.dsa = np.zeros((d, u), np.float64)
        self.io_seq = np.zeros(d, np.int64)
        # Engine.
        self.clock = np.zeros(d, T)
        self.bufs_lba = np.full((d, m.num_bufs), -1, np.int64)
        # Metrics.
        self.completed = np.zeros(d, np.int64)
        self.sum_e2e = np.zeros(d, np.float64)
        self.sum_target = np.zeros(d, np.float64)
        self.sum_proc = np.zeros(d, np.float64)
        self.last_completion = np.zeros(d, T)
        self.first_submit = np.full(d, self.far, T)
        self.lat_hist = np.zeros((d, HIST_BUCKETS), np.int64)
        self.rounds = 0

        # Prefill: io_depth entries per SQ, staggered for a total order.
        depth = m.io_depth
        req = (
            np.arange(q, dtype=np.int32)[:, None] * depth
            + np.arange(depth, dtype=np.int32)[None, :]
        )
        sub = (
            np.arange(depth, dtype=T)[None, :] * T(1e-3)
            + np.arange(q, dtype=T)[:, None] * T(1e-5)
        )
        for di in range(d):
            s = np.full(req.shape, self.salt[di])
            self.sq_time[di, :, :depth] = sub
            self.sq_req[di, :, :depth] = req
            self.sq_lba[di, :, :depth] = address(req, s, m.num_blocks)
            self.sq_op[di, :, :depth] = opcode(req, s)
            self.sq_buf[di, :, :depth] = req % m.num_bufs
        self.sq_tail[:] = depth
        self.last_submit = np.broadcast_to(sub.max(axis=1), (d, q)).copy()
        self.req_counter = np.full(d, q * depth, np.int64)

    def run(self, rounds: int) -> None:
        for _ in range(rounds):
            self.round()

    def round(self) -> None:
        m, T = self.m, self.T
        d = self.clock.shape[0]
        q, dep, f, u = m.num_sqs, m.sq_depth, m.fetch_width, m.num_units
        per_unit = q // u

        # -- 1. fetch ------------------------------------------------------
        j = np.arange(f)
        pos = (self.sq_head[:, :, None] + j) % dep
        t = np.take_along_axis(self.sq_time, pos, axis=2)
        avail = self.sq_tail - self.sq_head
        vis = (t <= self.clock[:, None, None]) & (j < avail[:, :, None])
        visible = np.cumprod(vis, axis=2).sum(axis=2)
        active = np.repeat(self.disp <= self.clock[:, None], per_unit, 1)
        nfetch = np.where(active, np.minimum(np.minimum(avail, visible), f),
                          0)
        nf = nfetch.astype(T)
        coal = T(m.dsa_coal_base_us) + nf * T(m.sqe_bytes /
                                              m.dsa_bytes_per_us)
        cost = np.where(nfetch > 0,
                        np.minimum(coal, nf * T(m.dsa_sqe_fetch_us)),
                        T(m.doorbell_poll_us))
        cost = np.where(active, cost, T(0)).astype(T)
        cum = np.cumsum(cost.reshape(d, u, per_unit), axis=2, dtype=T)
        start = np.maximum(self.disp, self.clock[:, None])
        fdone = (start[:, :, None] + cum).reshape(d, q)
        self.disp = start + cum[:, :, -1]

        # -- 2. timing lock -------------------------------------------------
        nv_u = nfetch.reshape(d, u, per_unit).sum(axis=2)
        ready_u = np.where(nfetch > 0, fdone, T(0)).reshape(
            d, u, per_unit).max(axis=2)
        lock_cost = np.where(nv_u > 0, T(m.lock_per_batch_us), T(0))
        lock_done = np.empty((d, u), T)
        tl = self.lock
        for ui in range(u):
            tl = (np.maximum(tl, ready_u[:, ui]) + lock_cost[:, ui]).astype(T)
            lock_done[:, ui] = tl
        self.lock = tl
        self.disp = np.maximum(self.disp, lock_done)
        arr_q = np.maximum(fdone, np.repeat(lock_done, per_unit, axis=1))

        # The fetched requests, SQ-major (dispatch order).
        counts = nfetch.reshape(-1)
        n = int(counts.sum())
        flat = np.repeat(np.arange(d * q), counts)
        jj = np.arange(n) - np.repeat(np.cumsum(counts) - counts, counts)
        di, qi = flat // q, flat % q
        rpos = (self.sq_head[di, qi] + jj) % dep
        sub = self.sq_time[di, qi, rpos]
        lba = self.sq_lba[di, qi, rpos]
        buf = self.sq_buf[di, qi, rpos]
        req = self.sq_req[di, qi, rpos]
        self.sq_head = self.sq_head + nfetch
        arr = arr_q[di, qi]
        per_drive = nfetch.sum(axis=1)

        # -- 3. aggregated timing model ------------------------------------
        k = m.n_instances
        sched, lmin = T(m.sched_us), T(m.l_min_us)
        p = np.arange(n) - np.repeat(np.cumsum(per_drive) - per_drive,
                                     per_drive)
        inst = (self.rr[di] + p) % k
        rank = p // k
        rank_t = rank.astype(T)
        a = (arr - rank_t * sched).astype(T)
        last_b = np.zeros((d, k), T)
        b = np.empty(n, T)
        for r in range(int(rank.max()) + 1 if n else 0):
            sel = rank == r
            prev = (self.busy if r == 0 else last_b)[di[sel], inst[sel]]
            b[sel] = np.maximum(a[sel], prev)
            last_b[di[sel], inst[sel]] = b[sel]
        start_t = (b + rank_t * sched).astype(T)
        target = np.maximum(start_t + sched, arr + lmin).astype(T)
        m_k = np.zeros((d, k), np.int64)
        np.add.at(m_k, (di, inst), 1)
        self.busy = np.where(m_k > 0, last_b + m_k.astype(T) * sched,
                             self.busy).astype(T)
        self.rr = (self.rr + per_drive) % k

        # -- 4. DSA data path (float64; only ``ready`` comes out of it) -----
        c = m.block_bytes / m.dsa_bytes_per_us + DSA_DESC_US
        dsa = self.dsa + nv_u * (m.sqe_bytes / m.dsa_bytes_per_us)
        ready_in = arr_q.astype(np.float64) + np.float64(T(m.dsa_issue_us))
        nq = nfetch.reshape(d, u, per_unit)
        rin = ready_in.reshape(d, u, per_unit)
        sq_start = np.empty((d, u, per_unit))
        for s in range(per_unit):
            sq_start[:, :, s] = np.maximum(rin[:, :, s], dsa)
            dsa = sq_start[:, :, s] + nq[:, :, s] * c
        self.dsa = np.maximum(dsa, self.dsa)
        ready = sq_start.reshape(d, q)[di, qi] + (jj + 1) * c
        done = np.maximum(np.maximum(target, ready.astype(T)), arr)

        # -- 5. data copy, CQ post, metrics -------------------------------
        self.bufs_lba[di, buf] = lba
        cpos = (self.cq_tail[di, qi] + jj) % dep
        self.cq_time[di, qi, cpos] = done
        self.cq_req[di, qi, cpos] = req
        self.cq_tail = self.cq_tail + nfetch
        self.io_seq += per_drive
        self.completed += per_drive
        e2e = (done - sub).astype(T)
        np.add.at(self.sum_e2e, di, e2e.astype(np.float64))
        np.add.at(self.sum_target, di, (target - arr).astype(np.float64))
        np.add.at(self.sum_proc, di, ready - arr.astype(np.float64))
        np.maximum.at(self.last_completion, di, done)
        np.minimum.at(self.first_submit, di, sub)
        np.add.at(self.lat_hist, (di, latency_bucket(e2e)), 1)

        # -- 6. closed-loop resubmission and clock --------------------------
        new_req = (self.req_counter[di] + qi * f + jj).astype(np.int32)
        salt = self.salt[di]
        rt = (done + T(m.resubmit_delay_us)).astype(T)
        np.maximum.at(self.last_submit, (di, qi), rt)
        order = np.lexsort((rt, flat))
        rank_sq = jj  # groups keep their sizes, so slot ranks are unchanged
        o_di, o_qi = di[order], qi[order]
        wpos = (self.sq_tail[o_di, o_qi] + rank_sq) % dep
        self.sq_time[o_di, o_qi, wpos] = rt[order]
        self.sq_op[o_di, o_qi, wpos] = opcode(new_req, salt)[order]
        self.sq_lba[o_di, o_qi, wpos] = address(new_req, salt,
                                                m.num_blocks)[order]
        self.sq_nblocks[o_di, o_qi, wpos] = 1
        self.sq_buf[o_di, o_qi, wpos] = buf[order]
        self.sq_req[o_di, o_qi, wpos] = new_req[order]
        self.sq_tenant[o_di, o_qi, wpos] = 0
        self.sq_tail = self.sq_tail + nfetch

        hpos = self.sq_head % dep
        head_t = np.take_along_axis(self.sq_time, hpos[:, :, None], 2)[..., 0]
        head_t = np.where(self.sq_tail > self.sq_head, head_t, self.far)
        nxt = head_t.min(axis=1)
        stepped = (self.clock + T(m.poll_quantum_us)).astype(T)
        self.clock = np.where(nxt < self.far, np.maximum(stepped, nxt),
                              stepped).astype(T)
        self.req_counter = self.req_counter + q * f
        self.rounds += 1
