#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

The plain reference, put in the program's place with its virtual time
computed in bfloat16 (the precision below the float32 the configurations
state), is compared with the float32 reference exactly as a run compares
the program. It has to come out as not correct; its numbers are the upper
readings the limits in ``bench/limits/`` were set below (PERF.md).

    python3 bench/control.py --workload <cell> --rounds <n> --seeds <s> ...

prints one JSON line per seed with the numbers, their limits and the
verdict. It needs no chip: the reference runs on the host.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.compare import gaps, verdict  # noqa: E402
from bench.data import drive_keys, image_rows  # noqa: E402
from bench.reference import Reference, model_from  # noqa: E402


def as_state(ref: Reference, image_keys, block_words: int):
    """A reference's state in the layout of the program's ``EngineState``
    (leading drive axis), so that ``compare.gaps`` can judge it."""
    r = ref
    n = SimpleNamespace
    bufs = np.zeros(r.bufs_lba.shape + (block_words,), np.float32)
    for d, key in enumerate(image_keys):
        filled = r.bufs_lba[d] >= 0
        bufs[d, filled] = image_rows(r.bufs_lba[d, filled], block_words,
                                     int(key))
    f32 = np.float32
    return n(
        rings=n(head=r.sq_head, tail=r.sq_tail, req_id=r.sq_req,
                lba=r.sq_lba, opcode=r.sq_op, buf_id=r.sq_buf,
                nblocks=r.sq_nblocks, tenant=r.sq_tenant,
                submit_time=r.sq_time.astype(f32)),
        cq=n(head=r.cq_tail, tail=r.cq_tail, req_id=r.cq_req,
             done_time=r.cq_time.astype(f32),
             visible_time=r.cq_time.astype(f32)),
        device=n(tstate=n(rr=r.rr, busy_until=r.busy.astype(f32)),
                 flash=n(io_seq=r.io_seq), disp_time=r.disp.astype(f32),
                 lock_time=r.lock.astype(f32), dsa_time=r.dsa),
        metrics=n(completed=r.completed, fetched=r.completed,
                  tenant_completed=r.completed[:, None],
                  sum_e2e=r.sum_e2e, tenant_sum_e2e=r.sum_e2e[:, None],
                  sum_target=r.sum_target, sum_proc=r.sum_proc,
                  first_submit=r.first_submit.astype(f32),
                  last_completion=r.last_completion.astype(f32),
                  lat_hist=r.lat_hist, tenant_lat_hist=r.lat_hist[:, None]),
        req_counter=r.req_counter, clock=r.clock.astype(f32),
        last_submit=r.last_submit.astype(f32), bufs=bufs,
    )


def control_numbers(cell, seed: int, rounds: int, time_dtype=None) -> dict:
    """The numbers of the control (the reference in ``time_dtype``,
    bfloat16 by default) against the float32 reference."""
    import ml_dtypes

    time_dtype = time_dtype or ml_dtypes.bfloat16
    salts, keys = drive_keys(seed, cell.config["drives"])
    model = model_from(cell.config, cell.traffic)
    ref = Reference(model, salts)
    ctl = Reference(model, salts, time_dtype=time_dtype)
    ref.run(rounds)
    ctl.run(rounds)
    words = cell.config["block_words"]
    return gaps(as_state(ctl, keys, words), ref, keys, words)


def main(argv=None) -> int:
    from bench.harness import load_cell, load_limits

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rounds", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    limits = load_limits(cell.config["name"])
    for seed in args.seeds:
        nums = control_numbers(cell, seed, args.rounds)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "rounds": args.rounds,
                          "correct": verdict(nums, limits),
                          "numbers": nums, "limits": limits}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
