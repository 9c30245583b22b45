"""Find a serve cell's highest sustained rate, once, on the chip.

    python3 benchmarks/chip/sweep.py --workload <serve cell> --seed <n> \\
        --seconds <s> --lo <rate> --hi <rate> [--steps 6]

Runs the cell's open loop at offered rates between ``lo`` and ``hi``, in
one process (one set-up, one compile), and bisects for the highest rate
that is sustained: by the window's close at least 99% of the requests
offered are answered, and the median latency of the last fifth of the
arrivals is within 3x that of the first fifth (the backlog does not
grow).  Prints one line per rate and, last, a JSON object with the
highest sustained rate and 0.8x of it, the number a cell's traffic file
records.  Every rate's answers are checked against the reference too.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as bench


def sustained(rec) -> tuple[bool, dict]:
    lat = rec.latency_ms()
    n = len(lat)
    fifth = max(1, n // 5)
    early = float(np.median(lat[:fifth]))
    late = float(np.median(lat[-fifth:]))
    answered = float((rec.done_s <= rec.window_s).mean())
    ok = answered >= 0.99 and late <= 3.0 * early
    return ok, {"answered_in_window": answered, "early_p50_ms": early,
                "late_p50_ms": late,
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--lo", type=float, required=True)
    ap.add_argument("--hi", type=float, required=True)
    ap.add_argument("--steps", type=int, default=6)
    args = ap.parse_args(argv)

    plan = bench.cell_plan(bench.load_spec(), args.workload)
    bench.enable_cache()
    try:
        device, peak = bench.device_info(plan["cell"]["chips"])
    except bench.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    def trial(rate: float) -> bool:
        plan["mix"]["rate_per_s"] = rate
        rec = bench.execute(plan, args.seed, args.seconds, False, device,
                            peak)
        ok, info = sustained(rec)
        correct = all(v <= lim for v, lim in rec.checks.values())
        print(json.dumps({"rate_per_s": rate, "sustained": ok,
                          "correct": correct, **info}), flush=True)
        return ok and correct

    lo, hi = args.lo, args.hi
    if not trial(lo):
        print(json.dumps({"error": f"rate {lo} is not sustained"}))
        return 1
    for _ in range(args.steps):
        mid = round((lo + hi) / 2.0)
        if trial(mid):
            lo = mid
        else:
            hi = mid
    print(json.dumps({"workload": args.workload, "sustained_per_s": lo,
                      "cell_rate_per_s": round(0.8 * lo),
                      "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
