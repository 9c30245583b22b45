"""The readings each limit in ``correct`` is set from, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds <n,n,...> [--seconds 3]

For every seed, in one process (one set-up cost), runs the cell's
window at its own load and prints the program's reading of each number
compared (the lower reading), then puts the control in the program's
place and prints the result line the harness makes of it (the upper
reading), which has to read ``correct: false``.  The control is the
reference computed from 4-bit instead of the configured 8-bit
intensities.  The last line gives the largest program reading, the
smallest control reading per number, and whether every control run
came out not correct.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chip import run as bench  # noqa: E402

CONTROL_BITS = 4


def as_control(rec) -> None:
    """Put the control's answers in the program's place in ``rec``."""
    rec.checks = rec.extra["judge"](rec.extra["control"](CONTROL_BITS))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    plan = bench.cell_plan(bench.load_spec(), args.workload)
    bench.enable_cache()
    try:
        device, peak = bench.device_info(plan["cell"]["chips"])
    except bench.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    lower: dict = {}
    upper: dict = {}
    every_control_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = bench.execute(plan, seed, args.seconds, False, device, peak)
        prog = bench.result_line(plan, rec, device, False)
        as_control(rec)
        ctl = bench.result_line(plan, rec, device, False)
        every_control_failed &= not ctl["correct"]
        print(json.dumps({"seed": seed, "attempted": rec.attempted,
                          "program": prog, "control": ctl}), flush=True)
        for k, c in prog["checks"].items():
            lower[k] = max(lower.get(k, c["value"]), c["value"])
        for k, c in ctl["checks"].items():
            upper[k] = min(upper.get(k, c["value"]), c["value"])
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "control_upper": upper,
                      "every_control_failed": every_control_failed,
                      "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
