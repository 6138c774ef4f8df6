"""Readings that set the limits of ``check.LIMITS``, in one process.

    python3 -m bench.readings --workload <cell> --seeds 1,2,3 --control-seeds 4,5,6

For each seed, one run of the cell with a window of one whole replay prints
the compared numbers of a sound run.  For each control seed, the same with
``check.reid_control`` (bfloat16) in the place of the re-ID dispatch, and
the latency gap of the plain simulator run in float32 time against its
float64 run, at the cell's size.  The benchmark's own runs never run a
control.  Needs the chip, like a run.
"""

from __future__ import annotations

import argparse
import json
import sys

from . import check, run, workload


def time_control_gap(cell_name: str, seed: int) -> float:
    """``latency_gap_s`` of the reference run in float32 time against its
    float64 run, over a whole replay of the cell."""
    cell = workload.load_cell(cell_name)
    plans = workload.query_plans(cell, seed)
    scn = cell.config["scenario"]
    horizon = scn["duration_s"] + 3.0 * scn["gamma"]
    want = run.reference_books(cell, {}, plans, [horizon])[horizon]
    got = run.reference_books(cell, {}, plans, [horizon], time32=True)[horizon]
    return check.timed_gap(got["timed"], want["timed"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    plan = [("sound", int(s), None) for s in args.seeds.split(",") if s]
    plan += [("control", int(s), check.reid_control)
             for s in args.control_seeds.split(",") if s]
    for kind, seed, matcher in plan:
        try:
            out = run.run_cell(args.workload, seed, 0.0, False, matcher=matcher, whole=True,
                               log=lambda s: print(s, file=sys.stderr))
        except (run.NoChip, run.Refused) as e:
            print(f"bench: {e}", file=sys.stderr)
            return 2
        row = {"kind": kind, "seed": seed, "correct": out["correct"],
               "checks": {k: v["value"] for k, v in out["checks"].items()}}
        if kind == "control":
            row["time32_latency_gap_s"] = time_control_gap(args.workload, seed)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
