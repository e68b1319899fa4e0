"""The check's control: the reference, with the ordering guarantee
broken, in the program's place.

Each window of W tasks runs as one synchronous wave: every task reads
the state as it was before the window, with no conflict check, and the
writes land in chain order. It is what a run that dropped the schedule
would compute. The harness drives it through a whole run of the cell,
at the cell's own sizes and load, and the check must read it as not
correct. The benchmark's own runs never run this.

    python bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 3]

Prints one JSON line: each seed's compared numbers and ``correct``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_system(case: dict, traffic: dict, cell):
    ref = cell.reference().Reference(case["ref_data"])
    w = int(traffic["window"])

    def run(state, n_tasks, *, seed):
        out = ref.replay(state, seed, n_tasks, window=w)
        windows = -(-n_tasks // w)
        return out, {"n_windows": windows, "total_waves": windows,
                     "overlap": False}

    return run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = harness.run(ROOT, args.workload, seed, args.seconds, False,
                              t_start=time.perf_counter(),
                              system=control_system)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        rows.append({"seed": seed, "correct": out["correct"],
                     "check": out["check"], "attempted": out["attempted"],
                     "failed": out["failed"]})
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    print(json.dumps({"workload": args.workload, "control": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
