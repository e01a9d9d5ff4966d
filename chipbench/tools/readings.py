"""The readings a cell's limits are set from, in one process on the
chip: the program's numbers over many seeds (the lower reading), and over
the first few the control's and the planted faults' (the upper reading),
each held to the cell's limits as a run is, so that the row records
``correct`` as the harness would decide it: true for the program, false
for the control and for each fault.

    python3 -m chipbench.tools.readings --workload W --seeds 11,12,13 \\
        --control-seeds 3 --seconds 5 --out chiprun_out/readings_W.jsonl

Each seed is one whole run of the cell's driver (own weights, own
traffic, the reference after it); compiled programs are shared within
the process, so only the first pays for them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    from chipbench import harness as H

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, H.ROOT)
    man = H.manifest()
    cell = H.Cell(man, args.workload)
    gate = H.rehearsal_gate() if args.rehearsal \
        else H.device_gate(cell.chips)
    H.keep_compiled_programs()
    meter = H.CompileMeter()
    driver = H.load_module("drivers", cell.traffic["driver"])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    with open(args.out, "a") as f:
        for i, seed in enumerate(seeds):
            ctx = H.context(cell, gate, seed, args.seconds,
                            rehearsal=args.rehearsal, meter=meter)
            run = driver.run(ctx)
            row = {"workload": cell.name, "seed": seed,
                   "numbers": run["numbers"],
                   "compared": run["checks"],
                   "correct": H.decide(run["checks"]),
                   "rate": run["work"] / run["window_s"],
                   "setup_s": run["setup_s"],
                   "reference_s": run["reference_s"],
                   "attempted": run["attempted"], "failed": run["failed"]}
            if i < args.control_seeds:
                row["planted"] = H.planted(run)
            print(json.dumps(row), flush=True)
            for side in ("program", "reference"):
                if side in run:       # a training cell: every leaf's norms
                    row[side] = run[side]   # (the look before a limit)
            f.write(json.dumps(row) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
