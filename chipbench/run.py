"""Run one cell of the benchmark once.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exit 0 and one JSON result line (the last line of stdout) only on a TPU
whose ``device_kind`` has a row in ``chipbench/peaks.json``.  With
``--rehearsal`` the cell's driver runs at the toy sizes of its files on
whatever JAX finds, says so, prints no result line and exits 3.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()          # set-up is counted from here

import argparse                           # noqa: E402
import os                                 # noqa: E402
import sys                                # noqa: E402
import traceback                          # noqa: E402

EXIT_NO_RESULT = 2
EXIT_REHEARSAL = 3


def main(argv=None) -> int:
    from chipbench import harness as H

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes on whatever JAX finds; prints no "
                         "result line, exits 3")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(H.ROOT, "paddle_tpu")):
        print("chipbench: no paddle_tpu/ beside chipbench/ — the "
              "benchmark measures the program, not itself",
              file=sys.stderr)
        return EXIT_NO_RESULT
    if H.ROOT not in sys.path:
        sys.path.insert(0, H.ROOT)
    try:
        man = H.manifest()
        cell = H.Cell(man, args.workload)
        seconds = float(man["run_seconds"] if args.seconds is None
                        else args.seconds)
        if args.rehearsal:
            gate = H.rehearsal_gate()
            print("chipbench: REHEARSAL on "
                  f"{gate['device']['platform']} — toy sizes, no result "
                  "line, no device number", flush=True)
        else:
            gate = H.device_gate(cell.chips)
    except H.BenchError as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return EXIT_NO_RESULT

    H.keep_compiled_programs()
    ctx = H.context(cell, gate, args.seed, seconds, trace=args.trace,
                    rehearsal=args.rehearsal, t_process=T_PROCESS)
    driver = H.load_module("drivers", cell.traffic["driver"])
    try:
        run = driver.run(ctx)
    except Exception:  # noqa: BLE001 - the boundary: report, no result
        traceback.print_exc(file=sys.stderr)
        print("chipbench: the run failed; no result", file=sys.stderr)
        return 1
    if args.rehearsal:
        for name, c in run["checks"].items():
            print(f"chipbench rehearsal compared {name} = "
                  f"{c['value']:.6g} (limit {c['limit']:.6g})")
        print("chipbench: rehearsal done (correct would be "
              f"{H.decide(run['checks'])}); it proves nothing about the "
              "chip")
        return EXIT_REHEARSAL

    device = dict(gate["device"])
    device["memory_peak_bytes"] = int(run["memory_peak_bytes"])
    breakdown = None
    try:
        if args.trace:
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
            breakdown = run["trace"]["breakdown"]
            metrics = H.read_metrics(cell.metrics(man, "per_layer"),
                                     run, cell)
        else:
            metrics = H.read_metrics(cell.metrics(man, "end_to_end"),
                                     run, cell)
    except H.BenchError as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 1
    H.print_result(run, metrics, device, breakdown)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (server loops, writers) are joined
    # by the drivers; nothing is left to wait for
    sys.exit(code)
