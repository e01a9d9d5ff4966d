"""Run one cell as ``chipbench.run`` does, with one or both of:

``--pending``  the per-layer metrics of ``pending/per_layer.json`` read
               too.  They read what PR 25 added to the program, and a
               program without it (the parent of that PR) reads nothing
               for them, which ``harness.read_metrics`` turns into a
               failed run; so they wait outside ``BENCHMARK.json``
               until a ``benchmark`` PR lets a metric be left out.
``--ring``     the program's span recorder on for the whole run (ring
               only, no fences, no profiler): the timed run against a
               plain one is what recording spans costs (with
               ``--keep``, the ring's last 4096 spans are kept too:
               ``<cell>.ring.json``).
``--keep DIR`` a traced run's ``.xplane.pb`` (``CHIPBENCH_KEEP_TRACE``)
               and the program's spans of the stretch
               (``<cell>.spans.json``) are kept under DIR, so that a
               reader can be worked on without the chip.

    python3 -m chipbench.tools.run_with --pending --workload <cell> --trace 1 …
"""

import json
import os
import sys


def main(argv):
    from chipbench import harness as H
    from chipbench import run

    argv, keep = list(argv), None
    if "--keep" in argv:
        at = argv.index("--keep")
        keep = os.path.abspath(argv[at + 1])
        del argv[at:at + 2]
        os.makedirs(keep, exist_ok=True)
        os.environ["CHIPBENCH_KEEP_TRACE"] = keep
        read_metrics = H.read_metrics

        def keeping(rows, run_, cell, *a, **kw):
            if run_.get("trace"):
                with open(os.path.join(
                        keep, cell.name + ".spans.json"), "w") as f:
                    json.dump(run_["trace"].get("spans", []), f)
            return read_metrics(rows, run_, cell, *a, **kw)

        H.read_metrics = keeping
    if "--pending" in argv:
        man = H.manifest()
        man["per_layer"] = man["per_layer"] + H.load_json(
            os.path.join(H.HERE, "pending", "per_layer.json"))
        H.manifest = lambda root=H.ROOT: man
    if "--ring" in argv:
        if H.ROOT not in sys.path:
            sys.path.insert(0, H.ROOT)
        from paddle_tpu.observe import trace as ptrace

        ptrace.enable(fences=False)
    code = run.main([a for a in argv if a not in ("--pending", "--ring")])
    if "--ring" in argv and keep:
        cell = argv[argv.index("--workload") + 1]
        with open(os.path.join(keep, cell + ".ring.json"), "w") as f:
            json.dump(ptrace.events(), f)
    return code


if __name__ == "__main__":
    code = main(sys.argv[1:])
    sys.stdout.flush()
    sys.stderr.flush()
    sys.exit(code)
