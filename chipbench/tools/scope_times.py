"""Device time of a kept trace by the program's scope paths: the whole
table that ``readers/scope_time_share.py`` reads single rows of.

    CHIPBENCH_KEEP_TRACE=<dir> python3 -m chipbench.run ... --trace 1
    python3 -m chipbench.tools.scope_times <dir>/<cell>.xplane.pb \\
        [--depth 3] [--span serve_prefill] [--under L*/ffn] \\
        [--row "(no name)"] [--layers] [--lines 20]

A row is a path cut to ``--depth`` names (the jit in front and the
primitive behind left off, the layers ``L<i>`` folded into ``L*`` unless
``--layers``): exclusive seconds, percent of the busy time counted, the
part of it in Pallas kernels, HLO lines.  ``--span`` counts only what ran
inside the program's spans of that name (``serve_prefill``,
``serve_decode_step`` …), ``--under`` only the ops under that scope,
``--row`` only those of that row (``(no name)``: the ops XLA gives no
``op_name``, its own copies); ``--lines`` lists the heaviest HLO lines of
what was counted, each with its full path."""

import argparse
import re

from chipbench import harness, tracelib


def clipped(trace, span):
    """``trace`` with its chips' op events cut to the host spans called
    ``span``."""
    whole = tracelib.union((h[0], h[1]) for h in trace.host_spans
                           if h[2] == span)
    return tracelib.Trace(
        {plane: [(max(s, lo), min(e, hi), n) for s, e, n in ops
                 for lo, hi in whole if e > lo and s < hi]
         for plane, ops in trace.device_ops.items()}, [], trace.window)


def row_of(path, depth, layers):
    if not path:
        return "(ambiguous)" if path is None else "(no name)"
    names = path.split("/")[:-1]
    if names and names[0].startswith("jit("):
        names = names[1:]
    if not layers:
        names = [re.sub(r"^L\d+$", "L*", n) for n in names]
    return "/".join(names[:depth]) or "(no scope)"


def main(argv=None):
    from paddle_tpu.utils import profiler

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("xplane")
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--span")
    ap.add_argument("--under")
    ap.add_argument("--row")
    ap.add_argument("--layers", action="store_true")
    ap.add_argument("--lines", type=int, default=0)
    args = ap.parse_args(argv)

    trace = tracelib.load(args.xplane)
    with open(args.xplane, "rb") as f:
        table = profiler.read_ops(f.read())
    if args.span:
        trace = clipped(trace, args.span)
    rows, lines, busy = {}, [], 0.0
    for path, line, seconds, _ in harness.load_module(
            "readers", "scope_time_share").rows_of(trace, table):
        name = row_of(path, args.depth, args.layers)
        if args.under and not profiler.in_scope(path, args.under) \
                or args.row and name != args.row:
            continue
        row = rows.setdefault(name, [0.0, 0.0, 0])
        row[0] += seconds
        row[1] += seconds if "tpu_custom_call" in line else 0.0
        row[2] += 1
        busy += seconds
        lines.append((seconds, line, path))
    print(f"{busy:.4f} s counted over {len(trace.device_ops)} chip(s)")
    print(f"{'seconds':>9} {'%':>6} {'kernels':>9} {'lines':>6}  path")
    for name, (s, k, n) in sorted(rows.items(), key=lambda kv: -kv[1][0]):
        print(f"{s:9.4f} {100 * s / max(busy, 1e-12):6.2f} {k:9.4f} "
              f"{n:6d}  {name}")
    for s, line, path in sorted(lines, reverse=True)[:args.lines]:
        print(f"{s:9.4f}  {tracelib.short_name(line)}  <- {path!r}")


if __name__ == "__main__":
    main()
