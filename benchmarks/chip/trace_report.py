"""One traced run of a cell, as `run.py --trace 1` makes it, read with the
program's own names as well (`chipbench/scopes.py`):

    python3 benchmarks/chip/trace_report.py --workload <cell> --seed <n> \
        --seconds <s>

The traced window is read as run.py reads it; besides, the profile's host
spans and op scopes give device seconds by scope (with the share of busy
time in no scope), `flush_share` (device time under `kv_flush` over the
window), the ten longest idle gaps named by the span that covers them, the
host spans' counts and seconds, and the host-device clock check (lag from
each decode program's end to the end of its `serve.readback`).  That
reading is printed as `trace report: {...}` on standard error and as the
next-to-last line of standard output; the last line is run.py's result,
which here also holds `tok_s` over the traced window (the cost of tracing
is its difference from an untraced run's).
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def main(argv=None) -> int:
    args = run.parse(argv)
    if not args.trace:
        argv = list(argv if argv is not None else sys.argv[1:]) \
            + ["--trace", "1"]
    read_window, metrics_of = run._trace_context, run.cell_metrics

    def trace_context(trace_dir, open_ns, close_ns):
        from chipbench import scopes
        from chipbench import trace as trace_lib

        tr = read_window(trace_dir, open_ns, close_ns)
        rep = scopes.report(trace_lib.find_xplane(trace_dir), tr)
        print("trace report: " + json.dumps(rep), file=sys.stderr,
              flush=True)
        print(json.dumps(rep), flush=True)
        return tr

    def cell_metrics(bench, cell_name):
        e2e, per_layer = metrics_of(bench, cell_name)
        return e2e, per_layer + [m for m in e2e if m["name"] == "tok_s"]

    run._trace_context, run.cell_metrics = trace_context, cell_metrics
    try:
        return run.main(argv)
    finally:
        run._trace_context, run.cell_metrics = read_window, metrics_of


if __name__ == "__main__":
    sys.exit(main())
