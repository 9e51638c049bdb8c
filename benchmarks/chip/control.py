"""The correctness check's controls on the chip: one run of a cell whose
comparison must come out as not correct.

    python3 benchmarks/chip/control.py --workload <cell> --seed <n> \
        --seconds <s> [--fault token|state|half_batch]

Without `--fault` the reference, computed in float8 (e4m3) matmuls, is put
in the program's place: `checks.logit_gap` holds the control's widest gap
(the standard-error line `readings:` carries the program's as
`logit_gap` and the control's as `control_logit_gap`).  With `--fault` the
program serves with that fault planted in its timed decode step and is
compared as in a benchmark run.  A limit is sound when every sound run
reads under it and every control run over it.  The benchmark's own runs
never run a control.
"""
import argparse
import sys

import run

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", choices=run.FAULTS, default=None)
    args, rest = ap.parse_known_args()
    sys.exit(run.main(rest + ["--trace", "0"], control=args.fault is None,
                      fault=args.fault))
