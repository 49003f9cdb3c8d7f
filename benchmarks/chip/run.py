"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

Run from the repository's root on a machine that holds the chips the
cell asks for.  Without a TPU, or with fewer chips, it exits non-zero
and prints no result.  The last line of standard output is the result
as one JSON object; the numbers the correctness check compared, each
beside its limit, are the last lines of standard error.

``--control 1`` puts the configuration's reference, computed one
precision lower, in the program's place for the correctness check;
``--rate`` overrides a serve cell's request rate; ``--keep-trace PATH``
keeps a traced run's reduced trace (``PATH.json``), its host records and
a list of the trace's lines.  They are for setting limits, finding the
knee and reading traces by hand, not for the benchmark's own runs.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]
sys.path[:0] = [os.path.dirname(_HERE),
                os.path.join(os.path.dirname(os.path.dirname(_HERE)), "src")]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--keep-trace", default=None, metavar="PATH")
    args = ap.parse_args()
    from chip import harness
    harness.main(args, T_START)


if __name__ == "__main__":
    main()
