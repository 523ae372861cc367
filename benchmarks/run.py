"""Run one cell of the benchmark once and print its result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a workload of ``BENCHMARK.json`` at the checkout's root. The run
makes its store (once per checkout), its weights and its read order from the
seed, warms up every shape, measures ``--seconds`` with the profiler off
(``--trace 0``, end-to-end metrics) or on (``--trace 1``, per-layer metrics),
checks what the timed path produced against the plain reference, and prints one
JSON line last on standard output; the compared numbers and their limits are
also the last lines on standard error. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero before any set-up.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness  # noqa: E402


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    cell = harness.Cell(harness.load_spec(), args.workload)
    devices = harness.require_chip(cell.chips)
    harness.peak_of(devices[0].device_kind)
    harness.configure_compile_cache()
    # a run that hangs shows where, before the caller's limit ends it
    faulthandler.dump_traceback_later(330, exit=False)
    result = harness.run(cell, args.seed, args.seconds, trace=bool(args.trace),
                         devices=devices, t0=T0)
    faulthandler.cancel_dump_traceback_later()
    for line in harness.check_lines(result):
        harness.log(line)
    print(harness.result_line(result), flush=True)


if __name__ == '__main__':
    main()
