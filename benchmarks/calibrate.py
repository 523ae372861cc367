"""Readings that the check's limits are set from, for one cell, on the chip.

    python3 benchmarks/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control 3] [--faults 3] [--seconds 0.5] [--out readings.jsonl]

For each seed it makes a run of the cell with a short window (the training
readings need none) and prints one JSON line with the program's numbers. On the
first ``--control`` seeds it also reads the control (the reference put in the
program's place and computed in fp8) and whether it passes the check's limits,
which it must not, and on the first ``--faults`` seeds it runs
the timed path with each fault that the cell can have planted in it. The
benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness  # noqa: E402


def faults_of(cell):
    return [f for f in harness.FAULTS if f != 'no_exchange' or cell.chips > 1]


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seeds', required=True)
    parser.add_argument('--control', type=int, default=3)
    parser.add_argument('--faults', type=int, default=3)
    parser.add_argument('--seconds', type=float, default=0.5)
    parser.add_argument('--out', default=None)
    args = parser.parse_args(argv)
    cell = harness.Cell(harness.load_spec(), args.workload)
    devices = harness.require_chip(cell.chips)
    harness.configure_compile_cache()
    out = open(args.out, 'a') if args.out else None
    try:
        for i, seed in enumerate(int(s) for s in args.seeds.split(',')):
            for fault in [None] + (faults_of(cell) if i < args.faults else []):
                start = time.perf_counter()
                result = harness.run(cell, seed, args.seconds, devices=devices, fault=fault,
                                     control=fault is None and i < args.control)
                line = {'workload': cell.name, 'seed': seed, 'fault': fault,
                        'correct': result['correct'],
                        'control_correct': result.get('_control_correct'),
                        'checks': {k: v['value'] for k, v in result['checks'].items()},
                        'readings': result['_readings'],
                        'setup_s': result['_run']['setup_s'],
                        'seconds': time.perf_counter() - start}
                text = json.dumps(line)
                print(text, flush=True)
                if out:
                    out.write(text + '\n')
                    out.flush()
    finally:
        if out:
            out.close()


if __name__ == '__main__':
    main()
