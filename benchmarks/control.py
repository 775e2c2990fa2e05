#!/usr/bin/env python3
"""python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 --seconds <s>
[--fault one_answer]: the control, on the chip at the cell's own size.

For each seed, in this one process: set up the cell, plant the fault under
the timed path (faults.py), run warm-up, a short window and the audit, and
print what the audit compared. Every seed has to read ``correct`` false;
the exit code is 0 only then. With ``--fault none`` it reads the sound
program on the same seeds instead (every seed has to read true). Not part
of a benchmark run."""

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    sys.path.insert(0, path)


def main(argv):
    import faults
    import harness
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seeds', required=True)
    parser.add_argument('--seconds', type=float, default=3.0)
    parser.add_argument('--fault', default='one_answer',
                        choices=faults.FAULTS + ('none',))
    args = parser.parse_args(argv)
    found = harness.resolve(args.workload)
    from automerge_tpu import jaxenv
    jaxenv.configure_compile_cache()
    stamp = jaxenv.require_platform()
    driver = found['driver']
    want = args.fault == 'none'
    as_wanted = True
    for seed in (int(s) for s in args.seeds.split(',')):
        undo = faults.plant(args.fault) if args.fault != 'none' \
            else (lambda: None)
        try:
            state = driver.setup(dict(found['config']), found['mix'], seed)
            driver.warmup(state)
            out = driver.window(state, args.seconds,
                                harness.Tracer(False, 0))
        finally:
            undo()
        compared = driver.audit(state)
        correct = all(value <= limit for value, limit in compared.values())
        as_wanted = as_wanted and correct == want
        print(json.dumps({
            'workload': args.workload, 'fault': args.fault, 'seed': seed,
            'device': stamp, 'correct': correct,
            'attempted': out['attempted'], 'failed': out['failed'],
            'compared': {k: list(v) for k, v in compared.items()}}),
            flush=True)
        del state
    return 0 if as_wanted else 1


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
