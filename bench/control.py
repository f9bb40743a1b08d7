#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

Runs the cell once per seed in this one process, as ``bench/run.py``
would, and also runs the int8 control (the architecture's ``score``) over
the same sample. Prints one JSON line per seed: the program's widest and mean
logit gap (lower readings, over many seeds) and the control's (upper ones).
The benchmark's own runs never run the control.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--seeds', type=int, nargs='+', required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, 'src'), ROOT]
    from bench import harness
    for seed in args.seeds:
        t = time.monotonic()
        out = harness.run(args.workload, seed, args.seconds,
                          False, t_start=t, control=True,
                          log=lambda s: print(s, file=sys.stderr,
                                              flush=True))
        print(json.dumps({'seed': seed, **out['readings'],
                          'correct': out['correct'],
                          'attempted': out['attempted'],
                          'failed': out['failed'],
                          'metrics': out['metrics'],
                          'device': out['device'],
                          'wall_s': time.monotonic() - t}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
