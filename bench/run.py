#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell, its configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` at the root of the checkout. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``,
and last ``checks``: each number compared for ``correct`` beside its limit.
The same numbers are the last lines of standard error. Without a TPU of a
kind in ``bench/peaks.json``, or with fewer chips than the cell asks for, it
exits with code 3 and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--keep-trace', default=None,
                    help='with --trace 1: write a trimmed trace here')
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, 'src', 'repro')):
        print(f'bench/run.py: no src/repro under {ROOT}: the program is not '
              'in this checkout', file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, 'src'), ROOT]
    from bench import harness
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START,
                          keep_trace=args.keep_trace,
                          log=lambda s: print(s, file=sys.stderr, flush=True))
    except harness.NoDevice as e:
        print(f'bench/run.py: {e}', file=sys.stderr)
        return 3
    for name, c in out['checks'].items():
        print(f'check {name}: {c["value"]} (limit {c["limit"]})',
              file=sys.stderr)
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
