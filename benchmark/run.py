#!/usr/bin/env python3
"""python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: the agent and the chip live in it.  Prints one JSON object
as the last line of standard output; exits non-zero, with no line, when
jax finds no TPU or another number of chips than the cell asks for.
"""
import time

T_START = time.monotonic()

import argparse                                         # noqa: E402
import json                                             # noqa: E402
import logging                                          # noqa: E402
import os                                               # noqa: E402
import sys                                              # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    # the compile cache sits at one fixed place inside the checkout,
    # whatever the environment says, so that two checkouts share nothing
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, ROOT)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    from benchmark import harness
    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), T_START)
    except harness.Refused as e:
        print(f"benchmark: refused: {e}", file=sys.stderr, flush=True)
        return 2
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
