"""Run one cell of the benchmark on the chip and print its result line.

    python3 chipbench/run.py --workload geonames.lookup.open --seed 7 \
        --seconds 10 --trace 0

Exits non-zero, printing no result, when JAX sees no TPU or fewer chips than
the cell asks for.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (with
``--trace 1`` also ``breakdown``), and last ``checks``, each number compared
with its limit; the same numbers end standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness, spec

    cell = spec.load_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.say(f"compile cache: {harness.use_compile_cache()}")
    result = harness.measure(cell, args.seed, args.seconds, bool(args.trace),
                             T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
