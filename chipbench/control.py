"""The control of ``correct``, read on the chip: it has to come out false.

    python3 chipbench/control.py --workload geonames.lookup.open \
        geonames.lookup.closed --seconds 10 --seeds 11 12 13

Runs each cell as ``run.py`` does, on each seed in turn in one process (the
cells of one config share the seed's store), with
the store served at ``faults.CONTROL_CAP`` and the overflow bit ignored, so
lanes that do not fit answer truncated.  Each seed prints its result line;
the ``checks`` there are the control's readings, the upper ends of the
limits in ``PERF.md``.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from chipbench import faults, harness, spec

    cells = [faults.control_cell(spec.load_cell(w)) for w in args.workload]
    if len({c.config_name for c in cells}) != 1:
        raise SystemExit("the cells of one call share one config")
    harness.require_chips(max(c.chips for c in cells))
    harness.use_compile_cache()
    for seed in args.seeds:
        built = harness.build(cells[0], seed)
        for cell in cells:
            with faults.control():
                result = harness.measure(cell, seed, args.seconds, False,
                                         time.perf_counter(), built=built)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "control": True, **result}), flush=True)
        del built
    return 0


if __name__ == "__main__":
    sys.exit(main())
