"""Find an open-loop cell's knee on the chip: one set-up, several rates.

    python3 chipbench/sweep.py --workload geonames.lookup.open --seed 5 \
        --seconds 10 --rates 400 800 1600 3200 1600:30

For each offered rate the cell's mix is sent open-loop for ``--seconds``;
each line gives the completed rate, p50 and p99 from the due time, how the
latency of the window's last quarter compares with its first (a backlog that
grows shows as a ratio well above 1), the generator's lateness, and whether
every answer equals the reference.  The knee is the highest rate whose
completed rate keeps up and whose backlog does not grow.  Not a benchmark
run: the cell's rate is written into its file from these lines.
"""

from __future__ import annotations

import argparse
import asyncio
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
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", nargs="+", required=True,
                    help="offered rates; RATE:SECONDS gives one its own window")
    args = ap.parse_args(argv)

    import numpy as np

    from chipbench import harness, loops, spec, traffic

    cell = spec.load_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.use_compile_cache()
    corpus, store = harness.build(cell, args.seed)
    broker = harness.make_broker(cell, store)
    names = traffic.tenant_names(cell.mix)

    async def sweep():
        rows = []
        async with broker:
            warm, _ = harness.workload(cell, corpus, args.seed, 1.0)
            await loops.burst(broker, names, warm)
            for k, token in enumerate(args.rates):
                rate, _, secs = token.partition(":")
                rate, secs = float(rate), float(secs or args.seconds)
                rng = np.random.default_rng([args.seed, k])
                due = traffic.poisson_arrivals(rate, secs, rng)
                reqs = traffic.draw(corpus.ids, cell.mix, len(due), rng)
                broker.reset_stats()
                t = time.perf_counter()
                log = await loops.open_loop(broker, names, reqs, due,
                                            secs, grace=30.0)
                a = log.arrays()
                ok = ~a["failed"] & ~np.isnan(a["done"])
                lat = np.where(ok, a["done"] - a["due"], np.inf)
                q = max(1, len(lat) // 4)
                row = {
                    "offered_per_s": rate,
                    "seconds": secs,
                    "completed_per_s": float(np.sum(ok & (a["done"] <= secs))
                                             / secs),
                    "p50_ms": float(np.percentile(lat, 50) * 1e3),
                    "p95_ms": float(np.percentile(lat, 95) * 1e3),
                    "p99_ms": float(np.percentile(lat, 99) * 1e3),
                    "last_over_first_quarter_p50": float(
                        np.median(lat[-q:]) / np.median(lat[:q])),
                    "late_p99_ms": float(np.percentile(a["sent"] - a["due"], 99) * 1e3),
                    "late_max_ms": float(np.max(a["sent"] - a["due"]) * 1e3),
                    "failed": int(np.sum(~ok)),
                    "batches": broker.stats()["batches"],
                    "wall_s": time.perf_counter() - t,
                    "check": harness.check(corpus, log),
                }
                print(json.dumps(row), flush=True)
                rows.append(row)
        return rows

    asyncio.run(sweep())
    return 0


if __name__ == "__main__":
    sys.exit(main())
