"""On-chip benchmark of the k²-triples serve path.

One run: ``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``.  Everything that belongs to one corpus, traffic mix, cell or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it (see ``spec.py``).
"""
