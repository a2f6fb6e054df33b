"""The chip benchmark of this repository: cells of BENCHMARK.json, run by
``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``.

Everything that measures lives here: traffic generation, the seeded
weights, the plain references that decide ``correct``, the reduction of
profiler traces to per-layer metrics and the table of chip peaks. The
program under ``src/`` is the system under test; nothing here is imported
by it.
"""
