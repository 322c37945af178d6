"""The chip benchmark: one cell per entry of BENCHMARK.json's `workloads`.

`python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell on the accelerator and prints one JSON result line.
"""
