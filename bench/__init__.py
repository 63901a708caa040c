"""Benchmark of the emulated complex GEMMs on the chip (see BENCHMARK.json).

Run one cell from the root of a checkout:

    python3 bench/run.py --workload cgemm_fast_n7.sq8192 --seed 1 \
        --seconds 20 --trace 0

Everything that belongs to one configuration, traffic mix, metric or entry
point sits in a file of its own (`configs/`, `traffic/`, `metrics/`,
`drivers/`), found by the name that BENCHMARK.json gives it.
"""
