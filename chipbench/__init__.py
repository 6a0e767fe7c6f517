"""On-chip benchmark of the MemCom compress -> serve path.

Run one cell from the root of a checkout::

    python3 chipbench/run.py --workload smollm360m.warm --seed 7 \
        --seconds 30 --trace 0

``BENCHMARK.json`` names the cells; each cell's configuration, traffic
mix and per-layer metric readers are files under this directory, found
by name (see ``chipbench/spec.py``).
"""
