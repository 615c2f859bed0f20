"""On-chip benchmark of the emulated-GEMM library and its serving stack.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON result line.  Everything a cell needs is
found by name: its configuration (``configs/<config>.json``), its
traffic (``traffic/<cell>.json``), its driver (``drivers/<kind>.py``)
and its per-layer metrics (``metrics/<metric>.py``).
"""
