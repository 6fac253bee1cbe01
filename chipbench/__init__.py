"""On-chip serving benchmark of the residue (RNS) serve path.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON result line.  Everything that defines a
cell is data found by name: ``configs/<config>.json``,
``traffic/<mix>.json``, ``cells/<cell>.json`` and one reader per
per-layer metric in ``metrics/``.
"""
