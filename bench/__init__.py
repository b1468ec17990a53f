"""The on-chip benchmark of the ELIS serving stack.

``python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is started on
and prints one JSON line.  Everything that measures lives here, apart from
the program: traffic generation, the plain reference and the comparison
that decides ``correct``, the FLOP and byte counts, the peaks table, the
trace reduction and the per-layer metric readers.
"""
