"""Chip benchmark for raft_tpu.

``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON result line.  Everything a cell needs is
found by name: its configuration in ``configs/``, its traffic mix in
``traffic/``, its index kind's adapter in ``systems/`` and each per-layer
metric's reader in ``metrics/``.  The yardstick (data generator, exact
reference, comparison, work counts, peaks and trace reduction) lives here
and imports nothing of the library.
"""
