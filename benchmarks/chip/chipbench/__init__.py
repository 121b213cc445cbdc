"""The on-chip benchmark of the federated trainer (``benchmarks/chip``).

Everything that belongs to one configuration, traffic mix, per-layer
metric or cell lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it (``catalog``). This package holds the general
parts: the traffic generator, the weights, the run itself, the reduction
of a profiler trace, the operation and byte counts, and the comparison
with the plain reference that decides ``correct``.
"""
