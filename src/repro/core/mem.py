"""Peak-memory accounting for compiled round executables (DESIGN.md §11).

XLA's ``CompiledMemoryStats`` (via ``executable.memory_analysis()``) reports,
per compiled executable, the bytes it holds live: arguments, outputs and the
internal temp buffer high-water mark. For the round engine that IS the
device-memory story — every round/bucket/slab runs as exactly one registry
executable — so "peak HBM of a round" reduces to a max over the engine's
executable registry, measured without running anything.

This is the measurement the chunked-streaming acceptance rides on: a round
of U clients in C-sized slabs must peak at O(C) client state, not O(U)
(``benchmarks/schedules_bench.py`` cohort_stream rows, tests/test_streaming
memory budget).
"""
from __future__ import annotations

from typing import Any

PyTree = Any


def executable_peak_bytes(exe) -> int:
    """Live bytes for one compiled executable: arguments + outputs + the
    temp high-water mark, minus donated/aliased double counting."""
    ma = exe.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def executable_peak_mb(exe) -> float:
    return executable_peak_bytes(exe) / 1e6


def engine_peak_mb(engine) -> float:
    """Max peak MB across a ``RoundEngine``'s compiled executables — the
    device high-water mark a training loop driven by that engine reaches
    (dispatches are sequential; at most one registry executable is live).
    0.0 before anything compiled."""
    peaks = [executable_peak_bytes(e)
             for e in getattr(engine, "_executables", {}).values()]
    return max(peaks) / 1e6 if peaks else 0.0


def trainer_peak_mb(trainer) -> float:
    """``engine_peak_mb`` of a trainer's engine."""
    return engine_peak_mb(trainer.engine)
