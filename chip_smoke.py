"""Chip smoke test: one federated training run end to end on a TPU.

Drives the normal path, ``repro.api.build(spec)`` -> ``FedAvgTrainer`` ->
backend, at qwen1.5-0.5b's published widths and depth (24 layers,
d_model 1024, vocab 151936) with random weights from a seed:

    python chip_smoke.py              # one chip (LocalBackend)
    python chip_smoke.py --chips 4    # MeshBackend over four chips only

One chip: 4 clients, 2 per round streamed one client at a time
(``fed.cohort_chunk=1``), int8 uplink (the fused Pallas decompress-reduce),
the paper's decaying-K ``rounds`` schedule (Eq. 10) from K0=4 over 3 rounds,
so K = 4, 4, 3 and two K-bucket shapes compile.

``--chips 4``: the same spec with 4 clients per round on a (4, 1)
data x model mesh, one full-width client per chip in one slab, against the
LocalBackend run of the same spec and seeds (``cohort_chunk=1``) on
device 0.

The script exits non-zero with a message when JAX finds no TPU, when Pallas
kernels would run interpreted, when no Mosaic kernel (``tpu_custom_call``)
is in the round executable, or when a loss is not finite. Its last line on
success is ``{"ok": true, "device": {...}}``. ``--cpu-rehearsal`` runs the
same phases on the CPU with the model cut to ``reduced()``, skips the
device checks and never prints that line.

Timings printed here are smoke timings on whatever the run holds, not a
benchmark.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ROUNDS = 3
#: |final-loss(mesh) - final-loss(local)| bound, relative to the loss. Both
#: runs train the same clients on the same batches from the same weights;
#: they differ only in the order of f32 sums: inside every contraction (the
#: mesh compiles a client-sharded program whose fusions and tiling differ
#: from the one-client program's) and in the aggregate (clients fold one at
#: a time into the slab accumulator on one chip, vs one psum of per-chip
#: partials on the mesh). A reordered sum of n f32 terms moves by about
#: sqrt(n) * 2^-24 relative, 3e-6 for the d_ff = 2816 contractions; 11 SGD
#: steps and the int8 rounding of each delta carry that into the loss. On a
#: TPU v5e the gap was 1.2e-5 relative after round 1 and 5.2e-5 after round
#: 3. A wrong aggregate moves the loss far more: round 1's aggregate step
#: lowers it by 0.61, so a 1% weight error alone shifts it by 6e-3 (5e-4
#: relative).
LOSS_RTOL = 1e-4


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def smoke_spec(reduced: bool, *overrides: str):
    from repro.api import ExperimentSpec
    return ExperimentSpec().with_overrides(
        "model.arch=qwen1.5-0.5b", f"model.reduced={reduced}",
        "data.clients=4", "data.seq_len=128", "data.samples_per_client=16",
        "fed.clients_per_round=2", "fed.cohort_chunk=1",
        f"fed.rounds={ROUNDS}", "fed.k0=4", "fed.k_schedule=rounds",
        "fed.k_quantize=false", "fed.batch_size=4", "fed.eta0=0.05",
        "transport.name=int8", *overrides)


def timed_registry():
    """An ExecutableRegistry that keeps what it compiles and the seconds
    spent lowering and compiling it."""
    from repro.core.engine.round import ExecutableRegistry

    class TimedRegistry(ExecutableRegistry):
        def __init__(self):
            super().__init__()
            self.compiled = {}
            self.compile_s = 0.0

        def get_or_build(self, key, build):
            def timed():
                t = time.perf_counter()
                exe = build()
                self.compile_s += time.perf_counter() - t
                self.compiled[key] = exe
                return exe
            return super().get_or_build(key, timed)

    return TimedRegistry()


def slab_executables(registry):
    return [exe for key, exe in registry.compiled.items() if "slab" in key]


def run_experiment(spec, label: str):
    """Build and run ``spec``; returns (experiment, registry, history)."""
    import jax
    from repro.api import build
    from repro.core.schedules import schedule_preview

    registry = timed_registry()
    exp = build(spec, registry=registry)
    t = time.perf_counter()
    h = exp.run()
    jax.block_until_ready(exp.params)
    first_s = time.perf_counter() - t
    for r, k, loss in zip(h.rounds, h.k, h.train_loss):
        print(f"[{label}] round {r} K={k} loss={loss:.6f}")
    if not all(math.isfinite(x) for x in h.train_loss):
        fail(f"{label}: non-finite round loss {h.train_loss}")
    want_k = schedule_preview(exp.trainer.fed, ROUNDS)
    if list(h.k) != want_k:
        fail(f"{label}: K per round {list(h.k)} != schedule {want_k}")
    print(f"[{label}] {exp.trainer.compile_count} bucket executable(s) "
          f"compiled in {registry.compile_s:.3f} s (lower+compile); "
          f"first run {first_s:.3f} s")
    return exp, registry, h


def param_info(params):
    import jax
    leaves = jax.tree.leaves(params)
    return (sum(int(x.size) for x in leaves),
            sum(int(x.size) * x.dtype.itemsize for x in leaves))


def peak_bytes(device):
    """(peak_bytes_in_use, bytes_limit); Nones where the backend has no
    memory stats (the CPU)."""
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("bytes_limit")


def one_chip(devices, rehearsal: bool) -> None:
    import jax

    exp, registry, h = run_experiment(smoke_spec(rehearsal), "1chip")
    n_params, n_bytes = param_info(exp.params)
    print(f"[1chip] {exp.label}: {n_params} params ({n_bytes} bytes)")

    # steady state: a second run replays the same 3 rounds on warm
    # executables; no compile may land inside the timed window
    compiles = exp.trainer.compile_count
    t = time.perf_counter()
    h2 = exp.run()
    jax.block_until_ready(exp.params)
    per_round = (time.perf_counter() - t) / ROUNDS
    if not all(math.isfinite(x) for x in h2.train_loss):
        fail(f"warm run: non-finite round loss {h2.train_loss}")
    if exp.trainer.compile_count != compiles:
        fail(f"warm run compiled {exp.trainer.compile_count - compiles} "
             f"executable(s) inside the timed window")
    print(f"[1chip] smoke timing (not a benchmark): {per_round:.4f} s per "
          f"round after warm-up, {ROUNDS} rounds, block_until_ready")
    peak, limit = peak_bytes(devices[0])
    print(f"[1chip] peak_bytes_in_use: {peak} of bytes_limit {limit}")

    mosaic = any("tpu_custom_call" in exe.as_text()
                 for exe in slab_executables(registry))
    print(f"[1chip] round executable contains tpu_custom_call: {mosaic}")
    if not mosaic and not rehearsal:
        fail("no tpu_custom_call in the round executable: the Pallas "
             "int8 decompress-reduce did not lower to Mosaic")


def four_chips(devices, rehearsal: bool) -> None:
    import gc

    if len(devices) != 4:
        fail(f"--chips 4 needs 4 devices, JAX found {len(devices)}")
    mesh_spec = smoke_spec(rehearsal, "fed.clients_per_round=4",
                           "fed.cohort_chunk=4", "backend.name=mesh",
                           "backend.strategy=parallel")
    exp, registry, h_mesh = run_experiment(mesh_spec, "mesh")
    _, n_bytes = param_info(exp.params)
    slabs = slab_executables(registry)
    text = "\n".join(exe.as_text() for exe in slabs)
    print(f"[mesh] slab executable contains all-reduce: "
          f"{'all-reduce' in text}")
    if "all-reduce" not in text:
        fail("no all-reduce in the mesh slab executable: the sharded "
             "int8 reduce did not cross chips")
    # one full-width client per chip: the slab's client axis must split
    # into one distinct client on each of the four devices
    for exe in slabs:
        x_sharding = exe.input_shardings[0][1]["x"]
        shape = exe.args_info[0][1]["x"].shape
        owner = {d.id: idx[0] for d, idx in
                 x_sharding.devices_indices_map(shape).items()}
        clients = sorted((s.start, s.stop) for s in owner.values())
        print(f"[mesh] client slices per device id: "
              f"{ {d: (s.start, s.stop) for d, s in sorted(owner.items())} }")
        if clients != [(i, i + 1) for i in range(4)]:
            fail(f"clients are not one per device: {owner}")
    peaks = []
    for d in devices:
        peak, limit = peak_bytes(d)
        peaks.append(peak)
        print(f"[mesh] device {d.id} peak_bytes_in_use: {peak} of "
              f"bytes_limit {limit}")
    if not rehearsal and any(p is None or p < n_bytes for p in peaks):
        fail(f"a device peaked below one parameter copy ({n_bytes} bytes): "
             f"{peaks}")
    del exp
    gc.collect()

    local_spec = smoke_spec(rehearsal, "fed.clients_per_round=4")
    _, _, h_local = run_experiment(local_spec, "local")
    a, b = h_mesh.train_loss[-1], h_local.train_loss[-1]
    tol = LOSS_RTOL * abs(b)
    print(f"[mesh] final loss mesh={a:.7f} local={b:.7f} "
          f"|diff|={abs(a - b):.3e} tolerance={tol:.3e}")
    if not abs(a - b) <= tol:
        fail(f"mesh and local final losses differ by {abs(a - b):.3e} > "
             f"{tol:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip run; 4: only the mesh phase")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU at reduced() size; never reports ok")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repro package is not next to this script ({e})")
    print(f"persistent compile cache: {enable_compile_cache()}")

    import jax
    from repro.kernels import ops

    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if not args.cpu_rehearsal:
        if dev.platform != "tpu":
            fail(f"JAX found platform {dev.platform!r}, not a TPU")
        if ops.interpret_mode():
            fail("Pallas kernels would run in interpret mode")

    if args.chips == 4:
        four_chips(devices, args.cpu_rehearsal)
    else:
        one_chip(devices, args.cpu_rehearsal)
    print(f"persistent compile cache: {cache_events['hits']} hit(s), "
          f"{cache_events['misses']} miss(es)")
    if args.cpu_rehearsal:
        print("cpu rehearsal finished: not a chip run, no result reported")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
