"""Fig. 1 / Fig. 2 / Table 4 analogue: FedAvg schedule comparison.

Runs every schedule of Table 3 on synthetic non-IID versions of the paper's
tasks under the paper's runtime model (Eq. 5, Table 1/2 constants), and
reports: min training loss within the time budget (Fig. 1), best validation
accuracy (Fig. 2), and SGD steps relative to K-eta-fixed (Table 4).

Also benchmarks the K-bucketed round engine against the seed per-round loop
(``engine_*`` rows): real rounds/sec speedup and compile count vs. the
K-quantization grid bound (DESIGN.md §6.4).

Schedule, transport and downlink rows construct their trainers through the
declarative ``ExperimentSpec`` front door (``build(spec)``), not hand-built
``FedConfig``/``FedAvgTrainer`` wiring — the spec is the configuration
artifact (ROADMAP: benchmarks stop hand-building trainers). The engine
speedup/backend rows still construct directly: they measure engine
internals (the seed parity oracle, injected backends) the facade
deliberately does not expose.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import jax
import numpy as np

from repro.api import ExperimentSpec, build
from repro.configs import get_paper_task
from repro.configs.base import FedConfig
from repro.core import (FedAvgTrainer, RuntimeModel, make_eval_fn,
                        quantize_k, run_reference_rounds)
from repro.data import make_paper_task
from repro.launch.mesh import make_mesh
from repro.models import small

SCHEDULES = [
    ("dsgd", "dsgd", "fixed"),
    ("K-eta-fixed", "fixed", "fixed"),
    ("K_r-rounds", "rounds", "fixed"),
    ("K_r-error", "error", "fixed"),
    ("K_r-step", "step", "fixed"),
    ("eta_r-rounds", "fixed", "rounds"),
    ("eta_r-error", "fixed", "error"),
    ("eta_r-step", "fixed", "step"),
]

# CPU-scale round counts (the harness takes --rounds for full runs)
QUICK = dict(rounds=40, clients=30, per_round=8, k0=10, samples=30)


def _task_spec(task_name: str, rounds: int, seed: int) -> ExperimentSpec:
    """The CPU-scale paper-task base spec (QUICK knobs + the task's own
    Table 1/2 runtime constants and eta0, exactly what the hand-built
    ``FedConfig``/``RuntimeModel`` wiring used to assemble)."""
    task = get_paper_task(task_name)
    rt = task.runtime
    return ExperimentSpec().with_overrides(
        "data.kind=paper", f"data.task={task_name}",
        f"data.clients={QUICK['clients']}",
        f"data.samples_per_client={QUICK['samples']}", f"data.seed={seed}",
        f"fed.clients_per_round={QUICK['per_round']}", f"fed.rounds={rounds}",
        f"fed.k0={QUICK['k0']}", f"fed.eta0={task.fed.eta0}",
        f"fed.batch_size={min(task.fed.batch_size, 16)}",
        f"fed.loss_window={max(rounds // 8, 3)}", f"fed.seed={seed}",
        f"runtime.download_mbps={rt.download_mbps}",
        f"runtime.upload_mbps={rt.upload_mbps}",
        f"runtime.beta_seconds={rt.beta_seconds}")


def run_task(task_name: str, rounds: int, *, seed: int = 0,
             verbose: bool = False) -> List[Dict]:
    results = []
    for name, ksch, esch in SCHEDULES:
        spec = _task_spec(task_name, rounds, seed).with_overrides(
            f"fed.k_schedule={ksch}", f"fed.eta_schedule={esch}",
            "fed.plateau_patience=3",
            f"fed.eval_every={max(rounds // 8, 1)}")
        exp = build(spec)      # data/param construction outside the clock
        t0 = time.time()
        h = exp.run()
        rel = h.sgd_steps[-1] / (QUICK["k0"] * rounds * QUICK["per_round"])
        results.append({
            "task": task_name, "schedule": name,
            "min_train_loss": h.min_train_loss[-1],
            "max_val_acc": h.max_val_acc[-1] if h.max_val_acc else 0.0,
            "sim_wall_clock_s": h.wall_clock_s[-1],
            "uplink_mbit": h.uplink_mbit[-1],
            "downlink_mbit": h.downlink_mbit[-1],
            "relative_sgd_steps": rel,
            "bench_s": time.time() - t0,
        })
        if verbose:
            r = results[-1]
            print(f"  {task_name:12s} {name:12s} loss={r['min_train_loss']:.4f} "
                  f"acc={r['max_val_acc']:.3f} W={r['sim_wall_clock_s']:.0f}s "
                  f"rel_steps={rel:.2f} up={r['uplink_mbit']:.0f}mbit")
    return results


def run_engine_speedup(rounds: int = 200, *, task_name: str = "sent140",
                       clients_per_round: int = 4, batch_size: int = 4,
                       prefetch: bool = False, seed: int = 0,
                       verbose: bool = False) -> Dict:
    """K-bucketed engine vs. seed loop on the ``rounds`` K-decay schedule.

    The default config is the dispatch-bound regime the bucketing targets:
    small per-round payloads over a long horizon — where per-round python,
    dispatch and the seed loop's blocking per-round loss sync dominate.
    (The background prefetch thread targets the opposite, compute-bound
    regime — see ``run_prefetch_overlap`` — so it is off here.)

    Both loops run twice and the second (warm-executable) pass is timed, so
    the numbers are steady-state rounds/sec — the regime long federated runs
    live in — not XLA compile time.  Also reports the engine's compile count
    against its bound, the K-quantization grid size (DESIGN.md §6.4)."""
    task = get_paper_task(task_name)
    data = make_paper_task(task_name, np.random.default_rng(seed),
                           num_clients=QUICK["clients"],
                           samples_per_client=QUICK["samples"])
    loss_fn = lambda p, b: small.task_loss(p, task, b)
    fed = FedConfig(total_clients=data.num_clients,
                    clients_per_round=clients_per_round, rounds=rounds,
                    k0=QUICK["k0"], eta0=task.fed.eta0,
                    batch_size=batch_size, k_schedule="rounds",
                    k_quantize=True, prefetch=prefetch, seed=seed)
    grid = len({quantize_k(k, fed.k0) for k in range(1, fed.k0 + 1)})
    params0 = small.init_task_model(jax.random.PRNGKey(seed), task)

    ref = run_reference_rounds(loss_fn, params0, data, fed, rounds)  # warm-up
    seed_compiles = len(set(ref.ks))
    t0 = time.time()
    run_reference_rounds(loss_fn, params0, data, fed, rounds,
                         round_fn=ref.round_fn)
    seed_s = time.time() - t0

    rt = RuntimeModel(task.model_size_mb, task.runtime, fed.clients_per_round)
    tr = FedAvgTrainer(loss_fn, params0, data, fed, rt)
    tr.run(rounds)                                                  # warm-up
    t0 = time.time()
    tr.run(rounds)     # loss-free schedule: identical K trajectory, warm jit
    engine_s = time.time() - t0

    out = {"rounds": rounds, "seed_s": seed_s, "engine_s": engine_s,
           "speedup": seed_s / engine_s,
           "seed_rps": rounds / seed_s, "engine_rps": rounds / engine_s,
           "compile_count": tr.compile_count, "seed_compiles": seed_compiles,
           "k_grid_size": grid}
    if verbose:
        print(f"  engine_bucketed[{task_name}]: {out['engine_rps']:.1f} "
              f"rounds/s vs seed {out['seed_rps']:.1f} rounds/s "
              f"({out['speedup']:.2f}x); compiles {out['compile_count']} <= "
              f"grid {grid} (seed loop: {seed_compiles})")
    return out


def run_backend_compare(rounds: int = 60, *, task_name: str = "sent140",
                        clients_per_round: int = 4, batch_size: int = 4,
                        seed: int = 0, verbose: bool = False) -> List[Dict]:
    """Local vs mesh ExecutionBackend on the same K-decay run (DESIGN.md §7).

    Both backends drive the identical FedAvgTrainer/K-bucketed scan; the
    mesh rows run on the host-device (devices x 1) data x model mesh —
    degenerate on 1 CPU device, but the same GSPMD/jit path a pod takes.
    Reports warm rounds/sec plus dispatch and compile counts, so the
    K-bucket amortisation (dispatches << rounds) is visible on both paths.
    """
    from repro.core.engine import MeshBackend

    task = get_paper_task(task_name)
    data = make_paper_task(task_name, np.random.default_rng(seed),
                           num_clients=QUICK["clients"],
                           samples_per_client=QUICK["samples"])
    loss_fn = lambda p, b: small.task_loss(p, task, b)
    params0 = small.init_task_model(jax.random.PRNGKey(seed), task)
    rt = RuntimeModel(task.model_size_mb, task.runtime, clients_per_round)
    mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    backends = [
        ("local", lambda: None),
        ("mesh_parallel", lambda: MeshBackend(mesh, strategy="parallel")),
        ("mesh_sequential", lambda: MeshBackend(mesh, strategy="sequential",
                                                groups=2)),
    ]
    out = []
    for name, mk in backends:
        fed = FedConfig(total_clients=data.num_clients,
                        clients_per_round=clients_per_round, rounds=rounds,
                        k0=QUICK["k0"], eta0=task.fed.eta0,
                        batch_size=batch_size, k_schedule="rounds",
                        k_quantize=True, seed=seed)
        tr = FedAvgTrainer(loss_fn, params0, data, fed, rt, backend=mk())
        tr.run(rounds)                                          # warm-up
        d0 = tr.engine.dispatch_count
        t0 = time.time()
        tr.run(rounds)
        dt = time.time() - t0
        row = {"backend": name, "rounds": rounds, "bench_s": dt,
               "rps": rounds / dt, "dispatches": tr.engine.dispatch_count - d0,
               "compiles": tr.compile_count}
        out.append(row)
        if verbose:
            print(f"  engine_backend[{name}]: {row['rps']:.1f} rounds/s, "
                  f"{row['dispatches']} dispatches / {rounds} rounds, "
                  f"{row['compiles']} compiles")
    return out


def run_transport_compare(rounds: int = 30, *, task_name: str = "femnist",
                          topk_frac: float = 0.05, seed: int = 0,
                          verbose: bool = False) -> List[Dict]:
    """Delta-transport codecs on the decaying-K schedule (DESIGN.md §8).

    Same task/schedule/seed per codec; reports final + min training loss
    (the 'matched final loss' contract — int8's error-feedback keeps it at
    the uncompressed loss), total modelled bytes-on-wire, the uplink
    reduction vs ``none``, and the modelled Eq. 5 wall-clock — the wire is
    a first-class axis of the decayed-K comparison now, not just FLOPs.
    Single-level int8 rides ~1.0003 bytes/param (value plane + one f32
    scale per leaf), i.e. the full 4x vs f32 up to per-leaf metadata.
    """
    out: List[Dict] = []
    for name in ("none", "int8", "topk"):
        spec = _task_spec(task_name, rounds, seed).with_overrides(
            "fed.k_schedule=rounds", "fed.k_quantize=true",
            f"transport.name={name}", f"transport.topk_frac={topk_frac}")
        exp = build(spec)      # data/param construction outside the clock
        t0 = time.time()
        h = exp.run()
        out.append({
            "transport": name, "task": task_name,
            "final_loss": h.train_loss[-1],
            "min_train_loss": h.min_train_loss[-1],
            "uplink_mbit": h.uplink_mbit[-1],
            "uplink_x": out[0]["uplink_mbit"] / h.uplink_mbit[-1]
            if out else 1.0,
            "dloss": h.train_loss[-1] - out[0]["final_loss"] if out else 0.0,
            "sim_wall_clock_s": h.wall_clock_s[-1],
            "bench_s": time.time() - t0,
        })
        if verbose:
            r = out[-1]
            print(f"  transport[{name:5s}] {task_name}: "
                  f"loss={r['final_loss']:.4f} (d={r['dloss']:+.4f}) "
                  f"uplink={r['uplink_mbit']:.0f}mbit "
                  f"({r['uplink_x']:.2f}x less) "
                  f"W={r['sim_wall_clock_s']:.0f}s")
    return out


def run_downlink_compare(rounds: int = 30, *, task_name: str = "femnist",
                         seed: int = 0, verbose: bool = False) -> List[Dict]:
    """Downlink broadcast codecs on the int8-uplink decayed-K config
    (DESIGN.md §8.6): same task/schedule/seed per row, only
    ``transport.downlink`` varies. Reports modelled downlink bytes-on-wire,
    the reduction vs the uncompressed broadcast (int8's delta-vs-reference
    payload is the full ~4x, so the ≥3x acceptance bar clears with
    metadata to spare), the Eq. 5 wall-clock, and the final-loss delta —
    the matched-final-loss contract is |dloss| <= 2% relative (the
    downlink EF residual recovers the quantisation error across rounds;
    rtol documented in DESIGN.md §8.6).

    The ``adaptive`` row exercises the per-round skip/int8/int8x2 policy
    (DESIGN.md §10) and the ``int8+q8ref`` row the quantised server-side
    reference store — ``state_mb`` reports the server bytes held for the
    broadcast state, the quantity q8 halves."""
    out: List[Dict] = []
    cases = (("none", ()), ("int8", ()), ("topk", ()), ("adaptive", ()),
             ("int8+q8ref", ("transport.ref_store=q8",)))
    for label, extra in cases:
        name = label.split("+")[0]
        spec = _task_spec(task_name, rounds, seed).with_overrides(
            "fed.k_schedule=rounds", "fed.k_quantize=true",
            "transport.name=int8", f"transport.downlink={name}", *extra)
        exp = build(spec)      # data/param construction outside the clock
        t0 = time.time()
        h = exp.run()
        dl = exp.trainer.engine.downlink
        state_mb = (dl.state_bytes(exp.trainer.engine.downlink_state) / 1e6
                    if dl is not None else 0.0)
        out.append({
            "downlink": label, "task": task_name,
            "final_loss": h.train_loss[-1],
            "min_train_loss": h.min_train_loss[-1],
            "uplink_mbit": h.uplink_mbit[-1],
            "downlink_mbit": h.downlink_mbit[-1],
            "downlink_x": out[0]["downlink_mbit"] / h.downlink_mbit[-1]
            if out and h.downlink_mbit[-1] else 1.0,
            "dloss": h.train_loss[-1] - out[0]["final_loss"] if out else 0.0,
            "state_mb": state_mb,
            "sim_wall_clock_s": h.wall_clock_s[-1],
            "bench_s": time.time() - t0,
        })
        if verbose:
            r = out[-1]
            print(f"  downlink[{label:10s}] {task_name}: "
                  f"loss={r['final_loss']:.4f} (d={r['dloss']:+.4f}) "
                  f"downlink={r['downlink_mbit']:.0f}mbit "
                  f"({r['downlink_x']:.2f}x less) "
                  f"state={r['state_mb']:.2f}MB "
                  f"W={r['sim_wall_clock_s']:.0f}s")
    return out


def run_prefetch_overlap(rounds: int = 48, *, seed: int = 0,
                         verbose: bool = False) -> Dict:
    """Background prefetch thread vs. the inline builder on a compute-bound
    config (large batches, fixed K0, periodic eval).

    Expected ≈1.0x on CPU: async dispatch already hides the depth-1 inline
    build behind the previous bucket's device work, so this row is an
    overhead check — the thread must not cost throughput.  Its value is the
    double-buffering contract for regimes where the main thread blocks
    (frequent feedback syncs, blocking dispatch) — see DESIGN.md §6.5/§6.6."""
    task = get_paper_task("femnist")
    data = make_paper_task("femnist", np.random.default_rng(seed),
                           num_clients=QUICK["clients"],
                           samples_per_client=QUICK["samples"])
    loss_fn = lambda p, b: small.task_loss(p, task, b)
    params0 = small.init_task_model(jax.random.PRNGKey(seed), task)
    rt = RuntimeModel(task.model_size_mb, task.runtime, 8)
    eval_fn = make_eval_fn(loss_fn, data)
    trainers = {}
    for prefetch in (False, True):
        fed = FedConfig(total_clients=data.num_clients, clients_per_round=8,
                        rounds=rounds, k0=QUICK["k0"], eta0=task.fed.eta0,
                        batch_size=32, k_schedule="fixed",
                        prefetch=prefetch, seed=seed)
        tr = FedAvgTrainer(loss_fn, params0, data, fed, rt, eval_fn=eval_fn)
        tr.run(rounds, eval_every=8)                                # warm-up
        trainers[prefetch] = tr
    times = {False: [], True: []}
    for _ in range(3):                     # alternate legs; min vs host noise
        for prefetch in (False, True):
            t0 = time.time()
            trainers[prefetch].run(rounds, eval_every=8)
            times[prefetch].append(time.time() - t0)
    out = {"rounds": rounds, "sync_s": min(times[False]),
           "prefetch_s": min(times[True]),
           "speedup": min(times[False]) / min(times[True])}
    if verbose:
        print(f"  prefetch_overlap: {rounds / out['prefetch_s']:.1f} rounds/s "
              f"vs sync {rounds / out['sync_s']:.1f} rounds/s "
              f"({out['speedup']:.2f}x)")
    return out


def run_cohort_stream(rounds: int = 6, *, task_name: str = "femnist",
                      clients: int = 64, clients_per_round: int = 32,
                      chunk: int = 4, seed: int = 0,
                      verbose: bool = False) -> List[Dict]:
    """Streaming cohorts vs the dense round (DESIGN.md §11).

    Same task/schedule/seed; the dense row runs the whole U-client cohort
    as one executable (bucket_rounds=1 so both rows hold exactly one
    round's tensors — the comparison is round-shape vs slab-shape, not
    bucket amortisation), the chunked row streams it as U/C slabs. Rows
    report warm rounds/sec and ``peakMB`` — the engine executables' live
    device bytes (arguments + outputs + XLA temp high-water mark, measured
    from ``memory_analysis``, ``repro.core.mem``). The chunked row's peak
    must sit >= 4x under dense at U/C = 8 (the CI cohort_scaling gate rides
    the same measurement). The population row runs the identical chunked
    config against 10^6 virtual client ids (population sampler +
    PopulationView) — O(cohort) host state, same slab-bounded device peak.
    """
    from repro.core import trainer_peak_mb

    base = _task_spec(task_name, rounds, seed).with_overrides(
        f"data.clients={clients}", "fed.k_schedule=rounds",
        "fed.k_quantize=true", f"fed.clients_per_round={clients_per_round}",
        "fed.bucket_rounds=1", "fed.eval_every=0")
    cases = (
        ("dense", ()),
        (f"chunk{chunk}", (f"fed.cohort_chunk={chunk}",)),
        ("population", (f"fed.cohort_chunk={chunk}",
                        "sampler.name=population",
                        "sampler.population=1000000")),
    )
    out: List[Dict] = []
    for label, extra in cases:
        exp = build(base.with_overrides(*extra))
        exp.run()                                               # warm-up
        t0 = time.time()
        h = exp.run()
        dt = time.time() - t0
        peak = trainer_peak_mb(exp.trainer)
        out.append({
            "case": label, "task": task_name, "rounds": rounds,
            "bench_s": dt, "rps": rounds / dt, "peak_mb": peak,
            "peak_x": out[0]["peak_mb"] / peak if out and peak else 1.0,
            "final_loss": h.train_loss[-1],
        })
        if verbose:
            r = out[-1]
            print(f"  cohort_stream[{label:10s}] {task_name}: "
                  f"{r['rps']:.1f} rounds/s peak={peak:.2f}MB "
                  f"({r['peak_x']:.2f}x less) loss={r['final_loss']:.4f}")
    return out


def run_sampler_compare(rounds: int = 30, *, task_name: str = "femnist",
                        seed: int = 0, verbose: bool = False) -> List[Dict]:
    """Client-sampling policies (DESIGN.md §9.3) on one task, constructed
    through the declarative API (``build(spec)``): uniform is the paper
    baseline, weighted biases toward data-rich clients, fixed_cohort is the
    cross-silo regime (per-client EF when combined with an EF transport),
    availability simulates device churn. Rows double as a facade check —
    ``build`` must add no measurable overhead over direct construction."""
    from repro.api import ExperimentSpec, build

    base = ExperimentSpec().with_overrides(
        "data.kind=paper", f"data.task={task_name}",
        f"data.clients={QUICK['clients']}",
        f"data.samples_per_client={QUICK['samples']}", f"data.seed={seed}",
        f"fed.rounds={rounds}", "fed.clients_per_round=8",
        f"fed.k0={QUICK['k0']}", "fed.eta0=0.3", "fed.batch_size=8",
        "fed.k_schedule=rounds", "fed.loss_window=5", f"fed.seed={seed}",
        "runtime.beta_seconds=0.05")
    out = []
    for sampler, extra in (("uniform", ()),
                           ("weighted", ()),
                           ("fixed_cohort", ("transport.name=int8",)),
                           ("availability", ("sampler.availability=0.6",))):
        spec = base.with_overrides(f"sampler.name={sampler}", *extra)
        exp = build(spec)
        t0 = time.time()
        h = exp.run()
        dt = time.time() - t0
        ef = getattr(exp.trainer.engine.transport, "ef_slots", None)
        out.append({"sampler": sampler, "task": task_name, "bench_s": dt,
                    "rps": rounds / dt, "final_loss": h.train_loss[-1],
                    "ef_slots": ef or 0})
        if verbose:
            print(f"  sampler[{sampler}]: {rounds / dt:.1f} rounds/s "
                  f"loss={h.train_loss[-1]:.4f}"
                  + (f" per-client-EF x{ef}" if ef else ""))
    return out


def run(tasks=("sent140", "femnist"), rounds=None,
        verbose=True) -> List[Tuple[str, float, str]]:
    rows = []
    for t in tasks:
        for r in run_task(t, rounds or QUICK["rounds"], verbose=verbose):
            rows.append((f"fig12_{r['task']}_{r['schedule']}",
                         r["bench_s"] * 1e6,
                         f"loss={r['min_train_loss']:.4f};"
                         f"acc={r['max_val_acc']:.3f};"
                         f"relsteps={r['relative_sgd_steps']:.3f};"
                         f"simW={r['sim_wall_clock_s']:.0f}s;"
                         f"upMbit={r['uplink_mbit']:.1f};"
                         f"downMbit={r['downlink_mbit']:.1f}"))
    e = run_engine_speedup(rounds=rounds or 200, verbose=verbose)
    rows.append(("engine_bucketed_vs_seed", e["engine_s"] * 1e6,
                 f"speedup={e['speedup']:.2f}x;"
                 f"rps={e['engine_rps']:.1f};"
                 f"compiles={e['compile_count']};"
                 f"grid={e['k_grid_size']}"))
    for b in run_backend_compare(rounds=rounds or 60, verbose=verbose):
        rows.append((f"engine_backend_{b['backend']}", b["bench_s"] * 1e6,
                     f"rps={b['rps']:.1f};"
                     f"dispatches={b['dispatches']};"
                     f"compiles={b['compiles']}"))
    for t in run_transport_compare(rounds=rounds or 30, verbose=verbose):
        rows.append((f"transport_{t['transport']}_{t['task']}",
                     t["bench_s"] * 1e6,
                     f"uplink_x={t['uplink_x']:.2f};"
                     f"loss={t['final_loss']:.4f};"
                     f"dloss={t['dloss']:+.4f};"
                     f"simW={t['sim_wall_clock_s']:.0f}s;"
                     f"upMbit={t['uplink_mbit']:.1f}"))
    for t in run_downlink_compare(rounds=rounds or 30, verbose=verbose):
        rows.append((f"downlink_{t['downlink']}_{t['task']}",
                     t["bench_s"] * 1e6,
                     f"downlink_x={t['downlink_x']:.2f};"
                     f"loss={t['final_loss']:.4f};"
                     f"dloss={t['dloss']:+.4f};"
                     f"stateMB={t['state_mb']:.2f};"
                     f"simW={t['sim_wall_clock_s']:.0f}s;"
                     f"upMbit={t['uplink_mbit']:.1f};"
                     f"downMbit={t['downlink_mbit']:.1f}"))
    for s in run_sampler_compare(rounds=rounds or 30, verbose=verbose):
        rows.append((f"sampler_{s['sampler']}_{s['task']}",
                     s["bench_s"] * 1e6,
                     f"rps={s['rps']:.1f};"
                     f"loss={s['final_loss']:.4f};"
                     f"efSlots={s['ef_slots']}"))
    for c in run_cohort_stream(rounds=min(rounds or 6, 6), verbose=verbose):
        rows.append((f"cohort_stream_{c['case']}_{c['task']}",
                     c["bench_s"] * 1e6,
                     f"rps={c['rps']:.1f};"
                     f"peakMB={c['peak_mb']:.2f};"
                     f"peak_x={c['peak_x']:.2f};"
                     f"loss={c['final_loss']:.4f}"))
    p = run_prefetch_overlap(rounds=rounds or 48, verbose=verbose)
    rows.append(("engine_prefetch_overlap", p["prefetch_s"] * 1e6,
                 f"speedup={p['speedup']:.2f}x;"
                 f"rps={p['rounds'] / p['prefetch_s']:.1f}"))
    return rows


def write_csv(rows: List[Tuple[str, float, str]], path: str) -> None:
    """CSV with bytes-on-wire and peak device memory as first-class columns
    (parsed back out of the ``upMbit=``/``downMbit=``/``peakMB=`` derived
    fields; empty for rows that don't measure them)."""
    import csv

    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["name", "us_per_call", "uplink_mbit", "downlink_mbit",
                    "peak_mb", "derived"])
        for name, us, derived in rows:
            up = down = peak = ""
            for part in derived.split(";"):
                if part.startswith("upMbit="):
                    up = part.split("=", 1)[1]
                elif part.startswith("downMbit="):
                    down = part.split("=", 1)[1]
                elif part.startswith("peakMB="):
                    peak = part.split("=", 1)[1]
            w.writerow([name, f"{us:.1f}", up, down, peak, derived])


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rounds", type=int, default=None,
                    help="rounds per run (small values = CI smoke)")
    ap.add_argument("--tasks", nargs="*", default=["sent140"])
    ap.add_argument("--csv", default=None,
                    help="also write the rows (incl. bytes-on-wire column) "
                         "to this CSV file")
    ap.add_argument("--quiet", action="store_true")
    a = ap.parse_args()
    all_rows = run(tasks=tuple(a.tasks), rounds=a.rounds,
                   verbose=not a.quiet)
    for name, us, derived in all_rows:
        print(f"{name},{us:.1f},{derived}")
    if a.csv:
        write_csv(all_rows, a.csv)
