"""Production federated-training launcher, driven by an ExperimentSpec.

Two front doors, one composition root (``repro.api.build``):

  * declarative — ``--spec examples/specs/local-int8-decayK.json`` plus any
    number of ``--set section.field=value`` dotted-path overrides;
  * legacy flags — the historical ``--arch/--rounds/--k-schedule/...``
    surface, now a thin translation layer that builds the SAME spec
    (bitwise-identical runs to the pre-spec launcher).

The resolved spec is printed before the run (and is itself valid ``--spec``
input), so every invocation leaves a reproducible artifact. With
``--checkpoint`` the final state is saved with the spec embedded —
``FederatedExperiment.restore(path)`` rebuilds the exact trainer.

    PYTHONPATH=src python -m repro.launch.train --spec run.json \\
        --set fed.rounds=100 --set transport.name=topk
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \\
        --rounds 50 --k-schedule rounds --checkpoint /tmp/ckpt
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.api import ExperimentSpec, build
from repro.configs import ARCHS
from repro.launch.compile_cache import enable_compile_cache


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    # --- declarative front door -------------------------------------
    ap.add_argument("--spec", default=None, metavar="FILE.json",
                    help="load a full ExperimentSpec; legacy flags below "
                         "are ignored except --rounds/--checkpoint")
    ap.add_argument("--set", action="append", default=[], metavar="PATH=V",
                    dest="overrides",
                    help="dotted-path spec override, repeatable "
                         "(e.g. --set fed.k0=4 --set transport.name=int8)")
    ap.add_argument("--sweep", nargs="+", default=[], metavar="PATH=V1,V2",
                    help="fan the resolved spec over a sweep grid and run "
                         "it as a packed fleet (repro.launch.fleet), e.g. "
                         "--sweep fed.k0=2,4,8 transport.name=int8,topk")
    ap.add_argument("--sweep-csv", default=None, metavar="FILE.csv",
                    help="write the fleet leaderboard CSV here (--sweep)")
    ap.add_argument("--share-k-grid", action="store_true",
                    help="with --sweep: pin one fed.k_grid0 anchor so k0 "
                         "points share bucket executables")
    ap.add_argument("--serial-sweep", action="store_true",
                    help="with --sweep: run points serially instead of "
                         "packed")
    # --- legacy flags (translated to a spec) ------------------------
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="cut the arch to the smoke-test shape "
                         "(configs/base.py reduced()); --no-reduced runs "
                         "its published widths and depth")
    ap.add_argument("--rounds", type=int, default=None,
                    help="round count (also applies on top of --spec)")
    ap.add_argument("--clients", type=int, default=24)
    ap.add_argument("--clients-per-round", type=int, default=6)
    ap.add_argument("--k0", type=int, default=8)
    ap.add_argument("--eta0", type=float, default=0.05)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--k-schedule", default="rounds",
                    choices=("fixed", "rounds", "error", "step", "cosine", "dsgd"))
    ap.add_argument("--eta-schedule", default="fixed",
                    choices=("fixed", "rounds", "error", "step"))
    ap.add_argument("--k-quantize", action="store_true")
    ap.add_argument("--server-optimizer", default="avg",
                    choices=("avg", "fedadam", "fedavgm", "fedyogi"))
    ap.add_argument("--aggregator", default="mean",
                    choices=("mean", "kernel", "median", "trimmed_mean"))
    ap.add_argument("--transport", default="none",
                    choices=("none", "int8", "int8x2", "topk"),
                    help="client-delta wire codec (DESIGN.md §8)")
    ap.add_argument("--topk-frac", type=float, default=0.1,
                    help="kept coordinate fraction for --transport topk")
    ap.add_argument("--downlink", default="none",
                    choices=("none", "int8", "int8x2", "topk", "adaptive"),
                    help="server broadcast codec: delta vs the last "
                         "broadcast reference (DESIGN.md §8.6; 'adaptive' "
                         "picks skip/int8/int8x2 per round, §10)")
    ap.add_argument("--ref-store", default="f32", choices=("f32", "q8"),
                    help="server-held downlink reference/residual store "
                         "(q8: two-level int8, ~2x less state, §10.3)")
    ap.add_argument("--aggregation", default="sync",
                    choices=("sync", "async"),
                    help="server aggregation policy: round-synchronous "
                         "FedAvg or FedBuff-style async buffering on the "
                         "simulated event clock (DESIGN.md §13)")
    ap.add_argument("--buffer-size", type=int, default=None,
                    help="async: apply the buffer after this many client "
                         "arrivals (default: the cohort size)")
    ap.add_argument("--staleness-weight", default="constant",
                    choices=("constant", "inv", "poly"),
                    help="async: per-arrival contribution scale vs "
                         "staleness s — 1, 1/(1+s), or (1+s)^-0.5")
    ap.add_argument("--max-staleness", type=int, default=None,
                    help="async: drop arrivals staler than this many "
                         "versions (default: keep all)")
    ap.add_argument("--sampler", default="uniform",
                    choices=("uniform", "weighted", "fixed_cohort",
                             "availability"),
                    help="client participation policy (DESIGN.md §9.3)")
    ap.add_argument("--availability", type=float, default=0.9,
                    help="per-round online probability for "
                         "--sampler availability")
    ap.add_argument("--backend", default="local", choices=("local", "mesh"),
                    help="execution backend: single-device or GSPMD mesh")
    ap.add_argument("--strategy", default="parallel",
                    choices=("parallel", "sequential"),
                    help="mesh client fan-out (ignored for --backend local)")
    ap.add_argument("--groups", type=int, default=1,
                    help="sequential-strategy client groups (hierarchical FL)")
    ap.add_argument("--bucket-rounds", type=int, default=8,
                    help="max rounds per jitted K-bucket scan")
    ap.add_argument("--feedback-bucket", type=int, default=1,
                    help="bucket length for error/step schedules")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the background batch prefetch thread")
    ap.add_argument("--serve-every", type=int, default=None,
                    help="serve-while-training: hot-swap the global model "
                         "into a live decode service and tick it every N "
                         "rounds / buffer applies (DESIGN.md §14; also "
                         "applies on top of --spec)")
    ap.add_argument("--serve-qps", type=float, default=None,
                    help="modelled decode queries/sec the server answers "
                         "alongside training (stretches the round clock by "
                         "1/(1-rho); also applies on top of --spec)")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def spec_from_legacy_args(args) -> ExperimentSpec:
    """Translate the historical flag surface into an ExperimentSpec.

    The resulting build reproduces the pre-spec launcher bit-for-bit: same
    data rng seeding, same param init, same FedConfig derivation (including
    the ``loss_window = max(rounds // 8, 3)`` rule and the beta=0.05s
    runtime constant)."""
    rounds = args.rounds if args.rounds is not None else 50
    # Optional async knobs only override when set: the spec refuses
    # buffer_size/max_staleness under aggregation="sync", and None is not
    # expressible as a dotted-path literal.
    async_overrides = [f"fed.aggregation={args.aggregation}",
                       f"fed.staleness_weight={args.staleness_weight}"]
    if args.buffer_size is not None:
        async_overrides.append(f"fed.buffer_size={args.buffer_size}")
    if args.max_staleness is not None:
        async_overrides.append(f"fed.max_staleness={args.max_staleness}")
    return ExperimentSpec().with_overrides(
        f"model.arch={args.arch}", f"model.reduced={args.reduced}",
        f"data.clients={args.clients}", f"data.seq_len={args.seq}",
        f"data.seed={args.seed}",
        f"fed.rounds={rounds}",
        f"fed.clients_per_round={args.clients_per_round}",
        f"fed.k0={args.k0}", f"fed.eta0={args.eta0}",
        f"fed.batch_size={args.batch_size}",
        f"fed.loss_window={max(rounds // 8, 3)}",
        f"fed.k_schedule={args.k_schedule}",
        f"fed.eta_schedule={args.eta_schedule}",
        f"fed.k_quantize={args.k_quantize}",
        f"fed.server_optimizer={args.server_optimizer}",
        f"fed.aggregator={args.aggregator}",
        f"fed.bucket_rounds={args.bucket_rounds}",
        f"fed.feedback_bucket_rounds={args.feedback_bucket}",
        f"fed.prefetch={not args.no_prefetch}",
        f"fed.seed={args.seed}",
        f"sampler.name={args.sampler}",
        f"sampler.availability={args.availability}",
        f"transport.name={args.transport}",
        f"transport.topk_frac={args.topk_frac}",
        f"transport.downlink={args.downlink}",
        f"transport.ref_store={args.ref_store}",
        f"backend.name={args.backend}", f"backend.strategy={args.strategy}",
        f"backend.groups={args.groups}",
        "runtime.beta_seconds=0.05",
        *async_overrides)


def resolve_spec(args) -> ExperimentSpec:
    if args.spec:
        spec = ExperimentSpec.load(args.spec)
        if args.rounds is not None:
            spec = spec.with_overrides(f"fed.rounds={args.rounds}")
    else:
        spec = spec_from_legacy_args(args)
    if args.overrides:
        spec = spec.with_overrides(*args.overrides)
    if args.serve_every is not None:
        spec = spec.with_overrides(f"serve.every={args.serve_every}")
    if args.serve_qps is not None:
        spec = spec.with_overrides(f"serve.qps={args.serve_qps}")
    return spec


def main(argv=None):
    args = make_parser().parse_args(argv)
    spec = resolve_spec(args).validate()
    if args.sweep:
        from repro.launch.fleet import run_fleet
        result = run_fleet(spec, args.sweep, packed=not args.serial_sweep,
                           rounds=args.rounds,
                           share_grid=args.share_k_grid,
                           verbose=True)
        print(result.leaderboard())
        if args.sweep_csv:
            result.to_csv(args.sweep_csv)
            print(f"[train] fleet csv -> {args.sweep_csv}")
        return result
    print("[train] resolved spec:")
    print(spec.to_json())

    exp = build(spec)
    trainer = exp.trainer
    rounds = spec.fed.rounds
    print(f"[train] {exp.label}: K-schedule={spec.fed.k_schedule}, "
          f"eta-schedule={spec.fed.eta_schedule}, "
          f"sampler={spec.sampler.name}, backend={spec.backend.name}")
    if spec.fed.aggregation == "async":
        print(f"[train] aggregation=async: buffer_size="
              f"{trainer.buffer_size}, "
              f"staleness_weight={spec.fed.staleness_weight}, "
              f"max_staleness={spec.fed.max_staleness}")
    engine = getattr(trainer, "engine", None)   # sync-only wire summaries
    transport = engine.transport if engine is not None else trainer.transport
    if transport is not None:
        rt = trainer.runtime
        ef = transport.ef_slots
        print(f"[train] transport={spec.transport.name}: uplink "
              f"{rt.uplink_compression:.2f}x compressed "
              f"({rt.uplink_mbit_per_client:.2f} of {rt.size:.2f} mbit "
              f"per client-round)"
              + (f", per-client EF x{ef}" if ef else ""))
    if engine is not None and engine.downlink is not None:
        rt = trainer.runtime
        print(f"[train] downlink={spec.transport.downlink}: broadcast "
              f"{rt.downlink_compression:.2f}x compressed "
              f"({rt.downlink_mbit_per_client:.2f} of {rt.size:.2f} mbit "
              f"per client-round)")

    h = exp.run()
    # the synchronous trainer's host counters (repro.core.obs spans)
    host = ("" if spec.fed.aggregation == "async" else
            f"; per round {1e3 * trainer.feed_wait_s / rounds:.2f} ms feed "
            f"wait, {1e3 * trainer.dispatch_s / rounds:.2f} ms in dispatch")
    print(f"[train] engine[{spec.backend.name}]: {trainer.compile_count} "
          f"bucket executable(s) compiled, {trainer.dispatch_count} "
          f"dispatch(es) for {rounds} rounds{host}")
    if spec.fed.aggregation == "async":
        print(f"[train] async: {trainer.applied_updates} updates applied, "
              f"{trainer.dropped_updates} dropped, mean staleness "
              f"{float(np.mean(h.staleness)) if h.staleness else 0.0:.2f}, "
              f"event-clock wall {h.wall_clock_s[-1]:.0f}s")
    step = max(rounds // 10, 1)
    for i in range(0, rounds, step):
        print(f"[train] round {h.rounds[i]:4d} K={h.k[i]:3d} "
              f"eta={h.eta[i]:.4f} loss={h.train_loss[i]:.4f} "
              f"simW={h.wall_clock_s[i]:.0f}s steps={h.sgd_steps[i]}")
    if spec.serve.every and h.serve_rounds:
        print(f"[train] serve: {len(h.serve_rounds)} tick(s), "
              f"{float(np.mean(h.serve_tokens_per_sec)):.0f} tok/s mean, "
              f"swap {float(np.mean(h.serve_swap_us)):.0f}us mean, "
              f"staleness <= {max(h.serve_staleness)}, "
              f"served version {trainer.serving.served_version} of "
              f"{trainer.store.version}")
    print(f"[train] final loss {h.train_loss[-1]:.4f} "
          f"(start {h.train_loss[0]:.4f}); total steps {h.sgd_steps[-1]}, "
          f"simulated wall-clock {h.wall_clock_s[-1]:.0f}s, "
          f"uplink {h.uplink_mbit[-1]:.0f} mbit, "
          f"downlink {h.downlink_mbit[-1]:.0f} mbit")
    if args.checkpoint:
        exp.save(args.checkpoint)
        print(f"[train] checkpoint (spec embedded) -> {args.checkpoint}")


if __name__ == "__main__":
    print(f"[train] persistent compile cache: {enable_compile_cache()}")
    main()
