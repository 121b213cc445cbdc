"""Fleet driver — one spec, an override grid, one device budget (§12).

Fans a base ``ExperimentSpec`` over a dotted-path sweep grid and runs every
point through two fleet-wide mechanisms:

  * **cross-experiment executable sharing** — all points compile into one
    process-level ``ExecutableRegistry``; points whose program fingerprint
    (``sweep.spec_program_key`` + mesh slice devices) and bucket input
    signatures coincide compile once and dispatch N times. With
    ``--share-k-grid`` the driver pins one ``fed.k_grid0`` anchor (the max
    ``fed.k0`` in the grid) so a ``fed.k0`` sweep collapses onto one bucket
    signature — 100% executable reuse across points.
  * **one-mesh experiment packing** — points run concurrently, each on its
    own backend slice (``ExecutionBackend.fleet_slices``: sub-meshes carved
    from a MeshBackend's device grid; fresh LocalBackends interleaving on
    the single-device dispatch queue), with per-point prefetch threads
    overlapping host batch builds. Small-model sweeps saturate the device
    instead of serialising warm-up after warm-up.

The result is one consolidated leaderboard/CSV: final/min loss, rounds/sec,
encoded up/down wire, peak executable MB and exact compile/shared/dispatch
counters per point.

    PYTHONPATH=src python -m repro.launch.fleet \\
        --sweep fed.k0=2,4,8 transport.name=int8,topk -- --rounds 20
    PYTHONPATH=src python -m repro.launch.train --spec run.json \\
        --sweep fed.k0=2,4,8 --share-k-grid

Run as a script, it warm-starts across *invocations* from JAX's persistent
compilation cache (``launch/compile_cache.py``), so a repeated fleet skips
XLA compiles entirely.
"""
from __future__ import annotations

import argparse
import csv
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

from repro.api import ExperimentSpec, build
from repro.api.sweep import SweepPoint, expand_sweep, spec_program_key
from repro.core.engine.round import ExecutableRegistry
from repro.core.mem import trainer_peak_mb
from repro.launch.compile_cache import enable_compile_cache

CSV_FIELDS = ("label", "overrides", "final_loss", "min_loss", "rounds",
              "wall_s", "rounds_per_sec", "uplink_mbit", "downlink_mbit",
              "peak_mb", "compiles", "shared", "dispatches")


@dataclass(frozen=True)
class PointResult:
    """One sweep point's consolidated row."""
    label: str
    overrides: Tuple[str, ...]
    spec: ExperimentSpec
    final_loss: float
    min_loss: float
    rounds: int
    wall_s: float
    rounds_per_sec: float
    uplink_mbit: float
    downlink_mbit: float
    peak_mb: float
    compile_count: int
    shared_count: int
    dispatch_count: int

    def as_row(self) -> dict:
        return {"label": self.label, "overrides": " ".join(self.overrides),
                "final_loss": f"{self.final_loss:.6f}",
                "min_loss": f"{self.min_loss:.6f}",
                "rounds": self.rounds, "wall_s": f"{self.wall_s:.3f}",
                "rounds_per_sec": f"{self.rounds_per_sec:.3f}",
                "uplink_mbit": f"{self.uplink_mbit:.2f}",
                "downlink_mbit": f"{self.downlink_mbit:.2f}",
                "peak_mb": f"{self.peak_mb:.2f}",
                "compiles": self.compile_count,
                "shared": self.shared_count,
                "dispatches": self.dispatch_count}


@dataclass
class FleetResult:
    points: List[PointResult]
    wall_s: float              # whole-fleet wall clock
    packed: bool
    compile_count: int         # distinct executables compiled fleet-wide
    shared_count: int          # per-point registry adoptions, summed
    dispatch_count: int

    def leaderboard(self) -> str:
        """Text table, best final loss first."""
        rows = sorted(self.points, key=lambda p: p.final_loss)
        head = (f"{'label':<28} {'loss':>9} {'min':>9} {'r/s':>7} "
                f"{'up':>8} {'down':>8} {'peakMB':>7} {'cmp':>4} {'shr':>4}")
        lines = [head, "-" * len(head)]
        for p in rows:
            lines.append(
                f"{p.label:<28} {p.final_loss:>9.4f} {p.min_loss:>9.4f} "
                f"{p.rounds_per_sec:>7.2f} {p.uplink_mbit:>8.1f} "
                f"{p.downlink_mbit:>8.1f} {p.peak_mb:>7.1f} "
                f"{p.compile_count:>4d} {p.shared_count:>4d}")
        lines.append(f"fleet: {len(self.points)} point(s) in "
                     f"{self.wall_s:.2f}s ({'packed' if self.packed else 'serial'}), "
                     f"{self.compile_count} compile(s), "
                     f"{self.shared_count} shared, "
                     f"{self.dispatch_count} dispatch(es)")
        return "\n".join(lines)

    def to_csv(self, path: str) -> None:
        with open(path, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=CSV_FIELDS)
            w.writeheader()
            for p in sorted(self.points, key=lambda p: p.final_loss):
                w.writerow(p.as_row())


def _program_key_for(spec: ExperimentSpec, backend) -> Tuple:
    """Registry program key for one packed point: the spec fingerprint plus
    the slice's device ids — AOT executables are bound to devices, so two
    points on different sub-meshes must never share an entry."""
    key = spec_program_key(spec)
    mesh = getattr(backend, "mesh", None)
    if mesh is not None:
        key = key + (("devices", tuple(int(d.id) for d in
                                       mesh.devices.flat)),)
    return key


def share_k_grid(points: Sequence[SweepPoint]) -> List[SweepPoint]:
    """Pin one ``quantize_k`` anchor — the grid's max ``fed.k0`` — on every
    point (forcing ``fed.k_quantize`` on), so points differing only in
    ``fed.k0`` snap to identical K values and share bucket executables."""
    anchor = max(p.spec.fed.k0 for p in points)
    out = []
    for p in points:
        spec = p.spec.with_overrides("fed.k_quantize=true",
                                     f"fed.k_grid0={anchor}").validate()
        out.append(SweepPoint(label=p.label, overrides=p.overrides,
                              spec=spec))
    return out


def _run_point(point: SweepPoint, backend, registry: ExecutableRegistry,
               rounds: Optional[int], verbose: bool) -> PointResult:
    program_key = _program_key_for(point.spec, backend) \
        if registry is not None else None
    exp = build(point.spec, backend=backend, registry=registry,
                program_key=program_key)
    t0 = time.perf_counter()
    h = exp.run(rounds, verbose=False)
    wall = time.perf_counter() - t0
    tr = exp.trainer
    n = len(h.rounds)
    res = PointResult(
        label=point.label, overrides=point.overrides, spec=point.spec,
        final_loss=float(h.train_loss[-1]) if h.train_loss else float("nan"),
        min_loss=float(min(h.min_train_loss)) if h.min_train_loss
        else float("nan"),
        rounds=n, wall_s=wall,
        rounds_per_sec=n / wall if wall > 0 else 0.0,
        uplink_mbit=float(h.uplink_mbit[-1]) if h.uplink_mbit else 0.0,
        downlink_mbit=float(h.downlink_mbit[-1]) if h.downlink_mbit else 0.0,
        peak_mb=trainer_peak_mb(tr),
        compile_count=tr.compile_count, shared_count=tr.shared_count,
        dispatch_count=tr.dispatch_count)
    if verbose:
        print(f"[fleet] {res.label}: loss {res.final_loss:.4f} in "
              f"{res.wall_s:.2f}s ({res.compile_count} compiled, "
              f"{res.shared_count} shared)")
    return res


def _slices_for(points: Sequence[SweepPoint], packed: bool) -> List[Any]:
    """One backend per point. Packed fleets with a single backend section
    carve slices from ONE parent backend (sub-meshes / fresh local
    instances); mixed-backend grids and serial fleets let ``build`` derive
    each point's backend from its own spec (None)."""
    if not packed:
        return [None] * len(points)
    from repro.api.experiment import _make_backend
    sections = {p.spec.backend for p in points}
    if len(sections) != 1:
        return [None] * len(points)
    parent = _make_backend(points[0].spec)
    return parent.fleet_slices(len(points))


def run_fleet(base: Optional[ExperimentSpec] = None,
              sweep: Sequence[str] = (), *,
              points: Optional[Sequence[SweepPoint]] = None,
              packed: bool = True, workers: Optional[int] = None,
              rounds: Optional[int] = None,
              registry: Optional[ExecutableRegistry] = None,
              share_grid: bool = False,
              verbose: bool = False) -> FleetResult:
    """Run a sweep as one fleet.

    ``base`` + ``sweep`` expand through ``expand_sweep`` (or pass
    pre-expanded ``points``). ``packed=True`` runs points concurrently on
    backend slices; False runs them serially (still sharing the registry).
    ``share_grid`` pins a fleet-wide ``fed.k_grid0`` anchor. ``registry``
    defaults to a fresh fleet-wide ``ExecutableRegistry``."""
    if points is None:
        points = expand_sweep(*sweep, base=base)
    points = list(points)
    if not points:
        raise ValueError("run_fleet: empty sweep grid")
    if share_grid:
        points = share_k_grid(points)
    registry = registry if registry is not None else ExecutableRegistry()
    backends = _slices_for(points, packed)
    t0 = time.perf_counter()
    if packed and len(points) > 1:
        n_workers = workers if workers else len(points)
        with ThreadPoolExecutor(max_workers=n_workers) as pool:
            futures = [pool.submit(_run_point, p, b, registry, rounds,
                                   verbose)
                       for p, b in zip(points, backends)]
            results = [f.result() for f in futures]
    else:
        results = [_run_point(p, b, registry, rounds, verbose)
                   for p, b in zip(points, backends)]
    wall = time.perf_counter() - t0
    return FleetResult(
        points=results, wall_s=wall, packed=packed,
        compile_count=registry.compile_count,
        shared_count=sum(r.shared_count for r in results),
        dispatch_count=sum(r.dispatch_count for r in results))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--spec", default=None, metavar="FILE.json",
                    help="base ExperimentSpec (default: ExperimentSpec())")
    ap.add_argument("--set", action="append", default=[], metavar="PATH=V",
                    dest="overrides",
                    help="base-spec dotted-path override, repeatable")
    ap.add_argument("--sweep", nargs="+", default=[], metavar="PATH=V1,V2",
                    help="sweep axes, e.g. --sweep fed.k0=2,4,8 "
                         "transport.name=int8,topk (cross product)")
    ap.add_argument("--rounds", type=int, default=None,
                    help="rounds per point (default: each spec's "
                         "fed.rounds)")
    ap.add_argument("--serial", action="store_true",
                    help="run points one after another instead of packed "
                         "(still shares the executable registry)")
    ap.add_argument("--workers", type=int, default=None,
                    help="max concurrent packed points (default: all)")
    ap.add_argument("--share-k-grid", action="store_true",
                    help="pin fed.k_grid0 to the grid's max fed.k0 so k0 "
                         "sweep points share bucket executables")
    ap.add_argument("--csv", default=None, metavar="FILE.csv",
                    help="write the consolidated leaderboard CSV here")
    ap.add_argument("--quiet", action="store_true")
    return ap


def main(argv=None) -> FleetResult:
    args = make_parser().parse_args(argv)
    base = ExperimentSpec.load(args.spec) if args.spec else ExperimentSpec()
    if args.overrides:
        base = base.with_overrides(*args.overrides)
    if not args.sweep:
        raise SystemExit("fleet: --sweep is required (e.g. --sweep "
                         "fed.k0=2,4,8)")
    result = run_fleet(base, args.sweep, packed=not args.serial,
                       workers=args.workers, rounds=args.rounds,
                       share_grid=args.share_k_grid,
                       verbose=not args.quiet)
    print(result.leaderboard())
    if args.csv:
        result.to_csv(args.csv)
        print(f"[fleet] csv -> {args.csv}")
    return result


if __name__ == "__main__":
    print(f"[fleet] persistent compile cache: {enable_compile_cache()}")
    main()
