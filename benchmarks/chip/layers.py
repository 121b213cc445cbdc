"""Split one cell's round into the program's own layers, on the chip.

    python3 benchmarks/chip/layers.py --workload <cell> --seed <n> \
        --seconds <s> [--out <file.json>]
    python3 benchmarks/chip/layers.py --workload <cell> --rehearse   # CPU

Set-up, the timed window and the trainer's counters over it are the
benchmark's own (``harness.Run``). After the window it runs the traced
stretch of ``run.py --trace 1`` twice, once untraced and once under the
profiler, and reduces the trace by the program's device scopes and host
spans (``chipbench.scopes``), mapping each op to its scope through the HLO
of the engine's executables. It prints one JSON object (and writes it to
``--out``): the per-round layer times (``scopes.layer_metrics``, as the
benchmark's readers give them), the checks of the reduction (scope
coverage of busy time, idle parts against idle time, host call spans
against the device's module starts) and the profiler's cost.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import catalog, harness, scopes, trace  # noqa: E402
from chipbench import traffic as traffic_mod  # noqa: E402


def timed_rounds(run, n: int) -> float:
    t = time.perf_counter()
    run.exp.run(n)
    run.jax.block_until_ready(run.exp.trainer.params)
    return time.perf_counter() - t


def sample(path: str, n: int = 12) -> dict:
    """A few raw events of the first device plane with all their stats: what
    the chip's trace carries, for the reader of the output."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            out = {"plane": plane.name, "lines": {}}
            for line in plane.lines:
                out["lines"][line.name] = [
                    {"name": e.name[:160], "stats": {
                        k: str(v)[:160] for k, v in e.stats}}
                    for e in list(line.events)[:n]]
            return out
    return {}


def measure(name: str, seed: int, seconds: float, rehearse: bool,
            out_dir: str) -> dict:
    run = harness.Run(name, seed, rehearse)
    run.setup()
    run.first_steps()
    run.window(seconds, T_START)
    c = run.counters
    # the counter readers' numbers, as the benchmark prints them
    counted = {name: r.read({"counters": c})
               for name, r in run.readers.items()
               if getattr(r, "COUNTERS", ())}
    n_tr = run.per_step * max(1, math.ceil(
        min(3.0, seconds) / run.round_s / run.per_step))
    per_round = traffic_mod.samples_per_round(run.g)
    plain_s = timed_rounds(run, n_tr)
    run.jax.profiler.start_trace(out_dir)
    with run.inst.span("bench.window"):
        traced_s = timed_rounds(run, n_tr)
    run.jax.profiler.stop_trace()
    texts = harness.hlo_texts(run.exp)
    smap = scopes.scope_map(texts)
    res = {
        "cell": name, "seed": seed,
        "platform": run.devs[0].platform, "kind": run.devs[0].device_kind,
        "window": {"rounds": c["rounds"], "window_s": c["window_s"],
                   "samples_per_s": c["samples_per_s"],
                   "mean_round_ms": 1e3 * c["window_s"] / c["rounds"]},
        "profiler_cost": {
            "rounds": n_tr,
            "untraced_samples_per_s": n_tr * per_round / plain_s,
            "traced_samples_per_s": n_tr * per_round / traced_s},
        "hlo": {"instructions_in_a_scope": len(smap),
                "scope_names_in_text": {
                    s: sum(t.count(s) for t in texts)
                    for s in scopes.SCOPES}},
    }
    path = harness._xplane(out_dir)
    loaded = scopes.load(path)
    red = scopes.reduce(loaded, smap, calls=scopes.pallas_calls(texts))
    res["layers"] = counted
    if red is None:
        return res
    res["sample"] = sample(path)
    res["devices"] = {}
    for plane, dev in red["devices"].items():
        busy = dev["busy_ns"]
        scoped = sum(dev["scopes_ns"].get(s, 0.0) for s in scopes.SCOPES)
        idle = red["window_ns"] - busy
        k_ns, k_calls = dev["pallas_ns"]["uplink.reduce"]
        leads = {m: {"counts": v["counts"],
                     "min_ms": min(v["leads_ns"], default=0.0) / 1e6,
                     "negative": sum(x < 0 for x in v["leads_ns"])}
                 for m, v in dev["launch_leads_ns"].items()}
        res["devices"][plane] = {
            "layers": {**scopes.layer_metrics([dev], n_tr), **counted},
            "idle_share": 100.0 * idle / red["window_ns"],
            "busy_ms_per_round": busy / 1e6 / n_tr,
            "scoped_share_of_busy": 100.0 * scoped / busy,
            "scopes_ms_per_round": {k: v / 1e6 / n_tr
                                    for k, v in dev["scopes_ns"].items()},
            "scoped_vs_busy_of_mean_round": scoped / 1e6 / n_tr / (
                (1.0 - idle / red["window_ns"])
                * res["window"]["mean_round_ms"]),
            "idle_by_span_ms_per_round": {
                k: v / 1e6 / n_tr
                for k, v in dev["idle_by_span_ns"].items()},
            "idle_parts_over_idle": sum(dev["idle_by_span_ns"].values())
            / idle if idle > 0 else None,
            "int8_reduce_ms_per_round": k_ns / 1e6 / n_tr,
            "int8_reduce_calls": k_calls,
            "launch_leads": leads,
        }
    res["layers"] = {**scopes.layer_metrics(list(red["devices"].values()),
                                            n_tr), **counted}
    return res


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, rehearsal sizes: no device numbers")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    out_dir = str(catalog.REPO / ".chipbench" / "layers"
                  / f"{args.workload}-{os.getpid()}")
    try:
        res = measure(args.workload, args.seed, args.seconds, args.rehearse,
                      out_dir)
    except harness.Fail as e:
        print(f"layers: FAIL: {e}", file=sys.stderr, flush=True)
        return 2
    finally:
        harness._rmtree(out_dir)
    text = json.dumps(res, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(json.dumps({k: res[k] for k in res if k != "sample"}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
