"""One run of one cell: set-up, the measured window, the traced stretch and
the comparison with the plain reference.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the window) builds the
experiment through ``repro.api.build``, swaps in the benchmark's client
data and weights made from ``--seed``, and drives the compiled round
through the cell's first steps (``checks/<cell>.json`` ``steps``), which
compile every executable the window uses. The window then runs whole
rounds through ``FederatedExperiment.run`` for about ``--seconds`` and ends
in ``block_until_ready``; a compile inside it fails the run. With
``--trace 1`` a short stretch of rounds after the window is profiled and
the per-layer metrics are printed instead of the end-to-end ones. Last,
the program's state is freed and the reference follows the same first
steps; the numbers of ``check`` decide ``correct``.

Each per-layer metric is a reader ``metrics/<name>.py`` with a
``read(ctx)``. A reader declares what it reads of the program, and the
harness hands it over:

- ``KERNELS``: instruction-name prefixes, timed in the trace
  (``ctx["trace"]["devices"][d]["kernels"]``);
- ``SCOPES`` and ``SPANS``: device scopes and host spans; the traced
  stretch is reduced with the reader's own scopes and every declared span
  (``chipbench.scopes.reduce``, as ``ctx["layers"]``), with the time of
  the Pallas kernels' calls under each scope;
- ``COUNTERS``: attributes of the trainer, read just before and just
  after the window (``ctx["counters"]["program"]``: the deltas, None for
  an attribute the trainer lacks).

``--rehearse`` runs the same path on the CPU at the rehearsal sizes of the
configuration and the mix, with Pallas interpreted, and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import queue
import statistics
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from chipbench import catalog, check, traffic as traffic_mod


class Fail(Exception):
    """A run that must exit non-zero with no result line."""


def enable_cache() -> str:
    """JAX's persistent compile cache (``repro.launch.compile_cache``),
    with HLO metadata in its key: the program's device scopes live in the
    ``op_name`` metadata, and an executable cached by a commit without
    them would come back unscoped."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return enable_compile_cache()


DECLARED = ("KERNELS", "SCOPES", "SPANS", "COUNTERS")


def declared(readers) -> Dict[str, tuple]:
    """The union of what ``readers`` (metric reader modules) declare, by
    kind (``DECLARED``), each name once, in the order first declared."""
    out: Dict[str, list] = {k: [] for k in DECLARED}
    for r in readers:
        for kind in DECLARED:
            out[kind] += [n for n in getattr(r, kind, ())
                          if n not in out[kind]]
    return {k: tuple(v) for k, v in out.items()}


def layer_reductions(loaded, texts: List[str], readers,
                     spans: Sequence[str]) -> Dict[tuple, Optional[dict]]:
    """``chipbench.scopes.reduce`` of one trace (``scopes.load``) for each
    distinct ``SCOPES`` the ``readers`` declare, with ``spans``; keyed by
    those scopes. Each reader's scopes are mapped apart (``scope_map``
    over the executables' HLO ``texts``), so that a scope one reader
    declares moves no op of another's."""
    from chipbench import scopes
    calls = scopes.pallas_calls(texts)
    out: Dict[tuple, Optional[dict]] = {}
    for r in readers:
        key = tuple(getattr(r, "SCOPES", ()))
        if key in out or not (key or getattr(r, "SPANS", ())):
            continue
        smap = scopes.scope_map(texts, scopes=key) if key else {}
        out[key] = scopes.reduce(loaded, smap, scopes=key, spans=spans,
                                 calls=calls)
    return out


def hlo_texts(exp) -> List[str]:
    """The optimised HLO of every executable the round engine holds."""
    return [e.as_text()
            for e in exp.trainer.engine.registry.executables()]


def parse(argv):
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, rehearsal sizes, interpreted kernels; no "
                         "result line")
    return ap.parse_args(argv)


def jax_key(seed: int):
    """A PRNG key from a seed of any size."""
    import jax
    word = int(np.random.SeedSequence(abs(int(seed))).generate_state(1)[0])
    return jax.random.PRNGKey(word)


# ---------------------------------------------------------------------------
# benchmark-side instrumentation around the program's own calls
# ---------------------------------------------------------------------------

class CommitClock:
    """Records when each round's committed parameters become ready, on a
    thread of its own, so that the trainer's dispatch is never held up.
    It keeps one small output leaf per round alive, never the parameters."""

    def __init__(self):
        self.times: List[float] = []
        self._q: "queue.Queue" = queue.Queue()
        self._t = threading.Thread(target=self._work, daemon=True,
                                   name="chipbench-commit-clock")
        self._t.start()

    def _work(self):
        while True:
            leaf = self._q.get()
            if leaf is None:
                return
            leaf.block_until_ready()
            self.times.append(time.perf_counter())

    def push(self, leaf):
        self._q.put(leaf)

    def close(self):
        self._q.put(None)
        self._t.join()


class Instruments:
    """Wrappers installed on one built experiment: feed timing and
    recording, dispatch spans, commit times."""

    def __init__(self, exp, data_keys: Callable):
        import jax
        from repro.data import pipeline
        self.exp, self._jax, self._pipeline = exp, jax, pipeline
        self.recording = False
        self.sampled: List = []          # (ids, weights) per round, in order
        self.fed: List = []              # per placed item: dict of x keys, y
        self.wait_s = 0.0
        self.clock: Optional[CommitClock] = None
        self._data_keys = data_keys
        self._orig_make_builder = pipeline.make_builder
        tr, eng, be = exp.trainer, exp.trainer.engine, exp.trainer.engine.backend
        orig_round = tr.sampler.round

        def sampler_round(*a, **kw):
            ids, w = orig_round(*a, **kw)
            if self.recording:
                self.sampled.append((np.asarray(ids).copy(),
                                     np.asarray(w, np.float64).copy()))
            return ids, w
        tr.sampler.round = sampler_round

        for meth in ("place_bucket", "place_slab"):
            orig = getattr(be, meth)
            setattr(be, meth, self._record_place(orig))

        def make_builder(*a, **kw):
            b = self._orig_make_builder(*a, **kw)
            orig_get = b.get

            def get():
                t = time.perf_counter()
                with self.span("bench.feed_wait"):
                    item = orig_get()
                self.wait_s += time.perf_counter() - t
                return item
            b.get = get
            return b
        pipeline.make_builder = make_builder

        for meth in ("run_bucket", "run_round_chunked"):
            orig = getattr(eng, meth)
            setattr(eng, meth, self._timed_dispatch(orig))
        orig_absorb = tr._absorb

        def absorb(*a, **kw):
            with self.span("bench.absorb"):
                return orig_absorb(*a, **kw)
        tr._absorb = absorb

    def span(self, name):
        return self._jax.profiler.TraceAnnotation(name)

    def _record_place(self, orig):
        def place(item):
            # the engine places again what the builder placed: record the
            # host arrays only
            if self.recording and isinstance(item.batches["x"], np.ndarray):
                x = np.asarray(item.batches["x"])
                self.fed.append({"x_keys": self._data_keys(x),
                                 "y": np.asarray(item.batches["y"]).copy(),
                                 "active": (np.asarray(item.active).copy()
                                            if hasattr(item, "active")
                                            else None)})
            return orig(item)
        return place

    def _timed_dispatch(self, orig):
        def dispatch(*a, **kw):
            with self.span("bench.dispatch"):
                out = orig(*a, **kw)
            if self.clock is not None:
                leaves = self._jax.tree.leaves(out[0])
                self.clock.push(min(leaves, key=lambda x: x.size))
            return out
        return dispatch

    def close(self):
        self._pipeline.make_builder = self._orig_make_builder


def compile_counter():
    """Counts backend compiles in the process, whatever compiles them."""
    import jax
    box = {"n": 0}

    def on(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            box["n"] += 1
    jax.monitoring.register_event_duration_secs_listener(on)
    return box


# ---------------------------------------------------------------------------
# rows fed -> row indices of the benchmark's own data
# ---------------------------------------------------------------------------

def make_row_keys(row_shape) -> Callable:
    """An exact key per row: the hash of the row's bytes (equal rows, equal
    keys; within one process)."""
    ndim = len(row_shape)

    def keys(x):
        x = np.ascontiguousarray(x)
        lead = x.shape[:x.ndim - ndim]
        rows = x.reshape((-1,) + tuple(row_shape))
        return np.array([hash(r.tobytes()) for r in rows],
                        np.int64).reshape(lead)
    return keys


def rebuild_rounds(inst: Instruments, xs, ys, g, rounds: int):
    """The rounds the program fed during set-up, rebuilt from the
    benchmark's data: a list per round of (x, y, weight) per client, and
    the count of fed rows not found among the client's own rows."""
    keys = inst._data_keys
    lookup: Dict[int, Dict[int, int]] = {}
    fed = []                  # (x keys (K, b), y (K, b, ...)) per client
    for item in inst.fed:
        xk, y, active = item["x_keys"], item["y"], item["active"]
        if active is None:    # a slab: some of one round's clients
            xk, y, active = xk[None], y[None], [True]
        for r, on in enumerate(active):
            if on:            # a bucket's padding rounds are not run
                fed += list(zip(xk[r], y[r]))
    n = int(g["clients_per_round"])
    if len(fed) != rounds * n or len(inst.sampled) != rounds:
        raise Fail(f"set-up fed {len(fed)} client(s) and sampled "
                   f"{len(inst.sampled)} round(s), expected {rounds} "
                   f"round(s) of {n}")
    joined = [fed[i * n:(i + 1) * n] for i in range(rounds)]
    unmatched, out = 0, []
    sizes = np.array([len(v) for v in ys], float)
    for (ids, _w), fed_round in zip(inst.sampled, joined):
        clients = []
        for c, (xk, y) in zip(ids, fed_round):
            c = int(c)
            if c not in lookup:
                lookup[c] = {int(k): r for r, k in enumerate(keys(xs[c]))}
            rows = np.vectorize(lambda k: lookup[c].get(int(k), -1),
                                otypes=[np.int64])(xk)
            bad = rows < 0
            rows = np.where(bad, 0, rows)
            diff = ys[c][rows] != y
            bad |= diff.reshape(bad.shape + (-1,)).any(-1)
            unmatched += int(bad.sum())
            w = sizes[c] / sizes[ids.astype(int)].sum()
            clients.append((xs[c][rows], ys[c][rows], float(w)))
        out.append(clients)
    return out, unmatched


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def spec_for(cell: catalog.Cell, g: Dict[str, Any], seed: int,
             rehearse: bool):
    from repro.api import ExperimentSpec
    over = list(cell.config["spec"])
    if rehearse:
        over += cell.config.get("rehearse", {}).get("spec", [])
    over += [
        f"data.clients={int(g['clients'])}", "data.samples_per_client=1",
        f"data.seq_len={int(g.get('seq', 8))}",
        f"fed.clients_per_round={int(g['clients_per_round'])}",
        f"fed.k0={int(g['k'])}", "fed.k_schedule=fixed",
        f"fed.batch_size={int(g['batch'])}", f"fed.eta0={float(g['eta'])}",
        f"fed.bucket_rounds={int(g['bucket_rounds'])}",
        f"fed.cohort_chunk={json.dumps(g.get('cohort_chunk'))}",
        f"transport.name={g['transport']}",
        f"backend.name={g['backend']}",
        f"fed.seed={abs(int(seed)) % (2 ** 31)}", "fed.rounds=1",
    ]
    return ExperimentSpec().with_overrides(*over)


def model_sizes(cell: catalog.Cell, rehearse: bool) -> Dict[str, Any]:
    m = dict(cell.config["model"])
    if rehearse:
        m.update(cell.config.get("rehearse", {}).get("model", {}))
    return m


def device_info(rehearse: bool, chips: int):
    import jax
    from repro.kernels import ops
    devs = jax.devices()
    d = devs[0]
    print(f"chipbench: platform={d.platform} device_kind={d.device_kind} "
          f"count={len(devs)}", file=sys.stderr, flush=True)
    if rehearse:
        return devs, None
    if d.platform != "tpu":
        raise Fail(f"JAX found platform {d.platform!r}, not a TPU")
    if ops.interpret_mode():
        raise Fail("Pallas kernels would run in interpret mode")
    if len(devs) < chips:
        raise Fail(f"the cell needs {chips} chip(s), JAX found {len(devs)}")
    return devs, catalog.load_peaks(d.device_kind)


class Run:
    """One cell at one seed, step by step: ``setup``, ``first_steps``,
    ``window``, ``traced``, ``free_program``, ``reference``. ``run_cell``
    drives them in that order; the calibration drives a subset."""

    def __init__(self, name: str, seed: int, rehearse: bool, repo=None,
                 root=None):
        self.cell = catalog.find_cell(name, repo=repo or catalog.REPO,
                                      root=root)
        self.seed, self.rehearse = seed, rehearse
        if rehearse and self.cell.chips > 1:  # virtual CPU devices
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={self.cell.chips}")
        import jax
        self.jax = jax
        if not rehearse:
            enable_cache()
        self.readers = {m["name"]: self.cell.metric_reader(m["name"])
                        for m in self.cell.per_layer}
        self.wants = declared(self.readers.values())
        self.compiles = compile_counter()
        self.devs, self.peaks = device_info(rehearse, self.cell.chips)
        self.g = traffic_mod.geometry(self.cell.traffic, rehearse)
        self.m = model_sizes(self.cell, rehearse)
        self.ref = self.cell.reference()
        self.steps = int(self.cell.checks.get("steps", 3))
        self.per_step = (1 if self.g.get("cohort_chunk")
                         else int(self.g["bucket_rounds"]))
        ref, m = self.ref, self.m
        self._init = jax.jit(lambda k: ref.to_program(ref.init(k, m)))
        self._init_ref = jax.jit(lambda k: ref.init(k, m))

    def setup(self):
        """Build through ``repro.api.build``; swap in the benchmark's data
        and weights; install the instruments."""
        jax = self.jax
        from repro.api import build
        from repro.data.synthetic import FederatedData
        self.exp = exp = build(spec_for(self.cell, self.g, self.seed,
                                        self.rehearse))
        self.xs, self.ys = traffic_mod.generate(self.g, self.m, self.seed)
        m = self.m
        exp.trainer.data = FederatedData(
            self.xs, self.ys, self.xs[0][:1], self.ys[0][:1],
            int(m["vocab_size"]))
        params = self._init(jax_key(self.seed))
        want = jax.tree.map(lambda a: (a.shape, a.dtype), exp.trainer.params)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if jax.tree.structure(want) != jax.tree.structure(got) or \
                jax.tree.leaves(want) != jax.tree.leaves(got):
            raise Fail("the reference's parameter tree does not match the "
                       "program's")
        # placed as the backend places the round's input (a mesh
        # replicates it), and the unplaced copy dropped at once
        exp.trainer.params = exp.trainer.engine.backend.place_params(params)
        del params
        gc.collect()
        self.inst = Instruments(exp, make_row_keys(self.xs[0].shape[1:]))
        jnp = jax.numpy
        self._prog_norms = jax.jit(lambda a, b: jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(x - y)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]))

    def _norms_now(self):
        p0 = self._init(jax_key(self.seed))
        out = np.asarray(self._prog_norms(self.exp.trainer.params, p0))
        del p0
        return out

    def first_steps(self):
        """The cell's first steps through the window's own call and feed;
        they compile every executable the window uses. Keeps the
        program's losses and change norms, and the fed rows."""
        jax, exp = self.jax, self.exp
        self.inst.recording = True
        losses, step_s = [], []
        for s in range(self.steps):
            t = time.perf_counter()
            h = exp.run(self.per_step)
            jax.block_until_ready(exp.trainer.params)
            step_s.append(time.perf_counter() - t)
            losses += list(h.train_loss[-self.per_step:])
            if s == 0:
                first = self._norms_now()
        self.inst.recording = False
        self.prog = {"losses": losses, "first": first,
                     "last": self._norms_now()}
        self.sizes = np.array([a.size for a in
                               jax.tree.leaves(exp.trainer.params)], float)
        # the fastest warm step: a host stall in set-up only lengthens a
        # step, and must not shorten the window sized from it
        self.round_s = min(step_s[1:] or step_s) / self.per_step

    def window(self, seconds: float, t_start: float):
        jax, exp, inst = self.jax, self.exp, self.inst
        rounds = max(1, int(round(seconds / max(self.round_s, 1e-6))))
        rounds = self.per_step * max(1, round(rounds / self.per_step))
        inst.wait_s = 0.0
        inst.clock = CommitClock()
        n_exec, n_comp = exp.trainer.compile_count, self.compiles["n"]
        p0 = self._program_counters()
        t0 = time.perf_counter()
        self.setup_s = t0 - t_start
        h = exp.run(rounds)
        jax.block_until_ready(exp.trainer.params)
        t1 = time.perf_counter()
        p1 = self._program_counters()
        inst.clock.close()
        commit, inst.clock = inst.clock.times, None
        if exp.trainer.compile_count != n_exec or \
                self.compiles["n"] != n_comp:
            raise Fail(f"{exp.trainer.compile_count - n_exec} round "
                       f"executable(s) and {self.compiles['n'] - n_comp} "
                       f"program(s) compiled inside the timed window")
        window_s = t1 - t0
        self.losses = list(h.train_loss[-rounds:])
        samples = rounds * traffic_mod.samples_per_round(self.g)
        round_ms = ([1e3 * (b - a) for a, b in
                     zip([t0] + commit[:-1], commit)]
                    if self.per_step == 1 else [])
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devs[:self.cell.chips])
        self.counters = {
            "rounds": rounds, "window_s": window_s, "samples": samples,
            "samples_per_s": samples / window_s, "round_ms": round_ms,
            "dispatches": len(commit),
            "pipeline_wait_ms": 1e3 * inst.wait_s / rounds,
            "peak_bytes": peak, "chips": self.cell.chips,
            "leaf_sizes": [int(x) for x in self.sizes],
            "program": {k: None if p0[k] is None or p1[k] is None
                        else p1[k] - p0[k] for k in p0},
        }

    def _program_counters(self) -> Dict[str, Any]:
        tr = self.exp.trainer
        return {k: getattr(tr, k, None) for k in self.wants["COUNTERS"]}

    def traced(self, seconds: float, out_dir: Optional[str] = None):
        """Profile a short stretch of rounds after the window and reduce
        the trace: the device numbers (``trace``, with the declared
        kernels) and the program's layers (``layers``, by the readers'
        scopes and spans). Both read None where the trace holds no device
        plane, as on the CPU."""
        jax, exp = self.jax, self.exp
        from chipbench import scopes, trace as trace_mod
        n_tr = self.per_step * max(1, math.ceil(
            min(3.0, seconds) / self.round_s / self.per_step))
        out_dir = out_dir or str(catalog.REPO / ".chipbench" / "trace"
                                 / f"{self.cell.name}-{os.getpid()}")
        jax.profiler.start_trace(out_dir)
        with self.inst.span("bench.window"):
            exp.run(n_tr)
            jax.block_until_ready(exp.trainer.params)
        jax.profiler.stop_trace()
        self.counters["traced_rounds"] = n_tr
        try:
            path = _xplane(out_dir)
            self.trace = trace_mod.reduce(trace_mod.load(path),
                                          kernels=self.wants["KERNELS"])
            self.layers = layer_reductions(
                scopes.load(path), hlo_texts(exp), self.readers.values(),
                self.wants["SPANS"])
        finally:
            _rmtree(out_dir)

    def free_program(self):
        """Rebuild the fed rounds from the benchmark's data, then drop the
        program and its state from the device."""
        self.inst.close()
        self.rounds, self.unmatched = rebuild_rounds(
            self.inst, self.xs, self.ys, self.g, self.steps * self.per_step)
        del self.exp, self.inst
        gc.collect()

    def reference(self, dtype=None, precision: str = "highest",
                  half_batch: bool = False) -> Dict[str, Any]:
        """The reference (or, with another dtype, the control) over the fed
        rounds: losses and the change norms after the first and the last
        step."""
        from chipbench.fedref import RoundReference
        jnp = self.jax.numpy
        rr = RoundReference(self.ref, self.m, codec=self.g["transport"],
                            eta=float(self.g["eta"]),
                            dtype=dtype or jnp.float32,
                            precision=precision, half_batch=half_batch)
        last = self.steps * self.per_step
        losses, snaps = rr.run(lambda: self._init_ref(jax_key(self.seed)),
                               self.rounds,
                               snapshot_after=(self.per_step, last))
        return {"losses": losses, "first": snaps[self.per_step],
                "last": snaps[last]}

    def numbers(self, ref_out, side=None, unmatched=None):
        """The compared numbers of ``side`` (default: the program) against
        the reference's output."""
        return check.numbers(side or self.prog, ref_out, self.sizes,
                             self.unmatched if unmatched is None
                             else unmatched)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             rehearse: bool, t_start: float, repo=None, root=None,
             trace_dir: Optional[str] = None) -> Dict[str, Any]:
    """Everything but the printing. Raises ``Fail`` where the run must not
    report. A cell reports only against the limits of its
    ``checks/<cell>.json``; a rehearsal without them compares against
    ``unmatched_rows`` alone."""
    limits = catalog.find_cell(name, repo=repo or catalog.REPO,
                               root=root).checks.get("limits")
    if limits is None and not rehearse:
        raise Fail(f"cell {name!r} has no limits in checks/{name}.json: "
                   f"take its readings with calibrate.py first")
    run = Run(name, seed, rehearse, repo=repo, root=root)
    run.setup()
    run.first_steps()
    run.window(seconds, t_start)
    run.trace, run.layers = None, {}
    if trace:
        run.traced(seconds, trace_dir)
    run.free_program()
    values = run.numbers(run.reference())
    if limits is None:
        limits = {k: 0.0 if k == "unmatched_rows" else float("inf")
                  for k in values}
    return {"cell": run.cell, "counters": run.counters,
            "setup_s": run.setup_s, "losses": run.losses,
            "trace": run.trace, "layers": run.layers,
            "readers": run.readers, "checks": check.judge(values, limits),
            "peaks": run.peaks, "model": run.m, "geometry": run.g,
            "devices": run.devs[:run.cell.chips], "ref": run.ref}


def _xplane(out_dir: str) -> str:
    for dirpath, _, files in os.walk(out_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    raise Fail(f"no .xplane.pb under {out_dir}")


def _rmtree(path: str) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def metric_context(res) -> Dict[str, Any]:
    c, g, m = res["counters"], res["geometry"], res["model"]
    return {"counters": c, "trace": res["trace"], "peaks": res["peaks"],
            "geometry": g, "model": m, "ref": res["ref"],
            "flops_per_sample": float(res["ref"].flops_per_sample(m, g))}


def p90(values: List[float]) -> float:
    return statistics.quantiles(values, n=10, method="inclusive")[8]


def result_line(res, trace: bool) -> Dict[str, Any]:
    cell, c = res["cell"], res["counters"]
    metrics: Dict[str, Dict[str, Any]] = {}
    if not trace:
        e2e = {"client_samples_per_s": c["samples_per_s"],
               "setup_s": res["setup_s"]}
        if len(c["round_ms"]) >= 2:
            e2e["round_ms.p90"] = p90(c["round_ms"])
        for mdef in cell.end_to_end:
            if mdef["name"] in e2e:
                metrics[mdef["name"]] = {"value": e2e[mdef["name"]],
                                         "unit": mdef["unit"]}
    else:
        ctx = metric_context(res)
        for mdef in cell.per_layer:
            reader = res["readers"][mdef["name"]]
            ctx["layers"] = res["layers"].get(
                tuple(getattr(reader, "SCOPES", ())))
            v = reader.read(ctx)
            if v is not None:
                metrics[mdef["name"]] = {"value": v, "unit": mdef["unit"]}
    devs = res["devices"]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": c["peak_bytes"]}
    out = {"correct": all(x["ok"] for x in res["checks"]),
           "attempted": c["rounds"],
           "failed": sum(1 for x in res["losses"] if not math.isfinite(x)),
           "metrics": metrics, "device": device}
    if trace and res["trace"] is not None:
        t = res["trace"]
        busy = [d["busy_ns"] for d in t["devices"].values()]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = t["window_ns"] / 1e9
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["checks"] = {x["name"]: {"value": x["value"], "limit": x["limit"]}
                     for x in res["checks"]}
    return out


def main(argv, t_start: float) -> int:
    args = parse(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), args.rehearse, t_start)
    except Fail as e:
        print(f"chipbench: FAIL: {e}", file=sys.stderr, flush=True)
        return 2
    line = result_line(res, bool(args.trace))
    c = res["counters"]
    if c["round_ms"]:
        slow = max(range(len(c["round_ms"])), key=c["round_ms"].__getitem__)
        print(f"chipbench: window {c['rounds']} rounds in "
              f"{c['window_s']:.3f} s; round_ms median "
              f"{statistics.median(c['round_ms']):.1f}, slowest "
              f"{c['round_ms'][slow]:.1f} (round {slow}), first "
              f"{c['round_ms'][0]:.1f}", file=sys.stderr)
    for x in res["checks"]:
        print(f"check {x['name']} {x['value']!r} limit {x['limit']!r} "
              f"{'ok' if x['ok'] else 'FAILED'}", file=sys.stderr)
    if args.rehearse:
        print("chipbench: rehearsal finished on "
              f"{res['devices'][0].platform}: no result reported; "
              f"correct={line['correct']} metrics={line['metrics']}",
              file=sys.stderr, flush=True)
        return 0
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
