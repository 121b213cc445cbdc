"""FedAvgTrainer on the layered round engine (DESIGN.md §6).

Drives: RoundScheduler (K-bucket plan) -> BatchPrefetcher (host tensors for
the upcoming bucket, built on a background thread) -> RoundEngine (one
jitted multi-round scan per bucket) -> DecayController feedback.

Synchronisation policy:
  * loss-free schedules (fixed/dsgd/rounds/cosine x fixed/rounds) never
    block mid-plan: bucket r's losses are materialised only after bucket
    r+1 has been dispatched, so host batch building, device compute and
    history accounting overlap;
  * error/step schedules sync at bucket boundaries only (bucket length
    ``fed.feedback_bucket_rounds``; the default 1 reproduces the seed
    per-round feedback loop exactly).

Evaluation happens at bucket boundaries; the scheduler cuts buckets at
``eval_every`` multiples so eval rounds match the seed loop exactly.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FedConfig
from repro.core import obs
from repro.core.engine.backends.base import LINEAR_AGGREGATORS
from repro.core.engine.model_store import GlobalModelStore
from repro.core.engine.round import LossFn, RoundEngine
from repro.core.engine.sampling import make_sampler
from repro.core.engine.scheduler import Bucket, RoundScheduler
from repro.core.engine.transport import get_transport
from repro.core.runtime_model import RuntimeModel
from repro.core.schedules import DecayController
from repro.data import pipeline
from repro.data.synthetic import FederatedData

PyTree = Any


# ---------------------------------------------------------------------------
# history
# ---------------------------------------------------------------------------

@dataclass
class History:
    rounds: List[int] = field(default_factory=list)
    k: List[int] = field(default_factory=list)
    eta: List[float] = field(default_factory=list)
    wall_clock_s: List[float] = field(default_factory=list)   # cumulative, Eq. 5
    sgd_steps: List[int] = field(default_factory=list)        # cumulative
    uplink_mbit: List[float] = field(default_factory=list)    # cumulative wire
    downlink_mbit: List[float] = field(default_factory=list)  # cumulative wire
    train_loss: List[float] = field(default_factory=list)     # Eq. 15 round mean
    min_train_loss: List[float] = field(default_factory=list) # Fig. 1 metric
    val_rounds: List[int] = field(default_factory=list)
    val_error: List[float] = field(default_factory=list)
    max_val_acc: List[float] = field(default_factory=list)    # Fig. 2 metric
    # --- async buffered aggregation (DESIGN.md §13; empty for sync runs,
    # missing-field defaults keep pre-async checkpoints loadable) ---
    staleness: List[float] = field(default_factory=list)      # per-apply mean
    applied_updates: List[int] = field(default_factory=list)  # cumulative
    dropped_updates: List[int] = field(default_factory=list)  # cumulative
    # --- serve-while-training (DESIGN.md §14; empty unless a ServingLoop
    # is attached, missing-field defaults keep older checkpoints loadable) ---
    serve_rounds: List[int] = field(default_factory=list)     # tick round/apply
    serve_tokens_per_sec: List[float] = field(default_factory=list)
    serve_swap_us: List[float] = field(default_factory=list)  # snapshot swap
    serve_staleness: List[int] = field(default_factory=list)  # versions behind

    def as_dict(self) -> Dict[str, list]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, list]) -> "History":
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - names)
        if unknown:
            # a checkpoint written by a different History schema: dropping
            # fields silently would hide drift from the operator
            warnings.warn(
                f"History.from_dict: ignoring unknown field(s) {unknown} "
                f"(checkpoint written by a different History schema?)",
                stacklevel=2)
        return cls(**{k: list(v) for k, v in d.items() if k in names})


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

class FedAvgTrainer:
    def __init__(self, loss_fn: LossFn, init_params: PyTree,
                 data: FederatedData, fed: FedConfig,
                 runtime: RuntimeModel,
                 eval_fn: Optional[Callable[[PyTree], Dict[str, float]]] = None,
                 use_kernel_avg: Optional[bool] = None, backend=None,
                 sampler=None, registry=None, program_key=None):
        """``backend``: an ``engine.backends.ExecutionBackend`` deciding the
        execution geometry (default LocalBackend; pass a MeshBackend to run
        the same schedules/aggregators/servers GSPMD-sharded).

        ``sampler``: a ``ClientSampler`` instance overriding
        ``fed.sampler`` (default: resolve ``fed.sampler`` through the
        registry; ``uniform`` reproduces the historical stream exactly).

        ``registry`` / ``program_key``: a shared
        ``engine.round.ExecutableRegistry`` + the experiment's program
        fingerprint, forwarded to the RoundEngine for cross-experiment AOT
        executable reuse in fleet sweeps (DESIGN.md §12). Default: private
        registry, historical behaviour.

        ``use_kernel_avg`` is DEPRECATED: use ``fed.aggregator="kernel"``
        (it has been folded into aggregator resolution; the kwarg is a
        one-release shim)."""
        self.loss_fn = loss_fn
        # the store owns every piece of server-side model state (params,
        # server-optimizer state, transport EF, downlink ref/residual,
        # version, cost counters); trainer attributes below are properties
        # delegating to it (DESIGN.md §14)
        self.store = GlobalModelStore()
        self.params = init_params
        self.data = data
        self.fed = fed
        self.runtime = runtime
        self.eval_fn = eval_fn
        self.ctrl = DecayController(fed)
        aggregator = fed.aggregator
        if use_kernel_avg is not None:
            warnings.warn(
                "FedAvgTrainer(use_kernel_avg=...) is deprecated and will "
                "be removed next release; use FedConfig(aggregator='kernel') "
                "or register a custom aggregator instead.",
                DeprecationWarning, stacklevel=2)
            if use_kernel_avg:
                aggregator = "kernel"
        self.sampler = sampler if sampler is not None else make_sampler(fed)
        if (getattr(self.sampler, "needs_weighted_aggregation", False)
                and aggregator not in LINEAR_AGGREGATORS):
            # e.g. availability shortfall pads the cohort at weight 0;
            # median/trimmed_mean ignore weights and would aggregate the
            # padded offline clients as full participants
            raise ValueError(
                f"sampler {self.sampler.name!r} encodes participation in "
                f"the aggregation weights and needs a weight-respecting "
                f"aggregator {LINEAR_AGGREGATORS}, got {aggregator!r}")
        transport = get_transport(getattr(fed, "transport", "none"),
                                  topk_frac=getattr(fed, "topk_frac", 0.1))
        if (transport is not None and transport.error_feedback
                and self.sampler.stateful_cohort):
            # fixed cohort: slot j is the same client every round, so the
            # codec residual moves from one server-aggregate buffer to
            # per-client slots (DESIGN.md §9.3)
            transport = transport.with_ef_slots(fed.clients_per_round)
        self.engine = RoundEngine(loss_fn, aggregator=aggregator,
                                  trim_fraction=fed.trim_fraction,
                                  server=fed.server_optimizer,
                                  server_lr=fed.server_lr,
                                  backend=backend,
                                  transport=transport,
                                  topk_frac=getattr(fed, "topk_frac", 0.1),
                                  downlink=getattr(fed, "downlink", "none"),
                                  downlink_ref=getattr(fed, "downlink_ref",
                                                       "f32"),
                                  cohort_chunk=getattr(fed, "cohort_chunk",
                                                       None),
                                  registry=registry,
                                  program_key=program_key)
        self.engine.bind_store(self.store)
        self.server_state = self.engine.init_server_state(init_params)
        self.engine.init_transport_state(init_params)
        self.engine.init_downlink_state(init_params)
        if self.engine.transport is not None or \
                self.engine.downlink is not None:
            # charge the wire what the codecs ship — on a trainer-owned
            # copy (an injected RuntimeModel may be shared across trainers
            # with different transports); clone the straggler rng so the
            # copy owns its draw stream too
            import copy as _copy
            rt = _copy.copy(runtime)
            rt._rng = np.random.default_rng()
            rt._rng.bit_generator.state = runtime._rng.bit_generator.state
            if self.engine.transport is not None:
                rt.uplink_compression = \
                    self.engine.transport.compression_ratio(init_params)
            if self.engine.downlink is not None:
                rt.downlink_compression = \
                    self.engine.downlink.compression_ratio(init_params)
                # adaptive codec: per-level ratios so each round's wire
                # charge follows the level it actually shipped (§10.4)
                level_ratios = getattr(self.engine.downlink, "level_ratios",
                                       None)
                if level_ratios is not None:
                    rt.downlink_level_ratios = level_ratios(init_params)
            self.runtime = rt
        self.history = History()
        self._np_rng = np.random.default_rng(fed.seed)
        self._completed_rounds = 0
        # serve-while-training: ``api.build`` attaches a ServingLoop +
        # cadence when the spec asks for one; the trainer itself only
        # ticks it at bucket boundaries (DESIGN.md §14)
        self.serving = None
        self.serve_every = 0
        # host seconds the trainer's thread waited on the batch builder
        self.feed_wait_s = 0.0

    # ------------------------------------------------------------------
    # state delegation: the GlobalModelStore owns it, the historical
    # attribute names keep reading/writing it
    # ------------------------------------------------------------------
    params = property(lambda self: self.store.params,
                      lambda self, v: setattr(self.store, "params", v))
    server_state = property(
        lambda self: self.store.server_state,
        lambda self, v: setattr(self.store, "server_state", v))
    _wall = property(lambda self: self.store.wall,
                     lambda self, v: setattr(self.store, "wall", v))
    _steps = property(lambda self: self.store.steps,
                      lambda self, v: setattr(self.store, "steps", v))
    _up_mbit = property(lambda self: self.store.up_mbit,
                        lambda self, v: setattr(self.store, "up_mbit", v))
    _down_mbit = property(lambda self: self.store.down_mbit,
                          lambda self, v: setattr(self.store, "down_mbit", v))
    _min_loss = property(lambda self: self.store.min_loss,
                         lambda self, v: setattr(self.store, "min_loss", v))
    _max_acc = property(lambda self: self.store.max_acc,
                        lambda self, v: setattr(self.store, "max_acc", v))

    # ------------------------------------------------------------------
    @property
    def compile_count(self) -> int:
        return self.engine.compile_count

    @property
    def shared_count(self) -> int:
        """Executables adopted from a shared registry without compiling."""
        return self.engine.shared_count

    @property
    def dispatch_count(self) -> int:
        return self.engine.dispatch_count

    @property
    def dispatch_s(self) -> float:
        """Host seconds spent in the round executables' calls."""
        return self.engine.dispatch_s

    def run(self, rounds: Optional[int] = None, eval_every: int = 10,
            verbose: bool = False, resume: bool = False) -> History:
        """``resume=True`` continues a restored run (``restore_state``) from
        the first unexecuted round; the default replays the full schedule
        (repeated ``run()`` calls keep their historical warm-rerun
        semantics)."""
        rounds = rounds if rounds is not None else self.fed.rounds
        start = self._completed_rounds + 1 if resume else 1
        if start > rounds:
            return self.history
        sched = RoundScheduler(
            self.ctrl, self.fed, total_rounds=rounds,
            eval_every=eval_every if self.eval_fn is not None else None,
            serve_every=self.serve_every if self.serving is not None
            else None,
            start_round=start)
        if (self.serving is not None
                and self.serving.served_version != self.store.version):
            # a restored (or warm-rerun) store is ahead of the loop's
            # construction-time snapshot — re-swap so the first tick's
            # staleness measures this run, not the gap
            self.serving.swap()
        # the builder consumes the trainer's persistent rng so repeated
        # run() calls continue one sample stream (seed-loop semantics)
        # buckets are device_put with the backend's client sharding as soon
        # as they are built — on the prefetch thread, the H2D transfer
        # overlaps the previous bucket's device compute
        builder = pipeline.make_builder(
            self.data, self.fed.clients_per_round, self.fed.batch_size,
            self._np_rng,
            background=self.fed.prefetch and sched.loss_free,
            place_fn=self.engine.backend.place_bucket,
            sampler=self.sampler,
            chunk=getattr(self.fed, "cohort_chunk", None),
            place_slab_fn=self.engine.backend.place_slab)
        try:
            if sched.loss_free:
                self._run_pipelined(sched, builder, rounds, verbose)
            else:
                self._run_feedback(sched, builder, rounds, verbose)
        finally:
            builder.close()
        self._completed_rounds = rounds
        return self.history

    # ------------------------------------------------------------------
    def _dispatch(self, bucket: Bucket, bb: pipeline.BucketBatch):
        """Run one bucket on device; returns the (B, N) first-loss futures
        and the bucket's (B,) adaptive downlink levels (None without an
        adaptive codec) — captured immediately because the engine attribute
        is overwritten by the next pipelined dispatch."""
        pad = bucket.shape_rounds - len(bucket)
        etas = np.asarray(list(bucket.etas) + [bucket.etas[-1]] * pad,
                          np.float32)
        self.params, firsts, _lasts, self.server_state = \
            self.engine.run_bucket(self.params, bb.batches, bb.weights,
                                   etas, bb.active, self.server_state)
        levels = (self.engine.last_downlink_levels
                  if getattr(self.runtime, "downlink_level_ratios", None)
                  is not None else None)
        self.store.advance(len(bucket))   # params committed for B rounds
        return firsts, levels

    def _submit(self, builder, bucket: Bucket) -> None:
        """Announce a bucket to the builder: a whole K-bucket, or — under
        streaming cohorts (DESIGN.md §11) — the single round's slab
        stream."""
        if getattr(self.fed, "cohort_chunk", None):
            builder.submit_slabs(bucket.k, round_id=bucket.rounds[0])
        else:
            builder.submit(len(bucket), bucket.k, pad_to=bucket.shape_rounds,
                           rounds=bucket.rounds)

    def _pull_dispatch(self, bucket: Bucket, builder):
        with obs.span("round.dispatch"):
            if getattr(self.fed, "cohort_chunk", None):
                return self._dispatch_chunked(bucket, builder)
            return self._dispatch(bucket, self._get(builder))

    def _get(self, builder):
        """The builder's next item, its wait added to ``feed_wait_s``."""
        t = time.perf_counter()
        with obs.span("feed.wait"):
            item = builder.get()
        self.feed_wait_s += time.perf_counter() - t
        return item

    def _dispatch_chunked(self, bucket: Bucket, builder):
        """One streaming round (the scheduler forces 1-round buckets under
        chunking): pull the round's ceil(U/C) slabs off the builder and
        fold them through the engine's slab/finalize executables. No
        adaptive downlink levels — chunking rejects downlink codecs."""
        n = min(self.fed.clients_per_round, self.data.num_clients)
        c = min(max(int(self.fed.cohort_chunk), 1), n)
        n_slabs = -(-n // c)

        def slabs():
            for _ in range(n_slabs):
                yield self._get(builder)

        self.params, firsts, _lasts, self.server_state = \
            self.engine.run_round_chunked(self.params, slabs(),
                                          bucket.etas[0], self.server_state)
        self.store.advance(1)
        return firsts, None

    def _run_pipelined(self, sched: RoundScheduler, builder, rounds: int,
                       verbose: bool) -> None:
        plan = sched.plan()
        pending: Optional[Tuple[Bucket, jax.Array, Any]] = None
        nxt = next(plan, None)
        if nxt is not None:
            self._submit(builder, nxt)
        while nxt is not None:
            cur, nxt = nxt, next(plan, None)
            if nxt is not None:   # scheduler announces the upcoming K-bucket
                self._submit(builder, nxt)
            firsts, levels = self._pull_dispatch(cur, builder)
            if pending is not None:     # sync bucket r-1 while r computes
                self._absorb(*pending)
                pending = None
            if cur.eval_after or cur.serve_after:
                # serve buckets absorb immediately too: the serve tick in
                # _absorb must run before the *next* dispatch commits, which
                # is what bounds served-version staleness at 1 (§14)
                self._absorb(cur, firsts, levels)
                if cur.eval_after:
                    self._eval(cur.rounds[-1], verbose)
            else:
                pending = (cur, firsts, levels)
        if pending is not None:
            self._absorb(*pending)

    def _run_feedback(self, sched: RoundScheduler, builder, rounds: int,
                      verbose: bool) -> None:
        # plan() is lazy: each iteration consults the controller, which has
        # absorbed the previous bucket's losses by the time it is advanced
        for bucket in sched.plan():
            self._submit(builder, bucket)
            firsts, levels = self._pull_dispatch(bucket, builder)
            self._absorb(bucket, firsts, levels)  # boundary sync
            if bucket.eval_after:
                self._eval(bucket.rounds[-1], verbose)

    # ------------------------------------------------------------------
    def _absorb(self, bucket: Bucket, firsts: jax.Array,
                levels=None) -> None:
        """Materialise a finished bucket into controller + history state.

        ``levels``: the bucket's (B,) adaptive downlink levels — only
        supplied (by ``_dispatch``) when the runtime carries per-level
        ratios, so fixed-rate codecs keep the historical charge exactly."""
        with obs.span("round.absorb"):
            with obs.span("loss.sync"):
                losses = np.asarray(firsts)       # device sync
            lv = None if levels is None else np.asarray(levels)
            h = self.history
            for i, r in enumerate(bucket.rounds):
                round_loss = float(np.mean(losses[i]))
                self.ctrl.observe_round_losses(round_loss)
                cost = self.runtime.round_cost(
                    bucket.k,
                    downlink_level=None if lv is None else int(lv[i]))
                self._wall += cost.wall_clock_s
                self._steps += cost.sgd_steps
                self._up_mbit += cost.uplink_mbit
                self._down_mbit += cost.downlink_mbit
                self.store.serve_queries += cost.serve_queries
                self._min_loss = min(self._min_loss, round_loss)
                h.rounds.append(r)
                h.k.append(bucket.k)
                h.eta.append(bucket.etas[i])
                h.wall_clock_s.append(self._wall)
                h.sgd_steps.append(self._steps)
                h.uplink_mbit.append(self._up_mbit)
                h.downlink_mbit.append(self._down_mbit)
                h.train_loss.append(round_loss)
                h.min_train_loss.append(self._min_loss)
                if (self.serving is not None and self.serve_every
                        and r % self.serve_every == 0):
                    self.serving.tick(r, h)

    # ------------------------------------------------------------------
    # full-state checkpointing (DESIGN.md §8: transport/EF state included)
    # ------------------------------------------------------------------
    def save_state(self, path: str,
                   extra_meta: Optional[Dict[str, Any]] = None) -> None:
        """Checkpoint everything a bitwise-identical continuation needs:
        params, server-optimizer state, transport error-feedback state, the
        numpy rng stream, controller feedback state, history and the
        simulated-cost counters. Restore with ``restore_state`` and continue
        via ``run(rounds, resume=True)``.

        ``extra_meta``: JSON-serializable entries merged into ``meta.json``
        (``FederatedExperiment.save`` embeds the ExperimentSpec here so a
        checkpoint alone rebuilds the exact trainer)."""
        from repro.checkpoint import save_checkpoint
        sd = self.store.state_dict()
        meta = {
            **(extra_meta or {}),
            "completed_rounds": self._completed_rounds,
            "history": self.history.as_dict(),
            "rng": self._np_rng.bit_generator.state,
            # straggler-model draw stream (heterogeneity > 0 consumes it
            # every round_cost call)
            "runtime_rng": self.runtime._rng.bit_generator.state,
            "wall": self._wall,
            **sd["meta"],
            "ctrl": self.ctrl.state_dict(),
        }
        save_checkpoint(path, sd["tree"], meta=meta)

    def restore_state(self, path: str) -> None:
        """Inverse of ``save_state`` on a trainer built with the same
        configuration (templates for every state tree come from the live
        trainer)."""
        # the q8 legacy-key fallback (pre-q8 checkpoint into a
        # ref_store="q8" trainer, DESIGN.md §10.3) lives in the store now
        tree, meta = self.store.load_checkpoint_tree(path)
        self.store.restore_tree(tree)
        self._completed_rounds = int(meta["completed_rounds"])
        self.history = History.from_dict(meta["history"])
        h = self.history
        if len(h.downlink_mbit) < len(h.rounds):
            # pre-downlink checkpoint: backfill the new cumulative series
            # (no broadcast bytes were charged then) so the per-round lists
            # stay index-aligned for CSV writers/plots
            h.downlink_mbit = ([0.0] * (len(h.rounds)
                                        - len(h.downlink_mbit))
                               + h.downlink_mbit)
        self._np_rng.bit_generator.state = meta["rng"]
        if "runtime_rng" in meta:
            self.runtime._rng.bit_generator.state = meta["runtime_rng"]
        self._wall = float(meta["wall"])
        # pre-PR-10 meta has no store_version: fall back to the round count
        self.store.load_counters_meta(
            meta, default_version=self._completed_rounds)
        self.ctrl.load_state_dict(meta["ctrl"])

    def _eval(self, r: int, verbose: bool) -> None:
        metrics = self.eval_fn(self.params)
        err = metrics.get("error", 1.0 - metrics.get("acc", 0.0))
        self.ctrl.observe_validation(err)
        self._max_acc = max(self._max_acc, metrics.get("acc", 0.0))
        h = self.history
        h.val_rounds.append(r)
        h.val_error.append(err)
        h.max_val_acc.append(self._max_acc)
        if verbose:
            print(f"round {r:5d} K={h.k[-1]:3d} eta={h.eta[-1]:.4f} "
                  f"loss={h.train_loss[-1]:.4f} val_err={err:.4f} "
                  f"W={self._wall:.1f}s steps={self._steps}")


# ---------------------------------------------------------------------------
# evaluation
# ---------------------------------------------------------------------------

def make_eval_fn(loss_fn: LossFn, data: FederatedData, batch_size: int = 128):
    """Validation accuracy/error over the global validation split.

    Per-batch means are weighted by batch size so the ragged tail batch
    (``val_batches`` keeps the remainder) contributes exactly its share.
    """
    batches = pipeline.val_batches(data, batch_size)

    @jax.jit
    def eval_batch(params, batch):
        loss, metrics = loss_fn(params, batch)
        return loss, metrics.get("acc", jax.numpy.zeros(()))

    def eval_fn(params) -> Dict[str, float]:
        loss_sum = acc_sum = 0.0
        n_tot = 0
        for b in batches:
            n = len(b["y"])
            l, a = eval_batch(params,
                              {k: jax.numpy.asarray(v) for k, v in b.items()})
            loss_sum += float(l) * n
            acc_sum += float(a) * n
            n_tot += n
        acc = acc_sum / max(n_tot, 1)
        return {"loss": loss_sum / max(n_tot, 1), "acc": acc,
                "error": 1.0 - acc}

    return eval_fn
