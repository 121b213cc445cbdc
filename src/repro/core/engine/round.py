"""Round execution: single-round core + K-bucketed multi-round scan.

Layering (DESIGN.md §6-§7):

    ClientUpdate (engine.client)   — K-step local SGD, vmapped over clients
    Aggregator   (engine.aggregators) — client-stack -> aggregate
    ServerOptimizer (engine.server)   — aggregate -> next global params
    ExecutionBackend (engine.backends) — where/how the fan-out executes

``RoundEngine`` asks its backend for the round core (LocalBackend: plain
vmap; MeshBackend: GSPMD-sharded vmap or grouped sequential scan) and
executes *buckets*: consecutive rounds sharing one quantized K, run as a
single multi-round ``lax.scan`` over the round axis. Each distinct input
signature (shapes + dtypes of params/batches/weights/etas/active/state) is
AOT-lowered and compiled exactly once into an explicit executable registry,
so with K snapped to the geometric grid (``quantize_k``) the compile count
is bounded by the grid size — and ``compile_count`` reports the registry
size exactly instead of probing jit-internal caches.

Buckets shorter than the executable shape are padded by repeating the last
round's batches with ``active=False``; inactive rounds pass params and
server state through a ``jnp.where`` select, which is bitwise transparent,
so padding never perturbs training state.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core.engine.aggregators import Aggregator, get_aggregator
from repro.core.engine.backends.base import (ExecutionBackend,
                                             LINEAR_AGGREGATORS)
from repro.core.engine.backends.local import (LocalBackend,
                                              make_parallel_round_core)
from repro.core.engine.model_store import GlobalModelStore
from repro.core.engine.server import ServerOptimizer, get_server_optimizer
from repro.core.engine.transport import get_downlink, get_transport

PyTree = Any
LossFn = Callable[[PyTree, Dict[str, jnp.ndarray]], Any]


def make_round_core(loss_fn: LossFn, aggregator: Aggregator,
                    server: ServerOptimizer, server_lr: float):
    """round_core(params, batches{(N,K,b,...)}, weights(N,), eta, state)
    -> (new_params, first_losses (N,), last_losses (N,), state)."""
    return make_parallel_round_core(loss_fn, aggregator, server, server_lr)


def make_bucket_fn(round_core):
    """Multi-round scan over a K-bucket.

    bucket_fn(params, batches{(B,N,K,b,...)}, weights(B,N), etas(B,),
              active(B,) bool, server_state)
        -> (new_params, first_losses (B,N), last_losses (B,N), server_state)
    """
    def bucket_fn(params, batches, weights, etas, active, server_state):
        def body(carry, xs):
            params, state = carry
            b, w, eta, act = xs
            new_p, first, last, new_s = round_core(params, b, w, eta, state)
            with obs.scope("server.step"):
                new_p = jax.tree.map(lambda n, o: jnp.where(act, n, o),
                                     new_p, params)
                new_s = jax.tree.map(lambda n, o: jnp.where(act, n, o),
                                     new_s, state)
            return (new_p, new_s), (first, last)

        (params, server_state), (firsts, lasts) = jax.lax.scan(
            body, (params, server_state), (batches, weights, etas, active))
        return params, firsts, lasts, server_state

    return bucket_fn


def make_transport_bucket_fn(round_core):
    """Multi-round scan for a transport-threaded core (DESIGN.md §8): the
    carry additionally holds the codec's error-feedback state, masked on
    padding rounds with the same bitwise-transparent ``jnp.where`` select
    as params and server state.

    bucket_fn(params, batches, weights, etas, active, server_state, t_state)
        -> (new_params, first_losses, last_losses, server_state, t_state)
    """
    def bucket_fn(params, batches, weights, etas, active, server_state,
                  t_state):
        def body(carry, xs):
            params, state, tstate = carry
            b, w, eta, act = xs
            new_p, first, last, new_s, new_t = round_core(
                params, b, w, eta, state, tstate)
            sel = lambda n, o: jnp.where(act, n, o)
            with obs.scope("server.step"):
                new_p = jax.tree.map(sel, new_p, params)
                new_s = jax.tree.map(sel, new_s, state)
                new_t = jax.tree.map(sel, new_t, tstate)
            return (new_p, new_s, new_t), (first, last)

        (params, server_state, t_state), (firsts, lasts) = jax.lax.scan(
            body, (params, server_state, t_state),
            (batches, weights, etas, active))
        return params, firsts, lasts, server_state, t_state

    return bucket_fn


def make_downlink_bucket_fn(round_core):
    """Multi-round scan for a downlink-fused core (DESIGN.md §10): the
    carry's trailing slot is the downlink state (or the ``(uplink,
    downlink)`` pair) and the core emits one extra per-round output — the
    adaptive codec level — stacked as a ``(B,)`` int32 ys alongside the
    losses. Padding rounds mask the state with the bitwise-transparent
    ``jnp.where`` select and report level -1 (the "not a real round"
    sentinel the trainer skips when charging the wire).

    bucket_fn(params, batches, weights, etas, active, server_state, extra)
        -> (new_params, first_losses, last_losses, server_state, extra,
            levels (B,) int32)
    """
    def bucket_fn(params, batches, weights, etas, active, server_state,
                  extra):
        def body(carry, xs):
            params, state, ex = carry
            b, w, eta, act = xs
            new_p, first, last, new_s, new_e, level = round_core(
                params, b, w, eta, state, ex)
            sel = lambda n, o: jnp.where(act, n, o)
            with obs.scope("server.step"):
                new_p = jax.tree.map(sel, new_p, params)
                new_s = jax.tree.map(sel, new_s, state)
                new_e = jax.tree.map(sel, new_e, ex)
                level = jnp.where(act, level, jnp.int32(-1))
            return (new_p, new_s, new_e), (first, last, level)

        (params, server_state, extra), (firsts, lasts, levels) = jax.lax.scan(
            body, (params, server_state, extra),
            (batches, weights, etas, active))
        return params, firsts, lasts, server_state, extra, levels

    return bucket_fn


def _signature(args) -> Tuple:
    """Hashable (treedef, leaf shapes/dtypes) key for the AOT registry."""
    leaves, treedef = jax.tree.flatten(args)
    return treedef, tuple((tuple(l.shape), jnp.result_type(l).name)
                          for l in leaves)


class ExecutableRegistry:
    """Process-level AOT executable cache, shareable across experiments.

    Entries are keyed by the engine compile key — ``(program_key,
    codec/downlink signature, argument treedef + leaf shapes/dtypes)`` — so
    two experiments share an executable exactly when they would lower the
    same traced program for the same input signature (DESIGN.md §12). The
    fleet driver hands one registry to every sweep point; points whose
    model/bucket/transport signatures coincide compile once and dispatch N
    times.

    ``get_or_build`` is thread-safe and single-flight: when packed sweep
    points race on one key, exactly one thread compiles while the rest wait
    on the in-flight event — "compile once, dispatch N" holds under
    concurrent packing.
    """

    def __init__(self):
        self._entries: Dict[Tuple, Any] = {}
        self._inflight: Dict[Tuple, threading.Event] = {}
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: Tuple) -> bool:
        with self._lock:
            return key in self._entries

    @property
    def compile_count(self) -> int:
        """Distinct executables compiled into this registry (exact)."""
        return len(self._entries)

    def executables(self) -> Tuple[Any, ...]:
        with self._lock:
            return tuple(self._entries.values())

    def get_or_build(self, key: Tuple, build: Callable[[], Any]
                     ) -> Tuple[Any, bool]:
        """Return ``(executable, built)``: the cached entry for ``key``, or
        the result of ``build()`` (stored under ``key``). ``built`` is True
        only for the caller that actually compiled — a concurrent caller
        that waited on the in-flight compile gets ``built=False``, so
        per-engine compile counters never double-count one compilation."""
        while True:
            with self._lock:
                exe = self._entries.get(key)
                if exe is not None:
                    return exe, False
                ev = self._inflight.get(key)
                if ev is None:
                    ev = threading.Event()
                    self._inflight[key] = ev
                    break
            ev.wait()           # someone else is compiling this key
        try:
            exe = build()
        except BaseException:
            with self._lock:
                del self._inflight[key]
            ev.set()
            raise
        with self._lock:
            self._entries[key] = exe
            del self._inflight[key]
        ev.set()
        return exe, True


class RoundEngine:
    """Bucket executor with an explicit per-signature executable registry.

    The backend decides execution geometry and placement; the engine owns
    compilation: ``run_bucket`` looks the placed arguments' signature up in
    the registry and AOT-compiles (``jit(...).lower(...).compile()``) on
    miss — one executable per distinct signature, counted exactly by
    ``compile_count`` (no reliance on private jit cache probes).
    """

    def __init__(self, loss_fn: LossFn, *, aggregator: str = "mean",
                 trim_fraction: float = 0.1, server: str = "avg",
                 server_lr: float = 1.0,
                 backend: Optional[ExecutionBackend] = None,
                 transport=None, topk_frac: float = 0.1, downlink=None,
                 downlink_ref: str = "f32",
                 cohort_chunk: Optional[int] = None,
                 registry: Optional[ExecutableRegistry] = None,
                 program_key: Optional[Tuple] = None):
        """``transport``: None/"none" keeps the historical param-space
        aggregation path bit-for-bit; "int8"/"int8x2"/"topk" (or a
        ``Transport`` instance) routes aggregation through the compressed
        delta pipeline (DESIGN.md §8). Compressed codecs require a linear
        aggregator; their error-feedback state is engine-owned
        (``transport_state``) and threads through every bucket scan.

        ``downlink``: None/"none" keeps the historical uncompressed server
        broadcast bit-for-bit; a codec name (or ``DownlinkCodec``) makes
        every round reconstruct the client model as ``params_ref +
        decode(payload)`` before local SGD (DESIGN.md §8.6) — decoded
        lazily inside the client step (DESIGN.md §10). The broadcast
        reference + downlink residual are engine-owned
        (``downlink_state``) and thread the bucket scan carry alongside
        the uplink state. Orthogonal to the aggregator choice.

        ``downlink_ref``: storage for the engine-owned broadcast reference
        and residual — "f32" (default, bit-exact PR-5 behaviour) or "q8"
        (int8+scale leaves, ~2x less server-held state, DESIGN.md §10.3).
        Requires a configured downlink codec.

        ``registry``: a shared ``ExecutableRegistry`` for cross-experiment
        executable reuse (DESIGN.md §12). When given, ``program_key`` is
        required — a hashable fingerprint of everything that shapes the
        traced program but is NOT in the input signature (model/task,
        aggregator/server, transport+downlink config, backend placement).
        Entries are keyed ``(program_key, codec_sig) + signature``, so two
        engines whose program keys and signatures coincide share one AOT
        executable; distinct codecs/backends never collide because their
        keys differ. Omitted, the engine owns a private registry and
        behaves exactly as before."""
        self.backend = backend if backend is not None else LocalBackend()
        self.transport = get_transport(transport, topk_frac=topk_frac)
        if self.transport is not None and \
                getattr(self.transport, "name", "") != "none" and \
                aggregator not in LINEAR_AGGREGATORS:
            raise ValueError(
                f"transport {self.transport.name!r} requires a linear "
                f"aggregator {LINEAR_AGGREGATORS}, got {aggregator!r}")
        self.downlink = self.backend.bind_downlink(
            get_downlink(downlink, topk_frac=topk_frac,
                         ref_store=downlink_ref))
        if self.downlink is None and downlink_ref != "f32":
            raise ValueError(
                f"downlink_ref={downlink_ref!r} requires a downlink codec")
        self.server = get_server_optimizer(server)
        self.round_core = self.backend.make_round_core(
            loss_fn, aggregator=aggregator, trim_fraction=trim_fraction,
            server=self.server, server_lr=server_lr,
            transport=self.transport, downlink=self.downlink)
        # streaming cohorts (DESIGN.md §11): the slab/finalize jits exist
        # only when chunking is on — cohort_chunk=None leaves the engine's
        # compiled program (and its executable registry) bit-for-bit
        # identical to the unchunked build
        self.cohort_chunk = cohort_chunk
        if cohort_chunk:
            if self.downlink is not None:
                raise ValueError(
                    "cohort_chunk cannot combine with a downlink codec: "
                    "the broadcast reference advances round-atomically and "
                    "does not stream over slabs")
            if aggregator not in LINEAR_AGGREGATORS:
                raise ValueError(
                    f"cohort_chunk requires a linear aggregator "
                    f"{LINEAR_AGGREGATORS}: streaming slabs fold into a "
                    f"running weighted sum, got {aggregator!r}")
            slab_core, fin_core = self.backend.make_slab_cores(
                loss_fn, aggregator=aggregator, server=self.server,
                server_lr=server_lr, transport=self.transport)
            chunk_per_client = (self.transport is not None
                                and self.transport.ef_slots is not None)

            def slab(params, batches, weights, eta, acc, ef):
                acc, f, l, ef = slab_core(params, batches, weights, eta,
                                          acc, ef)
                be = self.backend
                acc = (be.constrain_update(acc[0]),
                       be.constrain_update(acc[1]))
                ef = be.constrain_transport_update(
                    ef, per_client=chunk_per_client)
                return acc, f, l, ef

            def slabfin(params, acc, server_state):
                p, s, res = fin_core(params, acc, server_state)
                be = self.backend
                return be.constrain_update(p), s, be.constrain_update(res)

            self._jit_slab = jax.jit(slab)
            self._jit_slabfin = jax.jit(slabfin)
        # codec signature participates in the executable-registry key; the
        # downlink signature nests around it only when a downlink codec is
        # configured, so downlink="none" keys are untouched
        self._codec_sig = (() if self.transport is None
                           else self.transport.signature())
        if self.downlink is not None:
            self._codec_sig = (self._codec_sig, self.downlink.signature())
        if self.transport is None and self.downlink is None:
            raw = make_bucket_fn(self.round_core)

            def bucket(params, batches, weights, etas, active, server_state):
                p, f, l, s = raw(params, batches, weights, etas, active,
                                 server_state)
                return self.backend.constrain_update(p), f, l, s
        elif self.downlink is None:
            raw = make_transport_bucket_fn(self.round_core)
            per_client = self.transport.ef_slots is not None

            def bucket(params, batches, weights, etas, active, server_state,
                       t_state):
                p, f, l, s, t = raw(params, batches, weights, etas, active,
                                    server_state, t_state)
                be = self.backend
                return (be.constrain_update(p), f, l, s,
                        be.constrain_transport_update(t,
                                                      per_client=per_client))
        else:
            # downlink-fused core (built by the backend, DESIGN.md §10):
            # the bucket scan threads the downlink state and stacks the
            # per-round adaptive levels
            raw = make_downlink_bucket_fn(self.round_core)
            per_client = (self.transport is not None
                          and self.transport.ef_slots is not None)

            def bucket(params, batches, weights, etas, active, server_state,
                       extra):
                p, f, l, s, extra, levels = raw(params, batches, weights,
                                                etas, active, server_state,
                                                extra)
                be = self.backend
                d_state = extra if self.transport is None else extra[1]
                d_state = {
                    "ref": be.constrain_update(d_state["ref"]),
                    "res": be.constrain_update(d_state["res"]),
                }
                if self.transport is not None:
                    t = be.constrain_transport_update(extra[0],
                                                      per_client=per_client)
                    extra = (t, d_state)
                else:
                    extra = d_state
                return be.constrain_update(p), f, l, s, extra, levels
        self._jitted = jax.jit(bucket)
        if registry is not None and program_key is None:
            raise ValueError(
                "a shared ExecutableRegistry requires a program_key: the "
                "registry is keyed across experiments, so the engine must "
                "know which traced program its entries belong to")
        self._registry = registry if registry is not None \
            else ExecutableRegistry()
        self._program_key = program_key if program_key is not None else ()
        # engine-local view of the registry entries this engine touched:
        # mem.engine_peak_mb sizes live executables through it, and it keeps
        # the private-registry case bit-for-bit (compile_count == len)
        self._executables: Dict[Tuple, Any] = {}
        self._own_keys: set = set()     # compiled by THIS engine
        self._shared_keys: set = set()  # adopted from the shared registry
        self.dispatch_count = 0
        # host seconds spent in the round executables' calls: where the
        # host blocks when the device has no memory for the next call yet
        self.dispatch_s = 0.0
        # wire-state ownership lives in a GlobalModelStore (DESIGN.md §14);
        # the engine starts with a private one and the trainer re-binds its
        # own via bind_store(). transport_state/downlink_state stay
        # readable/writable attributes (store-backed properties below).
        self._store = GlobalModelStore(downlink=self.downlink)
        # (B,) int32 adaptive levels of the most recent bucket (-1 entries:
        # padding rounds / fixed-rate codecs); None until a downlink bucket
        # has run. The trainer reads this right after each dispatch to
        # charge the wire per level (DESIGN.md §10.4).
        self.last_downlink_levels = None

    def bind_store(self, store: GlobalModelStore) -> GlobalModelStore:
        """Adopt a trainer-owned GlobalModelStore as the wire-state owner.
        Any state the private store already holds migrates over; the codec
        binding moves with it so ``store.snapshot()`` brackets through this
        engine's ``store_tree``/``load_tree`` path."""
        store.downlink = self.downlink
        store.transport_state = self._store.transport_state
        store.downlink_state = self._store.downlink_state
        self._store = store
        return store

    @property
    def transport_state(self) -> Any:
        return self._store.transport_state

    @transport_state.setter
    def transport_state(self, value: Any) -> None:
        self._store.transport_state = value

    @property
    def downlink_state(self) -> Any:
        return self._store.downlink_state

    @downlink_state.setter
    def downlink_state(self, value: Any) -> None:
        self._store.downlink_state = value

    def _lookup(self, key: Tuple, jitted, args):
        """Fetch (or AOT-compile) the executable for ``key``.

        The full registry key prepends ``program_key`` so shared registries
        never alias across experiments; counters are exact either way: a
        key this engine compiled lands in ``_own_keys`` (-> compile_count),
        a registry hit built by another engine lands in ``_shared_keys``
        (-> shared_count) and is never double-counted as a local compile.

        Private registries (no program_key) keep the bare legacy key shape
        — ``key[0]`` stays the "slab"/"slabfin" tag some introspection
        relies on; aliasing is impossible in a single-engine registry.
        """
        full_key = (self._program_key,) + key if self._program_key else key
        exe = self._executables.get(full_key)
        if exe is None:
            exe, built = self._registry.get_or_build(
                full_key, lambda: jitted.lower(*args).compile())
            self._executables[full_key] = exe
            (self._own_keys if built else self._shared_keys).add(full_key)
        return exe

    def _call(self, name: str, exe, args):
        """Run one round executable under the host span ``name``, adding
        its host time to ``dispatch_s``."""
        t = time.perf_counter()
        with obs.span(name):
            out = exe(*args)
        self.dispatch_s += time.perf_counter() - t
        return out

    def init_server_state(self, params: PyTree) -> Any:
        return self.server.init(params)

    def init_transport_state(self, params: PyTree) -> Any:
        """Create (and own) the codec's error-feedback state. Engine-owned
        so ``run_bucket``'s signature and 4-tuple result stay unchanged;
        the trainer checkpoints it via ``transport_state``."""
        self.transport_state = (() if self.transport is None
                                else self.transport.init_state(params))
        return self.transport_state

    def init_downlink_state(self, params: PyTree) -> Any:
        """Create (and own) the downlink broadcast state: the reference
        params every client holds plus the downlink EF residual
        (DESIGN.md §8.6). The trainer checkpoints it via
        ``downlink_state``."""
        self.downlink_state = (() if self.downlink is None
                               else self.downlink.init_state(params))
        return self.downlink_state

    def run_bucket(self, params, batches, weights, etas, active, server_state
                   ) -> Tuple[PyTree, jnp.ndarray, jnp.ndarray, Any]:
        """batches leaves (B, N, K, b, ...); weights (B, N); etas/active (B,).

        Inputs may be host (numpy) or already-placed device arrays — the
        backend's placement hooks are idempotent, so prefetched buckets that
        were ``device_put`` on the build thread pass through untouched.
        """
        be = self.backend
        params = be.place_params(params)
        batches = be.place_batches(batches)
        weights = be.place_weights(weights)
        etas, active = be.place_scalars(etas, active)
        server_state = jax.tree.map(jnp.asarray, server_state)
        has_t, has_d = self.transport is not None, self.downlink is not None
        if not has_t and not has_d:
            args = (params, batches, weights, etas, active, server_state)
        else:
            if has_t:
                if self.transport_state is None:
                    self.init_transport_state(params)
                t_state = be.place_transport_state(
                    self.transport_state,
                    per_client=self.transport.ef_slots is not None)
            if has_d:
                if self.downlink_state is None:
                    self.init_downlink_state(params)
                d_state = be.place_downlink_state(self.downlink_state)
            extra = ((t_state, d_state) if has_t and has_d
                     else (t_state if has_t else d_state))
            args = (params, batches, weights, etas, active, server_state,
                    extra)
        key = (self._codec_sig,) + _signature(args)
        exe = self._lookup(key, self._jitted, args)
        self.dispatch_count += 1
        out = self._call("bucket.call", exe, args)
        if not has_t and not has_d:
            return out
        if has_d:
            params, firsts, lasts, server_state, extra, levels = out
            self.last_downlink_levels = levels
        else:
            params, firsts, lasts, server_state, extra = out
        if has_t and has_d:
            self.transport_state, self.downlink_state = extra
        elif has_t:
            self.transport_state = extra
        else:
            self.downlink_state = extra
        return params, firsts, lasts, server_state

    def run_round_chunked(self, params, slabs, eta, server_state
                          ) -> Tuple[PyTree, jnp.ndarray, jnp.ndarray, Any]:
        """Execute ONE round as streamed C-client slabs (DESIGN.md §11).

        ``slabs``: iterable of ``pipeline.SlabBatch`` covering the round's
        cohort in order (host or already-placed — ``place_slab`` is
        idempotent). Device memory in the client dim is O(C): the only
        cross-slab device state is the params-shaped f32 accumulator pair
        plus the current slab's EF slice. Returns the ``run_bucket``
        4-tuple with a B == 1 leading dim on the stacked losses.

        Engine-owned EF state commits round-atomically — per-client slab
        residuals accumulate host-side and replace ``transport_state`` only
        after the finalize step, so a checkpoint taken between rounds can
        never observe mid-round slab state.
        """
        if not self.cohort_chunk:
            raise ValueError("engine was built without cohort_chunk")
        be = self.backend
        params = be.place_params(params)
        server_state = jax.tree.map(jnp.asarray, server_state)
        has_t = self.transport is not None
        per_client = has_t and self.transport.ef_slots is not None
        agg_ef = (has_t and self.transport.error_feedback
                  and not per_client)
        if has_t and self.transport_state is None:
            self.init_transport_state(params)
        zeros = be.place_params(jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))
        acc = (zeros, zeros if agg_ef else ())
        # Each params-sized f32 buffer is dropped as soon as no later call
        # reads it: the zeros, every slab's inputs (the previous
        # accumulators among them) and, under aggregate EF, the slab's
        # pass-through residual. At published widths each one held across
        # the next slab or the finalize is 1.86 GB of a 16 GB chip.
        del zeros
        eta = jnp.asarray(eta, jnp.float32)
        firsts, lasts, ef_parts = [], [], []
        for sb in slabs:
            with obs.span("slab.place"):
                sb = be.place_slab(sb)
                ef = ()
                if per_client:
                    ef = be.place_transport_state(
                        jax.tree.map(lambda s: s[sb.start:sb.stop],
                                     self.transport_state), per_client=True)
                elif agg_ef:
                    ef = be.place_transport_state(self.transport_state)
            args = (params, sb.batches, sb.weights, eta, acc, ef)
            key = ("slab", self._codec_sig) + _signature(args)
            exe = self._lookup(key, self._jit_slab, args)
            acc, f, l, ef = self._call("slab.call", exe, args)
            firsts.append(f)
            lasts.append(l)
            if per_client:
                ef_parts.append(ef)
            del args, ef
        if not firsts:
            raise ValueError("run_round_chunked got an empty slab stream")
        fargs = (params, acc, server_state)
        key = ("slabfin", self._codec_sig) + _signature(fargs)
        exe = self._lookup(key, self._jit_slabfin, fargs)
        new_params, server_state, new_res = self._call("finalize.call", exe,
                                                       fargs)
        if per_client:
            self.transport_state = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *ef_parts)
        elif agg_ef:
            self.transport_state = new_res
        self.dispatch_count += 1
        return (new_params, jnp.concatenate(firsts)[None],
                jnp.concatenate(lasts)[None], server_state)

    @property
    def compile_count(self) -> int:
        """Distinct bucket executables built BY THIS ENGINE (exact). With a
        private registry this equals the historical registry size; with a
        shared registry, executables adopted from other experiments are
        excluded — they count under ``shared_count`` instead."""
        return len(self._own_keys)

    @property
    def shared_count(self) -> int:
        """Distinct executables this engine reused from the shared registry
        without compiling (0 with a private registry)."""
        return len(self._shared_keys)

    @property
    def registry(self) -> ExecutableRegistry:
        return self._registry


def make_round_fn(loss_fn: LossFn, *, server: str = "avg",
                  server_lr: float = 1.0, aggregator: str = "mean",
                  use_kernel_avg: Optional[bool] = None):
    """Seed-compatible single-round builder (one jitted FedAvg round).

    round_fn(params, batches{(N,K,b,...)}, weights (N,), eta, server_state)
        -> (new_params, first_losses (N,), mean_last_loss, server_state)

    Returns ``(round_fn, srv_init)`` where ``srv_init`` is None for the
    stateless ``avg`` server (its state is ``()``), matching the historical
    ``make_round_fn`` contract that `tests` and benchmarks rely on.

    ``aggregator`` resolves through the plugin registry;
    ``use_kernel_avg`` is DEPRECATED — pass ``aggregator="kernel"``.
    """
    if use_kernel_avg is not None:
        import warnings
        warnings.warn(
            "make_round_fn(use_kernel_avg=...) is deprecated and will be "
            "removed next release; pass aggregator='kernel' instead.",
            DeprecationWarning, stacklevel=2)
        if use_kernel_avg:
            aggregator = "kernel"
    srv = get_server_optimizer(server)
    core = make_round_core(loss_fn, get_aggregator(aggregator), srv,
                           server_lr)

    def round_fn(params, batches, weights, eta, server_state):
        new_params, first_losses, last_losses, server_state = core(
            params, batches, weights, eta, server_state)
        return new_params, first_losses, jnp.mean(last_losses), server_state

    srv_init = None if server == "avg" else srv.init
    return jax.jit(round_fn), srv_init
