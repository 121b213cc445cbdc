"""Transport — the compressed client-delta wire protocol (DESIGN.md §8).

Sits between ClientUpdate and Aggregator in every execution backend: clients
emit *encoded deltas* and aggregation consumes the payloads directly through
fused decompress-reduce kernels (``kernels.delta_codec``), so compressed
payloads are never materialised at full precision per client.

Codecs:

  * ``none``   — identity. ``get_transport("none")`` returns None and the
    engine keeps its historical param-space aggregation path verbatim, so
    the compiled program (and results) are bit-identical to the
    pre-transport engine. ``IdentityTransport`` is the same contract spelled
    through the protocol (used by tests).
  * ``int8``   — per-leaf int8 quantisation of the flattened delta, reusing
    the Q-KV quantiser (``models.attention.quantize_kv``: per-vector max/127
    scale). One int8 plane rides the wire (~4x uplink reduction, asymptotic
    in leaf size); the quantisation *residual is folded into the server-side
    error-feedback state* instead of being transmitted — the second Q-KV
    level for free, amortised across rounds.
  * ``int8x2`` — both Q-KV levels on the wire (primary + int8 residual,
    ``quantize_kv_residual`` verbatim): ~2x reduction, per-round error small
    enough (~1e-4 relative) that no feedback state is needed.
  * ``topk``   — magnitude top-k of the flattened delta (value + int32
    index, ``0.5/frac``x reduction) with server-side error feedback.

Error feedback (Karimireddy et al. '19, adapted to sampled stateless
clients): the paper's clients carry no state between rounds and cohorts
resample every round, so per-client residual memory is impossible — the
residual lives server-side at the *aggregate* level. The server broadcasts
it with the model (downlink already carries |x|); each client encodes
``delta_c + residual``; the new residual is the weighted compression error
``sum_c w_c (delta_c + residual) - hat``. The exact weighted-true-delta term
is directly computable in this single-process simulation; a physical
deployment would estimate it from the decoded payloads plus a residual
correction uplink — recorded in DESIGN.md §8. The residual is part of the
engine's checkpointable state (threads through the bucket scan carry and
``FedAvgTrainer.save_state``).

Compressed codecs require a *linear* aggregator (mean/kernel): the weighted
sum distributes over decode. Robust aggregators (median/trimmed_mean) need
the full client distribution and are rejected at engine construction.

The *downlink* leg (DESIGN.md §8.6, DoubleSqueeze-style bidirectional
compression — Tang et al. '19): ``DownlinkCodec`` wraps any of the codecs
above around the server broadcast. The server keeps the last broadcast
reference ``params_ref`` (exactly the model every client holds), encodes
``params_t - params_ref [+ residual]``, and clients reconstruct
``params_ref + decode(payload)`` through the fused decode-apply kernels
before local SGD — every client trains on the identical reconstructed
model, so the uplink aggregation contract is untouched (robust aggregators
included). The downlink error-feedback residual lives server-side next to
``params_ref``; both are engine state (``RoundEngine.downlink_state``),
thread the K-bucket scan carry and checkpoint with ``save_state``.
``downlink="none"`` keeps the historical broadcast (and compiled program)
bit-for-bit.
"""
from __future__ import annotations

import copy
import math
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# literal reuse of the Q-KV quantisation scheme (two-level int8 + per-vector
# f32 scales — models/attention.py §Perf Q-KV); pure jnp, no layer deps
from repro.api.registries import TRANSPORT_REGISTRY, register_transport
from repro.core import obs
from repro.core.engine.backends.base import axes_size as _axes_size
from repro.models.attention import quantize_kv, quantize_kv_residual

PyTree = Any

TRANSPORTS = ("none", "int8", "int8x2", "topk")   # builtins



def _weighted_true_sum(deltas, weights):
    """sum_c w_c delta_c in f32 — the EF truth term (an einsum per leaf; the
    (N, ...) stack already exists, nothing new is materialised)."""
    w32 = weights.astype(jnp.float32)
    return [jnp.einsum("c,c...->...", w32, d) for d in deltas]


class Transport:
    """Protocol. ``signature()`` keys the engine's compile cache; ``encode``
    runs per client (vmapped on parallel backends, inside the client scan on
    sequential ones); ``reduce`` consumes the stacked payloads fused."""

    name: str = "base"
    error_feedback: bool = False
    #: per-client error-feedback slot count (fixed cohorts, DESIGN.md §9.3):
    #: None = server-aggregate residual (stateless sampled clients); an int N
    #: = one residual slot per cohort slot, valid only when slot j maps to
    #: the same client every round (``ClientSampler.stateful_cohort``).
    ef_slots: Optional[int] = None

    # -- identity / compile-cache -------------------------------------
    def signature(self) -> Tuple:
        """Hashable codec signature, mixed into the AOT registry key."""
        return (self.name, self.error_feedback, self.ef_slots)

    # -- cohort binding -------------------------------------------------
    def with_ef_slots(self, n: int) -> "Transport":
        """A copy carrying per-client error feedback for an ``n``-client
        fixed cohort; identity for codecs without feedback state."""
        if not self.error_feedback:
            return self
        t = copy.copy(self)
        t.ef_slots = int(n)
        return t

    # -- mesh binding ---------------------------------------------------
    def with_mesh(self, mesh, client_axes: Optional[Sequence[str]],
                  reduce_tiers=None):
        """Backend hook: a copy bound to the mesh so ``reduce`` can route
        through the client-sharded decompress-reduce kernel.
        ``reduce_tiers`` selects the hierarchical grouped all-reduce
        (DESIGN.md §11) instead of the flat psum."""
        t = copy.copy(self)
        t._mesh = mesh
        t._client_axes = tuple(client_axes) if client_axes else None
        t._reduce_tiers = (tuple(tuple(tier) for tier in reduce_tiers)
                           if reduce_tiers else None)
        return t

    def _mesh_axes(self):
        return getattr(self, "_mesh", None), getattr(self, "_client_axes", None)

    def _tiers(self):
        return getattr(self, "_reduce_tiers", None)

    # -- state ----------------------------------------------------------
    def init_state(self, params: PyTree):
        if not self.error_feedback:
            return ()
        lead = (self.ef_slots,) if self.ef_slots else ()
        return jax.tree.map(
            lambda p: jnp.zeros(lead + tuple(p.shape), jnp.float32), params)

    # -- codec (per-leaf-list payloads, leaves in tree.flatten order) ----
    def encode(self, delta: PyTree):
        raise NotImplementedError

    def decode(self, payload, like: PyTree) -> PyTree:
        raise NotImplementedError

    def reduce(self, payloads, weights: jnp.ndarray, like: PyTree) -> PyTree:
        """Stacked payloads (leading client axis) -> weighted-sum delta
        pytree, via the fused decompress-reduce kernels."""
        raise NotImplementedError

    def decode_apply(self, payload, ref: PyTree) -> PyTree:
        """``ref + decode(payload)`` — the downlink reconstruction every
        client runs (DESIGN.md §8.6). Default: decode then add; codecs
        override with the fused decode-apply kernels so the dense f32
        delta is never materialised."""
        dec = self.decode(payload, like=ref)
        return jax.tree.map(
            lambda r, d: (r.astype(jnp.float32) + d).astype(r.dtype),
            ref, dec)

    # -- wire accounting -------------------------------------------------
    def encoded_bits(self, params: PyTree) -> int:
        """Uplink bits one client pays per round for this codec."""
        raise NotImplementedError

    def compression_ratio(self, params: PyTree,
                          bits_per_param: int = 32) -> float:
        full = bits_per_param * sum(int(l.size)
                                    for l in jax.tree.leaves(params))
        return full / float(self.encoded_bits(params))

    def nominal_ratio(self, bits_per_param: int = 32) -> float:
        """Asymptotic ratio (scale/metadata overhead -> 0 at model scale);
        used by analytic benches that have no concrete param tree."""
        raise NotImplementedError

    # -- the round-core entry point --------------------------------------
    def aggregate(self, aggregator, params: PyTree, client_stack: PyTree,
                  weights: jnp.ndarray, state):
        """(params, client-stacked params (N, ...), weights (N,), state) ->
        (aggregate pytree, new state). Compressed codecs ignore the
        aggregator (validated linear upstream) and work in delta space."""
        del aggregator
        with obs.scope("uplink.encode"):
            p32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
            deltas = jax.tree.map(
                lambda cp, p: cp.astype(jnp.float32) - p[None],
                client_stack, p32)
            if self.error_feedback:
                # compensate: per-client slots carry their own residual
                # (fixed cohorts), the aggregate residual is broadcast to
                # every client
                deltas = (jax.tree.map(jnp.add, deltas, state)
                          if self.ef_slots
                          else jax.tree.map(lambda d, r: d + r[None], deltas,
                                            state))
            payloads = jax.vmap(self.encode)(deltas)
            with obs.scope("uplink.reduce"):
                hat = self.reduce(payloads, weights, like=params)
            if not self.error_feedback:
                new_state = state
            elif self.ef_slots:
                # per-client residual: each slot keeps ITS OWN compression
                # error (Karimireddy et al. '19, the stateful-client
                # original) — no weighted-truth term, no cross-client
                # mixing. The residual NEEDS the per-client decode, so this
                # mode pays decode twice (once fused inside reduce, once
                # here); hat deliberately stays on the fused reduce so the
                # wire-aggregation program — and its numerics — are
                # identical across EF modes (the parity contracts in
                # tests/test_sampling.py key on this). Decode is O(N*M)
                # elementwise, dwarfed by the K local-SGD steps.
                decoded = jax.vmap(lambda pl: self.decode(pl, like=params))(
                    payloads)
                new_state = jax.tree.map(jnp.subtract, deltas, decoded)
            else:
                true = _weighted_true_sum(jax.tree.leaves(deltas), weights)
                new_state = jax.tree.unflatten(
                    jax.tree.structure(params),
                    [t - h for t, h in zip(true, jax.tree.leaves(hat))])
            with obs.scope("uplink.reduce"):
                aggregate = jax.tree.map(
                    lambda p, h: (p.astype(jnp.float32) + h).astype(p.dtype),
                    params, hat)
            return aggregate, new_state

    def aggregate_slab(self, params: PyTree, client_stack: PyTree,
                       weights: jnp.ndarray, state):
        """One C-client slab's contribution to a streaming round (DESIGN.md
        §11): the delta/EF-compensate/encode/reduce pipeline of
        ``aggregate`` verbatim, but instead of applying the weighted-sum
        delta it RETURNS the partials for the caller to fold into its
        running accumulators.

        ``weights`` are the slab's slice of the global round weights (they
        sum to 1 over the whole cohort, NOT over the slab), so the partial
        sums compose by plain addition. ``state`` is this slab's EF: the
        per-client residual slice for slotted EF, or the round-frozen
        aggregate residual (read-only here — the finalize step derives the
        new one as sum(true) - sum(hat), matching ``aggregate`` exactly).

        Returns ``(hat, true, new_state)``: ``hat`` the f32 weighted-sum of
        decoded deltas, ``true`` the f32 weighted-sum of raw deltas
        (aggregate-EF codecs only, else ``()``), ``new_state`` the slab's
        updated per-client residuals (slotted EF) or ``state`` unchanged."""
        with obs.scope("uplink.encode"):
            p32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
            deltas = jax.tree.map(
                lambda cp, p: cp.astype(jnp.float32) - p[None],
                client_stack, p32)
            if self.error_feedback:
                deltas = (jax.tree.map(jnp.add, deltas, state)
                          if self.ef_slots
                          else jax.tree.map(lambda d, r: d + r[None], deltas,
                                            state))
            payloads = jax.vmap(self.encode)(deltas)
            with obs.scope("uplink.reduce"):
                hat = self.reduce(payloads, weights, like=params)
            if not self.error_feedback:
                return hat, (), state
            if self.ef_slots:
                decoded = jax.vmap(lambda pl: self.decode(pl, like=params))(
                    payloads)
                return hat, (), jax.tree.map(jnp.subtract, deltas, decoded)
            true = _weighted_true_sum(jax.tree.leaves(deltas), weights)
            true_tree = jax.tree.unflatten(jax.tree.structure(params),
                                           list(true))
            return hat, true_tree, state


class IdentityTransport(Transport):
    """The degenerate codec: payloads ARE the client params; aggregation
    delegates to the configured Aggregator verbatim (robust ones included),
    so the round math is exactly the transport-less engine's."""

    name = "none"

    def encode(self, delta):
        return jax.tree.leaves(delta)

    def decode(self, payload, like):
        return jax.tree.unflatten(jax.tree.structure(like), list(payload))

    def encoded_bits(self, params):
        return 32 * sum(int(l.size) for l in jax.tree.leaves(params))

    def nominal_ratio(self, bits_per_param: int = 32) -> float:
        return 1.0

    def aggregate(self, aggregator, params, client_stack, weights, state):
        return aggregator(client_stack, weights), state


class Int8Transport(Transport):
    """Q-KV int8 codec on the flattened per-leaf delta.

    ``levels=1`` (the ``int8`` transport): one int8 plane + one f32 scale
    per leaf on the wire; the quantisation residual is recovered through the
    server-side error-feedback state across rounds. ``levels=2``
    (``int8x2``): ``quantize_kv_residual`` verbatim — primary + residual
    int8 planes with their scales, no feedback state needed.
    """

    name = "int8"

    def __init__(self, levels: int = 1, error_feedback: bool = True):
        if levels not in (1, 2):
            raise ValueError(f"int8 transport levels must be 1 or 2: {levels}")
        self.levels = levels
        self.error_feedback = error_feedback
        if levels == 2:
            self.name = "int8x2"

    def signature(self):
        return (self.name, self.levels, self.error_feedback, self.ef_slots)

    def encode(self, delta):
        out = []
        for leaf in jax.tree.leaves(delta):
            flat = leaf.astype(jnp.float32).reshape(-1)
            if self.levels == 1:
                q, s = quantize_kv(flat)
                out.append({"q": q, "s": s})
            else:
                q, s, qr, rs = quantize_kv_residual(flat)
                out.append({"q": q, "s": s, "qr": qr, "rs": rs})
        return out

    def decode(self, payload, like):
        leaves, treedef = jax.tree.flatten(like)
        dec = []
        for pl, leaf in zip(payload, leaves):
            x = pl["q"].astype(jnp.float32) * pl["s"]
            if self.levels == 2:
                x = x + pl["qr"].astype(jnp.float32) * pl["rs"]
            dec.append(x.reshape(leaf.shape))
        return jax.tree.unflatten(treedef, dec)

    def reduce(self, payloads, weights, like):
        from repro.kernels import ops as kops
        mesh, axes = self._mesh_axes()
        n = weights.shape[0]
        sharded = (mesh is not None and axes
                   and n % _axes_size(mesh, axes) == 0)
        leaves, treedef = jax.tree.flatten(like)
        out = []
        for pl, leaf in zip(payloads, leaves):
            # the flat payloads in the leaf's shape: the kernel takes its
            # lanes from the last dimension and returns the leaf's shape
            stack = lambda x: x.reshape((n,) + leaf.shape)
            w1 = weights.astype(jnp.float32) * pl["s"][:, 0]
            wr = (weights.astype(jnp.float32) * pl["rs"][:, 0]
                  if self.levels == 2 else None)
            qr = stack(pl["qr"]) if self.levels == 2 else None
            if sharded:
                hat = kops.int8_delta_reduce_sharded(
                    stack(pl["q"]), w1, qr, wr, mesh=mesh, client_axes=axes,
                    reduce_tiers=self._tiers())
            else:
                hat = kops.int8_delta_reduce(stack(pl["q"]), w1, qr, wr)
            out.append(hat)
        return jax.tree.unflatten(treedef, out)

    def decode_apply(self, payload, ref):
        from repro.kernels import ops as kops
        mesh, axes = self._mesh_axes()
        leaves, treedef = jax.tree.flatten(ref)
        out = []
        for pl, leaf in zip(payload, leaves):
            flat = leaf.reshape(-1)
            qr = pl["qr"] if self.levels == 2 else None
            rs = pl["rs"] if self.levels == 2 else None
            if (mesh is not None and axes
                    and flat.shape[0] % _axes_size(mesh, axes) == 0):
                rec = kops.int8_delta_apply_sharded(flat, pl["q"], pl["s"],
                                                    qr, rs, mesh=mesh,
                                                    axes=axes)
            else:
                rec = kops.int8_delta_apply(flat, pl["q"], pl["s"], qr, rs)
            out.append(rec.reshape(leaf.shape))
        return jax.tree.unflatten(treedef, out)

    def encoded_bits(self, params):
        bits = 0
        for leaf in jax.tree.leaves(params):
            bits += self.levels * (8 * int(leaf.size) + 32)   # planes + scales
        return bits

    def nominal_ratio(self, bits_per_param: int = 32) -> float:
        return bits_per_param / (8.0 * self.levels)


class TopKTransport(Transport):
    """Magnitude top-k of the flattened per-leaf delta (f32 value + int32
    index per kept coordinate) with server-side error feedback — the
    residual carries everything the sparsifier dropped into later rounds."""

    name = "topk"

    def __init__(self, frac: float = 0.1, error_feedback: bool = True):
        if not 0.0 < frac <= 1.0:
            raise ValueError(f"topk frac must be in (0, 1]: {frac}")
        self.frac = float(frac)
        self.error_feedback = error_feedback

    def signature(self):
        return (self.name, self.frac, self.error_feedback, self.ef_slots)

    def _k(self, size: int) -> int:
        # clamped to [1, size]: ceil can round below 1 on tiny leaves
        # (k == 0 would silently drop the leaf from the wire) and the index
        # payload is invalid past the leaf itself (lax.top_k rejects
        # k > size). Empty leaves ship an empty payload (k == 0).
        return min(size, max(1, int(math.ceil(self.frac * size))))

    def encode(self, delta):
        out = []
        for leaf in jax.tree.leaves(delta):
            flat = leaf.astype(jnp.float32).reshape(-1)
            _, idx = jax.lax.top_k(jnp.abs(flat), self._k(flat.shape[0]))
            out.append({"v": jnp.take(flat, idx), "i": idx.astype(jnp.int32)})
        return out

    def decode(self, payload, like):
        leaves, treedef = jax.tree.flatten(like)
        dec = []
        for pl, leaf in zip(payload, leaves):
            flat = jnp.zeros((int(leaf.size),), jnp.float32)
            dec.append(flat.at[pl["i"]].set(pl["v"]).reshape(leaf.shape))
        return jax.tree.unflatten(treedef, dec)

    def reduce(self, payloads, weights, like):
        from repro.kernels import ops as kops
        mesh, axes = self._mesh_axes()
        n = weights.shape[0]
        leaves, treedef = jax.tree.flatten(like)
        out = []
        for pl, leaf in zip(payloads, leaves):
            size = int(leaf.size)
            # sharded only where the Mosaic formulation itself applies:
            # the per-shard partial IS the one-hot kernel (ops gates it
            # off for large payloads in interpret mode)
            if (mesh is not None and axes
                    and n % _axes_size(mesh, axes) == 0
                    and kops.mosaic_scatter_ok(int(pl["v"].size), size)):
                flat = kops.topk_delta_reduce_sharded(
                    pl["v"], pl["i"], weights, size, mesh=mesh,
                    client_axes=axes, reduce_tiers=self._tiers())
            else:
                flat = kops.topk_delta_reduce(pl["v"], pl["i"], weights,
                                              size)
            out.append(flat.reshape(leaf.shape))
        return jax.tree.unflatten(treedef, out)

    def decode_apply(self, payload, ref):
        from repro.kernels import ops as kops
        leaves, treedef = jax.tree.flatten(ref)
        out = [kops.topk_delta_apply(leaf.reshape(-1), pl["v"], pl["i"]
                                     ).reshape(leaf.shape)
               for pl, leaf in zip(payload, leaves)]
        return jax.tree.unflatten(treedef, out)

    def encoded_bits(self, params):
        bits = 0
        for leaf in jax.tree.leaves(params):
            bits += 64 * self._k(int(leaf.size))         # f32 value + i32 idx
        return bits

    def nominal_ratio(self, bits_per_param: int = 32) -> float:
        return bits_per_param / (64.0 * self.frac)


REF_STORES = ("f32", "q8")


def _q8_encode(x) -> dict:
    """Params-shaped f32-equivalent leaf -> two-level int8 store leaf
    (DESIGN.md §10): Q-KV primary + residual planes over the flattened
    leaf, per-leaf f32 scales. ~2 bytes/param held instead of 4, worst-case
    value error ~max|x|/127^2 (~6e-5 relative) — the key names are
    prefixed so a store leaf can never be confused with a params subtree.
    """
    q, s, qr, rs = quantize_kv_residual(x.astype(jnp.float32).reshape(-1))
    return {"q8_q": q, "q8_s": s, "q8_qr": qr, "q8_rs": rs}


def _q8_decode(d: dict, like) -> jnp.ndarray:
    x = (d["q8_q"].astype(jnp.float32) * d["q8_s"]
         + d["q8_qr"].astype(jnp.float32) * d["q8_rs"])
    return x.reshape(like.shape).astype(like.dtype)


def _is_q8(x) -> bool:
    return isinstance(x, dict) and "q8_q" in x


class DownlinkCodec:
    """Server->client broadcast compression (DESIGN.md §8.6).

    Wraps one of the delta codecs above around the broadcast leg. State
    machine (all server-side, engine-owned):

      * ``params_ref`` — the last broadcast reconstruction, i.e. exactly
        the model every client currently holds (round 0: the init params,
        which clients received at enrolment).
      * ``residual``   — the downlink error-feedback buffer (codecs with
        ``error_feedback``; int8's untransmitted second level, top-k's
        dropped coordinates).

    Per round: ``payload = enc(params_t - params_ref + residual)``; every
    client reconstructs ``recon = params_ref + dec(payload)`` (the fused
    decode-apply kernels) and runs local SGD from ``recon``; the new
    reference IS ``recon`` and ``residual' = (delta + residual) -
    dec(payload)``. Because all clients reconstruct identically, the
    uplink aggregation contract is unchanged — the round core simply runs
    on ``recon`` instead of ``params_t`` (robust aggregators included).

    ``encode_broadcast`` is the split-phase entry point the fused round
    cores use (DESIGN.md §10): it returns the wire payload next to the f32
    reference view so clients can reconstruct *lazily* inside their own
    first forward (``decode_into``) instead of the engine materialising the
    recon tree up front; ``broadcast`` composes the two for callers that
    want the eager tree (tests, sequential cores).

    ``ref_store="q8"`` keeps ``params_ref``/``residual`` as two-level-int8
    store leaves (``_q8_encode``) instead of f32-equivalent trees — half
    the server-side bytes held; the reference is dequantised on demand and
    the next reference re-quantises the reconstruction. The quantisation
    error lives *inside* the ref/recon pair coherently (clients and server
    see the same dequantised view), so the EF algebra is unchanged.

    On EF codecs the server pays one extra decode per round to form the
    residual (dec is recomputed next to the fused apply — same f32 ops, so
    the residual is exact w.r.t. the shipped payload); clients only ever
    run the fused apply. Decode is O(|x|) elementwise, dwarfed by the K
    local-SGD steps.
    """

    def __init__(self, codec: Transport, ref_store: str = "f32"):
        if codec is None or getattr(codec, "name", "none") == "none":
            raise ValueError("DownlinkCodec wraps a real codec; use "
                             "downlink='none' for the uncompressed "
                             "broadcast")
        if ref_store not in REF_STORES:
            raise ValueError(f"downlink ref_store must be one of "
                             f"{REF_STORES}: {ref_store!r}")
        self.codec = codec
        self.name = codec.name
        self.error_feedback = bool(codec.error_feedback)
        self.ref_store = ref_store

    # -- identity / compile-cache -------------------------------------
    def signature(self) -> Tuple:
        sig = ("downlink",) + tuple(self.codec.signature())
        if self.ref_store != "f32":
            sig = sig + ("ref:" + self.ref_store,)
        return sig

    # -- mesh binding ---------------------------------------------------
    def with_mesh(self, mesh, client_axes, reduce_tiers=None):
        t = copy.copy(self)
        # the server-side eager decode (encode_broadcast) routes through
        # the mesh-sharded decode-apply kernel; the client-side lazy decode
        # (decode_into) runs inside the vmapped client trace where a
        # shard_map cannot nest — it keeps the unbound elementwise kernel
        # (bitwise-identical output) and GSPMD places it
        t._unbound = self.codec
        t.codec = self.codec.with_mesh(mesh, client_axes, reduce_tiers)
        return t

    # -- quantised ref store -------------------------------------------
    def store_tree(self, tree: PyTree) -> PyTree:
        """Params-shaped f32-equivalent tree -> stored representation."""
        if self.ref_store == "f32":
            return tree
        return jax.tree.map(_q8_encode, tree)

    def load_tree(self, stored: PyTree, like: PyTree) -> PyTree:
        """Stored representation -> params-shaped tree (dequantise on
        demand; ``like`` supplies shapes/dtypes)."""
        if self.ref_store == "f32":
            return stored
        return jax.tree.map(_q8_decode, stored, like,
                            is_leaf=lambda x: _is_q8(x))

    def state_bytes(self, state) -> int:
        """Server-side bytes held by ref + residual (bench accounting)."""
        return sum(int(l.size) * l.dtype.itemsize
                   for l in jax.tree.leaves(state))

    # -- state ----------------------------------------------------------
    def init_state(self, params: PyTree):
        ref = self.store_tree(jax.tree.map(
            lambda p: jnp.asarray(p), params))
        res = (self.store_tree(jax.tree.map(
            lambda p: jnp.zeros(tuple(p.shape), jnp.float32), params))
            if self.error_feedback else ())
        return {"ref": ref, "res": res}

    # -- the round entry points ------------------------------------------
    def encode_broadcast(self, params: PyTree, state):
        """(server params, state) -> (ref, payload, recon, new state).

        ``ref`` is the f32-equivalent reference view (dequantised for q8
        stores) and ``payload`` the encoded delta — together the lazy
        client-side reconstruction input (``decode_into``); ``recon`` is
        the same reconstruction computed eagerly for the server side
        (aggregate target + next reference). Under jit the eager and lazy
        decodes are identical elementwise programs, so XLA CSEs them when
        both land in one round core."""
        ref = self.load_tree(state["ref"], like=params)
        res = (self.load_tree(state["res"], like=params)
               if self.error_feedback else ())
        delta = jax.tree.map(
            lambda p, r: p.astype(jnp.float32) - r.astype(jnp.float32),
            params, ref)
        if self.error_feedback:
            delta = jax.tree.map(jnp.add, delta, res)
        payload = self.codec.encode(delta)
        recon = self.codec.decode_apply(payload, ref)
        if self.error_feedback:
            dec = self.codec.decode(payload, like=params)
            res = self.store_tree(jax.tree.map(jnp.subtract, delta, dec))
        return ref, payload, recon, {"ref": self.store_tree(recon),
                                     "res": res}

    def decode_into(self, payload, ref: PyTree) -> PyTree:
        """Client-side lazy reconstruction: ``ref + dec(payload)`` through
        the fused decode-apply kernels, run inside ClientUpdate's own
        trace (DESIGN.md §10) instead of on an engine-materialised tree."""
        return getattr(self, "_unbound", self.codec).decode_apply(payload,
                                                                  ref)

    def broadcast(self, params: PyTree, state):
        """(server params, state) -> (client reconstruction, new state)."""
        _, _, recon, new_state = self.encode_broadcast(params, state)
        return recon, new_state

    # -- wire accounting -------------------------------------------------
    def encoded_bits(self, params: PyTree) -> int:
        return self.codec.encoded_bits(params)

    def compression_ratio(self, params: PyTree,
                          bits_per_param: int = 32) -> float:
        return self.codec.compression_ratio(params, bits_per_param)

    def nominal_ratio(self, bits_per_param: int = 32) -> float:
        return self.codec.nominal_ratio(bits_per_param)


class AdaptiveDownlinkCodec(DownlinkCodec):
    """Per-round adaptive broadcast codec (DESIGN.md §10).

    Wraps the two-level int8 quantiser with a traced per-round policy on
    the EF-corrected delta:

      * level 0 — *skip*: ``|delta|`` is near zero relative to ``|ref|``
        (plateaued schedule, converged model): ship nothing; the whole
        delta folds into the EF residual and clients keep training on the
        previous reconstruction.
      * level 2 — *boost*: the EF residual norm spikes relative to the
        delta norm (compression error piling up faster than the model
        moves): ship both int8 planes (``int8x2``) to drain the residual.
      * level 1 — the default single-plane ``int8`` broadcast.

    The decision is data-dependent but shape-static: all planes are always
    computed, levels select via ``jnp.where`` masks so one compiled
    program covers every round. The chosen level rides out of the round
    core as an int32 scalar per round; ``FedAvgTrainer`` charges
    ``RuntimeModel`` per-level (level 0 pays zero broadcast bits).
    Error feedback is structural here — a skipped round's delta *must*
    survive in the residual — so the codec always runs with EF on.
    """

    def __init__(self, *, skip_rtol: float = 1e-3, boost_rtol: float = 0.5,
                 ref_store: str = "f32"):
        super().__init__(Int8Transport(levels=2, error_feedback=True),
                         ref_store=ref_store)
        self.name = "adaptive"
        self.skip_rtol = float(skip_rtol)
        self.boost_rtol = float(boost_rtol)

    def signature(self) -> Tuple:
        sig = ("downlink", "adaptive", self.skip_rtol, self.boost_rtol)
        if self.ref_store != "f32":
            sig = sig + ("ref:" + self.ref_store,)
        return sig

    @staticmethod
    def _norm(tree) -> jnp.ndarray:
        leaves = [jnp.sum(jnp.square(l.astype(jnp.float32)))
                  for l in jax.tree.leaves(tree)]
        return jnp.sqrt(sum(leaves)) if leaves else jnp.zeros(())

    def _level(self, delta, ref, res) -> jnp.ndarray:
        nd, nref, nres = self._norm(delta), self._norm(ref), self._norm(res)
        ship = nd > self.skip_rtol * (nref + 1e-12)
        boost = nres > self.boost_rtol * (nd + 1e-12)
        return jnp.where(ship, jnp.where(boost, 2, 1), 0).astype(jnp.int32)

    def encode_broadcast(self, params: PyTree, state):
        """Returns ``(ref, payload, recon, new_state, level)`` — one more
        element than the base codec: the traced per-round level."""
        ref = self.load_tree(state["ref"], like=params)
        res32 = self.load_tree(state["res"], like=params)
        delta = jax.tree.map(
            lambda p, r: p.astype(jnp.float32) - r.astype(jnp.float32),
            params, ref)
        # policy inputs: the raw round delta vs the accumulated residual
        level = self._level(delta, ref, res32)
        delta = jax.tree.map(jnp.add, delta, res32)
        payload = self.codec.encode(delta)   # both planes, always computed
        payload = [dict(pl, lvl=level) for pl in payload]
        recon = self.decode_into(payload, ref)
        dec = self._decode(payload, like=params)
        res = self.store_tree(jax.tree.map(jnp.subtract, delta, dec))
        return ref, payload, recon, {"ref": self.store_tree(recon),
                                     "res": res}, level

    def _decode(self, payload, like: PyTree) -> PyTree:
        """Level-masked dequantise: level 0 decodes to zero (nothing on
        the wire), level 1 the primary plane, level 2 both planes."""
        leaves, treedef = jax.tree.flatten(like)
        dec = []
        for pl, leaf in zip(payload, leaves):
            lvl = pl["lvl"]
            x = jnp.where(lvl >= 1,
                          pl["q"].astype(jnp.float32) * pl["s"], 0.0)
            x = x + jnp.where(lvl >= 2,
                              pl["qr"].astype(jnp.float32) * pl["rs"], 0.0)
            dec.append(x.reshape(leaf.shape))
        return jax.tree.unflatten(treedef, dec)

    def decode_into(self, payload, ref: PyTree) -> PyTree:
        dec = self._decode(payload, like=ref)
        return jax.tree.map(
            lambda r, d: (r.astype(jnp.float32) + d).astype(r.dtype),
            ref, dec)

    def broadcast(self, params: PyTree, state):
        _, _, recon, new_state, _ = self.encode_broadcast(params, state)
        return recon, new_state

    # -- wire accounting: nominal = the default level-1 broadcast ---------
    def _level_bits(self, params: PyTree, level: int) -> int:
        if level <= 0:
            return 0
        bits = 0
        for leaf in jax.tree.leaves(params):
            bits += level * (8 * int(leaf.size) + 32)    # planes + scales
        return bits

    def encoded_bits(self, params: PyTree) -> int:
        return self._level_bits(params, 1)

    def compression_ratio(self, params: PyTree,
                          bits_per_param: int = 32) -> float:
        full = bits_per_param * sum(int(l.size)
                                    for l in jax.tree.leaves(params))
        return full / float(self.encoded_bits(params))

    def level_ratios(self, params: PyTree,
                     bits_per_param: int = 32) -> dict:
        """{level: compression ratio} for RuntimeModel's per-level wire
        charging (level 0 ships nothing and is charged as such)."""
        full = bits_per_param * sum(int(l.size)
                                    for l in jax.tree.leaves(params))
        return {lvl: full / float(self._level_bits(params, lvl))
                for lvl in (1, 2)}

    def nominal_ratio(self, bits_per_param: int = 32) -> float:
        return bits_per_param / 8.0


def get_downlink(name, *, topk_frac: float = 0.1,
                 ref_store: str = "f32") -> Optional[DownlinkCodec]:
    """Resolve the broadcast codec through the same transport registry
    (any registered codec doubles as a downlink codec; downlink-only
    codecs like ``adaptive`` resolve here exclusively). ``None``/``"none"``
    -> None: the engine keeps the historical uncompressed broadcast (and
    its compiled program) bit-for-bit."""
    if name is None or isinstance(name, DownlinkCodec):
        return name
    codec = (name if isinstance(name, Transport)
             else TRANSPORT_REGISTRY.get(name)(topk_frac=topk_frac,
                                               ref_store=ref_store))
    if codec is None:                              # registry "none"
        return None
    if isinstance(codec, DownlinkCodec):           # e.g. "adaptive"
        return codec
    return DownlinkCodec(codec, ref_store=ref_store)


def get_transport(name, *, topk_frac: float = 0.1) -> Optional[Transport]:
    """Resolve a codec through the plugin registry. ``None``/``"none"`` ->
    None: the engine keeps its historical (bit-identical) param-space path.
    A ``Transport`` instance passes through. Unknown names get did-you-mean
    errors from the registry; downlink-only codecs are rejected."""
    if name is None:
        return None
    if isinstance(name, DownlinkCodec):
        raise ValueError(f"{name.name!r} is a downlink-only codec; it is "
                         f"valid for transport.downlink, not "
                         f"transport.name")
    if isinstance(name, Transport):
        return name
    codec = TRANSPORT_REGISTRY.get(name)(topk_frac=topk_frac)
    if isinstance(codec, DownlinkCodec):
        raise ValueError(f"{name!r} is a downlink-only codec; it is valid "
                         f"for transport.downlink, not transport.name")
    return codec


# builtin registrations — factory signature: f(*, topk_frac, **kw)
register_transport("none", lambda **kw: None)
register_transport("int8",
                   lambda **kw: Int8Transport(levels=1, error_feedback=True))
register_transport("int8x2",
                   lambda **kw: Int8Transport(levels=2, error_feedback=False))
register_transport(
    "topk",
    lambda *, topk_frac=0.1, **kw: TopKTransport(frac=topk_frac,
                                                 error_feedback=True))
register_transport(
    "adaptive",
    lambda *, ref_store="f32", **kw: AdaptiveDownlinkCodec(
        ref_store=ref_store))
