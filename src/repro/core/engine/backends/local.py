"""LocalBackend — single-device execution (the PR-1 engine's geometry).

The round's N clients run as a plain ``jax.vmap`` over the client axis;
aggregation is the configured Aggregator verbatim; placement is a plain
transfer. This is the degenerate point of the backend protocol: everything
``MeshBackend`` does collapses to this on a 1x1 mesh, which is exactly what
the parity tests assert (tests/test_backends.py).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core.engine.aggregators import Aggregator, get_aggregator
from repro.core.engine.backends.base import ExecutionBackend, LossFn
from repro.core.engine.client import make_client_update


def encode_broadcast(downlink, params, d_state):
    """Uniform downlink-core entry point: every downlink round core emits
    ``(ref, payload, recon, new_state, level)`` — codecs without a
    per-round level (everything but ``adaptive``) get the sentinel -1,
    which the trainer reads as "charge the configured ratio"."""
    out = downlink.encode_broadcast(params, d_state)
    if len(out) == 5:
        return out
    ref, payload, recon, new_state = out
    return ref, payload, recon, new_state, jnp.int32(-1)


def make_parallel_round_core(loss_fn: LossFn, aggregator: Aggregator,
                             server, server_lr: float, *,
                             client_spmd_axes: Optional[Sequence[str]] = None,
                             transport=None, downlink=None, constrain=None):
    """The vmap-over-clients round core shared by Local and Mesh-parallel.

    ``client_spmd_axes``: mesh axes the vmapped client dim is sharded over
    (``spmd_axis_name``); None on a single device.

    round_core(params, batches{(N,K,b,...)}, weights(N,), eta, server_state)
    -> (new_params, first_losses (N,), last_losses (N,), server_state).

    With ``transport`` (DESIGN.md §8) the clients' stacked params go through
    the codec's delta pipeline (encode -> fused decompress-reduce) instead
    of the aggregator, and the core threads the transport state:
    round_core(..., server_state, t_state) -> (..., server_state, t_state).

    With ``downlink`` (DESIGN.md §10) the broadcast is *fused into the
    client forward*: the core's extra carry slot is the downlink state (or
    an ``(uplink, downlink)`` pair), the server encodes once, and each
    vmapped client reconstructs ``ref + dec(payload)`` lazily inside its
    own first step (``client.reconstruct``) — the decoded f32 tree is
    never a separate engine-materialised round input. The server-side
    reconstruction (aggregate target, next reference) is the identical
    elementwise program, so XLA CSEs the two decodes under jit. Downlink
    cores additionally return the per-round adaptive level scalar.
    ``constrain`` pins the server-side reconstruction to the backend's
    param sharding (mesh); None on a single device.
    """
    if downlink is None:
        client = make_client_update(loss_fn)

        if transport is None:
            def round_core(params, batches, weights, eta, server_state):
                client_params, first_losses, last_losses = jax.vmap(
                    client, in_axes=(None, 0, None),
                    spmd_axis_name=client_spmd_axes)(params, batches, eta)
                with obs.scope("uplink.reduce"):
                    aggregate = aggregator(client_params, weights)
                with obs.scope("server.step"):
                    new_params, server_state = server.step(
                        params, aggregate, server_state, server_lr)
                return new_params, first_losses, last_losses, server_state

            return round_core

        def round_core(params, batches, weights, eta, server_state, t_state):
            client_params, first_losses, last_losses = jax.vmap(
                client, in_axes=(None, 0, None),
                spmd_axis_name=client_spmd_axes)(params, batches, eta)
            aggregate, t_state = transport.aggregate(
                aggregator, params, client_params, weights, t_state)
            with obs.scope("server.step"):
                new_params, server_state = server.step(
                    params, aggregate, server_state, server_lr)
            return (new_params, first_losses, last_losses, server_state,
                    t_state)

        return round_core

    # fused downlink path: the vmapped "params" argument is the broadcast
    # bundle (ref, payload), unbatched (in_axes=None) so the decode traces
    # once and is shared across clients
    fused = make_client_update(
        loss_fn, reconstruct=lambda b: downlink.decode_into(b[1], b[0]))

    def d_core(params, batches, weights, eta, server_state, extra):
        t_state, d_state = (extra if transport is not None
                            else (None, extra))
        with obs.scope("server.step"):   # the server encodes its broadcast
            ref, payload, recon, d_state, level = encode_broadcast(
                downlink, params, d_state)
        if constrain is not None:
            recon = constrain(recon)
        client_params, first_losses, last_losses = jax.vmap(
            fused, in_axes=(None, 0, None),
            spmd_axis_name=client_spmd_axes)((ref, payload), batches, eta)
        if transport is None:
            with obs.scope("uplink.reduce"):
                aggregate = aggregator(client_params, weights)
            with obs.scope("server.step"):
                new_params, server_state = server.step(
                    recon, aggregate, server_state, server_lr)
            return (new_params, first_losses, last_losses, server_state,
                    d_state, level)
        aggregate, t_state = transport.aggregate(
            aggregator, recon, client_params, weights, t_state)
        with obs.scope("server.step"):
            new_params, server_state = server.step(recon, aggregate,
                                                   server_state, server_lr)
        return (new_params, first_losses, last_losses, server_state,
                (t_state, d_state), level)

    return d_core


def make_parallel_slab_cores(loss_fn: LossFn, aggregator: Aggregator,
                             server, server_lr: float, *,
                             client_spmd_axes: Optional[Sequence[str]] = None,
                             transport=None):
    """Streaming-cohort cores (DESIGN.md §11) shared by Local and
    Mesh-parallel: a round's U clients arrive as ceil(U/C) slabs of C; each
    slab folds into f32 running sums and only the finalize step touches the
    server optimizer.

    slab_core(params, batches{(C,K,b,...)}, weights(C,), eta, acc, ef)
        -> (acc, first_losses (C,), last_losses (C,), ef_out)
    finalize_core(params, acc, server_state)
        -> (new_params, server_state, new_residual)

    ``acc`` is ``(hat_acc, true_acc)``: params-shaped f32 partial sums
    (``true_acc`` is ``()`` except for aggregate-EF codecs). ``weights``
    are the slab's slice of the GLOBAL round weights (sum 1 over U, not
    over C) so partial sums compose additively and the C == U slab
    reproduces the dense round bit-for-bit. ``ef`` is the slab's
    per-client residual slice (slotted EF), the round-frozen aggregate
    residual (read back unchanged; finalize emits the new one), or ``()``.
    """
    if transport is not None and transport.name == "none":
        transport = None  # IdentityTransport == plain aggregator path
    agg_ef = (transport is not None and transport.error_feedback
              and not transport.ef_slots)
    client = make_client_update(loss_fn)

    def slab_core(params, batches, weights, eta, acc, ef):
        client_params, first_losses, last_losses = jax.vmap(
            client, in_axes=(None, 0, None),
            spmd_axis_name=client_spmd_axes)(params, batches, eta)
        hat_acc, true_acc = acc
        if transport is None:
            with obs.scope("uplink.reduce"):
                part = aggregator(client_params, weights)
                hat_acc = jax.tree.map(
                    lambda a, p: a + p.astype(jnp.float32), hat_acc, part)
            return (hat_acc, true_acc), first_losses, last_losses, ef
        hat, true, ef = transport.aggregate_slab(
            params, client_params, weights, ef)
        with obs.scope("uplink.reduce"):
            hat_acc = jax.tree.map(jnp.add, hat_acc, hat)
            if agg_ef:
                true_acc = jax.tree.map(jnp.add, true_acc, true)
        return (hat_acc, true_acc), first_losses, last_losses, ef

    def finalize_core(params, acc, server_state):
        with obs.scope("server.step"):
            hat_acc, true_acc = acc
            if transport is None:
                # hat_acc holds sum_slabs aggregator(...) in f32; the cast
                # is the dense path's own einsum->dtype cast, deferred to
                # round end
                aggregate = jax.tree.map(lambda a, p: a.astype(p.dtype),
                                         hat_acc, params)
                new_params, server_state = server.step(
                    params, aggregate, server_state, server_lr)
                return new_params, server_state, ()
            aggregate = jax.tree.map(
                lambda p, h: (p.astype(jnp.float32) + h).astype(p.dtype),
                params, hat_acc)
            new_params, server_state = server.step(params, aggregate,
                                                   server_state, server_lr)
            new_res = (jax.tree.map(jnp.subtract, true_acc, hat_acc)
                       if agg_ef else ())
            return new_params, server_state, new_res

    return slab_core, finalize_core


class LocalBackend(ExecutionBackend):
    name = "local"

    def make_round_core(self, loss_fn: LossFn, *, aggregator: str = "mean",
                        trim_fraction: float = 0.1, server=None,
                        server_lr: float = 1.0, transport=None,
                        downlink=None):
        agg = get_aggregator(aggregator, trim_fraction=trim_fraction)
        return make_parallel_round_core(loss_fn, agg, server, server_lr,
                                        transport=transport,
                                        downlink=downlink)

    def make_slab_cores(self, loss_fn: LossFn, *, aggregator: str = "mean",
                        server=None, server_lr: float = 1.0, transport=None):
        agg = get_aggregator(aggregator)
        return make_parallel_slab_cores(loss_fn, agg, server, server_lr,
                                        transport=transport)

    def fleet_slices(self, n: int):
        """Fresh single-device backends, one per packed point: placement is
        stateless, so concurrent points interleave on the device dispatch
        queue (round-robin by arrival) while each keeps its own prefetch
        threads — the local fall-back of mesh sub-slicing (DESIGN.md §12)."""
        return [LocalBackend() for _ in range(n)]
