"""Pallas TPU kernel: FedAvg server aggregation  x_bar = sum_c p_c * x_c.

The paper's server op (Algorithm 1, line 11) is a memory-bound weighted
reduction over the client axis. On TPU we tile the (flattened) parameter
vector into VMEM-resident (N x BM) blocks, broadcast the (N,) weight vector
from a VMEM column, and fuse multiply + reduce + cast in one pass — one HBM
read of the client stack, one HBM write of the average, no intermediate
(N, M) f32 tensor.

Block layout:
  x:   (N, M)  -> blocks (N, BM), grid = (M // BM,)
  w:   (N, 1)  -> whole, broadcast within block
  out: (1, M)  -> blocks (1, BM)

``fedavg_reduce_sharded`` is the mesh variant (DESIGN.md §7): the client
stack arrives sharded over the mesh client axes, each shard runs the same
block-reduce over its local clients (partial weighted sums in f32), and a
single ``psum`` all-reduces the (M,)-sized partials — the collective moves
one model-size buffer per shard instead of the N-client stack.

``reduce_tiers`` (DESIGN.md §11) splits that single psum into a
*hierarchical* two-tier reduce: e.g. ``(("data",), ("pod",))`` first sums
within each pod's ``data`` sub-axis (the edge aggregation, a grouped
all-reduce local to the pod's interconnect) and then sums the per-pod
partials across pods. The math is identical — psum over disjoint axis
groups composes to the flat psum — but the collective decomposes into
pod-local + cross-pod phases, which is the shape a real edge-aggregation
topology wants. ``psum_tiers`` is the shared helper every sharded reduce
kernel (fedavg / int8 / top-k, ``kernels.delta_codec``) routes through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

DEFAULT_BLOCK = 4096


def psum_tiers(x, axes, reduce_tiers=None):
    """All-reduce ``x`` over ``axes`` — flat (one psum) or hierarchically.

    ``reduce_tiers``: None for the flat single-psum reduce, or a sequence of
    disjoint axis groups whose concatenation covers ``axes`` exactly, e.g.
    ``(("data",), ("pod",))`` for edge-then-cross-pod. Each tier is one
    grouped all-reduce; the composition equals the flat psum bitwise on a
    homogeneous mesh (f32 adds re-associate across tiers — the documented
    ≤1e-6 parity regime on real multi-device meshes)."""
    if reduce_tiers is None:
        return jax.lax.psum(x, tuple(axes))
    tiers = tuple(tuple(t) for t in reduce_tiers)
    flat = tuple(a for t in tiers for a in t)
    if sorted(flat) != sorted(tuple(axes)):
        raise ValueError(f"reduce_tiers {tiers} do not partition client "
                         f"axes {tuple(axes)}")
    for tier in tiers:
        x = jax.lax.psum(x, tier)
    return x


def _kernel(w_ref, x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (N, BM)
    w = w_ref[...].astype(jnp.float32)          # (N, 1)
    o_ref[...] = jnp.sum(x * w, axis=0, keepdims=True).astype(o_ref.dtype)


def _block_reduce(client_stack: jnp.ndarray, weights: jnp.ndarray,
                  block: int, interpret: bool,
                  out_dtype=None) -> jnp.ndarray:
    """The (N, M) x (N,) -> (M,) pallas_call, unjitted (shared by the
    single-device entry point and the per-shard body of the mesh variant)."""
    n, m = client_stack.shape
    pad = (-m) % block
    if pad:
        client_stack = jnp.pad(client_stack, ((0, 0), (0, pad)))
    mp = m + pad
    out = pl.pallas_call(
        _kernel,
        grid=(mp // block,),
        in_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),      # weights column
            pl.BlockSpec((n, block), lambda i: (0, i)),  # client block
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, mp),
                                       out_dtype or client_stack.dtype),
        interpret=interpret,
    )(weights[:, None], client_stack)
    return out[0, :m]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fedavg_reduce(client_stack: jnp.ndarray, weights: jnp.ndarray, *,
                  block: int = DEFAULT_BLOCK,
                  interpret: bool = False) -> jnp.ndarray:
    """client_stack: (N, M); weights: (N,) -> (M,)."""
    return _block_reduce(client_stack, weights, block, interpret)


def fedavg_reduce_sharded(client_stack: jnp.ndarray, weights: jnp.ndarray, *,
                          mesh, client_axes, block: int = DEFAULT_BLOCK,
                          interpret: bool = False,
                          reduce_tiers=None) -> jnp.ndarray:
    """Mesh variant: client_stack (N, M) with N sharded over ``client_axes``.

    Each shard block-reduces its N/shards local clients into an f32 (M,)
    partial, then one all-reduce over the client axes sums the partials;
    the result is replicated (every shard holds the new global params, which
    is exactly what the next round's broadcast wants). N must divide the
    product of the client axes' sizes. ``reduce_tiers`` turns the flat psum
    into the hierarchical grouped reduce (``psum_tiers``, DESIGN.md §11).
    """
    axes = tuple(client_axes)

    def local(x, w):                      # x (N/shards, M); w (N/shards,)
        partial = _block_reduce(x, w, block, interpret,
                                out_dtype=jnp.float32)
        return psum_tiers(partial, axes, reduce_tiers)

    # check_vma=False: shard_map has no varying-axes rule for pallas_call;
    # the psum makes the out_spec P() replication explicit ourselves
    out = jax.shard_map(local, mesh=mesh,
                        in_specs=(P(axes, None), P(axes)),
                        out_specs=P(), check_vma=False)(client_stack, weights)
    return out.astype(client_stack.dtype)
