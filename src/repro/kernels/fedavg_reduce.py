"""Pallas TPU kernel: FedAvg server aggregation  x_bar = sum_c p_c * x_c.

The paper's server op (Algorithm 1, line 11) is a memory-bound weighted
reduction over the client axis. One HBM read of the client stack, one HBM
write of the average, the weighting and the f32 sum fused per VMEM block,
and no intermediate (N, M) f32 tensor.

Block layout (``reduce_tiling`` chooses it from the stack's shape alone):
  x:   (N, *shape) -> viewed as (N, R, L), blocks (N, BR, L),
                      grid = (cdiv(R, BR),)
  w:   (N,)        -> whole, in SMEM; one scalar per client
  out: shape       -> computed as (R, L), blocks (BR, L)
The lane width L is the leaf's own last dimension when that is a multiple
of 128 and at most ``MAX_LANES``, so the (R, L) view is the leaf collapsed
over its leading dimensions and both the stack and the result keep the
leaf's tiled layout (no relayout copy on either side). Otherwise L is the
largest of 1024/512/256/128 that divides M, and only a size that none
divides is padded (by under 128 elements). BR is the largest multiple of
the input dtype's sublane tile whose block, its f32 temporaries and their
double buffers fit ``VMEM_BUDGET``, so it shrinks as N grows; a leaf of
fewer rows than that is one block. The last block may be partial: the sum
runs over clients, so its out-of-range rows only reach output rows the
pipeline masks away. Each block sums its clients in client order in f32:
at N = 1 the result is ``w * x.astype(f32)`` exactly.

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
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

#: the widest lane dimension a block takes; wider leaves fold into 1024 lanes
MAX_LANES = 4096
#: VMEM for one grid step's blocks, their double buffers and the f32
#: temporaries: half of v5e's 16 MiB default scoped VMEM
VMEM_BUDGET = 8 * 1024 * 1024


def _sublanes(dtype) -> int:
    """Rows of one native (sublanes x 128) tile: 8 for 32-bit dtypes, 16
    for 16-bit, 32 for 8-bit."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def reduce_tiling(n: int, m: int, dtype, last_dim: int):
    """(lanes, block_rows, pad) for reducing ``n`` client rows of ``m``
    values of ``dtype`` each, ``last_dim`` the leaf's last dimension.

    The (m + pad,) values per client are viewed as (rows, lanes) and
    reduced ``block_rows`` rows per grid step; ``block_rows == rows`` when
    the whole view fits one block. The block size depends on the shape
    alone: ``n`` only sets how many rows fit in ``VMEM_BUDGET``.
    """
    if last_dim % 128 == 0 and 0 < last_dim <= MAX_LANES:
        lanes, pad = last_dim, 0
    else:
        lanes = next((l for l in (1024, 512, 256, 128) if m % l == 0), 128)
        pad = (-m) % lanes
    rows = (m + pad) // lanes
    tile = _sublanes(dtype)
    # double-buffered input and f32 output blocks, a cast and an accumulator
    row_bytes = lanes * (2 * n * jnp.dtype(dtype).itemsize + 2 * 4 + 2 * 4)
    block_rows = max(tile, VMEM_BUDGET // row_bytes // tile * tile)
    return lanes, min(block_rows, rows), pad


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


def _client_spec(axes, stack):
    """PartitionSpec of a client stack sharded on its leading axis only."""
    return P(axes, *([None] * (stack.ndim - 1)))


def _kernel(*refs):
    # per plane: w (N,) in SMEM and x (N, BR, L); then o (BR, L). Each
    # plane sums its clients in order, then the planes add up
    planes = (len(refs) - 1) // 2
    total = None
    for w_ref, x_ref in zip(refs[:planes], refs[planes:-1]):
        acc = w_ref[0] * x_ref[0].astype(jnp.float32)
        for c in range(1, x_ref.shape[0]):
            acc = acc + w_ref[c] * x_ref[c].astype(jnp.float32)
        total = acc if total is None else total + acc
    refs[-1][...] = total.astype(refs[-1].dtype)


def _block_reduce(stacks, weights, interpret: bool,
                  out_dtype=None) -> jnp.ndarray:
    """sum over planes p and clients c of weights[p][c] * stacks[p][c]:
    (N, *shape) stacks of one shape and dtype, (N,) weights -> shape, one
    pallas_call (shared by the single-device entry points and the
    per-shard bodies of the mesh variants). One plane is the dense or
    1-level int8 reduce; two are the int8 primary and residual planes."""
    x0 = stacks[0]
    n, shape = x0.shape[0], x0.shape[1:]
    out_dtype = out_dtype or x0.dtype
    m = math.prod(shape)
    # every plane's block counts against the VMEM budget
    lanes, block_rows, pad = reduce_tiling(len(stacks) * n, m, x0.dtype,
                                           shape[-1] if shape else 1)
    if pad:                       # a flat view, padded to whole lanes
        stacks = [jnp.pad(x.reshape(n, -1), ((0, 0), (0, pad)))
                  for x in stacks]
    views = [x.reshape(n, -1, lanes) for x in stacks]
    rows = views[0].shape[1]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    block = pl.BlockSpec((n, block_rows, lanes), lambda i: (0, i, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[smem] * len(stacks) + [block] * len(stacks),
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), out_dtype),
        interpret=interpret,
    )(*[w.astype(jnp.float32) for w in weights], *views)
    if pad:
        out = out.reshape(-1)[:m]
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fedavg_reduce(client_stack: jnp.ndarray, weights: jnp.ndarray, *,
                  interpret: bool = False) -> jnp.ndarray:
    """client_stack: (N, *shape); weights: (N,) -> shape, e.g. (N, M) ->
    (M,)."""
    return _block_reduce((client_stack,), (weights,), interpret)


def fedavg_reduce_sharded(client_stack: jnp.ndarray, weights: jnp.ndarray, *,
                          mesh, client_axes, interpret: bool = False,
                          reduce_tiers=None) -> jnp.ndarray:
    """Mesh variant: client_stack (N, *shape) with N sharded over
    ``client_axes``.

    Each shard block-reduces its N/shards local clients into an f32 shape
    partial, then one all-reduce over the client axes sums the partials;
    the result is replicated (every shard holds the new global params, which
    is exactly what the next round's broadcast wants). N must divide the
    product of the client axes' sizes. ``reduce_tiers`` turns the flat psum
    into the hierarchical grouped reduce (``psum_tiers``, DESIGN.md §11).
    """
    axes = tuple(client_axes)

    def local(x, w):              # x (N/shards, *shape); w (N/shards,)
        partial = _block_reduce((x,), (w,), interpret,
                                out_dtype=jnp.float32)
        return psum_tiers(partial, axes, reduce_tiers)

    # check_vma=False: shard_map has no varying-axes rule for pallas_call;
    # the psum makes the out_spec P() replication explicit ourselves
    out = jax.shard_map(local, mesh=mesh,
                        in_specs=(_client_spec(axes, client_stack), P(axes)),
                        out_specs=P(), check_vma=False)(client_stack, weights)
    return out.astype(client_stack.dtype)
