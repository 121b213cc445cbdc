"""Pallas TPU kernels: fused decompress-reduce for compressed client deltas.

The transport layer (DESIGN.md §8) ships client deltas as quantized
payloads; the server aggregation is then  hat = sum_c w_c * dec(payload_c).
Decoding each client to f32 before reducing would materialise the full
(N, M) f32 stack again — exactly the buffer compression was meant to kill.
These kernels fuse dequantisation into the weighted block-reduce of
``fedavg_reduce``: the int8 payload is the only HBM-resident client stack,
the f32 decode happens per VMEM block, and one f32 output is written. They
share its block layout (``fedavg_reduce.reduce_tiling``): a stack of
client payloads in the leaf's shape, (N, *shape), is viewed as lane-dense
(N, R, L) rows, L the leaf's last dimension where it is a multiple of 128,
and reduced in (N, BR, L) blocks of 32-row int8 tiles, BR set by N and a
VMEM budget, on a ``cdiv`` grid whose last block may be partial. The
result comes back in the leaf's shape with no relayout.

Per-leaf int8 payloads carry a scalar scale per level, so the per-client
dequantise-and-weight factor folds into the weight column:
    sum_c w_c * (q_c * s_c [+ qr_c * rs_c]) = sum_c (w_c s_c) q_c [+ ...]
— i.e. the single-level reduce IS ``fedavg_reduce``'s block-reduce on int8
input with effective weights, and the two-level reduce is one fused kernel
over both int8 planes (one pass, one output write).

``int8_decompress_reduce_sharded`` extends ``fedavg_reduce_sharded``'s mesh
contract: the int8 client stack arrives sharded over the mesh client axes,
each shard decompress-reduces its local clients into an f32 (M,) partial,
and a single ``psum`` sums the partials — the collective moves one f32
model-size buffer per shard while the wire/HBM payload stays int8.

Top-k payloads reduce by scatter-add (``topk_scatter_reduce``): one flat
(N*S,) scatter into an f32 (M,) zero buffer — never an (N, M) dense stack.
The XLA scatter is kept as the oracle; ``topk_scatter_reduce_mosaic`` /
``topk_scatter_apply_mosaic`` are the Mosaic formulation (DESIGN.md §10):
a TPU has no fast random scatter, but the scatter-add is exactly

    out[m] = sum_t contrib[t] * [idx[t] == m]

— a (1, BS) x (BS, BM) matmul against a one-hot matrix built in-register
from an iota compare, accumulated over payload blocks with the output tile
resident in VMEM. Duplicate indices accumulate through the matmul
contraction (scatter-add semantics for free); padded payload slots carry
``idx == -1``, which matches no column. The work is dense T x M, which the
MXU streams far faster than a serialised scatter; ``kernels.ops`` picks the
formulation per call site (XLA scatter stays the oracle and the
interpret-mode fallback for large payloads, where dense T x M work is real
scalar FLOPs). ``topk_scatter_reduce_sharded`` follows
``fedavg_reduce_sharded``'s contract: payloads sharded over the mesh client
axes, per-shard one-hot partials, one psum.

The *downlink* leg (DESIGN.md §8.6) is the mirror image: the server ships
one encoded delta and every client applies it to the broadcast reference.
``int8_decode_apply`` fuses dequantise + add-to-ref in one pass — the int8
payload is read once, the reconstruction ``ref + q*s [+ qr*rs]`` is written
once, and no intermediate f32 delta buffer exists.
``int8_decode_apply_sharded`` follows ``fedavg_reduce_sharded``'s per-shard
kernel contract: the flat parameter vector is sharded over the mesh axes
and each shard decode-applies its local slice; being elementwise (no
contraction over clients), the psum degenerates away and the output keeps
the input sharding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from repro.kernels.fedavg_reduce import (_block_reduce, _client_spec,
                                         psum_tiers)

#: downlink decode-apply block: (1, DEFAULT_BLOCK) rows of the flat vector
DEFAULT_BLOCK = 4096


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_decompress_reduce(q, w_eff, qr=None, wr_eff=None, *,
                           interpret: bool = False) -> jnp.ndarray:
    """q (N, *shape) int8; w_eff (N,) = weights * per-client scales -> shape
    f32, e.g. (N, M) -> (M,). Pass the payloads in the leaf's shape: its
    last dimension then sets the lanes and the result needs no relayout.

    With the optional residual plane ``qr``/``wr_eff`` the two dequantise-
    weight-reduce passes fuse into one kernel invocation per block.
    """
    planes, weights = ((q,), (w_eff,)) if qr is None else ((q, qr),
                                                           (w_eff, wr_eff))
    return _block_reduce(planes, weights, interpret, out_dtype=jnp.float32)


def int8_decompress_reduce_sharded(q, w_eff, qr=None, wr_eff=None, *, mesh,
                                   client_axes, interpret: bool = False,
                                   reduce_tiers=None) -> jnp.ndarray:
    """Mesh variant (extends ``fedavg_reduce_sharded``): the int8 stack is
    sharded over ``client_axes``; per-shard fused decompress-reduce + one
    all-reduce of the f32 partials (``psum_tiers``: flat or the
    hierarchical grouped reduce). N must divide the axes' size."""
    axes = tuple(client_axes)
    planes = (q,) if qr is None else (q, qr)
    weights = (w_eff,) if qr is None else (w_eff, wr_eff)

    def local(planes, weights):
        partial = _block_reduce(planes, weights, interpret,
                                out_dtype=jnp.float32)
        return psum_tiers(partial, axes, reduce_tiers)

    # check_vma=False: pallas_call has no varying-axes rule; the psum makes
    # the P() out_spec replication explicit (as fedavg_reduce)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(tuple(_client_spec(axes, x) for x in planes),
                  tuple(P(axes) for _ in weights)),
        out_specs=P(), check_vma=False)(planes, weights)


# ---------------------------------------------------------------------------
# downlink: fused decode-apply (DESIGN.md §8.6)
# ---------------------------------------------------------------------------

def _apply_kernel1(s_ref, ref_ref, q_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)             # (1, BM) int8 plane
    o_ref[...] = (ref_ref[...].astype(jnp.float32)
                  + q * s_ref[...]).astype(o_ref.dtype)


def _apply_kernel2(s_ref, rs_ref, ref_ref, q_ref, qr_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    qr = qr_ref[...].astype(jnp.float32)
    o_ref[...] = (ref_ref[...].astype(jnp.float32)
                  + q * s_ref[...] + qr * rs_ref[...]).astype(o_ref.dtype)


def _block_apply(ref, q, s, qr, rs, block, interpret):
    """(M,) ref + int8 payload -> (M,) reconstruction, one fused pass."""
    m = ref.shape[0]
    pad = (-m) % block
    if pad:
        ref = jnp.pad(ref, (0, pad))
        q = jnp.pad(q, (0, pad))
        if qr is not None:
            qr = jnp.pad(qr, (0, pad))
    mp = m + pad
    scol = s.reshape(1, 1).astype(jnp.float32)
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    row_spec = pl.BlockSpec((1, block), lambda i: (0, i))
    if qr is None:
        out = pl.pallas_call(
            _apply_kernel1,
            grid=(mp // block,),
            in_specs=[scalar_spec, row_spec, row_spec],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct((1, mp), ref.dtype),
            interpret=interpret,
        )(scol, ref[None, :], q[None, :])
    else:
        out = pl.pallas_call(
            _apply_kernel2,
            grid=(mp // block,),
            in_specs=[scalar_spec, scalar_spec, row_spec, row_spec, row_spec],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct((1, mp), ref.dtype),
            interpret=interpret,
        )(scol, rs.reshape(1, 1).astype(jnp.float32),
          ref[None, :], q[None, :], qr[None, :])
    return out[0, :m]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def int8_decode_apply(ref, q, s, qr=None, rs=None, *,
                      block: int = DEFAULT_BLOCK,
                      interpret: bool = False) -> jnp.ndarray:
    """ref (M,); q (M,) int8; s scalar scale -> (M,) ``ref + q*s [+ qr*rs]``.

    The downlink reconstruction every client runs: dequantise + add-to-ref
    fused, so the f32 delta is never materialised in HBM. Accumulates in
    f32 and casts back to ``ref.dtype``.
    """
    return _block_apply(ref, q, s, qr, rs, block, interpret)


def int8_decode_apply_sharded(ref, q, s, qr=None, rs=None, *, mesh, axes,
                              block: int = DEFAULT_BLOCK,
                              interpret: bool = False) -> jnp.ndarray:
    """Mesh variant: the flat (M,) vector sharded over ``axes``; each shard
    runs the fused decode-apply on its local slice (scales replicated).
    Elementwise, so unlike the reduce kernels no psum is needed — the
    output keeps the per-shard layout and GSPMD reshards as consumed.
    The axes' size must divide M."""
    axes = tuple(axes)

    if qr is None:
        def local(r, x, sc):
            return _block_apply(r, x, sc, None, None, block, interpret)

        return jax.shard_map(local, mesh=mesh,
                             in_specs=(P(axes), P(axes), P(None)),
                             out_specs=P(axes), check_vma=False)(ref, q, s)

    def local(r, x, sc, xr, rsc):
        return _block_apply(r, x, sc, xr, rsc, block, interpret)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(axes), P(axes), P(None), P(axes),
                                   P(None)),
                         out_specs=P(axes), check_vma=False)(ref, q, s, qr, rs)


def topk_scatter_apply(ref, vals, idx) -> jnp.ndarray:
    """ref (M,); vals/idx (S,) -> ref with the kept coordinates added.

    One flat scatter-add into a copy of the reference — the dense decoded
    delta never exists (same XLA-scatter rationale as the uplink reduce).
    The XLA-scatter oracle for ``topk_scatter_apply_mosaic``."""
    shape = ref.shape
    flat = ref.astype(jnp.float32).reshape(-1)
    out = flat.at[idx].add(vals.astype(jnp.float32))
    return out.reshape(shape).astype(ref.dtype)


def topk_scatter_reduce(vals, idx, weights, size: int) -> jnp.ndarray:
    """vals/idx (N, S), weights (N,) -> (M,) f32 scatter-add reduction.

    One flat (N*S,) scatter into a zeroed (M,) buffer — the decoded dense
    per-client deltas are never materialised. The XLA-scatter oracle for
    ``topk_scatter_reduce_mosaic`` (and the large-payload interpret-mode
    fallback — see ``kernels.ops``).
    """
    contrib = vals.astype(jnp.float32) * weights.astype(jnp.float32)[:, None]
    out = jnp.zeros((size,), jnp.float32)
    return out.at[idx.reshape(-1)].add(contrib.reshape(-1))


# ---------------------------------------------------------------------------
# top-k scatter: Mosaic one-hot-matmul formulation (DESIGN.md §10)
# ---------------------------------------------------------------------------

#: MXU-aligned defaults: BM output columns stay VMEM-resident across the
#: payload-block loop; BS payload entries per one-hot matmul step.
TOPK_BLOCK_M = 512
TOPK_BLOCK_S = 256


def _one_hot_block(idx, block_m, base):
    """(BS,) int32 indices -> (BS, BM) f32 one-hot columns for the output
    tile starting at ``base``. Built from a 2D iota compare (TPU-legal);
    padded slots (idx == -1) match no column."""
    cols = base + jax.lax.broadcasted_iota(jnp.int32,
                                           (idx.shape[0], block_m), 1)
    return (idx[:, None] == cols).astype(jnp.float32)


def _scatter_kernel(idx_ref, c_ref, o_ref, *, block_m):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    oh = _one_hot_block(idx_ref[0, :], block_m, pl.program_id(0) * block_m)
    o_ref[...] += jnp.dot(c_ref[...].astype(jnp.float32), oh,
                          preferred_element_type=jnp.float32)


def _scatter_apply_kernel(ref_ref, idx_ref, c_ref, o_ref, *, block_m):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = ref_ref[...].astype(jnp.float32)

    oh = _one_hot_block(idx_ref[0, :], block_m, pl.program_id(0) * block_m)
    o_ref[...] += jnp.dot(c_ref[...].astype(jnp.float32), oh,
                          preferred_element_type=jnp.float32)


def _pad_flat(x, mult, value=0):
    pad = (-x.shape[0]) % mult
    return jnp.pad(x, (0, pad), constant_values=value) if pad else x


@functools.partial(jax.jit,
                   static_argnames=("size", "block_m", "block_s", "interpret"))
def topk_scatter_reduce_mosaic(vals, idx, weights, size: int, *,
                               block_m: int = TOPK_BLOCK_M,
                               block_s: int = TOPK_BLOCK_S,
                               interpret: bool = False) -> jnp.ndarray:
    """One-hot-matmul ``topk_scatter_reduce``: vals/idx (N, S), weights (N,)
    -> (M,) f32. The per-client weight folds into the payload values before
    flattening, so the kernel reduces one flat (T,) contribution stream;
    grid (M/BM, T/BS) with the output tile innermost-resident."""
    contrib = (vals.astype(jnp.float32)
               * weights.astype(jnp.float32)[:, None]).reshape(-1)
    if size == 0 or contrib.shape[0] == 0:      # empty leaf / k == 0 payload
        return jnp.zeros((size,), jnp.float32)
    c = _pad_flat(contrib, block_s)
    ix = _pad_flat(idx.reshape(-1).astype(jnp.int32), block_s, value=-1)
    mp = size + ((-size) % block_m)
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, block_m=block_m),
        grid=(mp // block_m, c.shape[0] // block_s),
        in_specs=[
            pl.BlockSpec((1, block_s), lambda i, j: (0, j)),   # idx block
            pl.BlockSpec((1, block_s), lambda i, j: (0, j)),   # contrib block
        ],
        out_specs=pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, mp), jnp.float32),
        interpret=interpret,
    )(ix[None, :], c[None, :])
    return out[0, :size]


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_s", "interpret"))
def topk_scatter_apply_mosaic(ref, vals, idx, *,
                              block_m: int = TOPK_BLOCK_M,
                              block_s: int = TOPK_BLOCK_S,
                              interpret: bool = False) -> jnp.ndarray:
    """One-hot-matmul ``topk_scatter_apply``: the output tile initialises
    from the reference block instead of zeros, so dequantise + add-to-ref
    stay one fused pass (downlink reconstruction, DESIGN.md §8.6)."""
    shape, dtype = ref.shape, ref.dtype
    flat = ref.astype(jnp.float32).reshape(-1)
    m = flat.shape[0]
    if m == 0 or vals.shape[0] == 0:            # empty leaf / empty payload
        return ref
    r = _pad_flat(flat, block_m)
    c = _pad_flat(vals.astype(jnp.float32).reshape(-1), block_s)
    ix = _pad_flat(idx.reshape(-1).astype(jnp.int32), block_s, value=-1)
    mp = r.shape[0]
    out = pl.pallas_call(
        functools.partial(_scatter_apply_kernel, block_m=block_m),
        grid=(mp // block_m, c.shape[0] // block_s),
        in_specs=[
            pl.BlockSpec((1, block_m), lambda i, j: (0, i)),   # ref tile
            pl.BlockSpec((1, block_s), lambda i, j: (0, j)),   # idx block
            pl.BlockSpec((1, block_s), lambda i, j: (0, j)),   # vals block
        ],
        out_specs=pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, mp), jnp.float32),
        interpret=interpret,
    )(r[None, :], ix[None, :], c[None, :])
    return out[0, :m].reshape(shape).astype(dtype)


def topk_scatter_reduce_sharded(vals, idx, weights, size: int, *, mesh,
                                client_axes,
                                block_m: int = TOPK_BLOCK_M,
                                block_s: int = TOPK_BLOCK_S,
                                interpret: bool = False,
                                reduce_tiers=None) -> jnp.ndarray:
    """Mesh variant (the ``fedavg_reduce_sharded`` contract): payload rows
    sharded over ``client_axes``, each shard one-hot-reduces its local
    clients into an f32 (M,) partial, ``psum_tiers`` sums the partials
    (flat or hierarchically grouped). N must divide the axes' size."""
    axes = tuple(client_axes)

    def local(v, ix, w):
        partial = topk_scatter_reduce_mosaic(
            v, ix, w, size, block_m=block_m, block_s=block_s,
            interpret=interpret)
        # check_vma=False: pallas_call has no varying-axes rule; the psum
        # makes the P() out_spec replication explicit (as fedavg_reduce)
        return psum_tiers(partial, axes, reduce_tiers)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(axes, None), P(axes, None), P(axes)),
                         out_specs=P(), check_vma=False)(vals, idx, weights)
