"""Public jit'd wrappers around the Pallas kernels.

Off TPU every kernel runs with interpret=True — the kernel body executes as
jax ops, which is how correctness is validated on the CPU. On TPU the same
pallas_call lowers to Mosaic. ``interpret_mode()`` decides per call, never
at import, so importing the package initialises no JAX backend (on a TPU
host, initialising one takes the chip).

Layout adapters live here: the model layers use (B, S, H, hd) attention
tensors while the kernel wants (B, H, S, hd); SSD per-head arrangement and
padding to MXU-aligned shapes also happen here.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels import delta_codec as _dc
from repro.kernels import fedavg_reduce as _fr
from repro.kernels import flash_attention as _fa
from repro.kernels import moe_gmm as _gmm
from repro.kernels import ssd_scan as _ssd

PyTree = Any


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted: everywhere but on a TPU."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# fedavg aggregation
# ---------------------------------------------------------------------------

def fedavg_reduce(client_stack: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """(N, *shape) x (N,) -> shape, e.g. (N, M) -> (M,)."""
    return _fr.fedavg_reduce(client_stack, weights, interpret=interpret_mode())


def fedavg_reduce_tree(client_params: PyTree, weights: jnp.ndarray) -> PyTree:
    """Weighted-average every leaf of a client-stacked param pytree.

    Leaves have a leading client axis: (N, ...) -> (...). Each leaf goes
    to the kernel in its own shape, so its last dimension sets the lanes.
    """
    return jax.tree.map(lambda leaf: fedavg_reduce(leaf, weights),
                        client_params)


def fedavg_reduce_sharded(client_stack: jnp.ndarray, weights: jnp.ndarray, *,
                          mesh, client_axes,
                          reduce_tiers=None) -> jnp.ndarray:
    """(N, *shape) x (N,) -> shape, N sharded over the mesh client axes: local
    Pallas block-reduce per shard + all-reduce of the f32 partials
    (``reduce_tiers`` selects the hierarchical grouped reduce, §11)."""
    return _fr.fedavg_reduce_sharded(client_stack, weights, mesh=mesh,
                                     client_axes=client_axes,
                                     interpret=interpret_mode(),
                                     reduce_tiers=reduce_tiers)


def fedavg_reduce_tree_sharded(client_params: PyTree, weights: jnp.ndarray,
                               *, mesh, client_axes,
                               reduce_tiers=None) -> PyTree:
    """Sharded weighted average of a client-stacked pytree (MeshBackend's
    ``aggregator="kernel"`` path — see DESIGN.md §7)."""
    return jax.tree.map(
        lambda leaf: fedavg_reduce_sharded(leaf, weights, mesh=mesh,
                                           client_axes=client_axes,
                                           reduce_tiers=reduce_tiers),
        client_params)


# ---------------------------------------------------------------------------
# compressed-delta transport (DESIGN.md §8)
# ---------------------------------------------------------------------------

def int8_delta_reduce(q, w_eff, qr=None, wr_eff=None) -> jnp.ndarray:
    """Fused dequantise + weighted reduce of an int8 client-delta stack:
    q (N, *shape) int8, w_eff (N,) = weights * per-client scales -> shape
    f32. Give q in the leaf's shape: its last dimension sets the kernel's
    lanes. Optional residual plane (two-level codec) fuses into the same
    pass."""
    return _dc.int8_decompress_reduce(q, w_eff, qr, wr_eff,
                                      interpret=interpret_mode())


def int8_delta_reduce_sharded(q, w_eff, qr=None, wr_eff=None, *, mesh,
                              client_axes, reduce_tiers=None) -> jnp.ndarray:
    """Mesh variant: int8 stack sharded over the client axes, per-shard
    fused decompress-reduce + all-reduce of f32 partials (the
    ``fedavg_reduce_sharded`` contract on compressed payloads)."""
    return _dc.int8_decompress_reduce_sharded(q, w_eff, qr, wr_eff,
                                              mesh=mesh,
                                              client_axes=client_axes,
                                              interpret=interpret_mode(),
                                              reduce_tiers=reduce_tiers)


#: Interpret-mode ceiling for the Mosaic one-hot scatter: its dense T x M
#: formulation is what makes the MXU fast on TPU, but in interpret mode
#: (CPU) those are real scalar FLOPs — large payloads fall back to the XLA
#: scatter oracle there. On TPU the Mosaic path is always taken.
MOSAIC_SCATTER_MAX_INTERPRET_WORK = 1 << 20


def mosaic_scatter_ok(payload_entries: int, size: int) -> bool:
    """Whether the one-hot Mosaic formulation is the right scatter for a
    ``payload_entries x size`` dense work volume on this backend."""
    return ((not interpret_mode())
            or payload_entries * size <= MOSAIC_SCATTER_MAX_INTERPRET_WORK)


def topk_delta_reduce(vals, idx, weights, size: int) -> jnp.ndarray:
    """Weighted scatter-add reduction of top-k payloads -> (M,) f32:
    Mosaic one-hot matmul (DESIGN.md §10), XLA scatter as the
    large-payload interpret fallback/oracle."""
    if mosaic_scatter_ok(int(vals.shape[0]) * int(vals.shape[1]), size):
        return _dc.topk_scatter_reduce_mosaic(vals, idx, weights, size,
                                              interpret=interpret_mode())
    return _dc.topk_scatter_reduce(vals, idx, weights, size)


def topk_delta_reduce_sharded(vals, idx, weights, size: int, *, mesh,
                              client_axes, reduce_tiers=None) -> jnp.ndarray:
    """Mesh variant: payload rows sharded over the client axes, per-shard
    one-hot partials + all-reduce (the ``fedavg_reduce_sharded`` contract
    on sparse payloads)."""
    return _dc.topk_scatter_reduce_sharded(vals, idx, weights, size,
                                           mesh=mesh,
                                           client_axes=client_axes,
                                           interpret=interpret_mode(),
                                           reduce_tiers=reduce_tiers)


def int8_delta_apply(ref, q, s, qr=None, rs=None) -> jnp.ndarray:
    """Downlink reconstruction: fused dequantise + add-to-ref
    (``ref + q*s [+ qr*rs]``), ref (M,) -> (M,) in ``ref.dtype``."""
    return _dc.int8_decode_apply(ref, q, s, qr, rs, interpret=interpret_mode())


def int8_delta_apply_sharded(ref, q, s, qr=None, rs=None, *, mesh,
                             axes) -> jnp.ndarray:
    """Mesh variant: flat vector sharded over ``axes``, per-shard fused
    decode-apply (elementwise — no collective; DESIGN.md §8.6)."""
    return _dc.int8_decode_apply_sharded(ref, q, s, qr, rs, mesh=mesh,
                                         axes=axes, interpret=interpret_mode())


def topk_delta_apply(ref, vals, idx) -> jnp.ndarray:
    """Downlink top-k reconstruction: scatter-add the kept coordinates into
    a copy of the broadcast reference — Mosaic one-hot matmul with the
    output tile initialised from the reference block; XLA scatter as the
    large-payload interpret fallback/oracle."""
    if mosaic_scatter_ok(int(vals.shape[0]), int(ref.size)):
        return _dc.topk_scatter_apply_mosaic(ref, vals, idx,
                                             interpret=interpret_mode())
    return _dc.topk_scatter_apply(ref, vals, idx)


# ---------------------------------------------------------------------------
# flash attention (model layout adapter)
# ---------------------------------------------------------------------------

def _pad_axis(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None):
    """Model layout: q (B, Sq, H, hd); k/v (B, Sk, KV, hd) -> (B, Sq, H, hd).

    The attention layer calls this when ``use_kernel=True``. Gradients flow
    through a recompute-based VJP: forward uses the kernel; backward
    differentiates the jnp oracle (flash backward kernels are a recorded
    future optimisation — see DESIGN.md).
    """
    B, Sq, H, hd = q.shape
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    out = _flash_vjp(qt, kt, vt, causal, window, softcap)
    return jnp.moveaxis(out, 2, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_vjp(q, k, v, causal, window, softcap):
    return _flash_fwd_impl(q, k, v, causal, window, softcap)


def _flash_fwd_impl(q, k, v, causal, window, softcap):
    B, H, Sq, hd = q.shape
    # pad head_dim and sequence dims to MXU-aligned multiples
    qp, pd = _pad_axis(q, 3, 128)
    kp, _ = _pad_axis(k, 3, 128)
    vp, _ = _pad_axis(v, 3, 128)
    qp, pq = _pad_axis(qp, 2, 128)
    kp, pk = _pad_axis(kp, 2, 128)
    vp, _ = _pad_axis(vp, 2, 128)
    # padded key positions must not contribute: causal masking handles query
    # padding; key padding is excluded via an effective window or the causal
    # mask only when Sq == Sk; otherwise mask by shifting scores — we simply
    # require no key padding for non-causal use.
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              softcap=softcap, interpret=interpret_mode(),
                              scale=1.0 / (hd ** 0.5))
    if pk and not causal:
        raise ValueError("non-causal flash path requires Sk % 128 == 0")
    return out[:, :, :Sq, :hd]


def _flash_fwd(q, k, v, causal, window, softcap):
    return _flash_fwd_impl(q, k, v, causal, window, softcap), (q, k, v)


def _flash_bwd(causal, window, softcap, res, g):
    q, k, v = res
    from repro.kernels import ref

    def f(q, k, v):
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       softcap=softcap)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


_flash_vjp.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# SSD scan (model layout adapter)
# ---------------------------------------------------------------------------

def ssd_scan(x, dt, a_log, b, c, d, *, chunk: int = 256):
    """Model layout (matches repro.models.ssm.ssd_chunked):
    x (B, S, H, P); dt (B, S, H); a_log=A (H,) negative rates;
    b/c (B, S, N); d (H,). Returns (y (B,S,H,P), state (B,H,N,P)).
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    NC = Sp // chunk
    # rearrange to per-(batch, head)
    xr = jnp.moveaxis(x, 2, 1).reshape(B * H, NC, chunk, P)
    dtr = jnp.moveaxis(dt, 2, 1).reshape(B * H, NC, chunk, 1)
    ar = dtr * jnp.tile(a_log, B)[:, None, None, None]
    br = jnp.broadcast_to(b[:, None], (B, H, Sp, N)).reshape(B * H, NC, chunk, N)
    cr = jnp.broadcast_to(c[:, None], (B, H, Sp, N)).reshape(B * H, NC, chunk, N)
    y, fs = _ssd.ssd_scan(xr.astype(jnp.float32), dtr.astype(jnp.float32),
                          ar.astype(jnp.float32), br.astype(jnp.float32),
                          cr.astype(jnp.float32), interpret=interpret_mode())
    y = jnp.moveaxis(y.reshape(B, H, Sp, P), 1, 2)[:, :S]
    y = y + x[:, :S] * d[None, None, :, None]
    return y.astype(x.dtype), fs.reshape(B, H, N, P)


# ---------------------------------------------------------------------------
# MoE grouped matmul
# ---------------------------------------------------------------------------

def gmm(x, w):
    """(E, C, d) @ (E, d, f) -> (E, C, f), padding C to the 128 tile."""
    E, C, d = x.shape
    xp, pc = _pad_axis(x, 1, 128)
    out = _gmm.gmm(xp, w, interpret=interpret_mode())
    return out[:, :C] if pc else out


def moe_gmm(x, gate, up, down, *, mlp_type: str = "swiglu"):
    """Full gated expert FFN on dispatched tokens: x (E, C, d) -> (E, C, d)."""
    if mlp_type == "swiglu":
        h = jax.nn.silu(gmm(x, gate).astype(jnp.float32))
        h = (h * gmm(x, up).astype(jnp.float32)).astype(x.dtype)
    else:
        h = jax.nn.gelu(gmm(x, up).astype(jnp.float32),
                        approximate=True).astype(x.dtype)
    return gmm(h, down)
