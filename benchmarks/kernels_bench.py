"""Kernel micro-benchmarks: interpret-mode Pallas vs jnp oracle wall-time.

On CPU the interpret path is NOT indicative of TPU speed — the number that
matters offline is the allclose delta (correctness) and the kernel/oracle
timing *ratio* (a machine-robust reference point across commits; absolute
microseconds shift with the runner). Lowered-TPU timing lands when hardware
is available.

``run_records()`` is the machine-readable entry point the CI perf gate
consumes (``benchmarks.perf_gate``): one dict per kernel with ``kernel_us``,
``oracle_us`` and ``max_abs_delta`` against the jnp oracle. ``run()`` keeps
the historical printed-row contract on top of it.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import delta_codec, ops, ref


def _time(f, *args, iters=3) -> float:
    f(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def _delta(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


def _topk_payload(key, n: int, k: int, m: int):
    """A stacked top-k payload with duplicate indices across clients (the
    scatter's accumulate path is exercised, not just the gather)."""
    kv, ki, kw = jax.random.split(key, 3)
    vals = jax.random.normal(kv, (n, k))
    idx = jax.random.randint(ki, (n, k), 0, m).astype(jnp.int32)
    weights = jax.nn.softmax(jax.random.normal(kw, (n,)))
    return vals, idx, weights


def _dense_scatter_oracle(vals, idx, weights, size):
    """The one-hot-matmul formulation in plain jnp (DESIGN.md §10.1) — the
    dense oracle both scatter implementations must match."""
    contrib = (vals.astype(jnp.float32)
               * weights.astype(jnp.float32)[:, None]).reshape(-1)
    oh = (idx.reshape(-1)[:, None] == jnp.arange(size)[None, :])
    return contrib @ oh.astype(jnp.float32)


def run_records() -> List[dict]:
    recs = []
    ks = jax.random.split(jax.random.PRNGKey(0), 4)

    x = jax.random.normal(ks[0], (16, 1 << 16))
    w = jax.nn.softmax(jax.random.normal(ks[1], (16,)))
    oracle = jax.jit(ref.fedavg_reduce_ref)
    recs.append({"name": "kern_fedavg_reduce",
                 "kernel_us": _time(ops.fedavg_reduce, x, w),
                 "oracle_us": _time(oracle, x, w),
                 "max_abs_delta": _delta(ops.fedavg_reduce(x, w),
                                         oracle(x, w))})

    # fused int8 decompress-reduce (transport, DESIGN.md §8): oracle is
    # decode-to-f32 then the weighted einsum — the (N, M) f32 materialise
    # the fused kernel avoids
    qi = jnp.clip(jnp.round(x * 40.0), -127, 127).astype(jnp.int8)
    qr = jnp.clip(jnp.round((x - qi * 0.025) * 5080.0), -127, 127
                  ).astype(jnp.int8)
    w1, w2 = w * 0.025, w * (0.025 / 127.0)
    oracle = jax.jit(lambda q, qr, w1, w2: jnp.einsum(
        "c,cm->m", w1, q.astype(jnp.float32))
        + jnp.einsum("c,cm->m", w2, qr.astype(jnp.float32)))
    recs.append({"name": "kern_int8_delta_reduce",
                 "kernel_us": _time(ops.int8_delta_reduce, qi, w1, qr, w2),
                 "oracle_us": _time(oracle, qi, qr, w1, w2),
                 "max_abs_delta": _delta(
                     ops.int8_delta_reduce(qi, w1, qr, w2),
                     oracle(qi, qr, w1, w2))})

    # top-k scatter-reduce/apply (DESIGN.md §10.1): XLA segment-scatter vs
    # the Mosaic one-hot-matmul kernel, both against the dense-matmul
    # oracle — duplicate indices included so accumulation is covered
    n, k, m = 8, 128, 4096
    vals, idx, weights = _topk_payload(ks[2], n, k, m)
    dense = jax.jit(_dense_scatter_oracle, static_argnums=3)
    want = dense(vals, idx, weights, m)
    us_dense = _time(lambda v, i, w: dense(v, i, w, m), vals, idx, weights)
    xla = jax.jit(lambda v, i, w: delta_codec.topk_scatter_reduce(
        v, i, w, m))
    mosaic = jax.jit(lambda v, i, w: delta_codec.topk_scatter_reduce_mosaic(
        v, i, w, m, interpret=ops.interpret_mode()))
    recs.append({"name": "kern_topk_scatter_reduce_xla",
                 "kernel_us": _time(xla, vals, idx, weights),
                 "oracle_us": us_dense,
                 "max_abs_delta": _delta(xla(vals, idx, weights), want)})
    recs.append({"name": "kern_topk_scatter_reduce_mosaic",
                 "kernel_us": _time(mosaic, vals, idx, weights),
                 "oracle_us": us_dense,
                 "max_abs_delta": _delta(mosaic(vals, idx, weights), want)})

    refv = jax.random.normal(ks[3], (m,))
    v1, i1 = vals[0], idx[0]
    apply_want = refv.at[i1].add(v1)     # XLA scatter-add == dense apply
    xla_a = jax.jit(delta_codec.topk_scatter_apply)
    mosaic_a = jax.jit(lambda r, v, i: delta_codec.topk_scatter_apply_mosaic(
        r, v, i, interpret=ops.interpret_mode()))
    us_oracle = _time(lambda r, v, i: r.at[i].add(v), refv, v1, i1)
    recs.append({"name": "kern_topk_scatter_apply_xla",
                 "kernel_us": _time(xla_a, refv, v1, i1),
                 "oracle_us": us_oracle,
                 "max_abs_delta": _delta(xla_a(refv, v1, i1), apply_want)})
    recs.append({"name": "kern_topk_scatter_apply_mosaic",
                 "kernel_us": _time(mosaic_a, refv, v1, i1),
                 "oracle_us": us_oracle,
                 "max_abs_delta": _delta(mosaic_a(refv, v1, i1),
                                         apply_want)})

    q = jax.random.normal(ks[0], (1, 512, 8, 64)) * 0.3
    kk = jax.random.normal(ks[1], (1, 512, 2, 64)) * 0.3
    v = jax.random.normal(ks[2], (1, 512, 2, 64))
    recs.append({"name": "kern_flash_attention",
                 "kernel_us": _time(lambda q: ops.flash_attention(q, kk, v),
                                    q),
                 "oracle_us": None, "max_abs_delta": None})

    xs = jax.random.normal(ks[0], (2, 512, 4, 64))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 512, 4)))
    A = -jnp.exp(jax.random.normal(ks[2], (4,)) * 0.3)
    b = jax.random.normal(ks[3], (2, 512, 32)) * 0.5
    recs.append({"name": "kern_ssd_scan",
                 "kernel_us": _time(
                     lambda x: ops.ssd_scan(x, dt, A, b, b, jnp.ones(4))[0],
                     xs),
                 "oracle_us": None, "max_abs_delta": None})

    xe = jax.random.normal(ks[0], (8, 256, 512)) * 0.1
    we = jax.random.normal(ks[1], (8, 512, 1024)) * 0.05
    oracle = jax.jit(ref.gmm_ref)
    recs.append({"name": "kern_moe_gmm",
                 "kernel_us": _time(ops.gmm, xe, we),
                 "oracle_us": _time(oracle, xe, we),
                 "max_abs_delta": _delta(ops.gmm(xe, we), oracle(xe, we))})
    return recs


def rows_from_records(recs: List[dict]) -> List[Tuple[str, float, str]]:
    rows = []
    for r in recs:
        if r["oracle_us"] is None:
            derived = "interpret"
        else:
            derived = (f"oracle_us={r['oracle_us']:.0f};"
                       f"delta={r['max_abs_delta']:.2e}")
        rows.append((r["name"], r["kernel_us"], derived))
    return rows


def run(verbose=True, records: List[dict] = None
        ) -> List[Tuple[str, float, str]]:
    rows = rows_from_records(records if records is not None
                             else run_records())
    if verbose:
        for n, us, d in rows:
            print(f"  {n:32s} {us:12.0f}us  {d}")
    return rows
