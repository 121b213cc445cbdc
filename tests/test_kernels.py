"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True)."""
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import delta_codec as dc
from repro.kernels import fedavg_reduce as fr
from repro.kernels import flash_attention as fa
from repro.kernels import moe_gmm as mg
from repro.kernels import ops, ref, ssd_scan as ss

TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


# ---------------------------------------------------------------------------
# fedavg_reduce
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m", [(4, 512), (16, 4096), (7, 1000), (50, 8193)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_reduce_sweep(n, m, dtype):
    rng = jax.random.PRNGKey(n * m)
    x = jax.random.normal(rng, (n, m), dtype)
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(1), (n,)))
    out = fr.fedavg_reduce(x, w, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.fedavg_reduce_ref(x, w), np.float32),
                               **TOL[dtype])


def test_fedavg_reduce_convex_combination():
    x = jnp.stack([jnp.zeros(300), jnp.ones(300)])
    w = jnp.array([0.25, 0.75])
    np.testing.assert_allclose(np.asarray(fr.fedavg_reduce(x, w, interpret=True)),
                               0.75, rtol=1e-6)


#: per-client leaf shapes of the lane-dense reduce: a last dimension that
#: is a multiple of 128 (the leaf's own lanes), a flat size whose only
#: such divisor is 128, sizes that need padding, a row count that is not a
#: multiple of the block rows, a single row
REDUCE_SHAPES = [(40, 256), (3, 64, 384), (24, 80), (1000,), (130,),
                 (1000, 1024), (1024,)]
RAGGED_ROWS = (1000, 1024)


def _reduce_case(kind, n, shape):
    """(kernel output, einsum reference, N = 1 exact form, stack dtype).
    The two-plane sum has no exact form: a backend may contract it into a
    fused multiply-add."""
    k = jax.random.split(jax.random.PRNGKey(n * 7919 + sum(shape)), 4)
    w = jax.random.uniform(k[0], (n,), jnp.float32, 0.1, 1.0)
    if kind in ("int8", "int8x2"):
        q = jax.random.randint(k[1], (n,) + shape, -127, 128, jnp.int8)
        q32 = q.astype(jnp.float32)
        if kind == "int8":
            return (dc.int8_decompress_reduce(q, w, interpret=True),
                    jnp.einsum("c,c...->...", w, q32), w[0] * q32[0], q.dtype)
        wr = jax.random.uniform(k[2], (n,), jnp.float32, 1e-3, 1e-2)
        qr = jax.random.randint(k[3], (n,) + shape, -127, 128, jnp.int8)
        qr32 = qr.astype(jnp.float32)
        return (dc.int8_decompress_reduce(q, w, qr, wr, interpret=True),
                jnp.einsum("c,c...->...", w, q32)
                + jnp.einsum("c,c...->...", wr, qr32), None, q.dtype)
    x = jax.random.normal(k[1], (n,) + shape, kind)
    x32 = x.astype(jnp.float32)
    return (fr.fedavg_reduce(x, w, interpret=True),
            jnp.einsum("c,c...->...", w, x32).astype(kind),
            (w[0] * x32[0]).astype(kind), x.dtype)


@pytest.mark.parametrize("shape", REDUCE_SHAPES, ids=str)
@pytest.mark.parametrize("n", [1, 2, 5])
@pytest.mark.parametrize("kind", ["int8", "int8x2", jnp.float32,
                                  jnp.bfloat16],
                         ids=["int8", "int8x2", "f32", "bf16"])
def test_lane_dense_reduce_matches_einsum(kind, n, shape):
    out, want, exact, dtype = _reduce_case(kind, n, shape)
    assert out.shape == shape
    if shape == RAGGED_ROWS:          # the case does end in a partial block
        planes = 2 * n if kind == "int8x2" else n
        lanes, block_rows, pad = fr.reduce_tiling(
            planes, math.prod(shape), dtype, shape[-1])
        assert shape[0] % block_rows and pad == 0
    if n == 1 and exact is not None:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(exact))
    tol = TOL[jnp.bfloat16 if kind == jnp.bfloat16 else jnp.float32]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_reduce_tiling_takes_every_qwen_leaf_lane_dense():
    """Every leaf of qwen1.5-0.5b reduces in its own last dimension as
    lanes, unpadded, in blocks of whole sublane tiles (or one block)."""
    from repro.configs import get_arch
    from repro.models import registry
    shapes = jax.eval_shape(lambda: registry.init(jax.random.PRNGKey(0),
                                                  get_arch("qwen1.5-0.5b")))
    leaves = jax.tree.leaves(shapes)
    assert len(leaves) == 14
    for leaf in leaves:
        m = math.prod(leaf.shape)
        for n, dtype, tile in ((1, jnp.int8, 32), (25, jnp.float32, 8)):
            lanes, block_rows, pad = fr.reduce_tiling(n, m, dtype,
                                                      leaf.shape[-1])
            assert (lanes, pad) == (leaf.shape[-1], 0), leaf.shape
            assert (block_rows % tile == 0
                    or block_rows == m // lanes), (leaf.shape, n)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 2, 2, 128, 128),      # MHA
    (2, 4, 2, 256, 64),       # GQA + padded head_dim
    (1, 8, 1, 384, 128),      # MQA-ish, odd-length grid
])
@pytest.mark.parametrize("variant", ["causal", "window", "softcap", "full"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, KV, S, hd, variant, dtype):
    rng = jax.random.split(jax.random.PRNGKey(0), 3)
    q = (jax.random.normal(rng[0], (B, H, S, hd)) * 0.3).astype(dtype)
    k = (jax.random.normal(rng[1], (B, KV, S, hd)) * 0.3).astype(dtype)
    v = jax.random.normal(rng[2], (B, KV, S, hd)).astype(dtype)
    kw = {"causal": dict(causal=True),
          "window": dict(causal=True, window=64),
          "softcap": dict(causal=True, softcap=20.0),
          "full": dict(causal=False)}[variant]
    out = fa.flash_attention(q, k, v, interpret=True, **kw)
    want = ref.flash_attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_flash_model_layout_and_grad():
    rng = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(rng[0], (2, 256, 4, 64)) * 0.3
    k = jax.random.normal(rng[1], (2, 256, 2, 64)) * 0.3
    v = jax.random.normal(rng[2], (2, 256, 2, 64))

    def f_kernel(q):
        return jnp.sum(ops.flash_attention(q, k, v, causal=True) ** 2)

    def f_ref(q):
        qt, kt, vt = (jnp.moveaxis(t, 1, 2) for t in (q, k, v))
        o = ref.flash_attention_ref(qt, kt, vt, causal=True)
        return jnp.sum(jnp.moveaxis(o, 2, 1) ** 2)

    np.testing.assert_allclose(float(f_kernel(q)), float(f_ref(q)), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(jax.grad(f_kernel)(q)),
                               np.asarray(jax.grad(f_ref)(q)),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 32, 16, 16),
    (2, 96, 3, 64, 32, 32),
    (1, 256, 1, 64, 128, 64),   # mamba2-780m-like ratios
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(S + H), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, N)) * 0.5
    c = jax.random.normal(ks[4], (B, S, N)) * 0.5
    d = jnp.linspace(0.5, 1.5, H)
    y, st = ops.ssd_scan(x, dt, A, b, c, d, chunk=chunk)
    yr, sr = ref.ssd_scan_ref(x, dt, A, b, c, d, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr),
                               rtol=5e-4, atol=5e-4)


def test_ssd_scan_state_equals_stepwise_recurrence():
    """Chunked SSD must equal the naive per-step recurrence."""
    B, S, H, P, N = 1, 40, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, N)) * 0.5
    c = jax.random.normal(ks[4], (B, S, N)) * 0.5
    d = jnp.zeros((H,))
    y, _ = ops.ssd_scan(x, dt, A, b, c, d, chunk=8)

    st = np.zeros((B, H, N, P))
    ys = []
    for t in range(S):
        decay = np.exp(np.asarray(dt[:, t]) * np.asarray(A))     # (B,H)
        st = st * decay[..., None, None] + np.einsum(
            "bh,bn,bhp->bhnp", np.asarray(dt[:, t]), np.asarray(b[:, t]),
            np.asarray(x[:, t]))
        ys.append(np.einsum("bn,bhnp->bhp", np.asarray(c[:, t]), st))
    y_naive = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y), y_naive, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# moe_gmm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,C,d,f", [(4, 128, 256, 512), (8, 100, 512, 384),
                                     (2, 257, 320, 640)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_sweep(E, C, d, f, dtype):
    ks = jax.random.split(jax.random.PRNGKey(E + C), 2)
    x = (jax.random.normal(ks[0], (E, C, d)) * 0.1).astype(dtype)
    w = (jax.random.normal(ks[1], (E, d, f)) * 0.05).astype(dtype)
    np.testing.assert_allclose(np.asarray(ops.gmm(x, w), np.float32),
                               np.asarray(ref.gmm_ref(x, w), np.float32),
                               **TOL[dtype])


def test_moe_ffn_kernel_matches_oracle():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (4, 64, 128)) * 0.3
    gate = jax.random.normal(ks[1], (4, 128, 256)) * 0.05
    up = jax.random.normal(ks[2], (4, 128, 256)) * 0.05
    down = jax.random.normal(ks[0], (4, 256, 128)) * 0.05
    np.testing.assert_allclose(
        np.asarray(ops.moe_gmm(x, gate, up, down)),
        np.asarray(ref.moe_ffn_ref(x, gate, up, down)),
        rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# top-k scatter (Mosaic one-hot matmul, DESIGN.md §10)
# ---------------------------------------------------------------------------

from repro.launch.mesh import make_host_mesh, make_mesh  # noqa: E402


def _topk_payload(seed, n, k, m):
    """Random (N, S) payload; indices drawn WITH replacement so duplicate
    coordinates (several clients keeping the same weight) are the common
    case, not the edge case."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    vals = jax.random.normal(ks[0], (n, k))
    idx = jax.random.randint(ks[1], (n, k), 0, max(m, 1), dtype=jnp.int32)
    w = jax.nn.softmax(jax.random.normal(ks[2], (n,)))
    return vals, idx, w


@pytest.mark.parametrize("n,k,m", [(3, 5, 17), (8, 64, 1000), (1, 1, 1),
                                   (2, 7, 1), (5, 130, 4099)])
def test_topk_scatter_reduce_mosaic_sweep(n, k, m):
    vals, idx, w = _topk_payload(n * 1000 + k, n, k, m)
    want = dc.topk_scatter_reduce(vals, idx, w, m)
    got = dc.topk_scatter_reduce_mosaic(vals, idx, w, m, interpret=True)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_topk_scatter_reduce_mosaic_duplicates_accumulate():
    """Colliding coordinates must sum, exactly as the XLA scatter-add."""
    vals = jnp.array([[1.0, 2.0, 4.0], [8.0, 16.0, 32.0]])
    idx = jnp.array([[0, 0, 3], [3, 1, 0]], jnp.int32)
    w = jnp.array([1.0, 0.5])
    got = dc.topk_scatter_reduce_mosaic(vals, idx, w, 5, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.array([1 + 2 + 16, 8, 0, 4 + 4, 0], np.float32))


def test_topk_scatter_reduce_mosaic_empty_payload():
    """k == 0 (codec kept nothing) must yield an exact zero reduction."""
    vals = jnp.zeros((2, 0))
    idx = jnp.zeros((2, 0), jnp.int32)
    w = jnp.array([0.5, 0.5])
    got = dc.topk_scatter_reduce_mosaic(vals, idx, w, 37, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(37, np.float32))


@pytest.mark.parametrize("s,m", [(6, 40), (1, 1), (130, 4099)])
def test_topk_scatter_apply_mosaic_matches_xla_bitwise(s, m):
    """Unique indices: the one-hot matmul adds exactly one f32 term per
    output slot, so reconstruction is bit-identical to the XLA scatter."""
    ks = jax.random.split(jax.random.PRNGKey(s * m), 3)
    refv = jax.random.normal(ks[0], (m,))
    vals = jax.random.normal(ks[1], (s,))
    idx = jax.random.permutation(ks[2], m)[:s].astype(jnp.int32)
    got = dc.topk_scatter_apply_mosaic(refv, vals, idx, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(dc.topk_scatter_apply(refv, vals, idx)))


def test_topk_scatter_apply_mosaic_duplicates_and_empty():
    refv = jnp.array([10.0, 20.0, 30.0])
    vals = jnp.array([1.0, 2.0, 4.0])
    idx = jnp.array([2, 2, 0], jnp.int32)
    got = dc.topk_scatter_apply_mosaic(refv, vals, idx, interpret=True)
    np.testing.assert_allclose(np.asarray(got), [14.0, 20.0, 33.0],
                               rtol=1e-6)
    # empty payload: the reference passes through untouched
    empty = dc.topk_scatter_apply_mosaic(
        refv, jnp.zeros((0,)), jnp.zeros((0,), jnp.int32), interpret=True)
    np.testing.assert_array_equal(np.asarray(empty), np.asarray(refv))


def test_topk_scatter_sharded_matches_unsharded():
    mesh = make_host_mesh()
    vals, idx, w = _topk_payload(11, 4, 16, 513)
    want = dc.topk_scatter_reduce_mosaic(vals, idx, w, 513, interpret=True)
    got = dc.topk_scatter_reduce_sharded(vals, idx, w, 513, mesh=mesh,
                                         client_axes=("data",),
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_import_picks_no_backend():
    """Importing the package must not initialise a JAX backend: on a TPU
    host that would take the chip from whichever process runs next. The
    interpret-mode decision is made per kernel call instead."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("import repro, repro.kernels, repro.api, repro.core, "
            "repro.launch.train, repro.launch.fleet\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "from repro.kernels import ops\n"
            "assert ops.interpret_mode()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": src}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_mosaic_scatter_dispatch_gate():
    """ops.topk_delta_reduce picks Mosaic for small dense work volumes and
    the XLA oracle beyond the interpret-mode ceiling — both must agree."""
    assert ops.mosaic_scatter_ok(8, 100)
    if ops.interpret_mode():
        assert not ops.mosaic_scatter_ok(1 << 12, 1 << 12)
    vals, idx, w = _topk_payload(0, 4, 16, 333)
    out = ops.topk_delta_reduce(vals, idx, w, 333)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dc.topk_scatter_reduce(vals, idx, w, 333)),
        rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# hierarchical two-tier reduce (grouped psum, DESIGN.md §11)
# ---------------------------------------------------------------------------

def _pod_data_mesh():
    """Two client axes on one host device — exercises the grouped-axes
    collective lowering without needing multiple devices."""
    return make_mesh((1, 1), ("pod", "data"))


def test_psum_tiers_rejects_non_partition():
    with pytest.raises(ValueError, match="partition"):
        fr.psum_tiers(jnp.zeros(4), ("pod", "data"), (("data",),))
    with pytest.raises(ValueError, match="partition"):
        fr.psum_tiers(jnp.zeros(4), ("pod", "data"),
                      (("data",), ("pod", "data")))


def test_fedavg_reduce_sharded_grouped_matches_flat():
    mesh = _pod_data_mesh()
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 4096))
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(1), (8,)))
    flat = fr.fedavg_reduce_sharded(x, w, mesh=mesh,
                                    client_axes=("pod", "data"),
                                    interpret=True)
    grouped = fr.fedavg_reduce_sharded(x, w, mesh=mesh,
                                       client_axes=("pod", "data"),
                                       interpret=True,
                                       reduce_tiers=(("data",), ("pod",)))
    assert np.abs(np.asarray(grouped) - np.asarray(flat)).max() <= 1e-6


def test_int8_delta_reduce_sharded_grouped_matches_flat():
    q = jax.random.randint(jax.random.PRNGKey(2), (4, 2048), -127, 128,
                           dtype=jnp.int8)
    w_eff = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(3), (4,)))
    mesh = _pod_data_mesh()
    kw = dict(mesh=mesh, client_axes=("pod", "data"), interpret=True)
    flat = dc.int8_decompress_reduce_sharded(q, w_eff, **kw)
    grouped = dc.int8_decompress_reduce_sharded(
        q, w_eff, reduce_tiers=(("data",), ("pod",)), **kw)
    assert np.abs(np.asarray(grouped) - np.asarray(flat)).max() <= 1e-6


def test_topk_scatter_sharded_grouped_matches_flat():
    vals, idx, w = _topk_payload(23, 4, 16, 513)
    mesh = _pod_data_mesh()
    kw = dict(mesh=mesh, client_axes=("pod", "data"), interpret=True)
    flat = dc.topk_scatter_reduce_sharded(vals, idx, w, 513, **kw)
    grouped = dc.topk_scatter_reduce_sharded(
        vals, idx, w, 513, reduce_tiers=(("data",), ("pod",)), **kw)
    assert np.abs(np.asarray(grouped) - np.asarray(flat)).max() <= 1e-6
