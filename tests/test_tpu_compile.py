"""Compiles for a described TPU v5e: the main path's kernels and one
full-width round step, at real widths, with no chip attached.

Interpret-mode tests cannot see what the Mosaic compiler refuses (tile
alignment, VMEM limits) or whether a program fits the chip's memory. The
topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
All such compiles live in this one file so they land on one worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import delta_codec as dc
from repro.kernels import fedavg_reduce as fr

#: qwen1.5-0.5b's tied embedding, flattened: the largest wire leaf
EMBED_M = 151936 * 1024
#: one MLP projection (d_model x d_ff) and its top-k payload at frac 0.1
MLP_M = 1024 * 2816
TOPK_S = MLP_M // 10
#: the same two leaves in their own shapes, as the int8 transport hands
#: them to the reduce: the embedding and the stacked layers' up projection
EMBED_SHAPE = (151936, 1024)
MLP_SHAPE = (24, 1024, 2816)
N_CLIENTS = 2
#: a large streamed cohort, for the reduce's VMEM budget
N_COHORT = 25
V5E_HBM_BYTES = 16 * 10**9
#: ``test_full_width_slab_step_fits_one_chip``'s reading at the commit
#: before the reduce took lane-dense blocks: arguments 7,423,812,608 B,
#: outputs 5,567,854,080 B, temporaries 1,181,658,112 B
SLAB_PEAK_BEFORE = 14_173_324_800


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep these out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name, on):
    """(function, argument shapes) for one main-path wire kernel."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    stack = lambda dt: s((N_CLIENTS, EMBED_M), dt)
    w = s((N_CLIENTS,), jnp.float32)
    one = lambda shape: s((1,) + shape, jnp.int8)
    w1 = s((1,), jnp.float32)
    flat = lambda dt: s((EMBED_M,), dt)
    scalar = s((), jnp.float32)
    cases = {
        "fedavg_reduce": (
            lambda x, w: fr.fedavg_reduce(x, w),
            (stack(jnp.float32), w)),
        "int8_reduce_1level": (
            lambda q, w: dc.int8_decompress_reduce(q, w),
            (stack(jnp.int8), w)),
        "int8_reduce_2level": (
            lambda q, w, qr, wr: dc.int8_decompress_reduce(q, w, qr, wr),
            (stack(jnp.int8), w, stack(jnp.int8), w)),
        "int8_reduce_1level_embed_n1": (
            lambda q, w: dc.int8_decompress_reduce(q, w),
            (one(EMBED_SHAPE), w1)),
        "int8_reduce_1level_mlp_n1": (
            lambda q, w: dc.int8_decompress_reduce(q, w),
            (one(MLP_SHAPE), w1)),
        "int8_reduce_2level_embed_n1": (
            lambda q, w, qr, wr: dc.int8_decompress_reduce(q, w, qr, wr),
            (one(EMBED_SHAPE), w1, one(EMBED_SHAPE), w1)),
        "int8_reduce_2level_mlp_n1": (
            lambda q, w, qr, wr: dc.int8_decompress_reduce(q, w, qr, wr),
            (one(MLP_SHAPE), w1, one(MLP_SHAPE), w1)),
        "fedavg_reduce_n25": (
            lambda x, w: fr.fedavg_reduce(x, w),
            (s((N_COHORT,) + MLP_SHAPE, jnp.float32),
             s((N_COHORT,), jnp.float32))),
        "int8_apply_1level": (
            lambda r, q, sc: dc.int8_decode_apply(r, q, sc),
            (flat(jnp.float32), flat(jnp.int8), scalar)),
        "int8_apply_2level": (
            lambda r, q, sc, qr, rs: dc.int8_decode_apply(r, q, sc, qr, rs),
            (flat(jnp.float32), flat(jnp.int8), scalar, flat(jnp.int8),
             scalar)),
        "topk_scatter_reduce": (
            lambda v, i, w: dc.topk_scatter_reduce_mosaic(v, i, w, MLP_M),
            (s((N_CLIENTS, TOPK_S), jnp.float32),
             s((N_CLIENTS, TOPK_S), jnp.int32), w)),
        "topk_scatter_apply": (
            lambda r, v, i: dc.topk_scatter_apply_mosaic(r, v, i),
            (s((MLP_M,), jnp.float32), s((TOPK_S,), jnp.float32),
             s((TOPK_S,), jnp.int32))),
    }
    return cases[name]


@pytest.mark.parametrize("name", [
    "fedavg_reduce", "int8_reduce_1level", "int8_reduce_2level",
    "int8_reduce_1level_embed_n1", "int8_reduce_1level_mlp_n1",
    "int8_reduce_2level_embed_n1", "int8_reduce_2level_mlp_n1",
    "fedavg_reduce_n25",
    "int8_apply_1level", "int8_apply_2level", "topk_scatter_reduce",
    "topk_scatter_apply"])
def test_wire_kernel_compiles_to_mosaic(name, one_chip):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if name.endswith("_n1"):
        # a stack in the leaf's own shape is reduced in place: a relayout
        # of the int8 payload or of the f32 result would be a temporary
        assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_full_width_slab_step_fits_one_chip(one_chip, monkeypatch):
    """One streamed client (``cohort_chunk=1``) of qwen1.5-0.5b at its
    published widths, K=4 local steps of batch 4 x seq 128, int8 uplink:
    the round's slab executable must fit a v5e and carry the Mosaic
    decompress-reduce, and the reduce's lane-dense view of the payloads
    may add no copy of them or of its output."""
    from repro.configs import get_arch
    from repro.core.engine.round import RoundEngine
    from repro.core.mem import executable_peak_bytes
    from repro.kernels import ops
    from repro.models import registry

    # the engine asks the (CPU) default backend whether to interpret; the
    # program compiled here is the chip's, so steer it to Mosaic
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg = get_arch("qwen1.5-0.5b")
    model_loss = registry.loss_fn(cfg, moe_path="dense")
    engine = RoundEngine(lambda p, b: model_loss(p, {"tokens": b["x"]}),
                         transport="int8", cohort_chunk=1)
    shapes = jax.eval_shape(lambda: registry.init(jax.random.PRNGKey(0),
                                                  cfg))
    on = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    params = jax.tree.map(lambda x: on(x.shape, x.dtype), shapes)
    f32 = jax.tree.map(lambda x: on(x.shape, jnp.float32), shapes)
    batches = {k: on((1, 4, 4, 128), jnp.int32) for k in ("x", "y")}
    compiled = engine._jit_slab.lower(
        params, batches, on((1,), jnp.float32), on((), jnp.float32),
        (f32, f32), f32).compile()
    total = executable_peak_bytes(compiled)
    assert total < V5E_HBM_BYTES, f"{total / 1e9:.2f} GB"
    assert total <= SLAB_PEAK_BEFORE * 1.01, f"{total:,} B"
    assert "tpu_custom_call" in compiled.as_text()
