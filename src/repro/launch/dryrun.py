import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, and extract the roofline terms.

The lines above MUST run before any other import (jax locks the platform
and device count at first init). This is a fake-device tool: it pins itself
to 512 placeholder CPU devices, so neither it nor its ``--all`` children
(which inherit the environment) ever take an accelerator. Smoke tests and
benches do NOT go through this module and keep seeing one CPU device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all   (spawns a subprocess per case)

Each case writes experiments/dryrun/<arch>__<shape>__<mesh>.json with
memory analysis, cost analysis, collective stats and roofline terms.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_arch, get_shape
from repro.distributed import sharding
from repro.distributed.strategies import (fed_batch_specs, fed_weight_specs,
                                          make_fed_train_step,
                                          make_prefill_step, make_serve_step)
from repro.launch import hlo_analysis, hlo_loops
from repro.launch.mesh import make_production_mesh
from repro.models import registry

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# dry-run federated round geometry (see DESIGN.md §2.1)
K_LOCAL = 4

# archs that must use strategy B / 2d params (cross-silo regime): one client
# copy of the params per data lane (strategy A) only fits up to ~7B at bf16
# on 16-way model sharding (measured: gemma2-27b needs 3.4 GB/chip params
# alone -> ~17 GB with grads + round carry + f32 averaging).
SEQUENTIAL_ARCHS = {"gemma2-27b", "phi3.5-moe-42b-a6.6b", "llava-next-34b",
                    "mixtral-8x22b", "nemotron-4-340b"}


def should_skip(cfg, shape) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return ("pure full-attention arch: long_500k requires sub-quadratic "
                "attention (DESIGN.md §2.5)")
    return None


def case_name(arch: str, shape: str, multi_pod: bool) -> str:
    mesh = "2x16x16" if multi_pod else "16x16"
    return f"{arch}__{shape}__{mesh}"


def build_case(arch_name: str, shape_name: str, multi_pod: bool,
               overrides: Optional[Dict[str, Any]] = None):
    """Construct (step_fn, example_args, in_shardings, out_shardings, meta)."""
    overrides = overrides or {}
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    dtype = jnp.bfloat16
    two_d = cfg.name in SEQUENTIAL_ARCHS or overrides.get("force_2d", False)
    two_d = overrides.get("two_d", two_d)
    strategy = "sequential" if cfg.name in SEQUENTIAL_ARCHS else "parallel"
    strategy = overrides.get("strategy", strategy)

    params_shapes = jax.eval_shape(
        lambda: registry.init(jax.random.PRNGKey(0), cfg, dtype))
    # multi-pod 2d archs also FSDP over the pod axis (512-way param sharding)
    fsdp_axes = ("data", "pod") if (two_d and multi_pod) else ("data",)
    pspecs = sharding.param_pspecs(cfg, params_shapes, mesh, two_d=two_d,
                                   fsdp_axes=fsdp_axes)
    p_shard = sharding.named(mesh, pspecs)
    meta: Dict[str, Any] = {
        "arch": cfg.name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "strategy": strategy, "two_d_params": two_d,
        "param_count": registry.param_count(cfg),
        "active_param_count": registry.active_param_count(cfg),
    }

    if shape.kind == "train":
        n_clients = overrides.get("n_clients",
                                  32 if (multi_pod and strategy == "parallel") else 16)
        if strategy == "sequential":
            # multi-pod: the pod axis is spent on FSDP param sharding (the
            # 100B+ archs need the memory), so clients stay one sequential
            # scan; cross-pod client groups would need the pod axis twice.
            groups: Optional[int] = 1
        else:
            groups = None
        k_local = overrides.get("k_local", K_LOCAL)
        batches = fed_batch_specs(cfg, shape, n_clients=n_clients,
                                  k_local=k_local, groups=groups, dtype=dtype)
        weights = fed_weight_specs(n_clients, groups)
        b_specs = sharding.fed_batch_pspecs(batches, mesh, strategy)
        if strategy == "parallel":
            w_spec = P(sharding.client_axes(mesh))
        else:
            w_spec = P(None, None)
        # production default: Megatron-style sequence parallelism — the
        # residual stream is sharded over 'model' along the SEQUENCE dim, so
        # remat-saved boundaries shrink 16x while matmul layouts stay 1d
        # (ablation in EXPERIMENTS §Perf; sharding over d_model instead was
        # measured 6x WORSE — it fights the col/row-parallel weight layout)
        act_mode = overrides.get("act_spec", "seq")
        # strategy A: the client vmap dim carries 'data' (via spmd_axis_name);
        # strategy B: the per-client batch dim itself is data-sharded.
        b_ax = "data" if strategy == "sequential" else None
        act_spec = None
        if act_mode == "seq" and shape.seq_len % mesh.shape["model"] == 0:
            act_spec = P(b_ax, "model", None)
        elif act_mode == "model" and cfg.d_model % mesh.shape["model"] == 0:
            act_spec = P(b_ax, None, "model")
        if strategy == "parallel":
            spmd_axes = sharding.client_axes(mesh)
        else:
            spmd_axes = None
        tr_moe_path = overrides.get("moe_path", "dispatch")
        tr_moe_shards, tr_moe_axes = 1, None
        if (cfg.moe is not None and "moe_path" not in overrides
                and shape.seq_len % mesh.shape["model"] == 0):
            tr_moe_path = "dispatch_sharded"
            tr_moe_shards, tr_moe_axes = mesh.shape["model"], ("model",)
        step = make_fed_train_step(
            cfg, strategy=strategy,
            remat=overrides.get("remat", True),
            moe_path=tr_moe_path, moe_shards=tr_moe_shards,
            moe_spmd_axes=tr_moe_axes,
            use_kernel_avg=overrides.get("use_kernel_avg", False),
            act_spec=act_spec,
            acc_dtype=overrides.get("acc_dtype", jnp.bfloat16),
            client_spmd_axes=spmd_axes if act_spec is not None else None,
            param_specs=pspecs if strategy == "sequential" else None)
        eta = jax.ShapeDtypeStruct((), jnp.float32)
        args = (params_shapes, batches, weights, eta)
        in_sh = (p_shard, sharding.named(mesh, b_specs),
                 NamedSharding(mesh, w_spec), NamedSharding(mesh, P()))
        out_sh = (p_shard, NamedSharding(mesh, P()))
        meta.update(n_clients=n_clients, k_local=k_local, groups=groups or 0,
                    tokens_per_round=shape.global_batch * shape.seq_len * k_local)
        return step, args, in_sh, out_sh, meta

    long_mode = shape.name == "long_500k"
    ba = sharding.serve_batch_axes(mesh)
    ba_size = 1
    for a in ba:
        ba_size *= mesh.shape[a]
    B = shape.global_batch

    if shape.kind == "prefill":
        act_mode = overrides.get("act_spec", "seq")
        pf_act = None
        pf_b = ba if B % ba_size == 0 else None
        if act_mode == "seq" and shape.seq_len % mesh.shape["model"] == 0:
            pf_act = P(pf_b, "model", None)
        # when kv heads don't divide the model axis, shard the attention
        # key-sequence dim instead — keeps probs buffers sharded (measured:
        # 25.8 GB/chip unsharded probs on nemotron prefill without this)
        kv_spec = None
        if (cfg.num_kv_heads % mesh.shape["model"] != 0
                and shape.seq_len % mesh.shape["model"] == 0):
            kv_spec = P(pf_b, "model", None, None)
        # MoE: shard-local dispatch along the seq-sharded token axis —
        # the global argsort/scatter path compiles but leaves the capacity
        # buffers unsharded (mixtral prefill: 62.8 GB/chip measured)
        moe_path = overrides.get("moe_path", "dispatch")
        moe_shards, moe_axes = 1, None
        if cfg.moe is not None and shape.seq_len % mesh.shape["model"] == 0:
            moe_path = overrides.get("moe_path", "dispatch_sharded")
            moe_shards, moe_axes = mesh.shape["model"], ("model",)
        step = make_prefill_step(cfg, long_mode=long_mode, moe_path=moe_path,
                                 act_spec=pf_act, attn_kv_spec=kv_spec,
                                 moe_shards=moe_shards, moe_spmd_axes=moe_axes)
        inputs = registry.input_specs(cfg, shape, dtype=dtype)
        in_batch_specs = {}
        for k, v in inputs.items():
            bspec = P(*([ba if B % ba_size == 0 else None]
                        + [None] * (v.ndim - 1)))
            in_batch_specs[k] = NamedSharding(mesh, bspec)
        args = (params_shapes, inputs)
        in_sh = (p_shard, in_batch_specs)
        if registry.is_encdec(cfg):
            out_sh = None
        else:
            # explicit shardings for the returned decode states — GSPMD left
            # them replicated (100+ GB/chip on gemma2/nemotron, measured)
            with mesh:
                out_shapes = jax.eval_shape(step, params_shapes, inputs)
            state_specs = sharding.cache_pspecs(cfg, out_shapes[1], mesh)
            logit_spec = P(ba if B % ba_size == 0 else None,
                           "model" if cfg.vocab_size % mesh.shape["model"] == 0
                           else None)
            out_sh = (NamedSharding(mesh, logit_spec),
                      sharding.named(mesh, state_specs))
        meta.update(tokens=B * shape.seq_len)
        return step, args, in_sh, out_sh, meta

    # decode
    ring = overrides.get("ring", False)   # windowed ring caches (§Perf R1)
    kv_quant = overrides.get("kv_quant", False)  # int8 caches (§Perf Q-KV)
    cache_shapes = registry.cache_specs(cfg, B, shape.seq_len, dtype=dtype,
                                        ring=ring, long_mode=long_mode,
                                        quant=kv_quant)
    c_specs = sharding.cache_pspecs(cfg, cache_shapes, mesh)
    c_shard = sharding.named(mesh, c_specs)
    step = make_serve_step(cfg, long_mode=long_mode,
                           moe_path=overrides.get("moe_path", "dispatch"),
                           ring=ring)
    token = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    tok_spec = NamedSharding(mesh, P(ba if B % ba_size == 0 else None))
    logit_spec = NamedSharding(
        mesh, P(ba if B % ba_size == 0 else None,
                "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None))
    args = (params_shapes, cache_shapes, token, pos)
    in_sh = (p_shard, c_shard, tok_spec, NamedSharding(mesh, P()))
    out_sh = (logit_spec, c_shard)
    meta.update(tokens=B)
    return step, args, in_sh, out_sh, meta


def run_case(arch_name: str, shape_name: str, multi_pod: bool,
             overrides: Optional[Dict[str, Any]] = None,
             write: bool = True) -> Dict[str, Any]:
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    name = case_name(arch_name, shape_name, multi_pod)
    skip = should_skip(cfg, shape)
    record: Dict[str, Any] = {"case": name, "arch": arch_name,
                              "shape": shape_name,
                              "mesh": "2x16x16" if multi_pod else "16x16"}
    if skip:
        record["status"] = "skipped"
        record["reason"] = skip
        if write:
            _write(record, name)
        return record

    t0 = time.time()
    step, args, in_sh, out_sh, meta = build_case(arch_name, shape_name,
                                                 multi_pod, overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    with mesh:
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # Loop-aware accounting: XLA:CPU cost_analysis counts while bodies once
    # (verified K=1 == K=4), so FLOPs/bytes/collectives are re-derived from
    # the optimized HLO with trip-count multipliers (hlo_loops).
    stats = hlo_loops.analyze(hlo)
    flops = stats.dot_flops                    # per chip
    bytes_accessed = stats.traffic_bytes       # per chip (fusion-boundary)
    terms = hlo_analysis.roofline(flops, bytes_accessed,
                                  stats.collective_bytes, n_chips)
    mf = hlo_analysis.model_flops(
        meta["param_count"], meta.get("tokens_per_round", meta.get("tokens", 0)),
        meta["active_param_count"])
    if shape.kind == "train":
        mf *= 3  # fwd + bwd

    record.update(meta)
    record.update({
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": _mem_dict(mem),
        "cost_analysis_raw": {k: v for k, v in (cost or {}).items()
                              if isinstance(v, (int, float))},
        "collectives": {"counts": stats.collective_counts,
                        "bytes": stats.collective_bytes_by_op,
                        "total_bytes": stats.collective_bytes},
        "trip_counts": stats.trip_counts,
        "roofline": terms.as_dict(),
        "model_flops": mf,
        "useful_flops_ratio": (mf / (flops * n_chips)) if flops else None,
        "hlo_bytes": len(hlo),
    })
    print(f"[dryrun] {name}: status=ok compile={t_compile:.1f}s "
          f"flops/chip={flops:.3e} bytes/chip={bytes_accessed:.3e} "
          f"coll/chip={stats.collective_bytes:.3e}B dominant={terms.dominant}")
    print(f"[dryrun] memory_analysis: {record['memory_analysis']}")
    if write:
        _write(record, name)
    return record


def _mem_dict(mem) -> Dict[str, Any]:
    if mem is None:
        return {}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        if hasattr(mem, attr):
            out[attr] = int(getattr(mem, attr))
    return out


def _write(record: Dict[str, Any], name: str) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, name + ".json"), "w") as f:
        json.dump(record, f, indent=2, default=str)


def all_cases():
    for arch in ARCHS:
        for shape in SHAPES:
            for multi_pod in (False, True):
                yield arch, shape, multi_pod


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape x mesh) in subprocesses")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.all:
        failures = []
        for arch, shape, mp in all_cases():
            name = case_name(arch, shape, mp)
            path = os.path.join(OUT_DIR, name + ".json")
            if args.skip_existing and os.path.exists(path):
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape]
            if mp:
                cmd.append("--multi-pod")
            print(f"[dryrun --all] {name}", flush=True)
            r = subprocess.run(cmd, env={**os.environ})
            if r.returncode != 0:
                failures.append(name)
        print(f"[dryrun --all] done; {len(failures)} failures: {failures}")
        return 1 if failures else 0

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    try:
        rec = run_case(args.arch, args.shape, args.multi_pod)
        return 0 if rec["status"] in ("ok", "skipped") else 1
    except Exception:
        traceback.print_exc()
        rec = {"case": case_name(args.arch, args.shape, args.multi_pod),
               "status": "error", "error": traceback.format_exc()}
        _write(rec, rec["case"])
        return 1


if __name__ == "__main__":
    sys.exit(main())
