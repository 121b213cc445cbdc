"""Public API tests: ExperimentSpec, plugin registries, FederatedExperiment.

The load-bearing contract is the build-parity matrix: an experiment built
from a JSON-round-tripped spec must train bitwise-identically to a
directly-constructed FedAvgTrainer, across backends x transports x
samplers (DESIGN.md §9)."""
import json
import os
import subprocess
import sys
import warnings
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.api import (ExperimentSpec, FederatedExperiment,
                       SpecValidationError, build)
from repro.api.registries import (AGGREGATOR_REGISTRY, BACKEND_REGISTRY,
                                  SAMPLER_REGISTRY, TRANSPORT_REGISTRY,
                                  register_aggregator)
from repro.configs import get_paper_task
from repro.configs.base import FedConfig, RuntimeModelConfig
from repro.core import FedAvgTrainer, RuntimeModel, make_eval_fn
from repro.core.engine import MeshBackend
from repro.core.engine.trainer import History
from repro.data import make_paper_task
from repro.launch.mesh import make_mesh
from repro.models import small


# ---------------------------------------------------------------------------
# spec serialization / overrides / validation
# ---------------------------------------------------------------------------

def _nondefault_spec() -> ExperimentSpec:
    return ExperimentSpec().with_overrides(
        "data.kind=paper", "data.task=femnist", "data.clients=12",
        "fed.rounds=8", "fed.clients_per_round=4", "fed.k0=3",
        "fed.k_schedule=rounds", "fed.eta0=0.3", "fed.batch_size=4",
        "sampler.name=fixed_cohort", "sampler.cohort=[0,2,5,7]",
        "transport.name=int8", "backend.name=mesh",
        "backend.strategy=sequential", "runtime.beta_seconds=0.05")


def test_spec_json_roundtrip_equality():
    spec = _nondefault_spec()
    again = ExperimentSpec.from_json(spec.to_json())
    assert again == spec
    # tuple fields survive the json list detour
    assert again.sampler.cohort == (0, 2, 5, 7)
    # and the round trip is a fixed point
    assert again.to_json() == spec.to_json()


def test_spec_file_roundtrip(tmp_path):
    spec = _nondefault_spec()
    path = os.path.join(tmp_path, "spec.json")
    spec.save(path)
    assert ExperimentSpec.load(path) == spec


def test_from_dict_rejects_unknown_keys_aggregated():
    d = ExperimentSpec().as_dict()
    d["fed"]["warp_factor"] = 9
    d["mystery"] = {}
    with pytest.raises(SpecValidationError) as ei:
        ExperimentSpec.from_dict(d)
    msg = str(ei.value)
    assert "fed.warp_factor" in msg and "mystery" in msg
    assert len(ei.value.errors) == 2


def test_with_overrides_types_and_errors():
    spec = ExperimentSpec().with_overrides(
        "fed.k0=4", "fed.eta0=0.25", "fed.k_quantize=true",
        "transport.name=topk", "sampler.cohort=null")
    assert spec.fed.k0 == 4 and isinstance(spec.fed.k0, int)
    assert spec.fed.eta0 == 0.25
    assert spec.fed.k_quantize is True
    assert spec.transport.name == "topk"
    assert spec.sampler.cohort is None
    with pytest.raises(SpecValidationError) as ei:
        ExperimentSpec().with_overrides("fed.nope=1", "bogus.k=2",
                                        "fed.k0=notanint")
    assert len(ei.value.errors) == 3


def test_validate_aggregates_all_errors():
    spec = ExperimentSpec().with_overrides(
        "fed.k_schedule=warp", "fed.aggregator=meen", "fed.rounds=0",
        "transport.topk_frac=7")
    with pytest.raises(SpecValidationError) as ei:
        spec.validate()
    msg = str(ei.value)
    for frag in ("fed.k_schedule", "fed.aggregator", "fed.rounds",
                 "transport.topk_frac"):
        assert frag in msg
    # did-you-mean rides through the registry error
    assert "mean" in msg


def test_validate_transport_needs_linear_aggregator():
    spec = ExperimentSpec().with_overrides("transport.name=int8",
                                           "fed.aggregator=median")
    with pytest.raises(SpecValidationError, match="linear"):
        spec.validate()


def test_validate_and_override_downlink_field():
    """transport.downlink resolves through the transport registry and rides
    dotted-path overrides; robust aggregators stay legal (downlink only
    changes the broadcast, DESIGN.md §8.6)."""
    spec = ExperimentSpec().with_overrides("transport.downlink=int8",
                                           "fed.aggregator=median")
    assert spec.transport.downlink == "int8"
    spec.validate()
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    with pytest.raises(SpecValidationError, match="transport.downlink"):
        ExperimentSpec().with_overrides(
            "transport.downlink=int9").validate()


def test_validate_cohort_length():
    spec = ExperimentSpec().with_overrides(
        "sampler.name=fixed_cohort", "sampler.cohort=[1,2]",
        "fed.clients_per_round=4")
    with pytest.raises(SpecValidationError, match="cohort"):
        spec.validate()


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------

def test_registry_did_you_mean_errors():
    with pytest.raises(KeyError, match="Did you mean 'mean'"):
        AGGREGATOR_REGISTRY.get("meen")
    with pytest.raises(KeyError, match="Did you mean 'fixed_cohort'"):
        SAMPLER_REGISTRY.get("fixed_cohrt")
    with pytest.raises(KeyError, match="Available: local, mesh"):
        BACKEND_REGISTRY.get("tpu-pod")


def test_registry_lists_builtins():
    assert set(AGGREGATOR_REGISTRY.available()) >= {
        "mean", "kernel", "median", "trimmed_mean"}
    assert set(TRANSPORT_REGISTRY.available()) >= {
        "none", "int8", "int8x2", "topk"}
    assert set(SAMPLER_REGISTRY.available()) >= {
        "uniform", "weighted", "fixed_cohort", "availability"}


def test_register_custom_aggregator_resolves_everywhere():
    from repro.core.engine.aggregators import get_aggregator, weighted_mean

    name = "test_double_mean"
    register_aggregator(name, lambda **kw: (
        lambda cp, w: jax.tree.map(lambda x: 2.0 * x,
                                   weighted_mean(cp, w))))
    try:
        agg = get_aggregator(name)
        stack = {"p": np.ones((3, 2), np.float32)}
        out = agg(stack, np.full(3, 1 / 3, np.float32))
        np.testing.assert_allclose(np.asarray(out["p"]), 2.0, rtol=1e-6)
        assert name in AGGREGATOR_REGISTRY.available()
    finally:
        AGGREGATOR_REGISTRY._entries.pop(name, None)


# ---------------------------------------------------------------------------
# build parity: from_json(to_json(spec)) == direct FedAvgTrainer, bitwise
# ---------------------------------------------------------------------------

def _direct_trainer(spec: ExperimentSpec):
    """Hand-constructed trainer for a paper-task spec (what a user would
    have written pre-API)."""
    task = get_paper_task(spec.data.task)
    data = make_paper_task(spec.data.task,
                           np.random.default_rng(spec.data.seed),
                           num_clients=spec.data.clients,
                           samples_per_client=spec.data.samples_per_client)
    loss_fn = lambda p, b: small.task_loss(p, task, b)
    params = small.init_task_model(jax.random.PRNGKey(spec.fed.seed), task)
    fed = FedConfig(total_clients=spec.data.clients,
                    clients_per_round=spec.fed.clients_per_round,
                    rounds=spec.fed.rounds, k0=spec.fed.k0,
                    eta0=spec.fed.eta0, batch_size=spec.fed.batch_size,
                    loss_window=spec.fed.loss_window,
                    k_schedule=spec.fed.k_schedule,
                    transport=spec.transport.name,
                    sampler=spec.sampler.name, cohort=spec.sampler.cohort,
                    seed=spec.fed.seed)
    rt = RuntimeModel(task.model_size_mb,
                      RuntimeModelConfig(beta_seconds=0.05),
                      fed.clients_per_round)
    backend = None
    if spec.backend.name == "mesh":
        mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
        backend = MeshBackend(mesh, strategy=spec.backend.strategy)
    eval_fn = (make_eval_fn(loss_fn, data) if spec.fed.eval_every else None)
    return FedAvgTrainer(loss_fn, params, data, fed, rt, eval_fn=eval_fn,
                         backend=backend)


@pytest.mark.parametrize("backend", ["local", "mesh"])
@pytest.mark.parametrize("transport", ["none", "int8"])
@pytest.mark.parametrize("sampler", ["uniform", "fixed_cohort"])
def test_build_matches_direct_construction_bitwise(backend, transport,
                                                   sampler):
    """The ISSUE-4 acceptance matrix: {local, mesh-parallel} x {none, int8}
    x {uniform, fixed_cohort}, 8 rounds, bitwise history + params."""
    spec = ExperimentSpec().with_overrides(
        "data.kind=paper", "data.task=femnist", "data.clients=10",
        "data.samples_per_client=20", "fed.rounds=8",
        "fed.clients_per_round=4", "fed.k0=3", "fed.k_schedule=rounds",
        "fed.eta0=0.3", "fed.batch_size=4", "fed.loss_window=5",
        f"backend.name={backend}", f"transport.name={transport}",
        f"sampler.name={sampler}", "runtime.beta_seconds=0.05")
    spec = ExperimentSpec.from_json(spec.to_json())     # serialization detour
    exp = build(spec)
    h = exp.run()
    tr = _direct_trainer(spec)
    h2 = tr.run(8)
    assert h.as_dict() == h2.as_dict()
    for a, b in zip(jax.tree.leaves(exp.params), jax.tree.leaves(tr.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # transport EF state agrees too (per-client slots for fixed cohorts)
    for a, b in zip(jax.tree.leaves(exp.trainer.engine.transport_state),
                    jax.tree.leaves(tr.engine.transport_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# experiment facade: checkpoint embeds the spec
# ---------------------------------------------------------------------------

def _small_spec(**over):
    base = ExperimentSpec().with_overrides(
        "data.kind=paper", "data.task=femnist", "data.clients=10",
        "data.samples_per_client=20", "fed.rounds=8",
        "fed.clients_per_round=4", "fed.k0=3", "fed.k_schedule=rounds",
        "fed.eta0=0.3", "fed.batch_size=4", "fed.loss_window=5",
        "runtime.beta_seconds=0.05")
    return base.with_overrides(*[f"{k}={v}" for k, v in over.items()])


def test_experiment_save_embeds_spec_and_restore_rebuilds(tmp_path):
    spec = _small_spec(**{"transport.name": "int8"})
    exp = build(spec)
    exp.run(rounds=4)
    path = os.path.join(tmp_path, "ckpt")
    exp.save(path)
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    assert ExperimentSpec.from_dict(meta["spec"]) == spec

    # restore rebuilds the exact trainer and continues bitwise: compare
    # against one uninterrupted 8-round run
    resumed = FederatedExperiment.restore(path)
    assert resumed.spec == spec
    resumed.trainer.run(8, resume=True)
    straight = build(spec)
    straight.run()
    assert resumed.history.as_dict() == straight.history.as_dict()
    for a, b in zip(jax.tree.leaves(resumed.params),
                    jax.tree.leaves(straight.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_without_spec_raises(tmp_path):
    spec = _small_spec()
    exp = build(spec)
    exp.run(rounds=2)
    path = os.path.join(tmp_path, "ckpt")
    exp.trainer.save_state(path)            # no embedded spec
    with pytest.raises(ValueError, match="no embedded spec"):
        FederatedExperiment.restore(path)


# ---------------------------------------------------------------------------
# deprecation shim + History schema drift
# ---------------------------------------------------------------------------

def test_use_kernel_avg_deprecated_but_resolves():
    task = get_paper_task("femnist")
    data = make_paper_task("femnist", np.random.default_rng(0),
                           num_clients=8, samples_per_client=10)
    loss_fn = lambda p, b: small.task_loss(p, task, b)
    params = small.init_task_model(jax.random.PRNGKey(0), task)
    fed = FedConfig(total_clients=8, clients_per_round=3, rounds=2, k0=2,
                    eta0=0.3, batch_size=4, loss_window=3)
    rt = RuntimeModel(task.model_size_mb, task.runtime, 3)
    with pytest.warns(DeprecationWarning, match="use_kernel_avg"):
        tr = FedAvgTrainer(loss_fn, params, data, fed, rt,
                           use_kernel_avg=True)
    assert tr.engine.compile_count == 0     # built fine, kernel aggregator


def test_make_round_fn_use_kernel_avg_deprecated():
    from repro.core import make_round_fn
    task = get_paper_task("femnist")
    loss_fn = lambda p, b: small.task_loss(p, task, b)
    with pytest.warns(DeprecationWarning, match="use_kernel_avg"):
        make_round_fn(loss_fn, use_kernel_avg=False)


def test_history_from_dict_warns_on_unknown_fields():
    d = History().as_dict()
    d["rounds"] = [1, 2]
    d["a_new_metric"] = [0.5, 0.6]
    with pytest.warns(UserWarning, match="a_new_metric"):
        h = History.from_dict(d)
    assert h.rounds == [1, 2]
    assert not hasattr(h, "a_new_metric")
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # clean dicts stay silent
        History.from_dict(History().as_dict())


@pytest.mark.parametrize("argv,reduced", [([], True), (["--reduced"], True),
                                          (["--no-reduced"], False)])
def test_legacy_reduced_flag_reaches_published_widths(argv, reduced):
    from repro.launch.train import make_parser, spec_from_legacy_args
    spec = spec_from_legacy_args(make_parser().parse_args(argv))
    assert spec.model.reduced is reduced


def test_compile_cache_placed_from_environment(tmp_path):
    """The entry points' cache helper keeps the cache where
    JAX_COMPILATION_CACHE_DIR says and sets no other directory; without it
    the cache sits at a fixed path in the checkout."""
    from repro.launch import compile_cache
    repo = Path(__file__).resolve().parents[1]
    assert compile_cache.DEFAULT_DIR == repo / ".jax_cache"

    cache = tmp_path / "cc"
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            f"assert enable_compile_cache() == {str(cache)!r}\n"
            f"assert jax.config.jax_compilation_cache_dir == {str(cache)!r}\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache),
           "PYTHONPATH": str(repo / "src")}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert any(cache.iterdir()), "no cache entry was written"
    assert sorted(p.name for p in tmp_path.iterdir()) == ["cc"]
