"""Chunked streaming cohort tests (DESIGN.md §11).

The contract: ``fed.cohort_chunk=C`` processes the round's U clients in
C-sized slabs folded into streaming f32 accumulators and must stay
equivalent to the dense vmapped round — bitwise when C == U (the single
slab preserves the dense summation order), within f32 partial-sum-reorder
tolerance otherwise. ``cohort_chunk=None`` must leave the compiled
program untouched (executable-key identity), chunking must refuse the
configurations it cannot honour (robust aggregators, downlink codecs,
mesh-sequential), the streamed round must actually shrink peak executable
memory, and checkpoints must never see mid-round slab state — a dense
checkpoint resumes bitwise into a chunked trainer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec, build
from repro.api.spec import SpecValidationError

COHORT = 6


def _spec(chunk=None, transport="none", sampler="uniform", *,
          backend="local", strategy="parallel", aggregator="mean",
          rounds=4, clients=12, cohort=COHORT, bucket_rounds=2,
          downlink="none"):
    d = {
        "data": {"kind": "paper", "task": "femnist", "clients": clients,
                 "samples_per_client": 8, "seed": 0},
        "fed": {"clients_per_round": cohort, "rounds": rounds, "k0": 2,
                "eta0": 0.3, "batch_size": 4, "eval_every": 0,
                "aggregator": aggregator, "bucket_rounds": bucket_rounds,
                "loss_window": 3, "seed": 0},
        "transport": {"name": transport, "downlink": downlink},
        "sampler": {"name": sampler},
        "backend": {"name": backend, "strategy": strategy},
    }
    if sampler == "fixed_cohort":
        d["sampler"]["cohort"] = list(range(cohort))
    if chunk is not None:
        d["fed"]["cohort_chunk"] = chunk
    return ExperimentSpec.from_dict(d)


def _run(spec):
    exp = build(spec)
    exp.run()
    return exp


def _assert_bitwise(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _max_abs(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# chunk invariance: cohort_chunk in {1, 3, U} vs the dense round
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("transport,sampler", [
    ("none", "uniform"),            # transportless streaming fold
    ("int8", "uniform"),            # codec + server-aggregate EF residual
    ("int8", "fixed_cohort"),       # codec + per-client EF slab slices
    ("topk", "fixed_cohort"),       # sparse codec + per-client EF
])
def test_chunk_invariance_local(transport, sampler):
    dense = _run(_spec(None, transport, sampler))
    # C == U: one slab, dense summation order preserved => bitwise
    full = _run(_spec(COHORT, transport, sampler))
    _assert_bitwise(full.params, dense.params)
    _assert_bitwise(full.trainer.engine.transport_state,
                    dense.trainer.engine.transport_state)
    # sub-cohort slabs: only the f32 partial-sum order differs; for int8/
    # topk the EF residual then re-quantises the reordered sum, so the
    # codec tolerance is a few quantisation ULPs rather than f32 eps
    tol = 1e-6 if transport == "none" else 2e-3
    for c in (1, 3):
        chunked = _run(_spec(c, transport, sampler))
        assert _max_abs(chunked.params, dense.params) <= tol, \
            f"cohort_chunk={c} diverged beyond streaming tolerance"


def test_chunk_invariance_kernel_aggregator():
    # the Pallas reduce is the other LINEAR aggregator; C == U stays bitwise
    dense = _run(_spec(None, aggregator="kernel"))
    _assert_bitwise(_run(_spec(COHORT, aggregator="kernel")).params,
                    dense.params)
    assert _max_abs(_run(_spec(3, aggregator="kernel")).params,
                    dense.params) <= 1e-6


def test_chunk_invariance_mesh_parallel():
    dense = _run(_spec(None, "int8", backend="mesh"))
    _assert_bitwise(_run(_spec(COHORT, "int8", backend="mesh")).params,
                    dense.params)
    assert _max_abs(_run(_spec(3, "int8", backend="mesh")).params,
                    dense.params) <= 2e-3


def test_chunked_matches_across_bucket_rounds():
    """The scheduler forces bucket_cap=1 under chunking; bucketing is
    execution detail, so dense bucket_rounds=4 == chunked regardless."""
    dense = _run(_spec(None, bucket_rounds=4))
    _assert_bitwise(_run(_spec(COHORT, bucket_rounds=4)).params,
                    dense.params)


# ---------------------------------------------------------------------------
# loud refusals: configurations streaming slabs cannot honour
# ---------------------------------------------------------------------------

def test_chunking_rejects_robust_aggregator():
    with pytest.raises(SpecValidationError, match="running weighted sum"):
        _spec(3, aggregator="median").validate()
    with pytest.raises(SpecValidationError, match="running weighted sum"):
        _spec(3, aggregator="trimmed_mean").validate()


def test_chunking_rejects_downlink_codec():
    with pytest.raises(SpecValidationError, match="downlink"):
        _spec(3, "int8", downlink="int8").validate()


def test_chunking_rejects_mesh_sequential():
    with pytest.raises(SpecValidationError, match="sequential"):
        _spec(3, backend="mesh", strategy="sequential").validate()


def test_engine_guard_rejects_robust_chunk():
    """Defence in depth below the spec layer: the engine itself refuses."""
    from repro.configs import get_paper_task
    from repro.core.engine.round import RoundEngine
    from repro.models import small

    task = get_paper_task("femnist")
    loss_fn = lambda p, b: small.task_loss(p, task, b)
    with pytest.raises(ValueError, match="running weighted sum"):
        RoundEngine(loss_fn, aggregator="median", cohort_chunk=2)


# ---------------------------------------------------------------------------
# cohort_chunk=None: the compiled program is untouched
# ---------------------------------------------------------------------------

def test_chunk_none_program_identical():
    base = _run(_spec())                 # no cohort_chunk key at all
    off = _run(_spec(None))              # explicit None — same thing
    keys_base = set(base.trainer.engine._executables)
    keys_off = set(off.trainer.engine._executables)
    assert keys_base == keys_off
    assert not any(k[0] in ("slab", "slabfin") for k in keys_base)
    _assert_bitwise(base.params, off.params)


def test_chunked_compiles_slab_executables():
    exp = _run(_spec(3))
    tags = {k[0] for k in exp.trainer.engine._executables}
    assert "slab" in tags and "slabfin" in tags
    # ragged tail slab (6 = 3 + 3 here: none) vs even slabs share one
    # executable per shape; chunk=4 over 6 clients adds the ragged shape
    exp2 = _run(_spec(4))
    slab_keys = [k for k in exp2.trainer.engine._executables
                 if k[0] == "slab"]
    assert len(slab_keys) == 2           # full slab (4) + ragged tail (2)


# ---------------------------------------------------------------------------
# memory: the streamed round must actually shrink the executable
# ---------------------------------------------------------------------------

def test_chunked_peak_memory_budget():
    """chunk = U/8 must cut peak executable bytes >= 4x (ISSUE acceptance:
    the chunked program never materialises the (U, K, b, ...) stack)."""
    from repro.core import trainer_peak_mb

    def spec(chunk):
        return _spec(chunk, clients=32, cohort=16, rounds=2,
                     bucket_rounds=1)

    dense = _run(spec(None))
    chunked = _run(spec(2))
    dense_mb = trainer_peak_mb(dense.trainer)
    chunk_mb = trainer_peak_mb(chunked.trainer)
    assert dense_mb > 0 and chunk_mb > 0
    assert dense_mb / chunk_mb >= 4.0, \
        f"peak {dense_mb:.2f}MB -> {chunk_mb:.2f}MB: reduction under 4x"


def test_chunked_round_frees_slab_buffers_before_finalize():
    """Across a streamed round, the only params-sized device buffers that
    stay live are the two f32 accumulators: the last slab's inputs (the
    previous accumulators) and its pass-through EF residual are freed before
    the finalize allocates, else a full-width model runs out of HBM there."""
    exp = build(_spec(1, "int8"))
    engine = exp.trainer.engine
    param_bytes = sum(x.size * 4 for x in jax.tree.leaves(exp.params))
    live = {}
    lookup = engine._lookup

    def counting_lookup(key, jitted, args):
        exe = lookup(key, jitted, args)

        def call(*a):
            live.setdefault(key[0], sum(x.nbytes for x in jax.live_arrays()))
            return exe(*a)
        return call

    engine._lookup = counting_lookup
    exp.run(1)
    # first slab: params, the shared zero accumulator, the EF residual;
    # finalize: params, two accumulators, the EF residual
    growth = (live["slabfin"] - live["slab"]) / param_bytes
    assert growth <= 1.5, f"{growth:.2f} extra params-sized buffers"


# ---------------------------------------------------------------------------
# checkpoints: mid-round slab state never persists
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("transport,sampler", [
    ("none", "uniform"),
    ("int8", "fixed_cohort"),            # per-client EF rides the checkpoint
])
def test_dense_checkpoint_resumes_bitwise_into_chunked(tmp_path, transport,
                                                       sampler):
    """Slab accumulators are round-atomic (commit at finalize), so trainer
    state after round r is identical dense vs chunked-at-C=U — a dense
    mid-schedule checkpoint restored into a chunked trainer continues
    bitwise."""
    straight = _run(_spec(None, transport, sampler))        # dense, 4 rounds

    half = build(_spec(None, transport, sampler))
    half.run(rounds=2)
    path = str(tmp_path / "dense2")
    half.trainer.save_state(path)

    cont = build(_spec(COHORT, transport, sampler))
    cont.trainer.restore_state(path)
    cont.trainer.run(4, resume=True)
    _assert_bitwise(cont.params, straight.params)
    _assert_bitwise(cont.trainer.engine.transport_state,
                    straight.trainer.engine.transport_state)
    assert straight.history.as_dict() == cont.history.as_dict()


def test_chunked_checkpoint_state_is_round_aligned(tmp_path):
    """What a chunked trainer persists is full-round state: the per-client
    EF tree keeps its (U, ...) leading dim (never a slab slice), and the
    saved checkpoint continues bitwise vs an uninterrupted chunked run."""
    spec = _spec(2, "int8", "fixed_cohort")
    straight = _run(spec)

    half = build(spec)
    half.run(rounds=2)
    ef_lead = jax.tree.leaves(half.trainer.engine.transport_state)[0].shape[0]
    assert ef_lead == COHORT             # U slots, not the slab's 2
    path = str(tmp_path / "chunk2")
    half.trainer.save_state(path)

    cont = build(spec)
    cont.trainer.restore_state(path)
    cont.trainer.run(4, resume=True)
    _assert_bitwise(cont.params, straight.params)
    _assert_bitwise(cont.trainer.engine.transport_state,
                    straight.trainer.engine.transport_state)


# ---------------------------------------------------------------------------
# prefetch: slab double-buffering must not change the stream
# ---------------------------------------------------------------------------

def test_chunked_prefetch_matches_sync():
    spec = _spec(3, "int8")
    pre = _run(spec)
    sync = _run(spec.with_overrides("fed.prefetch=false"))
    _assert_bitwise(pre.params, sync.params)
