"""The round engine's device scopes, host spans and counters
(``repro.core.obs``), on a profiler trace recorded on the CPU: a 2-round
streamed (``cohort_chunk=1``) int8 run and a dense one at rehearsal sizes."""
import os
import re
import sys

import jax
import pytest

from repro.api import ExperimentSpec, build
from repro.core import obs
from repro.kernels import fedavg_reduce

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip"))

from chipbench import scopes  # noqa: E402

MODULES = {"streamed": ("jit_slab", "jit_slabfin"), "dense": ("jit_bucket",)}
TAKEN = {"streamed": ("round.dispatch", "feed.wait", "slab.place",
                      "slab.call", "finalize.call", "round.absorb",
                      "loss.sync"),
         "dense": ("round.dispatch", "feed.wait", "bucket.call",
                   "round.absorb", "loss.sync")}


@pytest.fixture(scope="module", params=["streamed", "dense"])
def recorded(request, tmp_path_factory):
    path = request.param
    spec = ExperimentSpec().with_overrides(
        "model.arch=qwen1.5-0.5b", "model.reduced=true", "data.kind=lm",
        "data.clients=4", "data.samples_per_client=8", "data.seq_len=16",
        "fed.clients_per_round=2", "fed.k0=2", "fed.k_schedule=fixed",
        "fed.batch_size=2", "fed.bucket_rounds=1", "transport.name=int8",
        f"fed.cohort_chunk={1 if path == 'streamed' else 'null'}",
        "fed.rounds=2")
    # At published widths the int8 reduce is a Mosaic kernel, an op of its
    # own; interpreted here, a leaf that fits one block compiles to a few
    # elementwise ops the CPU compiler fuses into the server step. The
    # least block (one sublane tile per grid step) keeps the per-block
    # loop, as the chip keeps its kernel. Compiled afresh on both sides.
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fedavg_reduce, "VMEM_BUDGET", 0)
        jax.clear_caches()
        exp = build(spec)
        tr = exp.trainer
        counts = [(tr.feed_wait_s, tr.dispatch_s)]
        for rounds in (1, 2):       # the first compiles
            exp.run(rounds)
            jax.block_until_ready(tr.params)
            counts.append((tr.feed_wait_s, tr.dispatch_s))
        out = str(tmp_path_factory.mktemp(f"trace-{path}"))
        jax.profiler.start_trace(out)
        with jax.profiler.TraceAnnotation("test.window"):
            exp.run(2)
            jax.block_until_ready(tr.params)
        jax.profiler.stop_trace()
    jax.clear_caches()
    xplane = next(os.path.join(d, n) for d, _, fs in os.walk(out)
                  for n in fs if n.endswith(".xplane.pb"))
    texts = {t.splitlines()[0].split()[1].rstrip(","): t for t in
             (e.as_text() for e in tr.engine.registry.executables())}
    return {"path": path, "texts": texts, "counts": counts,
            "loaded": scopes.load(xplane)}


def test_the_benchmark_reads_the_names_the_program_writes():
    assert scopes.SCOPES == obs.SCOPES
    assert scopes.SPANS == obs.SPANS


def test_unknown_names_are_refused():
    with pytest.raises(ValueError):
        obs.scope("client.stepp")
    with pytest.raises(ValueError):
        obs.span("slab.cal")


def test_every_scope_is_in_the_executables_metadata(recorded):
    texts = recorded["texts"]
    assert sorted(texts) == sorted(MODULES[recorded["path"]])
    named = {scopes.scope_of(p) for t in texts.values()
             for p in re.findall(r'op_name="([^"]*)"', t)}
    assert set(obs.SCOPES) <= named


def test_every_op_of_the_round_resolves_to_a_scope(recorded):
    smap = scopes.scope_map(recorded["texts"].values())
    ops = [o for line in recorded["loaded"].ops.values() for o in line
           if o.module in MODULES[recorded["path"]]]
    assert ops
    got = {scopes.resolve(o, smap, recorded["loaded"].tf_ops) for o in ops}
    assert scopes.UNSCOPED not in got
    assert got == set(obs.SCOPES)


def test_the_spans_of_the_path_are_on_the_trainer_line(recorded):
    found = scopes.window_line(recorded["loaded"].host, "test.window")
    assert found is not None
    (lo, hi), line = found
    names = {n for n, s, e in line if lo <= s <= hi}
    assert set(TAKEN[recorded["path"]]) <= names
    assert not (set(obs.SPANS) - set(TAKEN[recorded["path"]])) & names


def test_host_counters_grow_with_the_rounds(recorded):
    (f0, d0), (f1, d1), (f2, d2) = recorded["counts"]
    assert f0 == d0 == 0.0
    assert 0.0 < d1 < d2 and 0.0 < f1 < f2
