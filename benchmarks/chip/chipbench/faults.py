"""Faults planted in the program under the harness, for the tests that show
``correct`` comes out false: each is a context manager that patches the
program while a run is built and driven, and restores it after.

- ``unchanged``: the round returns its input parameters.
- ``half_batch``: the loss reads half of each batch (the mean over the
  rest).
- ``token_altered``: one token of each fed batch is changed where the
  batch is built.
- ``answer_altered``: the largest parameter leaf moves twice its update.
- ``exchange_dropped``: on a mesh, the all-reduce that sums the chips'
  partial int8 reduces (``psum_tiers`` in ``kernels/delta_codec.py``) is
  left out, so each chip keeps the sum of its own clients alone.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Tuple


@contextlib.contextmanager
def _patched(obj, name, value) -> Iterator[None]:
    orig = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, orig)


def _wrap_rounds(edit):
    """Patch both round entry points of the engine so that ``edit(old,
    new)`` decides the committed parameters."""
    from repro.core.engine.round import RoundEngine
    stack = contextlib.ExitStack()
    for meth in ("run_round_chunked", "run_bucket"):
        orig = getattr(RoundEngine, meth)

        def wrapped(self, params, *a, _orig=orig, **kw):
            out = _orig(self, params, *a, **kw)
            return (edit(params, out[0]),) + tuple(out[1:])
        stack.enter_context(_patched(RoundEngine, meth, wrapped))
    return stack


def unchanged():
    return _wrap_rounds(lambda old, new: old)


def answer_altered():
    import jax

    def edit(old, new):
        lo, ln = jax.tree.leaves(old), jax.tree.leaves(new)
        i = max(range(len(ln)), key=lambda j: ln[j].size)
        ln = list(ln)
        ln[i] = ln[i] + (ln[i] - jax.numpy.asarray(lo[i]))
        return jax.tree.unflatten(jax.tree.structure(new), ln)
    return _wrap_rounds(edit)


@contextlib.contextmanager
def half_batch() -> Iterator[None]:
    from repro.models import registry
    orig = registry.loss_fn

    def loss_fn(*a, **kw):
        f = orig(*a, **kw)
        return lambda p, b: f(p, {k: v[: v.shape[0] // 2]
                                  for k, v in b.items()})
    with _patched(registry, "loss_fn", loss_fn):
        yield


def _alter(batches):
    x = batches["x"].reshape(-1)
    x[0] = 1 if x[0] != 1 else 2


@contextlib.contextmanager
def token_altered() -> Iterator[None]:
    from repro.data import pipeline
    orig_slabs, orig_bucket = pipeline.round_slabs, pipeline.bucket_batches

    def round_slabs(*a, **kw):
        for sb in orig_slabs(*a, **kw):
            _alter(sb.batches)
            yield sb

    def bucket_batches(*a, **kw):
        bb = orig_bucket(*a, **kw)
        _alter(bb.batches)
        return bb
    with _patched(pipeline, "round_slabs", round_slabs), \
            _patched(pipeline, "bucket_batches", bucket_batches):
        yield


@contextlib.contextmanager
def exchange_dropped() -> Iterator[None]:
    from repro.kernels import delta_codec
    with _patched(delta_codec, "psum_tiers",
                  lambda x, axes, reduce_tiers=None: x):
        yield


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "token_altered": token_altered, "answer_altered": answer_altered,
          "exchange_dropped": exchange_dropped}


def faults_for(chips: int) -> Tuple[str, ...]:
    """The faults a cell on ``chips`` chips can have: those of every cell
    and, across chips, the dropped exchange."""
    every = ("unchanged", "half_batch", "token_altered", "answer_altered")
    return every + (("exchange_dropped",) if chips > 1 else ())
