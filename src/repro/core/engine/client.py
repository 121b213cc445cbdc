"""ClientUpdate — the K-step local-SGD scan (Algorithm 1, lines 5-9).

This is the single source of truth for a client's local update; both the
single-process engine (`engine.round`) and the mesh-level strategies
(`distributed.strategies`) build on it, so the paper's local-SGD semantics
live in exactly one place (DESIGN.md §6.1).

The update is stateless plain SGD per the paper: clients carry no optimizer
state between rounds (the server may — see `engine.server`).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import obs

PyTree = Any
LossFn = Callable[[PyTree, Dict[str, jnp.ndarray]], Any]


class ClientResult(NamedTuple):
    """One client's round output."""
    params: PyTree          # x_{r,K}^c — params after K local steps
    first_loss: jnp.ndarray  # f_c(x_r, xi_{c,0}) — Eq. 15 feedback signal
    last_loss: jnp.ndarray   # f_c(x_{r,K-1}, xi_{c,K-1})


def client_update(loss_fn: LossFn, params: PyTree,
                  client_batches: Dict[str, jnp.ndarray],
                  eta: jnp.ndarray,
                  reconstruct: Any = None) -> ClientResult:
    """K steps of SGD from the round-start params.

    Leaves of ``client_batches`` have leading K axis; ``eta`` is a scalar.
    Updates are cast back to each weight's dtype so mixed-precision params
    stay in their storage dtype across the scan carry.

    ``reconstruct``: optional callable applied to ``params`` before the
    first step — the downlink lazy decode (DESIGN.md §10): ``params`` is
    then the (ref, payload) broadcast bundle and the client reconstructs
    its own round-start model inside its own trace, so the engine never
    materialises the decoded f32 tree as a separate round input.
    """
    def step(p, batch):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
        p = jax.tree.map(lambda w, g: (w - eta * g).astype(w.dtype), p, grads)
        return p, loss

    with obs.scope("client.step"):
        if reconstruct is not None:
            params = reconstruct(params)
        final, losses = jax.lax.scan(step, params, client_batches)
        return ClientResult(final, losses[0], losses[-1])


def make_client_update(loss_fn: LossFn, reconstruct: Any = None):
    """Bind ``loss_fn`` (and the optional downlink ``reconstruct`` hook):
    returns update(params, batches, eta) -> ClientResult."""
    def update(params, client_batches, eta):
        return client_update(loss_fn, params, client_batches, eta,
                             reconstruct)

    return update
