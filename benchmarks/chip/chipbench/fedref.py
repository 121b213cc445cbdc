"""The plain reference of a federated round, around a configuration's
reference model (``configs/<config>.ref.py``).

One round: every client of the cohort runs K steps of plain SGD from the
round's global parameters on its own batches; the server then takes the
size-weighted mean of the clients' parameters, or, with the ``int8``
uplink, adds the weighted sum of the clients' int8-coded deltas (one scale
per parameter leaf, max|x|/127, round to nearest) and keeps the coding
error as an error-feedback residual that the next round's deltas carry.
The server step is FedAvg with server learning rate 1.

Nothing here imports the program. ``dtype`` and ``precision`` make the
same code the control: ``bfloat16`` weights and compute, where the
reference proper is float32 at matmul precision ``highest``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any
#: one client's round input: (x (K, b, ...), y (K, b, ...), weight)
Client = Tuple[np.ndarray, np.ndarray, float]


def leaf_paths(tree: PyTree) -> List[str]:
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def make_norms(to_program: Callable) -> Callable:
    """jit(p, p0) -> per-leaf L2 norms of ``p - p0`` in the program's
    layout, as one float32 vector in ``leaf_paths`` order."""
    @jax.jit
    def norms(p, p0):
        d = jax.tree.leaves(to_program(p))
        d0 = jax.tree.leaves(to_program(p0))
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))) for a, b in
            zip(d, d0)])
    return norms


def _quantize(x):
    flat = x.reshape(-1)
    s = jnp.maximum(jnp.max(jnp.abs(flat)) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(flat / s), -127, 127)
    return (q * s).reshape(x.shape)


class RoundReference:
    """Runs rounds of the reference; see the module docstring."""

    def __init__(self, ref, model: Dict[str, Any], *, codec: str,
                 eta: float, dtype=jnp.float32, precision: str = "highest",
                 half_batch: bool = False):
        self.ref, self.model, self.codec = ref, model, codec
        self.eta = float(eta)
        self.dtype, self.precision = dtype, precision
        loss = ref.loss

        def client(p, xs, ys):
            def step(p, xy):
                x, y = xy
                if half_batch:          # a planted fault: half the rows
                    x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
                l, g = jax.value_and_grad(loss)(p, x, y, model)
                p = jax.tree.map(lambda w, gw: (w - self.eta * gw
                                                ).astype(w.dtype), p, g)
                return p, l
            p, ls = jax.lax.scan(step, p, (xs, ys))
            return p, ls[0]

        def fold_int8(hat, true, pc, p, res, w):
            d = jax.tree.map(lambda a, b, r: a.astype(jnp.float32)
                             - b.astype(jnp.float32) + r,
                             ref.to_program(pc), ref.to_program(p), res)
            hat = jax.tree.map(lambda h, x: h + w * _quantize(x), hat, d)
            true = jax.tree.map(lambda t, x: t + w * x, true, d)
            return hat, true

        def finish_int8(p, hat, true):
            new = jax.tree.map(lambda a, h: (a.astype(jnp.float32) + h
                                             ).astype(a.dtype),
                               ref.to_program(p), hat)
            return new, jax.tree.map(jnp.subtract, true, hat)

        def fold_mean(acc, pc, w):
            return jax.tree.map(lambda a, x: a + w * x.astype(jnp.float32),
                                acc, ref.to_program(pc))

        self._client = jax.jit(client)
        self._fold_int8 = jax.jit(fold_int8, donate_argnums=(0, 1))
        self._finish_int8 = jax.jit(finish_int8, donate_argnums=(1, 2))
        self._fold_mean = jax.jit(fold_mean, donate_argnums=(0,))
        self.norms = make_norms(ref.to_program)

    def _zeros(self, p):
        return jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                            self.ref.to_program(p))

    def _reorder(self, prog_leaves, like):
        """Program-layout leaves back into the reference's leaf order."""
        ids = jax.tree.unflatten(jax.tree.structure(like),
                                 list(range(len(jax.tree.leaves(like)))))
        where = jax.tree.leaves(self.ref.to_program(ids))
        out = [None] * len(where)
        for pos, i in enumerate(where):
            out[i] = prog_leaves[pos]
        return out

    def run(self, make_p0: Callable[[], PyTree],
            rounds: Sequence[Sequence[Client]],
            snapshot_after: Sequence[int]
            ) -> Tuple[List[float], Dict[int, np.ndarray]]:
        """Run ``rounds`` from the weights ``make_p0()`` makes (reference
        layout, float32; made again for each snapshot rather than held, to
        keep a copy of the model off the chip). Returns each round's mean
        first-step client loss and, after each round number in
        ``snapshot_after``, the per-leaf norms of the change from them."""
        with jax.default_matmul_precision(self.precision):
            p = jax.tree.map(lambda a: a.astype(self.dtype), make_p0())
            res = self._zeros(p) if self.codec == "int8" else None
            losses, snaps = [], {}
            for r, clients in enumerate(rounds, start=1):
                firsts = []
                hat = self._zeros(p)
                true = self._zeros(p) if self.codec == "int8" else None
                for xs, ys, w in clients:
                    pc, first = self._client(p, jnp.asarray(xs),
                                             jnp.asarray(ys))
                    firsts.append(first)
                    if self.codec == "int8":
                        hat, true = self._fold_int8(hat, true, pc, p, res,
                                                    jnp.float32(w))
                    else:
                        hat = self._fold_mean(hat, pc, jnp.float32(w))
                    del pc
                if self.codec == "int8":
                    new, res = self._finish_int8(p, hat, true)
                else:
                    new = jax.tree.map(lambda a: a, hat)
                del hat, true
                p = jax.tree.unflatten(
                    jax.tree.structure(p),
                    [a.astype(self.dtype) for a in
                     self._reorder(jax.tree.leaves(new), p)])
                del new
                losses.append(float(np.mean([float(f) for f in firsts])))
                if r in snapshot_after:
                    p0 = make_p0()
                    snaps[r] = np.asarray(self.norms(p, p0))
                    del p0
            return losses, snaps
