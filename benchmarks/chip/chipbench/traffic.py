"""The one traffic generator: a mix's JSON parameters in, client data out.

A mix file (``traffic/<mix>.json``) gives the round geometry (clients,
clients per round, local steps K, batch, sequence length, transport,
cohort chunk, backend, bucket length, client learning rate) and the data
generator with its parameters. The generator ``lm_tokens`` is a copy of
the program's ``data/synthetic.py`` ``make_lm_clients``, so that no change
to the program can change the traffic it is judged on: each client draws
token rows from one of ``num_styles`` Dirichlet(``alpha``) unigram styles
over the configuration's vocabulary.

Every array is drawn from ``numpy.random.default_rng(seed)``; the same seed
gives the same data, and every seed gives the same sizes.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

Clients = Tuple[List[np.ndarray], List[np.ndarray]]


def geometry(traffic: Dict[str, Any], rehearse: bool = False
             ) -> Dict[str, Any]:
    """The mix's parameters, with its ``rehearse`` block laid over them for
    the CPU rehearsal."""
    g = {k: v for k, v in traffic.items() if k != "rehearse"}
    if rehearse:
        g.update(traffic.get("rehearse", {}))
    return g


def samples_per_round(g: Dict[str, Any]) -> int:
    """Local-SGD examples one round processes over all its clients."""
    return int(g["clients_per_round"]) * int(g["k"]) * int(g["batch"])


def lm_tokens(rng: np.random.Generator, g: Dict[str, Any], vocab: int
              ) -> Clients:
    p = g["generator"]
    n, rows, seq = int(g["clients"]), int(g["samples_per_client"]), \
        int(g["seq"])
    styles = rng.dirichlet(np.full(vocab, float(p["alpha"])),
                           size=int(p["num_styles"]))
    clusters = rng.integers(0, int(p["num_styles"]), size=n)
    xs, ys = [], []
    for c in range(n):
        toks = rng.choice(vocab, size=(rows, seq + 1), p=styles[clusters[c]])
        xs.append(toks[:, :-1].astype(np.int32))
        ys.append(toks[:, 1:].astype(np.int32))
    return xs, ys


def generate(g: Dict[str, Any], model: Dict[str, Any], seed: int) -> Clients:
    """Client data ``(xs, ys)`` for mix geometry ``g`` and the configuration
    sizes ``model`` (``vocab_size``)."""
    rng = np.random.default_rng(seed)
    kind = g["generator"]["name"]
    if kind == "lm_tokens":
        return lm_tokens(rng, g, int(model["vocab_size"]))
    raise ValueError(f"unknown traffic generator {kind!r}")
