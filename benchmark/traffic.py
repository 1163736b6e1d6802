"""The one general traffic generator.  A mix is a data file of parameters
(``benchmark/traffic/<name>.json``); this reads it and makes the requests
or the training rows from ``--seed`` with numpy alone.

Every seed gets the SAME schedule: the quantile grid of the mix's
distributions at the run's length (prompt lengths, output budgets,
inter-arrival gaps), shuffled once by the mix's own ``schedule_seed``.
``--seed`` draws the token ids (and the weights), nothing else.  A seed
that also reordered the lengths moved ``tpot_p50_s`` by 10 % and the TTFTs
by 20 % at the same work (my chip runs, PR 25): which long prompt meets
which decode batch is the dynamics itself, not noise to average over.

Serving mix keys: ``mode`` (``backlog``: everything queued at t = 0;
``open_loop``: arrivals on a schedule whatever the server does),
``rate_per_s`` (open loop: Poisson arrivals; backlog: how many requests
per second of window are queued, enough to outlast it), ``prompt`` and
``output`` (``median``, ``sigma`` of a lognormal, ``min``, ``max``),
``max_total`` (prompt + output cap), ``schedule_seed``, ``shared_prefix`` (tokens shared by
all prompts; 0 = unshared), ``engine`` (the ServeConfig fields this mix
needs: buckets, chunk, page-table width).
Training mix keys: ``batch``, ``seq_len``.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_grid(n: int, spec: dict) -> np.ndarray:
    """n lengths: the quantile grid of a clipped lognormal."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf(float(u)) for u in _grid(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_grid(n: int, rate: float) -> np.ndarray:
    """n inter-arrival gaps: the quantile grid of Exp(rate)."""
    return -np.log1p(-_grid(n)) / rate


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def serving(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """[{rid, due_s, tokens, max_new_tokens}], sorted by due time.
    Open loop: only requests due inside the window are made."""
    n = max(1, math.ceil(mix["rate_per_s"] * seconds))
    prompts = lognormal_grid(n, mix["prompt"])
    outputs = lognormal_grid(n, mix["output"])
    order = mix.get("schedule_seed", 0)
    rng_for(order, 1).shuffle(prompts)
    rng_for(order, 2).shuffle(outputs)
    cap = mix.get("max_total")
    if cap:
        outputs = np.minimum(outputs, np.maximum(cap - prompts, 1))
    if mix["mode"] == "open_loop":
        gaps = exponential_grid(n, mix["rate_per_s"])
        rng_for(order, 3).shuffle(gaps)
        due = np.cumsum(gaps)
    elif mix["mode"] == "backlog":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown traffic mode {mix['mode']!r}")
    ids = rng_for(seed, 4)
    shared = ids.integers(0, vocab, size=int(mix.get("shared_prefix", 0)))
    out = []
    for i in range(n):
        if due[i] >= seconds:
            continue
        own = ids.integers(0, vocab, size=max(int(prompts[i]) - len(shared), 1))
        toks = np.concatenate([shared, own])[: int(prompts[i])]
        out.append({
            "rid": f"r{i:05d}", "due_s": float(due[i]),
            "tokens": [int(t) for t in toks],
            "max_new_tokens": int(outputs[i]),
        })
    out.sort(key=lambda r: (r["due_s"], r["rid"]))
    return out


def training_rows(mix: dict, seed: int, step: int, vocab: int) -> np.ndarray:
    """The batch of one step: [batch, seq_len] int32, every row and every
    step different."""
    rng = rng_for(seed, 1000 + step)
    return rng.integers(0, vocab, size=(mix["batch"], mix["seq_len"]),
                        dtype=np.int32)
