"""``correct`` for a serving cell: a sample of the requests the window
finished, the longest among them, against the plain reference (float32,
``highest``), once the window has closed and the pools are freed.

The reference runs once over each sampled prompt with its served tokens.
The number compared is ``logit_gap``: the widest gap, over every served
token of the sample, by which that token's reference logit lies below the
reference's best at its position (0 where the served token IS the
reference's choice).  It covers whatever produced the token: prefill or
chunked prefill, the paged decode kernel, a verify program, and the KV
writes under all of them, since a wrong cache row moves every later
logit.  Greedy tokens only; the mixes decode greedily.

The control (``benchmark/control.py``) reads the same number for the
token that the reference computed in fp8 would have put first.
"""

from __future__ import annotations


def sample(env, finished: list, n: int) -> list:
    """n finished requests drawn from the seed, the longest in it."""
    from benchmark import traffic

    if not finished:
        return []
    pool = sorted(finished, key=lambda r: r["rid"])
    longest = max(pool, key=lambda r: (len(r["tokens"]) + r["n"], r["rid"]))
    rest = [r for r in pool if r is not longest]
    rng = traffic.rng_for(env["seed"], 77)
    picks = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[int(i)] for i in sorted(picks)]


PADS = (768, 1024, 2048, 3072, 4224, 8192)


def pad_for(n_tokens: int) -> int:
    """The padded length the reference runs a sequence at: few sizes, so
    that a checkout's second run finds every reference program compiled."""
    return next((p for p in PADS if p >= n_tokens), n_tokens)


def reference_logits(fwd_by_pad: dict, family, c, quant, w, r):
    """float32 [n_served, vocab]: the logits that predict each served
    token of request ``r``, given the prompt and the served tokens before."""
    from benchmark.reference import decoder

    seq = list(r["tokens"]) + list(r["tokens_out"][:-1])
    pad = min(pad_for(len(seq)), max(c["max_seq_len"], len(seq)))
    key = (pad, quant)
    if key not in fwd_by_pad:
        fwd_by_pad[key] = decoder.Forward(family, c, decoder.QUANT[quant], pad)
    return fwd_by_pad[key].logits(w, seq, len(r["tokens"]) - 1,
                                  len(r["tokens_out"]))


def gaps(ref_logits, tokens):
    """Per position: reference's best logit minus the reference's logit of
    ``tokens[i]``."""
    import numpy as np

    tokens = np.asarray(tokens)
    return ref_logits.max(-1) - ref_logits[np.arange(len(tokens)), tokens]


def check(env, c, w, finished: list) -> dict:
    from benchmark import harness

    limits = harness.load_json(
        env["root"], f"benchmark/limits/{env['cell']['name']}.json")
    family = env["cfg"]["family"]
    picked = sample(env, finished, limits["sample_requests"])
    worst, n_tokens, cache, control, flips = 0.0, 0, {}, 0.0, 0
    for r in picked:
        ref = reference_logits(cache, family, c, None, w, r)
        g = gaps(ref, r["tokens_out"])
        worst = max(worst, float(g.max()))
        flips += int((g > 0).sum())
        n_tokens += len(r["tokens_out"])
        if env.get("control"):
            low = reference_logits(cache, family, c, env["control"], w, r)
            control = max(control, float(gaps(ref, low.argmax(-1)).max()))
    env["extra_notes"]["check"] = {
        "sampled": [r["rid"] for r in picked], "tokens": n_tokens,
        "served_tokens_not_the_reference_choice": flips}
    if env.get("control"):
        env["extra_notes"]["control"] = {
            "precision": env["control"], "logit_gap": control}
    lim = limits["rehearsal" if env["rehearse"] else "limits"]["logit_gap"]
    enough = n_tokens > 0
    return {
        "logit_gap": {"value": worst, "limit": lim, "ok": worst <= lim},
        "tokens_compared": {"value": n_tokens, "limit": 1, "ok": enough},
    }
