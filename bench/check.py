"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests that finished, drawn from the seed and always holding the
longest, is run through the reference: one forward pass over each prompt
followed by its served tokens. Greedy serving should emit the reference's
best token at every position, up to rounding. For each served token the
number read is the gap by which the reference's logit of that token lies
below its best logit, in units of the standard deviation of the reference's
logits at that position. A configuration's ``check`` gives a limit to one or
more of: ``max_logit_gap``, the widest such gap over the sample;
``mean_logit_gap``, their mean; ``share_off_best``, the share of served
tokens that are not the reference's best. A served token that is out of the vocabulary, or a request served
more tokens than asked, makes the run incorrect by itself.
"""
from __future__ import annotations

import sys

import numpy as np


def sample(finished: list, *, seed: int, min_tokens: int, max_requests: int) -> list:
    """``finished``: (rid, prompt, served) of requests served in full. The
    longest first, then others in an order drawn from the seed, until the
    sample holds ``min_tokens`` served tokens or ``max_requests`` requests."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r[1]) + len(r[2]), -r[0]))
    rest = [r for r in finished if r is not longest]
    order = np.random.default_rng([seed, 0xC4EC]).permutation(len(rest))
    out = [longest]
    for i in order:
        if len(out) >= max_requests or sum(len(r[2]) for r in out) >= min_tokens:
            break
        out.append(rest[i])
    return out


def gaps(ref_logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """Per served token: (best - served token's logit) / std over the
    vocabulary, from the reference's logits at the positions that chose
    each token (ref_logits[j] chose served[j])."""
    ref = np.asarray(ref_logits, np.float64)
    got = ref[np.arange(len(served)), served]
    return (ref.max(axis=1) - got) / ref.std(axis=1)


def compare(sampled: list, reference, pad_len: int, max_served: int) -> dict:
    """Run ``reference(tokens (pad_len,), positions (max_served,)) ->
    (max_served, vocab) logits`` over each sampled request (fixed shapes, so
    the reference compiles once). Returns the numbers a configuration can
    compare: the widest gap, the mean gap, and the share of served tokens
    that are not the reference's best, with what they rest on."""
    every = []
    for rid, prompt, served in sampled:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        assert len(seq) <= pad_len and len(served) <= max_served
        tokens = np.zeros(pad_len, np.int32)
        tokens[: len(seq)] = seq
        positions = np.full(max_served, len(seq) - 1, np.int32)
        positions[: len(served)] = np.arange(len(prompt) - 1, len(seq))
        every.append(gaps(reference(tokens, positions)[: len(served)], served))
    g = np.concatenate(every) if every else np.zeros(0)
    got = {"max_logit_gap": float(g.max()) if g.size else 0.0,
           "mean_logit_gap": float(g.mean()) if g.size else 0.0,
           "share_off_best": float((g > 0).mean()) if g.size else 0.0,
           "requests": len(sampled), "tokens": int(g.size)}
    if g.size:
        print(f"check: gaps over {g.size} tokens: max {got['max_logit_gap']!r}, mean "
              f"{got['mean_logit_gap']!r}, p99 {float(np.percentile(g, 99))!r}, share "
              f"off the reference's best {got['share_off_best']!r}",
              file=sys.stderr, flush=True)
    return got
