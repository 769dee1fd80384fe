"""The one traffic generator: every mix is a data file under ``bench/traffic``.

A mix file names its arrival process and its length distributions; this
module turns it into requests for one seed. Every seed gets the same
*amount* of work: the lengths are a fixed multiset (quantiles of the stated
distributions, laid out in blocks that each hold the whole distribution)
and the arrival gaps are a fixed multiset too; the seed only chooses the
order and the token ids. So two seeds differ in arrangement, not in load.

Keys of a mix file:

  arrivals        "backlog" (every request due at 0), "poisson" or "mmpp"
  requests        backlog only: how many requests are queued at the start
  rate_hz         poisson/mmpp: mean arrival rate; the window of ``seconds``
                  gets round(rate_hz * seconds) requests, the first due at 0
  burst_rate_factor, burst_mean_requests, quiet_mean_requests
                  mmpp only: bursts arrive at factor x rate_hz over runs of
                  geometric length with the stated mean; quiets arrive at the
                  rate that brings the long-run mean back to rate_hz
  prompt_tokens   {"values": [...], "weights": [...]}: a bucketed length mix
  output_tokens   {"median", "sigma", "min", "max"}: log-normal, clipped
  block           requests per block; each block holds every length quantile
  measure_from    "start": the window opens at the first arrival; "pool_full"
                  (a backlog): it opens once every decode slot is busy
  drain_first_tokens_s
                  seconds past the window's close that the run waits for the
                  first token of every request due in the window (0: stop at
                  the close)
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
_LAYOUT_SEED = 0x5EED  # the seed of the fixed length/gap multisets


@dataclasses.dataclass(frozen=True)
class Item:
    rid: int
    due_s: float          # seconds after the window opens
    prompt: np.ndarray    # (prompt_len,) int32
    new_tokens: int       # tokens to emit, the first one included


def load_mix(name: str, base: Path = TRAFFIC_DIR) -> dict:
    return json.loads((base / f"{name}.json").read_text())


def _quantiles_lognormal(n: int, spec: dict) -> np.ndarray:
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.round(v), spec["min"], spec["max"]).astype(np.int64)


def _quantiles_buckets(n: int, spec: dict) -> np.ndarray:
    vals = np.asarray(spec["values"], np.int64)
    w = np.asarray(spec["weights"], np.float64)
    cdf = np.cumsum(w / w.sum())
    q = (np.arange(n) + 0.5) / n
    return vals[np.minimum(np.searchsorted(cdf, q), len(vals) - 1)]


def lengths(mix: dict, n: int, rng: np.random.Generator):
    """(prompt_lens, output_lens) for n requests: every block of ``block``
    requests (and the shorter last one) holds the quantile multiset of its
    size, shuffled by ``rng``."""
    block = mix["block"]
    prompts, outs = [], []
    for start in range(0, n, block):
        size = min(block, n - start)
        prompts.append(rng.permutation(_quantiles_buckets(size, mix["prompt_tokens"])))
        outs.append(rng.permutation(_quantiles_lognormal(size, mix["output_tokens"])))
    return np.concatenate(prompts), np.concatenate(outs)


def _exp_quantiles(n: int) -> np.ndarray:
    return -np.log1p(-(np.arange(n) + 0.5) / n)  # unit-mean exponential


def arrival_times(mix: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds), first at 0, for the mix's process."""
    kind = mix["arrivals"]
    if kind == "backlog":
        return np.zeros(mix["requests"])
    rate = float(mix["rate_hz"])
    n = max(1, round(rate * seconds))
    if kind == "poisson":
        gaps = rng.permutation(_exp_quantiles(n)) / rate
    elif kind == "mmpp":
        f = mix["burst_rate_factor"]
        mb, mq = mix["burst_mean_requests"], mix["quiet_mean_requests"]
        # mean gap over a burst run plus a quiet run must be 1/rate
        quiet_rate = mq / ((mb + mq) / rate - mb / (f * rate))
        # the run layout is fixed (drawn from a fixed seed); the run seed
        # permutes burst runs among themselves and quiet runs among
        # themselves, and the gaps within each state
        lay = np.random.default_rng(_LAYOUT_SEED)
        runs_b, runs_q, total = [], [], 0
        while total < n:
            runs_b.append(int(lay.geometric(1 / mb)))
            runs_q.append(int(lay.geometric(1 / mq)))
            total += runs_b[-1] + runs_q[-1]
        runs_b = rng.permutation(runs_b)
        runs_q = rng.permutation(runs_q)
        state = np.concatenate([np.repeat([True, False], [b, q])
                                for b, q in zip(runs_b, runs_q)])[:n]
        nb = int(state.sum())
        gaps = np.empty(n)
        gaps[state] = rng.permutation(_exp_quantiles(nb)) / (f * rate)
        gaps[~state] = rng.permutation(_exp_quantiles(n - nb)) / quiet_rate
        gaps *= (n / rate) / gaps.sum()  # the window's mean rate exactly
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due[due < seconds]


def generate(mix: dict, *, seed: int, seconds: float, vocab_size: int) -> list[Item]:
    """The requests of one run: the same seed gives the same requests."""
    rng = np.random.default_rng([seed, 0x7A11])
    due = arrival_times(mix, seconds, rng)
    p_lens, o_lens = lengths(mix, len(due), rng)
    tok = np.random.default_rng([seed, 0x70C5])
    return [Item(i, float(due[i]),
                 tok.integers(0, vocab_size, int(p_lens[i])).astype(np.int32),
                 int(o_lens[i]))
            for i in range(len(due))]


def prefill_groups(items: list[Item], largest: int) -> list[tuple[int, int]]:
    """Every (group size, prompt length) that chunked admission can form from
    ``items``: the scheduler admits a FIFO run of waiting requests of one
    prompt length as one group, at most as many as there are free slots, so
    a group is part of a run of equal lengths in arrival order. Sizes stop at
    ``largest``."""
    run: dict[int, int] = {}
    prev, n = None, 0
    for it in items:
        n = n + 1 if len(it.prompt) == prev else 1
        prev = len(it.prompt)
        run[prev] = max(run.get(prev, 0), n)
    return [(k, length) for length in sorted(run)
            for k in range(1, min(run[length], largest) + 1)]
