"""A run whose timed path is broken underneath comes out not correct: the
harness runs as on the chip (less its look for one), with each fault a
served model can have planted in the program's decode step. One chip, so
no exchange between chips can be left out."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness


class TokenFault:
    """A served token altered where it is produced: each request's third
    token comes out of the decode step one id higher than computed."""

    def on_first(self, first, rids, rec):
        return first

    def on_decode(self, nxt, slots, rids, rec):
        nxt = nxt.copy()
        for s, rid in zip(slots, rids):
            if len(rec.tokens[rid]) == 2:
                nxt[s] = (nxt[s] + 1) % rec.vocab
        return nxt


def token(engine):
    return TokenFault()


def state(engine):
    """The decode step computes its tokens but returns the cache unchanged."""
    orig = engine._paged_decode

    def stale(params, cache, *a):
        out, _ = orig(params, jax.tree.map(jnp.copy, cache), *a)
        return out, cache
    engine._paged_decode = stale


def half(engine):
    """Every other decoding slot is left out of the step: its cache rows stay
    as they were and it gets its input token back."""
    from repro.serving.kv_cache import paged_keys

    orig = engine._paged_decode
    pkeys = set(paged_keys(engine.cfg))

    def step(params, cache, tok, pos, active, table):
        keep = active & (jnp.cumsum(active) % 2 == 1)
        old = jax.tree.map(jnp.copy, cache)
        (nxt, fin), new = orig(params, cache, tok, pos, keep, table)
        for k in new:
            if k not in pkeys:
                m = keep.reshape((1, -1) + (1,) * (new[k].ndim - 2))
                new[k] = jnp.where(m, new[k], old[k])
        return (jnp.where(keep, nxt, tok), fin), new
    engine._paged_decode = step


@pytest.mark.parametrize("fault", [token, state, half], ids=["token", "state", "half"])
@pytest.mark.parametrize("cell", ["granite8b-decode", "mamba2-chat"])
def test_planted_fault_is_not_correct(bench_root, cell, fault):
    out = harness.run(cell, 11, 1.5, False, t_process=time.perf_counter(),
                      size="cpu_test", require_tpu=False, fault=fault,
                      root=bench_root, log=lambda s: None)
    assert out["correct"] is False
    gap = out["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
