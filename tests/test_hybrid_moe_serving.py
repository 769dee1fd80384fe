"""granite-4.0-h-small's family (``hybrid_moe``: Mamba-2 and NoPE attention
layers, each followed by routed and shared experts) through the normal
serving path at its reduced size, against the plain f32 reference
(``models/reference.py``) on seeded random weights.

The path is the launcher's (``launch.serve.build_server``): the engine over
a paged pool (Mamba-2 state rows unpaged, the attention layer's K/V paged),
chunked prefill, the paged decode step, and a speculative verify whose
rejected drafts roll the Mamba-2 state back. Weights and the serving
computation are f32 and the layer holds an expert share (3 of 8 experts,
from expert 2), so the program and the reference differ by float
reassociation only: logits within 2e-5 of the reference's (their scale is
about 0.03, after the logit scaling of 16), and every served token's
reference logit within 2e-5 of the reference's best.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.launch import serve
from repro.models.model import forward, init_model
from repro.models.reference import hybrid_moe_logits
from repro.serving.kv_cache import has_recurrent_state, paged_keys
from repro.serving.load import Request
from repro.serving.pages import PagedSlotPool

ARCH = "granite-4.0-h-small"
ATOL = 2e-5


def _cfg():
    cfg = get_reduced_config(ARCH)
    return dataclasses.replace(cfg, dtype=jnp.float32, moe=dataclasses.replace(
        cfg.moe, experts_held=3, first_expert=2))


def _server(cfg, batch=3, max_len=64, chunk=8):
    args = serve.build_parser().parse_args([
        "--arch", ARCH, "--mode", "chunked", "--paged", "--batch", str(batch),
        "--max-len", str(max_len), "--page-size", "8", "--prefill-chunk", str(chunk)])
    engine, _, sched = serve.build_server(args, cfg)
    engine.params = jax.tree.map(lambda t: t.astype(jnp.float32),
                                 init_model(cfg, jax.random.PRNGKey(7)))
    return engine, sched


def _reference(engine, seq):
    with jax.default_matmul_precision("highest"):
        return np.asarray(hybrid_moe_logits(engine.params, np.asarray(seq), engine.cfg))


def _assert_best(ref, served):
    """Each served token's reference logit is the reference's best, up to
    ``ATOL`` (a near-tie may fall either way under reassociation)."""
    got = ref[np.arange(len(served)), served]
    np.testing.assert_array_less(ref.max(-1) - got, ATOL)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


def _prefill(engine, pool, slot, prompt, budget, chunk=8):
    """Chunked prefill of one request into ``slot``; the last chunk's logits."""
    seen = []
    step = engine._chunk

    def keep(*a):
        out = step(*a)
        seen.append(out[0])
        return out

    engine._chunk = keep
    try:
        st = engine.begin_chunked_prefill(pool, [slot], prompt[None], rids=[slot],
                                          budgets=[budget])
        while not st.done:
            engine.chunked_prefill_step(st, chunk)
        first = engine.finish_chunked_prefill(pool, st)
    finally:
        engine._chunk = step
    return int(first[0]), np.asarray(seen[-1][0, : engine.cfg.vocab_size])


def test_the_family_has_state_rows_and_paged_attention():
    cfg = _cfg()
    assert has_recurrent_state(cfg) and paged_keys(cfg) == ("k", "v")
    engine, sched = _server(cfg)
    pool = sched.pool
    assert isinstance(pool, PagedSlotPool) and not pool.share_prefix
    n_attn = len(cfg.attn_layers)
    assert pool.cache["state"].shape[:2] == (cfg.num_layers - n_attn, 3)
    assert pool.cache["k"].shape[:2] == (n_attn, pool.num_pages)


def test_attention_rejects_a_position_embedding():
    """The family's attention is NoPE: a config asking for RoPE is refused,
    not half served."""
    cfg = dataclasses.replace(_cfg(), rope=True)
    params = init_model(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="no position embedding"):
        jax.eval_shape(lambda p: forward(p, jnp.zeros((1, 8), jnp.int32), cfg), params)


def test_chunked_prefill_and_paged_decode_match_the_reference():
    engine, sched = _server(_cfg())
    pool = sched.pool
    prompts = {0: _prompt(20, 1), 1: _prompt(13, 2)}   # 13: a last chunk of 5
    served = {}
    for slot, p in prompts.items():
        first, logits = _prefill(engine, pool, slot, p, budget=10)
        ref = _reference(engine, p)
        np.testing.assert_allclose(logits, ref[-1], atol=ATOL)
        served[slot] = [first]
    for _ in range(9):
        nxt, fin = engine.masked_decode_step(pool)
        assert fin[[0, 1]].all()
        for slot in prompts:
            served[slot].append(int(nxt[slot]))
            pool.advance(slot, 1, int(nxt[slot]))
    for slot, p in prompts.items():
        seq = np.concatenate([p, served[slot]])
        ref = _reference(engine, seq[:-1])[len(p) - 1:]
        _assert_best(ref, served[slot])


def test_verify_rollback_restores_the_mamba_state():
    """Drafts that are the reference's next tokens and then wrong: the verify
    commits the accepted prefix, rolls the Mamba-2 state back to it, and the
    next tokens are the reference's again."""
    engine, sched = _server(_cfg())
    pool = sched.pool
    p = _prompt(16, 3)
    first, _ = _prefill(engine, pool, 0, p, budget=12)
    served = [first]
    for _ in range(2):                          # the chain the reference gives
        seq = np.concatenate([p, served])
        served.append(int(_reference(engine, seq)[-1].argmax()))
    drafts = np.zeros((pool.max_batch, 3), np.int32)
    drafts[0] = [served[1], served[2], (served[2] + 1) % 512]   # 2 right, 1 wrong
    toks, acc, fin = engine.masked_speculative_step(pool, drafts)
    assert fin[0] and int(acc[0]) == 2
    assert toks[0, :2].tolist() == served[1:3]
    served.append(int(toks[0, 2]))
    pool.advance(0, 3, int(toks[0, 2]))
    for _ in range(4):
        nxt, _ = engine.masked_decode_step(pool)
        served.append(int(nxt[0]))
        pool.advance(0, 1, int(nxt[0]))
    seq = np.concatenate([p, served])
    _assert_best(_reference(engine, seq[:-1])[len(p) - 1:], served)


def test_the_scheduler_serves_the_reference_tokens():
    """ContinuousBatchingScheduler with chunked admission over the paged
    pool: every request's tokens are the reference's greedy continuation."""
    engine, sched = _server(_cfg(), batch=2, max_len=48)
    reqs = [Request(i, 0.0, _prompt(n, 10 + i), k)
            for i, (n, k) in enumerate([(12, 6), (12, 4), (21, 5), (9, 7)])]
    report = sched.run(reqs)
    assert len(report.records) == len(reqs)
    for rec, r in zip(sorted(report.records, key=lambda x: x.rid), reqs):
        assert len(rec.tokens) == r.new_tokens
        seq = np.concatenate([r.prompt, rec.tokens])
        _assert_best(_reference(engine, seq[:-1])[len(r.prompt) - 1:], rec.tokens)
