"""Smoke run of the serving stack on one TPU v5e, at published widths.

Run from the repository root, with no ``JAX_PLATFORMS`` and no interpret
override:

    python chip_smoke.py

Everything runs in this one process (a chip belongs to one process), in
this order. Every check raises on failure, so a failed phase exits non-zero
before the last line is printed:

  device    the default backend is a TPU whose device kind has chip
            constants (``core.energy.chip_for_device``), and Pallas kernels
            resolve to Mosaic, not the interpreter.
  kernels   every Pallas kernel with ``interpret=False`` against its
            ``kernels/ref.py`` oracle: the LSTM kernels at the paper's
            workload and at B=40, S=28, D=H=256; ``int8_matmul`` at one
            granite-3-8b projection; ``flash_attention`` at granite-3-8b's
            heads over 2048 positions.
  serve     granite-3-8b at every published width, depth cut 40 -> 16 to
            fit one chip, bf16 seeded random weights, built by the serving
            launcher's own ``build_server`` (InferenceEngine -> PagedSlotPool
            -> ContinuousBatchingScheduler) with chunked admission, serving
            a Poisson stream with 512-2048-token prompts.
  numerics  the same widths at 2 layers: prefill, then 8 cached decode
            steps, against one f32 forward at highest matmul precision,
            compared on logits.

The last line of stdout is ``{"ok": true, "device": {...}}``. This is a
smoke run, not a benchmark: its times include compilation and set-up.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs import get_config  # noqa: E402
from repro.core.energy import chip_for_device  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels import runtime  # noqa: E402
from repro.kernels.runtime import enable_compile_cache  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.int8_matmul import int8_matmul  # noqa: E402
from repro.kernels.lstm_quant import quantize_lstm_weights  # noqa: E402
from repro.kernels.lstm_seq import (  # noqa: E402
    lstm_seq_fused, lstm_seq_fused_quantized, lstm_stack_fused)
from repro.launch import serve  # noqa: E402
from repro.models.layers import unembed_apply  # noqa: E402
from repro.models.model import (  # noqa: E402
    decode_step, forward, init_model, prefill)
from repro.serving.load import poisson_stream  # noqa: E402
from repro.serving.slots import grow_cache  # noqa: E402

SEED = 0
ARCH = "granite-3-8b"
SERVE_LAYERS = 16    # of 40: 16 layers of bf16 weights (6.7 GiB) leave the
                     # chip room for the 4 GiB page pool and the step buffers
NUMERICS_LAYERS = 2
PAPER_LSTM = (64, 28, 6, 20)     # batch, seq, d_in, hidden (core/fpga.py)
WIDE_LSTM = (40, 28, 256, 256)   # the larger committed LSTM shape
GRANITE_PROJ = (256, 4096, 12800)        # m, k, n: an MLP up-projection
GRANITE_ATTN = (1, 32, 8, 2048, 128)     # batch, heads, kv heads, seq, dim

# Tolerances, each with its reason -------------------------------------------
# LSTM outputs lie in (-1, 1). The kernels set no matmul precision, so Mosaic
# may contract f32 operands in one bf16 MXU pass (8 significant bits); over
# 28 recurrent steps through contracting gates that stays at the 1e-2 level,
# while a wrong gate order or time index moves outputs by 0.1-1.
LSTM_ATOL = 5e-2
# int8 x int8 accumulates exactly in int32; only the two f32 scale
# multiplies round, in the same order as the oracle.
INT8_RTOL = 1e-6
# Both attention outputs are rounded to bf16 (spacing 2^-7 of the value);
# f32 online-softmax reassociation can move a value across one rounding
# boundary, so allow two spacings at the largest output.
FLASH_RTOL = 2.0 ** -6
# The served model runs bf16 weights, activations and KV cache (relative
# rounding 2^-9 per operation). With well-conditioned weights (see
# ``_d_model_fan_in``) that moves logits by about 1% of their largest value;
# a precision, layout or stale-cache fault moves them by their own scale.
LOGIT_RTOL = 5e-2


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {what}")


def _compile_seconds():
    """Running total of backend compile time in this process."""
    total = [0.0]

    def listen(event: str, duration_secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration_secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def device_phase() -> jax.Device:
    devs = jax.devices()
    d = devs[0]
    require(d.platform == "tpu", f"default backend is {d.platform!r}, not a TPU")
    chip = chip_for_device(d.device_kind)  # unknown kinds raise
    print(f"device: {d.device_kind} x{len(devs)} (chip constants {chip.name})",
          flush=True)
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    require(env in ("", "0", "false", "no", "off"),
            f"REPRO_PALLAS_INTERPRET={env!r} forces the Pallas interpreter")
    require(not runtime.default_interpret(),
            "Pallas kernels would run in the interpreter")
    return d


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _lstm_seq_ref(x, w, u, b):
    """Per-step ``ref.lstm_cell_ref`` scanned over time: hs (B, S, H)."""
    zeros = jnp.zeros((x.shape[0], u.shape[0]), jnp.float32)

    def step(carry, xt):
        h, c = ref.lstm_cell_ref(xt, *carry, w, u, b)
        return (h, c), h

    _, hs = jax.lax.scan(step, (zeros, zeros), x.swapaxes(0, 1))
    return hs.swapaxes(0, 1)


def _lstm_q8_ref(x, w, u, b):
    return ref.lstm_seq_q8_ref(x, *quantize_lstm_weights(w, u, b))[0]


def _lstm_layers(key, d_in: int, hidden: int, n: int):
    out = []
    for i, k in enumerate(jax.random.split(key, n)):
        kw, ku, kb = jax.random.split(k, 3)
        d = d_in if i == 0 else hidden
        out.append((
            jax.random.normal(kw, (d, 4 * hidden)) / np.sqrt(d),
            jax.random.normal(ku, (hidden, 4 * hidden)) / np.sqrt(hidden),
            0.1 * jax.random.normal(kb, (4 * hidden,))))
    return out


def _max_err(got, want) -> tuple[float, float]:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    require(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    require(bool(np.isfinite(got).all()), "non-finite kernel output")
    return float(np.max(np.abs(got - want))), float(np.max(np.abs(want)))


def _report(name: str, err: float, tol: float) -> None:
    print(f"kernel {name}: max|err| = {err!r} (tolerance {tol!r})", flush=True)
    require(err <= tol, f"{name} error {err} above {tol}")


def _oracle(fn, *args):
    """Run a jnp oracle at full f32 matmul precision (the kernels under test
    are traced outside this context, so it never reaches them)."""
    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(fn(*args))


def kernel_phase(*, lstm_shapes=(PAPER_LSTM, WIDE_LSTM),
                 matmul_shape=GRANITE_PROJ, attention_shape=GRANITE_ATTN,
                 interpret: bool = False) -> dict[str, float]:
    """Each Pallas kernel against its oracle; returns max errors by name."""
    errs = {}
    key = jax.random.PRNGKey(SEED)
    for bsz, seq, d_in, hidden in lstm_shapes:
        key, kx, kl = jax.random.split(key, 3)
        x = jax.random.normal(kx, (bsz, seq, d_in), jnp.float32)
        l1, l2 = _lstm_layers(kl, d_in, hidden, 2)
        tag = f"B={bsz} S={seq} D={d_in} H={hidden}"
        cases = {
            f"lstm_seq_fused {tag}": (
                lstm_seq_fused(x, *l1, interpret=interpret),
                _oracle(_lstm_seq_ref, x, *l1)),
            f"lstm_seq_fused_quantized {tag}": (
                lstm_seq_fused_quantized(x, quantize_lstm_weights(*l1),
                                         interpret=interpret),
                _oracle(_lstm_q8_ref, x, *l1)),
            f"lstm_stack_fused f32 {tag}": (
                lstm_stack_fused(x, [l1, l2], interpret=interpret),
                _oracle(lambda x: _lstm_seq_ref(_lstm_seq_ref(x, *l1), *l2), x)),
            f"lstm_stack_fused q8 {tag}": (
                lstm_stack_fused(x, [l1, l2], quantized=True,
                                 interpret=interpret),
                _oracle(lambda x: _lstm_q8_ref(_lstm_q8_ref(x, *l1), *l2), x)),
        }
        for name, (got, want) in cases.items():
            errs[name], _ = _max_err(got, want)
            _report(name, errs[name], LSTM_ATOL)

    m, k, n = matmul_shape
    key, ka, kb = jax.random.split(key, 3)
    xq, sx = ref.quantize_rowwise(jax.random.normal(ka, (m, k)))
    wq, sw = ref.quantize_colwise(jax.random.normal(kb, (k, n)))
    name = f"int8_matmul m={m} k={k} n={n}"
    errs[name], scale = _max_err(
        int8_matmul(xq, wq, sx, sw, block_m="auto", block_n="auto",
                    block_k="auto", interpret=interpret),
        _oracle(ref.int8_matmul_ref, xq, wq, sx, sw))
    _report(name, errs[name], INT8_RTOL * scale)

    b, h, kvh, s, d = attention_shape
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, s, d), jnp.bfloat16)
    kt = jax.random.normal(kk, (b, kvh, s, d), jnp.bfloat16)
    vt = jax.random.normal(kv, (b, kvh, s, d), jnp.bfloat16)
    name = f"flash_attention H={h} KV={kvh} S={s} D={d} causal"
    errs[name], scale = _max_err(
        flash_attention(q, kt, vt, causal=True, block_q="auto",
                        block_k="auto", interpret=interpret),
        _oracle(lambda *a: ref.flash_attention_ref(*a, causal=True),
                q, kt, vt))
    _report(name, errs[name], FLASH_RTOL * scale)
    return errs


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def serve_phase(cfg, *, batch: int = 16, max_len: int = 4096,
                prefill_chunk: int = 256, n_requests: int = 16,
                rate_hz: float = 4.0,
                prompt_lens: tuple[int, ...] = (512, 1024, 1536, 2048),
                new_tokens: tuple[int, int] = (64, 128),
                compile_seconds=None) -> dict:
    """Serve a Poisson stream through the launcher's construction; returns
    the counts the checks read and the phase's wall-clock seconds."""
    compile_seconds = compile_seconds or (lambda: 0.0)
    args = serve.build_parser().parse_args([
        "--arch", ARCH, "--mode", "chunked", "--paged",
        "--batch", str(batch), "--max-len", str(max_len),
        "--prefill-chunk", str(prefill_chunk), "--seed", str(SEED)])
    c0, t0 = compile_seconds(), time.perf_counter()
    engine, _, sched = serve.build_server(args, cfg)
    jax.block_until_ready(engine.params)
    t_setup = time.perf_counter() - t0
    reqs = poisson_stream(n_requests, rate_hz=rate_hz, seed=SEED,
                          vocab_size=cfg.vocab_size, prompt_lens=prompt_lens,
                          new_tokens=new_tokens)
    t1 = time.perf_counter()
    rep = sched.run(reqs)
    t_run = time.perf_counter() - t1
    out = {
        "setup_s": t_setup, "run_s": t_run,
        "compile_s": compile_seconds() - c0,
        "completed": rep.items, "requests": len(reqs),
        "quarantined": rep.quarantined, "failed": rep.failed,
        "shed": rep.shed, "chunks": rep.chunks,
        "peak_active": rep.peak_active,
        "committed": sched.pool.committed,
        "budget_committed": sum(r.new_tokens - 1 for r in reqs),
    }
    print(f"serve: {out['completed']}/{out['requests']} requests, "
          f"{rep.chunks} prefill chunks, peak {rep.peak_active} active slots, "
          f"quarantined={rep.quarantined} failed={rep.failed} shed={rep.shed}",
          flush=True)
    print(f"serve: wall seconds: set-up {t_setup!r} (includes compiles), "
          f"run {t_run!r} (includes compiles); backend compiles in the phase "
          f"{out['compile_s']!r}", flush=True)
    require(rep.items == len(reqs), "not every request completed")
    require(rep.quarantined == 0 and rep.failed == 0 and rep.shed == 0,
            "a finiteness guard fired or a request was dropped")
    for r in rep.records:
        require(len(r.tokens) == r.new_tokens,
                f"request {r.rid}: {len(r.tokens)} tokens for a budget of "
                f"{r.new_tokens}")
        require(all(0 <= t < cfg.vocab_size for t in r.tokens),
                f"request {r.rid}: token outside the vocabulary")
    require(out["committed"] == out["budget_committed"],
            f"pool committed {out['committed']} decode tokens, budgets "
            f"say {out['budget_committed']}")
    return out


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------
def _d_model_fan_in(params):
    """Rescale the dense blocks' attention projections from the per-head
    fan-in ``init_model`` draws them with (``params._initialize`` reads a
    3-D leaf's second-to-last axis) to the d_model fan-in. With the per-head
    fan-in, softmax scores have a std near 128 and the random network is
    chaotic: relative weight noise of 2^-9 moves its f32 logits by up to
    50%, so no bf16 comparison could be tight. After the rescale the same
    noise moves them by about 1%."""
    attn = dict(params["blocks"]["attn"])

    def scaled(t, ratio):
        return (t.astype(jnp.float32) * np.sqrt(ratio)).astype(t.dtype)

    for k in ("wq", "wk", "wv"):  # (L, d_model, heads, head_dim)
        attn[k] = scaled(attn[k], attn[k].shape[-2] / attn[k].shape[-3])
    attn["wo"] = scaled(attn["wo"], 1 / attn["wo"].shape[-3])  # (L, h, hd, d)
    return {**params, "blocks": {**params["blocks"], "attn": attn}}


def numerics_phase(cfg, *, prompt_len: int = 512, steps: int = 8) -> float:
    """Prefill + ``steps`` cached decode steps in the model's own dtype
    against one f32 forward at highest precision; returns the worst
    position's max|logit error| / max|reference logit|."""
    params = _d_model_fan_in(init_model(cfg, jax.random.PRNGKey(SEED)))
    toks = jnp.asarray(np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, prompt_len + steps), jnp.int32)[None]
    logits, cache = jax.jit(lambda p, t: prefill(p, t, cfg))(
        params, toks[:, :prompt_len])
    got = [logits]
    cache = grow_cache(cfg, cache, prompt_len + steps)
    step = jax.jit(lambda p, c, t, pos: decode_step(p, c, t, pos, cfg),
                   donate_argnums=(1,))
    for i in range(steps):
        pos = prompt_len + i
        logits, cache = step(params, cache, toks[:, pos : pos + 1],
                             jnp.int32(pos))
        got.append(logits)
    del cache

    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    p32 = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    del params
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: unembed_apply(
            p["embed"], forward(p, t, cfg32)[0], cfg32))(p32, toks)
    want = np.asarray(want[0, prompt_len - 1 :, : cfg.vocab_size], np.float32)
    got = np.stack([np.asarray(g[0, : cfg.vocab_size], np.float32) for g in got])
    require(bool(np.isfinite(got).all()), "non-finite served logits")
    rel = np.max(np.abs(got - want), axis=1) / np.max(np.abs(want), axis=1)
    rms = np.sqrt(np.mean((got - want) ** 2, axis=1) / np.mean(want ** 2, axis=1))
    worst = float(rel.max())
    print(f"numerics: prefill + {steps} decode steps vs f32 forward; per "
          f"position max|err|/max|ref| = {[float(r) for r in rel]!r} "
          f"(tolerance {LOGIT_RTOL!r}), rms(err)/rms(ref) = "
          f"{[float(r) for r in rms]!r}", flush=True)
    require(worst <= LOGIT_RTOL, f"logit error {worst} above {LOGIT_RTOL}")
    return worst


def main() -> int:
    enable_compile_cache()
    dev = device_phase()
    compile_seconds = _compile_seconds()
    kernel_phase()

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, num_layers=SERVE_LAYERS)
    print(f"serve: {ARCH} at published widths (d_model {cfg.d_model}, "
          f"{cfg.num_heads}/{cfg.num_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}); depth cut {full.num_layers} -> "
          f"{cfg.num_layers} layers", flush=True)
    serve_phase(cfg, compile_seconds=compile_seconds)
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"serve: peak_bytes_in_use {peak} ({peak / 2**30!r} GiB)", flush=True)
    gc.collect()  # the engine's jits refer back to it: free its weights now

    numerics_phase(dataclasses.replace(full, num_layers=NUMERICS_LAYERS))
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
