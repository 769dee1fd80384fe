"""Training launcher.

Two modes:

  --execute     really train (reduced config on CPU, full config on a real
                pod) with the fault-tolerant Trainer: synthetic-bigram data,
                AdamW/Adafactor, async checkpoints, straggler detection,
                restart-with-replay.
  (default)     plan only: print the parallelism plan, parameter/optimizer
                footprint per device, and the analytical roofline for the
                chosen (arch × shape × mesh) — what a launch reviewer checks
                before burning pod-hours.

A third mode, ``--paper-lstm``, plans the paper's own LSTM workload on the
TPU kernel mapping: it reports the autotuned batch tile for the
sequence-resident Pallas kernel (``repro.kernels.lstm_seq``), checks it
against the jnp reference, and times it against the per-step scan path.

Examples:
  python -m repro.launch.train --arch granite-3-8b --shape train_4k
  python -m repro.launch.train --arch granite-3-8b --reduced --execute --steps 100
  python -m repro.launch.train --paper-lstm --batch 64
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import SHAPES, get_config, get_reduced_config, list_archs
from repro.core.cost_model import MeshPlan, bytes_per_device_estimate, estimate_step
from repro.data.pipeline import SyntheticLM
from repro.kernels.runtime import CACHE_DIR, enable_compile_cache
from repro.training.train_loop import Trainer, TrainerConfig


def plan(arch: str, shape_id: str, multi_pod: bool) -> None:
    cfg = get_config(arch)
    dp = 32 if multi_pod else 16
    p = MeshPlan(dp=dp, tp=16, fsdp=cfg.param_count() > 10e9)
    r = estimate_step(cfg, shape_id, p)
    print(f"arch={arch} shape={shape_id} chips={p.chips} (dp={p.dp} tp={p.tp} fsdp={p.fsdp})")
    print(f"params={cfg.param_count() / 1e9:.2f}B active={cfg.active_param_count() / 1e9:.2f}B "
          f"optimizer={cfg.optimizer}")
    print(f"resident/device ≈ {bytes_per_device_estimate(cfg, shape_id, p) / 1e9:.2f} GB")
    s = r.summary()
    print(f"roofline: compute={s['compute_s']:.3f}s memory={s['memory_s']:.3f}s "
          f"collective={s['collective_s']:.3f}s → T={s['t_step_s']:.3f}s "
          f"bottleneck={s['bottleneck']} mfu={s['mfu']:.3f}")
    print(f"energy/step ≈ {s['energy_j'] / 1e3:.1f} kJ → {s['gflops_per_j']:.0f} GFLOPs/J")


def plan_paper_lstm(batch: int, seq: int) -> None:
    """Kernel-level plan for the paper's flagship LSTM workload."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.fpga import paper_workload
    from repro.kernels.autotune import autotune, cache_key, predict_time_s
    from repro.kernels.runtime import backend_key, default_interpret
    from repro.models.lstm import lstm_apply, lstm_defs
    from repro.models.params import init_params

    lw = paper_workload()
    seq = seq or lw.seq
    problem = {"batch": batch, "seq": seq, "d_in": lw.d_in, "hidden": lw.hidden}
    cfg = autotune("lstm_seq", problem, dtype="float32")
    print(f"paper LSTM workload: batch={batch} seq={seq} d_in={lw.d_in} "
          f"hidden={lw.hidden} backend={backend_key()} "
          f"interpret={default_interpret()}")
    print(f"autotune[{cache_key('lstm_seq', problem, 'float32')}] → {cfg} "
          f"(predicted {predict_time_s('lstm_seq', problem, cfg) * 1e6:.1f} µs/call)")

    key = jax.random.PRNGKey(0)
    params = jax.tree.map(
        lambda t: t.astype(jnp.float32),
        init_params(lstm_defs(lw.d_in, lw.hidden), key),
    )
    x = jax.random.normal(key, (batch, seq, lw.d_in), jnp.float32)
    got = lstm_apply(params, x, fused="pallas_seq")
    want = lstm_apply(params, x, fused=True)
    err = float(jnp.max(jnp.abs(got - want)))
    print(f"sequence-resident kernel vs jnp reference: max |Δ| = {err:.2e}")
    assert np.isfinite(err) and err < 1e-4, err

    from repro.kernels.bench import compare_lstm_paths

    seq_us, step_us = compare_lstm_paths(batch, seq, lw.d_in, lw.hidden, n=15)
    print(f"median per-call: seq-resident {seq_us:.0f} µs vs per-step scan "
          f"{step_us:.0f} µs ({step_us / seq_us:.2f}x)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", default="train_4k", choices=[s for s in SHAPES])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--execute", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=None,
                    help="sequence length (default: 128, or the paper "
                         "workload's 28 under --paper-lstm)")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=str(CACHE_DIR / "train_ckpt"))
    ap.add_argument("--paper-lstm", action="store_true",
                    help="plan the paper LSTM workload on the TPU kernel mapping")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.paper_lstm:
        plan_paper_lstm(args.batch, args.seq or 0)
        return 0
    if args.arch is None:
        ap.error("--arch is required unless --paper-lstm is given")

    if not args.execute:
        plan(args.arch, args.shape, args.multi_pod)
        return 0

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    ds = SyntheticLM(
        vocab_size=cfg.vocab_size, seq_len=args.seq or 128, global_batch=args.batch
    )
    tc = TrainerConfig(
        num_steps=args.steps, accum=args.accum, checkpoint_dir=args.ckpt_dir,
        log_every=max(args.steps // 10, 1),
    )
    trainer = Trainer(cfg, ds, tc)
    stats = trainer.run()
    first, last = stats["metrics"][0], stats["metrics"][-1]
    print(f"steps={stats['final_step']} restarts={stats['restarts']} "
          f"loss {first['loss']:.3f} → {last['loss']:.3f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
