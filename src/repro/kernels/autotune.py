"""Shape-keyed, cost-model-driven block-size autotuner for the Pallas kernels.

The paper's Generator picks hardware design points by pruning a candidate
space with *analytical models* first and only then evaluating survivors
(§2.2/§2.3).  This module is the same methodology applied to kernel launch
geometry: instead of hard-coded ``block_*`` defaults, each kernel exposes
``block_* = "auto"`` and routes here, where we

  1. enumerate legal block candidates for the problem shape (powers of two
     clipped to the dims; exact divisors where the kernel requires them),
  2. prune with the existing ``core.cost_model`` roofline arithmetic:
     VMEM-footprint feasibility (double-buffered resident bytes must fit
     ``TPUChip.vmem_bytes``) and predicted step time — a ``Roofline`` built
     from the candidate's FLOPs and its *block-dependent* HBM traffic
     (smaller blocks re-stream operands more often), plus a per-grid-step
     launch overhead term that penalizes very fine grids,
  3. optionally refine the analytic top-k by empirical timing when the
     caller passes ``measure_fn`` (e.g. the benchmark driver), and
  4. cache the winner in-process and on disk, keyed by
     (kernel, shape, dtype, backend) — deterministic for a given key.

Supported kernels and their problem dicts:

  int8_matmul     {m, k, n}                 → block_m, block_n, block_k
  flash_attention {b, h, sq, sk, d}         → block_q, block_k
  lstm_cell       {batch, d_in, hidden}     → block_b
  lstm_seq        {batch, seq, d_in, hidden} → block_b
  lstm_stack      {batch, seq, d_in, hidden, layers} → block_b

The LSTM analytical models are DTYPE-AWARE: the resident/streamed weight
bytes follow the weight dtype (``core.cost_model.dtype_bytes``), so an
int8-quantized ``lstm_seq``/``lstm_stack`` (dtype="int8") has a 4× smaller
weight footprint than f32 and the feasibility check admits WIDER ``block_b``
batch tiles at the same VMEM budget — the precision×residency pairing the
paper identifies, expressed as launch geometry.  Activations/carries stay
f32 in the model (the quantized kernels do not quantize activations).
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import threading
from typing import Callable, Mapping

from repro.core.cost_model import Roofline, chip_for_dtype, dtype_bytes
from repro.core.energy import DEFAULT_CHIP, TPUChip
from repro.kernels.runtime import CACHE_DIR, backend_key

F32 = 4
INT8 = 1
# Fixed cost charged per grid step (sequencer/DMA issue) — what makes the
# model prefer coarser grids when the roofline terms tie.
GRID_STEP_OVERHEAD_S = 100e-9
# Double-buffering: Pallas overlaps the next block's DMA with compute, so
# streamed operands are resident twice.
PIPELINE_FACTOR = 2.0

_CANDIDATE_TILES = (8, 16, 32, 64, 128, 256, 512)


def _pow2_clipped(dim: int) -> list[int]:
    """Power-of-two tiles ≤ dim, plus dim itself (whole-axis block)."""
    out = [t for t in _CANDIDATE_TILES if t <= dim]
    if dim not in out:
        out.append(dim)
    return out


def _pow2_divisors(dim: int) -> list[int]:
    """Power-of-two tiles that divide dim exactly (kernels that assert
    divisibility instead of padding), plus dim itself."""
    out = [t for t in _CANDIDATE_TILES if t <= dim and dim % t == 0]
    if dim not in out:
        out.append(dim)
    return out


@dataclasses.dataclass(frozen=True)
class _Analysis:
    """Roofline inputs for one (problem, candidate) pair."""

    flops: float        # total useful FLOPs (or int8 ops)
    hbm_bytes: float    # block-dependent HBM traffic
    vmem_bytes: float   # peak resident bytes (before pipelining factor)
    grid_steps: int


# ---------------------------------------------------------------------------
# Per-kernel candidate spaces and analytical models
# ---------------------------------------------------------------------------
def _int8_matmul_candidates(p: Mapping[str, int]) -> list[dict]:
    return [
        {"block_m": bm, "block_n": bn, "block_k": bk}
        for bm in _pow2_divisors(p["m"])
        for bn in _pow2_divisors(p["n"])
        for bk in _pow2_divisors(p["k"])
    ]


def _int8_matmul_analyze(p: Mapping[str, int], c: Mapping[str, int],
                         dtype: str = "int8") -> _Analysis:
    m, k, n = p["m"], p["k"], p["n"]
    bm, bn, bk = c["block_m"], c["block_n"], c["block_k"]
    # x block re-streamed once per N tile; w block once per M tile; the
    # output tile stays in VMEM across the (innermost) K axis.
    traffic = (
        m * k * (n // bn) * INT8
        + k * n * (m // bm) * INT8
        + m * n * F32
        + m * F32 * (n // bn)  # row scales
        + n * F32 * (m // bm)  # col scales
    )
    resident = bm * bk * INT8 + bk * bn * INT8 + 2 * bm * bn * F32 + bm * F32 + bn * F32
    return _Analysis(
        flops=2.0 * m * n * k,
        hbm_bytes=float(traffic),
        vmem_bytes=float(resident),
        grid_steps=(m // bm) * (n // bn) * (k // bk),
    )


def _flash_candidates(p: Mapping[str, int]) -> list[dict]:
    return [
        {"block_q": bq, "block_k": bk}
        for bq in _pow2_divisors(p["sq"])
        for bk in _pow2_divisors(p["sk"])
    ]


def _flash_analyze(p: Mapping[str, int], c: Mapping[str, int],
                   dtype: str = "float32") -> _Analysis:
    b, h, sq, sk, d = p["b"], p["h"], p["sq"], p["sk"], p["d"]
    bq, bk = c["block_q"], c["block_k"]
    # q tile resident across the KV loop; k/v re-streamed once per q tile.
    traffic = (
        b * h * sq * d * F32 * 2                      # q in, o out
        + b * h * (sq // bq) * sk * d * F32 * 2        # k and v sweeps
    )
    lanes = max(d, 128)
    resident = (bq * d + 2 * bk * d + bq * d) * F32 + (2 * bq * lanes + bq * d) * F32
    return _Analysis(
        flops=4.0 * b * h * sq * sk * d,
        hbm_bytes=float(traffic),
        vmem_bytes=float(resident),
        grid_steps=b * h * (sq // bq) * (sk // bk),
    )


def _lstm_weight_bytes(p: Mapping[str, int], dtype: str = "float32",
                       d_in: int | None = None) -> float:
    """One layer's w+u+bias bytes at the WEIGHT dtype.  int8 additionally
    carries two 4H f32 per-gate-column scale vectors (lstm_quant)."""
    d = p["d_in"] if d_in is None else d_in
    hid = p["hidden"]
    wb = dtype_bytes(dtype)
    payload = (d + hid) * 4 * hid * wb
    bias = 4 * hid * F32
    scales = 2 * 4 * hid * F32 if "int8" in dtype else 0
    return float(payload + bias + scales)


def _lstm_stack_weight_bytes(p: Mapping[str, int], dtype: str) -> float:
    """All L layers: layer 0 projects from d_in, layers 1.. from hidden."""
    layers = p["layers"]
    first = _lstm_weight_bytes(p, dtype)
    rest = _lstm_weight_bytes(p, dtype, d_in=p["hidden"])
    return first + (layers - 1) * rest


def _lstm_blocks(p: Mapping[str, int]) -> list[dict]:
    # batch is padded to a block multiple by the kernels → any tile is legal
    return [{"block_b": bb} for bb in _pow2_clipped(max(p["batch"], 8))]


def _pad_up(n: int, b: int) -> int:
    return -(-n // b) * b


def _lstm_cell_analyze(p: Mapping[str, int], c: Mapping[str, int],
                       dtype: str = "float32") -> _Analysis:
    bsz, d, hid = p["batch"], p["d_in"], p["hidden"]
    bb = c["block_b"]
    nb = _pad_up(bsz, bb) // bb
    wbytes = _lstm_weight_bytes(p, dtype)
    traffic = nb * wbytes + bsz * (d + 4 * hid) * F32  # x,h,c in; h,c out
    resident = (
        wbytes
        + bb * (d + 2 * hid) * F32      # x, h, c blocks
        + bb * 2 * hid * F32            # outputs
        + bb * 4 * hid * F32            # gate pre-activations
    )
    return _Analysis(
        flops=2.0 * bsz * (d + hid) * 4 * hid,
        hbm_bytes=float(traffic),
        vmem_bytes=float(resident),
        grid_steps=nb,
    )


def _lstm_seq_resident_act_bytes(seq: int, bb: int, d: int, hid: int) -> float:
    """The f32 per-tile working set shared by seq and stack kernels:
    activations/carries stay f32 even when the weights are int8."""
    return float(
        seq * bb * d * F32              # x sequence tile
        + seq * bb * hid * F32          # hs output tile
        + seq * bb * 4 * hid * F32      # zx: precomputed input projections
        + 4 * bb * hid * F32            # h/c carry + final-state outputs
        + bb * 4 * hid * F32            # gate pre-activations
    )


def _lstm_seq_analyze(p: Mapping[str, int], c: Mapping[str, int],
                      dtype: str = "float32") -> _Analysis:
    bsz, seq, d, hid = p["batch"], p["seq"], p["d_in"], p["hidden"]
    bb = c["block_b"]
    nb = _pad_up(bsz, bb) // bb
    wbytes = _lstm_weight_bytes(p, dtype)
    # Residency win: weights stream once per BATCH BLOCK, not once per step
    # — and at the weight dtype, so int8 streams 4× fewer bytes.
    traffic = nb * wbytes + bsz * seq * (d + hid) * F32
    # The batch tile's WHOLE sequence is a VMEM block (grid walks batch
    # only; time loops in-kernel) — this is what bounds bb for long S.
    # int8 weights shrink the resident term, admitting wider bb.
    resident = wbytes + _lstm_seq_resident_act_bytes(seq, bb, d, hid)
    return _Analysis(
        flops=2.0 * bsz * seq * (d + hid) * 4 * hid,
        hbm_bytes=float(traffic),
        vmem_bytes=float(resident),
        grid_steps=nb,
    )


def _lstm_stack_analyze(p: Mapping[str, int], c: Mapping[str, int],
                        dtype: str = "float32") -> _Analysis:
    """Layer-fused stack: per-layer traffic model.

    L sequential ``lstm_seq`` calls pay the inter-layer h sequence through
    HBM (write + read of B·S·H f32) at every boundary; the fused stack
    keeps it in a VMEM scratch tile, so HBM traffic is one x in, one hs
    out, plus ONE weight stream per batch block covering all L layers."""
    bsz, seq, d, hid = p["batch"], p["seq"], p["d_in"], p["hidden"]
    layers = p["layers"]
    bb = c["block_b"]
    nb = _pad_up(bsz, bb) // bb
    wbytes = _lstm_stack_weight_bytes(p, dtype)
    traffic = (
        nb * wbytes
        + bsz * seq * (d + hid) * F32       # x in, last layer's hs out
        + bsz * 2 * layers * hid * F32      # per-layer final states out
    )
    resident = (
        wbytes
        + _lstm_seq_resident_act_bytes(seq, bb, d, hid)
        + seq * bb * hid * F32              # inter-layer VMEM scratch tile
    )
    flops = 2.0 * bsz * seq * (d + hid) * 4 * hid \
        + (layers - 1) * 2.0 * bsz * seq * (2 * hid) * 4 * hid
    return _Analysis(
        flops=flops,
        hbm_bytes=float(traffic),
        vmem_bytes=float(resident),
        grid_steps=nb,
    )


_KERNELS: dict[str, tuple[Callable, Callable]] = {
    "int8_matmul": (_int8_matmul_candidates, _int8_matmul_analyze),
    "flash_attention": (_flash_candidates, _flash_analyze),
    "lstm_cell": (_lstm_blocks, _lstm_cell_analyze),
    "lstm_seq": (_lstm_blocks, _lstm_seq_analyze),
    "lstm_stack": (_lstm_blocks, _lstm_stack_analyze),
}


# ---------------------------------------------------------------------------
# Roofline scoring (reuses core.cost_model arithmetic)
# ---------------------------------------------------------------------------
def vmem_footprint_bytes(kernel: str, problem: Mapping[str, int],
                         candidate: Mapping[str, int], *,
                         dtype: str = "float32") -> float:
    """Double-buffered VMEM bytes the candidate keeps resident (dtype-aware:
    int8-resident LSTM weights cost 1 B/elem + f32 scales)."""
    _, analyze = _KERNELS[kernel]
    return PIPELINE_FACTOR * analyze(problem, candidate, dtype).vmem_bytes


def is_feasible(kernel: str, problem: Mapping[str, int],
                candidate: Mapping[str, int], chip: TPUChip = DEFAULT_CHIP,
                *, dtype: str = "float32") -> bool:
    return vmem_footprint_bytes(kernel, problem, candidate,
                                dtype=dtype) <= chip.vmem_bytes


def predict_time_s(kernel: str, problem: Mapping[str, int],
                   candidate: Mapping[str, int], *, dtype: str = "float32",
                   chip: TPUChip = DEFAULT_CHIP) -> float:
    """Analytic step-time: cost_model roofline + per-grid-step overhead."""
    _, analyze = _KERNELS[kernel]
    a = analyze(problem, candidate, dtype)
    chip = chip_for_dtype(chip, dtype)  # MXU runs int8 at its own (2×) peak
    r = Roofline(
        flops_per_dev=a.flops,
        hbm_bytes_per_dev=a.hbm_bytes,
        coll_bytes_per_dev=0.0,
        chips=1,
        model_flops=a.flops,
        chip=chip,
    )
    return r.t_step_s + a.grid_steps * GRID_STEP_OVERHEAD_S


def feasible_candidates(kernel: str, problem: Mapping[str, int],
                        chip: TPUChip = DEFAULT_CHIP, *,
                        dtype: str = "float32") -> list[dict]:
    gen, _ = _KERNELS[kernel]
    cands = [c for c in gen(problem)
             if is_feasible(kernel, problem, c, chip, dtype=dtype)]
    if not cands:  # degenerate budget: keep the smallest-footprint candidate
        cands = sorted(
            gen(problem),
            key=lambda c: vmem_footprint_bytes(kernel, problem, c, dtype=dtype),
        )[:1]
    return cands


# ---------------------------------------------------------------------------
# Cache (in-process dict + JSON on disk)
# ---------------------------------------------------------------------------
_CACHE: dict[str, dict] = {}
_LOCK = threading.Lock()


def _cache_path() -> str:
    return os.environ.get("REPRO_AUTOTUNE_CACHE",
                          str(CACHE_DIR / "autotune.json"))


def cache_key(kernel: str, problem: Mapping[str, int], dtype: str,
              backend: str | None = None, chip: TPUChip = DEFAULT_CHIP) -> str:
    backend = backend or backend_key()
    shape = ",".join(f"{k}={problem[k]}" for k in sorted(problem))
    # The chip fingerprint is part of the key: a winner tuned against one
    # VMEM budget must not be served for a different chip.
    return f"{kernel}|{shape}|{dtype}|{backend}|{chip.name}:{chip.vmem_bytes}"


def _valid_entry(value) -> bool:
    """Disk entries are untrusted (any file at ``REPRO_AUTOTUNE_CACHE``):
    accept only a flat {block_*: positive int} mapping."""
    return (
        isinstance(value, dict)
        and bool(value)
        and all(
            isinstance(k, str) and k.startswith("block_")
            and isinstance(v, int) and not isinstance(v, bool) and v > 0
            for k, v in value.items()
        )
    )


def _load_disk() -> dict:
    try:
        with open(_cache_path()) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, ValueError):
        return {}


def _store_disk(key: str, value: dict) -> None:
    path = _cache_path()
    data = _load_disk()
    data[key] = value
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".")
        with os.fdopen(fd, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # disk cache is best-effort; in-process cache still holds it


def clear_cache(*, disk: bool = False) -> None:
    with _LOCK:
        _CACHE.clear()
        if disk:
            try:
                os.remove(_cache_path())
            except OSError:
                pass


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------
def autotune(kernel: str, problem: Mapping[str, int], *, dtype: str = "float32",
             backend: str | None = None, chip: TPUChip = DEFAULT_CHIP,
             measure_fn: Callable[[dict], float] | None = None,
             top_k: int = 3) -> dict:
    """Pick block sizes for ``kernel`` on ``problem``.

    Deterministic for a given (kernel, shape, dtype, backend, chip) key:
    candidates are scored by the analytic model and ties broken by coarsest
    grid.  When ``measure_fn`` (candidate → seconds) is given, the analytic
    top-k are re-ranked empirically before caching — an explicit
    ``measure_fn`` always re-tunes (cache hits only serve analytic calls).
    """
    if kernel not in _KERNELS:
        raise ValueError(f"no autotune model for kernel {kernel!r}")
    key = cache_key(kernel, problem, dtype, backend, chip)
    with _LOCK:
        if key in _CACHE and measure_fn is None:
            return dict(_CACHE[key])
        disk = _load_disk()
        if key in disk and measure_fn is None and _valid_entry(disk[key]):
            _CACHE[key] = disk[key]
            return dict(disk[key])

    cands = feasible_candidates(kernel, problem, chip, dtype=dtype)
    _, analyze = _KERNELS[kernel]
    scored = sorted(
        cands,
        key=lambda c: (
            predict_time_s(kernel, problem, c, dtype=dtype, chip=chip),
            analyze(problem, c, dtype).grid_steps,
            tuple(sorted(c.items())),
        ),
    )
    if measure_fn is not None and len(scored) > 1:
        head = scored[: max(top_k, 1)]
        best = min(head, key=lambda c: (measure_fn(dict(c)), tuple(sorted(c.items()))))
    else:
        best = scored[0]

    best = dict(best)
    with _LOCK:
        _CACHE[key] = best
        _store_disk(key, best)
    return dict(best)
