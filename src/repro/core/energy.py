"""Power/energy models for both hardware backends.

FPGA constants are calibrated so the paper's published numbers (C1–C4)
reproduce from the analytical models — every calibrated value is marked
``# CAL`` with its derivation (DESIGN.md §2 "Calibration note").

TPU constants are the documented v5e-class estimates used by the roofline
energy model (DESIGN.md §6). ``chip_for_device`` maps an attached device's
``device_kind`` to its constants; a kind without an entry is an error.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class FPGABoard:
    """Spartan-7-class board (Elastic Node V targets XC7S15/XC7S25)."""

    name: str = "spartan7-xc7s15"
    clock_hz: float = 100e6  # paper §5.1: 100 MHz on XC7S15
    # Resource budget (XC7S15: 8000 LUT6, 20 DSP48E1, 10 BRAM36)
    dsp: int = 20
    lut: int = 8000
    bram_kb: int = 360
    # Power model.
    p_idle_w: float = 0.028  # CAL: Spartan-7 quiescent+idle ≈ 28 mW
    p_cfg_w: float = 0.1414  # CAL: with t_cfg, gives E_cfg ≈ 14.14 mJ → C3 = 12.39×
    t_cfg_s: float = 0.100   # CAL: SPI bitstream load ~100 ms (XC7S15, ref [6] regime)
    p_lut_w: float = 4.17559e-5  # CAL: effective dynamic W per active LUT   } solved 2×2 from
    p_dsp_w: float = 1.195278e-2 # CAL: effective dynamic W per active DSP  } published EE pair
    #   (5.57, 12.98 GOPS/s/W at the two templates' resource mixes — core/fpga.py docstring)

    @property
    def e_cfg_j(self) -> float:
        return self.p_cfg_w * self.t_cfg_s

    def active_power(self, lut_used: int, dsp_used: int) -> float:
        return self.p_idle_w + lut_used * self.p_lut_w + dsp_used * self.p_dsp_w


@dataclasses.dataclass(frozen=True)
class TPUChip:
    """TPU v5e-class chip (the TARGET; this container only lowers for it)."""

    name: str = "tpu-v5e"
    peak_flops: float = 197e12       # bf16
    peak_int8_ops: float = 394e12
    hbm_bw: float = 819e9            # bytes/s
    hbm_bytes: int = 16 * 1024**3
    vmem_bytes: int = 16 * 1024**2   # on-chip vector memory per core (~16 MiB)
    ici_bw: float = 50e9             # bytes/s per link direction
    ici_links: int = 4               # 2D torus: 4 links per chip
    p_idle_w: float = 75.0
    p_peak_w: float = 200.0
    # "Configuration" analogue: program load + weight upload (DESIGN.md §2)
    reload_bw: float = 100e9         # bytes/s effective weight-refill bandwidth
    reload_fixed_s: float = 0.5      # program load / runtime re-init

    def step_power(self, compute_util: float) -> float:
        """Linear idle→peak power model in compute utilization."""
        u = min(max(compute_util, 0.0), 1.0)
        return self.p_idle_w + (self.p_peak_w - self.p_idle_w) * u

    def dvfs_power(self, compute_util: float, clock_frac: float) -> float:
        """Power at a throttled clock: the dynamic term scales with the
        clock fraction (frequency scaling), the static/idle term does not.
        ``dvfs_power(u, 1.0) == step_power(u)``; a tick stretched to
        ``base / f`` seconds therefore spends the same dynamic energy but
        ``1/f`` times the static energy — the paper's Slow-Down trade."""
        u = min(max(compute_util, 0.0), 1.0)
        f = min(max(clock_frac, 0.0), 1.0)
        return self.p_idle_w + (self.p_peak_w - self.p_idle_w) * u * f

    def reload_time(self, weight_bytes: float) -> float:
        return self.reload_fixed_s + weight_bytes / self.reload_bw


DEFAULT_BOARD = FPGABoard()
DEFAULT_CHIP = TPUChip()

# Chip constants by ``jax.Device.device_kind``. TPU v5e peaks: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM at
# 819 GB/s); power and reload terms are the model's estimates above.
CHIPS: dict[str, TPUChip] = {"TPU v5 lite": DEFAULT_CHIP}


def chip_for_device(device_kind: str) -> TPUChip:
    """Constants of the attached chip; unknown kinds raise (no default)."""
    if device_kind not in CHIPS:
        raise KeyError(f"no chip constants for device kind {device_kind!r} "
                       f"(known: {sorted(CHIPS)})")
    return CHIPS[device_kind]
