"""Hold the scheduler's clock to the wall clock, from outside the program.

``ContinuousBatchingScheduler`` keeps a virtual time ``t``: it admits a
request once ``t`` has passed its arrival, advances ``t`` by what its
calibration charges for each chunk and decode tick, and jumps ``t`` to the
next arrival when the pool is idle. Two objects of this module take the
places of the calibration and the duty-cycle policy:

  WallCalibration  charges each tick the wall seconds that passed since the
                   scheduler's ``t`` was last brought level with the wall, so
                   after every charge ``t`` equals the wall time of the
                   window; between charges it lags by at most one tick.
  WallIdlePolicy   waits out an idle gap in real time (sleeps until the next
                   arrival is due) and adds no modelled wake time.

Both share a ``WallClock``. Its ``check`` ends the window: the engine
wrappers call it before each engine call and it raises ``WindowClosed`` once
the run should stop, so no device work starts after that.
"""
from __future__ import annotations

import time

from repro.serving.policy import DutyCyclePolicy, GapOutcome


class WindowClosed(Exception):
    """Raised into the scheduler's loop when the measured window is over."""


class WallClock:
    def __init__(self, seconds: float):
        self.seconds = float(seconds)
        self.origin: float | None = None  # perf_counter at the window's start
        self.vt = 0.0                     # the scheduler's t, as charged
        self.waits: list[tuple[float, float]] = []  # idle waits (window s)
        self.stop_when = lambda now: now >= self.seconds

    def start(self, vt0: float = 0.0) -> None:
        self.vt = vt0
        self.origin = time.perf_counter()

    def now(self) -> float:
        """Seconds since the window opened."""
        return time.perf_counter() - self.origin

    def check(self) -> None:
        if self.origin is not None and self.stop_when(self.now()):
            raise WindowClosed


class WallCalibration:
    """Calibration whose every charge is the wall time since the last one."""

    def __init__(self, clock: WallClock, nominal_s: float = 0.05):
        self.clock = clock
        self.nominal_s = nominal_s  # what construction-time callers see

    def _charge(self) -> float:
        c = self.clock
        if c.origin is None:
            return self.nominal_s
        dt = max(c.now() - c.vt, 0.0)
        c.vt += dt
        return dt

    def step_s(self) -> float:
        return self._charge()

    def chunk_s(self, batch: int, chunk_tokens: int) -> float:
        return self._charge()

    def prefill_s(self, batch: int, s0: int) -> float:
        return self._charge()

    def verify_s(self, k: int) -> float:
        return self._charge()


class WallIdlePolicy(DutyCyclePolicy):
    """Idle gaps pass in real time; the device stays up (no wake cost)."""

    name = "wall_idle"

    def __init__(self, clock: WallClock):
        super().__init__(profile=None)
        self.clock = clock

    def on_gap(self, gap_s: float) -> GapOutcome:
        c = self.clock
        target = c.vt + gap_s
        start = c.now()
        if c.stop_when(target):
            # the next arrival lies past the close: the window ends idle
            c.vt = target
            raise WindowClosed
        while (left := target - c.now()) > 0:
            time.sleep(min(left, 0.002) if left < 0.004 else left - 0.002)
        c.waits.append((start, c.now()))
        c.vt = target
        return GapOutcome(0.0, 0.0, False)
