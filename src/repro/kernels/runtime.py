"""Shared runtime policy: where Pallas kernels execute, and where compiled
programs and tuner winners are cached.

Every kernel in this package takes ``interpret: bool | None = None`` and
resolves ``None`` through :func:`default_interpret` — True (Python/XLA
interpreter, correct everywhere) unless the default JAX backend is a TPU, in
which case the same calls lower through Mosaic. ``REPRO_PALLAS_INTERPRET``
overrides the decision: "1"/"true" force interpret, "0"/"false" force
Mosaic. A backend that fails to initialise raises here; it never quietly
selects interpret mode.

Centralizing this here means no kernel hard-codes ``interpret=True`` and a
TPU host gets compiled kernels with zero call-site changes.
"""
from __future__ import annotations

import functools
import os
from pathlib import Path

import jax

_FALSY = ("0", "false", "no", "off")

# Fixed, git-ignored cache root inside the checkout: JAX's persistent
# compilation cache (keyed by the directory too, so it must not move) and
# the autotuner's disk cache live under it.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".cache"


@functools.lru_cache(maxsize=None)
def has_tpu_backend() -> bool:
    """True when the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Resolve the interpret-mode default (env override > backend sniff)."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env.strip().lower() not in _FALSY
    return not has_tpu_backend()


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → :func:`default_interpret`; booleans pass through."""
    return default_interpret() if interpret is None else bool(interpret)


def backend_key() -> str:
    """Short backend tag used in autotune cache keys."""
    return "tpu" if has_tpu_backend() else "interpret"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``CACHE_DIR/jax``.
    Call before the first compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR / "jax"))
