"""Where the numeric core runs, decided in one place.

* ``pallas_interpret()`` — whether Pallas kernels run compiled (TPU) or in
  the interpreter (CPU, the test platform). Every ``kernels/*/ops.py``
  asks it; no kernel picks its own mode.
* ``DEVICE_ERRORS`` — the exceptions that mean the device, its runtime or
  its compiler failed. A transform that raises one of these failed for
  every record alike, so the runtime must fail the run instead of
  quarantining records (``repro.runtime.cluster``).
* ``enable_compile_cache()`` — JAX's persistent compilation cache, placed
  by the entry points (examples, benchmarks, ``chip_smoke.py``); importing
  the library never touches it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax._src.pallas.mosaic.error_handling import MosaicError
from jax.experimental.pallas.tpu import LoweringException

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# JaxRuntimeError: XLA compile and device runtime failures. Mosaic kernel
# compile failures arrive as MosaicError (JAX re-raises them with the
# kernel's Python traceback), Pallas-to-Mosaic lowering failures as
# LoweringException.
DEVICE_ERRORS = (jax.errors.JaxRuntimeError, MosaicError, LoweringException)


def pallas_interpret() -> bool:
    """True when Pallas kernels must run in interpret mode: compiled on a
    TPU, interpreted on the CPU. Any other platform raises — there is no
    Mosaic lowering for it, and interpreting there would hide that the
    kernels never ran on the device."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU; "
        f"the default JAX platform is {platform!r}")


def enable_compile_cache() -> str:
    """Give JAX's persistent compilation cache a fixed home and return it.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself, so
    nothing is overridden); otherwise the cache lives in ``<repo>/.jax_cache``
    (listed in ``.gitignore``). The path never depends on the process or the
    time: it is part of the cache key, so a moving directory never hits."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["DEVICE_ERRORS", "enable_compile_cache", "pallas_interpret"]
