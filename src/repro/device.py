"""Where JAX runs this process, and where its compiled programs are kept.

Nothing here runs at import: entry points (``chip_smoke.py``, the
examples, ``benchmarks/run.py``) call :func:`configure_compile_cache`
before their first compile, and kernels ask :func:`pallas_interpret`
when they build a ``pl.pallas_call``.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict

#: JAX's persistent compile cache when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed directory in the checkout (listed in ``.gitignore``),
#: so every process of this checkout finds what the others compiled
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def pallas_interpret() -> bool:
    """Pallas kernels run in interpret mode only where JAX's default
    backend is the CPU (the tests); on a TPU they compile through Mosaic,
    and a kernel Mosaic refuses is an error there, never an interpreted
    kernel."""
    import jax

    return jax.default_backend() == "cpu"


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself and no
    other directory is set here), else :data:`DEFAULT_CACHE_DIR`, which
    is then exported in that variable so worker processes spawned later
    share it.  Every compile is kept, however short: a segment kernel
    compiles in about a second, and a run compiles dozens."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.environ["JAX_COMPILATION_CACHE_DIR"] = \
            str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info() -> Dict[str, object]:
    """The device as JAX reports it: platform, kind and count."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
