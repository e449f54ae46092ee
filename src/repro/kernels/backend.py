"""Backend selection — the single source of truth for interpret-vs-compiled.

Two tiers of the stack used to carry their own ad-hoc flags: the kernel
API (``kernels.ops``) dispatched on an ``impl`` string with a private
``_on_tpu()`` probe, and the lowering tier (``lower.exec`` /
``lower.netexec``) threaded a bare ``interpret: bool``.  Both now resolve
through this module, so "what actually runs" is decided in exactly one
place:

kernel-impl tier (``kernels.ops``: attention / ssd wrappers)
    ``resolve_impl("auto")`` -> ``"pallas"`` on TPU, ``"jnp"`` elsewhere.

execution-backend tier (``lower.exec`` / ``lower.netexec`` / ``lower.fuse``)
    =============  ========================================================
    ``interpret``  per-layer ``pl.pallas_call(interpret=True)`` — the
                   bit-accuracy **oracle**; runs everywhere, slowly.
    ``pallas``     per-layer compiled ``pl.pallas_call`` for the TPU;
                   plans whose blocks break the TPU tiling are refused
                   before compiling (``exec._check_tpu_tiling``).
    ``compiled``   fused XLA segments (``lower.fuse``): every kernel of a
                   chain segment traced into **one** jitted executable —
                   the default measured path, and the one that runs on
                   the chip (``chip_smoke.py``).
    =============  ========================================================

``resolve_backend`` also accepts the legacy ``interpret`` bool so existing
call sites keep their meaning: ``interpret=True`` -> ``"interpret"``,
``interpret=False`` -> ``"pallas"``.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, Optional

import jax

#: execution backends of the lowering tier (see module docstring)
BACKENDS = ("interpret", "pallas", "compiled")

#: the default measured path: fused XLA segments, fast on every platform
DEFAULT_BACKEND = "compiled"

#: the numerics oracle every other backend is verified against
ORACLE_BACKEND = "interpret"


def on_tpu() -> bool:
    """True when jax's default backend is a TPU.  A backend that fails to
    initialize raises: it is not silently taken for "not a TPU"."""
    return jax.default_backend() == "tpu"


def device_info() -> Dict:
    """The device JAX runs on, named the way every measurement record
    names it, so a CPU timing is never read as a chip timing."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: a fixed directory in the checkout (the path is part of the
#: cache key, so it never moves; ``.gitignore`` lists it)
DEFAULT_COMPILE_CACHE = str(Path(__file__).resolve().parents[3]
                            / ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else at
    ``DEFAULT_COMPILE_CACHE``, and return the directory.  Entry points
    call this once; importing the package configures nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or DEFAULT_COMPILE_CACHE
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def default_impl() -> str:
    """Kernel-impl default: Pallas TPU kernels on TPU, pure-jnp elsewhere."""
    return "pallas" if on_tpu() else "jnp"


def resolve_impl(impl: str = "auto") -> str:
    """Resolve a kernel ``impl`` string (``kernels.ops`` dispatch)."""
    return default_impl() if impl == "auto" else impl


def resolve_backend(backend: Optional[str] = None,
                    interpret: Optional[bool] = None) -> str:
    """Resolve an execution backend name for the lowering tier.

    ``backend`` wins when given; otherwise the legacy ``interpret`` bool
    maps to its historical meaning; with neither, the default measured
    path (``compiled``) is chosen.
    """
    if backend is not None:
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; expected one "
                             f"of {BACKENDS}")
        return backend
    if interpret is not None:
        return "interpret" if interpret else "pallas"
    return DEFAULT_BACKEND


def backend_interprets(backend: str) -> bool:
    """Whether per-layer pallas_calls under this backend interpret (the
    flag handed through to ``pl.pallas_call``)."""
    return backend == "interpret"


__all__ = ["BACKENDS", "DEFAULT_BACKEND", "ORACLE_BACKEND", "on_tpu",
           "device_info", "DEFAULT_COMPILE_CACHE", "configure_compile_cache",
           "default_impl", "resolve_impl", "resolve_backend",
           "backend_interprets"]
