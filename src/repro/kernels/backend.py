"""Backend selection: the one switch between the lowering tier's
executors, and the kernel-impl default of ``kernels.ops``.

execution-backend tier (``lower.exec`` / ``lower.netexec`` /
``lower.meshexec`` / ``lower.fuse``): every entry point takes a
``backend`` name, checked by ``resolve_backend``.

    =============  ========================================================
    ``interpret``  per-layer ``pl.pallas_call(interpret=True)``
                   (``exec.pallas_kernels``) — the numerics **oracle**
                   backend; runs everywhere, slowly.
    ``pallas``     the same per-layer Pallas kernels, compiled for the
                   TPU; plans whose blocks break the TPU tiling are
                   refused before compiling (``exec._check_tpu_tiling``).
    ``compiled``   fused XLA (``fuse.compiled_kernels``): every layer of
                   a segment, or of the whole net, traced into **one**
                   jitted executable — the default measured path, and the
                   one the chip benchmark runs.
    =============  ========================================================

All three run the same graph walk (``netexec.layer_output``) on their
own map from layer kind to kernel, and are held to the ``kernels/ref.py``
oracles (``exec.ORACLE_KERNELS``).

kernel-impl tier (``kernels.ops``: attention / ssd wrappers)
    ``resolve_impl("auto")`` -> ``"pallas"`` on TPU, ``"jnp"`` elsewhere.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict

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


def resolve_backend(backend: str) -> str:
    """``backend``, checked to be one of ``BACKENDS``."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one "
                         f"of {BACKENDS}")
    return backend


def backend_interprets(backend: str) -> bool:
    """Whether per-layer pallas_calls under this backend interpret (the
    flag handed through to ``pl.pallas_call``)."""
    return backend == "interpret"


__all__ = ["BACKENDS", "DEFAULT_BACKEND", "ORACLE_BACKEND", "on_tpu",
           "device_info", "DEFAULT_COMPILE_CACHE", "configure_compile_cache",
           "default_impl", "resolve_impl", "resolve_backend",
           "backend_interprets"]
