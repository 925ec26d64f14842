"""Where JAX keeps its persistent compilation cache.

Called once by the entry points (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``) before anything compiles; library modules and tests never
call it, so importing ``repro`` changes no JAX setting.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that directory itself and
  nothing is set here.
* Otherwise the cache goes to ``<checkout>/.jax_cache``. The path is fixed
  because it is part of the cache key: a directory that moves never hits.
  The minimum compile time worth caching drops to 0 so that the small
  Pallas kernel compiles are kept too.
"""
from __future__ import annotations

import os
from typing import Mapping, Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
DIRNAME = ".jax_cache"


def checkout_root() -> str:
    """The checkout this package runs from (``src/repro/launch`` -> root)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(os.path.dirname(here)))


def cache_settings(environ: Optional[Mapping[str, str]] = None,
                   root: Optional[str] = None) -> dict:
    """The ``jax.config`` updates that place the cache: none when the
    environment already names a directory."""
    environ = os.environ if environ is None else environ
    if environ.get(ENV):
        return {}
    return {
        "jax_compilation_cache_dir": os.path.join(root or checkout_root(),
                                                  DIRNAME),
        "jax_persistent_cache_min_compile_time_secs": 0.0,
    }


def enable_compile_cache() -> str:
    """Apply :func:`cache_settings` and return the cache directory."""
    import jax

    settings = cache_settings()
    for name, value in settings.items():
        jax.config.update(name, value)
    return settings.get("jax_compilation_cache_dir") or os.environ[ENV]
