"""Where the entry points keep JAX's persistent compilation cache.

Call ``use_compile_cache()`` at the top of an entry point's ``main`` —
never at import, so importing a launcher changes no global JAX state.
"""
from __future__ import annotations

import os
import pathlib

import jax

# The checkout whose ``src/repro`` is running: the cache sits next to the
# code it was compiled from.
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
# A fixed path, not a temporary, per-process or per-run one: a new
# directory starts empty, so every run would pay every compile again.
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def _is_checkout(path: pathlib.Path) -> bool:
    pyproject = path / "pyproject.toml"
    return pyproject.is_file() and 'name = "tbn-repro"' in pyproject.read_text()


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    the variable itself) and no other is set; otherwise
    ``<checkout>/.jax_cache``. Raises when ``repro`` was imported from
    outside a checkout (an installed copy) and the variable is unset.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if not _is_checkout(CHECKOUT):
            raise RuntimeError(
                f"repro runs from {CHECKOUT}, which is not a checkout of "
                f"this repo; set JAX_COMPILATION_CACHE_DIR to choose where "
                f"the compile cache goes")
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
