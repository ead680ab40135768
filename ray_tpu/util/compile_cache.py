"""Where JAX's persistent compilation cache lives.

Every run on the chip starts on a fresh machine, so a cold compile of a
whole train step is paid again unless the cache can be placed from
outside.  The rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, that is
the cache and nothing in the program sets another; where it is not, the
entry point (``chip_smoke.py``, the benchmark) calls
:func:`place_compile_cache` before ``ray_tpu.init()`` and the cache goes
to a fixed path inside the checkout.  The path is part of the cache's
key, so it is never built from a temporary name, a pid, the session
directory or the time.  Head, raylet and every worker inherit the
variable through ``node.child_env()``; JAX reads it when it is imported.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def place_compile_cache(checkout_root: str) -> str:
    """The cache directory of this run, set in ``os.environ`` for every
    process started afterwards.  The environment wins; the default is
    ``<checkout_root>/.jax_cache`` (git-ignored)."""
    path = os.environ.get(ENV)
    if not path:
        path = os.path.join(os.path.abspath(checkout_root), ".jax_cache")
        os.environ[ENV] = path
    return path


def count_cache_entries(path: str) -> int:
    """Compiled programs in the cache: JAX writes one ``<key>-cache``
    file for each."""
    try:
        names = os.listdir(path)
    except FileNotFoundError:
        return 0
    return sum(1 for n in names if n.endswith("-cache"))
