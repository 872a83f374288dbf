"""The two questions every JAX entry point asks, answered in one place.

* ``platform()`` — which backend the process runs on. ``"tpu"`` selects
  the Pallas kernels, ``"cpu"`` the jnp sponge the tests run; anything
  else, or a backend that fails to initialise, raises. No caller wraps
  this in ``except -> False``: a chip that cannot start must stop the
  program, not quietly move the hashing to the host.
* ``named_jit()`` — what a jitted program is called on the device. A
  profiler trace tells programs apart by ``jit_<function name>`` and
  nothing else, so every program on a served path has a name of its
  own (docs/observability.md, "Names on the device").
* ``place_compile_cache()`` — where XLA's persistent compilation cache
  lives. One fused window signature costs ~30 s cold on a v5e, so every
  entry point that will touch JAX (``python -m khipu_tpu``,
  ``ServiceBoard``, ``scenarios.py``, ``chip_smoke.py``,
  ``__graft_entry__.py``) calls this before its first compile.
"""

from __future__ import annotations

import os

PLATFORMS = ("tpu", "cpu")

# <checkout>/.jax_cache — derived from the package's own path so the
# directory (part of the cache key) is the same on every run of one
# checkout; listed in .gitignore
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache",
)


def platform() -> str:
    """``"tpu"`` or ``"cpu"``; raises on any other backend and lets a
    backend-initialisation failure propagate."""
    import jax

    name = jax.default_backend()
    if name not in PLATFORMS:
        raise RuntimeError(
            f"unsupported jax backend {name!r}: khipu-tpu runs on "
            f"{' or '.join(PLATFORMS)}"
        )
    return name


def named_jit(name: str, fn, **jit_kwargs):
    """``jax.jit(fn)`` whose XLA module is ``jit_<name>``. Where the
    program is inlined into a caller's, ``jit(<name>)`` is a component
    of its instructions' ``op_name`` instead."""
    import jax

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


def place_compile_cache() -> str:
    """Point JAX's persistent compile cache somewhere stable; returns
    the directory in use. With ``JAX_COMPILATION_CACHE_DIR`` set JAX
    reads it itself and nothing is set in code."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return _DEFAULT_CACHE_DIR
