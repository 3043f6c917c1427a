"""Where the persistent XLA compile cache lives.

Every entry point that compiles a train step (both training CLIs,
``chip_smoke.py``) calls
:func:`place_compile_cache` before its first compile, so a second process
in the same checkout — or a second call on a machine that keeps its disk —
starts from compiled programs instead of minutes of ResNet-50 / 12-layer LM
compilation.  It is *not* called at package import: importing the library
changes no JAX configuration.

The same call arms the set-up ledger (``telemetry/setup_ledger.py``): the
entry points already make it before anything compiles, ``benchmark/run.py``
among them, so every program the process builds lands in the ledger and no
caller has a second thing to remember.  Package import would be earlier by
a few hundredths of a second and would register listeners in every process
that imports the library; this registers them where a train step is about
to be built.  It arms the step store (``utils/step_store.py``) too, in the
cache's subdirectory ``step_store``: a library caller that never places the
cache gets plain ``jax.jit`` steps.
"""

from __future__ import annotations

import os

__all__ = ["CACHE_DIR_ENV", "place_compile_cache"]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

# the directory is part of what makes an entry findable again, so it is
# derived from this file's location only: never a temp dir, a pid or a time
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory and return it.

    With ``JAX_COMPILATION_CACHE_DIR`` set in the environment JAX reads it
    by itself, and no code of this repo sets another directory.  Otherwise
    the cache is ``<checkout>/.jax_cache`` (git-ignored).  Arms the
    set-up ledger (a second call arms nothing) and the step store on the
    way.
    """
    from ..telemetry import setup_ledger
    from . import step_store

    setup_ledger.arm()
    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        import jax

        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    step_store.arm(path)
    return path
