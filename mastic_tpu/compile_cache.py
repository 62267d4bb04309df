"""The one place a process points JAX's persistent compile cache.

Every entry point (`chip_smoke.py`, `bench.py`, `tools/northstar.py`,
`tools/serve.py`, `tools/bake.py`, `tools/party.py`, the party
children of `drivers/parties.py`, `__graft_entry__.py`) calls
`configure()` right after importing jax, before its first compile.

`JAX_COMPILATION_CACHE_DIR`, when set, names the directory and no
other is set here.  Otherwise the cache lives at a fixed directory
inside the checkout (`.jax_cache/`, gitignored): the path is part of
what makes a later process find the entries again, so it must not
move between runs.
"""

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def configure() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
