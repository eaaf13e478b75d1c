"""Where JAX's persistent compilation cache lives.

A cold compile of the GPT-2 train step costs tens of seconds on the
chip, and every process that compiles it pays again unless they share a
cache.  The directory is decided outside the code: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here sets another; where it is not, the cache goes to ``.jax_cache/`` at
the root of the checkout — one fixed path, the same in every process
(the driver, a bench run, a TPU worker started by the node manager), so
that what one of them compiled the next one finds.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# Two settings go with the cache (PR 25 changed both; read off the chip's
# compiled text and its cache):
#
# * A Mosaic kernel travels inside the program as bytecode with its debug
#   locations, which XLA's key hashes along with the rest; by default a
#   location is the Python call stack at trace time, ten frames deep.
#   The same train step reached from another call site -- another
#   script, an actor's worker -- then never hits (seen on the chip: the
#   actor recompiled what the driver's child had just cached).  So no
#   frames at all: a location is the name stack alone, the same wherever
#   the program is traced from and in whichever directory the checkout
#   lies.  (Until PR 25 this was jax_include_full_tracebacks_in_locations
#   = False, which gives the same key but lowers every instruction's
#   ``op_name`` to its bare primitive, ``mul``: the name stack, and with
#   it every ``jax.named_scope`` of _private/scopes.py, never reached the
#   compiled program.)
# * By default the key leaves metadata out, so a hit may hand back an
#   executable compiled from the same graph under other names -- by the
#   commit before, say, whose text knows no scope: the scope map
#   (device_stats.ProgramRegistry.scope_map) read 0 entries for a
#   program the parent commit had compiled first.  The names are what
#   that map reads, so they are part of the key.
_SETTINGS = {"jax_traceback_in_locations_limit": 0,
             "jax_compilation_cache_include_metadata_in_key": True}


def compile_cache_dir() -> str:
    """The cache directory every process of this checkout uses."""
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile and return
    its directory.  Does not initialise a backend."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    for name, value in _SETTINGS.items():
        jax.config.update(name, value)
    return path


def compile_cache_env() -> dict:
    """The same settings as environment variables, for a process
    that is about to be started (a TPU worker) — JAX reads both at
    import, so the worker need not import JAX early to get them."""
    return {ENV_VAR: compile_cache_dir(),
            **{name.upper(): str(value)
               for name, value in _SETTINGS.items()}}


class CompileWatch:
    """Counts this process's XLA compiles and its persistent-cache
    traffic from the moment it is made, through ``jax.monitoring``:

    * ``compiles`` — programs handed to the backend compiler or loaded
      from the cache (every new shape, every jitted function, every
      eager op's first use);
    * ``hits`` — of those, the ones the persistent cache served;
    * ``writes`` — the ones compiled and then written to it (JAX skips
      programs that compile in under a second).

    A window with no compile is ``compiles`` unchanged across it.
    """

    def __init__(self):
        import jax

        self.compiles = self.hits = self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration_secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1
