"""Where JAX's persistent compilation cache lives.

A cold compile of the GPT-2 train step costs tens of seconds on the
chip, and every process that compiles it pays again unless they share a
cache.  The directory is decided outside the code: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here sets another; where it is not, the cache goes to ``.jax_cache/`` at
the root of the checkout — one fixed path, the same in every process
(the driver, a bench run, a TPU worker started by the node manager), so
that what one of them compiled the next one finds.

What the compiler and the cache then did is recorded here too: one pair
of ``jax.monitoring`` listeners a process turns every program JAX
compiles or loads into one ``compile`` record of
``_private/telemetry.py``'s set-up ring (`_on_duration`), and
`CompileWatch` counts them.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict

from ray_tpu._private import telemetry

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
    _listen()
    return path


def compile_cache_env() -> dict:
    """The same settings as environment variables, for a process
    that is about to be started (a TPU worker) — JAX reads both at
    import, so the worker need not import JAX early to get them."""
    return {ENV_VAR: compile_cache_dir(),
            **{name.upper(): str(value)
               for name, value in _SETTINGS.items()}}


# ---------------------------------------------------------------------------
# what JAX reports of every compile -> one record each
# ---------------------------------------------------------------------------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
#: a hit's two durations, by the record's field
_HIT_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}

_lock = threading.Lock()
_listening = False
#: the process's counts since the listeners were registered; a
#: `CompileWatch` is a view of them from its construction on
_counts = {"compiles": 0, "hits": 0, "writes": 0}
#: JAX calls a listener on the thread that compiles, so what a compile
#: has reported so far is kept per thread until its backend event
_pending = threading.local()


def _listen() -> None:
    """Register the process's one pair of listeners, once
    (`enable_compile_cache` and the first `CompileWatch` both ask)."""
    global _listening
    with _lock:
        if _listening:
            return
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True


def _count(what: str) -> None:
    with _lock:
        _counts[what] += 1


def _on_event(event: str, **kw: Any) -> None:
    """The cache's two verdicts.  They carry no name: they belong to
    the backend event they arrive inside of, on this thread."""
    if event == _HIT:
        _count("hits")
        _pending.__dict__["cache"] = "hit"
    elif event == _MISS:
        _count("writes")
        _pending.__dict__["cache"] = "miss"


def _on_duration(event: str, duration_secs: float, **kw: Any) -> None:
    """JAX reports a compile as three durations on the compiling
    thread: tracing (``fun_name`` the function's own name; one event for
    every jitted function, the outermost after the ones inside it, whose
    time it holds), lowering to MLIR (``jit(<name>)``) and the backend's
    part, which is either the cache's read or the real compile and
    closes the record.  The lowering traces small functions of its own
    (``add``, ``bitwise_or``: thousands of them for one serving program
    on the chip, PERF.md PR 54 step 0), so the trace that belongs to a
    lowering is not the last one before it but the last one of ITS
    name: traces wait by name until the next lowering takes its own and
    drops the rest.  A lowering whose jaxpr was traced earlier
    (``jax.eval_shape`` and no compile since) finds that trace."""
    pending: Dict[str, Any] = _pending.__dict__
    if event == _TRACE:
        pending.setdefault("traces", {})[kw.get("fun_name")] = (
            duration_secs, time.perf_counter())
    elif event == _LOWER:
        name = kw.get("fun_name") or ""
        traced = pending.pop("traces", {}).get(
            name[name.find("(") + 1:-1] if name.endswith(")") else name)
        pending["lower"] = (duration_secs, time.perf_counter(), name,
                            traced)
    elif event in _HIT_SECONDS:
        pending[_HIT_SECONDS[event]] = duration_secs
    elif event == _BACKEND:
        t1 = time.perf_counter()
        _count("compiles")
        lower_s, lowered, lowered_name, traced = pending.pop(
            "lower", (0.0, t1, None, None))
        trace_s, traced_at = traced or (0.0, t1)
        cache = pending.pop("cache", "none")
        fields = {"fun_name": kw.get("fun_name") or lowered_name,
                  "trace_s": trace_s, "lower_s": lower_s,
                  "backend_s": duration_secs, "cache": cache}
        for name in _HIT_SECONDS.values():
            seconds = pending.pop(name, None)
            if cache == "hit" and seconds is not None:
                fields[name] = seconds
        telemetry.record_setup(
            "compile",
            min(t1 - duration_secs, lowered - lower_s,
                traced_at - trace_s),
            t1, **fields)


class CompileWatch:
    """This process's XLA compiles and its persistent-cache traffic
    from the moment it is made (a view of the process's counts, which
    the one pair of listeners of `_listen` keeps):

    * ``compiles`` — programs handed to the backend compiler or loaded
      from the cache (every new shape, every jitted function, every
      eager op's first use);
    * ``hits`` — of those, the ones the persistent cache served;
    * ``writes`` — the ones compiled and then written to it (JAX skips
      programs that compile in under a second).

    A window with no compile is ``compiles`` unchanged across it.
    Which programs they were, what each cost and what caused it is in
    ``telemetry.setup_records()``.
    """

    def __init__(self):
        _listen()
        with _lock:
            self._since = dict(_counts)

    def _delta(self, what: str) -> int:
        return _counts[what] - self._since[what]

    compiles = property(lambda self: self._delta("compiles"))
    hits = property(lambda self: self._delta("hits"))
    writes = property(lambda self: self._delta("writes"))
