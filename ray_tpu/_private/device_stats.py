"""Device/compiler-side perf observatory: the compiled-program registry.

Host-side telemetry (serve/telemetry.py, train/telemetry.py) records what
*requests* did; nothing so far records what the **compiler and devices**
are doing.  This module keeps one process-wide :class:`ProgramRegistry`
of named jitted programs (``serve.prefill``, ``serve.decode``,
``train.step``, ...) and, per program:

* **compiled cost model** — ``compiled.cost_analysis()`` FLOPs / bytes
  accessed and ``compiled.memory_analysis()`` peak HBM, harvested once
  per program from an AOT ``fn.lower(*args).compile()`` of the first
  signature seen (the executing jit cache is untouched — the harvest is
  a side lowering, gated by ``RAYTPU_DEVICE_STATS_COST=0`` for models
  where a second compile is too expensive);
* **recompile watchdog** — every never-seen argument signature
  (leaf shapes + dtypes) counts one XLA compile; a sliding window of
  compile timestamps raises a ``recompile_storm`` WARNING event when
  churn crosses the threshold (the classic symptom of unbucketed
  dynamic shapes eating the serving hot path);
* **live roofline MFU** — achieved FLOPs/s from the compiler's own
  FLOP count over the recent invoke-time window, divided by the
  devices' peak (no hand-counted ``6*N*D`` formula involved);
* **scope map** — from a like harvest at every fresh signature,
  ``{instruction name: {result type and opcode: innermost named
  scope}}`` of the compiled text (``_private/scopes.py``): a profiler
  trace names an executed op by its instruction, not by its metadata,
  so this small map is what joins a device event to ``attn`` / ``mlp``
  / ``kv_pool`` (``scope_map(program)``).

Everything is surfaced three ways: Prometheus metrics
(``device_program_compile_events_total`` / ``device_program_compile_seconds_total``
/ per-program gauges / ``device_hbm_bytes_in_use``), registry
``snapshot()`` blocks merged into ``engine_stats()``, and the dashboard
``/api/perf/programs`` endpoint.  ``device_memory_stats()`` wraps
``device.memory_stats()`` with a stable key set (values are ``None`` on
backends that do not report allocator stats, e.g. CPU).

``STATIC_PROGRAM_MAP`` ties graftcheck's static ProgramSpec catalog to
the runtime program names; the ``observatory-mapping`` lint rule keeps
the two views of "hot-path programs" from drifting.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ray_tpu._private import telemetry as _core

#: dense bf16 peak FLOPs/s per chip by device kind — the SINGLE source
#: of truth for the whole repo: bench.py's peak_flops_per_chip wraps
#: this module's lookup (it used to carry a duplicate table), and the
#: autopilot roofline attribution classifies against it.
_PEAK_FLOPS_TABLE = {
    "v5 lite": 197e12, "v5litepod": 197e12, "v5e": 197e12,
    "v5p": 459e12, "v4": 275e12, "v6 lite": 918e12, "v6e": 918e12,
    "cpu": 1e12,
}

#: HBM bandwidth bytes/s per chip by device kind (public spec sheets).
#: peak_flops / hbm_bw is the roofline RIDGE POINT in FLOPs/byte: a
#: program whose arithmetic intensity sits below it is bandwidth-bound
#: no matter how well the MXU is fed — the autopilot's compute-bound
#: vs HBM-bound attribution hinges on this table.
_PEAK_HBM_BW_TABLE = {
    "v5 lite": 819e9, "v5litepod": 819e9, "v5e": 819e9,
    "v5p": 2765e9, "v4": 1228e9, "v6 lite": 1640e9, "v6e": 1640e9,
    "cpu": 100e9,
}

#: runtime program names the observatory hooks register under.  The
#: graftcheck ``observatory-mapping`` rule checks STATIC_PROGRAM_MAP
#: values against this set, so a typo in the map fails lint instead of
#: silently pointing at a program that never exists.
KNOWN_PROGRAMS = frozenset({
    "serve.prefill", "serve.paged_prefill", "serve.decode",
    "serve.spec_verify", "serve.spec_draft",
    "serve.kv_handoff_export", "serve.kv_handoff_install",
    "serve.sharded_prefill", "serve.sharded_paged_prefill",
    "serve.sharded_decode",
    "serve.sharded_spec_verify", "serve.sharded_spec_draft",
    "serve.sharded_kv_handoff_export",
    "serve.sharded_kv_handoff_install",
    "train.step",
    "bench.train_step",
})

#: graftcheck ProgramSpec name -> runtime registry program name.  Every
#: spec in tools/graftcheck/programs.py must appear here (enforced by
#: the ``observatory-mapping`` lint rule) so the static auditor's view
#: of the hot path and the runtime observatory's stay in lockstep.
STATIC_PROGRAM_MAP: Dict[str, str] = {
    "gpt2_train_step": "train.step",
    "llama_train_step": "train.step",
    "fused_ce_fwd": "train.step",
    "fused_ce_bwd": "train.step",
    "gpt2_prefill_ragged": "serve.prefill",
    "llama_prefill_ragged": "serve.prefill",
    "gpt2_decode_step": "serve.decode",
    "gpt2_paged_decode_step": "serve.decode",
    "jamba_paged_decode_step": "serve.decode",
    "jamba_paged_prefill_bucket": "serve.paged_prefill",
    "gpt2_sharded_decode_step": "serve.sharded_decode",
    "gpt2_spec_verify_step": "serve.spec_verify",
    # chunked streaming prefill reuses the paged_prefill program (one
    # invoke per chunk), so the static spec maps to the same runtime
    # name — the observatory sees N invokes per chunked admission
    "gpt2_chunked_prefill": "serve.paged_prefill",
    "gpt2_paged_prefill_bucket": "serve.paged_prefill",
    # disaggregated prefill/decode handoff: the export gather on the
    # prefill replica and the donated install splice on the decode
    # replica (serve/engine.py kv_handoff_* programs)
    "gpt2_kv_handoff_export": "serve.kv_handoff_export",
    "gpt2_kv_handoff_install": "serve.kv_handoff_install",
}

_metrics_lock = threading.Lock()
_metrics: Optional[Dict[str, Any]] = None


def _device_metrics() -> Dict[str, Any]:
    """Process-wide metric singletons (same pattern as
    serve/telemetry.py — one registration per name no matter how many
    registries tests construct)."""
    global _metrics
    with _metrics_lock:
        if _metrics is None:
            from ray_tpu.util.metrics import Counter, Gauge

            tags = ("program",)
            _metrics = {
                "compile_events": Counter(
                    "device_program_compile_events_total",
                    "XLA compiles per named program (one per never-seen "
                    "argument signature)", tag_keys=tags),
                "compile_seconds": Counter(
                    "device_program_compile_seconds_total",
                    "walltime spent compiling each named program",
                    tag_keys=tags),
                "storms": Counter(
                    "device_recompile_storms_total",
                    "recompile-storm watchdog trips (compile churn over "
                    "the sliding window)", tag_keys=tags),
                "xla_flops": Gauge(
                    "device_program_xla_flops",
                    "compiler cost_analysis FLOPs per invocation",
                    tag_keys=tags),
                "peak_hbm": Gauge(
                    "device_program_peak_hbm_bytes",
                    "compiler memory_analysis peak HBM per program",
                    tag_keys=tags),
                "mfu": Gauge(
                    "device_program_mfu",
                    "live roofline MFU from compiler FLOPs over recent "
                    "invoke walltime", tag_keys=tags),
                "hbm_in_use": Gauge(
                    "device_hbm_bytes_in_use",
                    "allocator bytes_in_use per chip (None-reporting "
                    "backends publish nothing)", tag_keys=("device",)),
            }
        return _metrics


def _peak(table: Dict[str, float], device: Any) -> float:
    """Look one chip's peak up by ``device_kind``.  The "cpu" entry
    answers only for a device whose platform IS cpu (tests); a device
    kind the table does not know raises — a roofline against another
    chip's peak is a wrong number, not a default."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return table["cpu"]
    kind = device.device_kind.lower()
    for key, val in table.items():
        if key != "cpu" and key in kind:
            return val
    raise ValueError(
        f"no published peak for device kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to the tables in "
        f"ray_tpu/_private/device_stats.py with its source")


def peak_flops_per_chip(device: Any = None) -> float:
    """Dense bf16 peak FLOPs/s for one chip of the running backend."""
    return _peak(_PEAK_FLOPS_TABLE, device)


def peak_hbm_bytes_per_sec(device: Any = None) -> float:
    """HBM bandwidth bytes/s for one chip of the running backend."""
    return _peak(_PEAK_HBM_BW_TABLE, device)


def device_roofline(device: Any = None) -> Dict[str, Any]:
    """The roofline constants every attribution consumer needs, in one
    JSON-able block: peak FLOPs/s, HBM bytes/s, and their ratio — the
    ridge point in FLOPs/byte.  Embedded in ``engine_stats()`` (so a
    dashboard dump of a REMOTE engine carries the remote device's
    ridge, not the reader's) and used directly by
    ``ray_tpu.tools.autopilot``."""
    if device is None:
        import jax

        device = jax.devices()[0]
    flops = peak_flops_per_chip(device)
    bw = peak_hbm_bytes_per_sec(device)
    return {
        "backend": device.platform,
        "device_kind": device.device_kind,
        "peak_flops_per_chip": flops,
        "peak_hbm_bytes_per_sec": bw,
        "ridge_flops_per_byte": round(flops / bw, 1),
    }


def _signature(args: tuple, kwargs: dict) -> tuple:
    """Hashable (shape, dtype) tuple over every array leaf — the same
    compile-detection key train/telemetry.py uses (a never-seen
    signature means XLA traced and compiled a fresh executable)."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kwargs))
    return tuple(
        (tuple(leaf.shape), str(leaf.dtype))
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
        else (type(leaf).__name__, repr(leaf)[:32])
        for leaf in leaves)


def _cost_summary(compiled: Any) -> Dict[str, Any]:
    """Normalize ``cost_analysis()`` / ``memory_analysis()`` across jax
    versions and backends into one flat dict (missing pieces omitted,
    never raising — observability must not take down the program it
    observes)."""
    out: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            if "flops" in ca:
                out["xla_flops"] = float(ca["flops"])
            if "bytes accessed" in ca:
                out["bytes_accessed"] = float(ca["bytes accessed"])
    except Exception:  # noqa: BLE001 - backend without cost model
        pass
    try:
        ma = compiled.memory_analysis()
        arg_b = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
        out_b = int(getattr(ma, "output_size_in_bytes", 0) or 0)
        tmp_b = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
        # argument bytes the executable writes its results into (a
        # donated KV pool updated in place shows up here whole)
        alias_b = int(getattr(ma, "alias_size_in_bytes", 0) or 0)
        peak = getattr(ma, "peak_memory_in_bytes", None)
        if peak is None:
            # a backend without the gauge: live args + temps + the
            # outputs that are not written over an argument bounds the
            # executable's footprint
            peak = arg_b + tmp_b + out_b - alias_b
        out.update(argument_bytes=arg_b, output_bytes=out_b,
                   temp_bytes=tmp_b, alias_bytes=alias_b,
                   peak_hbm_bytes=int(peak))
    except Exception:  # noqa: BLE001
        pass
    if out.get("xla_flops") and out.get("bytes_accessed"):
        out["arithmetic_intensity"] = round(
            out["xla_flops"] / out["bytes_accessed"], 3)
    return out


def _scoped(compiled: Any) -> Optional[tuple]:
    """(HloModule name, ``scopes.ScopeMap``) from a compiled program's
    text; the text itself is not kept."""
    try:
        from ray_tpu._private import scopes

        text = compiled.as_text()
        return scopes.hlo_module_name(text), \
            scopes.scope_map_from_hlo(text)
    except Exception:  # noqa: BLE001 - backend without HLO text
        return None


#: fresh signatures of one program whose scope map is harvested: a
#: program has a handful (the prefill buckets); one that keeps
#: compiling (a recompile storm) must not pay a side compile each time
_SCOPED_SIGNATURES = 16


def cost_capture_enabled() -> bool:
    """The AOT harvest (cost model at a program's first signature, scope
    map at every fresh one) compiles a fresh signature a second time,
    which the persistent compile cache answers where it is on; huge
    models can turn it off process-wide."""
    return os.environ.get("RAYTPU_DEVICE_STATS_COST", "1") != "0"


class ProgramRegistry:
    """Per-process registry of named compiled programs.

    ``instrument(name, jitted)`` wraps a jitted callable: the wrapper
    always executes the original (the battle-tested jit-cache hot path
    is untouched), and on the side detects compiles by argument
    signature, harvests the compiler cost model once, feeds the
    recompile watchdog, and records invoke walltimes for the live MFU.
    All clocks are injectable for deterministic tests."""

    def __init__(self, storm_window_s: float = 60.0,
                 storm_threshold: int = 5, invoke_history: int = 512,
                 now: Optional[Callable[[], float]] = None):
        self.storm_window_s = float(storm_window_s)
        self.storm_threshold = int(storm_threshold)
        self._now = now or time.perf_counter
        self._invoke_history = int(invoke_history)
        self._lock = threading.Lock()
        self._m = _device_metrics()
        self._programs: Dict[str, Dict[str, Any]] = {}
        self._subscribers: List[Any] = []
        self._storm_subscribers: List[Any] = []

    # -- bookkeeping -------------------------------------------------------

    def _rec(self, program: str) -> Dict[str, Any]:
        rec = self._programs.get(program)
        if rec is None:
            rec = self._programs[program] = {
                "compile_events": 0,
                "compile_seconds": 0.0,
                # of compile_seconds, what instrument's side compile
                # took (its ``harvest`` set-up records, summed)
                "harvest_seconds": 0.0,
                # (end_ts, seconds) of each retained compile
                "compile_times": collections.deque(maxlen=256),
                "invokes": 0,
                "invoke_s": collections.deque(
                    maxlen=self._invoke_history),
                # (end_ts, dur_s) on the process monotonic clock —
                # the tracebus reads these to place device work on
                # the same timeline as request spans
                "invoke_events": collections.deque(
                    maxlen=self._invoke_history),
                "cost": {},
                # compiled texts' {instruction: {key: scope}}, every
                # signature's merged into one, and the HloModule name
                # (what a trace calls the program)
                "scope_map": None,
                "module": None,
                "storms": 0,
                "storm_active": False,
            }
        return rec

    def record_compile(self, program: str, seconds: float,
                       cost: Optional[Dict[str, Any]] = None,
                       now: Optional[float] = None,
                       scoped: Optional[tuple] = None,
                       harvest_seconds: float = 0.0) -> None:
        """One XLA compile of `program` taking `seconds` walltime;
        `cost` is a ``_cost_summary`` dict and `scoped` a ``(module
        name, scope map)`` pair when the harvest ran, which took
        `harvest_seconds` of `seconds`."""
        ts = self._now() if now is None else now
        with self._lock:
            rec = self._rec(program)
            rec["compile_events"] += 1
            rec["compile_seconds"] += float(seconds)
            rec["harvest_seconds"] += float(harvest_seconds)
            rec["compile_times"].append((ts, float(seconds)))
            if cost:
                rec["cost"] = dict(cost)
            if scoped:
                from ray_tpu._private.scopes import merge_scope_maps

                rec["module"] = scoped[0]
                rec["scope_map"] = merge_scope_maps(
                    rec["scope_map"] or {}, scoped[1])
            recent = [t for t, _ in rec["compile_times"]
                      if ts - t <= self.storm_window_s]
            storm = len(recent) >= self.storm_threshold
            fresh_storm = storm and not rec["storm_active"]
            rec["storm_active"] = storm
            if fresh_storm:
                rec["storms"] += 1
            events = rec["compile_events"]
        self._m["compile_events"].inc(tags={"program": program})
        self._m["compile_seconds"].inc(max(0.0, float(seconds)),
                                       tags={"program": program})
        if cost:
            if cost.get("xla_flops") is not None:
                self._m["xla_flops"].set(cost["xla_flops"],
                                         tags={"program": program})
            if cost.get("peak_hbm_bytes") is not None:
                self._m["peak_hbm"].set(cost["peak_hbm_bytes"],
                                        tags={"program": program})
        if fresh_storm:
            self._m["storms"].inc(tags={"program": program})
            from ray_tpu._private.events import report_event

            report_event(
                "device_stats", "recompile_storm",
                f"program {program!r} compiled {len(recent)} times in "
                f"the last {self.storm_window_s:g}s ({events} total) — "
                f"likely unbucketed dynamic shapes on the hot path",
                severity="WARNING", program=program,
                compiles_in_window=len(recent),
                window_s=self.storm_window_s)
            self._notify_storms(program)
        self._notify(program)

    def record_invoke(self, program: str, seconds: float,
                      now: Optional[float] = None) -> None:
        """One steady-state invoke of `program` taking `seconds`;
        `now` is the invoke's END instant (monotonic), defaulting to
        the registry clock at record time."""
        ts = self._now() if now is None else now
        with self._lock:
            rec = self._rec(program)
            rec["invokes"] += 1
            rec["invoke_s"].append(float(seconds))
            rec["invoke_events"].append((ts, float(seconds)))

    def invoke_events(self, prefix: Optional[str] = None
                      ) -> Dict[str, List[tuple]]:
        """Timestamped invoke windows per program — ``{name:
        [(end_ts, dur_s), ...]}`` on the monotonic clock, optionally
        filtered to names starting with `prefix`.  Compile events are
        readable the same way via ``compile_events`` below.  This is
        the tracebus's device lane: program dispatches render next to
        request spans without touching snapshot()'s pinned shape."""
        with self._lock:
            return {name: list(rec["invoke_events"])
                    for name, rec in self._programs.items()
                    if prefix is None or name.startswith(prefix)}

    def scope_map(self, program: str
                  ) -> Optional[Dict[str, Dict[str, str]]]:
        """``{instruction name: {instruction key: innermost registered
        scope}}`` of `program` over every signature it was compiled at
        (the prefill buckets), or None where no harvest ran.  `program`
        is the registry's name (``serve.decode``) or the HloModule's
        (``jit_pool_step``, what a trace's ``XLA Modules`` line says).
        XLA numbers a signature's ``fusion.N`` anew, so a name alone is
        no identity: a reader takes an event's scope only where the
        event's key (``scopes.instruction_key``: result type and
        opcode) is the entry's too."""
        with self._lock:
            rec = self._programs.get(program)
            if rec is None:
                rec = next((r for r in self._programs.values()
                            if r["module"] == program), None)
            return None if rec is None or rec["scope_map"] is None \
                else {name: dict(keyed)
                      for name, keyed in rec["scope_map"].items()}

    def compile_windows(self, prefix: Optional[str] = None
                        ) -> Dict[str, List[tuple]]:
        """Per-program compile windows ``{name: [(end_ts, dur_s),
        ...]}``: each retained compile's end instant and its own
        seconds (a fresh signature's whole first call, as
        ``compile_seconds`` sums them)."""
        with self._lock:
            return {name: list(rec["compile_times"])
                    for name, rec in self._programs.items()
                    if prefix is None or name.startswith(prefix)}

    # -- subscribers (e.g. EngineTelemetry.record_program_compile) ---------

    def subscribe(self, callback: Callable[[str], None]) -> None:
        """Call `callback(program)` on every compile event.  Bound
        methods are held by WeakMethod so short-lived engines do not
        leak through the process singleton."""
        import weakref

        try:
            ref = weakref.WeakMethod(callback)
        except TypeError:
            ref = (lambda cb=callback: cb)  # plain callables held hard
        with self._lock:
            self._subscribers.append(ref)

    def subscribe_storms(self, callback: Callable[[str], None]) -> None:
        """Call `callback(program)` on every FRESH recompile-storm
        trip (inactive → active transition, same condition that fires
        the WARNING event).  Weakly held like `subscribe` — the SLO
        watchdog (serve/slo.py via EngineTelemetry.record_storm) uses
        this to postmortem-dump the flight record when the decode path
        starts thrashing the compiler."""
        import weakref

        try:
            ref = weakref.WeakMethod(callback)
        except TypeError:
            ref = (lambda cb=callback: cb)
        with self._lock:
            self._storm_subscribers.append(ref)

    def _notify(self, program: str) -> None:
        self._fanout("_subscribers", program)

    def _notify_storms(self, program: str) -> None:
        self._fanout("_storm_subscribers", program)

    def _fanout(self, attr: str, program: str) -> None:
        with self._lock:
            refs = list(getattr(self, attr))
        dead = []
        for ref in refs:
            cb = ref()
            if cb is None:
                dead.append(ref)
                continue
            try:
                cb(program)
            except Exception:  # noqa: BLE001 - observer must not break
                pass
        if dead:
            with self._lock:
                setattr(self, attr, [r for r in getattr(self, attr)
                                     if r not in dead])

    # -- instrumentation ---------------------------------------------------

    def instrument(self, program: str, fn: Callable,
                   n_devices: int = 1) -> Callable:
        """Wrap a jitted callable with compile detection + cost harvest
        + invoke timing under `program`.  The wrapped function executes
        `fn` itself — same jit cache, same donation/sharding semantics."""
        import functools

        registry = self
        seen: set = set()
        seen_lock = threading.Lock()
        harvested = [False]

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            try:
                sig = _signature(args, kwargs)
            except Exception:  # noqa: BLE001
                sig = None
            fresh = False
            do_harvest = first = False
            if sig is not None:
                with seen_lock:
                    fresh = sig not in seen
                    if fresh:
                        ordinal = len(seen)
                        seen.add(sig)
                    # claim the first signature's cost harvest under
                    # the same lock: two threads compiling fresh
                    # signatures concurrently must not both take it
                    # (the unlocked check-then-act raced)
                    if (fresh and len(seen) <= _SCOPED_SIGNATURES
                            and cost_capture_enabled()
                            and hasattr(fn, "lower")):
                        do_harvest = True
                        first, harvested[0] = not harvested[0], True
            if fresh:
                cost = scoped = None
                harvest_s = 0.0
                # what compiles below says which program, which fresh
                # signature and which of the two parts asked for it
                # (the set-up records of _private/telemetry.py)
                why = {"program": program, "signature": ordinal}
                t0 = time.perf_counter()
                if do_harvest:
                    with _core.cause(part="harvest", **why):
                        try:
                            # side AOT compile of a fresh signature:
                            # the first one's cost/memory analysis, and
                            # every one's scope map (XLA names each
                            # signature's instructions anew) — the
                            # executing call below still goes through
                            # fn's jit cache
                            compiled = fn.lower(*args, **kwargs).compile()
                            if first:
                                cost = _cost_summary(compiled)
                            scoped = _scoped(compiled)
                        except Exception:  # noqa: BLE001
                            cost = None
                    # the whole side block, the text's print and parse
                    # too, which no compile record holds
                    t_harvest = time.perf_counter()
                    harvest_s = t_harvest - t0
                    _core.record_setup("harvest", t0, t_harvest, **why)
                # the first call with a fresh signature IS the compile:
                # its walltime (trace + XLA compile + run) lands in
                # compile_seconds and stays out of the steady-state
                # invoke window so the live MFU is not diluted
                with _core.cause(part="call", **why):
                    out = fn(*args, **kwargs)
                registry.record_compile(
                    program, time.perf_counter() - t0, cost=cost,
                    scoped=scoped, harvest_seconds=harvest_s)
                return out
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            registry.record_invoke(program,
                                   time.perf_counter() - t0)
            registry._maybe_update_mfu(program, n_devices)
            return out

        wrapped.__wrapped__ = fn
        if hasattr(fn, "lower"):
            wrapped.lower = fn.lower
        return wrapped

    def _maybe_update_mfu(self, program: str, n_devices: int) -> None:
        """Refresh the per-program MFU gauge every 64 invokes (cheap
        enough to never matter on a ms-scale decode step, frequent
        enough for a 5 s Prometheus scrape)."""
        with self._lock:
            rec = self._programs.get(program)
            if rec is None or rec["invokes"] % 64:
                return
        snap = self.snapshot(n_devices=n_devices).get(program)
        if snap and snap.get("mfu") is not None:
            self._m["mfu"].set(snap["mfu"], tags={"program": program})

    # -- sinks -------------------------------------------------------------

    def snapshot(self, prefix: Optional[str] = None,
                 n_devices: int = 1,
                 peak_flops: Optional[float] = None
                 ) -> Dict[str, Dict[str, Any]]:
        """Per-program observability block:

        ``{compile_events, compile_seconds, harvest_seconds, invokes,
        invoke_ms, xla_flops, peak_hbm_bytes, alias_bytes, temp_bytes,
        ..., mfu, recompile_storm}``.  ``harvest_seconds`` is the part
        of ``compile_seconds`` that `instrument`'s side compiles took.
        ``alias_bytes`` is what the executable
        updates in place of its (donated) arguments, ``temp_bytes``
        what it allocates beside arguments and results: a serving
        program that updates its KV pool where it lies reads the
        pool's bytes in the first and well under them in the second.

        ``mfu`` is the live roofline: compiler FLOPs per invocation over
        the mean recent invoke walltime, against ``n_devices`` chips'
        peak (None until both a cost harvest and an invoke landed)."""
        if peak_flops is None:
            peak_flops = peak_flops_per_chip()
        with self._lock:
            items = [(name, dict(rec), list(rec["invoke_s"]))
                     for name, rec in self._programs.items()]
        out: Dict[str, Dict[str, Any]] = {}
        for name, rec, invoke_s in items:
            if prefix and not name.startswith(prefix):
                continue
            cost = rec["cost"]
            block: Dict[str, Any] = {
                "compile_events": rec["compile_events"],
                "compile_seconds": round(rec["compile_seconds"], 3),
                "harvest_seconds": round(rec["harvest_seconds"], 3),
                "invokes": rec["invokes"],
                "invoke_ms": _core.summarize(
                    [s * 1e3 for s in invoke_s]),
                "xla_flops": cost.get("xla_flops"),
                "bytes_accessed": cost.get("bytes_accessed"),
                "arithmetic_intensity": cost.get(
                    "arithmetic_intensity"),
                "peak_hbm_bytes": cost.get("peak_hbm_bytes"),
                "alias_bytes": cost.get("alias_bytes"),
                "temp_bytes": cost.get("temp_bytes"),
                "recompile_storm": rec["storm_active"],
                "recompile_storms_total": rec["storms"],
                "mfu": None,
            }
            flops = cost.get("xla_flops")
            if flops and invoke_s:
                mean_s = sum(invoke_s) / len(invoke_s)
                if mean_s > 0:
                    block["mfu"] = round(
                        flops / mean_s /
                        (max(1, n_devices) * peak_flops), 6)
            out[name] = block
        return out

    def programs(self) -> List[str]:
        with self._lock:
            return sorted(self._programs)

    def reset(self) -> None:
        with self._lock:
            self._programs.clear()
            self._subscribers.clear()


_registry_lock = threading.Lock()
_registry: Optional[ProgramRegistry] = None


def get_registry() -> ProgramRegistry:
    """The process singleton every hook (serve, train, bench,
    dashboard) reports through."""
    global _registry
    with _registry_lock:
        if _registry is None:
            _registry = ProgramRegistry()
        return _registry


def reset_registry() -> None:
    """Testing hook: drop all recorded programs and subscribers."""
    with _registry_lock:
        if _registry is not None:
            _registry.reset()


_DEVICE_STAT_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                     "largest_alloc_size")


def device_memory_stats(devices: Optional[List[Any]] = None
                        ) -> List[Dict[str, Any]]:
    """Per-chip allocator snapshot with a STABLE key set: every entry
    carries id/platform/device_kind plus the ``_DEVICE_STAT_KEYS``
    (``None`` where the backend reports nothing — CPU's
    ``memory_stats()`` returns None).  TPU entries additionally feed the
    ``device_hbm_bytes_in_use`` gauge."""
    if devices is None:
        try:
            import jax

            devices = list(jax.devices())
        except Exception:  # noqa: BLE001 - no backend
            return []
    metrics = _device_metrics()
    out: List[Dict[str, Any]] = []
    for dev in devices:
        entry: Dict[str, Any] = {
            "id": getattr(dev, "id", None),
            "platform": getattr(dev, "platform", None),
            "device_kind": getattr(dev, "device_kind", None),
        }
        stats = None
        try:
            stats = dev.memory_stats()
        except Exception:  # noqa: BLE001 - backend without allocator API
            stats = None
        for key in _DEVICE_STAT_KEYS:
            entry[key] = (stats or {}).get(key)
        if entry["bytes_in_use"] is not None:
            metrics["hbm_in_use"].set(
                entry["bytes_in_use"],
                tags={"device": str(entry["id"])})
        out.append(entry)
    return out
