"""A trace reduced by what the program says of itself (PR 25).

``xplane.py`` reads a trace from outside: op kinds, result shapes, the
benchmark's own ``bench.*`` spans.  Here the program's own names are
read:

* **device scopes**.  The jitted programs wrap their parts in
  ``jax.named_scope`` (``ray_tpu/_private/scopes.py``: ``attn``, ``mlp``,
  ``kv_pool``, ...).  A trace's op event carries the instruction's HLO
  text and no metadata (looked at by hand on the chip, PR 25), so the
  join is by instruction name, checked by result type and opcode: the
  program's registry keeps ``{instruction: {key: innermost scope}}``
  from the compiled text of every signature
  (``get_registry().scope_map("jit_step")``).  A name found under
  another key is another signature's instruction and counts as
  unscoped (``mismatched``).  So does time whose innermost scope only
  holds other scopes (``loss_and_grad``, ``layer_scan``): it belongs
  to no part of the model, and a layer that lost its scope would land
  there.  XLA's own copies of the K/V pool out of the layer scan carry
  no metadata of ours, or only the scan's: such a ``copy`` /
  ``dynamic-update-slice`` whose result has the pool's shape is counted
  as ``kv_pool`` by shape, and the table says how much.
* **host phases**.  The engine loop's ``raytpu.engine.*`` spans
  (``_private/telemetry.Phases``): one ``step`` per iteration and leaf
  phases that partition it, on the profiler's clock.  Idle time counts
  as attributed under a phase in which the host works; under a fence
  (the host waits) or the loop's own fragments it is only located.

The names below are ``ray_tpu/_private/scopes.py``'s, spelt again
because this file has to load against a program without that module
(``tests/benchmark/test_program_reduce.py`` holds the two equal); the
key function is imported from it where a scope map exists at all.

Against a program without these (the parent of PR 25) every function
finds nothing and returns None: the metric is left out of the line.
Everything below the loaders works on plain tuples, as in ``xplane.py``.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.harness import say
from benchmark.reduce import xplane
from benchmark.reduce.xplane import Event, Trace

#: the programs whose ops are attributed, by the name a trace gives them
PROGRAMS = ("jit_step", "jit_pool_step", "jit_paged_prefill_sample")
SPAN_PREFIX = "raytpu."
STEP_SPAN = "raytpu.engine.step"
ENGINE_PREFIX = "raytpu.engine."
FENCES = ("raytpu.engine.decode_fence", "raytpu.engine.prefill_fence")
DECODE_FENCE = "raytpu.engine.decode_fence"
#: spans of a step that ran a prefill beside its wave
PREFILLS = ("raytpu.engine.prefill_dispatch", "raytpu.engine.prefill_fence",
            "raytpu.engine.prefill_chunk")
LOOP = "loop"
UNSCOPED = "unscoped"
KV_POOL = "kv_pool"
LAYER_SCAN = "layer_scan"
#: scopes that only hold other scopes
CONTAINERS = ("loss_and_grad", LAYER_SCAN)
AMBIGUOUS = "ambiguous"
#: why an op's time counts as unscoped, beside a container's name
NO_SCOPE, MISMATCHED = "no_scope", "mismatched"
ScopeMap = Dict[str, Dict[str, str]]
_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")
_RESULT_DIMS = re.compile(r"^[a-z]+\d*\[([\d,]*)\]")
_POOL_OPS = ("copy", "dynamic-update-slice")


def instruction_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _INSTRUCTION.match(hlo_text)
    return m.group(1) if m else hlo_text.split(" ")[0].lstrip("%")


def result_dims(hlo_text: str) -> Optional[Tuple[int, ...]]:
    """Dimensions of an instruction's (first, untupled) result."""
    m = _RESULT_DIMS.match(hlo_text.split(" = ", 1)[-1].lstrip("("))
    if not m:
        return None
    return tuple(int(d) for d in m.group(1).split(",") if d)


def is_pool_copy(hlo_text: str, pool_dims: Optional[Sequence[int]]
                 ) -> bool:
    """A ``copy`` / ``dynamic-update-slice`` (or a fusion named after
    one) whose result is the K/V pool, whole (L, blocks, block, H, hd)
    or one layer of it (with or without a leading 1)."""
    if not pool_dims:
        return False
    kind = xplane.op_kind(hlo_text)
    if not any(op in kind for op in _POOL_OPS):
        return False
    dims = result_dims(hlo_text)
    pool = tuple(pool_dims)
    return dims is not None and dims in (pool, pool[1:],
                                         (1,) + pool[1:])


# ------------------------------------------------------------- device

def scope_of(text: str, scope_map: ScopeMap,
             pool_dims: Optional[Sequence[int]], key) -> Tuple[str, bool]:
    """(a registered scope, or the reason the op has none; claimed by
    the pool's shape).  The reasons: ``no_scope`` (no metadata of ours),
    ``mismatched`` (the name is another signature's instruction),
    ``ambiguous``, or the container the op sits in directly.  `key` is
    the function that made the map's keys."""
    keyed = scope_map.get(instruction_name(text))
    if keyed is None:
        scope = NO_SCOPE
    else:
        scope = keyed.get(key(text), MISMATCHED)
    if scope in (NO_SCOPE, LAYER_SCAN) and is_pool_copy(text, pool_dims):
        return KV_POOL, True
    return scope, False


def scope_times(trace: Trace, scope_maps: Dict[str, ScopeMap],
                pool_dims: Optional[Sequence[int]] = None
                ) -> Optional[Dict[str, object]]:
    """Device self time of the ops of the programs in `scope_maps`,
    summed by each op's scope, in nanoseconds averaged over devices.

    ``{"scopes": {scope: ns}, "unscoped": ns, "total": ns, "why":
    {reason: ns}, "by_shape": ns, "unscoped_ops": {reason:op kind:
    ns}, "signatures": {module event: [ns, ns checked]}, "mismatches":
    [(ns, event text, the map's keys)]}``.
    ``unscoped`` is the sum of ``why`` (`scope_of`'s reasons) and
    ``total`` the sum of ``scopes`` and ``unscoped``, so shares of it
    add up to one.  ``by_shape`` is the part of ``scopes["kv_pool"]``
    claimed by shape, not by name.  ``signatures`` holds, for each
    compiled signature the trace saw (``jit_step(8870...)``), its time
    and the part whose name checked under its key; ``mismatches`` the
    three longest events, of different names, that stood under other
    keys.  An op
    belongs to the program whose execution (``XLA Modules`` event)
    holds its start.  None where none of the programs ran."""
    # the program that keeps scope maps also says how it keyed them
    from ray_tpu._private.scopes import instruction_key

    acc: Dict[str, float] = {}
    why: Dict[str, float] = {}
    loose: Dict[str, float] = {}
    signatures: Dict[str, List[float]] = {}
    mismatches: List[Tuple[float, str, List[str]]] = []
    by_shape = 0.0
    found = False
    reasons = (NO_SCOPE, MISMATCHED, AMBIGUOUS) + CONTAINERS
    for dev in trace.devices:
        runs = sorted((s, s + d, xplane.module_name(name), name)
                      for name, s, d in dev.modules
                      if xplane.module_name(name) in scope_maps)
        if not runs:
            continue
        found = True
        starts = [r[0] for r in runs]
        for (text, s, _), (_, self_ns) in zip(
                dev.ops, xplane.self_times(dev.ops)):
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= runs[k][1]:
                continue                    # another program's op
            scope, shaped = scope_of(text, scope_maps[runs[k][2]],
                                     pool_dims, instruction_key)
            if shaped:
                by_shape += self_ns
            seen = signatures.setdefault(runs[k][3], [0.0, 0.0])
            seen[0] += self_ns
            if not shaped and scope not in (NO_SCOPE, MISMATCHED):
                seen[1] += self_ns
            if scope == MISMATCHED:
                mismatches.append((self_ns, text, sorted(
                    scope_maps[runs[k][2]][instruction_name(text)])))
            if scope in reasons:
                why[scope] = why.get(scope, 0.0) + self_ns
                kind = f"{scope}:{xplane.op_kind(text)}"
                loose[kind] = loose.get(kind, 0.0) + self_ns
            else:
                acc[scope] = acc.get(scope, 0.0) + self_ns
    if not found:
        return None
    n = len(trace.devices)
    scoped = {k: v / n for k, v in acc.items()}
    unscoped = sum(why.values()) / n
    return {"scopes": scoped, "unscoped": unscoped,
            "total": sum(scoped.values()) + unscoped,
            "why": {k: v / n for k, v in why.items()},
            "by_shape": by_shape / n,
            "unscoped_ops": {k: v / n for k, v in loose.items()},
            "signatures": {k: [v[0] / n, v[1] / n]
                           for k, v in signatures.items()},
            "mismatches": sorted({instruction_name(t): (ns, t, keys)
                                  for ns, t, keys in sorted(mismatches)
                                  }.values(), reverse=True)[:3]}


def _registry_maps() -> Dict[str, ScopeMap]:
    """The running program's scope maps, by trace name; empty against a
    program that keeps none."""
    try:
        from ray_tpu._private.device_stats import get_registry

        lookup = get_registry().scope_map
    except (ImportError, AttributeError):
        return {}
    maps = {}
    for program in PROGRAMS:
        found = lookup(program)
        if found:
            maps[program] = found
    return maps


def _pool_dims(run) -> Optional[Tuple[int, ...]]:
    """(L, blocks, block, H, hd) of a serving run's K/V pool; H is the
    number of K/V heads, which under grouped-query attention the family
    states apart (``n_kv_head``) from the query heads'."""
    eng = getattr(run, "engine", None)
    if eng is None or not hasattr(eng, "n_blocks"):
        return None
    cell = run.ctx.cell
    shape = cell.family.attention_shape(cell.config)
    return (shape["n_layer"], eng.n_blocks, eng.block,
            shape.get("n_kv_head", shape["n_head"]), shape["head_dim"])


def _cached(run, key: str, make):
    store = run.__dict__.setdefault("_program_reduce", {})
    if key not in store:
        store[key] = make()
    return store[key]


def device_table(run) -> Optional[Dict[str, object]]:
    """`scope_times` of one run's trace, once; its table is printed as
    the line ``[scope_shares]``."""
    trace = getattr(run, "trace", None)
    if trace is None:
        return None

    def make():
        maps = _registry_maps()
        table = scope_times(trace, maps, _pool_dims(run)) if maps else None
        if table and table["total"]:
            pct = lambda ns: round(100.0 * ns / table["total"], 3)  # noqa: E731
            loose = sorted(table["unscoped_ops"].items(),
                           key=lambda kv: -kv[1])[:8]
            say("scope_shares", programs=sorted(maps),
                device_ms=round(table["total"] / 1e6, 3),
                **{k: pct(v) for k, v in sorted(
                    table["scopes"].items(), key=lambda kv: -kv[1])},
                unscoped=pct(table["unscoped"]),
                unscoped_why={k: pct(v) for k, v in sorted(
                    table["why"].items(), key=lambda kv: -kv[1])},
                kv_pool_by_shape=pct(table["by_shape"]),
                # per compiled signature: % of the time, % checked
                signatures={k: [pct(v[0]), round(100.0 * v[1] / v[0], 2)]
                            for k, v in sorted(
                                table["signatures"].items()) if v[0]},
                unscoped_ops={k: pct(v) for k, v in loose},
                mismatches=[[pct(ns), text[:300], keys]
                            for ns, text, keys in table["mismatches"]])
        return table

    return _cached(run, "device", make)


def share_of(table, scope: str) -> Optional[float]:
    """`scope`'s part of a `scope_times` table, %; ``unscoped`` is what
    no part of the model claims (no scope, a container alone, or a
    name that did not check)."""
    if not table or not table["total"]:
        return None
    ns = table["unscoped"] if scope == UNSCOPED \
        else table["scopes"].get(scope, 0.0)
    return 100.0 * ns / table["total"]


def scope_share(run, scope: str) -> Optional[float]:
    """Device self time under `scope` (a name of
    ``ray_tpu/_private/scopes.py DEVICE_SCOPES``, or ``unscoped``) over
    the device time of the run's programs, %."""
    return share_of(device_table(run), scope)


# --------------------------------------------------------------- host

def overlap_ns(a: Sequence[Tuple[float, float]],
               b: Sequence[Tuple[float, float]]) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _clipped(spans: Iterable[Event], t0: float, t1: float
             ) -> List[Tuple[float, float]]:
    return xplane.union((max(s, t0), min(s + d, t1)) for _, s, d in spans)


def idle_by_phase(trace: Trace, spans: Sequence[Event]
                  ) -> Optional[Dict[str, object]]:
    """The first device's idle time inside the window, split over the
    engine's leaf phases by overlap: ``{"idle_ns": total, "phases":
    {phase: [idle ns under it, host ns it ran]}}``.  The leaves are the
    ``raytpu.engine.*`` spans but ``step``, and ``loop``: what of a
    step's span no leaf covers (the program opens no span for it).
    Idle time under no phase is what the engine's spans do not explain.
    None without engine spans."""
    engine = [e for e in spans if e[0].startswith(ENGINE_PREFIX)]
    if not engine:
        return None
    t0, t1 = trace.t0_ns, trace.t1_ns
    idle = xplane.subtract([(t0, t1)],
                           xplane.busy_intervals(trace.devices[0]))
    by_name: Dict[str, List[Event]] = {}
    for e in engine:
        by_name.setdefault(e[0][len(ENGINE_PREFIX):], []).append(e)
    steps = _clipped(by_name.pop("step", []), t0, t1)
    leaves = {name: _clipped(es, t0, t1) for name, es in by_name.items()}
    leaves["loop"] = xplane.subtract(steps, xplane.union(
        iv for ivs in leaves.values() for iv in ivs))
    return {"idle_ns": xplane.total(idle),
            "phases": {name: [overlap_ns(idle, ivs), xplane.total(ivs)]
                       for name, ivs in leaves.items() if ivs}}


_WAITS = tuple(f[len(ENGINE_PREFIX):] for f in FENCES)


def idle_split(table) -> Optional[Dict[str, float]]:
    """All idle seconds of a `idle_by_phase` table in four parts, %:
    ``attributed`` (under a leaf phase in which the host works),
    ``fences`` (the host waits for the device and the device is idle:
    launch and return latency), ``loop`` (the step's own fragments, no
    phase of their own) and ``outside`` (under no engine span)."""
    if not table or not table["idle_ns"]:
        return None
    pct = lambda ns: 100.0 * ns / table["idle_ns"]  # noqa: E731
    under = {name: v[0] for name, v in table["phases"].items()}
    fences = sum(under.get(name, 0.0) for name in _WAITS)
    loop = under.get(LOOP, 0.0)
    every = sum(under.values())
    return {"attributed": pct(every - fences - loop),
            "fences": pct(fences), "loop": pct(loop),
            "outside": pct(table["idle_ns"] - every)}


def idle_attributed_share_of(table) -> Optional[float]:
    """Idle seconds under a leaf phase in which the host works, over
    all idle seconds, %.  The fences and ``loop`` stay out of the
    numerator: together the spans cover the whole wall, so counting
    them would make the share true by construction."""
    split = idle_split(table)
    return None if split is None else split["attributed"]


def engine_host_ms(spans: Sequence[Event]) -> List[float]:
    """Per ``raytpu.engine.step`` that ran a decode wave and no prefill
    (holds a ``decode_fence``, no ``prefill_*``): its duration less the
    fence inside it, ms -- what the host does around one wave.  A step
    that also admits pays the prefill's dispatch, which is per request,
    not per step."""
    steps = sorted((s, s + d) for name, s, d in spans
                   if name == STEP_SPAN)
    if not steps:
        return []
    starts = [s for s, _ in steps]
    fenced = [0.0] * len(steps)
    waved = [False] * len(steps)
    prefilled = [False] * len(steps)
    for name, s, d in spans:
        if name not in FENCES and name not in PREFILLS:
            continue
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and s < steps[k][1]:
            if name in FENCES:
                fenced[k] += d
            waved[k] = waved[k] or name == DECODE_FENCE
            prefilled[k] = prefilled[k] or name in PREFILLS
    return [(e - s - f) / 1e6
            for (s, e), f, w, p in zip(steps, fenced, waved, prefilled)
            if w and not p]


def program_spans(run) -> Optional[List[Event]]:
    """The ``raytpu.*`` host spans of one run's trace file (read a
    second time: ``run.trace`` holds the ``bench.*`` spans only)."""
    trace = getattr(run, "trace", None)
    if trace is None:
        return None

    def make():
        try:
            path = xplane.find_xplane(run.ctx.trace_dir)
            return xplane.load(path, span_prefix=SPAN_PREFIX).host_spans
        except (FileNotFoundError, ValueError):
            return []

    return _cached(run, "spans", make) or None


def host_table(run) -> Optional[Dict[str, object]]:
    """`idle_by_phase` of one run, once; printed as ``[idle_by_phase]``
    (idle and host milliseconds per phase)."""
    spans = program_spans(run)
    if not spans:
        return None

    def make():
        table = idle_by_phase(run.trace, spans)
        if table:
            ms = lambda ns: round(ns / 1e6, 3)  # noqa: E731
            say("idle_by_phase", idle_ms=ms(table["idle_ns"]),
                **{f"{k}_pct": round(v, 3)
                   for k, v in idle_split(table).items()},
                steps=sum(e[0] == STEP_SPAN for e in spans),
                **{k: {"idle_ms": ms(v[0]), "host_ms": ms(v[1])}
                   for k, v in sorted(table["phases"].items(),
                                      key=lambda kv: -kv[1][0])})
        return table

    return _cached(run, "host", make)


def idle_attributed_share(run) -> Optional[float]:
    return idle_attributed_share_of(host_table(run))


def engine_host_ms_per_step(run) -> Optional[float]:
    from benchmark import estimators

    spans = program_spans(run)
    xs = engine_host_ms(spans) if spans else []
    return estimators.percentile(xs, 50) if xs else None
