"""The jaxpr auditor: static rules over traced hot-path programs.

``jax.make_jaxpr`` gives the exact program XLA will see — so instead of
hoping a review catches a host callback, a stray float64, or a
rematerialized ``(B*T, V)`` logits buffer, we trace each canonical
program (``programs.py``) and walk its equations.  The rules here grew
out of real regressions measured on the chip by the builders' runs of
2026-07-29…31 and out of the one-off jaxpr asserts the test suite carried
before this module existed (``tests/test_fused_ce.py``,
``tests/test_decode_prefill.py`` — both now call the shared helpers
below, so each invariant lives in exactly one place).

Rules (ids as reported / suppressed):

* ``host-transfer`` — no callback / infeed / outfeed primitives inside
  a jitted program: each one is a device->host fence that stalls the
  async dispatch pipeline.
* ``f64`` — no float64/complex128 intermediate anywhere: one doubles
  HBM and runs the VPU at a fraction of rate (TPUs have no f64 units).
* ``f32-matmul`` — large matmuls must feed the MXU bf16 operands
  (f32 accumulation via ``preferred_element_type`` is the sanctioned
  pattern); an f32xf32 ``dot_general`` above the size threshold runs
  ~3x slower via multi-pass unless the program whitelists it.
* ``logits-buffer`` — no buffer of ``(..., padded_vocab)`` covering >=
  n_tokens rows may appear (fwd or bwd): the fused/streaming CE paths
  exist precisely to keep the (B*T, V) f32 tensor out of HBM.
* ``t0-scan`` — prefill must not scan over the prompt length: a
  length-T0 scan is the one-dispatch-per-token regression.
* ``donation`` — buffers we claim to donate must actually alias an
  output in the lowered program (``tf.aliasing_output``); silently
  dropped donation doubles parameter+optimizer HBM.
* ``hbm-budget`` — a liveness-based peak-bytes estimate of the traced
  program checked against the budget the program declares.
* ``collectives`` — a mesh-sharded program's COMPILED HLO must contain
  the collectives its sharding implies (``require_collectives``
  substrings, e.g. the tensor-axis all-gather/all-reduce of TP
  attention) and must NOT contain any ``forbid_hlo_shapes`` substring
  (full-shape buffers that prove an input was silently replicated —
  the KV pool showing up unsharded is the regression this catches).
* ``per-chip-hbm`` — the compiled per-partition footprint
  (``memory_analysis().argument_size_in_bytes + temp_size_in_bytes``,
  which SPMD partitioning reports per chip) checked against
  ``per_chip_hbm_budget_bytes``.  Unlike ``hbm-budget`` this sees the
  post-partitioning sizes, so a pool that stopped sharding trips it
  even if the traced (global) program is unchanged.

* ``pool-inplace`` — a serving program that takes the paged KV pool
  (``inplace_pool`` names the cache argument, which the spec also
  donates) must update it where it lies: the COMPILED program aliases
  the pool's K and V to its results (``alias_size_in_bytes``), and no
  ``copy`` and no ``dynamic-slice`` in it has the pool's shape, nor a
  ``copy`` one layer's.  A ``dynamic-update-slice`` whose update is one
  layer is a layer written or stacked back: a program that writes one
  column a row (a decode step) may hold none, its pool is read-only in
  the layer scan and ``decode_common.PagedKV`` lands the rows after
  it; one that writes a block of columns (a prefill, a verify block)
  declares ``pool_layer_writes=2``, each tensor's layer written back
  into the carried pool, and a third is the pool stacked as the scan's
  ``ys`` beside it.  The one layer-sized ``dynamic-slice`` the gather
  reads is allowed.  Where the cache also holds a recurrent family's
  per-slot state and snapshot pool (``RECURRENT_STATE``), their bytes
  must alias too, and no ``dynamic-slice`` or update of a whole
  state's shape may be left: the state stacked as a scan's ``ys`` (a
  second state beside the donated one) loses the alias or shows as one
  of these.  Whole-state ``copy`` instructions are counted
  (``state_copies``), not refused: the CPU compiler this audit uses
  leaves some at a nested loop's edge that the TPU's does not.

Sharded specs declare ``min_devices``; on hosts with fewer devices the
spec is skipped with an info note instead of failing (tier-1 forces 8
virtual CPU devices via tests/conftest.py, so CI always runs them).

The estimator is conservative-but-approximate: it walks the flattened
equation list with last-use liveness and adds each inner jaxpr's own
peak on top of the bytes live at its call site.  It exists to catch
order-of-magnitude blowups (an accidental dense logits buffer is ~100x
a nano budget), not to referee 10% regressions.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu.tools.graftcheck.core import Violation

#: primitive names that move data or control to the host mid-program
HOST_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "python_callback",
    "callback", "host_callback_call", "infeed", "outfeed",
    "debug_print",      # what jax.debug.print binds in jax 0.9
})

#: f32xf32 dot_generals at or above this many elements (largest
#: operand) are flagged; below it the MXU penalty is noise
F32_MATMUL_MIN_ELEMENTS = 1 << 16


# ---------------------------------------------------------------------------
# jaxpr walking helpers (shared with the test suite)
# ---------------------------------------------------------------------------

def _sub_jaxprs(val):
    """Yield every jaxpr hiding in one eqn param value (ClosedJaxpr,
    raw Jaxpr, or lists/tuples of either — pjit/scan carry one, cond a
    tuple)."""
    if hasattr(val, "jaxpr") and hasattr(getattr(val, "jaxpr"), "eqns"):
        yield val.jaxpr                      # ClosedJaxpr
    elif hasattr(val, "eqns"):
        yield val                            # raw Jaxpr
    elif isinstance(val, (list, tuple)):
        for item in val:
            yield from _sub_jaxprs(item)


def iter_eqns(jaxpr):
    """Depth-first generator over every equation in ``jaxpr`` and every
    nested jaxpr (pjit bodies, scan bodies, cond branches, custom-vjp
    calls, pallas kernels...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for inner in _sub_jaxprs(val):
                yield from iter_eqns(inner)


def collect_shapes(jaxpr) -> List[Tuple[tuple, str]]:
    """(shape, dtype-str) of every in/out aval of every deep equation."""
    out = []
    for eqn in iter_eqns(jaxpr):
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            if aval is not None and getattr(aval, "shape", None) is not None:
                out.append((tuple(aval.shape),
                            str(getattr(aval, "dtype", ""))))
    return out


def scan_lengths(jaxpr) -> List[int]:
    """``length`` param of every scan primitive anywhere in the jaxpr
    (the shared form of tests/test_decode_prefill.py's walker)."""
    return [eqn.params["length"] for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name == "scan"]


def logits_sized_shapes(fn, args, n_tokens: int,
                        padded_vocab: int) -> List[tuple]:
    """Shapes in ``jax.make_jaxpr(fn)(*args)`` whose trailing dim is
    ``padded_vocab`` and whose leading dims cover >= ``n_tokens`` rows —
    i.e. (B, T, V)/(B*T, V) logits-class buffers.  The shared form of
    tests/test_fused_ce.py's detector."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    return [s for s, _dt in collect_shapes(closed.jaxpr)
            if len(s) >= 2 and s[-1] == padded_vocab
            and math.prod(s[:-1]) >= n_tokens]


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    itemsize = getattr(dtype, "itemsize", 4)
    n = 1
    for d in (shape or ()):
        n *= int(d)
    return n * itemsize


def estimate_peak_bytes(jaxpr) -> int:
    """Liveness-based peak-bytes estimate of one jaxpr.

    Linear walk with last-use refcounts over the top-level equations;
    each inner jaxpr contributes its own recursive peak (minus its
    inputs, which are already live at the call site).  Scan bodies run
    per-iteration, so their internal peak — not length x peak — is the
    right charge."""
    eqns = list(jaxpr.eqns)
    last_use: Dict[Any, int] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not hasattr(v, "val"):          # skip Literals
                last_use[v] = i
    for v in jaxpr.outvars:
        if not hasattr(v, "val"):
            last_use[v] = len(eqns)            # outputs live to the end
    live: Dict[Any, int] = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        live[v] = _aval_bytes(getattr(v, "aval", None))
    cur = sum(live.values())
    peak = cur
    for i, eqn in enumerate(eqns):
        inner_extra = 0
        for val in eqn.params.values():
            for inner in _sub_jaxprs(val):
                inner_inputs = sum(
                    _aval_bytes(getattr(v, "aval", None))
                    for v in list(inner.invars) + list(inner.constvars))
                inner_extra = max(
                    inner_extra,
                    estimate_peak_bytes(inner) - inner_inputs)
        for v in eqn.outvars:
            if v not in live:
                b = _aval_bytes(getattr(v, "aval", None))
                live[v] = b
                cur += b
        peak = max(peak, cur + max(0, inner_extra))
        for v in eqn.invars:
            if hasattr(v, "val"):
                continue
            if last_use.get(v) == i and v in live:
                cur -= live.pop(v)
    return peak


# ---------------------------------------------------------------------------
# program specs + the rules
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramSpec:
    """One canonical hot-path program and the invariants it declares.

    ``build()`` returns ``(fn, args)`` — kept lazy so importing the
    auditor never constructs models.  ``forbid_logits`` is the
    ``(n_tokens, padded_vocab)`` pair of the logits-buffer rule;
    ``donate_argnums`` asserts those arguments' leaves alias outputs in
    the lowered program; ``hbm_budget_bytes`` is the declared ceiling
    for the peak estimate (see docs/static-analysis.md for how to size
    one)."""

    name: str
    build: Callable[[], Tuple[Callable, tuple]]
    forbid_logits: Optional[Tuple[int, int]] = None
    forbid_scan_lengths: Tuple[int, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    hbm_budget_bytes: Optional[int] = None
    allow_f32_matmul: bool = False
    skip_rules: Tuple[str, ...] = ()
    #: skip the spec (info note, not a failure) below this device count
    min_devices: int = 1
    #: substrings that must appear in the compiled HLO (collectives a
    #: sharded program cannot be correct without)
    require_collectives: Tuple[str, ...] = ()
    #: substrings that must NOT appear in the compiled HLO (full
    #: unsharded buffer shapes = silent replication)
    forbid_hlo_shapes: Tuple[str, ...] = ()
    #: compiled per-partition arg+temp byte ceiling
    per_chip_hbm_budget_bytes: Optional[int] = None
    #: argnum of a paged cache ({"k", "v", ...}, also in
    #: ``donate_argnums``) the compiled program must update in place
    inplace_pool: Optional[int] = None
    #: layer-sized ``dynamic-update-slice`` updates that program may
    #: hold: 0 (a decode step) or 2 (K's and V's layer written back)
    pool_layer_writes: int = 0


def _check_host_transfer(jaxpr, spec) -> List[Violation]:
    out = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in HOST_PRIMITIVES or "callback" in name:
            out.append(Violation(
                "host-transfer",
                f"primitive '{name}' performs a host round-trip inside "
                f"the jitted program", program=spec.name))
    return out


def _check_f64(jaxpr, spec) -> List[Violation]:
    out = []
    seen = set()
    for shape, dtype in collect_shapes(jaxpr):
        if dtype in ("float64", "complex128") and (shape, dtype) not in seen:
            seen.add((shape, dtype))
            out.append(Violation(
                "f64",
                f"{dtype} buffer of shape {shape} in the traced program "
                f"(TPUs have no f64 units; dtype policy is bf16 compute "
                f"/ f32 accumulate)", program=spec.name))
    return out


def _check_f32_matmul(jaxpr, spec) -> List[Violation]:
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        avals = [getattr(v, "aval", None) for v in eqn.invars]
        if any(a is None for a in avals):
            continue
        if not all(str(getattr(a, "dtype", "")) == "float32"
                   for a in avals):
            continue
        biggest = max(math.prod(a.shape) if a.shape else 1
                      for a in avals)
        if biggest >= F32_MATMUL_MIN_ELEMENTS:
            shapes = [tuple(a.shape) for a in avals]
            out.append(Violation(
                "f32-matmul",
                f"f32xf32 dot_general over {shapes} (>= "
                f"{F32_MATMUL_MIN_ELEMENTS} elements) — feed the MXU "
                f"bf16 operands with preferred_element_type=f32, or "
                f"whitelist via allow_f32_matmul", program=spec.name))
    return out


def _check_logits_buffer(jaxpr, spec) -> List[Violation]:
    n_tokens, padded_vocab = spec.forbid_logits
    hits = [s for s, _dt in collect_shapes(jaxpr)
            if len(s) >= 2 and s[-1] == padded_vocab
            and math.prod(s[:-1]) >= n_tokens]
    if hits:
        return [Violation(
            "logits-buffer",
            f"(>= {n_tokens} tokens, {padded_vocab})-sized buffers "
            f"materialized: {sorted(set(hits))} — the fused/streaming "
            f"CE contract forbids a full logits tensor",
            program=spec.name)]
    return []


def _check_t0_scan(jaxpr, spec) -> List[Violation]:
    lengths = scan_lengths(jaxpr)
    out = []
    for forbidden in spec.forbid_scan_lengths:
        if forbidden in lengths:
            out.append(Violation(
                "t0-scan",
                f"scan of forbidden length {forbidden} traced (scan "
                f"lengths: {sorted(set(lengths))}) — prompt processing "
                f"regressed to per-token dispatches",
                program=spec.name))
    return out


def _check_donation(fn, args, spec) -> List[Violation]:
    import jax

    expected = 0
    for argnum in spec.donate_argnums:
        expected += len(jax.tree_util.tree_leaves(args[argnum]))
    lowered = jax.jit(
        fn, donate_argnums=spec.donate_argnums).lower(*args)
    aliased = lowered.as_text().count("tf.aliasing_output")
    if aliased < expected:
        return [Violation(
            "donation",
            f"only {aliased} of {expected} donated buffers alias an "
            f"output in the lowered program — dropped donation doubles "
            f"the HBM those arguments occupy", program=spec.name)]
    return []


def _check_compiled(fn, args, spec) -> Tuple[List[Violation],
                                             Dict[str, Any]]:
    """Lower + compile once and run the HLO-text rules: required
    collectives, forbidden (replicated) shapes, and the per-partition
    footprint.  Compilation is the only way to see these — collectives
    are inserted by the SPMD partitioner, after the jaxpr."""
    import jax

    out: List[Violation] = []
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    if "collectives" not in spec.skip_rules:
        for pat in spec.require_collectives:
            if pat not in hlo:
                out.append(Violation(
                    "collectives",
                    f"compiled program contains no '{pat}' — the mesh "
                    f"sharding this spec declares implies one; the "
                    f"inputs are likely no longer committed to the "
                    f"mesh", program=spec.name))
        for pat in spec.forbid_hlo_shapes:
            if pat in hlo:
                out.append(Violation(
                    "collectives",
                    f"compiled program materializes forbidden "
                    f"full-shape buffer '{pat}' — an input meant to be "
                    f"sharded is being replicated", program=spec.name))
    info: Dict[str, Any] = {}
    if spec.per_chip_hbm_budget_bytes \
            and "per-chip-hbm" not in spec.skip_rules:
        ma = compiled.memory_analysis()
        # arg+temp is the per-partition resident footprint; outputs
        # alias args under donation so counting them would double-bill
        per_chip = int(ma.argument_size_in_bytes
                       + ma.temp_size_in_bytes)
        info["per_chip_hbm_bytes"] = per_chip
        info["per_chip_hbm_budget_bytes"] = \
            spec.per_chip_hbm_budget_bytes
        if per_chip > spec.per_chip_hbm_budget_bytes:
            out.append(Violation(
                "per-chip-hbm",
                f"compiled per-chip footprint {per_chip / 2**20:.2f} "
                f"MiB exceeds the declared per-chip budget "
                f"{spec.per_chip_hbm_budget_bytes / 2**20:.2f} MiB",
                program=spec.name))
    return out, info


#: ``%name = type[dims]{layout} opcode(operands...`` in compiled text
_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \w+\[([\d,]*)\][^ ]* "
    r"([\w\-]+)\((.*)$")


#: the cache entries of a recurrent family's state and snapshot pool
RECURRENT_STATE = ("conv", "ssm", "snap_conv", "snap_ssm")


def pool_moves(hlo: str, pool: Tuple[int, ...], whole_only: bool = False):
    """(opcode, instruction, dims moved) for every instruction of a
    compiled module's text (fused computations included) that
    materialises the K/V pool of shape `pool`, or one layer of it,
    beside the pool: a ``copy`` of either, a ``dynamic-slice`` of the
    pool, a ``dynamic-update-slice`` whose UPDATE is either (a layer
    written or stacked back).  The layer-sized ``dynamic-slice`` a
    gather reads and row-sized updates are not moves."""
    big = (pool,) if whole_only else (pool, pool[1:], (1,) + pool[1:])
    found = []
    for line in hlo.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m:
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            # operands print bare (``%a, %b``) or typed
            # (``f32[2,3]{1,0} %a``): keep the names
            found.append((m.group(1), dims, m.group(3),
                          re.findall(r"%([\w.\-]+)", m.group(4))))
    dims_of = {name: dims for name, dims, _op, _ops in found}
    for name, dims, op, operands in found:
        if op == "copy" and dims in big:
            yield op, name, dims
        elif op == "dynamic-slice" and dims == pool:
            yield op, name, dims
        elif op == "dynamic-update-slice" and len(operands) > 1 \
                and dims_of.get(operands[1]) in big:
            yield op, name, dims_of[operands[1]]


def _check_pool_inplace(fn, args, spec) -> Tuple[List[Violation],
                                                 Dict[str, Any]]:
    """Compile with the spec's donation and hold the program to
    "no pool-sized or layer-sized K/V buffer other than the pool"."""
    import jax

    cache = args[spec.inplace_pool]
    pool = tuple(cache["k"].shape)
    pool_bytes = sum(cache[n].size * cache[n].dtype.itemsize
                     for n in ("k", "v"))
    # a recurrent family's per-slot state and snapshot pool
    # (models/jamba_decode.py): donated with the pool, held to the same
    state = [cache[n] for n in RECURRENT_STATE if n in cache]
    state_bytes = sum(a.size * a.dtype.itemsize for a in state)
    compiled = jax.jit(
        fn, donate_argnums=spec.donate_argnums).lower(*args).compile()
    out: List[Violation] = []
    ma = compiled.memory_analysis()
    info = {"alias_bytes": int(ma.alias_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "pool_bytes": int(pool_bytes),
            "state_bytes": int(state_bytes)}
    if info["alias_bytes"] < pool_bytes + state_bytes:
        out.append(Violation(
            "pool-inplace",
            f"the compiled program aliases {info['alias_bytes']} bytes "
            f"of its arguments to its results, the K/V pool has "
            f"{pool_bytes} and the recurrent state {state_bytes}: one "
            f"of them is copied, not updated in place",
            program=spec.name))
    hlo = compiled.as_text()
    info["state_copies"] = 0
    for dims in sorted({tuple(a.shape) for a in state}):
        for op, name, moved in pool_moves(hlo, dims, whole_only=True):
            if op == "copy":
                # not held against the program: this audit compiles for
                # the CPU, whose compiler copies a buffer carried into a
                # nested loop at the loop's edge, where the TPU's does
                # not (the walk over layers of two kinds nests two
                # scans; the TPU text holds no such copy: PERF.md, PR
                # 28).  A state stacked as `ys` loses the alias above.
                info["state_copies"] += 1
                continue
            out.append(Violation(
                "pool-inplace",
                f"compiled `{op}` {name} moves a {list(moved)} buffer, "
                f"the shape of the recurrent state: it is materialised "
                f"beside the state (a scan's stacked `ys`)",
                program=spec.name))
    moves = list(pool_moves(hlo, pool))
    layer_writes = [m for m in moves
                    if m[0] == "dynamic-update-slice" and m[2] != pool]
    info["pool_layer_writes"] = len(layer_writes)
    if len(layer_writes) <= spec.pool_layer_writes:
        moves = [m for m in moves if m not in layer_writes]
    for op, name, moved in moves:
        out.append(Violation(
            "pool-inplace",
            f"compiled `{op}` {name} moves a {list(moved)} buffer "
            f"(the K/V pool is {list(pool)}; "
            f"{spec.pool_layer_writes} layers written back allowed): "
            f"the pool, or a layer of it, is materialised beside the "
            f"pool", program=spec.name))
    return out, info


def audit_program(spec: ProgramSpec
                  ) -> Tuple[List[Violation], Dict[str, Any]]:
    """Trace one program and run every rule it doesn't skip.  Returns
    (violations, info) where info carries the audit telemetry that
    rides into the JSON report (eqn count, peak-HBM estimate)."""
    import jax

    if len(jax.devices()) < spec.min_devices:
        return [], {"skipped": f"requires >= {spec.min_devices} "
                               f"devices, have {len(jax.devices())}"}
    fn, args = spec.build()
    closed = jax.make_jaxpr(fn)(*args)
    jaxpr = closed.jaxpr
    checks = {
        "host-transfer": lambda: _check_host_transfer(jaxpr, spec),
        "f64": lambda: _check_f64(jaxpr, spec),
        "f32-matmul": lambda: (
            [] if spec.allow_f32_matmul
            else _check_f32_matmul(jaxpr, spec)),
        "logits-buffer": lambda: (
            _check_logits_buffer(jaxpr, spec)
            if spec.forbid_logits else []),
        "t0-scan": lambda: _check_t0_scan(jaxpr, spec),
        "donation": lambda: (
            _check_donation(fn, args, spec)
            if spec.donate_argnums else []),
    }
    violations: List[Violation] = []
    for rule, run in checks.items():
        if rule not in spec.skip_rules:
            violations.extend(run())
    info: Dict[str, Any] = {
        "eqns": sum(1 for _ in iter_eqns(jaxpr)),
    }
    if "hbm-budget" not in spec.skip_rules:
        peak = estimate_peak_bytes(jaxpr)
        info["peak_hbm_bytes"] = int(peak)
        info["hbm_budget_bytes"] = spec.hbm_budget_bytes
        if spec.hbm_budget_bytes and peak > spec.hbm_budget_bytes:
            violations.append(Violation(
                "hbm-budget",
                f"estimated peak HBM {peak / 2**20:.2f} MiB exceeds the "
                f"declared budget "
                f"{spec.hbm_budget_bytes / 2**20:.2f} MiB",
                program=spec.name))
    if (spec.require_collectives or spec.forbid_hlo_shapes
            or spec.per_chip_hbm_budget_bytes):
        vs, compiled_info = _check_compiled(fn, args, spec)
        violations.extend(vs)
        info.update(compiled_info)
    if spec.inplace_pool is not None \
            and "pool-inplace" not in spec.skip_rules:
        vs, pool_info = _check_pool_inplace(fn, args, spec)
        violations.extend(vs)
        info.update(pool_info)
    return violations, info


def audit_programs(specs) -> Tuple[List[Violation],
                                   Dict[str, Dict[str, Any]]]:
    """Audit every spec; a program whose build/trace itself crashes is
    reported as an ``audit-error`` violation instead of killing the
    whole run (the other programs' results still matter)."""
    violations: List[Violation] = []
    infos: Dict[str, Dict[str, Any]] = {}
    for spec in specs:
        try:
            vs, info = audit_program(spec)
        except Exception as e:  # noqa: BLE001 - surfaced as a finding
            violations.append(Violation(
                "audit-error",
                f"tracing failed: {type(e).__name__}: {str(e)[:200]}",
                program=spec.name))
            infos[spec.name] = {"error": type(e).__name__}
            continue
        violations.extend(vs)
        infos[spec.name] = info
    return violations, infos
