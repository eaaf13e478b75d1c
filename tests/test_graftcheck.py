"""graftcheck acceptance suite.

Three layers:

1. **repo guard** — the full check (`run_repo_check`) over this
   checkout must come back clean; this is the tier-1 hook that makes
   every hot-path invariant a test failure.
2. **planted jaxpr violations** — each auditor rule must fire on a
   minimal program that breaks exactly it (host transfer, f64, f32
   matmul, logits buffer, length-T0 scan, dropped donation, HBM
   budget), proving none of the rules is vacuously green.
3. **lint fixtures** — each ast rule gets a positive snippet, a
   suppressed variant, and an out-of-scope/clean variant.

The suite also carries the non-vacuity sentinels inherited from the
retired tests/test_metrics_guard.py and tests/test_ops_kernel_guard.py
(the rules themselves moved into graftcheck).
"""

import json
import pathlib
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.tools import graftcheck as gc
from ray_tpu.tools.graftcheck.jaxpr_audit import ProgramSpec, audit_program
from ray_tpu.tools.graftcheck.lint import (KERNEL_EXPORTS,
                                           _autopilot_attribution,
                                           _observatory_mapping,
                                           _pallas_interpret_tests,
                                           lint_repo, lint_source,
                                           pallas_modules)

pytestmark = pytest.mark.fast

ROOT = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# 1. the repo guard
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def repo_report():
    """One full run (lint + 7 traced programs) shared by the guard
    tests below — tracing the train steps is the expensive part."""
    return gc.run_repo_check(ROOT)


def test_repo_is_clean(repo_report):
    assert repo_report["ok"], gc.render_text(repo_report)


def test_repo_audit_covers_canonical_programs(repo_report):
    audited = set(repo_report["programs"])
    assert {"gpt2_train_step", "llama_train_step",
            "gpt2_prefill_ragged", "llama_prefill_ragged",
            "gpt2_decode_step", "gpt2_sharded_decode_step",
            "gpt2_spec_verify_step", "gpt2_chunked_prefill",
            "fused_ce_fwd", "fused_ce_bwd"} <= audited
    for name, info in repo_report["programs"].items():
        assert "error" not in info, f"{name} failed to trace: {info}"
        assert "skipped" not in info, \
            f"{name} skipped under CI's forced 8 devices: {info}"
        assert info["eqns"] > 0
        assert info["peak_hbm_bytes"] > 0


def test_repo_sharded_spec_ran_compiled_rules(repo_report):
    # conftest forces 8 CPU devices, so the sharded spec must have
    # compiled and reported its per-partition footprint — and a
    # sharded pool means strictly less than the global estimate
    info = repo_report["programs"]["gpt2_sharded_decode_step"]
    assert info["per_chip_hbm_bytes"] > 0
    assert info["per_chip_hbm_bytes"] < info["peak_hbm_bytes"]


def test_repo_suppressions_are_visible(repo_report):
    # serve/llm.py and serve/engine.py carry deliberate host fences behind
    # disable comments; the report must surface (not hide) that they exist
    # (round 11 moved the finish-path fence into a sync helper, so
    # the count dropped from 7 to 6; PR 28 moved the decode wave's
    # fence into one, `_land_wave`: 5)
    assert repo_report["summary"]["n_suppressed"] >= 5
    assert repo_report["summary"]["files_scanned"] > 100


def test_repo_metric_scan_not_vacuous():
    # inherited from the retired test_metrics_guard.py: the lint scan
    # must actually SEE the telemetry metrics
    violations, stats = lint_repo(ROOT)
    names = [v for v in violations if v.rule == "metric-name"]
    assert not names, names
    assert "serve_ttft_ms" in stats["metric_names"]
    assert "train_step_time_ms" in stats["metric_names"]
    assert len(stats["metric_names"]) >= 15


def test_pallas_module_detector_not_vacuous():
    # inherited from the retired test_ops_kernel_guard.py
    stems = pallas_modules(ROOT)
    assert "flash_attention" in stems
    assert "fused_ce" in stems


def test_new_pallas_kernel_needs_both_chipless_checks(tmp_path):
    """A kernel needs an interpret-mode numerics test AND an AOT compile
    for the described TPU topology (tests/test_tpu_compile.py)."""
    ops = tmp_path / "ray_tpu" / "ops"
    ops.mkdir(parents=True)
    (ops / "newkern.py").write_text("pl.pallas_call(kernel)\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    found = _pallas_interpret_tests(tmp_path)
    assert len(found) == 2
    assert all(v.rule == "pallas-interpret-test" for v in found)
    (tests / "test_newkern.py").write_text("run(interpret=True)\n")
    (tests / "test_tpu_compile.py").write_text("# flash only\n")
    found = _pallas_interpret_tests(tmp_path)
    assert len(found) == 1 and "test_tpu_compile.py" in found[0].message
    (tests / "test_tpu_compile.py").write_text(
        "from ray_tpu.ops.newkern import k\n")
    assert _pallas_interpret_tests(tmp_path) == []


def test_kernel_exports_not_vacuous():
    import ray_tpu.ops as ops

    for name in KERNEL_EXPORTS:
        assert name in ops.__all__
        assert callable(getattr(ops, name))


def test_observatory_mapping_clean():
    # round 10: the repo's own spec->runtime map must be complete
    assert _observatory_mapping() == []


def test_observatory_mapping_planted_violations(monkeypatch):
    from ray_tpu._private import device_stats as ds

    # a spec with no runtime mapping
    missing = dict(ds.STATIC_PROGRAM_MAP)
    spec = next(iter(missing))
    del missing[spec]
    monkeypatch.setattr(ds, "STATIC_PROGRAM_MAP", missing)
    rules = {v.rule for v in _observatory_mapping()}
    assert rules == {"observatory-mapping"}

    # a mapping pointing at a program the runtime never registers
    bad_value = dict(ds.STATIC_PROGRAM_MAP)
    bad_value[spec] = "serve.bogus"
    monkeypatch.setattr(ds, "STATIC_PROGRAM_MAP", bad_value)
    msgs = [v.message for v in _observatory_mapping()]
    assert any("not a KNOWN_PROGRAMS" in m for m in msgs)

    # a stale mapping for a spec that no longer exists
    stale = dict(ds.STATIC_PROGRAM_MAP)
    stale[spec] = ds.STATIC_PROGRAM_MAP[spec]
    stale["ghost_spec"] = "train.step"
    monkeypatch.setattr(ds, "STATIC_PROGRAM_MAP", stale)
    msgs = [v.message for v in _observatory_mapping()]
    assert any("matches no" in m for m in msgs)


def test_autopilot_attribution_clean():
    # round 12: the autopilot's knob catalog must cover every runtime
    # program the static map targets
    assert _autopilot_attribution() == []


def test_autopilot_attribution_planted_violations(monkeypatch):
    from ray_tpu.tools.autopilot import attribution as ap

    # a runtime program the static map targets with no knob entry
    missing = dict(ap.PROGRAM_KNOBS)
    del missing["train.step"]
    monkeypatch.setattr(ap, "PROGRAM_KNOBS", missing)
    viols = _autopilot_attribution()
    assert {v.rule for v in viols} == {"autopilot-attribution"}
    assert any("'train.step'" in v.message for v in viols)

    # a knob entry for a program the runtime never registers
    bogus = dict(ap.PROGRAM_KNOBS)
    bogus["serve.bogus"] = ("spec_k",)
    monkeypatch.setattr(ap, "PROGRAM_KNOBS", bogus)
    msgs = [v.message for v in _autopilot_attribution()]
    assert any("not a KNOWN_PROGRAMS" in m for m in msgs)


# ---------------------------------------------------------------------------
# 2. planted jaxpr violations — every auditor rule must fire
# ---------------------------------------------------------------------------

def _spec(fn, args, **kw):
    return ProgramSpec(name="planted", build=lambda: (fn, args), **kw)


def _rules(violations):
    return {v.rule for v in violations}


def test_planted_host_transfer_detected():
    def fn(x):
        jax.debug.print("leak {}", x[0])
        return x * 2

    vs, _ = audit_program(_spec(fn, (jnp.zeros((8,)),)))
    assert "host-transfer" in _rules(vs)


def test_planted_f64_detected():
    def fn(x):
        return (x.astype(jnp.float64) * 2.0).astype(jnp.float32)

    with jax.enable_x64(True):
        vs, _ = audit_program(_spec(fn, (jnp.zeros((8, 8)),)))
    assert "f64" in _rules(vs)


def test_planted_f32_matmul_detected():
    x = jnp.zeros((256, 256), jnp.float32)   # 65536 elems = threshold
    vs, _ = audit_program(_spec(lambda a: a @ a.T, (x,)))
    assert "f32-matmul" in _rules(vs)
    # the whitelist silences exactly that rule
    vs, _ = audit_program(
        _spec(lambda a: a @ a.T, (x,), allow_f32_matmul=True))
    assert "f32-matmul" not in _rules(vs)


def test_planted_logits_buffer_detected():
    h = jnp.zeros((128, 64), jnp.float32)
    w = jnp.zeros((512, 64), jnp.float32)
    vs, _ = audit_program(_spec(lambda a, b: a @ b.T, (h, w),
                                forbid_logits=(128, 512)))
    assert "logits-buffer" in _rules(vs)
    # a buffer with fewer rows than n_tokens (e.g. a transposed
    # (d_model, V) weight view) must NOT trip the rule
    small = jnp.zeros((64, 64), jnp.float32)
    vs, _ = audit_program(_spec(lambda a, b: a @ b.T, (small, w),
                                forbid_logits=(128, 512)))
    assert "logits-buffer" not in _rules(vs)


def test_planted_t0_scan_detected():
    def fn(xs):
        def body(c, x):
            return c + x, x

        c, _ys = jax.lax.scan(body, jnp.zeros(()), xs)
        return c

    vs, _ = audit_program(_spec(fn, (jnp.zeros((64,)),),
                                forbid_scan_lengths=(64,)))
    assert "t0-scan" in _rules(vs)
    vs, _ = audit_program(_spec(fn, (jnp.zeros((64,)),),
                                forbid_scan_lengths=(128,)))
    assert "t0-scan" not in _rules(vs)


def test_planted_dropped_donation_detected():
    # a reduction's output can never alias its donated input, so the
    # lowered program records no tf.aliasing_output for it
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        vs, _ = audit_program(_spec(lambda x: jnp.sum(x),
                                    (jnp.zeros((32, 32)),),
                                    donate_argnums=(0,)))
    assert "donation" in _rules(vs)
    # same-shape output CAN alias: the rule stays quiet
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        vs, _ = audit_program(_spec(lambda x: x + 1.0,
                                    (jnp.zeros((32, 32)),),
                                    donate_argnums=(0,)))
    assert "donation" not in _rules(vs)


def test_planted_hbm_budget_blowup_detected():
    x = jnp.zeros((256, 256), jnp.float32)   # 256 KiB input
    vs, info = audit_program(
        _spec(lambda a: a @ a.T, (x,), allow_f32_matmul=True,
              hbm_budget_bytes=100 * 1024))
    assert "hbm-budget" in _rules(vs)
    assert info["peak_hbm_bytes"] > 100 * 1024


def test_planted_spec_verify_full_logits_detected():
    """The spec-verify ProgramSpec's whole point is that verify logits
    are (B, k+1, V), never the full-sequence class — a verify that
    materializes the (B*max_seq, V) buffer must trip the rule under
    the real spec's own constraints (and the real spec must carry the
    KV-pool donation + budget the engine depends on)."""
    from ray_tpu.tools.graftcheck.programs import default_programs

    spec = next(s for s in default_programs()
                if s.name == "gpt2_spec_verify_step")
    assert spec.donate_argnums == (1,)
    assert spec.hbm_budget_bytes > 0
    fn, args = spec.build()

    def bad(p, c, b, k):
        out, n_acc, cache = fn(p, c, b, k)
        full = jnp.zeros(spec.forbid_logits, jnp.float32)  # planted
        return out, n_acc + jnp.sum(full).astype(jnp.int32), cache

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # cpu donation warning
        vs, _ = audit_program(
            ProgramSpec(name="planted", build=lambda: (bad, args),
                        forbid_logits=spec.forbid_logits,
                        donate_argnums=spec.donate_argnums,
                        allow_f32_matmul=True))
    assert "logits-buffer" in _rules(vs)


def test_planted_chunked_prefill_full_sequence_detected():
    """The chunked-prefill ProgramSpec pins the whole point of
    chunking: each chunk program touches only its own tail, never a
    full-sequence buffer.  A variant that materializes the
    (max_seq, V) logits class or scans the full 128-step sequence
    must trip the rule under the real spec's own constraints."""
    from ray_tpu.tools.graftcheck.programs import default_programs

    spec = next(s for s in default_programs()
                if s.name == "gpt2_chunked_prefill")
    assert spec.forbid_logits == (128, 512)
    assert spec.forbid_scan_lengths == (128,)
    assert spec.hbm_budget_bytes > 0
    fn, args = spec.build()

    def bad_logits(p, c, t, bt, pl, nt, s):
        logits, cache = fn(p, c, t, bt, pl, nt, s)
        full = jnp.zeros(spec.forbid_logits, jnp.float32)  # planted
        return logits + jnp.sum(full), cache

    vs, _ = audit_program(
        ProgramSpec(name="planted", build=lambda: (bad_logits, args),
                    forbid_logits=spec.forbid_logits,
                    allow_f32_matmul=True))
    assert "logits-buffer" in _rules(vs)

    def bad_scan(p, c, t, bt, pl, nt, s):
        logits, cache = fn(p, c, t, bt, pl, nt, s)
        acc, _ys = jax.lax.scan(lambda carry, x: (carry + x, x),
                                jnp.zeros(()),
                                jnp.zeros((128,)))  # planted full seq
        return logits + acc, cache

    vs, _ = audit_program(
        ProgramSpec(name="planted", build=lambda: (bad_scan, args),
                    forbid_scan_lengths=spec.forbid_scan_lengths,
                    allow_f32_matmul=True))
    assert "t0-scan" in _rules(vs)


def test_planted_handoff_logits_and_donation_detected():
    """The disaggregated-handoff ProgramSpecs pin the hop's two
    invariants: a handoff moves K/V bytes and never computes (no
    logits-class buffer in either side), and the decode-side install
    donates the pool (two live pools per handoff is exactly the HBM
    spike disaggregation cannot afford).  A variant that materializes
    the full logits class, or an install that drops the donation, must
    trip under the real specs' own constraints."""
    from ray_tpu.tools.graftcheck.programs import default_programs

    progs = {s.name: s for s in default_programs()}
    exp = progs["gpt2_kv_handoff_export"]
    ins = progs["gpt2_kv_handoff_install"]
    assert exp.hbm_budget_bytes > 0 and ins.hbm_budget_bytes > 0
    assert ins.donate_argnums == (0,)

    # export that routes a forward through the hop: logits buffer
    fn, args = exp.build()

    def bad_export(c, blk_ids):
        ks, vs = fn(c, blk_ids)
        full = jnp.zeros(exp.forbid_logits, jnp.float32)  # planted
        return ks + jnp.sum(full), vs

    vs_, _ = audit_program(
        ProgramSpec(name="planted", build=lambda: (bad_export, args),
                    forbid_logits=exp.forbid_logits,
                    allow_f32_matmul=True))
    assert "logits-buffer" in _rules(vs_)

    # install that reduces the spliced pool instead of returning it:
    # no output can alias the donated pool, so the donation is dropped
    ifn, iargs = ins.build()

    def bad_install(c, blk_ids, ks, vs, slot, bt, pos):
        out = ifn(c, blk_ids, ks, vs, slot, bt, pos)
        return jnp.sum(out["k"]) + jnp.sum(out["v"])

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # cpu donation warning
        vs_, _ = audit_program(
            ProgramSpec(name="planted",
                        build=lambda: (bad_install, iargs),
                        donate_argnums=(0,), allow_f32_matmul=True))
    assert "donation" in _rules(vs_)
    # the REAL install keeps the donation live end to end
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        vs_, _ = audit_program(ins)
    assert "donation" not in _rules(vs_)


def test_peak_estimate_counts_live_buffers():
    one_mib = jnp.zeros((512, 512), jnp.float32)  # exactly 1 MiB
    _, info = audit_program(_spec(lambda x: x + 1.0, (one_mib,)))
    # input + output both live at the add: >= 2 MiB
    assert info["peak_hbm_bytes"] >= 2 * 2**20


@pytest.mark.parametrize("name", ["gpt2_paged_decode_step",
                                  "gpt2_chunked_prefill",
                                  "gpt2_paged_prefill_bucket",
                                  "gpt2_spec_verify_step"])
def test_paged_programs_update_the_pool_in_place(name):
    """The engine's decode step, paged prefill (a chunk, and a bucket
    the serving cells run) and verify take the KV pool donated and
    update it where it lies: compiled, K and V alias their results, no
    copy / dynamic-slice of the pool and no copy of a layer is left,
    and a layer is written back exactly as often as the program's
    route says: never by a decode step (its pool is read-only in the
    layer scan), once a tensor by a prefill or a verify block (into
    the carried pool, with no stacked `ys` beside it)."""
    from ray_tpu.tools.graftcheck.programs import default_programs

    spec = next(s for s in default_programs() if s.name == name)
    assert spec.donate_argnums == (1,) and spec.inplace_pool == 1
    assert spec.pool_layer_writes == (
        0 if name == "gpt2_paged_decode_step" else 2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        vs, info = audit_program(spec)
    assert not _rules(vs), [v.message for v in vs]
    assert info["alias_bytes"] >= info["pool_bytes"] > 0
    assert info["pool_layer_writes"] == spec.pool_layer_writes


def _stacked_back(fn):
    """`fn` with its result's K pool handed back as a layer scan's
    stacked `ys` (every layer sliced out, written, stacked)."""
    def through_the_scan(p, c, *rest):
        logits, out = fn(p, c, *rest)

        def body(carry, lidx):
            lk = jax.lax.dynamic_index_in_dim(out["k"], lidx, 0,
                                              keepdims=False)
            return carry, lk.at[0, 0].add(1.0)

        _, ks = jax.lax.scan(body, 0, jnp.arange(out["k"].shape[0]))
        return logits, dict(out, k=ks)
    return through_the_scan


@pytest.mark.parametrize("name,allowed", [
    ("gpt2_paged_decode_step", 0), ("gpt2_paged_prefill_bucket", 2)])
def test_planted_pool_through_the_scan_detected(name, allowed):
    """The regression the rule exists for, on both routes: a program
    that hands the pool back as a layer scan's stacked `ys` moves a
    layer-sized buffer per layer: one more than a prefill's two
    write-backs, one where a decode step has none."""
    from ray_tpu.tools.graftcheck.programs import default_programs

    spec = next(s for s in default_programs() if s.name == name)
    fn, args = spec.build()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        vs, info = audit_program(ProgramSpec(
            name="planted", build=lambda: (_stacked_back(fn), args),
            donate_argnums=(1,), inplace_pool=1,
            pool_layer_writes=allowed, allow_f32_matmul=True))
    assert "pool-inplace" in _rules(vs)
    assert info["pool_layer_writes"] > allowed
    assert any("dynamic-update-slice" in v.message for v in vs)


def test_planted_dropped_pool_loses_the_alias():
    """A program that never writes its donated pool's buffers drops
    the alias, and the rule says so."""
    from ray_tpu.tools.graftcheck.programs import default_programs

    spec = next(s for s in default_programs()
                if s.name == "gpt2_paged_decode_step")
    fn, args = spec.build()

    def pool_dropped(p, c, t):
        logits, out = fn(p, c, t)
        return logits, {k: v for k, v in out.items()
                        if k not in ("k", "v")}

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # cpu donation warning
        vs, info = audit_program(
            ProgramSpec(name="planted",
                        build=lambda: (pool_dropped, args),
                        inplace_pool=1, allow_f32_matmul=True))
    assert "pool-inplace" in _rules(vs)
    assert info["alias_bytes"] < info["pool_bytes"]


def test_skip_rules_waives_a_jaxpr_rule():
    def fn(x):
        jax.debug.print("leak {}", x[0])
        return x * 2

    vs, _ = audit_program(_spec(fn, (jnp.zeros((8,)),),
                                skip_rules=("host-transfer",)))
    assert "host-transfer" not in _rules(vs)


def test_planted_missing_collective_detected():
    # an unsharded program can never contain an all-reduce, so a spec
    # requiring one must fire
    vs, _ = audit_program(_spec(lambda x: x + 1.0,
                                (jnp.zeros((8, 8)),),
                                require_collectives=("all-reduce",)))
    assert "collectives" in _rules(vs)


def test_planted_replicated_shape_detected():
    # the input's own full shape appears in the compiled HLO — the
    # forbidden-shape form of the collectives rule must fire on it
    vs, _ = audit_program(_spec(lambda x: x + 1.0,
                                (jnp.zeros((8, 8)),),
                                forbid_hlo_shapes=("f32[8,8]",)))
    assert "collectives" in _rules(vs)


def test_planted_per_chip_hbm_blowup_detected():
    x = jnp.zeros((256, 256), jnp.float32)   # 256 KiB unsharded
    vs, info = audit_program(
        _spec(lambda a: a @ a.T, (x,), allow_f32_matmul=True,
              per_chip_hbm_budget_bytes=1024))
    assert "per-chip-hbm" in _rules(vs)
    assert info["per_chip_hbm_bytes"] > 1024


def test_min_devices_skips_not_fails():
    vs, info = audit_program(
        _spec(lambda x: x + 1.0, (jnp.zeros((8,)),),
              min_devices=10_000))
    assert vs == []
    assert "skipped" in info


# ---------------------------------------------------------------------------
# 3. lint fixtures — positive, suppressed, out-of-scope per rule
# ---------------------------------------------------------------------------

_SERVE = "ray_tpu/serve/fixture.py"


def test_lint_blocking_call_positive():
    src = textwrap.dedent("""\
        import numpy as np

        async def handler(prompt):
            return np.asarray(prompt)
    """)
    kept, n_sup = lint_source(src, _SERVE)
    assert [v.rule for v in kept] == ["blocking-call-in-async"]
    assert n_sup == 0


def test_lint_blocking_call_variants():
    src = textwrap.dedent("""\
        import time
        import ray

        async def handler(ref, arr):
            x = ray.get(ref)
            arr.block_until_ready()
            time.sleep(1)
            return x
    """)
    kept, _ = lint_source(src, _SERVE)
    assert len(kept) == 3
    assert {v.rule for v in kept} == {"blocking-call-in-async"}


def test_lint_blocking_call_suppressed():
    src = textwrap.dedent("""\
        import numpy as np

        async def handler(prompt):
            # deliberate host fence
            # graftcheck: disable=blocking-call-in-async(result fetch)
            return np.asarray(prompt)
    """)
    kept, n_sup = lint_source(src, _SERVE)
    assert not kept
    assert n_sup == 1


def test_lint_blocking_call_scoped_to_serve():
    src = textwrap.dedent("""\
        import numpy as np

        async def handler(prompt):
            return np.asarray(prompt)
    """)
    kept, _ = lint_source(src, "ray_tpu/train/fixture.py")
    assert not kept


def test_lint_blocking_call_ignores_sync_and_nested():
    src = textwrap.dedent("""\
        import numpy as np

        def sync_helper(p):
            return np.asarray(p)

        async def handler(prompt):
            def jitted_body(t):
                return np.asarray(t)   # runs under jit, not the loop
            return jitted_body(prompt)
    """)
    kept, _ = lint_source(src, _SERVE)
    assert not kept


def test_lint_wallclock_positive_and_suppressed():
    src = textwrap.dedent("""\
        import time

        def record():
            return time.time()
    """)
    kept, _ = lint_source(src, "ray_tpu/serve/telemetry.py")
    assert [v.rule for v in kept] == ["wallclock-in-telemetry"]
    # perf_counter is the sanctioned clock
    kept, _ = lint_source(src.replace("time.time()",
                                      "time.perf_counter()"),
                          "ray_tpu/serve/telemetry.py")
    assert not kept
    # out of scope: same call elsewhere is fine
    kept, _ = lint_source(src, "ray_tpu/serve/other.py")
    assert not kept
    suppressed = src.replace(
        "return time.time()",
        "return time.time()  "
        "# graftcheck: disable=wallclock-in-telemetry(epoch label)")
    kept, n_sup = lint_source(suppressed, "ray_tpu/train/telemetry.py")
    assert not kept
    assert n_sup == 1


def test_lint_wallclock_covers_flightrec_and_slo():
    # the flight recorder and SLO burn-rate engine promised monotonic
    # clocks — a planted time.time() in either path must flag
    src = textwrap.dedent("""\
        import time

        def record(kind):
            return time.time()
    """)
    for rel in ("ray_tpu/_private/flightrec.py",
                "ray_tpu/serve/slo.py"):
        kept, _ = lint_source(src, rel)
        assert [v.rule for v in kept] == ["wallclock-in-telemetry"], rel
        kept, _ = lint_source(src.replace("time.time()",
                                          "time.perf_counter()"), rel)
        assert not kept, rel
    # neighbours of the scoped files stay out of scope
    kept, _ = lint_source(src, "ray_tpu/serve/kv_pager.py")
    assert not kept


def test_lint_fleet_router_in_both_rule_scopes():
    # round 11: the fleet router schedules WFQ virtual time and
    # journals routing decisions — both the monotonic-clock and the
    # no-blocking-in-async invariants extend to it
    wall = textwrap.dedent("""\
        import time

        def record_route(req):
            return time.time()
    """)
    kept, _ = lint_source(wall, "ray_tpu/serve/router.py")
    assert [v.rule for v in kept] == ["wallclock-in-telemetry"]
    kept, _ = lint_source(wall.replace("time.time()",
                                       "time.perf_counter()"),
                          "ray_tpu/serve/router.py")
    assert not kept
    block = textwrap.dedent("""\
        import numpy as np

        async def submit(prompt):
            return np.asarray(prompt)
    """)
    kept, _ = lint_source(block, "ray_tpu/serve/router.py")
    assert [v.rule for v in kept] == ["blocking-call-in-async"]


def test_lint_autopilot_in_both_rule_scopes():
    # round 12: the dashboard calls the autopilot from its event loop
    # and verdicts promised ledger-reproducibility — both the
    # monotonic-clock and no-blocking-in-async invariants extend over
    # ray_tpu/tools/autopilot/
    wall = textwrap.dedent("""\
        import time

        def stamp_plan():
            return time.time()
    """)
    kept, _ = lint_source(wall, "ray_tpu/tools/autopilot/planner.py")
    assert [v.rule for v in kept] == ["wallclock-in-telemetry"]
    kept, _ = lint_source(wall.replace("time.time()",
                                       "time.perf_counter()"),
                          "ray_tpu/tools/autopilot/planner.py")
    assert not kept
    block = textwrap.dedent("""\
        import numpy as np

        async def collect(snapshot):
            return np.asarray(snapshot)
    """)
    kept, _ = lint_source(block,
                          "ray_tpu/tools/autopilot/attribution.py")
    assert [v.rule for v in kept] == ["blocking-call-in-async"]
    # sibling tools stay out of both scopes
    kept, _ = lint_source(wall, "ray_tpu/tools/graftcheck/fixture.py")
    assert not kept
    kept, _ = lint_source(block, "ray_tpu/tools/graftcheck/fixture.py")
    assert not kept


def test_lint_wallclock_covers_trainwatch():
    # round 14: the trainwatch anatomy promises legs that sum exactly
    # to the step wall on ONE clock — a planted time.time() in
    # train/goodput.py breaks that invariant and must flag
    src = textwrap.dedent("""\
        import time

        def record_step(call_s):
            return time.time()
    """)
    kept, _ = lint_source(src, "ray_tpu/train/goodput.py")
    assert [v.rule for v in kept] == ["wallclock-in-telemetry"]
    kept, _ = lint_source(src.replace("time.time()",
                                      "time.perf_counter()"),
                          "ray_tpu/train/goodput.py")
    assert not kept
    # train-package neighbours stay out of scope (telemetry.py is
    # covered by the */telemetry.py glob, grad_accum.py is not timed)
    kept, _ = lint_source(src, "ray_tpu/train/grad_accum.py")
    assert not kept


def test_lint_wallclock_covers_kvscope():
    # round 16: the kvscope occupancy ring promised perf_counter
    # timestamps (wall-clock steps would corrupt the timeline around
    # NTP slews) — a planted time.time() in either the host-side core
    # or the CLI must flag
    src = textwrap.dedent("""\
        import time

        def sample(free):
            return time.time()
    """)
    for rel in ("ray_tpu/serve/kvscope.py",
                "ray_tpu/tools/kvscope.py"):
        kept, _ = lint_source(src, rel)
        assert [v.rule for v in kept] == ["wallclock-in-telemetry"], rel
        kept, _ = lint_source(src.replace("time.time()",
                                          "time.perf_counter()"), rel)
        assert not kept, rel
    # the pager itself stays OUT of scope (allocation is not timed)
    kept, _ = lint_source(src, "ray_tpu/serve/kv_pager.py")
    assert not kept


def test_lint_kvscope_sources_clean():
    # kvscope lints itself clean under the full rule set
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rel in ("ray_tpu/serve/kvscope.py",
                "ray_tpu/tools/kvscope.py"):
        with open(os.path.join(repo, rel)) as f:
            kept, _ = lint_source(f.read(), rel)
        assert not kept, [str(v) for v in kept]


def test_lint_wallclock_covers_kv_tier():
    # round 17: the host KV tier never reads a clock — the engine
    # feeds it measured H2D/D2H seconds (note_h2d/note_d2h) — so a
    # planted time.time() inside serve/kv_tier.py must flag
    src = textwrap.dedent("""\
        import time

        def put(key, rows):
            return time.time()
    """)
    kept, _ = lint_source(src, "ray_tpu/serve/kv_tier.py")
    assert [v.rule for v in kept] == ["wallclock-in-telemetry"]
    kept, _ = lint_source(src.replace("time.time()",
                                      "time.perf_counter()"),
                          "ray_tpu/serve/kv_tier.py")
    assert not kept


def test_lint_blocking_call_covers_kv_tier():
    # kv_tier.py lives under ray_tpu/serve/, so the async-path
    # blocking-call scope already covers it: a planted D2H gather
    # inside an async def must flag
    src = textwrap.dedent("""\
        import numpy as np

        async def spill(cache, blk):
            return np.asarray(cache[blk])
    """)
    kept, _ = lint_source(src, "ray_tpu/serve/kv_tier.py")
    assert [v.rule for v in kept] == ["blocking-call-in-async"]


def test_lint_kv_tier_source_clean():
    # the shipped tier lints clean under the full rule set (both the
    # wallclock and blocking-call scopes now include it)
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rel = "ray_tpu/serve/kv_tier.py"
    with open(os.path.join(repo, rel)) as f:
        kept, _ = lint_source(f.read(), rel)
    assert not kept, [str(v) for v in kept]


def test_lint_wallclock_covers_healthwatch():
    # round 19: healthwatch state transitions and incident timelines
    # are rebased onto the perf_counter clock shared with flightrec
    # and tracebus — a planted time.time() in the monitor, the chaos
    # injector, or the incidents CLI would skew detection latency and
    # mis-order merged lanes around NTP slews, so each must flag
    src = textwrap.dedent("""\
        import time

        def heartbeat(name):
            return time.time()
    """)
    for rel in ("ray_tpu/serve/health.py",
                "ray_tpu/serve/chaos.py",
                "ray_tpu/tools/incidents.py"):
        kept, _ = lint_source(src, rel)
        assert [v.rule for v in kept] == ["wallclock-in-telemetry"], rel
        kept, _ = lint_source(src.replace("time.time()",
                                          "time.perf_counter()"), rel)
        assert not kept, rel
    # untimed tools neighbours stay out of scope
    kept, _ = lint_source(src, "ray_tpu/tools/fixture.py")
    assert not kept


def test_lint_blocking_call_covers_incidents():
    # health.py/chaos.py live under ray_tpu/serve/ (already in the
    # async blocking-call scope); the incidents CLI is pulled in
    # explicitly so a future async export path can't sneak a
    # device-blocking call past review
    src = textwrap.dedent("""\
        import numpy as np

        async def export(doc):
            return np.asarray(doc)
    """)
    for rel in ("ray_tpu/serve/health.py",
                "ray_tpu/serve/chaos.py",
                "ray_tpu/tools/incidents.py"):
        kept, _ = lint_source(src, rel)
        assert [v.rule for v in kept] == ["blocking-call-in-async"], rel


def test_lint_healthwatch_sources_clean():
    # the shipped healthwatch trio lints clean under the full rule set
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rel in ("ray_tpu/serve/health.py",
                "ray_tpu/serve/chaos.py",
                "ray_tpu/tools/incidents.py"):
        with open(os.path.join(repo, rel)) as f:
            kept, _ = lint_source(f.read(), rel)
        assert not kept, (rel, [str(v) for v in kept])


def test_lint_mutable_global_positive():
    src = textwrap.dedent("""\
        from ray_tpu import remote

        CACHE = {}

        @remote
        def worker(x):
            CACHE[x] = 1
            return x
    """)
    kept, _ = lint_source(src, "ray_tpu/train/fixture.py")
    assert [v.rule for v in kept] == ["mutable-global-in-remote"]


def test_lint_mutable_global_actor_method_and_reads_ok():
    src = textwrap.dedent("""\
        import ray_tpu

        SEEN = []

        @ray_tpu.remote
        class Actor:
            def push(self, x):
                SEEN.append(x)

            def peek(self):
                return len(SEEN)
    """)
    kept, _ = lint_source(src, "ray_tpu/train/fixture.py")
    assert len(kept) == 1           # push mutates; peek only reads
    assert kept[0].rule == "mutable-global-in-remote"
    # non-remote functions may mutate module state freely
    src2 = textwrap.dedent("""\
        CACHE = {}

        def local(x):
            CACHE[x] = 1
    """)
    kept, _ = lint_source(src2, "ray_tpu/train/fixture.py")
    assert not kept


def test_lint_metric_name_positive_and_suppressed():
    src = textwrap.dedent("""\
        from ray_tpu.util.metrics import Counter

        c = Counter("Bad-Name", "desc")
    """)
    kept, _ = lint_source(src, "ray_tpu/util/fixture.py")
    assert [v.rule for v in kept] == ["metric-name"]
    kept, _ = lint_source(src.replace("Bad-Name", "good_name_total"),
                          "ray_tpu/util/fixture.py")
    assert not kept
    # a computed name can't be verified: also a finding
    kept, _ = lint_source(src.replace('"Bad-Name"', "some_var"),
                          "ray_tpu/util/fixture.py")
    assert [v.rule for v in kept] == ["metric-name"]
    suppressed = src.replace(
        'c = Counter("Bad-Name", "desc")',
        'c = Counter("Bad-Name", "desc")  '
        '# graftcheck: disable=metric-name(legacy dashboard name)')
    kept, n_sup = lint_source(suppressed, "ray_tpu/util/fixture.py")
    assert not kept
    assert n_sup == 1


def test_suppression_comment_semantics():
    sup = gc.parse_suppressions(textwrap.dedent("""\
        x = 1  # graftcheck: disable=rule-a
        # graftcheck: disable=rule-b,rule-c
        y = 2
        z = 3
    """))
    assert sup[1] == {"rule-a"}
    assert sup[2] == {"rule-b", "rule-c"}   # standalone covers itself
    assert sup[3] == {"rule-b", "rule-c"}   # ...and the next line
    assert 4 not in sup


# ---------------------------------------------------------------------------
# suppression hygiene: reasons required, waivers must earn their keep
# ---------------------------------------------------------------------------

_HYGIENE_BAD = textwrap.dedent("""\
    import numpy as np

    async def handler(prompt):
        # graftcheck: disable=blocking-call-in-async{reason}
        return np.asarray(prompt)
""")


def test_hygiene_bare_suppression_needs_reason():
    kept, n_sup = lint_source(_HYGIENE_BAD.format(reason=""),
                              "ray_tpu/serve/fixture.py")
    assert [v.rule for v in kept] == ["suppression-reason"]
    assert n_sup == 1          # the waiver still works, it just owes a why


def test_hygiene_reasoned_effective_waiver_is_clean():
    kept, n_sup = lint_source(
        _HYGIENE_BAD.format(reason="(host-side fixture)"),
        "ray_tpu/serve/fixture.py")
    assert kept == []
    assert n_sup == 1


def test_hygiene_unknown_rule_is_stale():
    kept, _ = lint_source(textwrap.dedent("""\
        # graftcheck: disable=no-such-rule(typo'd long ago)
        x = 1
    """), "ray_tpu/serve/fixture.py")
    assert [v.rule for v in kept] == ["stale-suppression"]
    assert "no-such-rule" in kept[0].message


def test_hygiene_noop_waiver_is_stale():
    # the waived rule exists but nothing on the covered lines fires it
    kept, n_sup = lint_source(textwrap.dedent("""\
        # graftcheck: disable=blocking-call-in-async(left behind)
        x = 1
    """), "ray_tpu/serve/fixture.py")
    assert [v.rule for v in kept] == ["stale-suppression"]
    assert n_sup == 0


def test_hygiene_noop_all_waiver_is_stale_too():
    # even a blanket 'all' must actually drop something to stay
    kept, _ = lint_source(textwrap.dedent("""\
        # graftcheck: disable=all(generated file)
        x = 1
    """), "ray_tpu/serve/fixture.py")
    assert [v.rule for v in kept] == ["stale-suppression"]


# ---------------------------------------------------------------------------
# contract-registry / perfledger-direction: planted drift
# ---------------------------------------------------------------------------

def test_contract_registry_clean():
    from ray_tpu.tools.graftcheck.contracts import contract_registry

    assert contract_registry(ROOT) == []


def test_contract_registry_planted_new_component(monkeypatch):
    import ray_tpu.serve.telemetry as telemetry
    from ray_tpu.tools.graftcheck.contracts import contract_registry

    monkeypatch.setattr(
        telemetry, "CRITICAL_PATH_COMPONENTS",
        tuple(telemetry.CRITICAL_PATH_COMPONENTS) + ("phantom_ms",))
    msgs = [v.message for v in contract_registry(ROOT)]
    # the new component must be pinned in every downstream view
    assert any("no COMPONENT_SPANS entry" in m for m in msgs)
    assert any("missing from the golden" in m for m in msgs)
    assert any("not documented" in m for m in msgs)


def test_contract_registry_planted_stale_span(monkeypatch):
    import ray_tpu.tools.tracebus as tracebus
    from ray_tpu.tools.graftcheck.contracts import contract_registry

    spans = dict(tracebus.COMPONENT_SPANS)
    spans["ghost_ms"] = "ghost.span"
    monkeypatch.setattr(tracebus, "COMPONENT_SPANS", spans)
    msgs = [v.message for v in contract_registry(ROOT)]
    assert any("stale mapping" in m for m in msgs)
    assert any("never emits a 'ghost.span'" in m for m in msgs)


def test_perfledger_direction_clean_and_planted(monkeypatch):
    import ray_tpu.tools.perfledger as perfledger
    from ray_tpu.tools.graftcheck.contracts import perfledger_direction

    assert perfledger_direction(ROOT) == []
    monkeypatch.setattr(
        perfledger, "_SWEEP_FIELDS",
        tuple(perfledger._SWEEP_FIELDS) + ("mystery_blips",))
    vs = perfledger_direction(ROOT)
    assert [v.rule for v in vs] == ["perfledger-direction"]
    assert "mystery_blips" in vs[0].message


def test_sweep_record_carries_v2_rule_counters(monkeypatch):
    import ray_tpu.tools.graftcheck as graftcheck_pkg
    import sweep_tpu

    # stub the (expensive, jaxpr-tracing) repo check: the counters'
    # arithmetic is what's under test, the real report shape is
    # pinned by the CLI tests above
    monkeypatch.setattr(graftcheck_pkg, "run_repo_check", lambda: {
        "ok": False,
        "violations": [
            {"rule": "shared-state-race", "message": "m"},
            {"rule": "shared-state-race", "message": "m"},
            {"rule": "rng-discipline", "message": "m"},
        ],
        "summary": {"n_violations": 3, "n_suppressed": 0,
                    "files_scanned": 1, "rules_failed":
                    ["shared-state-race", "rng-discipline"]},
    })
    rec = sweep_tpu._graftcheck_record()
    summary = rec["graftcheck"]
    assert summary["shared_state_race"] == 2
    assert summary["rng_discipline"] == 1
    assert summary["contract_registry"] == 0
    assert rec["ok"] is False


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_json_clean_on_repo(capsys):
    from ray_tpu.tools.graftcheck.__main__ import main

    rc = main(["--root", str(ROOT), "--skip-jaxpr", "--format", "json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert report["ok"] is True
    assert report["summary"]["n_suppressed"] >= 5


def test_cli_nonzero_on_planted_violation(tmp_path, capsys):
    from ray_tpu.tools.graftcheck.__main__ import main

    pkg = tmp_path / "ray_tpu" / "serve"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(textwrap.dedent("""\
        import numpy as np

        async def handler(prompt):
            return np.asarray(prompt)
    """))
    (tmp_path / "ray_tpu" / "ops").mkdir()
    (tmp_path / "tests").mkdir()
    rc = main(["--root", str(tmp_path), "--skip-jaxpr",
               "--format", "json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert report["ok"] is False
    assert "blocking-call-in-async" in report["summary"]["rules_failed"]


def test_cli_github_format_annotations(tmp_path, capsys):
    from ray_tpu.tools.graftcheck.__main__ import main

    pkg = tmp_path / "ray_tpu" / "serve"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(textwrap.dedent("""\
        import numpy as np

        async def handler(prompt):
            return np.asarray(prompt)
    """))
    (tmp_path / "ray_tpu" / "ops").mkdir()
    (tmp_path / "tests").mkdir()
    rc = main(["--root", str(tmp_path), "--skip-jaxpr",
               "--format", "github"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "::error file=ray_tpu/serve/bad.py,line=4::" in out
    assert "[blocking-call-in-async]" in out
    assert "::notice::graftcheck:" in out


def test_cli_changed_lints_only_the_range(tmp_path, capsys):
    import subprocess

    from ray_tpu.tools.graftcheck.__main__ import main

    def git(*argv):
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t",
             *argv], cwd=tmp_path, check=True, capture_output=True)

    pkg = tmp_path / "ray_tpu" / "serve"
    pkg.mkdir(parents=True)
    git("init", "-q")
    git("commit", "-qm", "root", "--allow-empty")
    # commit 2: a clean file plus a bad file that predates the range
    (pkg / "old_bad.py").write_text(
        "import numpy as np\n\n"
        "async def old(prompt):\n    return np.asarray(prompt)\n")
    (pkg / "clean.py").write_text("x = 1\n")
    git("add", "-A")
    git("commit", "-qm", "seed")
    # commit 3: touch clean.py only — the old violation is out of range
    (pkg / "clean.py").write_text("x = 2\n")
    git("add", "-A")
    git("commit", "-qm", "touch clean")
    rc = main(["--root", str(tmp_path), "--changed", "HEAD~1..HEAD",
               "--format", "json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert report["ok"] is True
    assert report["summary"]["files_scanned"] == 1
    # now a range that includes the bad file
    rc = main(["--root", str(tmp_path), "--changed",
               "HEAD~2..HEAD", "--format", "json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert "blocking-call-in-async" in report["summary"]["rules_failed"]


def test_cli_changed_bad_range_exits_2(tmp_path, capsys):
    import subprocess

    from ray_tpu.tools.graftcheck.__main__ import main

    subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True,
                   capture_output=True)
    rc = main(["--root", str(tmp_path), "--changed",
               "not-a-rev..HEAD"])
    assert rc == 2


def test_cli_subprocess_entry_point():
    import subprocess

    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu.tools.graftcheck",
         "--skip-jaxpr", "--root", str(ROOT)],
        capture_output=True, text=True, cwd=str(ROOT), timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "graftcheck:" in proc.stdout
