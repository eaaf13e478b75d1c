"""The engine's build as phases (PR 54): ``raytpu.setup.*`` leaves
around the parts of ``EngineBase.__init__``, the table
``engine_stats()["setup"]``, one ``phase`` set-up record a leaf, and the
compiles inside naming the leaf as their cause.  What a serving or
training step can reach of it is ``instrument``'s fresh-signature
branch: a seen signature pays nothing new."""

import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu._private import device_stats as ds, scopes, telemetry  # noqa: E402
from ray_tpu._private.compile_cache import CompileWatch  # noqa: E402
from ray_tpu.serve.llm import SpecConfig, build_llm_deployment  # noqa: E402

_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}


def _build(**kw):
    kw.setdefault("scheduler", "continuous")
    if kw["scheduler"] == "continuous":
        kw.setdefault("kv_layout", "paged")
        kw.setdefault("kv_block_size", 16)
        kw.setdefault("prefill_bucket", 16)
        kw.setdefault("max_slots", 2)
    dep = build_llm_deployment(
        "gpt2", "nano", max_new_tokens=3, temperature=0.0,
        config_overrides=_OVR, **kw)
    CompileWatch()                       # the listeners are on
    t0 = time.perf_counter()
    inst = dep.func_or_class()
    t1 = time.perf_counter()
    try:
        return inst.engine_stats()["setup"], telemetry.setup_records(t0), \
            t0, t1
    finally:
        if hasattr(inst, "shutdown_engine"):
            inst.shutdown_engine()


@pytest.mark.parametrize("kv_layout", ["paged", "dense"])
def test_the_constructor_is_four_leaves_inside_its_wall(kv_layout):
    table, records, t0, t1 = _build(kv_layout=kv_layout)
    assert set(table) == set(scopes.SETUP_PHASES)
    for count, seconds in table.values():
        assert count == 1 and seconds >= 0.0
    assert 0.0 < sum(s for _, s in table.values()) <= t1 - t0
    phases = [r for r in records if r["kind"] == "phase"]
    # in the constructor's order, one after the other, inside its wall
    assert [r["phase"] for r in phases] == list(scopes.SETUP_PHASES)
    stamps = [t0] + [x for r in phases for x in (r["t0"], r["t1"])] + [t1]
    assert stamps == sorted(stamps)
    for r in phases:
        # the record is the leaf's own stamps: no second timing
        assert r["t1"] - r["t0"] == pytest.approx(
            table[r["phase"]][1], abs=1e-6)
        assert r["cause"] is None


def test_what_compiles_in_a_leaf_names_it():
    jax.clear_caches()          # as a fresh process: the eager ops too
    table, records, _, _ = _build()
    compiles = [r for r in records if r["kind"] == "compile"]
    phases = {r["phase"]: r for r in records if r["kind"] == "phase"}
    assert compiles
    for r in compiles:
        leaf = phases[r["cause"]["phase"]]
        assert leaf["t0"] <= r["t0"] and r["t1"] <= leaf["t1"]
    by_phase = {}
    for r in compiles:
        by_phase.setdefault(r["cause"]["phase"], []).append(r)
    for name, rs in by_phase.items():
        spent = sum(r["trace_s"] + r["lower_s"] + r["backend_s"]
                    for r in rs)
        assert spent <= table[name][1]
    # fam.init's eager ops: a fresh process compiles them in ``params``
    assert "params" in by_phase


def test_a_draft_models_parameters_are_a_second_params_leaf():
    table, records, _, _ = _build(
        spec_decode=SpecConfig(draft="gpt2:nano", k=2))
    assert table["params"][0] == 2 and table["cache"][0] == 2
    assert table["config"][0] == table["programs"][0] == 1
    assert [r["phase"] for r in records if r["kind"] == "phase"] == [
        "config", "params", "cache", "params", "cache", "programs"]


def test_the_batch_scheduler_has_no_cache_leaf():
    table, records, _, _ = _build(scheduler="batch", max_batch_size=2)
    assert set(table) == {"config", "params", "programs"}


def test_a_seen_signature_stays_inside_its_budget(per_call_us,
                                                  monkeypatch):
    """What `instrument` adds to a call whose signature it has seen
    (the signature, two stamps, one locked append): measured against
    the bare jitted call, min of repeats.  The budget is what it was
    before the cause stack and the harvest record went into the fresh
    branch; neither is on this path."""
    monkeypatch.setenv("RAYTPU_DEVICE_STATS_COST", "0")
    reg = ds.ProgramRegistry()
    fn = jax.jit(lambda x: x + 1)
    wrapped = reg.instrument("serve.decode", fn)
    x = jnp.ones((4,), jnp.float32)
    wrapped(x)                            # the fresh signature
    t = time.perf_counter()
    bare = per_call_us(lambda: fn(x))
    with_registry = per_call_us(lambda: wrapped(x))
    assert with_registry - bare < 60.0, (bare, with_registry)
    assert reg.snapshot()["serve.decode"]["invokes"] == per_call_us.calls
    # nothing of the set-up records is on this path
    assert telemetry.setup_records(t) == []
    assert telemetry.current_cause() is None
