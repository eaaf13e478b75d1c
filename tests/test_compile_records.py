"""Set-up records (PR 54): one ``compile`` record for every program JAX
compiles or loads, with what caused it; ``harvest`` records around
``device_stats.instrument``'s side compile; one ring a process
(``_private/telemetry.py``), one pair of ``jax.monitoring`` listeners
(``_private/compile_cache.py``)."""

import threading
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax._src import monitoring  # noqa: E402

from ray_tpu._private import device_stats as ds  # noqa: E402
from ray_tpu._private import telemetry  # noqa: E402
from ray_tpu._private.compile_cache import CompileWatch  # noqa: E402


def _compiles(since, name=None):
    return [r for r in telemetry.setup_records(since)
            if r["kind"] == "compile"
            and (name is None or r["fun_name"] == name)]


def _fresh(tag):
    """A jitted function no test has compiled yet, named `tag`."""
    def fn(x):
        return x * 3 + 1
    fn.__name__ = tag
    return jax.jit(fn)


@pytest.fixture
def cache_dir(tmp_path):
    """A persistent cache of this test's own that keeps everything:
    both of JAX's thresholds at zero."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    yield str(tmp_path)
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_a_fresh_jit_call_makes_exactly_one_record():
    CompileWatch()
    t = time.perf_counter()
    f = _fresh("one_record_fn")
    f(jnp.ones((5,), jnp.float32))
    f(jnp.ones((5,), jnp.float32))       # seen: no compile, no record
    mine = _compiles(t, "jit(one_record_fn)")
    assert len(mine) == 1
    r, = mine
    assert r["kind"] == "compile" and r["cause"] is None
    assert r["trace_s"] >= 0 and r["lower_s"] >= 0 and r["backend_s"] >= 0
    assert r["cache"] in ("hit", "miss", "none")
    assert t <= r["t0"] <= r["t1"] <= time.perf_counter()
    # the extent holds the three parts
    assert r["t1"] - r["t0"] >= r["backend_s"]
    # a new shape is a new program: one more
    f(jnp.ones((6,), jnp.float32))
    assert len(_compiles(t, "jit(one_record_fn)")) == 2


def test_a_record_takes_the_trace_of_its_own_name():
    """The outer function's tracing holds the inner's, and the lowering
    traces small functions of its own after both (``add``, on the chip
    thousands of them a program): the record takes the trace that
    carries its lowering's name, not the last one and not their sum."""
    from jax import lax

    CompileWatch()
    inner = _fresh("inner_fn")

    def outer_fn(x):
        time.sleep(0.05)                 # tracing this takes 50 ms
        # a custom_jvp function is lowered through a trace of its own
        return jax.nn.relu(inner(x)) + lax.cumsum(x)

    t = time.perf_counter()
    jax.jit(outer_fn)(jnp.ones((7,), jnp.float32))
    wall = time.perf_counter() - t
    r, = _compiles(t, "jit(outer_fn)")
    assert not _compiles(t, "jit(inner_fn)")     # inlined, never compiled
    assert 0.05 <= r["trace_s"] <= wall
    assert r["trace_s"] + r["lower_s"] + r["backend_s"] <= wall
    assert r["t0"] >= t


def test_a_trace_without_a_compile_is_taken_by_no_other_program():
    CompileWatch()

    def shaped_only_fn(x):
        time.sleep(0.05)
        return x + 1

    jax.eval_shape(jax.jit(shaped_only_fn), jnp.ones((3,), jnp.float32))
    t = time.perf_counter()
    _fresh("after_eval_shape_fn")(jnp.ones((3,), jnp.float32))
    r, = _compiles(t, "jit(after_eval_shape_fn)")
    assert r["trace_s"] < 0.05 and r["t0"] >= t


def test_the_cache_serves_the_same_program_after_clear_caches(cache_dir):
    watch = CompileWatch()
    f = _fresh("cached_fn")
    t = time.perf_counter()
    f(jnp.ones((9,), jnp.float32))
    first, = _compiles(t, "jit(cached_fn)")
    assert first["cache"] == "miss"              # compiled and written
    assert "retrieval_s" not in first
    assert watch.writes >= 1 and watch.hits == 0
    jax.clear_caches()
    t = time.perf_counter()
    f(jnp.ones((9,), jnp.float32))
    again, = _compiles(t, "jit(cached_fn)")
    assert again["cache"] == "hit"
    assert again["retrieval_s"] >= 0 and "saved_s" in again
    assert watch.hits >= 1


def test_without_a_cache_a_compile_reads_none():
    from jax.experimental.compilation_cache import compilation_cache as cc

    CompileWatch()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        t = time.perf_counter()
        _fresh("uncached_fn")(jnp.ones((3,), jnp.float32))
        r, = _compiles(t, "jit(uncached_fn)")
        assert r["cache"] == "none"
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def test_harvest_and_call_name_themselves(monkeypatch):
    monkeypatch.setenv("RAYTPU_DEVICE_STATS_COST", "1")
    CompileWatch()
    reg = ds.ProgramRegistry()
    f = reg.instrument("serve.decode", _fresh("harvested_fn"))
    t = time.perf_counter()
    with telemetry.cause(phase="warmup"):
        f(jnp.ones((4,), jnp.float32))
        f(jnp.ones((8,), jnp.float32))
    records = telemetry.setup_records(t)
    harvests = [r for r in records if r["kind"] == "harvest"]
    assert [(r["program"], r["signature"]) for r in harvests] == [
        ("serve.decode", 0), ("serve.decode", 1)]
    # the harvest record is caused by what surrounds it, not by itself
    assert all(r["cause"] == {"phase": "warmup", "program": None,
                              "signature": None, "part": None}
               for r in harvests)
    compiles = _compiles(t, "jit(harvested_fn)")
    by_part = {}
    for r in compiles:
        assert r["cause"]["program"] == "serve.decode"
        assert r["cause"]["phase"] == "warmup"
        by_part.setdefault(r["cause"]["part"], []).append(
            r["cause"]["signature"])
    # the side compile of each fresh signature; the executing call
    # compiles nothing anew where JAX hands it the side compile's
    # executable, and says "call" where it does
    assert by_part["harvest"] == [0, 1]
    assert set(by_part) <= {"harvest", "call"}
    for h, c in zip(harvests, [r for r in compiles
                               if r["cause"]["part"] == "harvest"]):
        assert h["t0"] <= c["t0"] and c["t1"] <= h["t1"]
    snap = reg.snapshot()["serve.decode"]
    assert snap["harvest_seconds"] == pytest.approx(
        sum(r["t1"] - r["t0"] for r in harvests), abs=2e-3)
    assert 0 < snap["harvest_seconds"] <= snap["compile_seconds"]


def test_the_executing_call_names_itself_without_a_harvest(monkeypatch):
    monkeypatch.setenv("RAYTPU_DEVICE_STATS_COST", "0")
    CompileWatch()
    reg = ds.ProgramRegistry()
    f = reg.instrument("train.step", _fresh("called_fn"))
    t = time.perf_counter()
    f(jnp.ones((4,), jnp.float32))
    r, = _compiles(t, "jit(called_fn)")
    assert r["cause"] == {"phase": None, "program": "train.step",
                          "signature": 0, "part": "call"}
    assert not [x for x in telemetry.setup_records(t)
                if x["kind"] == "harvest"]
    assert reg.snapshot()["train.step"]["harvest_seconds"] == 0.0
    # a seen signature: nothing is recorded and no cause is pushed
    t = time.perf_counter()
    f(jnp.ones((4,), jnp.float32))
    assert telemetry.setup_records(t) == []


def test_compile_windows_are_each_compiles_own():
    reg = ds.ProgramRegistry()
    reg.record_compile("p", 1.0, now=10.0)
    reg.record_compile("p", 3.0, now=20.0)
    reg.record_compile("q", 0.5, now=30.0)
    assert reg.compile_windows() == {"p": [(10.0, 1.0), (20.0, 3.0)],
                                     "q": [(30.0, 0.5)]}
    assert reg.compile_windows("q") == {"q": [(30.0, 0.5)]}


def test_a_compile_on_a_second_thread_takes_that_threads_cause():
    CompileWatch()
    t = time.perf_counter()
    seen = {}

    def work(tag, phase):
        def go():
            if phase is None:
                _fresh(tag)(jnp.ones((3,), jnp.float32))
            else:
                with telemetry.cause(phase=phase):
                    seen[tag] = telemetry.current_cause()
                    _fresh(tag)(jnp.ones((3,), jnp.float32))
        return go

    with telemetry.cause(phase="main", program="serve.decode"):
        threads = [threading.Thread(target=work("thread_a_fn", "other")),
                   threading.Thread(target=work("thread_b_fn", None))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        _fresh("main_fn")(jnp.ones((3,), jnp.float32))
    a, = _compiles(t, "jit(thread_a_fn)")
    b, = _compiles(t, "jit(thread_b_fn)")
    m, = _compiles(t, "jit(main_fn)")
    assert a["cause"]["phase"] == "other" and a["cause"]["program"] is None
    assert seen["thread_a_fn"] == a["cause"]
    assert b["cause"] is None
    assert m["cause"]["phase"] == "main"
    assert m["cause"]["program"] == "serve.decode"
    assert telemetry.current_cause() is None


def test_causes_nest_and_unwind():
    assert telemetry.current_cause() is None
    with telemetry.cause(phase="programs"):
        with telemetry.cause(program="serve.decode", signature=2,
                             part="harvest"):
            assert telemetry.current_cause() == {
                "phase": "programs", "program": "serve.decode",
                "signature": 2, "part": "harvest"}
        assert telemetry.current_cause() == {
            "phase": "programs", "program": None, "signature": None,
            "part": None}
        with pytest.raises(RuntimeError):
            with telemetry.cause(part="call"):
                raise RuntimeError("the call failed")
        assert telemetry.current_cause()["part"] is None
    assert telemetry.current_cause() is None


def test_watches_count_from_their_own_construction():
    first = CompileWatch()
    n_duration = len(monitoring.get_event_duration_listeners())
    n_event = len(monitoring.get_event_listeners())
    _fresh("watched_a_fn")(jnp.ones((3,), jnp.float32))
    second = CompileWatch()
    assert first.compiles >= 1 and second.compiles == 0
    before = first.compiles
    _fresh("watched_b_fn")(jnp.ones((3,), jnp.float32))
    assert second.compiles >= 1
    assert first.compiles - before == second.compiles
    assert first.hits >= second.hits and first.writes >= second.writes
    for _ in range(5):
        CompileWatch()
    assert len(monitoring.get_event_duration_listeners()) == n_duration
    assert len(monitoring.get_event_listeners()) == n_event


def test_the_ring_keeps_its_bound():
    t = time.perf_counter()
    for i in range(telemetry.SETUP_HISTORY + 10):
        telemetry.record_setup("phase", t, t, phase=f"filler{i}")
    records = telemetry.setup_records()
    assert len(records) == telemetry.SETUP_HISTORY
    assert records[-1]["phase"] == f"filler{telemetry.SETUP_HISTORY + 9}"
    seqs = [r["seq"] for r in records]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert seqs[0] > 0                    # a reader sees what was lost
    assert telemetry.setup_records(since=time.perf_counter()) == []
