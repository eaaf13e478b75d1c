"""Named scopes inside the jitted programs (``_private/scopes.py``).

A scope is metadata on a program's instructions.  These tests read the
lowered text of the decode step, paged prefill and verify step of both
dense-K/V families (models/kv_decode.py over the GPT-2 and the llama
block) and of GPT-2's train step at nano size and hold the naming to
its coverage; they read the compiled
text through ``scope_map_from_hlo`` and the program registry, which is
how the benchmark's readers join a trace's op events to a scope; and
they check that the Pallas flash kernels carry names of their own."""

import collections
import functools
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from ray_tpu._private import scopes  # noqa: E402
from ray_tpu._private.device_stats import ProgramRegistry  # noqa: E402
from ray_tpu.models import families, gpt2_loss  # noqa: E402
from ray_tpu.models.decode_common import sample_token  # noqa: E402
from ray_tpu.train.jax_trainer import jax_utils  # noqa: E402

_LOC_DEF = re.compile(r'^#loc(\d+) = loc\("([^"]*)"', re.M)
_OP = re.compile(r"(stablehlo\.[\w.]+|\bcall @\w+).*loc\(#loc(\d+)\)\s*$")


@functools.lru_cache(maxsize=None)
def _serving(family):
    """(cfg, params, fresh paged cache, the programs as the engine
    wraps them) of a family at nano size."""
    fam = families.family(family)
    cfg = fam.config("nano", max_seq=64, use_flash=False)

    def paged_cache():
        return fam.init_paged_cache(cfg, 2, num_blocks=8, block_size=16)

    def pool_step(p, cache, toks, key):
        logits, cache = fam.step(p, cache, toks, cfg)
        return sample_token(logits, key, 0.0, None), cache

    def prefill_sample(p, cache, toks, row_bt, key):
        logits, cache = fam.paged_prefill(p, cache, toks, cfg,
                                          row_bt=row_bt, prefix_len=0,
                                          n_tail=5, slot=0)
        return sample_token(logits[None], key, 0.0, None), cache

    def verify(p, cache, block):
        return fam.verify(p, cache, block, cfg)

    return (cfg, fam.init(jax.random.PRNGKey(0), cfg), paged_cache,
            {"pool_step": pool_step, "prefill_sample": prefill_sample,
             "verify": verify, "init_cache": fam.init_cache})


@pytest.fixture(scope="module")
def params():
    return _serving("gpt2")[1]


def _train_step():
    tx = optax.adamw(1e-3)
    step = jax_utils.build_train_step(
        lambda p, b: gpt2_loss(p, b, _serving("gpt2")[0]), tx,
        telemetry=False)
    return step, tx


def _lowered(name, family="gpt2"):
    cfg, params, paged_cache, fns = _serving(family)
    key = jax.random.PRNGKey(1)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    if name == "decode_step":
        return jax.jit(fns["pool_step"]).lower(params, paged_cache(),
                                               i32(2), key)
    if name == "decode_step_dense":
        return jax.jit(fns["pool_step"]).lower(
            params, fns["init_cache"](cfg, 2), i32(2), key)
    if name == "paged_prefill":
        return jax.jit(fns["prefill_sample"]).lower(
            params, paged_cache(), i32(1, 16), i32(4), key)
    if name == "verify_step":
        return jax.jit(fns["verify"]).lower(params, paged_cache(),
                                            i32(2, 3))
    step, tx = _train_step()
    return step.lower(params, tx.init(params), {"tokens": i32(4, 33)})


def _scopes_in(scope_map):
    return {s for keyed in scope_map.values() for s in keyed.values()}


def _op_scopes(lowered):
    """(op, innermost registered scope or None) per operation of the
    lowered text that carries a name stack (``jit(pool_step)/attn/add``
    in the main function, ``kv_pool/gather`` in the scan's body, which
    is lowered as a function of its own).  jnp's own jitted helpers
    (``remainder``, ``_where``: a few scalar index ops) are lowered as
    private functions whose ops carry the bare primitive's name
    (``rem``) in this JAX; their call sites carry the stack and are
    counted."""
    text = lowered.as_text(debug_info=True)
    names = dict(_LOC_DEF.findall(text))
    out = []
    for line in text.splitlines():
        m = _OP.search(line)
        if m is None or m.group(1) in ("stablehlo.constant",
                                       "stablehlo.return"):
            continue
        name = names.get(m.group(2), "")
        if "/" in name:
            out.append((m.group(1), scopes.innermost_scope(name)))
    return out


#: what every serving program of a dense-K/V family names
_SERVING_SCOPES = {"embed", "ln", "attn", "kv_pool", "mlp", "lm_head",
                   "layer_scan"}


@pytest.mark.parametrize("family,program,expected", [
    (family, program, _SERVING_SCOPES | also)
    for family in ("gpt2", "llama")
    for program, also in (("decode_step", {"sample"}),
                          ("decode_step_dense", {"sample"}),
                          ("paged_prefill", {"sample"}),
                          ("verify_step", set()))
] + [("gpt2", "train_step", {"embed", "ln", "attn", "mlp", "lm_head_ce",
                             "loss_and_grad", "optimizer"})])
def test_at_most_a_tenth_of_a_program_is_unscoped(family, program,
                                                  expected):
    ops = _op_scopes(_lowered(program, family))
    assert len(ops) > 100
    found = collections.Counter(s for _, s in ops)
    assert set(found) - {None} == expected
    loose = [op for op, s in ops if s is None]
    assert len(loose) <= 0.10 * len(ops), collections.Counter(loose)
    # an op whose innermost scope only holds other scopes is in no part
    # of the model either, and a reader counts its time as unscoped:
    # the scan's slices of the stacked weights and stacking of what it
    # returns (a few per leaf), and nothing that computes
    alone = collections.Counter(
        op for op, s in ops if s in scopes.CONTAINER_SCOPES)
    assert not {"stablehlo.dot_general", "stablehlo.exponential",
                "stablehlo.tanh", "stablehlo.gather",
                "stablehlo.scatter"} & set(alone), alone


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/loss_and_grad/transpose(jvp(attn))/ln/mul", "ln"),
    ("jit(step)/loss_and_grad/jvp(attn)/dot_general", "attn"),
    ("jit(step)/loss_and_grad/jvp()/while/body/add", "loss_and_grad"),
    ("jit(step)/loss_and_grad/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/mlp/tanh", "mlp"),
    ("jit(step)/optimizer/mul", "optimizer"),
    ("jit(pool_step)/layer_scan/while/body/kv_pool/gather", "kv_pool"),
    ("jit(pool_step)/layer_scan/while/body/dynamic_slice", "layer_scan"),
    ("jit(attn)/jit(mlp)/add", None),        # a jit's name is no scope
    ("jit(pool_step)/vmap(sample)/argmax", "sample"),
    ("copy", None),
])
def test_innermost_scope(op_name, scope):
    assert scopes.innermost_scope(op_name) == scope


def test_scope_map_from_compiled_text_picks_the_innermost(params):
    text = _lowered("train_step").compile().as_text()
    assert scopes.hlo_module_name(text) == "jit_step"
    found = scopes.scope_map_from_hlo(text)
    assert {"attn", "mlp", "ln", "lm_head_ce", "optimizer",
            "loss_and_grad", "embed"} <= _scopes_in(found)
    # each entry is the innermost scope of that instruction's op_name,
    # under the instruction's own key (result type and opcode)
    lines = dict(scopes._INSTRUCTION.findall(text))
    by_name = {n: m.group(1) for n, body in lines.items()
               if (m := scopes._OP_NAME.search(body))}
    for name, keyed in found.items():
        (key, scope), = keyed.items()
        assert scopes.innermost_scope(by_name[name]) == scope
        assert key == scopes.instruction_key(lines[name])
        assert lines[name].startswith(key + "(")
    # a layernorm inside loss_and_grad is ln, never loss_and_grad
    assert any("ln" in keyed.values() and "loss_and_grad" in by_name[n]
               for n, keyed in found.items())
    # instructions without a registered scope are left out
    assert all(scopes.innermost_scope(p) is None
               for n, p in by_name.items() if n not in found)


def test_a_second_signature_brings_a_map_of_its_own(params, monkeypatch):
    """XLA numbers each signature's instructions anew (``fusion.N`` of
    one prefill bucket is another instruction in the next), so the
    registry harvests the map at every fresh signature and merges them
    by name AND key: each bucket's instructions then find their own
    scope, and a name that checks under no key finds none."""
    monkeypatch.setenv("RAYTPU_DEVICE_STATS_COST", "1")  # conftest: 0
    reg = ProgramRegistry()
    _, _, _paged_cache, fns = _serving("gpt2")
    prefill_sample = fns["prefill_sample"]
    fn = reg.instrument("serve.prefill", jax.jit(prefill_sample))
    key = jax.random.PRNGKey(1)
    texts = []
    for bucket in (16, 32):
        args = (params, _paged_cache(),
                jnp.zeros((1, bucket), jnp.int32),
                jnp.zeros(4, jnp.int32), key)
        jax.block_until_ready(fn(*args))
        texts.append(jax.jit(prefill_sample).lower(*args).compile()
                     .as_text())
    merged = reg.scope_map("jit_prefill_sample")
    assert reg.snapshot()["serve.prefill"]["compile_events"] == 2
    own = [scopes.scope_map_from_hlo(t) for t in texts]
    assert own[0] != own[1]
    # every instruction of either bucket finds its scope under its key
    for mine in own:
        for name, keyed in mine.items():
            (k, scope), = keyed.items()
            assert merged[name][k] in (scope, scopes.AMBIGUOUS)
    # the names of the two buckets overlap, and some stand for other
    # instructions: only the key tells them apart
    shared = set(own[0]) & set(own[1])
    assert shared and any(own[0][n] != own[1][n] for n in shared)
    n_ambiguous = sum(s == scopes.AMBIGUOUS for keyed in merged.values()
                      for s in keyed.values())
    assert n_ambiguous <= 0.01 * len(merged)


def test_a_key_is_cut_before_the_operands_whatever_follows():
    """On four chips an ``all-gather`` under ``embed`` read mismatched
    (0.33% of the sharded step): its ``backend_config`` holds `` = ``
    again, and the map's key was cut there (found by the check itself,
    PR 25)."""
    line = ('  %all-gather.49 = bf16[50304,1600]{0,1:T(8,128)(2,1)} '
            'all-gather(%convert.151), channel_id=86, dimensions={1}, '
            'metadata={op_name="jit(step)/loss_and_grad/jvp(embed)/'
            'convert_element_type" stack_frame_id=17}, backend_config='
            '{"note":"per_stride_size = 40243200 bytes"}')
    event = ('%all-gather.49 = bf16[50304,1600]{0,1:T(8,128)(2,1)} '
             'all-gather(bf16[50304,400]{0,1:T(8,128)(2,1)S(1)} '
             '%convert.151), channel_id=86')
    key = "bf16[50304,1600]{0,1:T(8,128)(2,1)} all-gather"
    assert scopes.instruction_key(event) == key
    assert scopes.scope_map_from_hlo(line) == {
        "all-gather.49": {key: "embed"}}


def test_merge_marks_one_key_under_two_scopes():
    a = {"fusion.1": {"f32[8]{0} fusion": "attn"}}
    b = {"fusion.1": {"f32[8]{0} fusion": "mlp", "f32[16]{0} fusion": "ln"},
         "copy.2": {"f32[8]{0} copy": "kv_pool"}}
    assert scopes.merge_scope_maps(a, b) == {
        "fusion.1": {"f32[8]{0} fusion": scopes.AMBIGUOUS,
                     "f32[16]{0} fusion": "ln"},
        "copy.2": {"f32[8]{0} copy": "kv_pool"}}
    assert scopes.merge_scope_maps(dict(b), b) == b       # idempotent


def test_registry_keeps_the_map_not_the_text(params, monkeypatch):
    """``instrument`` harvests the scope map where it harvests the cost
    model: from the side compile of a fresh signature."""
    monkeypatch.setenv("RAYTPU_DEVICE_STATS_COST", "1")  # conftest: 0
    reg = ProgramRegistry()
    step, tx = _train_step()
    wrapped = reg.instrument("train.step", step)
    batch = {"tokens": jnp.zeros((4, 33), jnp.int32)}
    mine = jax.tree.map(jnp.copy, params)        # the step donates
    out = wrapped(mine, tx.init(mine), batch)
    jax.block_until_ready(out)
    by_registry_name = reg.scope_map("train.step")
    assert by_registry_name and by_registry_name == reg.scope_map(
        "jit_step")                        # what a trace calls it
    assert reg.scope_map("jit_nothing") is None
    assert "optimizer" in _scopes_in(by_registry_name)
    rec = reg._programs["train.step"]
    assert rec["module"] == "jit_step"
    assert all(isinstance(name, str) and isinstance(k, str)
               and isinstance(v, str)
               for name, keyed in rec["scope_map"].items()
               for k, v in keyed.items())
    assert "scope_map" not in reg.snapshot()["train.step"]  # golden shape


def test_registry_without_harvest_has_no_map(params, monkeypatch):
    monkeypatch.setenv("RAYTPU_DEVICE_STATS_COST", "0")
    reg = ProgramRegistry()
    fn = reg.instrument("serve.decode", jax.jit(lambda x: x + 1))
    fn(jnp.ones(3))
    assert reg.scope_map("serve.decode") is None


def test_the_compile_cache_settings_keep_the_name_stack(monkeypatch,
                                                        tmp_path):
    """What ``enable_compile_cache`` sets must not eat the scopes: it
    keeps a Mosaic kernel's key the same from every call site by
    cutting the frames out of locations.  The setting it used
    before, no full tracebacks, gives the same key but lowers every
    ``op_name`` to its bare primitive (seen in the chip's compiled
    text, PR 25): the second half of this test pins why it went."""
    from ray_tpu._private import compile_cache

    def f(x):
        with jax.named_scope(scopes.ATTN):
            return x * 2.0 + 1.0

    def op_names():
        # a fresh function each time: lowering is cached per function
        text = jax.jit(lambda x: f(x)).lower(
            jnp.ones(4)).compile().as_text()
        return _scopes_in(scopes.scope_map_from_hlo(text))

    # with the variable set, enable_compile_cache moves no directory
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    limit = jax.config.jax_traceback_in_locations_limit
    try:
        compile_cache.enable_compile_cache()
        assert op_names() == {scopes.ATTN}
        jax.config.update("jax_include_full_tracebacks_in_locations",
                          False)
        assert op_names() == set()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations",
                          True)
        jax.config.update("jax_traceback_in_locations_limit", limit)
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", False)


def test_a_cache_hit_never_hands_back_another_commits_names(tmp_path):
    """By default the persistent cache's key leaves metadata out, so a
    program compiled first without scopes (by the commit before) is a
    hit for the same graph with scopes, and its text knows none: on the
    chip the prefill program's scope map read 0 entries after the
    parent commit had run (PR 25).  ``enable_compile_cache`` therefore
    puts the names into the key."""
    from jax.experimental.compilation_cache import compilation_cache

    from ray_tpu._private import compile_cache

    def plain(x):
        return jnp.tanh(x @ x) * 2.0 + 1.0

    def scoped(x):
        with jax.named_scope(scopes.ATTN):
            return jnp.tanh(x @ x) * 2.0 + 1.0

    def scopes_of(fn):
        fn.__name__ = "same_program"          # one HloModule name
        jax.clear_caches()                     # only the disk can hit
        text = jax.jit(fn).lower(jnp.ones((8, 8))).compile().as_text()
        return _scopes_in(scopes.scope_map_from_hlo(text))

    knobs = {"jax_compilation_cache_dir": str(tmp_path),
             "jax_persistent_cache_min_compile_time_secs": 0,
             "jax_persistent_cache_min_entry_size_bytes": -1,
             "jax_traceback_in_locations_limit": 0,
             "jax_compilation_cache_include_metadata_in_key": False}
    before = {k: getattr(jax.config, k) for k in knobs}
    try:
        for k, v in knobs.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        assert scopes_of(plain) == set()
        assert scopes_of(scoped) == set()     # the stale hit, as found
        for k, v in compile_cache._SETTINGS.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        assert scopes_of(plain) == set()
        assert scopes_of(scoped) == {scopes.ATTN}
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_flash_kernels_carry_their_own_names():
    """``pl.pallas_call(name=)``: the name reaches the lowered program,
    so a Mosaic call in a trace no longer has to be told apart by its
    result shapes."""
    from ray_tpu.ops.flash_attention import flash_attention

    q = jnp.ones((1, 128, 2, 64), jnp.bfloat16)

    def loss(q, k, v, resident):
        return flash_attention(q, k, v, causal=True, interpret=True,
                               resident_kv=resident).astype(
                                   jnp.float32).sum()

    for resident, names in ((False, scopes.KERNELS[:3]),
                            (True, scopes.KERNELS[3:6])):
        jaxpr = str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: loss(q, k, v, resident), argnums=(0, 1, 2)))(
                q, q, q))
        for name in names:
            assert name in jaxpr, (resident, name)
    # the default causal path at a T its tile divides: the triangle
    # kernels, which say in the trace that they ran
    q = jnp.ones((1, 256, 2, 64), jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: loss(q, k, v, None), argnums=(0, 1, 2)))(q, q, q))
    for name in scopes.KERNELS[6:8]:
        assert name in jaxpr, name
