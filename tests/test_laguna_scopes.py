"""What the Laguna programs call their own parts on the profiler's
timeline (ray_tpu/_private/scopes.py): ``attn_full`` and ``attn_window``
by layer kind, ``kv_pool`` for pool and ring reads and writes,
``moe_router`` / ``moe_experts`` / ``mlp`` as every expert family's, at
most a tenth of the operations outside any."""

import collections

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu._private import scopes  # noqa: E402
from ray_tpu.models.decode_common import sample_token  # noqa: E402
from ray_tpu.models.laguna import laguna_config, laguna_init  # noqa: E402
from ray_tpu.models.laguna_decode import (  # noqa: E402
    laguna_decode_step, laguna_init_cache, laguna_init_paged_cache,
    laguna_paged_prefill)
from tests.test_scopes import _op_scopes  # noqa: E402

CFG = laguna_config("nano")
EVERY = {"embed", "ln", "attn_full", "attn_window", "kv_pool", "mlp",
         "moe_router", "moe_experts", "lm_head", "sample"}


@pytest.fixture(scope="module")
def params():
    return laguna_init(jax.random.PRNGKey(0), CFG)


def _lowered(name, params):
    key = jax.random.PRNGKey(1)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    paged = laguna_init_paged_cache(CFG, 2, num_blocks=20, block_size=16)

    def pool_step(p, cache, toks, key):
        logits, cache = laguna_decode_step(p, cache, toks, CFG)
        return sample_token(logits, key, 0.0, None), cache

    def prefill_sample(p, cache, toks, row_bt, key, state):
        logits, cache = laguna_paged_prefill(
            p, cache, toks, CFG, row_bt=row_bt, prefix_len=0, n_tail=21,
            slot=0, state=state)
        return sample_token(logits[None], key, 0.0, None), cache

    if name == "decode_step":
        return jax.jit(pool_step).lower(params, paged, i32(2), key)
    if name == "decode_step_dense":
        return jax.jit(pool_step).lower(
            params, laguna_init_cache(CFG, 2), i32(2), key)
    return jax.jit(prefill_sample).lower(params, paged, i32(1, 32), i32(8),
                                         key, i32(3))


@pytest.mark.parametrize("program", ["decode_step", "decode_step_dense",
                                     "paged_prefill"])
def test_at_most_a_tenth_of_a_program_is_unscoped(program, params):
    ops = _op_scopes(_lowered(program, params))
    assert len(ops) > 100
    found = collections.Counter(s for _, s in ops)
    assert set(found) - {None} == EVERY
    loose = [op for op, s in ops if s is None]
    assert len(loose) <= 0.10 * len(ops), collections.Counter(loose)
    # what does the work is under a scope of its own, never bare
    heavy = {"stablehlo.dot_general", "stablehlo.exponential",
             "stablehlo.gather", "stablehlo.scatter", "chlo.ragged_dot"}
    assert not heavy & set(loose), collections.Counter(loose)


@pytest.mark.parametrize("program", ["decode_step", "paged_prefill"])
def test_each_kinds_scores_are_under_its_own_scope(program, params):
    """The exponentials of the softmaxes (one running softmax a kind in
    each program) lie under ``attn_full`` and ``attn_window``; the
    gathers of the pool and the ring under ``kv_pool``."""
    ops = _op_scopes(_lowered(program, params))
    exps = collections.Counter(s for op, s in ops
                               if op == "stablehlo.exponential")
    assert exps[scopes.ATTN_FULL] and exps[scopes.ATTN_WINDOW]
    moved = collections.Counter(
        s for op, s in ops
        if op in ("stablehlo.gather", "stablehlo.scatter"))
    assert moved[scopes.KV_POOL]
    assert not moved[scopes.ATTN_FULL] and not moved[scopes.ATTN_WINDOW]


@pytest.mark.parametrize("op_name,scope", [
    ("jit(pool_step)/attn_window/kv_pool/scatter", "kv_pool"),
    ("jit(pool_step)/attn_window/exp", "attn_window"),
    ("jit(pool_step)/attn_full/while/body/attn_full/kv_pool/gather",
     "kv_pool"),
    ("jit(pool_step)/attn_full/while/body/attn_full/dot_general",
     "attn_full"),
    ("jit(pool_step)/attn_full/jit(gqa_paged_decode)/gqa_paged_decode",
     "attn_full"),
    ("jit(prefill)/attn_window/while/body/closed_call/attn_window/"
     "while/body/attn_window/exp", "attn_window"),
    ("jit(prefill)/moe_experts/while/body/moe_experts/ragged_dot_general",
     "moe_experts"),
])
def test_innermost_scope_of_the_new_names(op_name, scope):
    assert scopes.innermost_scope(op_name) == scope


def test_the_chips_decode_step_walks_the_pool_under_attn_full(
        params, monkeypatch):
    """On the chip (the backend steered) a paged decode step's full
    layers are the kernel ``gqa_paged_decode``, one call a layer, each
    under ``attn_full`` and not under ``kv_pool``: the time of the walk
    reads as the layer's attention (`full_attn_time_share.offline`),
    and `attn_decode_roofline.offline` divides the three scopes' sum."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    paged = laguna_init_paged_cache(CFG, 2, num_blocks=20, block_size=16)
    jaxpr = jax.make_jaxpr(
        lambda c, t: laguna_decode_step(params, c, t, CFG))(
            paged, jnp.zeros((2,), jnp.int32)).jaxpr

    def kernels(jaxpr, stack=""):
        for eqn in jaxpr.eqns:
            path = f"{stack}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"], scopes.innermost_scope(path)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from kernels(sub, path)

    walks = [scope for name, scope in kernels(jaxpr)
             if name == scopes.GQA_PAGED_DECODE]
    assert walks == [scopes.ATTN_FULL] * len(CFG.layers_of("full")), walks
    assert scopes.GQA_PAGED_DECODE in scopes.KERNELS
    assert scopes.GQA_PAGED_DECODE not in scopes.DEVICE_SCOPES


@pytest.mark.parametrize("rows,few", [(2, True), (64, False)],
                         ids=["a_decode_wave", "a_long_prefill"])
def test_the_experts_kernel_is_under_moe_experts(params, monkeypatch,
                                                 rows, few):
    """On the chip (steered) a decode wave's expert layers hold ONE
    ``grouped_swiglu`` each between ``moe_dispatch`` and
    ``moe_combine``, all three under ``moe_experts`` (nothing new reads
    unscoped, and `moe_expert_roofline.offline` divides the time the
    kernel takes); a prefill that hands an expert many rows holds the
    same three (its groups end to end under tall row tiles) and no
    `ragged_dot`."""
    from ray_tpu.models import experts as ex
    from ray_tpu.models.laguna_decode import laguna_paged_prefill

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ex.few_a_group(rows, CFG.experts) == few
    paged = laguna_init_paged_cache(CFG, 2, num_blocks=20, block_size=16)
    if few:
        fn = lambda c, t: laguna_decode_step(params, c, t, CFG)  # noqa: E731
        args = (paged, jnp.zeros((rows,), jnp.int32))
    else:
        fn = lambda c, t: laguna_paged_prefill(  # noqa: E731
            params, c, t, CFG, row_bt=jnp.zeros((8,), jnp.int32),
            prefix_len=0, n_tail=rows - 3, slot=0)
        args = (paged, jnp.zeros((1, rows), jnp.int32))
    found = []

    def walk(jaxpr, stack=""):
        for eqn in jaxpr.eqns:
            path = f"{stack}/{eqn.source_info.name_stack}"
            name = eqn.primitive.name
            if name == "pallas_call":
                found.append((eqn.params["name"],
                              scopes.innermost_scope(path)))
                continue
            if name.startswith("ragged_dot"):
                found.append((name, scopes.innermost_scope(path)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, path)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    experts = [name for name, scope in found
               if scope == scopes.MOE_EXPERTS]
    assert all(scope is not None for _, scope in found), found
    layer = [scopes.MOE_DISPATCH, scopes.GROUPED_SWIGLU, scopes.MOE_COMBINE]
    n_sparse = sum(kind == "sparse" for kind in CFG.mlp_types)
    assert n_sparse and experts == layer * n_sparse, experts
    assert scopes.GROUPED_SWIGLU in scopes.KERNELS
    assert scopes.GROUPED_SWIGLU not in scopes.DEVICE_SCOPES


def test_the_new_scopes_are_registered_and_hold_no_other():
    new = {scopes.ATTN_FULL, scopes.ATTN_WINDOW}
    assert new <= scopes.DEVICE_SCOPES
    assert not new & scopes.CONTAINER_SCOPES
    assert not new & set(scopes.KERNELS)
