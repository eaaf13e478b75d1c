"""What the Kimi-K2 programs call their own parts on the profiler's
timeline (ray_tpu/_private/scopes.py): ``mla``, ``moe_router``,
``moe_experts`` beside the scopes every family has, at most a tenth of
the operations outside any, and the compiler's own grouped-matmul
kernel named after what it was made from."""

import collections

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu._private import scopes  # noqa: E402
from ray_tpu.models.decode_common import sample_token  # noqa: E402
from ray_tpu.models.kimi_k2 import (kimi_k2_config,  # noqa: E402
                                    kimi_k2_init)
from ray_tpu.models.kimi_k2_decode import (  # noqa: E402
    kimi_k2_decode_step, kimi_k2_init_cache, kimi_k2_init_paged_cache,
    kimi_k2_paged_prefill)
from ray_tpu.models import experts as ex  # noqa: E402
from tests.test_scopes import _op_scopes  # noqa: E402

CFG = kimi_k2_config("nano", held=(0, 1, 2, 3, 4, 5))
EVERY = {"embed", "ln", "mla", "kv_pool", "mlp", "moe_router",
         "moe_experts", "lm_head", "sample", "layer_scan"}


@pytest.fixture(scope="module")
def params():
    return kimi_k2_init(jax.random.PRNGKey(0), CFG)


def _lowered(name, params):
    key = jax.random.PRNGKey(1)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    paged = kimi_k2_init_paged_cache(CFG, 2, num_blocks=20, block_size=16)

    def pool_step(p, cache, toks, key):
        logits, cache = kimi_k2_decode_step(p, cache, toks, CFG)
        return sample_token(logits, key, 0.0, None), cache

    def prefill_sample(p, cache, toks, row_bt, key):
        logits, cache = kimi_k2_paged_prefill(
            p, cache, toks, CFG, row_bt=row_bt, prefix_len=0, n_tail=5,
            slot=0)
        return sample_token(logits[None], key, 0.0, None), cache

    if name == "decode_step":
        return jax.jit(pool_step).lower(params, paged, i32(2), key)
    if name == "decode_step_dense":
        return jax.jit(pool_step).lower(
            params, kimi_k2_init_cache(CFG, 2), i32(2), key)
    return jax.jit(prefill_sample).lower(params, paged, i32(1, 16), i32(8),
                                         key)


@pytest.mark.parametrize("program", ["decode_step", "decode_step_dense",
                                     "paged_prefill"])
def test_at_most_a_tenth_of_a_program_is_unscoped(program, params):
    ops = _op_scopes(_lowered(program, params))
    assert len(ops) > 100
    found = collections.Counter(s for _, s in ops)
    assert set(found) - {None} == EVERY
    loose = [op for op, s in ops if s is None]
    assert len(loose) <= 0.10 * len(ops), collections.Counter(loose)
    alone = collections.Counter(
        op for op, s in ops if s in scopes.CONTAINER_SCOPES)
    assert not {"stablehlo.dot_general", "stablehlo.exponential",
                "stablehlo.gather", "stablehlo.scatter",
                "chlo.ragged_dot"} & set(alone), alone


@pytest.mark.parametrize("op_name,scope", [
    ("ragged-dot-none", "moe_experts"),
    ("ragged-dot-metadata", "moe_experts"),
    ("jit(pool_step)/layer_scan/while/body/closed_call/moe_experts/"
     "while/body/ragged_dot_general", "moe_experts"),
    ("jit(pool_step)/layer_scan/while/body/closed_call/moe_router/"
     "dot_general", "moe_router"),
    ("jit(pool_step)/layer_scan/while/body/closed_call/mla/kv_pool/"
     "gather", "kv_pool"),
    ("jit(pool_step)/layer_scan/while/body/closed_call/mla/exp", "mla"),
    ("jit(pool_step)/layer_scan/while/body/closed_call/mla/"
     "jit(mla_paged_decode)/pallas_call", "mla"),
])
def test_innermost_scope_of_the_new_names(op_name, scope):
    assert scopes.innermost_scope(op_name) == scope


def test_the_new_scopes_are_registered_and_hold_no_other():
    new = {scopes.MLA, scopes.MOE_ROUTER, scopes.MOE_EXPERTS}
    assert new <= scopes.DEVICE_SCOPES
    assert not new & scopes.CONTAINER_SCOPES
    assert set(scopes.REWRITTEN.values()) <= scopes.DEVICE_SCOPES


def _traced(program, params):
    """(primitive, its params, the name stack with the scans' and
    loops' around it joined) of every equation `program` traces to,
    kernels' own bodies apart.  Traced anew at every call (a trace is
    kept by the function's identity, and a test that steers
    ``jax.default_backend`` needs the program to ask again)."""
    paged = kimi_k2_init_paged_cache(CFG, 2, num_blocks=20, block_size=16)
    if program == "decode_step":
        fn = lambda p, c, t: kimi_k2_decode_step(p, c, t, CFG)  # noqa: E731
        args = (params, paged, jnp.zeros((2,), jnp.int32))
    else:
        # 16 columns: rows few an expert, as a decode wave's; 64: many
        t_pad = 64 if program == "long_prefill" else 16
        fn = lambda p, c, t, bt: kimi_k2_paged_prefill(  # noqa: E731
            p, c, t, CFG, row_bt=bt, prefix_len=0, n_tail=t_pad - 11,
            slot=0)
        args = (params, paged, jnp.zeros((1, t_pad), jnp.int32),
                jnp.zeros((8,), jnp.int32))
    found = []

    def walk(inner, stack):
        for eqn in inner.eqns:
            here = f"{stack}/{eqn.source_info.name_stack}"
            found.append((eqn.primitive.name, eqn.params, here))
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, here)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, "jit(program)")
    return found


#: which programs hand an expert few rows (`experts.few_a_group`)
FEW = {"decode_step": True, "paged_prefill": True, "long_prefill": False}


@pytest.mark.parametrize("program", FEW)
def test_the_chips_kernels_are_named_and_scoped(program, params,
                                                monkeypatch):
    """On the chip the paged decode step holds the kernel
    ``mla_paged_decode`` (ray_tpu/ops/mla_paged_decode.py), one call in
    each scan over layers, under ``mla``: not unscoped, not ``kv_pool``
    (a reader divides the kernel's bytes by the time under ``mla``);
    and both programs move the held experts' rows with ``moe_dispatch``
    and ``moe_combine`` (ray_tpu/ops/moe_dispatch.py), one call each in
    the expert layers' scan, inside the loop over row tiles, under
    ``moe_experts``; between them, whether an expert expects few rows
    (a decode wave, a short prefill) or many, ONE ``grouped_swiglu``
    (ray_tpu/ops/grouped_swiglu.py) under ``moe_experts`` too, so that
    its time reads as the experts' (`moe_time_share.offline`,
    `moe_expert_roofline.offline`) and nothing new reads unscoped.
    Traced only: tests/test_tpu_compile.py reads the
    compiled programs' own scope maps."""
    assert {scopes.MLA_PAGED_DECODE, scopes.MOE_DISPATCH,
            scopes.MOE_COMBINE, scopes.GROUPED_SWIGLU} <= set(scopes.KERNELS)
    assert ex.few_a_group({"decode_step": 2, "paged_prefill": 16,
                           "long_prefill": 64}[program],
                          CFG.experts) == FEW[program]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    by_scope = collections.defaultdict(list)
    for name, eqn_params, stack in _traced(program, params):
        if name == "pallas_call":
            by_scope[scopes.innermost_scope(stack)].append(
                eqn_params["name"])
    assert by_scope.pop(scopes.MOE_EXPERTS) == [
        scopes.MOE_DISPATCH, scopes.GROUPED_SWIGLU, scopes.MOE_COMBINE]
    # (a toy tail takes the prefill's jnp walk, not its flash kernel)
    assert dict(by_scope) == ({scopes.MLA: [scopes.MLA_PAGED_DECODE] * 2}
                              if program == "decode_step" else {})


@pytest.mark.parametrize("program", FEW)
def test_the_expert_layer_sorts_and_scatters_nothing(program, params,
                                                     monkeypatch):
    """What the serving programs hold under ``moe_experts`` off the
    chip is the kernels' `jnp` references, a sort and a scatter among
    them; on the chip (steered) no sort, gather or scatter is traced
    there at all: the rows are moved by the kernels, and multiplied
    by one kernel whether they are few an expert or many (no
    `ragged_dot` is left in a serving program)."""
    def under_experts():
        return collections.Counter(
            name for name, _, stack in _traced(program, params)
            if scopes.innermost_scope(stack) == scopes.MOE_EXPERTS)

    moved = {"sort", "gather", "scatter", "scatter-add", "scatter_add"}
    assert moved & set(under_experts())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip = under_experts()
    assert not moved & set(on_chip), on_chip
    ragged = on_chip["ragged_dot_general"] + on_chip["ragged_dot"]
    assert (on_chip["pallas_call"], ragged) == (3, 0), on_chip
