#!/usr/bin/env python3
"""The quickest proof that ray_tpu still starts, compiles and answers on
the chip.

    python chip_smoke.py             # one TPU chip: train, serve, runtime
    python chip_smoke.py --chips 4   # one four-chip host: the paths that
                                     # exist only across chips

It drives the device plane's main path once through the entry points a
user calls, at the full width of GPT-2 124M (12 layers, d 768, 12 heads,
padded vocabulary 50,304, bf16, sequence 1,024), with weights, batches
and prompts made from a seed, and checks every result against a plain
reference.  Any phase that fails makes the script exit non-zero; only
when all passed is the last line of stdout

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reported it in the processes that held the chip.
Without a TPU (``JAX_PLATFORMS=cpu``, or no accelerator) it exits non-zero
and prints no such line.  It needs ``g++`` (the object store builds on
first import), no network and no files besides the checkout.

One process owns a chip at a time.  This process never imports JAX: it
runs each phase as a child, one after another, and each child has exited
— and freed the chip — before the next starts.  The ``runtime`` child
does not import JAX either; there the ``num_tpus=1`` actor's worker owns
the chip.  All of them share one persistent compilation cache
(ray_tpu/_private/compile_cache.py).

Phases, one chip:
  train    JaxTrainer's build_train_step over gpt2_loss, batch 24, flash
           attention by auto-dispatch, mlp_only remat, donated.
  serve    the continuous engine with the paged KV cache under
           shared-prefix traffic, against models.gpt2_decode.generate.
  runtime  the README quick start: ray_tpu.init() finds the chip, a
           num_tpus=1 actor trains on it, a num_tpus=0 task stays off it.
  mla      the kernels of the Kimi-K2 cell at its shapes, each against
           its jnp reference: the prefill's flash kernel, the decode
           step's walk over the paged latent pool, the rotary pool's
           re-lay, and the expert layer's dispatch and combine.
  gqa      the Laguna-XS.2 cell's decode kernel at its shapes against
           its jnp reference: the full layers' walk over the paged
           grouped-query K/V pools.
  kda      the Solar-Open2 cell's two kernels at its shapes (64 heads
           of 128).  A prefill's: 1,024 and 8,192 columns with pads at
           the left, a state handed in, beta up to 2, a captured
           column: the chunked delta rule as one kernel against the jnp
           scan and, at 1,024, against the recurrence.  A decode
           wave's: 64 rows, idle ones among them, on the middle layer of
           a stack of three, against `kda_step` on that layer.  Prints
           both forms' milliseconds, and the wave's GB/s.  And the
           Olmo-Hybrid cell's prefill kernel at its shape (30 heads of
           96 x 192, ONE decay a head, 1,024 and 6,144 columns, bf16
           operands) against the jnp matmul form, run unedited as the
           oracle: the largest relative error, both forms'
           milliseconds a layer and compiled temporaries; and its
           decode wave's kernel (32 rows, the middle layer of a stack
           of three, float32) against `kda_step` on that layer.
  ring     the window layers' decode kernel at the Phi-4-mini-flash and
           Laguna-XS.2 cells' shapes (eight stacked rings of 64 rows x
           512 x 1,280 lanes under 10 pair-heads; three of 1,024 lanes
           under 8 K/V heads): every row's ring read where it lies in
           the stack against `attend_rows` over the ring sliced out, in
           the first and last layer, rows from idle to several laps;
           then a wave as the decode steps run it (a scan over the
           layers, the new rows scattered into the donated stacks): the
           compiled program aliases both stacks and holds no temporary
           of a ring's size.  Prints ms a call and GB/s.
  banded   a prefill's banded flash kernel (ops/banded_flash.py) at
           the geometries of the three cells that share it (Laguna's
           full layers, 48 query heads over 8 K/V heads, and its window
           layers, 64; Phi-4-mini-flash's 40 over 10 pair-heads at its
           stated scale; Solar-Open2's 64 over 8): a causal triangle
           over the gathered view or a band of 512 over the laid ring
           and tail, pad columns at the left, at 1,024 columns and at
           the largest bucket, against the jnp walk `banded_walk`.
           Prints the kernel's and the walk's milliseconds a layer
           beside the arithmetic's at the chip's peak.
Phases, --chips 4 (and no one-chip phase):
  mesh_train    the train step over data=4 and data=2 x fsdp=2 against
                the same step on device 0.
  tensor_serve  the paged engine with mesh= tensor degree 4 against the
                one-chip engine.
  fleet         four one-chip replicas behind build_llm_fleet, on four
                distinct devices.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List

ONE_CHIP = ("train", "serve", "runtime", "mla", "gqa", "kda", "ring",
            "banded")
FOUR_CHIPS = ("mesh_train", "tensor_serve", "fleet")
#: the driver allows 1200 s; leave room for the parent's own exit
DEADLINE_S = 1100.0
_RESULT_TAG = "CHIP_SMOKE_PHASE "
#: how a Pallas (Mosaic) kernel shows in a compiled program's text
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'

# Stated tolerances.  bf16 carries 8 bits of mantissa (2**-8 = 0.4%).
#: bf16 flash step vs float32 XLA reference, relative, on a loss of ~10.8
LOSS_REF_RTOL = 2e-3
#: sharded vs one-chip losses over the first MESH_STEPS steps, relative:
#: the same arithmetic reduced in another order (later steps are printed,
#: not held: at this learning rate without warm-up the loss bounces, and
#: a last-digit difference has room to grow)
LOSS_MESH_RTOL = 5e-3
MESH_STEPS = 3
#: kernel output/gradient vs XLA reference, max error over max magnitude
KERNEL_TOL = 3e-2
#: selective-scan kernel vs the XLA chain, float32 both, max error over
#: max magnitude of outputs and states: the same operations on the
#: state, one sum over d_state associated otherwise (the chip read
#: 4.8e-8 on outputs and 0 on both states at T = 1,024, PR 31;
#: tests/test_ssm_scan.py holds the CPU's 2.4e-7)
SCAN_TOL = 1e-5
#: where greedy tokens part from the oracle's, the oracle's own logit for
#: the engine's token must be this close to its maximum: a bf16 near-tie,
#: which seeded weights with nearly flat logits (std ~0.55, top-two gap
#: ~0.1) make likely.  Twelve layers of bf16 rounding put ~5e-3 of noise
#: on a logit, so two programs part by ~7e-3 and a third sees the gap of
#: a flipped pair within about four such deviations.
LOGIT_TIE_TOL = 3e-2


@dataclasses.dataclass
class Size:
    """What a run exercises.  The defaults are the real thing; a test
    passes a toy size to walk the control flow on the CPU."""

    preset: str = "gpt2"
    seq: int = 1024
    batch: int = 24
    steps: int = 6
    #: GPT2Config overrides on top of the on-chip defaults
    cfg: Dict[str, Any] = dataclasses.field(default_factory=dict)
    seed: int = 0
    # kernel checks: (B, T, H, D) attention, (N, D, V, valid V) fused CE
    #: the 124M step's heads, and one fsdp4 chip's share of the XL's
    #: (3 rows x 25 heads: an odd number of heads a chip)
    attn_shapes: tuple = ((2, 1024, 12, 64), (3, 1024, 25, 64))
    ce_shape: tuple = (2048, 768, 50304, 50257)
    #: (B, T, d_inner, d_state) of the selective scan: Jamba2-3B's
    #: published widths at a middle and at the largest prefill bucket
    scan_shapes: tuple = ((1, 640, 5120, 16), (1, 1024, 5120, 16))
    # mla: Kimi-K2's published widths; (T, prefix_len, real columns) of
    #: a prefill's tail over `mla_max_seq` slots: the largest bucket
    #: whole, and a short tail behind a long resident prefix
    mla_preset: str = "kimi-k2-code"
    mla_max_seq: int = 8704
    mla_tile: int = 512
    mla_prefills: tuple = ((8192, 0, 8155), (1024, 7173, 1019))
    #: (rows, pool blocks, layers) of a decode wave over the paged pool
    mla_wave: tuple = (64, 8193, 6)
    #: (tokens, rows of grouped order a pass takes) of the expert
    #: layer's dispatch and combine: the largest prefill bucket, a
    #: wave; with the first `moe_held` experts held
    moe_rows: tuple = ((8192, 2304), (64, 32))
    moe_held: int = 12
    # gqa: Laguna-XS.2's published widths; (rows, pool blocks) of a
    #: decode wave over the full layers' paged K/V pools (the cell's:
    #: 4 GiB of K and V) under tables of `gqa_max_seq` slots
    gqa_preset: str = "laguna-xs2"
    gqa_max_seq: int = 8704
    gqa_wave: tuple = (64, 32768)
    # kda: Solar-Open2's published widths: (heads, head size) of a KDA
    #: layer, and (columns, pads at the left) of a prefill's delta
    #: rule: the smallest bucket, held to the recurrence too, and the
    #: largest; (rows, KDA layers in the stack) of a decode wave
    kda_heads: tuple = (64, 128)
    kda_prefills: tuple = ((1024, 37), (8192, 700))
    kda_wave: tuple = (64, 3)
    #: Olmo-Hybrid-7B's published widths: (heads, keys, values) of a
    #: Gated DeltaNet layer, ONE decay a head, and (columns, pads at the
    #: left) of a prefill's delta rule: the cell's smallest bucket and
    #: its largest
    delta_heads: tuple = (30, 96, 192)
    delta_prefills: tuple = ((1024, 37), (6144, 37))
    #: (rows, linear layers in the stack) of its decode wave
    delta_wave: tuple = (32, 3)
    # ring: (window layers, rows, window, query heads, K/V heads, head
    #: size, the scores' factor) of a decode wave over the stacked
    #: rings: Phi-4-mini-flash's eight window layers at its pair-heads
    #: (models/phi4flash.py `pairs`), Laguna-XS.2's three
    ring_waves: tuple = ((8, 64, 512, 40, 10, 128, 64 ** -0.5),
                         (3, 64, 512, 64, 8, 128, 128 ** -0.5))
    # banded: (query heads, K/V heads, the scores' factor, window or
    #: None, rows of a full layer's view, the oracle's tile, buckets)
    #: of a prefill's attention layer: Laguna-XS.2's full and window
    #: layers, Phi-4-mini-flash's window and full layers at its
    #: pair-heads, Solar-Open2's softmax layer
    banded_layers: tuple = (
        (48, 8, 128 ** -0.5, None, 8704, 512, (1024, 8192)),
        (64, 8, 128 ** -0.5, 512, None, 512, (1024, 8192)),
        (40, 10, 64 ** -0.5, 512, None, 256, (1024, 4096)),
        (40, 10, 64 ** -0.5, None, 4864, 256, (1024, 4096)),
        (64, 8, 128 ** -0.5, None, 8704, 512, (1024, 8192)))
    #: the kernel's own tiles (None) or a toy's (block_q, block_k)
    banded_tiles: Any = None
    # serve: bench.py's on-chip TrafficSpec cut to a few dozen requests
    requests: int = 32
    #: warm-up requests of the serve phase (another seed's traffic), and
    #: the whole load of each four-chip serve comparison
    warm_requests: int = 12
    prefix_groups: int = 4
    prefix_len: int = 256
    tail_mean: float = 32.0
    tail_max: int = 128
    vocab: int = 50000
    rate_rps: float = 32.0
    max_slots: int = 8
    new_tokens: int = 64
    prefill_bucket: int = 128
    kv_block: int = 16


def say(phase: str, **facts) -> None:
    """One readable line per fact group; the last line is reserved."""
    print(f"[{phase}] " + " ".join(
        f"{k}={json.dumps(v, default=str)}" for k, v in facts.items()),
        flush=True)


def describe_devices() -> Dict[str, Any]:
    """The device as JAX reports it: the last line's "device" block."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_block(platform: str) -> Dict[str, Any]:
    """describe_devices(), after checking it is the platform this run is
    for — anything else ends the run before it does work."""
    device = describe_devices()
    if device["platform"] != platform:
        raise SystemExit(
            f"chip_smoke: JAX found platform {device['platform']!r}, not "
            f"{platform!r}: no accelerator, nothing to smoke")
    return device


def _peak_hbm(device) -> Any:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / (np.max(np.abs(want)) + 1e-6))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _check_flash(shape, seed: int, interpret: bool) -> None:
    """The default causal path (the triangle kernels at these shapes)
    beside the classic and the resident-kv kernels, forward and
    gradients against the XLA reference in float32."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import reference_attention
    from ray_tpu.ops.flash_attention import flash_attention

    B, T, H, D = shape
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(x, (B, T, H, D), jnp.bfloat16)
               for x in (kq, kk, kv))
    w = jax.random.normal(kw, (B, T, H, D), jnp.float32)

    def run(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)
        return jax.jit(lambda q, k, v: (
            attn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v))
        )(q, k, v)

    with jax.default_matmul_precision("float32"):
        want = run(lambda q, k, v: reference_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32)))
    for name, resident in (("flash_default", None),
                           ("flash_classic", False),
                           ("flash_resident", True)):
        t0 = time.perf_counter()
        got = run(lambda q, k, v: flash_attention(
            q, k, v, resident_kv=resident, interpret=interpret))
        jax.block_until_ready(got)
        errs = [_rel_err(got[0], want[0])] + [
            _rel_err(g, r) for g, r in zip(got[1], want[1])]
        say("train", kernel=name, shape=[B, T, H, D],
            err_o_dq_dk_dv=[round(e, 5) for e in errs],
            seconds=round(time.perf_counter() - t0, 2))
        assert max(errs) <= KERNEL_TOL, (name, errs)


def _check_ssm_scan(shape, seed: int, interpret: bool) -> None:
    """ops/ssm_scan.py against the XLA chain it replaces in a prefill:
    a left-padded row from a non-zero state with a captured column, the
    outputs and both states held to SCAN_TOL, the pads to the bit
    (the same row without them gives the same states), and what a call
    of each takes on the device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.ssm_scan import (selective_scan,
                                      selective_scan_reference)

    B, T, di, N = shape
    pads, capture = T // 5, jnp.int32(T - T // 3)
    ks = jax.random.split(jax.random.PRNGKey(seed + 2), 6)
    real = (jnp.arange(T) >= pads)[None, :, None]
    dt = jnp.where(real, jax.random.uniform(
        ks[0], (B, T, di), jnp.float32, 1e-3, 1e-1), 0.0)
    x = jnp.where(real, jax.random.normal(ks[1], (B, T, di)), 0.0)
    Bm, Cm = (jax.random.normal(k, (B, T, N)) for k in ks[2:4])
    A = -jnp.broadcast_to(
        jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, di))
    s0 = jax.random.normal(ks[5], (B, N, di))
    kernel = functools.partial(selective_scan, interpret=interpret)

    def chain(*a):                 # 32 columns a chunk, as the 3B has it
        return selective_scan_reference(*a[:6], 32, a[6])

    args = (dt, x, A, Bm, Cm, s0, capture)

    def layer_ms(fn, layers=2 if interpret else 26):
        """Device time a call: `layers` dependent calls in one program,
        as a prefill's walk over its Mamba layers has them (a single
        call is shorter than its dispatch)."""
        def walk(x):
            def layer(x, _):
                y, s, snap = fn(dt, x, *args[2:])
                return x + 1e-2 * y, (s[0, 0, 0], snap[0, 0, 0])
            return jax.lax.scan(layer, x, None, length=layers)

        walk = jax.jit(walk)
        jax.block_until_ready(walk(x))              # compiles
        t0 = time.perf_counter()
        jax.block_until_ready(walk(x))
        return (time.perf_counter() - t0) / layers * 1e3

    got, want = jax.jit(kernel)(*args), jax.jit(chain)(*args)
    errs = [_rel_err(g[:, pads:] if i == 0 else g,
                     w[:, pads:] if i == 0 else w)
            for i, (g, w) in enumerate(zip(got, want))]
    cut = lambda a: a[:, pads:]  # noqa: E731
    bare = kernel(cut(dt), cut(x), A, cut(Bm), cut(Cm), s0,
                  capture - pads)
    pads_exact = all(np.array_equal(np.asarray(g), np.asarray(b))
                     for g, b in zip(got[1:], bare[1:]))
    say("train", kernel="ssm_scan", shape=[B, T, di, N], pads=pads,
        err_y_state_snapshot=[float(f"{e:.3g}") for e in errs],
        pads_exact=pads_exact,
        kernel_ms_a_layer=round(layer_ms(kernel), 4),
        xla_chain_ms_a_layer=round(layer_ms(chain), 4))
    assert max(errs) <= SCAN_TOL and pads_exact, ("ssm_scan", errs)


def check_kernels(size: Size, *, interpret: bool = False) -> None:
    """The Pallas kernels the train step is built from — and those it
    does not take by default (classic and resident-kv flash, fused
    lm-head+CE) — each forward and backward against its XLA reference,
    and the selective-scan kernel a Jamba prefill takes against the XLA
    chain.  `interpret` is False on the chip; only a CPU test asks for
    the interpreter."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.layers import nll_from_logits
    from ray_tpu.ops.fused_ce import fused_lm_ce

    for shape in size.attn_shapes:
        _check_flash(shape, size.seed, interpret)

    N, Dm, V, valid = size.ce_shape
    kh, kt, kg = jax.random.split(jax.random.PRNGKey(size.seed + 1), 3)
    h = jax.random.normal(kh, (N, Dm), jnp.float32)
    wte = jax.random.normal(kt, (V, Dm), jnp.float32) * 0.02
    tgt = jax.random.randint(kg, (N,), 0, valid)

    def dense(h, wte):
        logits = jnp.einsum("nd,vd->nv", h.astype(jnp.bfloat16),
                            wte.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        return nll_from_logits(logits, tgt, valid, V)

    def fused(h, wte):
        return fused_lm_ce(h, wte, tgt, valid, interpret=interpret)

    def both(fn):
        return jax.jit(lambda h, wte: (
            fn(h, wte),
            jax.grad(lambda h, wte: jnp.mean(fn(h, wte)),
                     argnums=(0, 1))(h, wte)))(h, wte)

    t0 = time.perf_counter()
    with jax.default_matmul_precision("float32"):
        want = both(dense)
    got = both(fused)
    jax.block_until_ready(got)
    errs = [_rel_err(got[0], want[0])] + [
        _rel_err(g, r) for g, r in zip(got[1], want[1])]
    say("train", kernel="fused_lm_ce", shape=[N, Dm, V],
        err_nll_dh_dw=[round(e, 5) for e in errs],
        seconds=round(time.perf_counter() - t0, 2))
    assert max(errs) <= KERNEL_TOL, ("fused_lm_ce", errs)

    for shape in size.scan_shapes:
        _check_ssm_scan(shape, size.seed, interpret)


def train_setup(size: Size):
    """(cfg, loss_fn, tx, tokens): the train program at its on-chip
    defaults and one fixed seeded batch — shared by the train phase, the
    runtime phase's actor and the four-chip comparison, so they compile
    the same program and the cache can serve it."""
    import numpy as np
    import optax

    from ray_tpu.models import gpt2_config, gpt2_loss

    cfg = gpt2_config(size.preset, max_seq=size.seq,
                      **{"remat_policy": "mlp_only", **size.cfg})
    tokens = np.random.default_rng(size.seed).integers(
        0, cfg.vocab_size, (size.batch, size.seq + 1)).astype(np.int32)

    def loss_fn(params, batch):
        return gpt2_loss(params, batch, cfg)

    return cfg, loss_fn, optax.adamw(3e-4, weight_decay=0.1), tokens


def run_steps(step, params, opt_state, batch, n: int,
              phase: str = "train") -> List[float]:
    """n fenced steps of a build_train_step step; the losses."""
    losses = []
    for i in range(n):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        loss.block_until_ready()
        losses.append(float(loss))
        say(phase, step=i, loss=round(losses[-1], 5),
            seconds=round(time.perf_counter() - t0, 3))
    return losses


def compile_step(step, params, opt_state, batch, watch, phase: str):
    """AOT-compile the step once for its text and memory analysis and
    print what it cost (the calls that follow reuse the executable).
    Returns it and whether the persistent cache served it."""
    hits, writes = watch.hits, watch.writes
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, batch).compile()
    seconds = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    say(phase, compile_seconds=round(seconds, 2),
        cache_hit=watch.hits > hits, cache_write=watch.writes > writes,
        argument_bytes=ma.argument_size_in_bytes,
        alias_bytes=ma.alias_size_in_bytes,
        peak_memory_bytes=ma.peak_memory_in_bytes)
    return compiled, watch.hits > hits


def assert_losses_fall(losses: List[float]) -> None:
    import math

    assert all(math.isfinite(x) for x in losses), losses
    assert losses[-1] < losses[0], losses


def phase_train(size: Size, platform: str = "tpu") -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from ray_tpu._private.compile_cache import CompileWatch
    from ray_tpu.models import gpt2_init, gpt2_loss
    from ray_tpu.train.jax_trainer import jax_utils

    device = device_block(platform)
    watch = CompileWatch()
    check_kernels(size)

    cfg, loss_fn, tx, tokens = train_setup(size)
    params = gpt2_init(jax.random.PRNGKey(size.seed), cfg)
    batch = {"tokens": tokens}

    # the plain reference: the initial parameters' loss on two
    # sequences, flash + bf16 against XLA attention in float32
    two = {"tokens": tokens[:2]}
    ref_cfg = dataclasses.replace(cfg, use_flash=False,
                                  dtype=jnp.float32)
    loss_chip = float(jax.jit(loss_fn)(params, two))
    with jax.default_matmul_precision("float32"):
        loss_ref = float(jax.jit(
            lambda p, b: gpt2_loss(p, b, ref_cfg))(params, two))
    say("train", loss_two_rows=round(loss_chip, 5),
        loss_two_rows_f32_reference=round(loss_ref, 5),
        rel_diff=round(abs(loss_chip - loss_ref) / abs(loss_ref), 6))
    assert abs(loss_chip - loss_ref) <= LOSS_REF_RTOL * abs(loss_ref)

    opt_state = tx.init(params)
    step = jax_utils.build_train_step(loss_fn, tx,
                                      telemetry_name="chip_smoke")
    compiled, _ = compile_step(step, params, opt_state, batch, watch,
                               "train")
    if platform == "tpu":
        # the flash kernels are in the program (the triangle forward
        # and its one-pass backward): the XLA reference did not run in
        # their place
        n_kernels = compiled.as_text().count(MOSAIC_CALL)
        say("train", tpu_custom_calls=n_kernels)
        assert n_kernels >= 2, n_kernels
    losses = run_steps(step, params, opt_state, batch, size.steps)
    assert_losses_fall(losses)
    say("train", peak_hbm_bytes=_peak_hbm(jax.devices()[0]),
        compiles=watch.compiles, cache_hits=watch.hits,
        cache_writes=watch.writes)
    return {"device": device, "losses": losses}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def traffic(size: Size, seed: int, n: int):
    from ray_tpu.serve.traffic import TrafficGenerator, TrafficSpec

    return TrafficGenerator(TrafficSpec(
        num_requests=n, seed=seed, rate_rps=size.rate_rps,
        num_prefix_groups=size.prefix_groups, prefix_len=size.prefix_len,
        p_shared=0.75, tail_len_mean=size.tail_mean,
        tail_len_max=size.tail_max, vocab=size.vocab)).requests()


def engine_kw(size: Size) -> Dict[str, Any]:
    return dict(scheduler="continuous", kv_layout="paged",
                kv_block_size=size.kv_block, max_slots=size.max_slots,
                max_new_tokens=size.new_tokens, temperature=0.0,
                prefill_bucket=size.prefill_bucket, seed=size.seed,
                config_overrides=dict(size.cfg) or None)


async def fire(target, requests, time_scale: float = 1.0):
    """Send each request at its arrival time, as serve.traffic.drive
    does, and keep the answers; any failure propagates."""
    import asyncio

    import numpy as np

    t0 = time.perf_counter()

    async def one(req):
        delay = req.arrival_s * time_scale - (time.perf_counter() - t0)
        if delay > 0:
            await asyncio.sleep(delay)
        return np.asarray(await target(req.prompt))

    return await asyncio.gather(*[one(r) for r in requests])


def assert_answered(requests, outs, new_tokens: int) -> None:
    import numpy as np

    assert len(outs) == len(requests)
    for req, out in zip(requests, outs):
        n = len(req.prompt)
        assert out.shape == (n + new_tokens,), (out.shape, n)
        assert np.array_equal(out[:n], req.prompt)


def oracle_generate(params, cfg, prompts, new_tokens: int):
    """models.gpt2_decode.generate on the dense cache, greedy: the
    prompts left-padded into one ragged batch, so one program serves."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.gpt2_decode import generate

    lens = [len(p) for p in prompts]
    t0 = max(lens)
    padded = np.zeros((len(prompts), t0), np.int32)
    for i, p in enumerate(prompts):
        padded[i, t0 - lens[i]:] = p
    out = np.asarray(jax.jit(lambda p, toks, n: generate(
        p, toks, cfg, lengths=n, max_new_tokens=new_tokens,
        temperature=0.0))(params, jnp.asarray(padded),
                          jnp.asarray(lens, jnp.int32)))
    return [out[i, t0 - lens[i]:] for i in range(len(prompts))]


def assert_same_greedy(phase: str, label: str, params, cfg, prompt,
                       got, want) -> bool:
    """Greedy tokens must be identical.  Where bf16 near-ties part them,
    the rule is stated, not loosened: at the first diverging position
    the oracle, teacher-forced on the engine's own tokens, must hold the
    engine's token within LOGIT_TIE_TOL of its maximum logit (position 0
    is the first-step logits).  Returns whether identity held."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt2_forward

    n = len(prompt)
    if np.array_equal(got, want):
        say(phase, request=label, prompt_len=n, token_identical=True)
        return True
    pos = int(np.argmax(got[n:] != want[n:]))
    logits = np.asarray(jax.jit(
        lambda p, t: gpt2_forward(p, t, cfg)[0, -1])(
            params, jnp.asarray(got[None, :n + pos])))[:cfg.vocab_size]
    gap = float(logits.max() - logits[got[n + pos]])
    say(phase, request=label, prompt_len=n, token_identical=False,
        first_diverging_position=pos, engine_token=int(got[n + pos]),
        oracle_token=int(want[n + pos]), oracle_logit_gap=round(gap, 5),
        tolerance=LOGIT_TIE_TOL)
    assert gap <= LOGIT_TIE_TOL, (label, pos, gap)
    return False


def phase_serve(size: Size, platform: str = "tpu") -> Dict[str, Any]:
    import asyncio

    import jax

    from ray_tpu._private.compile_cache import CompileWatch
    from ray_tpu.serve.llm import build_llm_deployment

    device = device_block(platform)
    watch = CompileWatch()
    t0 = time.perf_counter()
    engine = build_llm_deployment("gpt2", size.preset,
                                  **engine_kw(size)).func_or_class()
    say("serve", engine_build_seconds=round(time.perf_counter() - t0, 2))
    warm = traffic(size, size.seed + 1, size.warm_requests)
    requests = traffic(size, size.seed, size.requests)

    async def main():
        try:
            t0 = time.perf_counter()
            assert_answered(warm, await fire(engine, warm, 0.0),
                            size.new_tokens)
            say("serve", warm_up_seconds=round(
                time.perf_counter() - t0, 2), compiles=watch.compiles,
                cache_hits=watch.hits, cache_writes=watch.writes)
            seen = {r["id"] for r in engine.trace_records()}
            compiles, t0 = watch.compiles, time.perf_counter()
            outs = await fire(engine, requests)
            seconds = time.perf_counter() - t0
            stats = engine.engine_stats()
            records = [r for r in engine.trace_records()
                       if r["id"] not in seen]
            return outs, seconds, watch.compiles - compiles, stats, \
                records
        finally:
            engine.shutdown_engine()

    outs, seconds, late_compiles, stats, records = asyncio.run(main())
    assert_answered(requests, outs, size.new_tokens)
    kv = stats["kv_cache"]
    say("serve", requests=len(requests), seconds=round(seconds, 2),
        finished=stats["requests"]["finished"],
        tokens_generated=stats["tokens_generated"],
        prefix_block_hits=kv["prefix_block_hits"],
        prefix_hit_rate=kv["prefix_hit_rate"],
        prefill_buckets=stats["prefill_buckets"],
        compiles_after_warm_up=late_compiles,
        peak_hbm_bytes=_peak_hbm(jax.devices()[0]))
    assert kv["prefix_block_hits"] > 0, kv
    assert late_compiles == 0, late_compiles

    # one request that hit the prefix cache and one that met its prefix
    # cold, told apart by the engine's own per-request records (matched
    # to requests by prompt length, in order of arrival)
    assert len(records) == len(requests), (len(records), len(requests))
    waiting = sorted(range(len(requests)),
                     key=lambda i: requests[i].arrival_s)
    hit_blocks = {}
    for rec in sorted(records, key=lambda r: r["id"]):
        i = next(i for i in waiting
                 if len(requests[i].prompt) == rec["prompt_len"])
        waiting.remove(i)
        hit_blocks[i] = rec["kv_reserve"][3]
    shared = [i for i, r in enumerate(requests) if r.group >= 0]
    picks = {"prefix_hit": [i for i in shared if hit_blocks[i] > 0],
             "cold": [i for i in shared if hit_blocks[i] == 0]}
    assert all(picks.values()), picks
    picks = {label: found[0] for label, found in picks.items()}
    want = oracle_generate(engine.params, engine.cfg,
                           [requests[i].prompt for i in picks.values()],
                           size.new_tokens)
    identical = [assert_same_greedy(
        "serve", f"{label}#{i}", engine.params, engine.cfg,
        requests[i].prompt, outs[i], w)
        for (label, i), w in zip(picks.items(), want)]
    return {"device": device, "token_identical": all(identical)}


# ---------------------------------------------------------------------------
# runtime
# ---------------------------------------------------------------------------

def actor_train(size: Size, steps: int) -> Dict[str, Any]:
    """Runs inside the num_tpus=1 actor: the train phase's program again,
    in another process — so its compile must come from the cache."""
    import jax

    from ray_tpu._private.compile_cache import (CompileWatch,
                                                compile_cache_dir)
    from ray_tpu.models import gpt2_init
    from ray_tpu.train.jax_trainer import jax_utils

    watch = CompileWatch()
    cfg, loss_fn, tx, tokens = train_setup(size)
    params = gpt2_init(jax.random.PRNGKey(size.seed), cfg)
    opt_state = tx.init(params)
    batch = {"tokens": tokens}
    step = jax_utils.build_train_step(loss_fn, tx,
                                      telemetry_name="chip_smoke")
    _, cache_hit = compile_step(step, params, opt_state, batch, watch,
                                "runtime")
    losses = run_steps(step, params, opt_state, batch, steps, "runtime")
    return {"losses": losses, "cache_hit": cache_hit,
            "cache_dir": compile_cache_dir(),
            "device": describe_devices()}


def phase_runtime(size: Size, platform: str = "tpu",
                  num_tpus: Any = None) -> Dict[str, Any]:
    """The README's quick start.  `num_tpus=None` lets init() count the
    chips itself (node.py detect_num_tpus) — what a user's init() does;
    a CPU test passes the count, as there is nothing to find."""
    import ray_tpu

    t0 = time.perf_counter()
    ray_tpu.init(num_tpus=num_tpus)
    try:
        found = ray_tpu.cluster_resources().get("TPU", 0)
        say("runtime", init_seconds=round(time.perf_counter() - t0, 2),
            tpus_found=found)
        assert found == 1, found

        @ray_tpu.remote(num_tpus=1)
        class Learner:
            def train(self, size, steps):
                return actor_train(size, steps)

        @ray_tpu.remote(num_tpus=0)
        def off_chip_platform():
            import jax

            return jax.devices()[0].platform

        t0 = time.perf_counter()
        out = ray_tpu.get(Learner.remote().train.remote(size, 2),
                          timeout=900)
        say("runtime", actor_seconds=round(time.perf_counter() - t0, 2),
            **out)
        off_chip = ray_tpu.get(off_chip_platform.remote(), timeout=300)
        say("runtime", num_tpus_0_task_platform=off_chip)
    finally:
        ray_tpu.shutdown()
    assert out["device"]["platform"] == platform, out["device"]
    assert off_chip == "cpu", off_chip
    assert_losses_fall(out["losses"])
    if platform == "tpu":
        # the cache path is the same in every process: the train phase
        # compiled this program, the actor's worker must find it
        assert out["cache_hit"], out
    return {"device": out["device"], "losses": out["losses"]}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _bytes_on(tree, device) -> int:
    import jax

    return sum(s.data.nbytes for leaf in jax.tree.leaves(tree)
               for s in leaf.addressable_shards if s.device == device)


def phase_mesh_train(size: Size, platform: str = "tpu"
                     ) -> Dict[str, Any]:
    import jax

    from ray_tpu._private.compile_cache import CompileWatch
    from ray_tpu.models import gpt2_init, gpt2_logical_axes
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import shard_params
    from ray_tpu.train.jax_trainer import jax_utils

    device = device_block(platform)
    devices = jax.devices()
    assert len(devices) == 4, devices
    watch = CompileWatch()
    cfg, loss_fn, tx, tokens = train_setup(size)
    axes = gpt2_logical_axes(cfg)
    batch = {"tokens": tokens}

    def fresh():
        return gpt2_init(jax.random.PRNGKey(size.seed), cfg)

    # what it is compared with: the same step, batch and seed on device 0
    params = jax.device_put(fresh(), devices[0])
    total = _bytes_on(params, devices[0])
    # committed whole: optax's step counter is born uncommitted, and a
    # step whose inputs change from uncommitted to committed between
    # its first and second call compiles twice
    opt_state = jax.device_put(tx.init(params), devices[0])
    step = jax_utils.build_train_step(loss_fn, tx,
                                      telemetry_name="one_chip")
    compile_step(step, params, opt_state, batch, watch, "mesh_train")
    want = run_steps(step, params, opt_state, batch, size.steps,
                     "mesh_train:one_chip")
    del params, opt_state

    all_losses = {"one_chip": want}
    for name, spec, param_split in (
            ("data=4", MeshSpec(data=4), 1),
            ("data=2,fsdp=2", MeshSpec(data=2, fsdp=2), 2)):
        mesh = make_mesh(spec, devices=devices)
        with jax.set_mesh(mesh):
            params = shard_params(fresh(), axes, mesh)
            opt_state = tx.init(params)
            per_dev = [_bytes_on((params, opt_state), d) for d in devices]
            say("mesh_train", layout=name,
                param_bytes_unsharded=total,
                param_bytes_per_device=[_bytes_on(params, d)
                                        for d in devices],
                param_and_opt_bytes_per_device=per_dev)
            # split as the layout says — every device an equal share,
            # fsdp halving what each holds — not piled onto device 0
            assert len(set(per_dev)) == 1, per_dev
            held = _bytes_on(params, devices[0])
            assert abs(held - total // param_split) <= total // 100, \
                (held, total, param_split)
            step = jax_utils.build_train_step(
                loss_fn, tx, mesh=mesh, logical_axes=axes,
                telemetry_name=name)
            compiled, _ = compile_step(step, params, opt_state, batch,
                                       watch, f"mesh_train:{name}")
            text = compiled.as_text()
            say("mesh_train", layout=name,
                tpu_custom_calls=text.count(MOSAIC_CALL),
                all_reduces=text.count("all-reduce("),
                all_gathers=text.count("all-gather("))
            if platform == "tpu":
                assert text.count(MOSAIC_CALL) >= 2
            got = run_steps(step, params, opt_state, batch, size.steps,
                            f"mesh_train:{name}")
        del params, opt_state
        assert_losses_fall(got)
        rel = [abs(g - w) / abs(w) for g, w in zip(got, want)]
        say("mesh_train", layout=name,
            rel_loss_diff=[round(r, 7) for r in rel],
            held_steps=MESH_STEPS, tolerance=LOSS_MESH_RTOL)
        assert max(rel[:MESH_STEPS]) <= LOSS_MESH_RTOL, (name, got, want)
        all_losses[name] = got
    return {"device": device, "losses": all_losses}


def phase_tensor_serve(size: Size, platform: str = "tpu"
                       ) -> Dict[str, Any]:
    import asyncio

    import jax

    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.serve.llm import build_llm_deployment

    device = device_block(platform)
    devices = jax.devices()
    assert len(devices) == 4, devices
    requests = traffic(size, size.seed, size.warm_requests)
    mesh = make_mesh(MeshSpec(tensor=4), devices=devices)
    engines = {
        "one_chip": build_llm_deployment(
            "gpt2", size.preset, **engine_kw(size)
        ).func_or_class(device=devices[0]),
        "tensor=4": build_llm_deployment(
            "gpt2", size.preset, mesh=mesh, **engine_kw(size)
        ).func_or_class(),
    }
    sharded = engines["tensor=4"]
    say("tensor_serve",
        wte_devices=len(sharded.params["wte"].devices()),
        kv_pool_devices=len(sharded._cache["k"].devices()),
        kv_pool_shard_shape=list(
            sharded._cache["k"].addressable_shards[0].data.shape),
        kv_pool_shape=list(sharded._cache["k"].shape))
    assert len(sharded._cache["k"].devices()) == 4
    assert engines["one_chip"]._cache["k"].devices() == {devices[0]}

    async def main(engine):
        try:
            return await fire(engine, requests, 0.0)
        finally:
            engine.shutdown_engine()

    outs = {}
    for name, engine in engines.items():
        t0 = time.perf_counter()
        outs[name] = asyncio.run(main(engine))
        assert_answered(requests, outs[name], size.new_tokens)
        say("tensor_serve", engine=name, requests=len(requests),
            seconds=round(time.perf_counter() - t0, 2))
    one = engines["one_chip"]
    identical = [assert_same_greedy(
        "tensor_serve", f"request#{i}", one.params, one.cfg, r.prompt,
        outs["tensor=4"][i], outs["one_chip"][i])
        for i, r in enumerate(requests)]
    say("tensor_serve", token_identical=sum(identical),
        of=len(identical))
    return {"device": device, "token_identical": all(identical)}


def phase_fleet(size: Size, platform: str = "tpu") -> Dict[str, Any]:
    import asyncio

    import jax

    from ray_tpu.serve.router import build_llm_fleet

    device = device_block(platform)
    assert len(jax.devices()) == 4, jax.devices()
    requests = traffic(size, size.seed, size.warm_requests)
    kw = engine_kw(size)
    # the fleet forces scheduler and layout on, and `seed` is its own
    for k in ("scheduler", "kv_layout", "seed"):
        kw.pop(k)
    fleet = build_llm_fleet("gpt2", size.preset, num_replicas=4,
                            seed=size.seed, **kw)
    replicas = fleet.router.live_replicas
    placed = {r.name: (r.inst.params["wte"].devices(),
                       r.inst._cache["k"].devices()) for r in replicas}
    say("fleet", placement={n: [sorted(str(d) for d in p),
                                sorted(str(d) for d in c)]
                            for n, (p, c) in placed.items()})
    for params_on, pool_on in placed.values():
        assert params_on == pool_on and len(params_on) == 1
    assert len({next(iter(p)) for p, _ in placed.values()}) == 4

    async def main():
        try:
            # every replica answers (asked directly), then the router
            # spreads a burst over them
            direct = await asyncio.gather(*[
                fire(r.inst, requests[i:i + 1], 0.0)
                for i, r in enumerate(replicas)])
            routed = await fire(fleet, requests, 0.0)
            return [d[0] for d in direct], routed
        finally:
            fleet.shutdown()

    t0 = time.perf_counter()
    direct, routed = asyncio.run(main())
    assert_answered(requests[:4], direct, size.new_tokens)
    assert_answered(requests, routed, size.new_tokens)
    finished = {r.name: r.inst.engine_stats()["requests"]["finished"]
                for r in replicas}
    say("fleet", seconds=round(time.perf_counter() - t0, 2),
        finished_per_replica=finished)
    assert all(n >= 1 for n in finished.values()), finished
    return {"device": device}


# ---------------------------------------------------------------------------
# mla
# ---------------------------------------------------------------------------

def _check_mla_prefill(size: Size, interpret: bool) -> None:
    """ops/mla_flash_prefill.py against the jnp walk it replaces in a
    Kimi-K2 prefill on the chip (kimi_k2_decode.attend_blockwise), and
    what a layer's call of each takes."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.kimi_k2 import (expand_latents, kimi_k2_config,
                                        softmax_scale)
    from ray_tpu.models.kimi_k2_decode import attend_blockwise
    from ray_tpu.ops.mla_flash_prefill import mla_flash_prefill

    S = size.mla_max_seq
    cfg = kimi_k2_config(size.mla_preset, max_seq=S)
    H, c = cfg.n_head, cfg.kv_lora_rank
    ks = jax.random.split(jax.random.PRNGKey(size.seed + 3), 5)
    p = {"wk_b": jax.random.normal(ks[0], (c, H, cfg.qk_nope_dim),
                                   cfg.dtype) * c ** -0.5,
         "wv_b": jax.random.normal(ks[1], (c, H, cfg.v_head_dim),
                                   cfg.dtype) * c ** -0.5}
    ckv = jax.random.normal(ks[2], (S, c), cfg.dtype)
    kpe = jax.random.normal(ks[3], (S, cfg.qk_rope_dim), cfg.dtype)

    @jax.jit
    def kernel(q, prefix_len, pad):
        k_nope, v = expand_latents(ckv, p, cfg)
        return mla_flash_prefill(
            q, k_nope, kpe, v, prefix_len, pad, scale=softmax_scale(cfg),
            block_q=2 * size.mla_tile, block_k=size.mla_tile,
            strip=size.mla_tile // 2, interpret=interpret)

    @jax.jit
    def walk(q, prefix_len, pad):
        col = jnp.arange(q.shape[0])
        return attend_blockwise(q, ckv, kpe, p, prefix_len + col - pad,
                                col >= pad, cfg)

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))            # compiles
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        return out, round((time.perf_counter() - t0) * 1e3, 3)

    for T, prefix_len, n_tail in size.mla_prefills:
        q = jax.random.normal(ks[4], (T, H, cfg.qk_head_dim), cfg.dtype)
        args = (q, jnp.int32(prefix_len), jnp.int32(T - n_tail))
        got, kernel_ms = timed(kernel, *args)
        want, walk_ms = timed(walk, *args)
        took = {"kernel_ms_a_layer": kernel_ms,
                "jnp_walk_ms_a_layer": walk_ms}
        err = _rel_err(got, want)
        pads_zero = not bool(jnp.any(got[:T - n_tail]))
        say("mla", kernel="mla_flash_prefill",
            shape=[T, S, H, cfg.qk_head_dim, cfg.v_head_dim],
            prefix_len=prefix_len, real_columns=n_tail,
            err=round(err, 5), pads_zero=pads_zero, **took)
        assert err <= KERNEL_TOL and pads_zero, ("mla_flash_prefill", err)


def _check_mla_decode(size: Size, interpret: bool) -> None:
    """ops/mla_paged_decode.py: the rotary pool's re-lay against its
    transpose, to the bit, and one decode column of every row over the
    pool where it lies against the gathered views."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.kimi_k2 import kimi_k2_config, softmax_scale
    from ray_tpu.ops.mla_paged_decode import (
        mla_paged_decode, mla_paged_decode_reference, rotary_lanes,
        rotary_lanes_reference)

    B, blocks, L = size.mla_wave
    cfg = kimi_k2_config(size.mla_preset, max_seq=size.mla_max_seq)
    H, c, r, bs = (cfg.n_head, cfg.kv_lora_rank, cfg.qk_rope_dim,
                   size.kv_block)
    nb = cfg.max_seq // bs
    ks = jax.random.split(jax.random.PRNGKey(size.seed + 4), 6)
    rng = np.random.default_rng(size.seed)
    ckv = jax.random.normal(ks[0], (L, blocks, bs, c), cfg.dtype)
    kpe = jax.random.normal(ks[1], (L, blocks, bs, r), cfg.dtype)
    # rows of every length from idle to the whole table, over blocks
    # out of order (block 0 is the null block and is no row's)
    tables = jnp.asarray(rng.integers(1, blocks, (B, nb)), jnp.int32)
    pos = jnp.asarray(np.linspace(0, nb * bs, B).astype(np.int32))
    q_lat = jax.random.normal(ks[2], (B, H, c), cfg.dtype) * c ** -0.5
    q_rope = jax.random.normal(ks[3], (B, H, r), cfg.dtype)
    fresh = (jax.random.normal(ks[4], (B, c), cfg.dtype),
             jax.random.normal(ks[5], (B, r), cfg.dtype))

    t0 = time.perf_counter()
    lanes = rotary_lanes(kpe, interpret=interpret)
    same = bool(jnp.array_equal(lanes, rotary_lanes_reference(kpe)))
    say("mla", kernel="mla_rotary_lanes", shape=list(kpe.shape),
        identical=same, seconds=round(time.perf_counter() - t0, 2))
    assert same, "mla_rotary_lanes"
    for lidx in sorted({0, L - 1}):
        t0 = time.perf_counter()
        args = (q_lat, q_rope, ckv)
        rest = (tables, pos, jnp.int32(lidx), fresh)
        got = mla_paged_decode(*args, lanes, *rest,
                               scale=softmax_scale(cfg),
                               interpret=interpret)
        want = jax.jit(functools.partial(
            mla_paged_decode_reference, scale=softmax_scale(cfg)))(
                *args, kpe, *rest)
        err = _rel_err(got, want)
        say("mla", kernel="mla_paged_decode", layer=lidx,
            shape=[B, nb, H, c, r], err=round(err, 5),
            seconds=round(time.perf_counter() - t0, 2))
        assert err <= KERNEL_TOL, ("mla_paged_decode", lidx, err)


def _check_moe_rows(size: Size, interpret: bool) -> None:
    """ops/moe_dispatch.py: the local assignments' rows into grouped
    order, to the bit, and the grouped results back onto their tokens,
    against the sort / gather / scatter-add they replace; a first pass
    and, with half the rows a pass, the second."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.kimi_k2 import kimi_k2_config
    from ray_tpu.ops import moe_dispatch as md

    cfg = kimi_k2_config(size.mla_preset,
                         held=range(size.moe_held)).experts
    K, g, d = cfg.top_k, cfg.n_held, cfg.d_model
    for n, rows in size.moe_rows:
        ks = jax.random.split(jax.random.PRNGKey(size.seed + 5 + n), 5)
        _, chosen = jax.lax.top_k(
            jax.random.uniform(ks[0], (n, cfg.n_routed)), K)
        place = np.full((cfg.n_routed,), g, np.int32)
        place[list(cfg.held_ids)] = np.arange(g)
        loc = jnp.where((jnp.arange(n) >= n // 50)[:, None],
                        jnp.asarray(place)[chosen], g)  # a few pad rows
        counts = jnp.sum(loc[..., None] == jnp.arange(g), axis=(0, 1))
        starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
        n_local = int(counts.sum())
        x = jax.random.normal(ks[1], (n, d), jnp.float32)
        base = jax.random.normal(ks[2], (n, d), jnp.float32)
        w = jax.random.uniform(ks[3], (n, K), jnp.float32)
        for lo, take in ((0, rows), (rows // 2, rows // 2)):
            ys = md.slabs(jax.random.normal(ks[4], (take, d), jnp.float32))
            t0 = time.perf_counter()
            xs = md.moe_dispatch(x, loc, starts, lo, rows=take,
                                 interpret=interpret)
            held = min(max(n_local - lo, 0), take)
            same = bool(jnp.array_equal(xs[:held], md.dispatch_reference(
                x, loc, starts, lo, rows=take)[:held]))
            got = md.moe_combine(base, ys, loc, w, starts, lo,
                                 interpret=interpret)
            err = float(jnp.max(jnp.abs(got - md.combine_reference(
                base, ys, loc, w, starts, lo))))
            say("mla", kernel="moe_dispatch+moe_combine",
                shape=[n, take, d, K, g], first_row=lo, local_rows=n_local,
                dispatch_identical=same, combine_err=round(err, 7),
                seconds=round(time.perf_counter() - t0, 2))
            assert same and err <= 1e-4, ("moe_dispatch", n, lo, same, err)


def _check_grouped_swiglu(size: Size, interpret: bool) -> None:
    """ops/grouped_swiglu.py over a stack of two layers' held experts,
    against the three grouped matmuls it replaces: a decode wave's
    rows, few an expert and some experts none, every group begun on a
    row tile of 8 (`experts.fused_reference`); and a prefill pass's,
    groups of uneven heights end to end under 256-row tiles, one group
    empty (`experts._grouped` on the sizes as they are)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import experts as ex
    from ray_tpu.models.kimi_k2 import kimi_k2_config
    from ray_tpu.ops.grouped_swiglu import ROW_TILE, TALL, grouped_swiglu
    from ray_tpu.ops.moe_dispatch import rows_of, slabs

    cfg = kimi_k2_config(size.mla_preset,
                         held=range(size.moe_held)).experts
    g, d, f = cfg.n_held, cfg.d_model, cfg.d_expert
    ks = jax.random.split(jax.random.PRNGKey(size.seed + 11), 4)
    p = {"w_gate": jax.random.normal(ks[0], (2, g, d, f), cfg.dtype) * .02,
         "w_up": jax.random.normal(ks[1], (2, g, d, f), cfg.dtype) * .02,
         "w_down": jax.random.normal(ks[2], (2, g, f, d), cfg.dtype) * .02}
    rng = np.random.default_rng(size.seed)
    wave = rng.integers(0, 4, g)
    wave[0], wave[-1] = 0, 2 * ROW_TILE + 1           # one empty, one tall
    tall = rng.integers(40, 300, g)
    tall[1] = 0
    layer = jnp.int32(1)
    for counts, tm, aligned in ((wave, ROW_TILE, True),
                                (tall, 2 * TALL, False)):
        room = -(-counts // tm) * tm if aligned else counts
        first = np.cumsum(room) - room
        owned = np.zeros((-(-int(room.sum()) // tm) + 2) * tm, bool)
        for at, n in zip(first, counts):
            owned[at:at + n] = True
        xs = slabs(jnp.where(owned[:, None], jax.random.normal(
            ks[3], (owned.size, d), jnp.float32), 0.0))
        sizes = jnp.asarray(counts, jnp.int32)
        t0 = time.perf_counter()
        got = grouped_swiglu(xs, p["w_gate"], p["w_up"], p["w_down"], sizes,
                             layer, dtype=cfg.dtype, tm=tm, aligned=aligned,
                             interpret=interpret)
        want = jax.jit(ex.fused_reference, static_argnums=(3, 5, 6))(
            xs, p, sizes, cfg.dtype, layer, tm, aligned)
        err = _rel_err(rows_of(got)[owned], rows_of(want)[owned])
        say("mla", kernel="grouped_swiglu", aligned=aligned,
            shape=[owned.size, g, d, f], row_tile=tm,
            touched=int((counts > 0).sum()), err=round(err, 5),
            seconds=round(time.perf_counter() - t0, 2))
        assert err <= KERNEL_TOL, ("grouped_swiglu", aligned, err)


def check_mla_kernels(size: Size, *, interpret: bool = False) -> None:
    _check_mla_prefill(size, interpret)
    _check_mla_decode(size, interpret)
    _check_moe_rows(size, interpret)
    _check_grouped_swiglu(size, interpret)


def phase_mla(size: Size, platform: str = "tpu") -> Dict[str, Any]:
    device = device_block(platform)
    check_mla_kernels(size)
    return {"device": device}


def check_gqa_kernels(size: Size, *, interpret: bool = False) -> None:
    """ops/gqa_paged_decode.py: one decode column of every row over the
    K/V pools where they lie against the gathered views, in each full
    layer of the pools."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.laguna import FULL, laguna_config
    from ray_tpu.ops.gqa_paged_decode import (gqa_paged_decode,
                                              gqa_paged_decode_reference)

    B, blocks = size.gqa_wave
    cfg = laguna_config(size.gqa_preset, max_seq=size.gqa_max_seq)
    n_full, bs = len(cfg.layers_of(FULL)), size.kv_block
    nb = cfg.max_seq // bs
    ks = jax.random.split(jax.random.PRNGKey(size.seed + 5), 5)
    rng = np.random.default_rng(size.seed)
    pools = [jax.random.normal(k, (n_full, blocks, bs, cfg.kv_width),
                               cfg.dtype) for k in ks[:2]]
    # rows of every length from idle to the whole table, over blocks
    # out of order (block 0 is the null block and is no row's)
    tables = jnp.asarray(rng.integers(1, blocks, (B, nb)), jnp.int32)
    pos = jnp.asarray(np.linspace(0, nb * bs, B).astype(np.int32))
    q = jax.random.normal(ks[2], (B, cfg.n_head, cfg.head_dim), cfg.dtype)
    fresh = tuple(jax.random.normal(k, (B, cfg.kv_width), cfg.dtype)
                  for k in ks[3:])
    kw = dict(n_kv_head=cfg.n_kv_head, scale=cfg.head_dim ** -0.5)
    for f in range(n_full):
        t0 = time.perf_counter()
        args = (q, *pools, tables, pos, jnp.int32(f), fresh)
        got = gqa_paged_decode(*args, interpret=interpret, **kw)
        want = jax.jit(functools.partial(gqa_paged_decode_reference,
                                         **kw))(*args)
        err = _rel_err(got, want)
        say("gqa", kernel="gqa_paged_decode", layer=f,
            shape=[B, nb, cfg.n_head, cfg.n_kv_head, cfg.head_dim],
            err=round(err, 5), seconds=round(time.perf_counter() - t0, 2))
        assert err <= KERNEL_TOL, ("gqa_paged_decode", f, err)


def phase_gqa(size: Size, platform: str = "tpu") -> Dict[str, Any]:
    device = device_block(platform)
    check_gqa_kernels(size)
    return {"device": device}


def check_kda_kernels(size: Size, *, interpret: bool = False) -> None:
    """ops/kda.py: a prefill's delta rule as one kernel (`kda_chunk`:
    the call ``kda_chunk`` with a decay a channel at Solar-Open2's
    heads, the call ``delta_chunk`` with ONE decay a head at
    Olmo-Hybrid's) against the `jnp` chunk form it replaces on the
    chip, both with the serving dtype's operands and compiled as one
    program each, whose temporaries are printed, and at the smallest
    bucket against the recurrence over time in float32: a row with pads
    at its left (beta = 0, g = 0), a state handed in, beta up to 2, a
    captured column.
    Then a decode wave's (`kda_decode`: the call ``kda_decode`` at
    Solar-Open2's heads, the call ``delta_decode`` with ONE decay a head
    at Olmo-Hybrid's) on the middle layer of the stacked state, donated,
    against its `jnp` form (`kda_step` on that layer indexed out and set
    back): every fifth row idle, the other layers and the idle rows
    back to the bit, the largest error printed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.kda import (_step_on_layer, kda_chunk, kda_chunked,
                                 kda_decode, kda_recurrent)

    jnp_form = functools.partial(kda_chunked, dtype=jnp.bfloat16)
    kernel = functools.partial(kda_chunk, dtype=jnp.bfloat16,
                               interpret=interpret)

    def timed(f, *args, runs=3):
        jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(runs):
            out = f(*args)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / runs * 1e3

    def compiled(form, *args):
        """`form` with a captured column as one program, and the
        temporaries it was compiled with, MB."""
        program = jax.jit(lambda capture, *a: form(
            *a, capture=capture)).lower(*args).compile()
        return program, round(
            program.memory_analysis().temp_size_in_bytes / 1e6, 1)

    H, hd = size.kda_heads
    # (heads, keys, values, a decay's trailing size, the call's name,
    # columns, pads): KDA's a decay a channel, then ONE decay a head
    cases = [(H, hd, hd, hd, "kda_chunk", T, pad)
             for T, pad in size.kda_prefills] \
        + [(*size.delta_heads, 1, "delta_chunk", T, pad)
           for T, pad in size.delta_prefills]
    for n, (heads, dk, dv, gate, name, T, pad) in enumerate(cases):
        ks = jax.random.split(jax.random.PRNGKey(size.seed + 7 + n), 6)

        def unit(key):
            x = jax.random.normal(key, (1, T, heads, dk))
            return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

        real = (jnp.arange(T) >= pad)[None, :, None]
        # a token's decay log-uniform in (0.5, 0.999)
        g = jnp.where(real[..., None], np.log(0.5) + (
            np.log(0.999) - np.log(0.5)) * jax.random.uniform(
                ks[3], (1, T, heads, gate)), 0.0)
        beta = jnp.where(real, 2.0 * jax.random.uniform(
            ks[4], (1, T, heads)), 0.0)
        args = (jnp.int32(pad + (T - pad) // 2),
                unit(ks[0]) * dk ** -0.5, unit(ks[1]),
                jax.random.normal(ks[2], (1, T, heads, dv)), g, beta,
                jax.random.normal(ks[5], (1, heads, dk, dv)))
        kernel_program, temp = compiled(kernel, *args)
        jnp_program, temp_jnp = compiled(jnp_form, *args)
        got, ms = timed(kernel_program, *args)
        want, ms_jnp = timed(jnp_program, *args)
        errs = {"o": _rel_err(got[0][:, pad:], want[0][:, pad:]),
                "state": _rel_err(got[1], want[1]),
                "snapshot": _rel_err(got[2], want[2])}
        if T <= 1024:       # the smallest bucket, held to the recurrence
            o, state = jax.jit(kda_recurrent)(*args[1:])
            errs["o_recurrence"] = _rel_err(got[0][:, pad:], o[:, pad:])
            errs["state_recurrence"] = _rel_err(got[1], state)
        say("kda", kernel=name, shape=[T, pad, heads, dk, dv],
            ms=round(ms, 3), ms_jnp=round(ms_jnp, 3), temp_mb=temp,
            temp_mb_jnp=temp_jnp,
            **{k: round(v, 6) for k, v in errs.items()})
        assert max(errs.values()) <= KERNEL_TOL, (name, T, errs)

    # (rows, layers, heads, keys, values, a decay's trailing size, the
    # call's name): KDA's a decay a channel, then ONE decay a head
    waves = [(*size.kda_wave, H, hd, hd, hd, "kda_decode"),
             (*size.delta_wave, *size.delta_heads, 1, "delta_decode")]
    for n, (B, layers, heads, dk, dv, gate, name) in enumerate(waves):
        j = layers // 2
        ks = jax.random.split(jax.random.PRNGKey(size.seed + 11 + n), 6)

        def unit(key):
            x = jax.random.normal(key, (B, heads, dk))
            return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

        idle = jnp.arange(B) % 5 == 1
        g = jnp.where(idle[:, None, None], 0.0, np.log(0.5) + (
            np.log(0.999) - np.log(0.5)) * jax.random.uniform(
                ks[3], (B, heads, gate)))
        beta = jnp.where(idle[:, None], 0.0,
                         2.0 * jax.random.uniform(ks[4], (B, heads)))
        wave = (unit(ks[0]) * dk ** -0.5, unit(ks[1]),
                jax.random.normal(ks[2], (B, heads, dv)), g, beta)
        stacked = jax.jit(lambda: jax.random.normal(
            ks[5], (layers, B, heads, dk, dv)))
        before = stacked()
        # all that may move: layer j's matrices of the rows that decode
        moves = ((jnp.arange(layers) == j)[:, None]
                 & ~idle)[:, :, None, None, None]

        def timed_in_place(form, runs=10):
            f = jax.jit(lambda *a: form(*a, j), donate_argnums=(5,))
            o, stack = f(*wave, stacked())
            first = jax.device_get(
                (o, stack[j], jnp.all(moves | (stack == before))))
            t0 = time.perf_counter()
            for _ in range(runs):
                o, stack = f(*wave, stack)
            jax.block_until_ready(stack)
            return first, (time.perf_counter() - t0) / runs * 1e3

        (o, after, kept), ms = timed_in_place(functools.partial(
            kda_decode, interpret=interpret))
        (want_o, want, _), ms_jnp = timed_in_place(_step_on_layer)
        errs = {"o": _rel_err(o, want_o), "state": _rel_err(after, want)}
        kept = bool(kept)
        moved = 2 * B * heads * dk * dv * 4
        say("kda", kernel=name, shape=[layers, B, heads, dk, dv],
            ms=round(ms, 4), ms_jnp=round(ms_jnp, 4),
            gb_per_s=round(moved / ms / 1e6, 1), kept_to_the_bit=kept,
            **{k: round(v, 8) for k, v in errs.items()})
        assert kept and max(errs.values()) <= KERNEL_TOL, (name, errs)


def phase_kda(size: Size, platform: str = "tpu") -> Dict[str, Any]:
    device = device_block(platform)
    check_kda_kernels(size)
    return {"device": device}


def check_ring_kernels(size: Size, *, interpret: bool = False) -> None:
    """ops/ring_decode.py: one decode column of every row over its ring
    where it lies in the stacked rings, against `attend_rows` over the
    ring sliced out, in the first and the last window layer; then a
    whole wave as the decode steps run it (a scan over the layers: the
    new rows scattered into the donated stacks, the kernel behind
    them), whose compiled program must alias both stacks to its results
    and hold no temporary of a ring's size."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from ray_tpu.models.banded_attention import _ring_mask, attend_rows
    from ray_tpu.ops.ring_decode import ring_decode

    for n, B, W, H, n_kv, hd, scale in size.ring_waves:
        width, dt = n_kv * hd, jnp.bfloat16
        cfg = types.SimpleNamespace(n_kv_head=n_kv, head_dim=hd, dtype=dt)
        ks = jax.random.split(jax.random.PRNGKey(size.seed + 13), 5)
        stacked = jax.jit(lambda k: jax.random.normal(k, (n, B, W, width),
                                                      dt))
        wk, wv = stacked(ks[0]), stacked(ks[1])
        q = jax.random.normal(ks[2], (B, H, hd), dt)
        # rows from idle through a ring partly filled to several laps
        pos = jnp.asarray(np.linspace(0, 5 * W, B).astype(np.int32))
        start = jnp.zeros((B,), jnp.int32)
        kernel = jax.jit(functools.partial(
            ring_decode, n_kv_head=n_kv, scale=scale, interpret=interpret))
        oracle = jax.jit(lambda q, wk, wv, j, pos, start: attend_rows(
            q, wk[j], wv[j], _ring_mask(pos, start, W), cfg, scale))
        moved = 2 * B * W * width * dt.dtype.itemsize
        for j in (0, n - 1):
            args = (q, wk, wv, jnp.int32(j), pos, start)
            got = jax.block_until_ready(kernel(*args))
            runs = 1 if interpret else 20
            t0 = time.perf_counter()
            for _ in range(runs):
                got = kernel(*args)
            jax.block_until_ready(got)
            ms = (time.perf_counter() - t0) / runs * 1e3
            err = _rel_err(got, oracle(*args))
            say("ring", kernel="ring_decode", layer=j,
                shape=[n, B, W, H, n_kv, hd], err=round(err, 5),
                ms=round(ms, 4), gb_per_s=round(moved / ms / 1e6, 1))
            assert err <= KERNEL_TOL, ("ring_decode", j, err)

        def wave(q, wk, wv, new_k, new_v):
            at = jnp.where(pos > 0, pos % W, W)   # an idle row: dropped

            def layer(carry, j):
                wk, wv = (r.at[j, jnp.arange(B), at].set(x, mode="drop")
                          for r, x in zip(carry, (new_k, new_v)))
                return (wk, wv), ring_decode(
                    q, wk, wv, j, pos, start, n_kv_head=n_kv, scale=scale,
                    interpret=interpret)

            return lax.scan(layer, (wk, wv), jnp.arange(n, dtype=jnp.int32))

        new = tuple(jax.random.normal(k, (B, width), dt) for k in ks[3:])
        compiled = jax.jit(wave, donate_argnums=(1, 2)).lower(
            q, wk, wv, *new).compile()
        memory = compiled.memory_analysis()
        in_place = (memory.alias_size_in_bytes >= 2 * wk.nbytes
                    and memory.temp_size_in_bytes < wk.nbytes // (n * 2))
        (wk, wv), outs = compiled(q, wk, wv, *new)
        runs = 1 if interpret else 10
        jax.block_until_ready(outs)
        t0 = time.perf_counter()
        for _ in range(runs):
            (wk, wv), outs = compiled(q, wk, wv, *new)
        jax.block_until_ready(outs)
        ms = (time.perf_counter() - t0) / runs / n * 1e3
        err = _rel_err(outs[n - 1], oracle(q, wk, wv, n - 1, pos, start))
        say("ring", kernel="ring_decode", wave=[n, B, W, width],
            ms_a_layer=round(ms, 4), gb_per_s=round(moved / ms / 1e6, 1),
            alias_bytes=memory.alias_size_in_bytes,
            temp_bytes=memory.temp_size_in_bytes, in_place=in_place,
            err=round(err, 5))
        assert err <= KERNEL_TOL, ("ring_decode in a scan", err)
        # (the interpreter's program is no Mosaic call: nothing to alias)
        assert in_place or interpret, memory


def phase_ring(size: Size, platform: str = "tpu") -> Dict[str, Any]:
    device = device_block(platform)
    check_ring_kernels(size)
    return {"device": device}


def check_banded_kernels(size: Size, *, interpret: bool = False) -> None:
    """ops/banded_flash.py against `banded_attention.banded_walk`, the
    `jnp` walk it replaces on the chip: each layer geometry of
    `size.banded_layers` at each of its buckets, a 27th of the columns
    pads at the left, bf16 operands as the cells run them."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.banded_attention import banded_walk, prefill_reach
    from ray_tpu.ops.banded_flash import banded_flash

    hd, dt = 128, jnp.bfloat16
    tiles = {} if size.banded_tiles is None else dict(zip(
        ("block_q", "block_k"), size.banded_tiles))
    peak = 197e12                       # v5e, bf16 (benchmark/peaks.json)
    for H, n_kv, scale, window, rows, block, buckets in size.banded_layers:
        cfg = types.SimpleNamespace(dtype=dt, attn_block=block,
                                    n_kv_head=n_kv, head_dim=hd)
        kernel = jax.jit(functools.partial(
            banded_flash, n_kv_head=n_kv, head_dim=hd, scale=scale,
            interpret=interpret, **tiles))
        oracle = jax.jit(functools.partial(
            banded_walk, cfg=cfg, scope="attn_full", scale=scale))
        for T in buckets:
            S = rows if window is None else window + T
            pads = T // 27
            ks = jax.random.split(jax.random.PRNGKey(size.seed + 17), 3)
            q = jax.random.normal(ks[0], (T, H, hd), dt)
            k, v = (jax.random.normal(key, (S, n_kv * hd), dt)
                    for key in ks[1:])
            reach = prefill_reach(T, 0, T - pads, window, xp=np)
            args = (q, k, v, *(jnp.asarray(a) for a in reach))
            ms, outs = {}, {}
            for name, fn in (("kernel", kernel), ("jnp", oracle)):
                outs[name] = jax.block_until_ready(fn(*args))
                runs = 1 if interpret else 5
                t0 = time.perf_counter()
                for _ in range(runs):
                    out = fn(*args)
                jax.block_until_ready(out)
                ms[name] = (time.perf_counter() - t0) / runs * 1e3
            err = _rel_err(outs["kernel"], outs["jnp"])
            attended = int(np.maximum(reach[1] - reach[0] + 1, 0).sum())
            say("banded", kernel="banded_flash",
                shape=[T, S, H, n_kv, hd], window=window,
                ms=round(ms["kernel"], 4), ms_jnp=round(ms["jnp"], 4),
                ms_at_peak=round(4 * hd * H * attended / peak * 1e3, 4),
                err=round(err, 5))
            assert err <= KERNEL_TOL, ("banded_flash", H, window, T, err)
            assert not np.asarray(outs["kernel"][:pads], np.float32).any()


def phase_banded(size: Size, platform: str = "tpu") -> Dict[str, Any]:
    device = device_block(platform)
    check_banded_kernels(size)
    return {"device": device}


PHASES = {"train": phase_train, "serve": phase_serve,
          "runtime": phase_runtime, "mla": phase_mla, "gqa": phase_gqa,
          "kda": phase_kda, "ring": phase_ring, "banded": phase_banded,
          "mesh_train": phase_mesh_train,
          "tensor_serve": phase_tensor_serve, "fleet": phase_fleet}


# ---------------------------------------------------------------------------
# processes
# ---------------------------------------------------------------------------

def run_phase(name: str) -> int:
    """Child process: one phase at full size, then its result line."""
    t0 = time.perf_counter()
    if name != "runtime":       # the runtime driver stays off JAX
        from ray_tpu._private.compile_cache import enable_compile_cache

        say(name, compile_cache_dir=enable_compile_cache())
    result = PHASES[name](Size())
    if name == "runtime":
        assert "jax" not in sys.modules, "the driver imported JAX"
    say(name, phase_seconds=round(time.perf_counter() - t0, 1))
    print(_RESULT_TAG + json.dumps({"phase": name, **result}),
          flush=True)
    return 0


def run_child(name: str, deadline: float) -> Dict[str, Any]:
    """Run one phase in its own process group, echo what it prints, and
    leave nothing of it running.  Raises SystemExit unless it passed."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name],
        stdout=subprocess.PIPE, text=True, start_new_session=True)
    result = None
    try:
        timer = _alarm(deadline - time.monotonic(), proc)
        for line in proc.stdout:
            if line.startswith(_RESULT_TAG):
                result = json.loads(line[len(_RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        code = proc.wait()
        timer.cancel()
    finally:
        _kill_group(proc)
    if code != 0 or result is None:
        raise SystemExit(f"chip_smoke: phase {name!r} failed "
                         f"(exit {code})")
    return result


def _kill_group(proc) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def _alarm(seconds: float, proc):
    import threading

    timer = threading.Timer(max(seconds, 1.0), _kill_group, (proc,))
    timer.daemon = True
    timer.start()
    return timer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs the across-chip phases and no other")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help="run one phase in this process (what the "
                         "parent starts; also handy for debugging)")
    args = ap.parse_args(argv)
    if args.phase:
        return run_phase(args.phase)
    t0 = time.monotonic()
    devices = []
    for name in (FOUR_CHIPS if args.chips == 4 else ONE_CHIP):
        t_phase = time.monotonic()
        devices.append(run_child(name, t0 + DEADLINE_S)["device"])
        print(f"[{name}] passed in {time.monotonic() - t_phase:.1f}s",
              flush=True)
    device = devices[0]
    if any(d != device for d in devices) or device["platform"] != "tpu" \
            or device["count"] != args.chips:
        raise SystemExit(f"chip_smoke: phases disagree on the device or "
                         f"it is not {args.chips} TPU chip(s): {devices}")
    print(f"[all] passed in {time.monotonic() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
