"""The canonical hot-path programs graftcheck audits.

These are the jitted programs the paper's perf story rides on: the
train step for both model families, batched (ragged) prefill, the
pooled ragged decode step, and the fused-CE kernel's forward and
backward.  Every spec uses the CPU-traceable nano presets — jaxpr
structure (primitives, scans, buffer shapes, donation) is preset- and
backend-independent, so invariants proven on nano hold for the real
configs.

Conventions:

* ``n_tokens`` for the logits-buffer rule is the full token count of
  the traced batch; it must exceed ``d_model`` so a transposed
  ``(d_model, padded_vocab)`` weight view can never alias the
  forbidden shape class.
* HBM budgets are the measured peak estimate of the healthy program
  rounded up ~2-3x — generous enough to survive jax-version jitter in
  the trace, tight enough that an order-of-magnitude blowup (remat
  accidentally storing every layer's activations, a full-cache copy
  per decode step) trips the rule.  To declare a budget for a new
  program: run ``python -m ray_tpu.tools.graftcheck --format json``,
  read ``programs.<name>.peak_hbm_bytes``, round up 2-3x.  Measured
  2026-08 (jax 0.4.37, CPU trace): train 2.2-3.0 MiB, prefill/decode
  1.3-2.1 MiB, fused-CE 0.2-0.3 MiB.
"""

from __future__ import annotations

from typing import List

from ray_tpu.tools.graftcheck.jaxpr_audit import ProgramSpec

#: nano-family shape constants shared by the builders below
_B, _T = 2, 64           # train batch: 128 tokens (> d_model=64)
_PB, _PT0 = 4, 64        # prefill batch: T0 != n_layer so no aliasing
_CE_N, _CE_D, _CE_V, _CE_VALID = 128, 64, 512, 500
_NANO_VOCAB = 512        # padded_vocab of the nano presets
_CHUNK_T = 32            # chunked-prefill tail bucket (< max_seq=128)
_BUCKET_T = 64           # a one-shot paged prefill's bucket

_MiB = 2 ** 20


def _nano_gpt2_cfg():
    from ray_tpu.models import gpt2_config

    return gpt2_config("nano", ce_impl="pallas", ce_block_n=16,
                       ce_block_v=128, remat=False)


def _nano_llama_cfg():
    from ray_tpu.models import llama_config

    return llama_config("nano", ce_impl="pallas", ce_block_n=16,
                        ce_block_v=128)


def _sgd_step(loss_fn):
    """The minimal donated train step shape (value_and_grad + in-place
    update) — optimizer choice doesn't change the audited invariants."""
    import jax

    def step(params, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch))(params)
        new = jax.tree.map(lambda p, g: p - 0.01 * g.astype(p.dtype),
                           params, grads)
        return new, loss

    return step


def _build_gpt2_train_step():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_init, gpt2_loss

    cfg = _nano_gpt2_cfg()
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((_B, _T + 1), jnp.int32)}
    return _sgd_step(lambda p, b: gpt2_loss(p, b, cfg)), (params, batch)


def _build_llama_train_step():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama_init, llama_loss

    cfg = _nano_llama_cfg()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((_B, _T + 1), jnp.int32)}
    return _sgd_step(lambda p, b: llama_loss(p, b, cfg)), (params, batch)


def _build_gpt2_prefill():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_config, gpt2_init
    from ray_tpu.models.gpt2_decode import prefill

    cfg = gpt2_config("nano", dtype=jnp.float32, use_flash=False,
                      remat=False)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((_PB, _PT0), jnp.int32)
    lens = jnp.full((_PB,), _PT0 // 2, jnp.int32)
    return (lambda p, t, n: prefill(p, t, cfg, lengths=n),
            (params, toks, lens))


def _build_llama_prefill():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama_config, llama_init
    from ray_tpu.models.llama_decode import llama_prefill

    cfg = llama_config("nano")
    params = llama_init(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((_PB, _PT0), jnp.int32)
    lens = jnp.full((_PB,), _PT0 // 2, jnp.int32)
    return (lambda p, t, n: llama_prefill(p, t, cfg, lengths=n),
            (params, toks, lens))


def _build_gpt2_decode_step():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_config, gpt2_init
    from ray_tpu.models.gpt2_decode import decode_step, init_cache

    cfg = gpt2_config("nano", dtype=jnp.float32, use_flash=False,
                      remat=False)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, _PB)
    toks = jnp.zeros((_PB,), jnp.int32)
    return (lambda p, c, t: decode_step(p, c, t, cfg),
            (params, cache, toks))


def _build_gpt2_paged_decode_step():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_config, gpt2_init
    from ray_tpu.models.gpt2_decode import decode_step, init_paged_cache

    cfg = gpt2_config("nano", dtype=jnp.float32, use_flash=False,
                      remat=False)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    # null block + one full sequence of blocks per pooled row — the
    # serve engine's default sizing (LLMEngine._init_scheduler)
    bs = 16
    per_row = cfg.max_seq // bs
    cache = init_paged_cache(cfg, _PB, num_blocks=1 + _PB * per_row,
                             block_size=bs)
    # identity tables so the traced program exercises the real
    # gather/scatter indirection (all-zero tables would too, but this
    # mirrors a live engine's layout)
    cache["block_tables"] = 1 + jnp.arange(
        _PB * per_row, dtype=jnp.int32).reshape(_PB, per_row)
    toks = jnp.zeros((_PB,), jnp.int32)
    return (lambda p, c, t: decode_step(p, c, t, cfg),
            (params, cache, toks))


def _build_gpt2_sharded_decode_step():
    """The paged decode step with params + pool committed to an
    8-device (data=4, tensor=2) mesh under DECODE_RULES — the serve
    engine's tensor-parallel configuration.  Compiled-HLO rules assert
    the TP collectives exist and the full (unsharded) pool shape does
    NOT: GSPMD silently replicating an input it can no longer shard is
    exactly the regression class this spec exists to catch."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_config, gpt2_init, gpt2_logical_axes
    from ray_tpu.models.decode_common import shard_cache
    from ray_tpu.models.gpt2_decode import decode_step, init_paged_cache
    from ray_tpu.parallel import MeshSpec, fake_mesh
    from ray_tpu.parallel.sharding import DECODE_RULES, shard_by_shape

    cfg = gpt2_config("nano", dtype=jnp.float32, use_flash=False,
                      remat=False)
    mesh = fake_mesh(8, MeshSpec(data=4, tensor=2))
    params = shard_by_shape(gpt2_init(jax.random.PRNGKey(0), cfg),
                            gpt2_logical_axes(cfg), mesh, DECODE_RULES)
    bs = 16
    per_row = cfg.max_seq // bs
    cache = init_paged_cache(cfg, _PB, num_blocks=1 + _PB * per_row,
                             block_size=bs, mesh=mesh)
    cache["block_tables"] = 1 + jnp.arange(
        _PB * per_row, dtype=jnp.int32).reshape(_PB, per_row)
    cache = shard_cache(cache, mesh)   # re-commit the edited tables
    toks = jnp.zeros((_PB,), jnp.int32)
    return (lambda p, c, t: decode_step(p, c, t, cfg),
            (params, cache, toks))


def _build_gpt2_spec_verify_step():
    """The spec-decode verify program (round 11): ONE dispatch ingests
    a (B, k+1) draft block, scores every position, runs the
    accept/reject fold, and advances the paged pool by the kept
    count.  The logits rule forbids a (B*max_seq, V) buffer — the
    whole point of the verify step is that its logits are (B, k+1, V),
    never the full-sequence shape; the KV pool (arg 1) is donated
    because the verify round is the engine's steady-state hot program
    and keeping two pools alive would double decode HBM."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_config, gpt2_init
    from ray_tpu.models.decode_common import make_spec_verify
    from ray_tpu.models.gpt2_decode import init_paged_cache, verify_step

    cfg = gpt2_config("nano", dtype=jnp.float32, use_flash=False,
                      remat=False)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    bs = 16
    per_row = cfg.max_seq // bs
    cache = init_paged_cache(cfg, _PB, num_blocks=1 + _PB * per_row,
                             block_size=bs)
    cache["block_tables"] = 1 + jnp.arange(
        _PB * per_row, dtype=jnp.int32).reshape(_PB, per_row)
    spec_verify = make_spec_verify(verify_step, cfg)
    block = jnp.zeros((_PB, 5), jnp.int32)      # [cur, d_1..d_4], k=4
    key = jax.random.PRNGKey(0)
    return (lambda p, c, b, k: spec_verify(p, c, b, k),
            (params, cache, block, key))


def _build_gpt2_chunked_prefill(t_pad=_CHUNK_T, n_tail=_CHUNK_T):
    """One chunk of streaming prefill (round 14): the serve engine's
    chunked admission runs the SAME ``paged_prefill`` program once per
    chunk with ``prefix_len`` = tokens already filled, so the audited
    shape is a chunk-sized tail bucket (Tt=32) against a warm pool
    with one resident prefix block.  The invariants that make
    chunking's TTFT story real: the forward must never scan over the
    FULL sequence length (the chunk's cost must be O(chunk), not
    O(max_seq) — that is the whole head-of-line-blocking fix), and
    peak HBM must stay at pool + chunk-sized temps (a dense
    re-materialization of the pool per chunk would multiply the
    engine's hottest loop)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_config, gpt2_init
    from ray_tpu.models.gpt2_decode import init_paged_cache, paged_prefill

    cfg = gpt2_config("nano", dtype=jnp.float32, use_flash=False,
                      remat=False)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    bs = 16
    per_row = cfg.max_seq // bs
    cache = init_paged_cache(cfg, _PB, num_blocks=1 + _PB * per_row,
                             block_size=bs)
    cache["block_tables"] = 1 + jnp.arange(
        _PB * per_row, dtype=jnp.int32).reshape(_PB, per_row)
    row_bt = 1 + jnp.arange(per_row, dtype=jnp.int32)
    toks = jnp.zeros((1, t_pad), jnp.int32)
    # prefix_len=16: one already-resident block (the previous chunk);
    # n_tail == bucket (full chunk); dynamic scalars as in the engine
    return (lambda p, c, t, bt, pl, nt, s: paged_prefill(
        p, c, t, cfg, row_bt=bt, prefix_len=pl, n_tail=nt, slot=s),
        (params, cache, toks, row_bt, jnp.int32(16),
         jnp.int32(n_tail), jnp.int32(0)))


def _build_gpt2_paged_prefill_bucket():
    """The engine's one-shot paged prefill at a bucket the serving
    cells run (Tt=64 of nano's 128), a prefix hit of one block and
    three pad columns: the same program as a chunk, at the size where
    the tail's K/V are most of what the pool receives."""
    return _build_gpt2_chunked_prefill(_BUCKET_T, _BUCKET_T - 3)


def _jamba_paged_nano():
    """A nano jamba of two periods (eight layers, the third of each four
    attention: the pool has two layers, so a layer of it is not the
    pool), its parameters and the serve engine's paged cache for it: the K/V
    pool of the attention layers, the rows' recurrent state and the
    snapshot pool (models/jamba_decode.py)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.jamba import jamba_config, jamba_init
    from ray_tpu.models.jamba_decode import jamba_init_paged_cache

    cfg = jamba_config("nano", n_layer=8, dtype=jnp.float32,
                       use_flash=False, remat=False)
    params = jamba_init(jax.random.PRNGKey(0), cfg)
    bs = 16
    per_row = cfg.max_seq // bs
    cache = jamba_init_paged_cache(cfg, _PB,
                                   num_blocks=1 + _PB * per_row,
                                   block_size=bs)
    cache["block_tables"] = 1 + jnp.arange(
        _PB * per_row, dtype=jnp.int32).reshape(_PB, per_row)
    # rows that hold a sequence: an empty row's state is not advanced
    cache["pos"] = jnp.full((_PB,), 20, jnp.int32)
    return cfg, params, cache, per_row


def _build_jamba_paged_decode_step():
    """The engine's decode step for the hybrid family: the K/V pool
    read-only in the walk over layers (PagedKV, as for GPT-2), every
    row's recurrent state read, advanced and written back one Mamba
    layer at a time into the carried state, never stacked."""
    import jax.numpy as jnp

    from ray_tpu.models.jamba_decode import jamba_decode_step

    cfg, params, cache, _ = _jamba_paged_nano()
    return (lambda p, c, t: jamba_decode_step(p, c, t, cfg),
            (params, cache, jnp.zeros((_PB,), jnp.int32)))


def _build_jamba_paged_prefill_bucket():
    """Its one-shot paged prefill at bucket 64 with three pad columns,
    one block resident, started from snapshot entry 0 and leaving the
    state after 48 tokens in entry 1: the slot's state rows and one
    snapshot entry are written where they lie."""
    import jax.numpy as jnp

    from ray_tpu.models.jamba_decode import jamba_paged_prefill

    cfg, params, cache, per_row = _jamba_paged_nano()
    row_bt = 1 + jnp.arange(per_row, dtype=jnp.int32)
    return (lambda p, c, t, bt, pl, nt, s, st: jamba_paged_prefill(
        p, c, t, cfg, row_bt=bt, prefix_len=pl, n_tail=nt, slot=s,
        state=st),
        (params, cache, jnp.zeros((1, _BUCKET_T), jnp.int32), row_bt,
         jnp.int32(16), jnp.int32(_BUCKET_T - 3), jnp.int32(0),
         jnp.asarray([0, 1, 48], jnp.int32)))


def _paged_nano_pool():
    """The serve engine's default nano paged pool (null block + one
    full chain per pooled row) with identity tables — shared by the
    handoff program builders below."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt2_config, gpt2_decode

    cfg = gpt2_config("nano", dtype=jnp.float32, use_flash=False,
                      remat=False)
    bs = 16
    per_row = cfg.max_seq // bs
    cache = gpt2_decode.init_paged_cache(
        cfg, _PB, num_blocks=1 + _PB * per_row, block_size=bs)
    cache["block_tables"] = 1 + jnp.arange(
        _PB * per_row, dtype=jnp.int32).reshape(_PB, per_row)
    return cache, per_row


def _build_gpt2_kv_handoff_export():
    """Disaggregated serving's prefill-side program (round 18): ONE
    dispatch gathers a finished prefill's filled block rows out of the
    pool — the read twin of the tier's install program, fixed-shape
    over a padded id vector.  The export must be a pure slice of the
    pool: no logits buffer may appear (the handoff moves K/V bytes,
    never recomputes), and peak HBM is pool + one stacked-row copy —
    a densified whole-pool intermediate would double the prefill
    replica's steady-state footprint on every handoff."""
    import jax.numpy as jnp

    from ray_tpu.models.decode_common import kv_handoff_export

    cache, per_row = _paged_nano_pool()
    ids = jnp.zeros((per_row,), jnp.int32)
    return kv_handoff_export, (cache, ids)


def _build_gpt2_kv_handoff_install():
    """The decode-side splice: exported rows + block table + pos +
    start land in ONE donated dispatch, so the receiving row is
    decode-ready when the program retires and the first decode step
    reads exactly the rows the prefill replica wrote.  The pool (arg
    0) must be donated — an undonated install would hold two pools
    live per handoff, exactly the HBM spike disaggregation cannot
    afford on the decode fleet."""
    import jax.numpy as jnp

    from ray_tpu.models.decode_common import (block_rows,
                                              kv_handoff_install)

    cache, per_row = _paged_nano_pool()
    ids = jnp.zeros((per_row,), jnp.int32)
    rows = block_rows(cache, per_row)
    stack = jnp.zeros(rows.shape, rows.dtype)
    row_bt = jnp.zeros((per_row,), jnp.int32)
    return kv_handoff_install, (cache, ids, stack, stack, jnp.int32(0),
                                row_bt, jnp.int32(48))


def _ce_inputs():
    import jax
    import jax.numpy as jnp

    k = jax.random.PRNGKey(0)
    h = jax.random.normal(k, (_CE_N, _CE_D), jnp.float32)
    w = jax.random.normal(k, (_CE_V, _CE_D), jnp.float32)
    t = jnp.zeros((_CE_N,), jnp.int32)
    return h, w, t


def _build_fused_ce_fwd():
    import jax.numpy as jnp

    from ray_tpu.ops.fused_ce import fused_lm_ce

    h, w, t = _ce_inputs()
    return (lambda a, b, c: fused_lm_ce(
        a, b, c, _CE_VALID, block_n=16, block_v=128,
        compute_dtype=jnp.bfloat16), (h, w, t))


def _build_fused_ce_bwd():
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.fused_ce import fused_lm_ce

    h, w, t = _ce_inputs()

    def loss(a, b):
        return jnp.sum(fused_lm_ce(a, b, t, _CE_VALID, block_n=16,
                                   block_v=128,
                                   compute_dtype=jnp.bfloat16))

    return jax.grad(loss, argnums=(0, 1)), (h, w)


def default_programs() -> List[ProgramSpec]:
    """The registry ``python -m ray_tpu.tools.graftcheck`` audits."""
    return [
        ProgramSpec(
            name="gpt2_train_step",
            build=_build_gpt2_train_step,
            forbid_logits=(_B * _T, _NANO_VOCAB),
            donate_argnums=(0,),
            hbm_budget_bytes=8 * _MiB),
        ProgramSpec(
            name="llama_train_step",
            build=_build_llama_train_step,
            forbid_logits=(_B * _T, _NANO_VOCAB),
            donate_argnums=(0,),
            hbm_budget_bytes=8 * _MiB),
        ProgramSpec(
            name="gpt2_prefill_ragged",
            build=_build_gpt2_prefill,
            forbid_logits=(_PB * _PT0, _NANO_VOCAB),
            forbid_scan_lengths=(_PT0,),
            # prefill runs the full-precision f32 nano config on CPU;
            # the dtype policy is audited on the train-step programs
            allow_f32_matmul=True,
            hbm_budget_bytes=6 * _MiB),
        ProgramSpec(
            name="llama_prefill_ragged",
            build=_build_llama_prefill,
            forbid_logits=(_PB * _PT0, _NANO_VOCAB),
            forbid_scan_lengths=(_PT0,),
            hbm_budget_bytes=6 * _MiB),
        ProgramSpec(
            name="gpt2_decode_step",
            build=_build_gpt2_decode_step,
            forbid_logits=(_PB * 128, _NANO_VOCAB),  # B * max_seq rows
            allow_f32_matmul=True,
            hbm_budget_bytes=6 * _MiB),
        ProgramSpec(
            name="gpt2_paged_decode_step",
            build=_build_gpt2_paged_decode_step,
            forbid_logits=(_PB * 128, _NANO_VOCAB),  # B * max_seq rows
            allow_f32_matmul=True,
            # budget covers the block pool (1 + B*max_seq/bs blocks,
            # == dense cache footprint + one null block) plus the
            # per-layer gathered (B, max_seq) views inside the scan; a
            # hidden dense re-materialization of the WHOLE pool per
            # layer would blow straight through it
            hbm_budget_bytes=6 * _MiB,
            # the engine donates the pool to its decode program and
            # the step writes 2 * L * B rows into it where it lies
            donate_argnums=(1,), inplace_pool=1),
        ProgramSpec(
            name="gpt2_sharded_decode_step",
            build=_build_gpt2_sharded_decode_step,
            forbid_logits=(_PB * 128, _NANO_VOCAB),  # B * max_seq rows
            allow_f32_matmul=True,
            min_devices=8,
            # TP attention/MLP insert a tensor-axis all-gather (per-chip
            # KV head shards -> the attention view) and all-reduce (the
            # row-parallel o/proj partial sums); the full (L, 1+B*8,
            # bs, H, hd) pool shape must never appear in the compiled
            # HLO — its presence means GSPMD replicated the pool
            require_collectives=("all-gather", "all-reduce"),
            forbid_hlo_shapes=("f32[2,33,16,2,32]",),
            hbm_budget_bytes=6 * _MiB,
            # measured compiled per-partition arg+temp ~0.74 MiB on 8
            # CPU devices (jax 0.4.37); ~2x headroom.  Pool-replication
            # regressions are caught by the forbidden-shape rule above;
            # this budget catches per-chip blowups from new temps (e.g.
            # a densified per-layer pool copy inside the scan)
            per_chip_hbm_budget_bytes=int(1.6 * _MiB)),
        ProgramSpec(
            name="gpt2_spec_verify_step",
            build=_build_gpt2_spec_verify_step,
            forbid_logits=(_PB * 128, _NANO_VOCAB),  # B * max_seq rows
            allow_f32_matmul=True,
            # a verify block writes each tensor's layer back
            donate_argnums=(1,), inplace_pool=1, pool_layer_writes=2,
            # same pool sizing as the paged decode step plus the tiny
            # (B, k+1, V) verify logits and accept-fold temps
            hbm_budget_bytes=6 * _MiB),
        ProgramSpec(
            name="gpt2_chunked_prefill",
            build=_build_gpt2_chunked_prefill,
            # full-sequence logits must never appear: the chunk emits
            # one row of logits (and intermediate chunks discard it)
            forbid_logits=(128, _NANO_VOCAB),        # max_seq rows
            # the chunk forward must be O(chunk): no scan of length
            # max_seq (a per-position pool walk would re-introduce the
            # head-of-line stall chunking exists to remove)
            forbid_scan_lengths=(128,),
            allow_f32_matmul=True,
            # pool (same sizing as the paged decode step) + (Tt, ...)
            # chunk temps; a dense pool re-materialization per chunk
            # blows through this
            hbm_budget_bytes=6 * _MiB,
            # a chunk writes each tensor's layer back, like a prefill
            donate_argnums=(1,), inplace_pool=1, pool_layer_writes=2),
        ProgramSpec(
            name="gpt2_paged_prefill_bucket",
            build=_build_gpt2_paged_prefill_bucket,
            forbid_logits=(128, _NANO_VOCAB),        # max_seq rows
            forbid_scan_lengths=(128,),
            allow_f32_matmul=True,
            hbm_budget_bytes=6 * _MiB,
            # K's and V's layer written back into the carried pool,
            # and no third: the pool is never the scan's stacked ys
            donate_argnums=(1,), inplace_pool=1, pool_layer_writes=2),
        ProgramSpec(
            name="jamba_paged_decode_step",
            build=_build_jamba_paged_decode_step,
            forbid_logits=(_PB * 128, _NANO_VOCAB),  # B * max_seq rows
            allow_f32_matmul=True,
            # pool (2 attention layers' worth: a quarter of GPT-2
            # nano's) + state and snapshots + per-layer views
            hbm_budget_bytes=6 * _MiB,
            # pool AND recurrent state donated and updated in place:
            # the rule holds the state to it too (jaxpr_audit)
            donate_argnums=(1,), inplace_pool=1),
        ProgramSpec(
            name="jamba_paged_prefill_bucket",
            build=_build_jamba_paged_prefill_bucket,
            forbid_logits=(128, _NANO_VOCAB),        # max_seq rows
            # the scan over time is chunked: never a step a column
            forbid_scan_lengths=(128, _BUCKET_T),
            allow_f32_matmul=True,
            hbm_budget_bytes=6 * _MiB,
            donate_argnums=(1,), inplace_pool=1, pool_layer_writes=2),
        ProgramSpec(
            name="gpt2_kv_handoff_export",
            build=_build_gpt2_kv_handoff_export,
            # a handoff never computes: full-sequence logits in the
            # export program mean someone routed a forward through it
            forbid_logits=(_PB * 128, _NANO_VOCAB),  # B * max_seq rows
            allow_f32_matmul=True,
            # pool + one (maxn, L, bs, H, hd) stacked-row pair; a
            # densified whole-pool gather would blow through this
            hbm_budget_bytes=6 * _MiB),
        ProgramSpec(
            name="gpt2_kv_handoff_install",
            build=_build_gpt2_kv_handoff_install,
            forbid_logits=(_PB * 128, _NANO_VOCAB),  # B * max_seq rows
            allow_f32_matmul=True,
            # the donated pool is the whole point: two live pools per
            # install is the regression this spec exists to catch
            donate_argnums=(0,),
            hbm_budget_bytes=6 * _MiB),
        ProgramSpec(
            name="fused_ce_fwd",
            build=_build_fused_ce_fwd,
            forbid_logits=(_CE_N, _CE_V),
            hbm_budget_bytes=1 * _MiB),
        ProgramSpec(
            name="fused_ce_bwd",
            build=_build_fused_ce_bwd,
            forbid_logits=(_CE_N, _CE_V),
            hbm_budget_bytes=1 * _MiB),
    ]
