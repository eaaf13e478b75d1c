"""Autoregressive KV-cache decoding for the LLaMA family.

Same TPU-first shape as gpt2_decode (static max_seq cache, single
full-sequence `llama_prefill` dispatch, one compiled per-token step
scanned over stacked layers, per-sequence position vectors for ragged
batches), adapted to the llama block: RMSNorm, RoPE applied at each
row's live position, grouped-query attention (the cache stores the kv
heads only — GQA's memory win is exactly here: cache bytes scale with
n_kv_head, not n_head), SwiGLU, untied lm_head.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import decode_common
from ray_tpu.models.decode_common import (PagedKV, dense_layer_kv,
                                          generate_with, is_paged,
                                          scan_prefill, slot_mask)
from ray_tpu.models.llama import (LlamaConfig, _rmsnorm,
                                  rope_frequencies)

__all__ = ["llama_init_cache", "llama_init_paged_cache",
           "llama_prefill", "llama_paged_prefill", "llama_decode_step",
           "llama_verify_step", "llama_generate"]


def llama_init_cache(cfg: LlamaConfig, batch: int,
                     mesh=None) -> Dict[str, jnp.ndarray]:
    """(L, B, S, n_kv_head, hd) key/value cache + per-sequence position
    vectors (decode_common cache contract).  With `mesh`, the cache is
    born partitioned — KV heads over `tensor` when n_kv_head divides
    the tensor degree, replicated otherwise (GQA guard)."""
    shape = (cfg.n_layer, batch, cfg.max_seq, cfg.n_kv_head,
             cfg.head_dim)

    def build():
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype),
                "pos": jnp.zeros((batch,), jnp.int32),
                "start": jnp.zeros((batch,), jnp.int32)}

    if mesh is None:
        return build()
    return decode_common.partitioned_cache_init(build, mesh)


def llama_init_paged_cache(cfg: LlamaConfig, batch: int, *,
                           num_blocks: int, block_size: int,
                           mesh=None) -> Dict[str, jnp.ndarray]:
    """Block-pool cache (decode_common paged contract): K/V pools of
    (L, num_blocks, block_size, n_kv_head, hd) shared by all rows,
    per-row block tables initialized to the reserved null block 0.
    With `mesh`, the pool is born partitioned (see llama_init_cache;
    tables/pos/start stay replicated for the host pager)."""
    if cfg.max_seq % block_size:
        raise ValueError(f"max_seq={cfg.max_seq} must be a multiple of "
                         f"block_size={block_size}")
    shape = (cfg.n_layer, num_blocks, block_size, cfg.n_kv_head,
             cfg.head_dim)

    def build():
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype),
                "block_tables": jnp.zeros(
                    (batch, cfg.max_seq // block_size), jnp.int32),
                "pos": jnp.zeros((batch,), jnp.int32),
                "start": jnp.zeros((batch,), jnp.int32)}

    if mesh is None:
        return build()
    return decode_common.partitioned_cache_init(build, mesh)


def _rope_at(x, cos_t, sin_t):
    """Rotate (B, H, hd) by per-row table rows (B, hd/2)."""
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    c = cos_t[:, None, :]
    s = sin_t[:, None, :]
    out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c],
                    axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def _rope_bt(x, cos_bt, sin_bt):
    """Rotate (B, T, H, hd) by per-row, per-column tables (B, T, hd/2)
    — the ragged-prefill variant of llama.apply_rope, whose (T, hd/2)
    tables assume every row shares the same position ladder."""
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    c = cos_bt[:, :, None, :]
    s = sin_bt[:, :, None, :]
    out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c],
                    axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def llama_prefill(params, tokens: jnp.ndarray, cfg: LlamaConfig, *,
                  lengths: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-dispatch prompt ingestion: tokens (B, T0) int32 →
    (last_logits (B, padded_vocab) float32, primed cache).

    One full-sequence forward (training-path attention; flash kernel
    under the same dispatch rules on the equal-length path), K/V for
    all T0 positions written with one dynamic_update_slice per cache
    tensor — the cache keeps kv heads only (pre-repeat, post-RoPE),
    exactly what llama_decode_step expects.  Ragged rows are
    LEFT-padded with `lengths` (B,); RoPE angles follow each row's
    logical positions, so pads never shift a real token's rotation."""
    from ray_tpu.ops.attention import prefill_attention
    from ray_tpu.parallel.sharding import DECODE_RULES

    B, T0 = tokens.shape
    d, h, kv, hd = (cfg.d_model, cfg.n_head, cfg.n_kv_head,
                    cfg.head_dim)
    cache = llama_init_cache(cfg, B)
    if lengths is None:
        start = jnp.zeros((B,), jnp.int32)
        pos_ids = jnp.broadcast_to(jnp.arange(T0), (B, T0))
    else:
        start = (T0 - jnp.asarray(lengths, jnp.int32)).astype(jnp.int32)
        pos_ids = jnp.maximum(jnp.arange(T0)[None, :] - start[:, None], 0)
    x = params["wte"].astype(cfg.dtype)[tokens]          # (B, T0, d)
    cos, sin = rope_frequencies(cfg.max_seq, hd, cfg.rope_theta)
    cos_p, sin_p = cos[pos_ids], sin[pos_ids]            # (B, T0, hd/2)
    attn_start = None if lengths is None else start

    def body(x, layer):
        p, = layer
        xa = _rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps)
        xa = xa.astype(cfg.dtype)
        q = (xa @ p["attn"]["wq"].astype(cfg.dtype).reshape(d, h * hd)
             ).reshape(B, T0, h, hd)
        k = (xa @ p["attn"]["wk"].astype(cfg.dtype).reshape(d, kv * hd)
             ).reshape(B, T0, kv, hd)
        v = (xa @ p["attn"]["wv"].astype(cfg.dtype).reshape(d, kv * hd)
             ).reshape(B, T0, kv, hd)
        q = _rope_bt(q, cos_p, sin_p)
        k = _rope_bt(k, cos_p, sin_p)
        if kv != h:
            rep = h // kv
            kr = jnp.repeat(k, rep, axis=2)
            vr = jnp.repeat(v, rep, axis=2)
        else:
            kr, vr = k, v
        o = prefill_attention(q, kr, vr, start=attn_start,
                              use_flash=cfg.use_flash,
                              resident=cfg.flash_resident,
                              rules=DECODE_RULES)
        wo = p["attn"]["wo"].astype(cfg.dtype).reshape(h * hd, d)
        x = x + (o.reshape(B, T0, h * hd) @ wo).astype(x.dtype)
        xm = _rmsnorm(x, p["ln2"]["scale"], cfg.rms_eps)
        xm = xm.astype(cfg.dtype)
        gate = xm @ p["mlp"]["w_gate"].astype(cfg.dtype)
        up = xm @ p["mlp"]["w_up"].astype(cfg.dtype)
        hmid = jax.nn.silu(gate) * up
        x = x + (hmid @ p["mlp"]["w_down"].astype(cfg.dtype)
                 ).astype(x.dtype)
        return x, (k, v)

    x, (ks, vs) = lax.scan(body, x, (params["blocks"],))
    cache["k"] = lax.dynamic_update_slice(cache["k"], ks,
                                          (0, 0, 0, 0, 0))
    cache["v"] = lax.dynamic_update_slice(cache["v"], vs,
                                          (0, 0, 0, 0, 0))
    cache["pos"] = jnp.full((B,), T0, jnp.int32)
    cache["start"] = start
    x = _rmsnorm(x, params["ln_f"]["scale"], cfg.rms_eps)
    last = x[:, -1]                 # left padding ⇒ last real token
    logits = (last.astype(cfg.dtype)
              @ params["lm_head"].astype(cfg.dtype)
              ).astype(jnp.float32)
    return logits, cache


def llama_paged_prefill(params, cache, tokens: jnp.ndarray,
                        cfg: LlamaConfig, *, row_bt: jnp.ndarray,
                        prefix_len, n_tail, slot
                        ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Prompt-tail ingestion for ONE sequence against the block pool
    (see gpt2_decode.paged_prefill for the full contract): tokens
    (1, Tt) RIGHT-aligned tail, prefix K/V read from resident pool
    blocks via row_bt, tail K/V (post-RoPE, kv heads only) written into
    the pool where it lies (pads are masked writes;
    decode_common.PagedKV).  RoPE follows logical positions, and the
    kv heads are repeated to n_head for attention exactly as in
    llama_prefill so the hidden states match the dense path."""
    _, Tt = tokens.shape
    d, h, kv, hd = (cfg.d_model, cfg.n_head, cfg.n_kv_head,
                    cfg.head_dim)
    prefix_len = jnp.asarray(prefix_len, jnp.int32)
    n_tail = jnp.asarray(n_tail, jnp.int32)
    pad = Tt - n_tail
    col = jnp.arange(Tt, dtype=jnp.int32)
    real = col >= pad                          # (Tt,), False on pads
    logical = prefix_len + col - pad           # position iff real
    pos_ids = jnp.maximum(logical, 0)          # pads clip to position 0
    # pad columns MUST be masked writes (slot max_seq) — their logical
    # index can alias a live prefix slot
    pkv = PagedKV(cache, row_bt[None],
                  jnp.where(real, logical, cfg.max_seq)[None])
    mask = real[:, None] & (
        jnp.arange(cfg.max_seq)[None, :] <= logical[:, None])
    scale = 1.0 / math.sqrt(hd)
    x = params["wte"].astype(cfg.dtype)[tokens[0]]       # (Tt, d)
    cos, sin = rope_frequencies(cfg.max_seq, hd, cfg.rope_theta)
    cos_p, sin_p = cos[pos_ids], sin[pos_ids]            # (Tt, hd/2)

    def body(carry, layer):
        x, lidx, pools = carry
        p, = layer
        xa = _rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps)
        xa = xa.astype(cfg.dtype)
        q = (xa @ p["attn"]["wq"].astype(cfg.dtype).reshape(d, h * hd)
             ).reshape(Tt, h, hd)
        k = (xa @ p["attn"]["wk"].astype(cfg.dtype).reshape(d, kv * hd)
             ).reshape(Tt, kv, hd)
        v = (xa @ p["attn"]["wv"].astype(cfg.dtype).reshape(d, kv * hd)
             ).reshape(Tt, kv, hd)
        q = _rope_at(q, cos_p, sin_p)
        k = _rope_at(k, cos_p, sin_p)
        pools, (kview, vview) = pkv.attend(lidx, pools, k[None],
                                          v[None])
        kview, vview = kview[0], vview[0]                # (S,kv,hd)
        if kv != h:
            rep = h // kv
            kview = jnp.repeat(kview, rep, axis=1)
            vview = jnp.repeat(vview, rep, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q,
                            kview).astype(jnp.float32) * scale
        scores = jnp.where(mask[None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        o = jnp.einsum("hqk,khd->qhd", probs, vview)
        wo = p["attn"]["wo"].astype(cfg.dtype).reshape(h * hd, d)
        x = x + (o.reshape(Tt, h * hd) @ wo).astype(x.dtype)
        xm = _rmsnorm(x, p["ln2"]["scale"], cfg.rms_eps)
        xm = xm.astype(cfg.dtype)
        gate = xm @ p["mlp"]["w_gate"].astype(cfg.dtype)
        up = xm @ p["mlp"]["w_up"].astype(cfg.dtype)
        hmid = jax.nn.silu(gate) * up
        x = x + (hmid @ p["mlp"]["w_down"].astype(cfg.dtype)
                 ).astype(x.dtype)
        return (x, lidx + 1, pools), (k[None], v[None])

    (x, _, pools), (new_k, new_v) = lax.scan(
        body, (x, jnp.int32(0), pkv.pools),
        (params["blocks"],))
    x = _rmsnorm(x, params["ln_f"]["scale"], cfg.rms_eps)
    last = x[-1]                    # right-aligned ⇒ last real token
    logits = (last.astype(cfg.dtype)
              @ params["lm_head"].astype(cfg.dtype)
              ).astype(jnp.float32)
    out = pkv.commit(pools, new_k, new_v)
    out["block_tables"] = cache["block_tables"].at[slot].set(row_bt)
    out["pos"] = cache["pos"].at[slot].set(prefix_len + n_tail)
    out["start"] = cache["start"].at[slot].set(0)
    return logits, out


def llama_decode_step(params, cache, tokens, cfg: LlamaConfig
                      ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One token per sequence: tokens (B,) int32, row b at cache slot
    cache["pos"][b]; RoPE at each row's LOGICAL position pos - start.

    Works on both cache layouts (decode_common.is_paged): dense caches
    write slot pos[b] in a (B, S, ...) layer; paged caches attend over
    the block-table view gathered from the pool with the new token in
    it (value-identical to dense, so the attention math is shared) and
    write the step's K/V into the pool where it lies
    (decode_common.PagedKV: read-only inside the layer scan, the rows
    land after it).

    Returns (logits (B, padded_vocab) float32, updated cache)."""
    B = tokens.shape[0]
    d, h, kv, hd = (cfg.d_model, cfg.n_head, cfg.n_kv_head,
                    cfg.head_dim)
    g = h // kv
    paged = is_paged(cache)
    pos = cache["pos"]                                   # (B,)
    start = cache["start"]                               # (B,)
    rows = jnp.arange(B)
    x = params["wte"].astype(cfg.dtype)[tokens]          # (B, d)
    cos, sin = rope_frequencies(cfg.max_seq, hd, cfg.rope_theta)
    cos_t, sin_t = cos[pos - start], sin[pos - start]    # (B, hd/2)
    attn_mask = slot_mask(start, pos + 1, cfg.max_seq)   # (B, S)
    pkv = PagedKV(cache, cache["block_tables"],
                  pos[:, None]) if paged else None

    def body(carry, layer):
        x, lidx, pools = carry
        p, = layer
        xa = _rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps)
        xa = xa.astype(cfg.dtype)
        q = (xa @ p["attn"]["wq"].astype(cfg.dtype).reshape(d, h * hd)
             ).reshape(B, h, hd)
        k_new = (xa @ p["attn"]["wk"].astype(cfg.dtype)
                 .reshape(d, kv * hd)).reshape(B, kv, hd)
        v_new = (xa @ p["attn"]["wv"].astype(cfg.dtype)
                 .reshape(d, kv * hd)).reshape(B, kv, hd)
        q = _rope_at(q, cos_t, sin_t)
        k_new = _rope_at(k_new, cos_t, sin_t)
        if paged:
            new = (k_new[:, None], v_new[:, None])       # (B,1,kv,hd)
            pools, (ck, cv) = pkv.attend(lidx, pools, *new)
        else:
            lk, lv = dense_layer_kv(cache, lidx)
            ck = lk.at[rows, pos].set(k_new)   # row b → slot pos[b]
            cv = lv.at[rows, pos].set(v_new)
            new = (ck, cv)
        # grouped-query attention against the kv-head cache: query
        # heads reshape to (kv, group) — no head repetition needed
        qg = q.reshape(B, kv, g, hd)
        scores = jnp.einsum("bkgd,bskd->bkgs", qg,
                            ck).astype(jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(attn_mask[:, None, None, :], scores,
                           -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        o = jnp.einsum("bkgs,bskd->bkgd", probs, cv)
        wo = p["attn"]["wo"].astype(cfg.dtype).reshape(h * hd, d)
        x = x + (o.reshape(B, h * hd) @ wo).astype(x.dtype)
        xm = _rmsnorm(x, p["ln2"]["scale"], cfg.rms_eps)
        xm = xm.astype(cfg.dtype)
        gate = xm @ p["mlp"]["w_gate"].astype(cfg.dtype)
        up = xm @ p["mlp"]["w_up"].astype(cfg.dtype)
        hmid = jax.nn.silu(gate) * up
        x = x + (hmid @ p["mlp"]["w_down"].astype(cfg.dtype)
                 ).astype(x.dtype)
        return (x, lidx + 1, pools), new

    (x, _, pools), (new_k, new_v) = lax.scan(
        body, (x, jnp.int32(0), pkv.pools if pkv else ()),
        (params["blocks"],))
    x = _rmsnorm(x, params["ln_f"]["scale"], cfg.rms_eps)
    logits = (x.astype(cfg.dtype)
              @ params["lm_head"].astype(cfg.dtype)
              ).astype(jnp.float32)
    if paged:
        out = pkv.commit(pools, new_k, new_v)
    else:
        out = dict(cache, k=new_k, v=new_v)
    out["pos"] = pos + 1
    return logits, out


def llama_verify_step(params, cache, block, cfg: LlamaConfig
                      ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Speculative-decode verify forward, llama flavour (see
    gpt2_decode.verify_step for the shared contract): block (B, T=k+1)
    int32 = [cur, d_1..d_k], one dispatch producing logits (B, T,
    padded_vocab) equal to T sequential llama_decode_step calls.  RoPE
    rotates each (row, column) at its own logical position via the
    per-row-per-column tables (_rope_bt); GQA attends through the
    kv-head cache with the (kv, group) query reshape.  Writes past
    max_seq route to the null block (paged) / drop (dense); pos is NOT
    advanced — make_spec_verify moves it by the accepted count."""
    B, T = block.shape
    d, h, kv, hd = (cfg.d_model, cfg.n_head, cfg.n_kv_head,
                    cfg.head_dim)
    g = h // kv
    paged = is_paged(cache)
    pos = cache["pos"]                                   # (B,)
    start = cache["start"]                               # (B,)
    rows = jnp.arange(B)
    offs = jnp.arange(T, dtype=jnp.int32)
    slot_ids = pos[:, None] + offs[None, :]              # (B, T)
    pos_ids = jnp.minimum(jnp.maximum(slot_ids - start[:, None], 0),
                          cfg.max_seq - 1)
    x = params["wte"].astype(cfg.dtype)[block]           # (B, T, d)
    cos, sin = rope_frequencies(cfg.max_seq, hd, cfg.rope_theta)
    cos_p, sin_p = cos[pos_ids], sin[pos_ids]            # (B, T, hd/2)
    s = jnp.arange(cfg.max_seq)
    attn_mask = (s[None, None, :] >= start[:, None, None]) & \
                (s[None, None, :] <= slot_ids[:, :, None])
    pkv = None
    if paged:
        # slots past max_seq are PagedKV's masked writes
        pkv = PagedKV(cache, cache["block_tables"], slot_ids)
    else:
        write_idx = jnp.where(slot_ids < cfg.max_seq, slot_ids,
                              cfg.max_seq)

    def body(carry, layer):
        x, lidx, pools = carry
        p, = layer
        xa = _rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps)
        xa = xa.astype(cfg.dtype)
        q = (xa @ p["attn"]["wq"].astype(cfg.dtype).reshape(d, h * hd)
             ).reshape(B, T, h, hd)
        k_new = (xa @ p["attn"]["wk"].astype(cfg.dtype)
                 .reshape(d, kv * hd)).reshape(B, T, kv, hd)
        v_new = (xa @ p["attn"]["wv"].astype(cfg.dtype)
                 .reshape(d, kv * hd)).reshape(B, T, kv, hd)
        q = _rope_bt(q, cos_p, sin_p)
        k_new = _rope_bt(k_new, cos_p, sin_p)
        if paged:
            new = (k_new, v_new)
            pools, (ck, cv) = pkv.attend(lidx, pools, *new)
        else:
            lk, lv = dense_layer_kv(cache, lidx)
            ck = lk.at[rows[:, None], write_idx].set(
                k_new, mode="drop")
            cv = lv.at[rows[:, None], write_idx].set(
                v_new, mode="drop")
            new = (ck, cv)
        qg = q.reshape(B, T, kv, g, hd)
        scores = jnp.einsum("btkgd,bskd->bkgts", qg,
                            ck).astype(jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(attn_mask[:, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        o = jnp.einsum("bkgts,bskd->btkgd", probs, cv)
        wo = p["attn"]["wo"].astype(cfg.dtype).reshape(h * hd, d)
        x = x + (o.reshape(B, T, h * hd) @ wo).astype(x.dtype)
        xm = _rmsnorm(x, p["ln2"]["scale"], cfg.rms_eps)
        xm = xm.astype(cfg.dtype)
        gate = xm @ p["mlp"]["w_gate"].astype(cfg.dtype)
        up = xm @ p["mlp"]["w_up"].astype(cfg.dtype)
        hmid = jax.nn.silu(gate) * up
        x = x + (hmid @ p["mlp"]["w_down"].astype(cfg.dtype)
                 ).astype(x.dtype)
        return (x, lidx + 1, pools), new

    (x, _, pools), (new_k, new_v) = lax.scan(
        body, (x, jnp.int32(0), pkv.pools if pkv else ()),
        (params["blocks"],))
    x = _rmsnorm(x, params["ln_f"]["scale"], cfg.rms_eps)
    logits = (x.astype(cfg.dtype)
              @ params["lm_head"].astype(cfg.dtype)
              ).astype(jnp.float32)
    if paged:
        return logits, pkv.commit(pools, new_k, new_v)
    return logits, dict(cache, k=new_k, v=new_v)


def _scan_prefill(params, tokens, cfg, *, lengths=None):
    """prefill-shaped wrapper over the per-token reference scan."""
    if lengths is not None:
        raise ValueError("prefill_impl='scan' is the equal-length "
                         "reference path; ragged prompts need the "
                         "batched prefill")
    return scan_prefill(llama_init_cache, llama_decode_step, params,
                        tokens, cfg)


def llama_generate(params, prompt: jnp.ndarray, cfg: LlamaConfig, *,
                   max_new_tokens: int, temperature: float = 1.0,
                   top_k: int = 0, top_p: float = 1.0,
                   lengths: Optional[jnp.ndarray] = None,
                   key: Optional[jax.Array] = None,
                   prefill_impl: str = "batched",
                   kv_layout: str = "dense",
                   kv_block_size: int = 16) -> jnp.ndarray:
    """LLaMA generation via the shared loop (decode_common).  `lengths`
    marks LEFT-padded ragged prompts; prefill_impl="scan" keeps the
    per-token reference prefill for parity testing; kv_layout="paged"
    decodes through the block-pool layout (dense is its oracle);
    top_k/top_p are jit-static sampling filters."""
    prefill_fn = (llama_prefill if prefill_impl == "batched"
                  else _scan_prefill)
    return generate_with(prefill_fn, llama_decode_step, params, prompt,
                         cfg, max_new_tokens=max_new_tokens,
                         lengths=lengths, temperature=temperature,
                         top_k=top_k, top_p=top_p,
                         key=key, kv_layout=kv_layout,
                         kv_block_size=kv_block_size)
