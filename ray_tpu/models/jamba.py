"""Jamba-family hybrid decoder: Mamba mixers with a few attention
mixers between them, every layer followed by a SwiGLU MLP.

Same template as gpt2.py / llama.py (pure init/apply over pytrees,
logical sharding axes, bf16 compute over float32 or bf16 weights), with
one difference the rest of the zoo does not have: the layers are NOT
all alike.  Layer ``i`` of ``n_layer`` is an attention layer iff
``i % attn_period == attn_offset`` and a Mamba layer otherwise (the
``jamba`` convention of the published ``config.json``), so the
parameters are stacked BY KIND (``params["mamba"]`` on a leading axis of
``n_mamba``, ``params["attn"]`` on one of ``n_attn``) and the pattern is
walked by period (`walk_layers`): one scan over the periods, in it a
scan over the Mamba layers before the period's attention layer and one
over those after.  The compiled program holds each kind of layer twice
at most, never ``n_layer`` times, and each layer's weights are sliced
out of their stack where they lie.

The layer equations (Lieber et al. 2024, "Jamba"; Gu & Dao 2023,
"Mamba"; Hugging Face ``modeling_jamba``), ``u = RMSNorm(h)``:

  * attention: ``q = W_q u`` (n_head heads), ``k = W_k u``, ``v = W_v u``
    (n_kv_head heads), causal softmax of ``q k^T / sqrt(head_dim)``, no
    positional encoding of any kind, no bias; ``W_o``.
  * Mamba: ``[x, z] = W_in u``; ``x <- silu(conv1d_causal_depthwise(x;
    d_conv, bias))``; ``[dt, B, C] = split(W_x x, [dt_rank, d_state,
    d_state])``, each through its own RMSNorm (Jamba's addition to
    Mamba-1); ``D = softplus(W_dt dt + b_dt)``; ``A = -exp(A_log)``;
    ``s_t = exp(D_t A) * s_{t-1} + (D_t * x_t) (x) B_t``;
    ``y_t = s_t C_t + D_skip * x_t``; ``out = W_out(y * silu(z))``.
  * every layer: ``h <- h + mixer(RMSNorm(h))``, then ``h <- h +
    W_down(silu(W_gate m) * W_up m)`` with ``m = RMSNorm(h)``.
  * logits ``= RMSNorm(h) E^T``: the embedding is tied.

A Mamba layer's past is not a K/V row per token but two small tensors
per SEQUENCE: the last ``d_conv - 1`` inputs of the convolution (the
*window*, in the compute dtype) and the SSM state ``s`` (float32).
`mamba_mix` takes both and hands both back; the decoders
(jamba_decode.py) keep them in the cache beside the K/V of the
attention layers.  The state is laid out ``(d_state, d_inner)`` and the
window ``(d_conv - 1, batch, d_inner)``: ``d_inner`` is the lane axis of
a TPU tile, and a minor dimension of 16 or 3 would be padded eightfold.

A pad column (a left-padded ragged row, a prefill bucket's pad) leaves
both untouched: its ``D`` is 0, so ``exp(0 A) = 1`` and nothing is
added, exactly, and the window is laid directly before the first real
column.  The recurrence runs in the order of the tokens whatever the
padding, so a prompt gives bit-equal state at every bucket size.  The
scan over time (`ssm_scan`) is one Pallas kernel on a TPU wherever a
program holds more than one column (ops/ssm_scan.py: the state in VMEM,
the columns walked inside); a decode step, and every program off the
TPU, runs the ``jnp`` chain: the exponentials and outer products of
``scan_chunk`` columns at once, then the chain of multiply-adds through
them, each column's output reduced as its state passes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models.layers import nll_from_logits, plain_rmsnorm
from ray_tpu.models.mamba import mamba_mix
from ray_tpu.parallel.sharding import (DEFAULT_RULES,
                                       with_logical_constraint)


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65_536
    max_seq: int = 2048
    n_layer: int = 28
    n_head: int = 20
    n_kv_head: int = 1
    d_model: int = 2560
    d_ff: int = 8192
    #: layer i is attention iff i % attn_period == attn_offset
    attn_period: int = 14
    attn_offset: int = 7
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    expand: int = 2
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: what the SSM state is KEPT in between two programs (a decode
    #: step, a prefill chunk); the recurrence itself runs in float32
    state_dtype: Any = jnp.float32
    remat: bool = True
    use_flash: Optional[bool] = None    # None = auto (flash on TPU)
    vocab_pad_to: int = 128
    #: columns of the SSM scan's ``jnp`` chain computed at once
    #: (`ssm_scan`; the Pallas kernel sizes itself from the shape)
    scan_chunk: int = 32

    def __post_init__(self):
        if self.n_layer % self.attn_period:
            raise ValueError(
                f"invalid JambaConfig: n_layer {self.n_layer} is not a "
                f"whole number of periods of {self.attn_period}")
        if not 0 <= self.attn_offset < self.attn_period:
            raise ValueError(
                f"invalid JambaConfig: attn_offset {self.attn_offset} "
                f"outside the period of {self.attn_period}")
        if self.n_head % self.n_kv_head:
            raise ValueError(f"n_head {self.n_head} must divide by "
                             f"n_kv_head {self.n_kv_head}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return (self.vocab_size + p - 1) // p * p

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_attn(self) -> int:
        return self.n_layer // self.attn_period

    @property
    def n_mamba(self) -> int:
        return self.n_layer - self.n_attn

    @property
    def n_kv_layer(self) -> int:
        """Layers that keep K/V: what a K/V pool of this model holds."""
        return self.n_attn

    @property
    def state_bytes_per_slot(self) -> int:
        """SSM state and convolution window of one sequence through
        every Mamba layer."""
        state = self.d_state * self.d_inner \
            * jnp.dtype(self.state_dtype).itemsize
        window = (self.d_conv - 1) * self.d_inner \
            * jnp.dtype(self.dtype).itemsize
        return self.n_mamba * (state + window)


_PRESETS = {
    # name: (n_layer, period, offset, n_head, n_kv_head, d_model, d_ff,
    #        dt_rank)
    "nano": (4, 4, 2, 2, 1, 64, 128, 8),
    "jamba2-3b": (28, 14, 7, 20, 1, 2560, 8192, 160),
}


def jamba_config(name: str = "jamba2-3b", **overrides) -> JambaConfig:
    L, per, off, h, kv, d, f, r = _PRESETS[name]
    kw: Dict[str, Any] = dict(n_layer=L, attn_period=per,
                              attn_offset=off, n_head=h, n_kv_head=kv,
                              d_model=d, d_ff=f, dt_rank=r)
    if name == "nano":
        kw.update(vocab_size=512, max_seq=128, scan_chunk=8)
    kw.update(overrides)
    return JambaConfig(**kw)


def _mamba_params(cfg: JambaConfig) -> int:
    d, di, N, K, R = (cfg.d_model, cfg.d_inner, cfg.d_state, cfg.d_conv,
                      cfg.dt_rank)
    return (d * 2 * di + K * di + di + di * (R + 2 * N) + R * di + di
            + di * N + di + di * d + R + 2 * N)


def jamba_param_count(cfg: JambaConfig) -> int:
    d, hd = cfg.d_model, cfg.head_dim
    mlp_norms = 3 * d * cfg.d_ff + 2 * d
    attn = 2 * d * cfg.n_head * hd + 2 * d * cfg.n_kv_head * hd
    return (cfg.vocab_size * d + d
            + cfg.n_mamba * (_mamba_params(cfg) + mlp_norms)
            + cfg.n_attn * (attn + mlp_norms))


def _shared_axes() -> Dict[str, Any]:
    return {"ln1": {"scale": (None, "embed")},
            "ln2": {"scale": (None, "embed")},
            "mlp": {"w_gate": (None, "embed", "mlp"),
                    "w_up": (None, "embed", "mlp"),
                    "w_down": (None, "mlp", "embed")}}


def jamba_logical_axes(cfg: JambaConfig) -> Dict[str, Any]:
    """Pytree (matching jamba_init's) of logical-axis tuples; the
    leading None on a layer's leaves is its kind's stacked axis.
    ``d_inner`` shards as the MLP's hidden width does."""
    return {
        "wte": ("vocab", "embed"),
        "ln_f": {"scale": ("embed",)},
        "mamba": dict(_shared_axes(), mixer={
            "in_proj": (None, "embed", "mlp"),
            "conv_w": (None, None, "mlp"),
            "conv_b": (None, "mlp"),
            "x_proj": (None, "mlp", None),
            "dt_norm": (None, None), "b_norm": (None, None),
            "c_norm": (None, None),
            "dt_proj": (None, None, "mlp"),
            "dt_bias": (None, "mlp"),
            "A_log": (None, None, "mlp"),
            "D": (None, "mlp"),
            "out_proj": (None, "mlp", "embed")}),
        "attn": dict(_shared_axes(), attn={
            "wq": (None, "embed", "heads", "head_dim"),
            "wk": (None, "embed", "kv_heads", "head_dim"),
            "wv": (None, "embed", "kv_heads", "head_dim"),
            "wo": (None, "heads", "head_dim", "embed")}),
    }


def jamba_init(key, cfg: JambaConfig) -> Dict[str, Any]:
    """Seeded weights.  Projections N(0, 0.02), those into the residual
    scaled by 1/sqrt(2 n_layer); the SSM as Mamba-1 initialises it:
    ``A_log = log(1..d_state)`` (S4D-real), ``D = 1``, ``dt_proj``
    N(0, dt_rank^-1/2) with a bias whose softplus is log-uniform in
    [1e-3, 1e-1]; norms 1."""
    d, f, di, N, K, R = (cfg.d_model, cfg.d_ff, cfg.d_inner, cfg.d_state,
                         cfg.d_conv, cfg.dt_rank)
    h, kv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    Lm, La = cfg.n_mamba, cfg.n_attn
    pd = cfg.param_dtype
    k = iter(jax.random.split(key, 24))
    std = 0.02
    res_std = std / math.sqrt(2 * cfg.n_layer)

    def norm(shape, s=std):
        return (jax.random.normal(next(k), shape, dtype=jnp.float32)
                * s).astype(pd)

    def shared(L):
        return {"ln1": {"scale": jnp.ones((L, d), pd)},
                "ln2": {"scale": jnp.ones((L, d), pd)},
                "mlp": {"w_gate": norm((L, d, f)),
                        "w_up": norm((L, d, f)),
                        "w_down": norm((L, f, d), res_std)}}

    dt = jnp.exp(jax.random.uniform(next(k), (Lm, di), jnp.float32)
                 * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    mixer = {
        "in_proj": norm((Lm, d, 2 * di)),
        "conv_w": norm((Lm, K, di), 1.0 / math.sqrt(K)),
        "conv_b": norm((Lm, di)),
        "x_proj": norm((Lm, di, R + 2 * N)),
        "dt_norm": jnp.ones((Lm, R), pd),
        "b_norm": jnp.ones((Lm, N), pd),
        "c_norm": jnp.ones((Lm, N), pd),
        "dt_proj": norm((Lm, R, di), R ** -0.5),
        # softplus^-1(dt)
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None],
            (Lm, N, di)).astype(pd),
        "D": jnp.ones((Lm, di), pd),
        "out_proj": norm((Lm, di, d), res_std),
    }
    attn = {"wq": norm((La, d, h, hd)), "wk": norm((La, d, kv, hd)),
            "wv": norm((La, d, kv, hd)),
            "wo": norm((La, h, hd, d), res_std)}
    return {"wte": norm((cfg.padded_vocab, d)),
            "ln_f": {"scale": jnp.ones((d,), pd)},
            "mamba": dict(shared(Lm), mixer=mixer),
            "attn": dict(shared(La), attn=attn)}


# ---------------------------------------------------------------------------
# the walk over layers of two kinds
# ---------------------------------------------------------------------------

def layer_at(stack, index):
    """One layer's weights out of its kind's stack, where they lie."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, index, 0, keepdims=False),
        stack)


def walk_layers(cfg: JambaConfig, x, carry, mamba_layer: Callable,
                attn_layer: Callable):
    """`x` through all ``n_layer`` layers in the model's order.

    ``mamba_layer(x, carry, m) -> (x, carry)`` is Mamba layer ``m`` of
    ``n_mamba`` (with its MLP); ``attn_layer(x, carry, a) -> (x, carry,
    ys)`` attention layer ``a`` of ``n_attn``.  `carry` is whatever the
    caller threads through (cache pools, recurrent state: updated where
    it lies, never stacked); the attention layers' ``ys`` come back
    stacked on a leading axis of ``n_attn``.  Returns (x, carry, ys)."""
    per, off = cfg.attn_period - 1, cfg.attn_offset

    def run(x, carry, first, count):
        if not count:
            return x, carry

        def body(c, j):
            return mamba_layer(*c, first + j), None

        (x, carry), _ = lax.scan(body, (x, carry),
                                 jnp.arange(count, dtype=jnp.int32))
        return x, carry

    def period(c, a):
        x, carry = run(*c, a * per, off)
        x, carry, ys = attn_layer(x, carry, a)
        x, carry = run(x, carry, a * per + off, per - off)
        return (x, carry), ys

    with jax.named_scope(scopes.LAYER_SCAN):
        (x, carry), ys = lax.scan(
            period, (x, carry), jnp.arange(cfg.n_attn, dtype=jnp.int32))
    return x, carry, ys


@jax.named_scope(scopes.EMBED)
def embed(params, tokens, cfg: JambaConfig):
    """The residual stream's first value: the tokens' embeddings."""
    return params["wte"].astype(cfg.dtype)[tokens]


@jax.named_scope(scopes.MLP)
def swiglu(x, p, cfg: JambaConfig):
    xc = x.astype(cfg.dtype)
    gate = xc @ p["w_gate"].astype(cfg.dtype)
    up = xc @ p["w_up"].astype(cfg.dtype)
    return ((jax.nn.silu(gate) * up)
            @ p["w_down"].astype(cfg.dtype)).astype(x.dtype)


@jax.named_scope(scopes.LN)
def rmsnorm(x, scale, eps):
    return plain_rmsnorm(x, scale, eps)


def mlp_residual(x, p, cfg: JambaConfig):
    """``x + MLP(RMSNorm(x))``: the second half of every layer."""
    return x + swiglu(rmsnorm(x, p["ln2"]["scale"], cfg.rms_eps),
                      p["mlp"], cfg)


@jax.named_scope(scopes.LM_HEAD)
def lm_logits(x, params, cfg: JambaConfig):
    """Float32 logits of ``RMSNorm(x)`` through the tied embedding."""
    x = plain_rmsnorm(x, params["ln_f"]["scale"], cfg.rms_eps)
    return jnp.einsum("...d,vd->...v", x.astype(cfg.dtype),
                      params["wte"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


@jax.named_scope(scopes.ATTN)
def qkv(xa, p, cfg: JambaConfig):
    """``xa`` (..., d) -> q (..., n_head, hd), k, v (..., n_kv_head,
    hd): no bias, no rotation."""
    d, h, kv, hd = cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    xa = xa.astype(cfg.dtype)
    lead = xa.shape[:-1]
    q = xa @ p["wq"].astype(cfg.dtype).reshape(d, h * hd)
    k = xa @ p["wk"].astype(cfg.dtype).reshape(d, kv * hd)
    v = xa @ p["wv"].astype(cfg.dtype).reshape(d, kv * hd)
    return (q.reshape(*lead, h, hd), k.reshape(*lead, kv, hd),
            v.reshape(*lead, kv, hd))


@jax.named_scope(scopes.ATTN)
def attn_out(o, p, cfg: JambaConfig):
    """Heads (..., n_head, hd) back to the residual's width."""
    h, hd = cfg.n_head, cfg.head_dim
    wo = p["wo"].astype(cfg.dtype).reshape(h * hd, cfg.d_model)
    return o.reshape(*o.shape[:-2], h * hd) @ wo


# ---------------------------------------------------------------------------
# the Mamba mixer
# ---------------------------------------------------------------------------


def zero_recurrent(cfg: JambaConfig, batch: int, layers: bool = True):
    """(window, state) of zeros: a sequence that has seen nothing.
    With `layers`, stacked over the Mamba layers on a leading axis."""
    lead = (cfg.n_mamba,) if layers else ()
    return (jnp.zeros(lead + (cfg.d_conv - 1, batch, cfg.d_inner),
                      cfg.dtype),
            jnp.zeros(lead + (batch, cfg.d_state, cfg.d_inner),
                      cfg.state_dtype))


# ---------------------------------------------------------------------------
# full-sequence forward and loss
# ---------------------------------------------------------------------------

def jamba_hidden(params, tokens, cfg: JambaConfig, rules=DEFAULT_RULES):
    """tokens (B, T) -> the last layer's residual (B, T, d), every
    sequence from a zero state."""
    from ray_tpu.ops.attention import causal_attention

    B, T = tokens.shape
    x = embed(params, tokens, cfg)
    x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
    window, state = zero_recurrent(cfg, B, layers=False)

    def mamba_layer(x, carry, m):
        p = layer_at(params["mamba"], m)
        out, _, _, _ = mamba_mix(
            p["mixer"], rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps), cfg,
            window, state)
        x = mlp_residual(x + out, p, cfg)
        return with_logical_constraint(
            x, ("batch", "seq", "embed"), rules), carry

    def attn_layer(x, carry, a):
        p = layer_at(params["attn"], a)
        q, k, v = qkv(rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps),
                      p["attn"], cfg)
        rep = cfg.n_head // cfg.n_kv_head
        with jax.named_scope(scopes.ATTN):
            o = causal_attention(q, jnp.repeat(k, rep, axis=2),
                                 jnp.repeat(v, rep, axis=2),
                                 use_flash=cfg.use_flash, rules=rules)
        x = mlp_residual(x + attn_out(o, p["attn"], cfg).astype(x.dtype),
                         p, cfg)
        return with_logical_constraint(
            x, ("batch", "seq", "embed"), rules), carry, ()

    if cfg.remat:
        policy = jax.checkpoint_policies.nothing_saveable
        mamba_layer = jax.checkpoint(mamba_layer, policy=policy)
        attn_layer = jax.checkpoint(attn_layer, policy=policy)
    x, _, _ = walk_layers(cfg, x, (), mamba_layer, attn_layer)
    return x


def jamba_forward(params, tokens, cfg: JambaConfig,
                  rules=DEFAULT_RULES) -> jnp.ndarray:
    """tokens (B, T) int32 -> logits (B, T, padded_vocab) float32."""
    logits = lm_logits(jamba_hidden(params, tokens, cfg, rules), params,
                       cfg)
    return with_logical_constraint(logits, ("batch", "seq", "vocab"),
                                   rules)


def jamba_loss(params, batch, cfg: JambaConfig,
               rules=DEFAULT_RULES) -> jnp.ndarray:
    """Next-token cross-entropy; batch = {"tokens": (B, T+1)} or
    {"inputs", "targets"}, optionally {"mask"}; the padded vocabulary's
    tail is masked (the NLL shared with gpt2 and llama)."""
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    nll = nll_from_logits(jamba_forward(params, inputs, cfg, rules),
                          targets, cfg.vocab_size, cfg.padded_vocab)
    mask = batch.get("mask")
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


__all__ = ["JambaConfig", "jamba_config", "jamba_init", "jamba_forward",
           "jamba_loss", "jamba_logical_axes", "jamba_param_count",
           "jamba_hidden", "walk_layers",
           "zero_recurrent"]
