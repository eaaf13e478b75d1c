"""A dropless sparse expert layer that is told which experts it holds.

A published mixture of experts routes every token over ALL of its
``n_routed`` experts; a deployment spreads the experts over chips
(expert parallelism), so one chip holds a few of them, ``held``, and
computes for each token only the part of the result its own experts
give.  This module is that one chip's layer:

    sigma  = score(x W_g)                       (n_routed scores, float32)
    chosen = top_k(sigma + b)                   b selects, never weighs
    w      = sigma[chosen] / sum(sigma[chosen]) * route_scale
    y      = sum_{e in chosen & held} w_e Expert_e(x)  +  Shared(x)
    Expert(x) = W_down(silu(W_gate x) * W_up x)

What the experts on other chips would have added is NOT here, and no
code stands in for them or for the exchange that would bring their
tokens: with ``held`` = all experts the layer is the whole published
layer, and the routed parts of a partition of the experts add up to it
(tests/test_experts.py holds both).

Dropless, with static shapes.  The ``N * top_k`` (token, expert)
assignments are sorted so that those on held experts come first,
grouped by expert; the groups go through `jax.lax.ragged_dot` (on a TPU
XLA lowers it to a grouped-matmul kernel that visits only the tiles a
group has rows in, so an expert no token chose is never read).  The
sorted rows are walked in tiles of a static size, as many tiles as the
held assignments fill: the bound is the ``N * top_k`` assignments
themselves, never a capacity, so imbalance costs time and drops
nothing.  `moe_layer` hands back, beside the result, what the routing
did on this chip (`STATS`), computed where the counts already are.

`models/moe.py` is another layer (GShard: softmax, a capacity, drops,
GELU, biases) wired into GPT-2's training path; it is left as it is.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu._private import scopes

#: what `moe_layer` reports of one layer's routing on this chip:
#: assignments that fell on held experts, held experts with at least
#: one token, and the fullest held expert's tokens over their mean
STATS = ("assignments_local", "experts_touched", "load_max_over_mean")


@dataclasses.dataclass(frozen=True)
class ExpertsConfig:
    d_model: int
    d_expert: int
    n_routed: int
    top_k: int
    #: indices (of 0..n_routed-1) of the experts this chip holds, in the
    #: order of the stacked weights; None holds them all
    held: Optional[Tuple[int, ...]] = None
    #: "sigmoid" (scores are independent; `bias` joins them for the
    #: selection only) or "softmax" over the n_routed logits
    scoring: str = "sigmoid"
    norm_topk: bool = True
    route_scale: float = 1.0
    #: shared experts every token takes, as ONE MLP of n_shared*d_expert
    n_shared: int = 1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: most rows of sorted assignments one grouped matmul takes
    tile_rows: int = 2048

    def __post_init__(self):
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring must be 'sigmoid' or 'softmax', "
                             f"got {self.scoring!r}")
        if not 1 <= self.top_k <= self.n_routed:
            raise ValueError(f"top_k {self.top_k} outside 1..n_routed="
                             f"{self.n_routed}")
        held = self.held_ids
        if len(set(held)) != len(held) or not all(
                0 <= e < self.n_routed for e in held):
            raise ValueError(f"held must be distinct experts of "
                             f"0..{self.n_routed - 1}, got {held}")

    @property
    def held_ids(self) -> Tuple[int, ...]:
        return tuple(range(self.n_routed)) if self.held is None \
            else tuple(self.held)

    @property
    def n_held(self) -> int:
        return len(self.held_ids)


def held_range(start: int, count: int) -> Tuple[int, ...]:
    """``held`` for a chip that holds `count` consecutive experts."""
    return tuple(range(start, start + count))


def experts_init(key, cfg: ExpertsConfig, *, std: float = 0.02,
                 out_std: Optional[float] = None) -> Dict[str, Any]:
    """Router (float32 whatever ``param_dtype``: the scores decide which
    experts run), the held experts' stacked weights, the shared MLP.
    `out_std` is the down projections' (into the residual stream)."""
    d, f, g = cfg.d_model, cfg.d_expert, cfg.n_held
    out_std = std if out_std is None else out_std
    ks = jax.random.split(key, 8)
    pd = cfg.param_dtype

    def normal(k, shape, s, dtype=pd):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    p = {"router": {"w": normal(ks[0], (d, cfg.n_routed), std,
                                jnp.float32),
                    # non-zero, so that "selects but does not weigh" is
                    # exercised by seeded weights, and SMALL where it
                    # acts: the 8th of 384 sigmoid scores sits near
                    # 0.97, where 0.01 of bias is a third of a logit
                    # and makes one expert 1.6 times as likely as
                    # another (a trained bias evens the load out, it
                    # does not tilt it; PERF.md, PR 32)
                    "bias": normal(ks[1], (cfg.n_routed,), 0.001,
                                   jnp.float32)},
         "experts": {"w_gate": normal(ks[2], (g, d, f), std),
                     "w_up": normal(ks[3], (g, d, f), std),
                     "w_down": normal(ks[4], (g, f, d), out_std)}}
    if cfg.n_shared:
        fs = cfg.n_shared * f
        p["shared"] = {"w_gate": normal(ks[5], (d, fs), std),
                       "w_up": normal(ks[6], (d, fs), std),
                       "w_down": normal(ks[7], (fs, d), out_std)}
    return p


def experts_logical_axes(cfg: ExpertsConfig, lead=()) -> Dict[str, Any]:
    """Logical axes of `experts_init`'s tree; `lead` is prepended (a
    stacked layer axis)."""
    lead = tuple(lead)
    axes = {"router": {"w": lead + ("embed", None), "bias": lead + (None,)},
            "experts": {"w_gate": lead + (None, "embed", "mlp"),
                        "w_up": lead + (None, "embed", "mlp"),
                        "w_down": lead + (None, "mlp", "embed")}}
    if cfg.n_shared:
        axes["shared"] = {"w_gate": lead + ("embed", "mlp"),
                          "w_up": lead + ("embed", "mlp"),
                          "w_down": lead + ("mlp", "embed")}
    return axes


def experts_param_count(cfg: ExpertsConfig) -> int:
    d, f = cfg.d_model, cfg.d_expert
    return (d * cfg.n_routed + cfg.n_routed
            + (cfg.n_held + cfg.n_shared) * 3 * d * f)


@jax.named_scope(scopes.MOE_ROUTER)
def route(router, x32, cfg: ExpertsConfig):
    """x32 (N, d) float32 -> (chosen (N, top_k) int32 expert ids,
    weights (N, top_k) float32).  Float32 throughout, the matmul at
    ``highest`` precision (a TPU's default would round its inputs to
    bf16, and the 8th and 9th of 384 scores lie close)."""
    logits = jnp.dot(x32.astype(jnp.float32),
                     router["w"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if cfg.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        select = scores + router["bias"].astype(jnp.float32)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        select = scores
    _, chosen = lax.top_k(select, cfg.top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), w * cfg.route_scale


def _swiglu(x, p, dtype):
    g = x @ p["w_gate"].astype(dtype)
    u = x @ p["w_up"].astype(dtype)
    return (jax.nn.silu(g) * u) @ p["w_down"].astype(dtype)


@jax.named_scope(scopes.MLP)
def shared_expert(p, x, cfg: ExpertsConfig):
    """The shared experts' MLP on every row of x (N, d)."""
    return _swiglu(x.astype(cfg.dtype), p, cfg.dtype)


def _grouped(xs, p, sizes, dtype, layer=None):
    """The held experts' SwiGLU on rows xs (R, d) already grouped by
    expert, `sizes` (n_held,) rows each; rows past their sum give 0.

    With `layer`, p's weights are a STACK over layers (L, n_held, ...):
    every layer's experts are groups of one grouped matmul and only
    layer `layer`'s have rows.  The stack is never sliced: a slice of it
    handed to the kernel would be copied first, every expert of the
    layer, touched or not (1 GB a layer at the published widths)."""
    def dot(a, w):
        gs = sizes
        if layer is not None:
            L, g = w.shape[:2]
            w = w.reshape(L * g, *w.shape[2:])
            gs = lax.dynamic_update_slice(
                jnp.zeros((L * g,), sizes.dtype), sizes, (layer * g,))
        return lax.ragged_dot(a, w.astype(dtype), gs,
                              preferred_element_type=jnp.float32)

    h = jax.nn.silu(dot(xs, p["w_gate"])) * dot(xs, p["w_up"])
    return dot(h.astype(dtype), p["w_down"])


def tile_rows(n_assign: int, cfg: ExpertsConfig) -> int:
    """Rows of sorted assignments one grouped matmul takes: twice this
    chip's even share of them, in whole lane tiles of 128, at most
    ``cfg.tile_rows`` (and never more than there are).  A decode wave's
    handful of local rows must not be padded to a prefill's tile: the
    kernel's row tile is the matmul's M."""
    share = 2 * n_assign * cfg.n_held // cfg.n_routed
    rows = min(cfg.tile_rows, max(128, -(-share // 128) * 128))
    return min(rows, n_assign)


@jax.named_scope(scopes.MOE_EXPERTS)
def routed_experts(p, x, chosen, w, cfg: ExpertsConfig, valid=None,
                   tiled: bool = True, layer=None):
    """The held experts' part of the routed sum.

    x (N, d) compute dtype; chosen, w (N, top_k) as `route` gives them;
    valid (N,) bool or None: rows that hold no token (a prefill's pads,
    a decode pool's idle rows) are routed nowhere.  `tiled` False takes
    all N*top_k sorted rows in one grouped matmul (static all through,
    so it differentiates; the training forward uses it), True walks
    them in `tile_rows` as far as the held assignments reach.  `layer`
    says that p is a stack over layers and which of them this is
    (`_grouped`).

    Returns (y (N, d) float32, stats (len(STATS),) float32)."""
    N, d = x.shape
    K, g = cfg.top_k, cfg.n_held
    A = N * K
    # expert id -> its place among the held, g for an expert elsewhere
    place = np.full((cfg.n_routed,), g, np.int32)
    place[list(cfg.held_ids)] = np.arange(g, dtype=np.int32)
    local = jnp.asarray(place)[chosen]                      # (N, K)
    if valid is not None:
        local = jnp.where(valid[:, None], local, g)
    local = local.reshape(A)
    order = jnp.argsort(local, stable=True)     # held first, by expert
    tok = (order // K).astype(jnp.int32)        # the row each came from
    wt = jnp.where(local[order] < g, w.reshape(A)[order], 0.0)
    counts = jnp.sum(local[:, None] == jnp.arange(g)[None, :], axis=0,
                     dtype=jnp.int32)                       # (g,)
    n_local = jnp.sum(counts)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    x = x.astype(cfg.dtype)
    R = tile_rows(A, cfg) if tiled else A

    # (a loop's body names its scope again: it is lowered as a function
    # of its own, kimi_k2_decode.attend_blockwise)
    @jax.named_scope(scopes.MOE_EXPERTS)
    def tile(i, y):
        s = i * R
        t = lax.dynamic_slice_in_dim(tok, s, R)
        sizes = jnp.clip(ends - s, 0, R) - jnp.clip(starts - s, 0, R)
        out = _grouped(x[t], p, sizes, cfg.dtype, layer)
        weight = lax.dynamic_slice_in_dim(wt, s, R)
        return y.at[t].add(out * weight[:, None])

    y = jnp.zeros((N, d), jnp.float32)
    if R == A:
        y = tile(0, y)
    else:
        # the sorted rows padded to whole tiles (a dynamic_slice
        # clamps a last tile that would run over, and would take rows
        # twice); sorted rows past n_local carry weight 0
        pad = -A % R
        if pad:
            tok = jnp.concatenate([tok, jnp.zeros((pad,), tok.dtype)])
            wt = jnp.concatenate([wt, jnp.zeros((pad,), wt.dtype)])
        y = lax.fori_loop(0, (n_local + R - 1) // R, tile, y)
    load = counts.astype(jnp.float32)
    mean = jnp.sum(load) / g
    stats = jnp.stack([
        n_local.astype(jnp.float32),
        jnp.sum(counts > 0).astype(jnp.float32),
        jnp.where(mean > 0, jnp.max(load) / jnp.maximum(mean, 1e-9), 0.0)])
    return y, stats


def moe_layer(p, x32, cfg: ExpertsConfig, valid=None, tiled: bool = True):
    """One expert layer on rows x32 (N, d) float32 (the block's normed
    input): routed part of the held experts plus the shared experts.
    ``p["experts"]`` is this layer's weights, or with ``p["layer"]`` (an
    int32 scalar) the stack of every layer's, left whole (`_grouped`).
    Returns (y (N, d) in ``cfg.dtype``, stats as `routed_experts`)."""
    chosen, w = route(p["router"], x32, cfg)
    x = x32.astype(cfg.dtype)
    y, stats = routed_experts(p["experts"], x, chosen, w, cfg, valid,
                              tiled, p.get("layer"))
    if cfg.n_shared:
        y = y + shared_expert(p["shared"], x, cfg).astype(jnp.float32)
    return y.astype(cfg.dtype), stats
