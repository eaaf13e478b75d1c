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

Dropless, with static shapes.  The held experts' rows are put in
GROUPED order (expert 0's first, each expert's by ascending token).
The serving path finds a choice's place among the held by comparison
and moves the rows with two Pallas kernels that walk the tokens
(ops/moe_dispatch.py): no sort over the ``N * top_k`` assignments, no
gather, no scatter.  A pass takes a static number of grouped rows
(`tile_rows`: what a prefill's local rows fit in), as many passes as
the local rows fill: the bound is the assignments themselves, never a
capacity, so imbalance costs time and drops nothing.  What multiplies
the groups is one Pallas kernel that streams each touched expert's
weights and does gate, up, SwiGLU and down on its rows
(ops/grouped_swiglu.py), so an expert no token chose is never read; how
it is asked follows the rows a group expects (`few_a_group`): few (a
decode wave), and every group begins on a row tile of 8 of its own;
many (a prefill), and the groups lie end to end under tall row tiles,
one that two groups share visited once for each.  The path that
differentiates sorts all ``N * top_k`` assignments and takes them in
one grouped matmul (`jax.lax.ragged_dot`).
`moe_layer` hands back, beside the result, what the routing did on this
chip (`STATS`), computed where the counts already are.

`models/moe.py` is another layer (GShard: softmax, a capacity, drops,
GELU, biases) wired into GPT-2's training path; it is left as it is.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models.decode_common import EXPERTS
from ray_tpu.ops.grouped_swiglu import (ROW_TILE, grouped_swiglu,
                                        row_tiles, visit_rows, visits)
from ray_tpu.ops.moe_dispatch import (combine_reference,
                                      dispatch_reference, moe_combine,
                                      moe_dispatch, rows_of, slabs)

#: what `moe_layer` reports of one layer's routing on this chip:
#: assignments that fell on held experts, held experts with at least
#: one token, the fullest held expert's tokens over their mean, and the
#: visits `grouped_swiglu` makes the experts' rows: where they are
#: `few_a_group` the row tiles they fill (as many as experts touched
#: unless one's rows overflow a tile), else the tall tiles they lie in
#: (one more than they fill for every tile's edge a group straddles)
STATS = ("assignments_local", "experts_touched", "load_max_over_mean",
         "row_tiles_visited")


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
    #: most rows of grouped assignments one pass takes (`tile_rows`)
    tile_rows: int = 4096

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


def _fused(xs, p, sizes, dtype, layer=None, tm=ROW_TILE, aligned=True,
           interpret=False):
    """`_grouped` as one kernel that fetches a touched expert's weights
    once a visit (ops/grouped_swiglu.py).  xs (R, s, l) float32 slabs
    as `moe_dispatch` writes them, in row tiles of `tm`; with `aligned`
    every group begun on a whole row tile, else where the last one
    ended; slabs back, as `moe_combine` reads them.  Rows nobody owns
    come back as whatever they make, and `moe_combine` reads owned rows
    only."""
    return grouped_swiglu(xs, p["w_gate"], p["w_up"], p["w_down"], sizes,
                          layer, dtype=dtype, tm=tm, aligned=aligned,
                          interpret=interpret)


def fused_reference(xs, p, sizes, dtype, layer=None, tm=ROW_TILE,
                    aligned=True):
    """`_fused`'s contract through `_grouped`: with `aligned` every
    group's rows rounded up to whole row tiles, so that a group begins
    where the last one's tile ends.  What runs off the chip."""
    return slabs(_grouped(
        rows_of(xs).astype(dtype), p,
        row_tiles(sizes, tm) * tm if aligned else sizes, dtype, layer))


def _local_rows(n_tokens: int, cfg: ExpertsConfig) -> int:
    """This chip's even share of the tokens' assignments and four of
    its standard deviations more (a choice is local one time in
    n_routed / n_held: the share's variance is the share), never more
    than can be local."""
    share = n_tokens * cfg.top_k * cfg.n_held // cfg.n_routed
    return min(share + 4 * math.isqrt(share),
               n_tokens * min(cfg.top_k, cfg.n_held))


def few_a_group(n_tokens: int, cfg: ExpertsConfig) -> bool:
    """A held expert expects fewer than 16 of a pass's rows (a decode
    wave, a short prefill over many small experts): a group of two or
    three rows that straddled a row tile would be visited twice and
    fetch nothing less, so every group begins on a short row tile of
    its own; else the groups lie end to end under tall ones."""
    return _local_rows(n_tokens, cfg) < 16 * cfg.n_held


def row_tile(n_tokens: int, cfg: ExpertsConfig) -> int:
    """Rows of one visit of the experts' kernel: `ROW_TILE` where the
    rows are `few_a_group`, else what the pass's rows call for
    (ops/grouped_swiglu.py `visit_rows`)."""
    return ROW_TILE if few_a_group(n_tokens, cfg) \
        else visit_rows(min(_local_rows(n_tokens, cfg), cfg.tile_rows))


def tile_rows(n_tokens: int, cfg: ExpertsConfig) -> int:
    """Rows of grouped order one pass of `routed_experts` takes:
    `_local_rows`, so that a prefill's local rows fit one pass and the
    held weights are read once a layer, in whole row tiles (`row_tile`),
    at most ``cfg.tile_rows`` (one tile at the least).

    Where the rows are `few_a_group`, every touched group begins on a
    row tile of its own: the pass holds `ROW_TILE` - 1 rows more for
    each group that can have a row.  Else no row is added: the groups
    begin where they begin."""
    rows, tm = _local_rows(n_tokens, cfg), row_tile(n_tokens, cfg)
    if few_a_group(n_tokens, cfg):
        rows += (ROW_TILE - 1) * min(cfg.n_held, rows)
    return max(min(-(-rows // tm), cfg.tile_rows // tm), 1) * tm


def _sorted_whole(p, x, local, w, counts, cfg: ExpertsConfig, layer):
    """The routed sum through XLA alone, static all through, so it
    differentiates: the N * top_k assignments sorted so that those on
    held experts come first by expert, their tokens' rows gathered, ONE
    grouped matmul over all of them, a scatter-add back."""
    N, d = x.shape
    K, g = cfg.top_k, cfg.n_held
    flat = local.reshape(N * K)
    order = jnp.argsort(flat, stable=True)      # held first, by expert
    tok = (order // K).astype(jnp.int32)        # the row each came from
    wt = jnp.where(flat[order] < g, w.reshape(N * K)[order], 0.0)
    out = _grouped(x.astype(cfg.dtype)[tok], p, counts, cfg.dtype, layer)
    return jnp.zeros((N, d), jnp.float32).at[tok].add(out * wt[:, None])


def _walked(p, x, local, w, counts, cfg: ExpertsConfig, layer, base):
    """The routed sum onto `base` with no sort, gather or scatter
    (ops/moe_dispatch.py): the local assignments' rows are copied into
    grouped order by a walk over the tokens, `tile_rows` of them go
    through the experts, and the same walk adds each result row, times
    its weight, onto its token's.  As many passes as the local rows
    fill: one, but under an imbalance `tile_rows`' margin does not
    cover; the bound is the assignments themselves.

    Where the rows are `few_a_group`, grouped order leaves room: every
    group begins on a whole row tile (the walks take the groups'
    `starts` as they are given), and the rows between belong to nobody
    (`_fused`).  `R` is whole row tiles, so a pass begins on one."""
    N, _ = x.shape
    R, tm = tile_rows(N, cfg), row_tile(N, cfg)
    few = few_a_group(N, cfg)
    room = row_tiles(counts) * ROW_TILE if few else counts
    starts = jnp.cumsum(room) - room
    ends = starts + counts
    # the chip runs the kernels; elsewhere their `jnp` references, the
    # parity oracle (tests/test_experts.py steers the kernels in, in the
    # Pallas interpreter)
    dispatch, combine, fused = (moe_dispatch, moe_combine, _fused) \
        if jax.default_backend() == "tpu" \
        else (dispatch_reference, combine_reference, fused_reference)
    x = x.astype(jnp.float32)

    # (a loop's body names its scope again: it is lowered as a function
    # of its own, kimi_k2_decode.attend_blockwise)
    @jax.named_scope(scopes.MOE_EXPERTS)
    def tile(i, y):
        lo = i * R
        xs = dispatch(x, local, starts, lo, rows=R)
        sizes = jnp.clip(ends - lo, 0, R) - jnp.clip(starts - lo, 0, R)
        out = fused(xs, p, sizes, cfg.dtype, layer, tm, few)
        return combine(y, out, local, w, starts, lo)

    last = jnp.max(jnp.where(counts > 0, ends, 0))
    return lax.fori_loop(0, (last + R - 1) // R, tile, base)


@jax.named_scope(scopes.MOE_EXPERTS)
def routed_experts(p, x, chosen, w, cfg: ExpertsConfig, valid=None,
                   tiled: bool = True, layer=None, base=None):
    """The held experts' part of the routed sum, added to `base`.

    x (N, d), the compute dtype or the float32 it is rounded from;
    chosen, w (N, top_k) as `route` gives them; valid (N,) bool or
    None: rows that hold no token (a prefill's pads, a decode pool's
    idle rows) are routed nowhere; base (N, d) float32 or None (zeros):
    what the sum starts from, the shared experts' part.  `tiled` True
    is the serving path (`_walked`: Pallas kernels around the grouped
    matmuls), False the one that differentiates (`_sorted_whole`; the
    training forward, and what the other is tested against).  `layer`
    says that p is a stack over layers and which of them this is
    (`_grouped`).

    Returns (y (N, d) float32, stats (len(STATS),) float32)."""
    N, d = x.shape
    g = cfg.n_held
    # a choice's place among the held, g for an expert elsewhere: by
    # comparison with the g held ids, not by a table looked up
    # N * top_k times
    hit = chosen[:, :, None] == np.asarray(cfg.held_ids, np.int32)
    if valid is not None:
        hit = hit & valid[:, None, None]
    local = g + jnp.sum(jnp.where(hit, np.arange(g, dtype=np.int32) - g, 0),
                        axis=-1, dtype=jnp.int32)               # (N, K)
    counts = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)         # (g,)
    if tiled:
        y = _walked(p, x, local, w, counts, cfg, layer,
                    jnp.zeros((N, d), jnp.float32) if base is None
                    else base)
    else:
        y = _sorted_whole(p, x, local, w, counts, cfg, layer)
        y = y if base is None else y + base
    load = counts.astype(jnp.float32)
    mean = jnp.sum(load) / g
    stats = jnp.stack([
        jnp.sum(load),
        jnp.sum(counts > 0).astype(jnp.float32),
        jnp.where(mean > 0, jnp.max(load) / jnp.maximum(mean, 1e-9), 0.0),
        jnp.sum(visits(counts, row_tile(N, cfg),
                       few_a_group(N, cfg))).astype(jnp.float32)])
    return y, stats


def program_counters(cfg: ExpertsConfig, stats):
    """One program's `decode_common.EXPERT_COUNTERS` from its expert
    layers' stats (layers, len(STATS)), or None for a program without
    an expert layer."""
    if stats is None:
        return jnp.asarray([cfg.n_held, cfg.n_routed, 0, 0, 0, 0],
                           jnp.float32)
    return jnp.stack([
        jnp.float32(cfg.n_held), jnp.float32(cfg.n_routed),
        jnp.sum(stats[:, 0]), jnp.mean(stats[:, 1]) / cfg.n_held,
        jnp.max(stats[:, 2]),
        jnp.sum(stats[:, 3]) / jnp.maximum(jnp.sum(stats[:, 1]), 1.0)])


def _with_counters(cache, cfg, stats):
    """`cache` with what this program's expert layers did, under
    `decode_common.EXPERTS`: `program_counters` of a family config's
    ``cfg.experts`` and the layers' `stats` (None, or no row, for a
    program without an expert layer), in the experts' scope."""
    if stats is not None and not stats.shape[0]:
        stats = None
    with jax.named_scope(scopes.MOE_EXPERTS):
        cache[EXPERTS] = program_counters(cfg.experts, stats)
    return cache


def moe_layer(p, x32, cfg: ExpertsConfig, valid=None, tiled: bool = True):
    """One expert layer on rows x32 (N, d) float32 (the block's normed
    input): routed part of the held experts plus the shared experts.
    ``p["experts"]`` is this layer's weights, or with ``p["layer"]`` (an
    int32 scalar) the stack of every layer's, left whole (`_grouped`).
    Returns (y (N, d) in ``cfg.dtype``, stats as `routed_experts`)."""
    chosen, w = route(p["router"], x32, cfg)
    base = shared_expert(p["shared"], x32.astype(cfg.dtype),
                         cfg).astype(jnp.float32) if cfg.n_shared else None
    y, stats = routed_experts(p["experts"], x32, chosen, w, cfg, valid,
                              tiled, p.get("layer"), base)
    with jax.named_scope(scopes.MOE_EXPERTS):       # the combine's cast
        return y.astype(cfg.dtype), stats
