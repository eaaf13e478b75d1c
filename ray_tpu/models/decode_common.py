"""The generation machinery shared by every decoder family.

Every family's decode module (models/<family>_decode.py) supplies its
(init_cache_fn, prefill_fn, decode_step_fn) triple; this module owns
the family-neutral prefill dispatch + sampling scan (`generator`) and
what the cache contract below makes common to them, so fixes land once.
The families whose cache is plain K/V share their programs too
(kv_decode.py).

Cache contract (vector positions, round 7 — ragged batches decode
together):

  k, v  : (L, B, S, ...) preallocated at cfg.max_seq
  pos   : (B,) int32 — next cache slot each sequence writes
  start : (B,) int32 — first valid slot (the left-pad offset); the
          LOGICAL position of the token at slot s is s - start[b], so
          the next token's wpe/RoPE index is pos[b] - start[b]

The per-slot attention mask is derived, not stored: slot s is
attendable for row b iff start[b] <= s <= pos[b] (after the current
token's K/V lands at slot pos[b]).  Equal-length prompts are the
degenerate case start == 0.

Paged layout (round 8 — block-paged KV with prefix reuse): instead of
dense per-row (L, B, S, ...) cache tensors, K/V live in a shared pool
of fixed-size blocks

  k, v          : (L, num_blocks, block_size, ...) preallocated pool
  block_tables  : (B, S // block_size) int32 — row b's j-th table entry
                  names the pool block holding slots
                  [j*block_size, (j+1)*block_size); block 0 is the
                  reserved null/trash block (never allocated, absorbs
                  masked pad writes)
  pos, start    : unchanged

The jitted programs read the cache through a gather by block id (one
layer at a time inside the layer scan — never the whole dense cache at
once) and write their new K/V at (block_tables[b, slot//bs],
slot % bs), into the pool where it lies (`PagedKV` below: the layer
scan never hands the pool back as its stacked output).  Because table
entries are kept in sequence order, the gathered view is
value-identical to the dense layout, so attention numerics are
bit-identical between layouts — the dense path stays the parity oracle
(same pattern as prefill_impl="scan").  Host-side block allocation /
refcounting / prefix hashing lives in ray_tpu/serve/kv_pager.py.

A second kind of state (models/jamba_decode.py): a family with recurrent
layers keeps, beside K/V and per SEQUENCE, not per token, a state per
such layer (``conv``, ``ssm``) and in the paged layout a snapshot pool of
it (``snap_conv``, ``snap_ssm``).  Its paged prefill takes one more
argument, ``state`` int32 (3,) = [source, snapshot entry, snapshot
boundary]; the constants below are its vocabulary, and the engine's.

A third (models/kimi_k2_decode.py): latent attention caches per token
and layer no K and V per head but ONE latent ``ckv`` (kv_lora_rank
wide, after its norm) and ONE rotary key ``kpe`` shared by every head,
(L, B, S, width) dense and (L, num_blocks, block_size, width) paged.
They are positional as K and V are, so everything below that walks
"the K/V" walks `positional(cache)`: whichever of the pairs the cache
holds (or the triple: a family whose attention reads what a learned
indexer selects keeps the indexer's key a token, ``kidx``, as a third
per-position tensor in the same blocks, models/glm_dsa_decode.py).
Two tensors and not one 576 wide: compiled for the chip,
the 512-wide one keeps its rows whole in the tiles and is gathered and
scattered where it lies, where one of 576 (4.5 lane tiles) is stored
block-minor and re-laid a layer at a time, as the 64-wide one still is
(PERF.md, PR 32).

A fourth (models/laguna_decode.py): layers of two REACHES in one
cache.  A layer that attends the whole context keeps its K/V in the
paged pool, whose leading axis counts those layers alone (as a hybrid's
pool counts its attention layers); a layer that attends a bounded
window keeps, per SLOT and not per token, a ring of its last ``window``
K/V rows (``wk``, ``wv``: (window layers, B, window, width), row ``slot
mod window``, keys after rotary) and in the paged layout a snapshot
pool of it (``snap_wk``, ``snap_wv``): per-slot state as ``conv`` and
``ssm`` are, told to the paged prefill by the same ``state`` argument,
so a block reserves full-reach rows for the full layers alone.  K and V
of such a cache are FOLDED, (L, ..., n_kv_head * head_dim): a row is
whole lane tiles.  `cache_reach` says what a cache reserves by reach.

What a cache is made of (its keys, which axis of each tensor is the
slot or the block, what a block weighs) is written in this module and
nowhere else: the serving engine moves rows, blocks and state through
the cache operations below (`admit` ... `state_bytes`) and never
indexes the pytree itself.

A cache handed to a jitted ENGINE program (serve/engine_programs.py) is
consumed: those programs donate it, the result is the same buffers
updated, and the caller rebinds.  The functions here are pure; donation
is the caller's choice, and what makes `PagedKV` write in place.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models import families


#: a recurrent family's paged prefill, ``state[0]``: where the slot's
#: recurrent state starts (a value >= 0 is a snapshot entry)
STATE_FROM_ZERO, STATE_FROM_SLOT = -1, -2
#: ``state[1]``: this prefill leaves no snapshot
NO_SNAPSHOT = -1


# -- a recurrent family's per-slot state, a layer or a slot of it at a time --
#
# ``conv`` (layers, K-1, B, width) and ``ssm`` (layers, B, ...): the
# layer is axis 0 of both, the slot axis 2 of ``conv`` and axis 1 of
# ``ssm``; the snapshot pool has the same axes, an entry where the slot
# is.  `scope` is the family's name for its state in a profile
# (scopes.SSM_STATE, scopes.LINEAR_STATE): every accessor runs under it.

def layer_state(scope: str, conv, ssm, j):
    """Every row's (window, state) of recurrent layer `j`."""
    with jax.named_scope(scope):
        return (lax.dynamic_index_in_dim(conv, j, 0, keepdims=False),
                lax.dynamic_index_in_dim(ssm, j, 0, keepdims=False))


def set_layer_state(scope: str, conv, ssm, j, window, state):
    with jax.named_scope(scope):
        return (lax.dynamic_update_index_in_dim(conv, window, j, 0),
                lax.dynamic_update_index_in_dim(ssm, state, j, 0))


def layer_window(scope: str, conv, j):
    """Every row's window of recurrent layer `j`."""
    with jax.named_scope(scope):
        return lax.dynamic_index_in_dim(conv, j, 0, keepdims=False)


def set_layer_window(scope: str, conv, j, window):
    with jax.named_scope(scope):
        return lax.dynamic_update_index_in_dim(conv, window, j, 0)


def slot_rows(scope: str, conv, ssm, row):
    """Row `row`'s (windows, states) of every recurrent layer: (layers,
    K-1, 1, width), (layers, 1, ...)."""
    with jax.named_scope(scope):
        return (lax.dynamic_slice_in_dim(conv, row, 1, axis=2),
                lax.dynamic_slice_in_dim(ssm, row, 1, axis=1))


def land_rows(scope: str, conv, ssm, row, windows, states):
    with jax.named_scope(scope):
        return (lax.dynamic_update_slice_in_dim(conv, windows, row, 2),
                lax.dynamic_update_slice_in_dim(ssm, states, row, 1))


def stacked(pairs):
    """A list [(window, state) a layer] -> (windows, states) stacked
    over the layers; a TUPLE is a pair of stacks already and is
    itself (a family whose walk carries the stacks)."""
    if isinstance(pairs, tuple):
        return pairs
    return tuple(jnp.stack(part) for part in zip(*pairs))


def begin_rows(scope: str, cache, slot, source):
    """The (windows, states) a paged prefill's slot starts from, every
    recurrent layer's, by ``state[0]`` = `source`: zeros
    (`STATE_FROM_ZERO`), the slot's own rows (`STATE_FROM_SLOT`: the
    previous chunk of this prompt left them) or snapshot entry ``source
    >= 0``.  The slot's rows leave the big state ONCE, here, before the
    walk, and go back once after it (`leave_rows`), as `PagedKV` lands a
    decode step's rows: the walk carries the slot's own (layers, ...)
    rows and the snapshot's, a few MB.  A row written into the carried
    (layers, slots, ...) state layer by layer made the compiler copy
    the whole state every layer (1.4 ms each on the chip; PERF.md, PR
    28)."""
    own = slot_rows(scope, cache["conv"], cache["ssm"], slot)
    held = slot_rows(scope, cache[_SNAP + "conv"], cache[_SNAP + "ssm"],
                     jnp.maximum(source, 0))
    with jax.named_scope(scope):
        return tuple(
            jnp.where(source >= 0, h,
                      jnp.where(source == STATE_FROM_SLOT, o,
                                jnp.zeros_like(o)))
            for o, h in zip(own, held))


def leave_rows(scope: str, cache, slot, ends, entry, keep, snaps):
    """What a paged prefill leaves of the recurrent state, as the four
    cache entries it writes: `ends` in row `slot`, and `snaps` in
    snapshot entry `keep` (``max(entry, 0)``) where ``state[1]`` =
    `entry` names one; without a snapshot to leave, entry `keep` gets
    back what it has.  `ends`, `snaps`: as `stacked` takes them."""
    out = {}
    out["conv"], out["ssm"] = land_rows(
        scope, cache["conv"], cache["ssm"], slot, *stacked(ends))
    pool = cache[_SNAP + "conv"], cache[_SNAP + "ssm"]
    kept = slot_rows(scope, *pool, keep)
    with jax.named_scope(scope):
        left = tuple(jnp.where(entry >= 0, new, old)
                     for new, old in zip(stacked(snaps), kept))
    out[_SNAP + "conv"], out[_SNAP + "ssm"] = land_rows(
        scope, *pool, keep, *left)
    return out


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Jit-static sampling knobs (round 11).

    Frozen + hashable on purpose: the serve engine keys its compiled
    program cache on this object, so two engines (or two requests)
    with different knobs can never alias one stale XLA program.
    top_k=0 disables the top-k filter; top_p=1.0 disables nucleus
    filtering; temperature 0 is greedy (filters become no-ops since
    argmax of a superset equals argmax of the kept set's union with
    -inf tails).
    """
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0

    def __post_init__(self):
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(
                f"top_p must be in (0, 1], got {self.top_p}")


def slot_mask(start: jnp.ndarray, end: jnp.ndarray,
              max_seq: int) -> jnp.ndarray:
    """(B, S) bool — cache slots holding attendable K/V per row:
    start[b] <= s < end[b] (end exclusive)."""
    s = jnp.arange(max_seq)
    return (s[None, :] >= start[:, None]) & (s[None, :] < end[:, None])


def is_paged(cache) -> bool:
    """The cache pytree itself is the layout knob: a pool cache carries
    a block table, a dense cache doesn't.  Static under jit (pytree
    structure), so the python branch costs nothing."""
    return "block_tables" in cache


#: what a cache keeps PER POSITION: K and V per head, or latent
#: attention's latent and rotary key (one of each a token, no heads),
#: or those two and the key a learned indexer scores the position by
#: (models/glm_dsa_decode.py: written by every program, shared by a
#: prefix's blocks as the other two are, never read by the attention
#: itself)
_KV = ("k", "v")
_LATENT = ("ckv", "kpe")
_LATENT_INDEXED = (*_LATENT, "kidx")


def positional(cache):
    """The names of the cache's per-position tensors, in the order the
    family's programs hand their new rows over: ``("k", "v")``,
    ``("ckv", "kpe")`` or ``("ckv", "kpe", "kidx")``."""
    if _LATENT[0] not in cache:
        return _KV
    return _LATENT_INDEXED if _LATENT_INDEXED[-1] in cache else _LATENT


@jax.named_scope(scopes.KV_POOL)
def dense_layer_kv(cache, lidx):
    """Layer `lidx`'s per-position tensors (K and V, or latent and
    rotary key) out of the stacked DENSE cache (the parity oracle's
    layout; a paged pool goes through PagedKV)."""
    return tuple(lax.dynamic_index_in_dim(cache[name], lidx, axis=0,
                                          keepdims=False)
                 for name in positional(cache))


class PagedKV:
    """One program's use of the paged pool, for every decoder family.

    cache["k"] / cache["v"] are the WHOLE stacked pools
    (L, num_blocks, bs, H, hd) -- or, for a latent cache, cache["ckv"] /
    cache["kpe"], (L, num_blocks, bs, width): the class walks
    `positional(cache)` and every pool keeps its own trailing dims --;
    block_tables (B, max_blk) int32 names
    the rows the program attends over; slots (B, T) int32 is the cache
    slot each of the program's new K/V rows lands at (one decode token:
    pos[:, None]; a prefill's tail: one row of Tt columns).  A slot
    >= max_blk*bs is a masked write (a prefill's pad column, a verify
    position past max_seq): it goes to the null block 0 and is dropped
    from the view, so it can never land on a live slot.  Use:

        kv = PagedKV(cache, block_tables, slots)
        def body((x, lidx, pools), layer):
            ...
            pools, (ck, cv) = kv.attend(lidx, pools, k_new, v_new)
            ...
            return (x, lidx + 1, pools), (k_new, v_new)
        (x, _, pools), (ks, vs) = lax.scan(body, (x, 0, kv.pools), ...)
        cache = kv.commit(pools, ks, vs)

    `attend` slices layer lidx out of the pool and gathers the rows'
    blocks into the dense-equivalent (B, max_blk*bs, H, hd) views, with
    the new (B, T, H, hd) rows in them at their slots.  Table entries
    are sequence-ordered, so every attendable view[b, s] holds exactly
    what the dense cache holds at slot s (each active row's tail block
    is private, so only its own write can be in it); unattended slots
    carry other sequences' bytes, which the slot mask replaces with the
    same -1e30 the dense path writes: logits stay bit-identical.

    How the rows reach the pool follows from the program's shape, the
    static T.  One column a row (T == 1, a decode step): the pool is
    READ-ONLY in the scan, neither written nor stacked; the row goes
    into the gathered view, and `commit` lands the scan's stacked new
    rows with one dynamic_update_slice a row, every layer at once.  A
    block of columns a row (a prefill's tail, a verify block): `attend`
    writes them into its copy of the layer, gathers the views from that
    and writes the layer back into the carried pool; `commit` has
    nothing left to do.  Either way the pool is updated where it lies:
    with the cache donated to the jitted program no second pool exists,
    and the layer scan never stacks one as its `ys`.  (Why two ways,
    and what each costs on the chip: PERF.md, PR 26.)

    ``whole=True`` (the latent pool's programs) reads and writes the
    pool itself instead: a block of columns is scattered at (layer,
    block, offset) into the carried pool and the views are gathered at
    (layer, block table) out of it, so no copy of a layer is ever made;
    and one column a row is NOT put into the gathered view: the caller
    attends its new rows beside the view (kimi_k2.attend_absorbed
    ``fresh``), which saves the view's copy.  Only a pool whose rows
    are whole lane tiles (512 wide) is taken where it lies
    (``in_place``); a narrower one still goes by the layer.

    A third way to read the pool makes no view at all: on the chip the
    latent family's decode step does not call `attend` but hands the
    whole pool to a kernel that walks each row's block table over it,
    as far as the row's length reaches (kimi_k2_decode.attend_paged,
    ops/mla_paged_decode.py; PERF.md, PR 33).  The class is then the
    step's index arithmetic and its `commit`: the pool stays read-only
    in the scan and the rows land after it, as above.

    (Otherwise the layer is sliced out before the gather on purpose.  A TPU
    stores the pool with the block axis minor-most, the only order of
    this shape its tiles do not pad, and a gather or a scatter by block
    id needs the block axis major: given the whole pool, either makes
    the compiler re-lay ALL of it, padded, and the program no longer
    fits the chip.  Sliced, it re-lays one layer at a time;
    dynamic_update_slice works in any layout.)"""

    def __init__(self, cache, block_tables, slots, whole=False):
        self.cache = cache
        self.whole = whole
        self.block_tables = block_tables
        self.slots = slots
        self.names = positional(cache)
        self.L, _, self.bs = cache[self.names[0]].shape[:3]
        #: each pool's dims after (L, blocks, block): (H, hd) or (width,)
        self.tails = tuple(cache[n].shape[3:] for n in self.names)
        #: the pools read and written where they lie (`whole`): those
        #: whose rows are whole lane tiles.  A narrower one (a 64-wide
        #: rotary key) is stored block-minor, and a gather or scatter
        #: on all of it would re-lay all of it: it goes by the layer
        self.in_place = tuple(whole and t[-1] % 128 == 0
                              for t in self.tails)
        self.B, self.nb = block_tables.shape
        self.T = slots.shape[1]
        #: one column a row: the pool is read-only in the scan and
        #: `commit` writes the rows; more: `attend` writes layers back
        self.by_rows = self.T == 1
        self.rows = jnp.arange(self.B)[:, None]
        with jax.named_scope(scopes.KV_POOL):
            live = slots < self.nb * self.bs
            self.blk = jnp.where(
                live, block_tables[self.rows,
                                   jnp.minimum(slots // self.bs,
                                               self.nb - 1)], 0)
            self.off = jnp.where(live, slots % self.bs, 0)

    @property
    def pools(self):
        """What the layer scan carries: (K pool, V pool)."""
        return tuple(self.cache[name] for name in self.names)

    @jax.named_scope(scopes.KV_POOL)
    def attend(self, lidx, pools, *new_rows):
        """Inside the scan body: (pools, (ck, cv)) for layer `lidx`."""
        out, views = [], []
        for pool, new, tail, in_place in zip(pools, new_rows, self.tails,
                                             self.in_place):
            if in_place:
                # scatter and gather on the pool itself: no copy of a
                # layer is made (a 512-wide latent pool keeps its rows
                # whole in the chip's tiles, so neither re-lays it)
                if not self.by_rows:
                    pool = pool.at[lidx, self.blk, self.off].set(new)
                view = pool[lidx, self.block_tables]
            else:
                layer = lax.dynamic_index_in_dim(pool, lidx, 0,
                                                 keepdims=False)
                if not self.by_rows:
                    layer = layer.at[self.blk, self.off].set(new)
                    pool = lax.dynamic_update_index_in_dim(pool, layer,
                                                           lidx, 0)
                view = layer[self.block_tables]  # (B,max_blk,bs,H,hd)
            view = view.reshape(self.B, self.nb * self.bs, *tail)
            if self.by_rows and not self.whole:
                # into the gathered copy, not the layer's
                view = view.at[self.rows, self.slots].set(new,
                                                         mode="drop")
            out.append(pool)
            views.append(view)
        return tuple(out), tuple(views)

    @jax.named_scope(scopes.KV_POOL)
    def commit(self, pools, *stacked):
        """After the scan: the cache with the program's new rows in
        the pool (stacked: the scan's (L, B, T, H, hd) of K and of V,
        or (L, B, T, width) of latent and rotary key)."""
        if self.by_rows:
            def one(b, pools):
                out = []
                for pool, new, tail in zip(pools, stacked, self.tails):
                    zeros = (0,) * len(tail)
                    row = lax.dynamic_slice(
                        new, (0, b, 0, *zeros), (self.L, 1, 1, *tail))
                    out.append(lax.dynamic_update_slice(
                        pool, row,
                        (0, self.blk[b, 0], self.off[b, 0], *zeros)))
                return tuple(out)

            pools = lax.fori_loop(0, self.B, one, pools)
        return dict(self.cache, **dict(zip(self.names, pools)))


_HEADS = (None, None, None, "heads", "head_dim")
#: every tensor a cache may hold beside its position vectors and block
#: tables: the axis its slots lie on (dense K/V rows and state rows; a
#: paged pool has its BLOCKS there), and the logical axes it shards by
#: (None: replicated, a recurrent state has no sharding rule yet).  The
#: heads axis sits at index 3 in BOTH K/V layouts, dense
#: (L, B, S, H, hd) and paged (L, num_blocks, bs, H, hd), so one
#: annotation serves both, and under DECODE_RULES only that dim splits
#: (over `tensor`).  A state tensor's snapshot pool is "snap_" + name,
#: same axes, one entry a slot.
_TENSORS = {"k": (1, _HEADS), "v": (1, _HEADS),
            "ckv": (1, None), "kpe": (1, None), "kidx": (1, None),
            "ssm": (1, None), "conv": (2, None),
            "wk": (1, None), "wv": (1, None)}
#: what a cache may keep per SLOT beside its per-position tensors: a
#: recurrent layer's state, a window layer's ring of K/V rows
_STATE = ("ssm", "conv", "wk", "wv")
#: the rings among them: (window layers, slots, window, width)
_WINDOW = ("wk", "wv")
_SNAP = "snap_"


def cache_logical_axes(cache):
    """Logical-axis pytree matching a decode cache, dense or paged.
    pos/start/block_tables stay replicated: they are the host
    scheduler's view of the pool and must be readable without
    collectives."""
    def axes(name):
        _, logical = _TENSORS.get(name.removeprefix(_SNAP), (None, None))
        if logical is None or len(logical) != cache[name].ndim:
            return (None,) * cache[name].ndim   # folded K/V: no heads axis
        return logical
    return {name: axes(name) for name in cache}


def cache_shardings(cache, mesh, rules=None):
    """NamedSharding pytree for a cache on `mesh` (shape-guarded, so
    a KV-head count that doesn't divide the tensor degree replicates
    instead of erroring — llama nano GQA with one KV head)."""
    from ray_tpu.parallel.sharding import (DECODE_RULES,
                                           shardings_by_shape)
    return shardings_by_shape(cache, cache_logical_axes(cache), mesh,
                              rules if rules is not None
                              else DECODE_RULES)


def shard_cache(cache, mesh, rules=None):
    """Commit an existing cache's leaves to the mesh (device_put).
    Used when re-laying an already-populated cache; fresh caches
    should go through partitioned_cache_init instead so the full pool
    never materialises on one chip."""
    return jax.device_put(cache, cache_shardings(cache, mesh, rules))


def partitioned_cache_init(build_fn, mesh, rules=None):
    """Materialise a zeros cache directly in partitioned form:
    eval_shape the builder, derive guarded shardings, then jit it with
    out_shardings so each chip allocates only its own KV-pool shard.
    A 7B-class pool born this way never exists unsharded anywhere."""
    shapes = jax.eval_shape(build_fn)
    shardings = cache_shardings(shapes, mesh, rules)
    return jax.jit(build_fn, out_shardings=shardings)()


def dense_to_paged(cache, block_size: int):
    """Re-lay a dense cache into a fresh block pool (row-major block
    tables, block 0 reserved as the null block).  Pure reshape +
    concat — the pool holds byte-identical K/V, so paged decode
    continues a dense prefill exactly.  Used by generate_with's
    kv_layout="paged" path and the parity tests; the serve engine
    builds its pool through kv_pager instead."""
    names = positional(cache)
    L, B, S = cache[names[0]].shape[:3]
    if S % block_size:
        raise ValueError(f"max_seq={S} must be a multiple of "
                         f"block_size={block_size}")
    nb = S // block_size
    out = dict(cache)
    for name in names:
        tail = cache[name].shape[3:]
        pool = cache[name].reshape(L, B * nb, block_size, *tail)
        null = jnp.zeros((L, 1, block_size, *tail), pool.dtype)
        out[name] = jnp.concatenate([null, pool], axis=1)
    out["block_tables"] = (
        1 + jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb))
    return out


def copy_block(cache, src, dst):
    """Copy-on-write fork: duplicate pool block `src` into `dst` across
    every layer of both K and V, on device.  src/dst are dynamic int32
    scalars, so ONE jitted program serves every fork.  The pager calls
    this before a sequence writes into a block whose refcount > 1."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    out = dict(cache)
    for name in positional(cache):
        pool = cache[name]                 # (L, num_blocks, bs, ...)
        out[name] = pool.at[:, dst].set(pool[:, src])
    return out


def admit(pool, row, slot):
    """A prefilled one-sequence cache `row` into row `slot` of a DENSE
    pool (the engine's, or a draft model's): its K/V rows, a recurrent
    family's state rows beside them, its positions."""
    out = dict(pool)
    for name, (axis, _) in _TENSORS.items():
        if name in pool:
            out[name] = lax.dynamic_update_slice_in_dim(
                pool[name], row[name], slot, axis=axis)
    for name in ("pos", "start"):
        out[name] = lax.dynamic_update_slice_in_dim(
            pool[name], row[name], slot, axis=0)
    return out


def clear_row(cache, slot):
    """Retire a paged row: its table points at the null block, so the
    (masked, unread) writes of an idle row can never land in a block
    the pager has handed to someone else."""
    out = dict(cache)
    out["block_tables"] = cache["block_tables"].at[slot].set(0)
    out["pos"] = cache["pos"].at[slot].set(0)
    return out


def restore_state(cache, entry, slot):
    """Row `slot`'s recurrent state becomes snapshot `entry`'s, NOW: a
    chunked admission that hit a snapshot runs its chunks later, and
    the entry may be another prefix's by then."""
    out = dict(cache)
    for name in _STATE:
        if name not in cache:
            continue
        axis = _TENSORS[name][0]
        out[name] = lax.dynamic_update_slice_in_dim(
            cache[name], lax.dynamic_slice_in_dim(
                cache[_SNAP + name], entry, 1, axis=axis),
            slot, axis=axis)
    return out


def install_blocks(cache, blk_ids, k_stack, v_stack):
    """Splice block rows into the pool in ONE dispatch (a host-tier
    restore, serve/kv_tier.py; a handoff's arrival).  blk_ids is a
    fixed-length (max_seq // block_size) id vector and the stacks are
    (N, L, block_size, H, head_dim) rows (`block_rows`), so every
    splice shares one compiled program whatever the chain's length.
    Padding entries target the null block (id 0): block 0 is the masked
    write-sink idle rows already scribble into, so the pad write is
    harmless by the same contract.  On a sharded pool the committed
    cache shardings re-distribute the replicated rows."""
    out = dict(cache)
    for name, stack in zip(positional(cache), (k_stack, v_stack)):
        out[name] = cache[name].at[:, blk_ids].set(stack.swapaxes(0, 1))
    return out


def save_block(cache, blk):
    """One block's (K rows, V rows) out of the pool together: an
    eviction's spill costs one dispatch and one D2H transfer pair."""
    return tuple(cache[name][:, blk] for name in positional(cache))


def kv_handoff_export(cache, blk_ids):
    """The read twin of `install_blocks`: gather a finished prefill's
    block rows, (K stack, V stack) as `install_blocks` takes them, by
    the same fixed-length id vector.  Pad entries (id 0) gather the
    null block's garbage rows; they install back into the null block
    on the other side."""
    return tuple(cache[name][:, blk_ids].swapaxes(0, 1)
                 for name in positional(cache))


def kv_handoff_install(cache, blk_ids, k_stack, v_stack, slot, row_bt, pos):
    """`install_blocks`, and row `slot` pointed at the rows in the same
    dispatch: its block table, `pos` (the prompt's length, what
    paged_prefill leaves behind: prefix_len + n_tail) and start 0 like
    every paged admission.  The row is decode-ready the moment the
    program retires, and its first decode step reads exactly the rows
    the prefill replica wrote."""
    out = install_blocks(cache, blk_ids, k_stack, v_stack)
    out["block_tables"] = cache["block_tables"].at[slot].set(row_bt)
    out["pos"] = cache["pos"].at[slot].set(pos)
    out["start"] = cache["start"].at[slot].set(0)
    return out


def block_bytes(cache) -> int:
    """Bytes of one block of a paged cache (K and V, or latent and
    rotary key), over all the layers the pool holds (a hybrid's pool
    holds its attention layers only)."""
    return sum(cache[name].nbytes // cache[name].shape[1]
               for name in positional(cache))


def block_rows(cache, n: int) -> jax.ShapeDtypeStruct:
    """Shape and dtype of `n` blocks' rows of K (V's are the same), as
    `kv_handoff_export` returns and `install_blocks` takes them.  A
    latent cache's two tensors differ in width, so what moves rows of
    ONE shape (the host tier, the handoff) is refused for it at the
    options check (serve/llm.py)."""
    L, _, *row = cache["k"].shape
    return jax.ShapeDtypeStruct((n, L, *row), cache["k"].dtype)


def kv_shards(cache) -> int:
    """How many ways the K/V tensors' heads axis is split over the
    devices the cache lives on: 1 on one device, and where the head
    count does not divide the mesh's tensor degree (`cache_shardings`
    replicates it then); 1 for a latent cache, which has no heads."""
    if positional(cache) is not _KV:
        return 1
    k = cache["k"]
    return k.shape[3] // k.sharding.shard_shape(k.shape)[3]


def state_bytes(cache) -> int:
    """Bytes of the per-slot state a cache holds beside its K/V pool (a
    recurrent layer's, a window layer's ring): every slot's, and the
    snapshot pool's (0 for a family that keeps none)."""
    return sum(cache[name].nbytes
               for state in _STATE for name in (state, _SNAP + state)
               if name in cache)


def cache_reach(cache) -> dict:
    """What a paged cache reserves, by its layers' reach.  The pool's
    layers keep every position of a sequence: ``pool_bytes_per_token``,
    over all of them.  A window layer keeps ``window_rows`` rows a slot
    whatever the sequence's length: ``window_bytes_per_slot``, over all
    of them (0 rows and bytes for a cache without such layers).
    ``full_reach_bytes_per_token`` is what a token would weigh were
    every layer, the window layers too, kept at full reach."""
    per_token = sum(
        cache[n].nbytes // (cache[n].shape[1] * cache[n].shape[2])
        for n in positional(cache))
    rings = [cache[n] for n in _WINDOW if n in cache]
    per_slot = sum(r.nbytes // r.shape[1] for r in rings)
    rows = rings[0].shape[2] if rings else 0
    return {"pool_bytes_per_token": per_token,
            "window_bytes_per_slot": per_slot, "window_rows": rows,
            "full_reach_bytes_per_token":
                per_token + (per_slot // rows if rows else 0)}


#: a family with a sparse expert layer (models/experts.py) keeps under
#: this key what the routing of its LAST program did on this chip, a
#: float32 vector of `EXPERT_COUNTERS`: every program overwrites it, and
#: the engine's fused programs hand it out beside their tokens
#: (`program_counters`), so it lands at the fence the tokens land at
EXPERTS = "experts"
#: experts held by this chip, of how many routed; (token, expert)
#: assignments that fell on held experts, summed over the layers; held
#: experts with at least one token over the held, mean over layers;
#: the fullest held expert's tokens over the held experts' mean, worst
#: layer; the visits `ops/grouped_swiglu.py` makes the experts' rows
#: over the experts touched, summed over the layers (a decode wave: the
#: row tiles the rows fill, 1.0 unless an expert's overflow one; a
#: prefill: the tall tiles a group lies in, 1 + the edges it straddles)
EXPERT_COUNTERS = ("held", "of", "assignments_local",
                   "experts_touched_share", "load_max_over_mean",
                   "row_tiles_per_touched")


#: a family whose attention reads only what a learned indexer selects
#: (models/glm_dsa_decode.py) keeps under this key what the selection
#: did in its LAST program, a float32 vector of `INDEX_COUNTERS`,
#: overwritten and handed out as the experts' is
INDEX = "index"
#: positions the program's queries attended, summed over its rows (a
#: prefill's real columns) and layers; positions they could have
#: reached (every earlier one and their own): what attention over the
#: whole context would have read
INDEX_COUNTERS = ("index_selected", "index_reachable")


def program_counters(cache):
    """Every counter vector the last program left in the cache, by its
    key (`EXPERTS`, `INDEX`), or None for a family that keeps none."""
    return {key: cache[key] for key in (EXPERTS, INDEX)
            if key in cache} or None


def _positions(batch: int, experts: bool = True):
    """What a fresh cache holds beside its tensors, all zeros: the
    position vectors and, for a family with expert layers, the
    counters."""
    held = {"pos": jnp.zeros((batch,), jnp.int32),
            "start": jnp.zeros((batch,), jnp.int32)}
    if experts:
        held[EXPERTS] = jnp.zeros((len(EXPERT_COUNTERS),), jnp.float32)
    return held


def _refuse_mesh(family: str, mesh) -> None:
    """A cache that is more than K/V has no sharding rule for what it
    holds beside them: its family's init functions refuse a mesh."""
    if mesh is not None:
        kind = families.cache_kind(family)
        raise ValueError(
            f"family {family!r} keeps a {kind} cache "
            f"({families.CACHE_HOLDS[kind]}) and has no sharding for it "
            f"yet: mesh-sharded caches are refused")


def _block_of(cfg, n: int) -> int:
    """The tile a prefill's blockwise attention walks `n` queries or
    keys by: ``cfg.attn_block`` where it divides `n`, else `n` whole."""
    return cfg.attn_block if n % cfg.attn_block == 0 else n


def make_vocab_tail_mask(cfg) -> Optional[jnp.ndarray]:
    """Static (padded_vocab,) bool mask, True on the real vocab — built
    ONCE per generation (or jitted serve program) so sampling is a
    single jnp.where instead of rebuilding a fill tensor and scattering
    it over the tail on every sampled token.  None when nothing is
    padded."""
    if cfg.padded_vocab == cfg.vocab_size:
        return None
    return jnp.arange(cfg.padded_vocab) < cfg.vocab_size


def _mask_to_top_k(logits, top_k: int):
    """Keep only entries >= the k-th largest per row (last axis); ties
    at the threshold all survive.  Any leading batch dims."""
    kth = lax.top_k(logits, top_k)[0][..., -1:]
    return jnp.where(logits >= kth, logits,
                     jnp.asarray(-1e30, logits.dtype))


def _mask_to_top_p(logits, top_p: float):
    """Nucleus filter over the last axis: keep the smallest
    descending-probability prefix whose mass reaches top_p.  A token
    is kept iff the mass STRICTLY BEFORE it is < top_p, so the top-1
    token always survives.  Works on logits already scaled by
    temperature (the nucleus is defined on the sampling
    distribution)."""
    order = jnp.argsort(-logits, axis=-1)
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    probs = jax.nn.softmax(sorted_logits.astype(jnp.float32), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < top_p
    inv = jnp.argsort(order, axis=-1)
    keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    return jnp.where(keep, logits, jnp.asarray(-1e30, logits.dtype))


def filter_logits(logits, temperature: float,
                  tail_mask: Optional[jnp.ndarray],
                  top_k: int = 0, top_p: float = 1.0):
    """Temperature-scale then apply the static tail/top-k/top-p masks;
    returns the filtered f32-safe logits the categorical (or the
    spec-decode accept test) draws from.  temperature must be > 0."""
    if tail_mask is not None:
        logits = jnp.where(tail_mask, logits,
                           jnp.asarray(-1e30, logits.dtype))
    scaled = logits / jnp.float32(temperature)
    if top_k > 0:
        scaled = _mask_to_top_k(scaled, top_k)
    if top_p < 1.0:
        scaled = _mask_to_top_p(scaled, top_p)
    return scaled


@jax.named_scope(scopes.SAMPLE)
def sample_token(logits, key, temperature: float,
                 tail_mask: Optional[jnp.ndarray],
                 top_k: int = 0, top_p: float = 1.0) -> jnp.ndarray:
    """(..., padded_vocab) logits → (...,) int32 token; the padded
    vocab tail can never be sampled.  temperature 0 = greedy (key and
    the filters are unused — argmax is filter-invariant).  top_k /
    top_p are jit-STATIC knobs (python ints/floats baked into the
    compiled program): top_k keeps the k most likely tokens, top_p
    keeps the smallest nucleus reaching that probability mass, both
    composed AFTER temperature scaling and with the tail mask
    preserved."""
    if temperature == 0.0:
        if tail_mask is not None:
            logits = jnp.where(tail_mask, logits,
                               jnp.asarray(-1e30, logits.dtype))
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = filter_logits(logits, temperature, tail_mask, top_k,
                           top_p)
    return jax.random.categorical(key, scaled).astype(jnp.int32)


def spec_accept(logits, block, key, temperature: float,
                tail_mask: Optional[jnp.ndarray],
                top_k: int = 0, top_p: float = 1.0,
                draft_probs=None):
    """Speculative accept/reject over one verify round (round 11).

    block (B, T=k+1) int32 is [cur, d_1..d_k] — the last sampled token
    followed by the draft's k proposals; logits (B, T, padded_vocab)
    is the target model's verify forward over exactly those positions,
    so logits[:, t] is the target's distribution for the token AFTER
    block[:, t].  Returns (out_tokens (B, T) int32, n_acc (B,) int32);
    row b emitted out_tokens[b, :n_acc[b] + 1] — the accepted draft
    prefix plus one target-sampled correction/bonus token, so every
    round nets at least one token and the greedy path is bit-identical
    to sequential argmax decoding.

    temperature 0: accept d_{t+1} iff it equals argmax(logits[:, t])
    cumulatively (deterministic, key unused).  temperature > 0:
    standard rejection sampling — accept with prob min(1, p/q) where q
    is draft_probs (B, k, V), the draft's post-filter sampling
    distribution, or a one-hot on the proposal when the draft supplies
    no distribution (n-gram draft); the correction token comes from
    the normalised residual max(p - q, 0), which degenerates to p for
    the all-accepted bonus position (q is zero-padded there).
    """
    B, T = block.shape
    k = T - 1
    drafts = block[:, 1:]                                   # (B, k)
    cols = jnp.arange(T)
    if temperature == 0.0:
        if tail_mask is not None:
            logits = jnp.where(tail_mask, logits,
                               jnp.asarray(-1e30, logits.dtype))
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, T)
        match = (drafts == g[:, :-1]).astype(jnp.int32)
        n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
        corr = jnp.take_along_axis(g, n_acc[:, None], axis=1)
    else:
        filt = filter_logits(logits, temperature, tail_mask, top_k,
                             top_p)
        p = jax.nn.softmax(filt.astype(jnp.float32), axis=-1)
        V = p.shape[-1]
        if draft_probs is None:
            q = jax.nn.one_hot(drafts, V, dtype=p.dtype)
        else:
            q = draft_probs.astype(p.dtype)
        u_key, s_key = jax.random.split(key)
        u = jax.random.uniform(u_key, (B, k))
        idx = drafts[..., None]
        p_d = jnp.take_along_axis(p[:, :k], idx, axis=-1)[..., 0]
        q_d = jnp.take_along_axis(q, idx, axis=-1)[..., 0]
        ratio = p_d / jnp.maximum(q_d, 1e-20)
        accept = (u < jnp.minimum(1.0, ratio)).astype(jnp.int32)
        n_acc = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)
        q_pad = jnp.concatenate(
            [q, jnp.zeros((B, 1, V), q.dtype)], axis=1)
        sel = n_acc[:, None, None]
        p_at = jnp.take_along_axis(p, jnp.broadcast_to(sel, (B, 1, V)),
                                   axis=1)[:, 0]             # (B, V)
        q_at = jnp.take_along_axis(q_pad,
                                   jnp.broadcast_to(sel, (B, 1, V)),
                                   axis=1)[:, 0]
        residual = jnp.maximum(p_at - q_at, 0.0)
        mass = jnp.sum(residual, axis=-1, keepdims=True)
        residual = jnp.where(mass > 0, residual / mass, p_at)
        corr = jax.random.categorical(
            s_key, jnp.log(residual + 1e-30))[:, None]
    drafts_pad = jnp.concatenate(
        [drafts, jnp.zeros((B, 1), drafts.dtype)], axis=1)
    out = jnp.where(cols[None, :] < n_acc[:, None], drafts_pad,
                    corr.astype(drafts_pad.dtype))
    return out.astype(jnp.int32), n_acc.astype(jnp.int32)


def make_spec_verify(verify_step_fn, cfg, temperature: float = 0.0,
                     top_k: int = 0, top_p: float = 1.0):
    """Compose a family's verify_step with spec_accept into the
    canonical spec-decode verify program: ONE target dispatch checks a
    whole draft block and advances pos by the tokens actually kept.

    Returned greedy signature: (params, cache, block, key) →
    (out_tokens, n_acc, cache); sampled adds a trailing draft_probs
    arg.  The cache's pos lands at old_pos + n_acc + 1 — the next
    write slot after the last EMITTED token's K/V (the correction
    token itself has no K/V yet, exactly like a freshly sampled token
    in the plain decode step).  K/V written for rejected draft
    positions sits at slots >= the new pos: never attendable under
    slot_mask, overwritten by later rounds — the dense rollback IS the
    pos rewind.  Paged caches need no block surgery either: every
    row's blocks are reserved for the full request at admission, so
    rejected writes land in row-private blocks (or the null block past
    max_seq) that the row still owns."""
    tail = make_vocab_tail_mask(cfg)
    if temperature == 0.0:
        def spec_verify(params, cache, block, key):
            logits, cache = verify_step_fn(params, cache, block, cfg)
            out, n_acc = spec_accept(logits, block, key, 0.0, tail)
            cache = dict(cache)
            cache["pos"] = cache["pos"] + n_acc + 1
            return out, n_acc, cache
        return spec_verify

    def spec_verify(params, cache, block, key, draft_probs=None):
        logits, cache = verify_step_fn(params, cache, block, cfg)
        out, n_acc = spec_accept(logits, block, key, temperature,
                                 tail, top_k, top_p, draft_probs)
        cache = dict(cache)
        cache["pos"] = cache["pos"] + n_acc + 1
        return out, n_acc, cache
    return spec_verify


def spec_rewind(cache, n_rejected):
    """Roll a cache back over rejected draft positions: pure per-row
    pos arithmetic (n_rejected (B,) int32).  The stale K/V needs no
    scrubbing — slot_mask derives attendability from pos, so rewound
    slots are invisible until overwritten."""
    out = dict(cache)
    out["pos"] = cache["pos"] - jnp.asarray(n_rejected, jnp.int32)
    return out


def make_draft_propose(decode_step_fn, cfg, k: int,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 1.0, with_probs: bool = False):
    """Build the jitted draft-side program for model-draft spec
    decode: rewind the draft cache over last round's rejections, then
    run k+1 chained draft decode steps in a scan — feeding
    [cur, d_1..d_k] so the final step writes d_k's K/V, which makes
    the rewind arithmetic uniform (the draft cache always holds K/V
    for every fed token; pos nets +n_acc+1 per round, mirroring the
    target).

    Returned signature: (params, cache, cur (B,), n_rejected (B,),
    key) → (drafts (B, k), cache) — or (drafts, probs (B, k, V),
    cache) when with_probs (the post-filter distribution each d_t was
    sampled from, required by sampled-mode spec_accept)."""
    if with_probs and temperature == 0.0:
        raise ValueError("with_probs requires temperature > 0 (greedy "
                         "spec_accept never consults draft_probs)")
    tail = make_vocab_tail_mask(cfg)

    def draft_propose(params, cache, cur, n_rejected, key):
        cache = spec_rewind(cache, n_rejected)

        def body(carry, kk):
            cache, tok = carry
            logits, cache = decode_step_fn(params, cache, tok, cfg)
            if temperature == 0.0:
                nxt = sample_token(logits, kk, 0.0, tail)
                probs = jnp.zeros((), jnp.float32)      # unused
            else:
                filt = filter_logits(logits, temperature, tail,
                                     top_k, top_p)
                probs = jax.nn.softmax(filt.astype(jnp.float32),
                                       axis=-1)
                nxt = jax.random.categorical(kk, filt).astype(
                    jnp.int32)
            return (cache, nxt), (nxt, probs)

        keys = jax.random.split(key, k)
        (cache, last), (drafts, probs) = lax.scan(
            body, (cache, cur), keys)
        # Extra (k+1)-th step: ingest d_k's K/V, logits discarded.
        _, cache = decode_step_fn(params, cache, last, cfg)
        drafts = drafts.T                               # (B, k)
        if with_probs:
            return drafts, jnp.swapaxes(probs, 0, 1), cache
        return drafts, cache
    return draft_propose


def ngram_propose(tokens, k: int, order: int = 2):
    """Host-side zero-weight draft: propose the k tokens that followed
    the most recent previous occurrence of the current trailing
    `order`-gram in this request's own history (prompt + emitted).
    Falls back to repeating the last token when no prior occurrence
    (or history shorter than the gram) exists — proposal quality only
    moves the acceptance rate, never correctness, because every
    proposal is target-verified."""
    toks = list(tokens)
    n = len(toks)
    fallback = [toks[-1]] * k if toks else [0] * k
    if n <= order:
        return fallback
    gram = toks[n - order:]
    for i in range(n - order - 1, -1, -1):
        if toks[i:i + order] == gram:
            cont = toks[i + order:i + order + k]
            if cont:
                return (cont + [cont[-1]] * (k - len(cont)))[:k]
            break
    return fallback


def generate_with(prefill_fn, decode_step_fn, params,
                  prompt: jnp.ndarray, cfg, *, max_new_tokens: int,
                  lengths: Optional[jnp.ndarray] = None,
                  temperature: float = 1.0,
                  top_k: int = 0, top_p: float = 1.0,
                  key: Optional[jax.Array] = None,
                  kv_layout: str = "dense",
                  kv_block_size: int = 16) -> jnp.ndarray:
    """The generation loop shared by every decoder family (a family's
    `generate` is `generator`'s): ONE batched prefill dispatch + a
    sampling scan over the family's decode_step.  prompt (B, T0) int32
    → (B, T0 + max_new_tokens) int32; `lengths` (B,) marks ragged LEFT-padded
    prompts (row b's real tokens occupy columns [T0 - lengths[b], T0));
    temperature 0 = greedy; top_k/top_p are jit-static sampling
    filters (see sample_token); the whole program jits (static cfg /
    max_new_tokens).  kv_layout="paged" re-lays the prefilled cache
    into kv_block_size blocks and decodes through the block-table
    gather/scatter path — the dense layout is its parity oracle."""
    B, T0 = prompt.shape
    if kv_layout not in ("dense", "paged"):
        raise ValueError(f"kv_layout must be 'dense' or 'paged', got "
                         f"{kv_layout!r}")
    if T0 + max_new_tokens > cfg.max_seq:
        # Past max_seq JAX clamps dynamic_update_slice/gather indices, so
        # KV writes would silently pile onto the last cache slot (and
        # position lookups would saturate) — error loudly instead.
        raise ValueError(
            f"prompt length {T0} + max_new_tokens {max_new_tokens} "
            f"exceeds cfg.max_seq={cfg.max_seq}")
    if key is None:
        key = jax.random.PRNGKey(0)
    tail_mask = make_vocab_tail_mask(cfg)
    last_logits, cache = prefill_fn(params, prompt, cfg,
                                    lengths=lengths)
    if kv_layout == "paged":
        cache = dense_to_paged(cache, kv_block_size)

    def gen_step(carry, k):
        cache, logits = carry
        tok = sample_token(logits, k, temperature, tail_mask,
                           top_k, top_p)
        new_logits, cache = decode_step_fn(params, cache, tok, cfg)
        return (cache, new_logits), tok

    keys = jax.random.split(key, max_new_tokens)
    (_, _), new_tokens = lax.scan(gen_step, (cache, last_logits), keys)
    return jnp.concatenate([prompt, new_tokens.T.astype(prompt.dtype)],
                           axis=1)


def generator(prefill_fn, decode_step_fn, init_cache_fn=None):
    """A family's public `generate`: `generate_with` over its
    `prefill_fn` and `decode_step_fn`, taking `generate_with`'s
    keywords.  A family that hands its `init_cache_fn` too can ingest
    an equal-length prompt by ``prefill_impl="scan"``, the per-token
    reference prefill: T0 sequential decode_step dispatches (the
    pre-round-7 path), kept as the numerics oracle for the batched
    prefill's parity tests (which it is not for a family whose step
    leaves an empty row alone: jamba_decode.py)."""
    def scan_prefill(params, prompt, cfg, *, lengths=None):
        if lengths is not None or init_cache_fn is None:
            raise ValueError("prefill_impl='scan' is the equal-length "
                             "reference path of a family that has one; "
                             "ragged prompts need the batched prefill")

        def prefill_step(cache, tok):
            logits, cache = decode_step_fn(params, cache, tok, cfg)
            return cache, logits

        cache, logits_seq = lax.scan(
            prefill_step, init_cache_fn(cfg, prompt.shape[0]), prompt.T)
        return logits_seq[-1], cache

    def generate(params, prompt, cfg, *, prefill_impl: str = "batched",
                 **how) -> jnp.ndarray:
        return generate_with(
            prefill_fn if prefill_impl == "batched" else scan_prefill,
            decode_step_fn, params, prompt, cfg, **how)

    generate.__doc__ = generate_with.__doc__
    return generate
