"""kv_tier — tiered host-RAM KV cache (the second-chance store under
`BlockPager`'s LRU eviction).

HBM holds the hot working set of paged KV blocks; this module is the
warm tier behind it.  When the pager's LRU eviction claims a
registered prefix block, the engine copies that block's K/V rows
device→host and `put()`s them here under the SAME content-addressed
token-tuple key the prefix index uses — eviction becomes a D2H copy
instead of an erasure.  On a later admission whose HBM prefix match
falls short, the pager probes this store second-chance
(`BlockPager.tier_lookup`): a hit means the engine allocates fresh
block rows, installs the host copy via one H2D copy + block-table
splice, and bumps ``prefix_len`` so ``paged_prefill`` skips those
tokens exactly as it does for HBM-resident prefixes.  Content
addressing makes the restore bit-identical to a re-prefill by
construction — same tokens, same K/V rows — so outputs stay
bit-identical to the dense one-shot oracle.

The same move the Ray object store makes for objects (spill cold data
to a cheaper tier, restore on demand rather than recompute), applied
to KV blocks: the effective prefix cache grows far beyond HBM and a
re-admitted prefix costs one H2D copy instead of a full re-prefill
(kvscope's ``reprefill_waste_tokens`` is exactly the compute this
saves).

Division of labor:

  * the TIER (this module) is a byte-budgeted, LRU-evicting host
    store — pure bookkeeping over numpy arrays, no device access,
    no clocks (graftcheck's `wallclock-in-telemetry` rule covers this
    file; the engine feeds measured copy seconds into
    ``note_h2d``/``note_d2h``, trainwatch-style);
  * the PAGER decides WHEN to spill (its eviction path) and WHAT to
    restore (its second-chance lookup), and keeps the scope/journal
    accounting honest — a tier restore books ``tier_hits`` /
    ``tokens_restored``, never ``reprefill_waste_tokens``;
  * the ENGINE owns every device copy: its block-saver callback
    gathers a block's K/V rows to host at spill time, and its jitted
    ``install_blocks`` program splices a restored chain back into the
    pool in one fixed-shape dispatch (on sharded engines the H2D
    transfer re-distributes the replicated host rows under the
    cache's shardings).
"""

from __future__ import annotations

import collections
from typing import Dict, Optional, Tuple

import numpy as np

__all__ = ["HostKVTier", "empty_kv_tier", "staging_buffers"]


def staging_buffers(maxn: int, row_shape: Tuple[int, ...],
                    dtype) -> Tuple[np.ndarray, np.ndarray,
                                    np.ndarray]:
    """Persistent host staging triple ``(ids, k_rows, v_rows)`` for
    fixed-shape block-splice dispatches: the tier restore path and the
    disaggregated handoff's staged D2H→H2D hop (serve/engine.py) both
    refill these in place per transfer instead of re-allocating pad
    arrays.  ``maxn`` is the id-vector length (max_seq // block_size)
    and ``row_shape`` the stacked per-block row shape the engine's
    install program expects."""
    return (np.zeros((maxn,), np.int32),
            np.zeros(row_shape, dtype),
            np.zeros(row_shape, dtype))

#: one stored block: per-layer K rows, per-layer V rows (host numpy,
#: shape (n_layer, block_size, kv_heads, head_dim)), byte footprint
Entry = Dict[str, object]


class HostKVTier:
    """Byte-budgeted LRU host store of evicted KV blocks, keyed by
    the pager's content-addressed prefix keys (exact token tuples —
    no hash collisions, so a restored block can never be wrong
    content).

    ``put`` spills one block (evicting least-recently-used entries
    until the budget fits; an entry larger than the whole budget is
    dropped on the floor rather than thrashing the store), ``take``
    is the counted second-chance probe, and the ``note_*`` hooks
    absorb engine-measured copy seconds so ``stats()`` can report
    h2d/d2h cost without this module ever reading a clock.
    """

    def __init__(self, bytes_budget: int):
        if int(bytes_budget) <= 0:
            raise ValueError(
                f"bytes_budget={bytes_budget} must be positive")
        self.bytes_budget = int(bytes_budget)
        #: key -> {"k": np, "v": np, "bytes": int}; insertion order ==
        #: LRU order (put/take both move-to-end)
        self._store: "collections.OrderedDict[Tuple[int, ...], Entry]" \
            = collections.OrderedDict()
        self.bytes_resident = 0
        self.hits = 0          # take() probes that found the key
        self.misses = 0        # take() probes that came up empty
        self.saves = 0         # blocks spilled in (D2H copies)
        self.evictions = 0     # entries LRU-dropped to fit the budget
        self.tokens_restored = 0  # token slots re-admitted via H2D
        # engine-fed copy time (seconds accumulate, stats reports ms)
        self._h2d_s = 0.0
        self._d2h_s = 0.0

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, key: Tuple[int, ...]) -> bool:
        return key in self._store

    # -- spill / restore -----------------------------------------------

    def put(self, key: Tuple[int, ...], k_rows, v_rows) -> int:
        """Spill one evicted block's host K/V rows under `key`.
        Returns the bytes now resident for the key (0 when the entry
        alone exceeds the whole budget and was skipped).  Re-putting a
        resident key refreshes its rows and its LRU position."""
        nbytes = int(k_rows.nbytes) + int(v_rows.nbytes)
        if nbytes > self.bytes_budget:
            return 0
        old = self._store.pop(key, None)
        if old is not None:
            self.bytes_resident -= int(old["bytes"])
        while self._store and \
                self.bytes_resident + nbytes > self.bytes_budget:
            _, victim = self._store.popitem(last=False)   # LRU
            self.bytes_resident -= int(victim["bytes"])
            self.evictions += 1
        self._store[key] = {"k": k_rows, "v": v_rows, "bytes": nbytes}
        self.bytes_resident += nbytes
        self.saves += 1
        return nbytes

    def refresh(self, key: Tuple[int, ...]) -> int:
        """LRU-touch `key` if resident; returns its byte footprint
        (0 when absent).  The pager's eviction path calls this FIRST:
        content addressing makes the rows under a key immutable, so
        when the key is already resident the D2H gather would copy
        bit-identical bytes — the spill becomes a free LRU refresh.
        Not a probe (take() counts hit/miss) and not a save (no copy
        happened), so the counters stay honest."""
        if key not in self._store:
            return 0
        self._store.move_to_end(key)
        return int(self._store[key]["bytes"])

    def take(self, key: Tuple[int, ...]) -> Optional[Entry]:
        """Second-chance probe: the entry for `key`, or None.  A hit
        refreshes the entry's LRU position but KEEPS it resident —
        the tier is a cache, and the same prefix can be evicted from
        HBM and restored again later."""
        entry = self._store.get(key)
        if entry is None:
            self.misses += 1
            return None
        self._store.move_to_end(key)
        self.hits += 1
        return entry

    # -- engine-fed accounting -----------------------------------------

    def note_restored(self, tokens: int) -> None:
        """The pager registered tier-restored blocks covering
        `tokens` token slots — prefill work the tier just saved."""
        self.tokens_restored += int(tokens)

    def note_h2d(self, seconds: float) -> None:
        """Engine-measured restore (host→device install) seconds."""
        self._h2d_s += max(0.0, float(seconds))

    def note_d2h(self, seconds: float) -> None:
        """Engine-measured spill (device→host gather) seconds."""
        self._d2h_s += max(0.0, float(seconds))

    # -- introspection -------------------------------------------------

    def stats(self) -> Dict[str, object]:
        """The ``kv_tier`` block of ``engine_stats()`` (shape pinned
        by test_engine_stats_schema; `empty_kv_tier` is the zeroed
        twin engines without a tier report)."""
        probes = self.hits + self.misses
        return {
            "enabled": True,
            "bytes_budget": self.bytes_budget,
            "bytes_resident": self.bytes_resident,
            "entries": len(self._store),
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": round(self.hits / probes, 4) if probes
            else 0.0,
            "saves": self.saves,
            "evictions": self.evictions,
            "tokens_restored": self.tokens_restored,
            "h2d_ms": round(self._h2d_s * 1e3, 3),
            "d2h_ms": round(self._d2h_s * 1e3, 3),
        }


def empty_kv_tier() -> Dict[str, object]:
    """The stable zero-shaped ``kv_tier`` block engines WITHOUT a
    host tier report (dense layouts, paged with the knob unset) —
    same keys as a live tier so dashboards, fleet pooling, and the
    golden-schema test never branch on configuration."""
    return {
        "enabled": False,
        "bytes_budget": 0,
        "bytes_resident": 0,
        "entries": 0,
        "hits": 0,
        "misses": 0,
        "hit_rate": 0.0,
        "saves": 0,
        "evictions": 0,
        "tokens_restored": 0,
        "h2d_ms": 0.0,
        "d2h_ms": 0.0,
    }
