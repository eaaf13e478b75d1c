"""The cache's description by layer reach leaves the families that were
there as they were: the tensors, shapes and dtypes `init_paged_cache`
builds for ``gpt2``, ``llama``, ``jamba`` and ``kimi_k2``, and what the
pager is told a block weighs, are the values of the tree before the
description came (PR 42's parent), written down here."""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import decode_common as dc
from ray_tpu.models import families
from ray_tpu.serve.kv_pager import BlockPager

SLOTS, BLOCKS, BS = 3, 24, 16
I32, F32, BF16 = "int32", "float32", "bfloat16"
#: family -> ({tensor: (shape, dtype)} of the paged cache of preset
#: ``nano``, bytes of one block, bytes of per-slot state)
PARENT = {
    "gpt2": ({"k": ((2, 24, 16, 2, 32), BF16), "v": ((2, 24, 16, 2, 32), BF16),
              "block_tables": ((3, 8), I32), "pos": ((3,), I32),
              "start": ((3,), I32)}, 8192, 0),
    "llama": ({"k": ((2, 24, 16, 1, 32), BF16),
               "v": ((2, 24, 16, 1, 32), BF16),
               "block_tables": ((3, 8), I32), "pos": ((3,), I32),
               "start": ((3,), I32)}, 4096, 0),
    "jamba": ({"k": ((1, 24, 16, 1, 32), BF16),
               "v": ((1, 24, 16, 1, 32), BF16),
               "conv": ((3, 3, 3, 128), BF16), "ssm": ((3, 3, 16, 128), F32),
               "snap_conv": ((3, 3, 3, 128), BF16),
               "snap_ssm": ((3, 3, 16, 128), F32),
               "block_tables": ((3, 8), I32), "pos": ((3,), I32),
               "start": ((3,), I32)}, 2048, 2 * (6912 + 73728)),
    "kimi_k2": ({"ckv": ((3, 24, 16, 32), BF16), "kpe": ((3, 24, 16, 8), BF16),
                 "block_tables": ((3, 8), I32), "pos": ((3,), I32),
                 # (six counters since PR 46: `row_tiles_per_touched`)
                 "start": ((3,), I32), "experts": ((6,), F32)}, 3840, 0),
}


def _cache(name):
    fam = families.family(name)
    cfg = fam.config("nano")
    return cfg, fam.init_paged_cache(cfg, SLOTS, num_blocks=BLOCKS,
                                     block_size=BS)


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_paged_cache_is_the_parents(name):
    tensors, _, _ = PARENT[name]
    _, cache = _cache(name)
    assert {k: (tuple(v.shape), str(v.dtype)) for k, v in cache.items()} \
        == tensors


@pytest.mark.parametrize("name", sorted(PARENT))
def test_a_block_and_the_state_weigh_what_they_did(name):
    _, block, state = PARENT[name]
    cfg, cache = _cache(name)
    assert dc.block_bytes(cache) == block
    assert dc.state_bytes(cache) == state
    pager = BlockPager(BLOCKS, BS, cfg.max_seq,
                       bytes_per_block=dc.block_bytes(cache))
    # a request reserves prompt + new tokens of the pool's layers, capped
    # at the context: every layer of these families lies in the pool
    assert pager.blocks_needed(40, 6) == 3
    assert pager.blocks_needed(40, 6, headroom=4) == 4
    assert pager.blocks_needed(cfg.max_seq, 6) == cfg.max_seq // BS
    reach = dc.cache_reach(cache)
    assert reach["window_bytes_per_slot"] == reach["window_rows"] == 0
    assert reach["pool_bytes_per_token"] * BS == block \
        == reach["full_reach_bytes_per_token"] * BS


@pytest.mark.parametrize("name,kind,slot_state", [
    ("gpt2", families.KV, False), ("llama", families.KV, False),
    ("jamba", families.RECURRENT, True), ("kimi_k2", families.LATENT, False),
    ("laguna", families.WINDOWED, True)])
def test_what_a_familys_cache_holds(name, kind, slot_state):
    assert families.cache_kind(name) == kind
    assert (kind in families.PER_SLOT_STATE) == slot_state


def test_restore_state_moves_whatever_state_the_cache_keeps():
    """A snapshot entry into a slot, for a recurrent state and for a
    window layer's rings alike; nothing else is touched."""
    for name, state in (("jamba", ("conv", "ssm")),
                        ("laguna", ("wk", "wv"))):
        _, cache = _cache(name)
        cache = {k: jnp.full(v.shape, 7, v.dtype)
                 if k.startswith("snap_") else v for k, v in cache.items()}
        out = jax.jit(dc.restore_state)(cache, jnp.int32(2), jnp.int32(1))
        for n in state:
            axis = dc._TENSORS[n][0]
            rows = jnp.moveaxis(out[n], axis, 0)
            assert float(rows[1].min()) == 7 and float(rows[0].max()) == 0
        assert set(out) == set(cache)
