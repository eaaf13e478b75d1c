"""The KV pool is updated where it lies (decode_common.PagedKV +
donation in serve/llm.py).

Model layer: a paged program slices each layer out of the pool, puts
its new K/V rows where attention will read them and gathers the rows'
views; the pool itself is written in place, either row by row after
the layer scan (one column a row: a decode step; the pool is read-only
in the scan) or layer by layer inside it (a block of columns: a prefill's
tail, a verify block), never stacked as the scan's output.  Every case
below holds both routes to the dense layout, the parity oracle, for
both families: logits bit-identical where both layouts run one
program (decode, verify), the existing 1e-5 where the dense side is the
batched prefill, tokens identical everywhere, and the pool holding the
dense cache's K/V afterwards.

Engine: every program that takes the cache and returns it consumes it.
A session that walks the whole cache life cycle must never touch a
consumed buffer, and ``engine_stats()["programs"]`` must say the pool
was aliased, not copied."""

import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import (gpt2_config, gpt2_init, llama_config,
                            llama_init)  # noqa: E402
from ray_tpu.models import gpt2_decode, llama_decode  # noqa: E402
from ray_tpu.models.decode_common import (dense_to_paged,
                                          PagedKV)  # noqa: E402

BS = 16
FAMILIES = ["gpt2", "llama"]
_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}


def _family(name):
    if name == "gpt2":
        cfg = gpt2_config("nano", **_OVR)
        return dict(
            cfg=cfg, params=gpt2_init(jax.random.PRNGKey(0), cfg),
            prefill=gpt2_decode.prefill,
            paged_prefill=gpt2_decode.paged_prefill,
            step=gpt2_decode.decode_step,
            verify=gpt2_decode.verify_step,
            init_paged=gpt2_decode.init_paged_cache)
    cfg = llama_config("nano", dtype=jnp.float32, use_flash=False)
    return dict(
        cfg=cfg, params=llama_init(jax.random.PRNGKey(0), cfg),
        prefill=llama_decode.llama_prefill,
        paged_prefill=llama_decode.llama_paged_prefill,
        step=llama_decode.llama_decode_step,
        verify=llama_decode.llama_verify_step,
        init_paged=llama_decode.llama_init_paged_cache)


def _prompt(seed, n, vocab):
    return np.random.RandomState(seed).randint(2, vocab, n).astype(
        np.int32)


def _right_aligned(tokens, t_pad):
    out = np.zeros((1, t_pad), np.int32)
    out[0, t_pad - len(tokens):] = tokens
    return jnp.asarray(out)


def _row_view(cache, row_bt, name="k"):
    """(L, S, H, hd): a row's K (or V) as the dense layout holds it."""
    pool = np.asarray(cache[name])
    got = pool[:, np.asarray(row_bt)]
    return got.reshape(pool.shape[0], -1, *pool.shape[3:])


# ---------------------------------------------------------------------------
# decode and verify: one program per layout, logits bit-identical
# ---------------------------------------------------------------------------

def _ragged_dense_cache(f, lens=(9, 5)):
    """A dense cache primed with two left-padded rows of different
    length (so pos and start differ by row)."""
    cfg = f["cfg"]
    t0 = max(lens)
    toks = np.zeros((len(lens), t0), np.int32)
    for b, n in enumerate(lens):
        toks[b, t0 - n:] = _prompt(20 + b, n, cfg.vocab_size)
    _, cache = f["prefill"](f["params"], jnp.asarray(toks), cfg,
                            lengths=jnp.asarray(lens, jnp.int32))
    return cache


@pytest.mark.parametrize("family", FAMILIES)
def test_paged_decode_matches_dense_bitwise(family):
    f = _family(family)
    cfg, params = f["cfg"], f["params"]
    dense = _ragged_dense_cache(f)
    paged = dense_to_paged(dense, BS)
    tok = jnp.asarray([7, 11], jnp.int32)
    for _ in range(3):
        want, dense = f["step"](params, dense, tok, cfg)
        got, paged = f["step"](params, paged, tok, cfg)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        tok = jnp.argmax(want[:, :cfg.vocab_size], -1).astype(jnp.int32)
    # the pool holds what the dense cache holds, slot for slot
    np.testing.assert_array_equal(np.asarray(paged["pos"]),
                                  np.asarray(dense["pos"]))
    for name in ("k", "v"):
        for b in range(2):
            np.testing.assert_array_equal(
                _row_view(paged, paged["block_tables"][b], name),
                np.asarray(dense[name])[:, b])


@pytest.mark.parametrize("family", FAMILIES)
def test_paged_verify_matches_dense_bitwise(family):
    """k+1 tokens a row in one dispatch; the second row sits so close
    to max_seq that its last positions are masked writes (dropped by
    the view, null block in the pool)."""
    f = _family(family)
    cfg, params = f["cfg"], f["params"]
    dense = _ragged_dense_cache(f)
    dense["pos"] = dense["pos"].at[1].set(cfg.max_seq - 2)
    paged = dense_to_paged(dense, BS)
    block = jnp.asarray(np.random.RandomState(5).randint(
        2, cfg.vocab_size, (2, 4)), jnp.int32)
    want, dense_out = f["verify"](params, dense, block, cfg)
    got, paged_out = f["verify"](params, paged, block, cfg)
    # every position of the in-range row, and the in-range positions
    # of the row that runs off the end
    np.testing.assert_array_equal(np.asarray(got)[0],
                                  np.asarray(want)[0])
    np.testing.assert_array_equal(np.asarray(got)[1, :2],
                                  np.asarray(want)[1, :2])
    for name in ("k", "v"):
        for b in range(2):
            np.testing.assert_array_equal(
                _row_view(paged_out, paged["block_tables"][b], name),
                np.asarray(dense_out[name])[:, b])
    # pos is the caller's to move
    np.testing.assert_array_equal(np.asarray(paged_out["pos"]),
                                  np.asarray(paged["pos"]))


# ---------------------------------------------------------------------------
# paged prefill against the dense batched prefill
# ---------------------------------------------------------------------------

def _dense_reference(f, prompt):
    logits, cache = f["prefill"](f["params"], jnp.asarray(prompt[None]),
                                 f["cfg"],
                                 lengths=jnp.asarray([len(prompt)]))
    return np.asarray(logits)[0], cache


def _check_row_against_dense(cache, row_bt, dense, n):
    for name in ("k", "v"):
        np.testing.assert_allclose(
            _row_view(cache, row_bt, name)[:, :n],
            np.asarray(dense[name])[:, 0, :n], atol=1e-5)


@pytest.mark.parametrize("t_pad", [48, 64], ids=["pads15", "pads31"])
@pytest.mark.parametrize("family", FAMILIES)
def test_paged_prefill_with_pad_columns_matches_dense(family, t_pad):
    """A cold prefill in a bucket wider than the prompt: the pad
    columns (negative logical positions) are dropped by the view and
    land in the null block."""
    f = _family(family)
    cfg = f["cfg"]
    n = 33
    prompt = _prompt(2, n, cfg.vocab_size)
    want, dense = _dense_reference(f, prompt)
    nb = cfg.max_seq // BS
    cache = f["init_paged"](cfg, 1, num_blocks=1 + nb, block_size=BS)
    row_bt = jnp.arange(1, 1 + nb, dtype=jnp.int32)
    got, cache = f["paged_prefill"](
        f["params"], cache, _right_aligned(prompt, t_pad), cfg,
        row_bt=row_bt, prefix_len=np.int32(0), n_tail=np.int32(n),
        slot=np.int32(0))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    assert int(np.argmax(got)) == int(np.argmax(want))
    _check_row_against_dense(cache, row_bt, dense, n)
    # nothing was written past the prompt in the row's own blocks
    assert not _row_view(cache, row_bt)[:, n:].any()


@pytest.mark.parametrize("tail", [2, 13], ids=["tail2", "tail13"])
@pytest.mark.parametrize("family", FAMILIES)
def test_paged_prefill_prefix_hit_pads_never_alias_live_slots(family,
                                                              tail):
    """B's prompt extends two blocks A wrote.  B's tail sits right-
    aligned in a 16-wide bucket, so its pad columns carry logical
    positions 32 - pad .. 31: LIVE slots of the shared prefix.  A pad
    that landed there, in the pool or in the view, would corrupt A's
    blocks or B's logits."""
    f = _family(family)
    cfg, params = f["cfg"], f["params"]
    shared = _prompt(3, 32, cfg.vocab_size)
    a = np.concatenate([shared, _prompt(4, 3, cfg.vocab_size)])
    b = np.concatenate([shared, _prompt(5, tail, cfg.vocab_size)])
    nb = cfg.max_seq // BS
    cache = f["init_paged"](cfg, 2, num_blocks=1 + 2 * nb,
                            block_size=BS)
    bt_a = jnp.arange(1, 1 + nb, dtype=jnp.int32)
    _, cache = f["paged_prefill"](
        params, cache, _right_aligned(a, 48), cfg, row_bt=bt_a,
        prefix_len=np.int32(0), n_tail=np.int32(len(a)),
        slot=np.int32(0))
    before = {n_: np.asarray(cache[n_]) for n_ in ("k", "v")}
    bt_b = np.zeros(nb, np.int32)
    bt_b[:3] = 1, 2, 1 + nb
    got, cache = f["paged_prefill"](
        params, cache, _right_aligned(b[32:], 16), cfg,
        row_bt=jnp.asarray(bt_b), prefix_len=np.int32(32),
        n_tail=np.int32(tail), slot=np.int32(1))
    want, dense = _dense_reference(f, b)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    assert int(np.argmax(got)) == int(np.argmax(want))
    _check_row_against_dense(cache, bt_b, dense, len(b))
    # every block B did not own is byte for byte what it was: A's
    # shared prefix (1, 2), A's own tail block (3) and the rest
    owned = [0, 1 + nb]
    for name in ("k", "v"):
        after = np.asarray(cache[name])
        keep = [i for i in range(after.shape[1]) if i not in owned]
        np.testing.assert_array_equal(after[:, keep],
                                      before[name][:, keep])


@pytest.mark.parametrize("family", FAMILIES)
def test_chunked_paged_prefill_matches_one_shot(family):
    """The engine's chunked admission: the same program once per
    chunk, prefix_len = tokens already filled.  Chunks of 16, 16 and
    8 (the last one padded) leave the pool and the logits where one
    shot leaves them."""
    f = _family(family)
    cfg, params = f["cfg"], f["params"]
    n = 40
    prompt = _prompt(6, n, cfg.vocab_size)
    nb = cfg.max_seq // BS
    row_bt = jnp.arange(1, 1 + nb, dtype=jnp.int32)

    def fresh():
        return f["init_paged"](cfg, 1, num_blocks=1 + nb,
                               block_size=BS)

    want, one = f["paged_prefill"](
        params, fresh(), _right_aligned(prompt, 48), cfg,
        row_bt=row_bt, prefix_len=np.int32(0), n_tail=np.int32(n),
        slot=np.int32(0))
    cache, filled = fresh(), 0
    for c in (16, 16, 8):
        got, cache = f["paged_prefill"](
            params, cache,
            _right_aligned(prompt[filled:filled + c], 16), cfg,
            row_bt=row_bt, prefix_len=np.int32(filled),
            n_tail=np.int32(c), slot=np.int32(0))
        filled += c
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)
    assert int(np.argmax(got)) == int(np.argmax(want))
    for name in ("k", "v"):
        np.testing.assert_allclose(
            _row_view(cache, row_bt, name)[:, :n],
            _row_view(one, row_bt, name)[:, :n], atol=1e-5)
    dense_logits, dense = _dense_reference(f, prompt)
    np.testing.assert_allclose(np.asarray(got), dense_logits,
                               atol=1e-5)
    _check_row_against_dense(cache, row_bt, dense, n)


@pytest.mark.parametrize("T", [1, 2],
                         ids=["rows_after_the_scan", "layers_in_it"])
def test_paged_kv_contract_on_a_toy_pool(T):
    """PagedKV's own contract, for both ways the rows reach the pool
    (one column a row: `commit` lands them after the scan, the pool
    read-only in it; more: `attend` writes each layer back): a live
    slot lands at (table[slot // bs], slot % bs) in every layer and
    only there; a slot >= max_blk * bs never lands outside the null
    block, whatever the row's table says; the views are the updated
    layer's."""
    L, nblk, bs, H, hd = 2, 5, 4, 1, 2
    pool = jnp.arange(L * nblk * bs * H * hd, dtype=jnp.float32
                      ).reshape(L, nblk, bs, H, hd)
    cache = {"k": pool, "v": -pool}
    bt = jnp.asarray([[3, 1], [2, 4]], jnp.int32)        # max_blk 2
    # 8 and 99 are masked; T == 1 keeps row 0 live, row 1 masked
    slots = jnp.asarray([[5, 8], [99, 0]], jnp.int32)[:, :T]
    B = 2
    new = 1000.0 + jnp.arange(B * T * H * hd, dtype=jnp.float32
                              ).reshape(B, T, H, hd)
    kv = PagedKV(cache, bt, slots)
    assert kv.by_rows == (T == 1)

    def body(carry, layer_new):
        lidx, pools = carry
        pools, views = kv.attend(lidx, pools, layer_new, -layer_new)
        return (lidx + 1, pools), (views, layer_new, -layer_new)

    stacked = jnp.stack([new, new + 100.0])              # (L, B, T, ..)
    (_, pools), ((ck, cv), ks, vs) = jax.lax.scan(
        body, (jnp.int32(0), kv.pools), stacked)
    out = kv.commit(pools, ks, vs)
    exp = np.asarray(pool).copy()
    for layer in range(L):
        exp[layer, 1, 1] = np.asarray(stacked)[layer, 0, 0]   # slot 5
        if T == 2:
            exp[layer, 2, 0] = np.asarray(stacked)[layer, 1, 1]  # 0
    got = np.asarray(out["k"])
    # masked writes: block 0 offset 0 at most, nowhere else
    np.testing.assert_array_equal(got[:, 1:], exp[:, 1:])
    np.testing.assert_array_equal(got[:, 0, 1:], exp[:, 0, 1:])
    np.testing.assert_array_equal(np.asarray(out["v"])[:, 1:],
                                  -exp[:, 1:])
    # the views: each row's blocks of the updated layer, in order
    for layer in range(L):
        want = exp[layer][np.asarray(bt)].reshape(2, 2 * bs, H, hd)
        np.testing.assert_array_equal(np.asarray(ck)[layer], want)
        np.testing.assert_array_equal(np.asarray(cv)[layer], -want)


@pytest.mark.parametrize("t_pad", [32, 64],
                         ids=["a_chunk", "an_engine_bucket"])
def test_compiled_prefill_updates_the_pool_in_place(t_pad):
    """A prefill compiled with the cache donated: K and V alias their
    results, nothing pool-sized is copied or sliced, and the one move
    left is each tensor's layer written back into the carried pool."""
    from ray_tpu.tools.graftcheck.jaxpr_audit import pool_moves

    f = _family("gpt2")
    cfg = f["cfg"]
    nb = cfg.max_seq // BS
    cache = f["init_paged"](cfg, 2, num_blocks=1 + 2 * nb,
                            block_size=BS)

    def prefill(p, c, toks, bt):
        return f["paged_prefill"](p, c, toks, cfg, row_bt=bt,
                                  prefix_len=np.int32(16),
                                  n_tail=np.int32(t_pad - 3),
                                  slot=np.int32(0))

    args = (f["params"], cache, jnp.zeros((1, t_pad), jnp.int32),
            jnp.arange(1, 1 + nb, dtype=jnp.int32))
    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        *args).compile()
    pool = tuple(cache["k"].shape)
    pool_bytes = 2 * cache["k"].size * cache["k"].dtype.itemsize
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    moves = list(pool_moves(compiled.as_text(), pool))
    assert sorted(op for op, _name, _dims in moves) == [
        "dynamic-update-slice"] * 2, moves
    assert all(dims != pool for _op, _name, dims in moves), moves


# ---------------------------------------------------------------------------
# engine: the cache handed to a program is consumed
# ---------------------------------------------------------------------------

def _donated(fn, argnum, *args):
    """Whether jitted `fn` declares every leaf of args[argnum]
    donated (read off the lowering, so it holds on a backend that
    ignores donation at run time too)."""
    info = fn.lower(*args).args_info[0][argnum]
    return all(leaf.donated for leaf in jax.tree.leaves(info))


def _session_engine(**kw):
    from ray_tpu.serve.llm import build_llm_deployment

    # a temperature no other test uses: this engine's programs are its
    # own entry in serve/engine_programs.py's _JIT_CACHE
    return build_llm_deployment(
        "gpt2", "nano", max_new_tokens=3, temperature=0.0,
        top_k=0, top_p=1.0, scheduler="continuous", kv_layout="paged",
        kv_block_size=16, kv_num_blocks=12, prefill_bucket=16,
        max_slots=2, kv_host_tier_bytes=1 << 24,
        config_overrides=_OVR, **kw)


def test_engine_session_never_touches_a_consumed_cache():
    """Admission, a prefix hit with a copy-on-write fork, finishing
    waves (clear_row), host-tier spills and a restore, and a handoff
    export, in one session on one engine.  The backend honours
    donation (asserted), so a read of a cache a program consumed would
    raise "Array has been deleted" and fail the request."""
    rng = np.random.RandomState(11)
    prefixes = [rng.randint(2, 300, size=48).astype(np.int32)
                for _ in range(6)]
    dep = _session_engine()

    async def main():
        inst = dep.func_or_class()
        outs = []
        try:
            full = prefixes[0]                      # exactly 3 blocks
            outs.append(await inst(full))           # cold admission
            held = inst._cache
            outs.append(await inst(full))           # full hit: COW fork
            # the cache the second request started from was consumed
            consumed = held["k"].is_deleted()
            # a handoff export reads the live cache between steps...
            ids = jnp.zeros((inst.cfg.max_seq // 16,), jnp.int32
                            ).at[:3].set(jnp.asarray([1, 2, 3]))
            k_rows, v_rows = inst._fns.kv_handoff_export(inst._cache,
                                                         ids)
            # ...and its rows outlive the donating calls that follow:
            # two laps over six prefixes through a 12-block pool evict
            # (spill to the host tier) and restore
            for lap in range(2):
                for i, pre in enumerate(prefixes):
                    tail = rng.randint(2, 300, size=4).astype(np.int32)
                    outs.append(await inst(np.concatenate(
                        [pre, np.int32([i + 2]), tail])))
            rows = np.asarray(k_rows), np.asarray(v_rows)
            stats = inst.engine_stats()
            live = not inst._cache["k"].is_deleted()
            fns, cache, params = inst._fns, inst._cache, inst.params
            declared = {
                "decode": _donated(
                    fns.pool_step.__wrapped__, 1, params, cache,
                    jnp.zeros((2,), jnp.int32), jax.random.PRNGKey(0)),
                "pool_logits": _donated(
                    fns.pool_logits, 1, params, cache,
                    jnp.zeros((2,), jnp.int32)),
                "clear_row": _donated(fns.clear_row, 0, cache,
                                      np.int32(0)),
                "copy_block": _donated(fns.copy_block, 0, cache,
                                       np.int32(1), np.int32(2)),
                "install_blocks": _donated(
                    fns.install_blocks, 0, cache, ids, k_rows, v_rows),
            }
        finally:
            inst.shutdown_engine()
        return outs, stats, consumed, live, rows, declared

    outs, stats, consumed, live, rows, declared = asyncio.run(main())
    assert len(outs) == 14 and all(len(o) >= 48 + 3 for o in outs)
    np.testing.assert_array_equal(outs[0], outs[1])
    assert all(declared.values()), declared
    assert consumed, "this backend ignored the donation"
    assert live
    kv = stats["kv_cache"]
    assert kv["cow_copies"] >= 1 and kv["prefix_block_hits"] >= 3
    assert kv["evictions"] >= 1
    tier = stats["kv_tier"]
    assert tier["saves"] >= 1 and tier["hits"] >= 1
    assert stats["requests"]["finished"] == 14
    assert rows[0].shape[0] == 8 and np.isfinite(rows[0]).all()
    assert rows[0][:3].any() and np.isfinite(rows[1]).all()


def test_engine_stats_report_the_pool_aliased(monkeypatch):
    """``engine_stats()["programs"]``: the decode step and the paged
    prefill write their results over the donated pool (alias_bytes >=
    the pool's bytes) and allocate well under one pool beside it."""
    monkeypatch.setenv("RAYTPU_DEVICE_STATS_COST", "1")
    from ray_tpu._private import device_stats as ds
    from ray_tpu.serve.llm import build_llm_deployment

    ds.get_registry().reset()
    # unique sampling knobs -> fresh _JIT_CACHE entry -> wrappers that
    # harvest under the opt-in above
    dep = build_llm_deployment(
        "gpt2", "nano", max_new_tokens=4, temperature=0.0131,
        scheduler="continuous", kv_layout="paged", kv_block_size=16,
        kv_num_blocks=128, prefill_bucket=16, max_slots=2,
        config_overrides=_OVR)
    prompts = [_prompt(30 + i, n, 400) for i, n in enumerate((7, 21))]

    async def main():
        inst = dep.func_or_class()
        try:
            await asyncio.gather(*[inst(p) for p in prompts])
            return inst.engine_stats()
        finally:
            inst.shutdown_engine()

    stats = asyncio.run(main())
    pool_bytes = stats["kv_cache"]["pool_bytes"]
    assert pool_bytes > 0
    for program in ("serve.decode", "serve.paged_prefill"):
        block = stats["programs"][program]
        assert block["alias_bytes"] >= pool_bytes, (program, block)
        assert block["temp_bytes"] < pool_bytes, (program, block)
        # arguments + temporaries, the aliased results not counted
        # twice
        assert block["peak_hbm_bytes"] < 2 * pool_bytes + \
            block["temp_bytes"] + (4 << 20)
