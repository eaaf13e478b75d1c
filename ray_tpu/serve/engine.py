"""The continuous-batching engine behind `build_llm_deployment`.

`LLMEngine` is the slot-pool scheduler: a fixed pool of `max_slots`
cache rows, one batched prefill dispatch per admitted request, one
jitted decode step per token shared by all active slots, mid-flight
admission and retirement (serve/llm.py's docstrings say what each
option does).  It owns the host side only: which request sits in which
slot, which blocks the pager (serve/kv_pager.py) gave it, what is in
flight on the chip.  The programs it dispatches are
serve/engine_programs.py's, what they compute is a
`models.families.Family`'s, and the cache it carries from call to call
is a pytree only models/decode_common.py looks into.

`EngineBase` is what the batch scheduler (serve/llm.py BatchLLM) shares
with it: parameters on their device, and the telemetry surface.  Both
read their options from `self.opt`, an `EngineOptions` that
`build_llm_deployment` binds on the subclass it deploys.
"""

from __future__ import annotations

import collections
import pickle
from typing import Any, Dict

import numpy as np

from ray_tpu._private import scopes
from ray_tpu._private.telemetry import Phases, setup_phase
from ray_tpu.models import decode_common as dc
from ray_tpu.models.decode_common import SamplingParams
from ray_tpu.models.families import PER_SLOT_STATE, family as _family
from ray_tpu.serve.batching import (ChunkCursor, HandoffCursor,
                                    OverloadedError, RequestQueue)
from ray_tpu.serve.engine_programs import _jitted_engine_fns
from ray_tpu.serve.telemetry import EngineTelemetry

#: how much work the continuous engine may queue on the chip ahead of
#: the host, in seconds of decode waves and at most so many waves
#: (`LLMEngine._depth`): what a host that is held up for a tenth of a
#: second (a collector's pause, a neighbour, a frozen sandbox) can be
#: late by before the chip runs dry.  A request admitted meanwhile
#: starts behind that work, which is the price and why it is bounded: a
#: wave longer than this is never queued behind another.
_AHEAD_S = 0.13
_AHEAD_MAX = 16

#: what a launch's dict holds of the device and of its requests while
#: it is in flight (`LLMEngine._launch`); dropped when it lands, so the
#: ring of landed records pins neither
_IN_FLIGHT = ("toks", "tok", "stepped", "st", "tokens", "experts")
#: the fields of a launch that its dispatch span carries as attributes
_SPAN_ATTRS = ("seq", "kind", "rows", "req", "bucket", "n_tail", "ahead")


def _span_attrs(launch) -> dict:
    return {k: launch[k] for k in _SPAN_ATTRS if k in launch}


class EngineBase:
    """Parameters, telemetry and the stats surface of either scheduler.
    `opt` is bound by the subclass `build_llm_deployment` deploys."""

    opt = None
    _pager = None

    def __init__(self, device=None):
        """device: the one ``jax.Device`` this engine lives on —
        parameters, KV pool and (through their committed inputs)
        every jitted program.  build_llm_fleet gives each replica
        its own; None keeps JAX's default device.  A `mesh` engine
        already has its placement."""
        import jax
        import jax.numpy as jnp

        opt = self.opt
        if device is not None and opt.mesh is not None:
            raise ValueError("an engine takes a mesh or one "
                             "device, not both")
        self.device = device
        #: where this constructor's seconds go, as raytpu.setup.*
        #: spans, the engine_stats()["setup"] table and the ``phase``
        #: set-up records that the compiles inside name as their cause
        #: (`_build`; the leaves are scopes.SETUP_PHASES)
        self._setup = Phases(scopes.SETUP)

        with self._build("config"):
            overrides = dict(opt.config_overrides or {})
            fam = _family(opt.family)
            self._prefill_attention = fam.prefill_attention
            self.cfg = fam.config(opt.preset, **overrides)
        with self._build("params"):
            if opt.checkpoint_path:
                with open(opt.checkpoint_path, "rb") as f:
                    self.params = jax.tree.map(jnp.asarray,
                                               pickle.load(f))
            else:
                self.params = fam.init(jax.random.PRNGKey(opt.seed),
                                       self.cfg)
            self.mesh = opt.mesh
            if opt.mesh is not None:
                # commit params to the mesh once at construction; the
                # committed shardings propagate through every jitted
                # program below, turning them SPMD without annotation
                from ray_tpu.parallel.sharding import (DECODE_RULES,
                                                       shard_by_shape)
                self.params = shard_by_shape(
                    self.params, fam.logical_axes(self.cfg), opt.mesh,
                    DECODE_RULES)
            self.params = self._to_engine(self.params)
            # per-call PRNG threading: without it every temperature>0
            # request would sample under the same default key and
            # return identical "random" continuations
            self._rng = jax.random.PRNGKey(opt.seed + 1)
        # host-side lifecycle telemetry (enqueue/admit/first-token/
        # step/finish records -> metrics + engine_stats + timeline);
        # never touches the jitted programs
        self._telemetry = EngineTelemetry(
            f"llm_{opt.family}_{opt.preset}",
            max_slots=(opt.max_slots if opt.scheduler == "continuous"
                       else opt.max_batch_size),
            role=opt.role)
        #: what the scheduler loop does between device calls, as
        #: raytpu.engine.* spans on the profiler's clock and the
        #: engine_stats()["phases"] table (_private/telemetry.py)
        self._phases = Phases(scopes.ENGINE)
        #: disaggregated serving role — the fleet router reads
        #: this to type replicas ("prefill" | "decode" | "both")
        self.role = opt.role
        #: round-19 healthwatch/chaos attach points — the fleet
        #: (serve/router.py LLMFleet) overwrites these after
        #: construction; standalone engines keep them None, so
        #: the engine loop's only cost is one `is None` check
        #: per wave
        self._health = None
        self._chaos = None
        self._replica_label = f"llm_{opt.family}_{opt.preset}"
        self._init_scheduler(fam)

    def _init_scheduler(self, fam) -> None:
        """The scheduler's own state and programs over family `fam`
        (a models.families.Family), each part inside its `_build`
        phase."""
        raise NotImplementedError

    def _build(self, phase):
        """``with self._build("cache"):`` -- one leaf of the
        constructor (scopes.SETUP_PHASES)."""
        return setup_phase(self._setup, phase)

    def _to_engine(self, tree):
        """Commit arrays made elsewhere (fresh inits, another
        replica's handoff rows) to this engine's device; an
        engine without one takes them as they are."""
        import jax

        if self.device is not None:
            return jax.device_put(tree, self.device)
        return tree

    # -- telemetry surface (works for both schedulers) -----------

    def engine_stats(self):
        """p50/p95/p99 TTFT + queue wait, throughput, slot
        utilization, request counts, rejections by reason, and
        (paged layout) the live kv_cache block/prefix-hit stats —
        `handle.method("engine_stats").remote()` or GET
        /api/serve/stats."""
        opt = self.opt
        pager = self._pager     # the continuous scheduler's, if paged
        if pager is not None:
            self._telemetry.record_kv_stats(pager.stats())
            self._telemetry.record_kv_scope(self._compose_kv_scope())
            if pager.tier is not None:
                self._telemetry.record_kv_tier(pager.tier.stats())
            if pager.snapshots is not None:
                self._telemetry.record_recurrent(
                    pager.snapshots.stats(dc.state_bytes(self._cache)))
        if self._health is not None:
            self._telemetry.record_health(
                self._health.replica_block(self._replica_label))
        stats = self._telemetry.engine_stats()
        # {phase: [count, seconds]} of the scheduler loop; "step"
        # counts iterations, the others are its leaves
        stats["phases"] = self._phases.snapshot()
        # the same table of the constructor's leaves: what building
        # this engine took, by scopes.SETUP_PHASES
        stats["setup"] = self._setup.snapshot()
        if opt.admission_policy is not None:
            stats["admission_policy"] = opt.admission_policy.describe()
        # perf observatory: compiled-cost / recompile / live-MFU
        # block for this engine's programs (process-wide registry,
        # filtered to the serve namespace)
        from ray_tpu._private.device_stats import (
            device_memory_stats, get_registry)

        mesh = self.mesh
        stats["programs"] = get_registry().snapshot(
            prefix="serve.",
            n_devices=int(mesh.size) if mesh is not None else 1)
        if mesh is not None:
            stats["mesh"] = {
                "axes": {a: int(s)
                         for a, s in self.mesh.shape.items()
                         if int(s) > 1},
                "n_devices": int(self.mesh.size),
                "kv_shards": dc.kv_shards(self._cache),
                # per-chip allocator stats (stable keys; values
                # are None on backends without memory_stats())
                "devices": device_memory_stats(
                    list(self.mesh.devices.flat)),
            }
        return stats

    def export_timeline(self, path=None):
        """Chrome-trace engine timeline (queue lane, per-slot
        occupancy lanes, engine-step lane); writes `path` when
        given and returns the event list."""
        return self._telemetry.export_timeline(path)

    # -- tracebus surface (tools/tracebus.py collects these) -----

    def trace_records(self):
        """Tracebus request snapshots (hop timestamps, token
        trail, router spans) for every retained request."""
        return self._telemetry.trace_records()

    def request_trace(self, request_id):
        """One request's tracebus snapshot by trace id (or
        engine-local id); None when unknown to this replica —
        `handle.method("request_trace").remote(rid)` or GET
        /api/serve/trace/<rid>."""
        return self._telemetry.find_request(request_id)

    def anatomy_samples(self, tenant=None):
        """Raw latency-anatomy samples (ITL gaps, TPOT,
        critical-path components) — fleet_stats pools these
        across replicas before summarizing."""
        return self._telemetry.anatomy_samples(tenant=tenant)

    def metrics_snapshot(self):
        """This replica's serve_* metric dumps (histogram buckets
        included) straight from the process-local registry."""
        from ray_tpu.util.metrics import _registry

        return {name: dump for name, dump
                in _registry.snapshot().items()
                if name.startswith("serve_")}


class LLMEngine(EngineBase):
    """The continuous scheduler: slot pool with mid-flight admission."""

    # ------------------------------------------------------------
    # "continuous" scheduler: slot pool with mid-flight admission
    # ------------------------------------------------------------

    def _init_scheduler(self, fam) -> None:
        import jax
        import jax.numpy as jnp

        opt = self.opt
        cfg = self.cfg
        with self._build("cache"):
            self._recurrent = fam.cache_kind in PER_SLOT_STATE
            self._pager = None
            self._kvscope_budget = None     # _compose_kv_scope's, cached
            if opt.kv_layout == "paged":
                from ray_tpu.serve.kv_pager import BlockPager

                max_blk = cfg.max_seq // opt.kv_block_size
                # default pool: every slot can hold a full sequence,
                # plus one sequence of headroom so the prefix cache and
                # COW forks survive a fully-occupied pool
                n_blocks = (opt.kv_num_blocks
                            if opt.kv_num_blocks is not None
                            else 1 + (opt.max_slots + 1) * max_blk)
                self._cache = fam.init_paged_cache(
                    cfg, opt.max_slots, num_blocks=n_blocks,
                    block_size=opt.kv_block_size, mesh=self.mesh)
                # tiered host-RAM KV cache: evicted prefix blocks
                # spill device→host and re-admit via H2D copy instead
                # of re-prefill (serve/kv_tier.py)
                host_tier = None
                if opt.kv_host_tier_bytes is not None:
                    from ray_tpu.serve.kv_tier import HostKVTier

                    host_tier = HostKVTier(opt.kv_host_tier_bytes)
                self._pager = BlockPager(
                    n_blocks, opt.kv_block_size, cfg.max_seq,
                    bytes_per_block=dc.block_bytes(self._cache),
                    tensor_shards=dc.kv_shards(self._cache),
                    recorder=self._telemetry.flightrec,
                    host_tier=host_tier)
                if host_tier is not None:
                    self._pager.set_block_saver(self._tier_save)
                # what the cache reserves by its layers' reach (a token in
                # the pool, a slot's windows): `_reserved_by_reach`
                self._reach = dc.cache_reach(self._cache)
                if self._recurrent:
                    # prefix reuse for a recurrent family: one snapshot
                    # of the state a slot, keyed as the pager keys the
                    # block at its boundary (kv_pager.StateSnapshots)
                    from ray_tpu.serve.kv_pager import StateSnapshots

                    self._pager.set_snapshots(StateSnapshots(opt.max_slots))
            else:
                self._cache = fam.init_cache(cfg, opt.max_slots,
                                             mesh=self.mesh)
            self._cache = self._to_engine(self._cache)
            # a family with a sparse expert layer leaves its routing
            # counters in the cache, program by program, and one whose
            # attention reads what an indexer selects its selection's
            # (_counters)
            self._counted = dc.program_counters(self._cache) is not None
            self._cur = np.zeros((opt.max_slots,), np.int32)
            self._slots = [None] * opt.max_slots
            # what the chip has been given and the host has not fenced
            # yet, oldest first: decode waves, and the prefills admitted
            # between them (_wave, _land); when the last wave landed and
            # what the last waves took, fence to fence
            self._flight = collections.deque()
            # launches made so far (a launch's `seq`), loop iterations
            # with work in them, and when the loop last let its callers
            # run (None: parked, or not started)
            self._seq = 0
            self._iteration = 0
            self._held_from = None
            # slot -> first token, still on the device, of each prefill
            # in flight that the next wave takes up (join_token)
            self._joins = {}
            self._t_landed = 0.0
            self._wave_s = collections.deque(maxlen=33)
            self._queue = RequestQueue()
            self._wake = None           # asyncio.Event, made on-loop
            self._engine_task = None
            self._default_sp = opt.default_sp
            self._samplers = {}     # SamplingParams -> jitted sampler
            # chunked streaming prefill (round 15): round-robin cursor
            # over slots mid-prefill, plus a constant key for the
            # discarded samples of intermediate chunks (the engine RNG
            # splits once per admission, at the FINAL chunk — the same
            # stream a one-shot admission sees)
            self._chunk_rr = 0
            # the same constant key rides with a greedy decode wave:
            # argmax reads no key, and the eager split it replaces was
            # 1 ms of idle device a step (`_step`)
            self._dummy_key = jax.random.PRNGKey(0)

        # spec decode: (model drafts) the draft family's
        # config/params/cache pool
        d_fam = None
        self._draft_params = self._draft_cache = None
        self._draft_cfg = None
        self._spec_sampled = (opt.spec_decode is not None
                              and opt.temperature > 0.0)
        if opt.spec_decode is not None:
            # draft rewind bookkeeping: per slot, how many of last
            # round's drafted tokens the target rejected (the
            # draft cache rolls back exactly this many positions
            # at the top of the next propose dispatch)
            self._spec_rej = np.zeros((opt.max_slots,), np.int32)
            if opt.spec_decode.draft != "ngram":
                d_family, d_preset = opt.spec_decode.draft.split(":")
                d_fam = _family(d_family)
                # overrides describe THIS family's config fields;
                # a cross-family draft takes its preset verbatim
                d_over = (dict(opt.config_overrides or {})
                          if d_family == opt.family else {})
                d_cfg = d_fam.config(d_preset, **d_over)
                if (d_cfg.vocab_size != cfg.vocab_size
                        or d_cfg.padded_vocab != cfg.padded_vocab):
                    raise ValueError(
                        f"spec draft vocab "
                        f"{d_cfg.vocab_size}/{d_cfg.padded_vocab} "
                        f"!= target "
                        f"{cfg.vocab_size}/{cfg.padded_vocab} — "
                        "draft proposals index the target vocab")
                if d_cfg.max_seq < cfg.max_seq:
                    raise ValueError(
                        f"spec draft max_seq {d_cfg.max_seq} < "
                        f"target max_seq {cfg.max_seq} — the "
                        "draft cache must track every target "
                        "position")
                d_seed = (opt.spec_decode.draft_seed
                          if opt.spec_decode.draft_seed is not None
                          else opt.seed)
                with self._build("params"):
                    self._draft_params = self._to_engine(d_fam.init(
                        jax.random.PRNGKey(d_seed), d_cfg))
                with self._build("cache"):
                    # draft pool: always dense, never mesh-sharded —
                    # the draft is small by construction and a dense
                    # row pool keeps its pos arithmetic trivial
                    self._draft_cache = self._to_engine(
                        d_fam.init_cache(d_cfg, opt.max_slots))
                self._draft_cfg = d_cfg

        with self._build("programs"):
            fns = _jitted_engine_fns(
                fam, cfg, opt.default_sp, kv_layout=opt.kv_layout,
                mesh=self.mesh, spec=opt.spec_decode, draft=d_fam,
                draft_cfg=self._draft_cfg)
            self._fns = fns
            (self._prefill, self._paged_prefill, self._pool_step,
             self._admit, self._copy_block, self._clear_row) = (
                fns.prefill, fns.paged_prefill, fns.pool_step,
                fns.admit, fns.copy_block, fns.clear_row)
            # compiled here, not at the first admission that meets a
            # decode wave in flight
            fns.join_token(self._cur, np.int32(0), self._cur[:1])
            if self._pager is not None and self._pager.tier is not None:
                # pre-compile the H2D splice program with an all-pad
                # call (every id 0 → zero rows into the null write
                # sink): restores share ONE fixed-shape program, so
                # the first real tier restore pays a copy inside its
                # kv_fetch window, not a compile
                from ray_tpu.serve.kv_tier import staging_buffers

                maxn = cfg.max_seq // opt.kv_block_size
                rows = dc.block_rows(self._cache, maxn)
                # persistent host staging buffers for the restore path
                # (ids, k rows, v rows) — refilled in place per
                # restore instead of re-allocating pad arrays
                self._tier_stage = staging_buffers(maxn, rows.shape,
                                                   rows.dtype)
                zr = jnp.zeros(rows.shape, rows.dtype)
                self._cache = fns.install_blocks(
                    self._cache, jnp.zeros((maxn,), jnp.int32),
                    zr, zr)
                jax.block_until_ready(self._cache)
            if self._pager is not None:
                # handoff id staging buffer: role-split engines use it
                # every handoff; a role="both" engine only if a caller
                # feeds it packages via admit_prefilled directly
                self._handoff_ids = np.zeros(
                    (cfg.max_seq // opt.kv_block_size,), np.int32)
            if opt.role != "both":
                # disaggregated handoff: pre-compile this role's side
                # of the block move with an all-pad call so the first
                # real handoff pays a copy inside its handoff window,
                # not an XLA compile (the tier-splice precompile
                # discipline, applied to the new programs)
                maxn = cfg.max_seq // opt.kv_block_size
                pad_ids = jnp.zeros((maxn,), jnp.int32)
                if opt.role == "prefill":
                    k_rows, v_rows = fns.kv_handoff_export(
                        self._cache, pad_ids)
                    jax.block_until_ready(k_rows)
                    del k_rows, v_rows
                else:
                    rows = dc.block_rows(self._cache, maxn)
                    zr = jnp.zeros(rows.shape, rows.dtype)
                    self._cache = fns.kv_handoff_install(
                        self._cache, pad_ids, zr, zr, np.int32(0),
                        jnp.zeros((maxn,), jnp.int32), np.int32(0))
                    jax.block_until_ready(self._cache)
        # perf observatory: mirror process-wide program compile
        # events into this deployment's program-keyed recompile
        # counter (decode/sharded-decode shape churn visible, not
        # just prefill buckets); weak subscription — a retired
        # engine drops out of the registry automatically
        from ray_tpu._private.device_stats import get_registry

        get_registry().subscribe(self._telemetry.record_program_compile)
        # recompile-storm trips journal into the flight recorder
        # and (with an SLOConfig) trigger postmortem dumps
        get_registry().subscribe_storms(self._telemetry.record_storm)
        if opt.slo is not None:
            from ray_tpu.serve.slo import SLOTracker

            self._telemetry.slo = SLOTracker(
                opt.slo, self._telemetry,
                recorder=self._telemetry.flightrec)

    def _launch(self, kind, program, rows, **facts) -> dict:
        """The record of the launch about to be made: the one dict
        the engine keeps of a program it hands the device, from
        before its dispatch (the span carries these fields:
        `_span_attrs`) through its time in `_flight` (where it also
        holds what the fence will read: `_IN_FLIGHT`) to the ring of
        landed records (`_landed`).  `program` is the jitted
        function, named here as a trace names its executions;
        `rows` the decoding rows a wave steps, or that stand behind
        a prefill; `facts` what the site has in hand beside
        (`EngineTelemetry.record_launch` lists the fields)."""
        self._seq += 1
        return dict(facts, seq=self._seq, kind=kind,
                    program="jit_" + program.__name__, rows=rows,
                    ahead=len(self._flight))

    def _landed(self, launch, fence=None) -> None:
        """A launch is fenced (`fence`: the fence phase's stamps) or
        given up unfenced: it lets go of the device and joins the
        landed records.  A launch whose one phase holds dispatch and
        fence (``fused``) has that phase's stamps as both."""
        launch["fence"] = fence
        if launch.get("fused"):
            launch["dispatch"] = fence
        for key in _IN_FLIGHT:
            launch.pop(key, None)
        self._telemetry.record_launch(launch)

    def _give_up(self) -> None:
        """Forget what is in flight (its rows have all ended, or the
        loop failed): the launches were made, so each is recorded as
        it stands, unfenced."""
        while self._flight:
            self._landed(self._flight.popleft())
        self._joins.clear()

    def launch_records(self):
        """The landed launch records, oldest first, at most
        `serve.telemetry.LAUNCH_HISTORY`
        (`EngineTelemetry.record_launch` says what one holds);
        `serve.telemetry.recent_launches()` reads the same ring once
        the engine is gone."""
        return self._telemetry.launch_records()

    def _counters(self):
        """The counter vectors the program just dispatched leaves, by
        their cache key and still on the device (None for a family that
        keeps none): a copy queued behind the program, which its fence
        reads beside its tokens (`_book_counters`)."""
        if not self._counted:
            return None
        return self._fns.take_counters(dc.program_counters(self._cache))

    def _book_counters(self, program, counters) -> None:
        book = {dc.EXPERTS: self._telemetry.record_experts,
                dc.INDEX: self._telemetry.record_index}
        for key, vector in (counters or {}).items():
            book[key](program, np.asarray(vector))

    def _sampler_for(self, sp):
        """Per-SamplingParams jitted full-batch sampler for
        requests overriding the engine default.  Cached per sp —
        the override path costs one extra dispatch per step, never
        a recompile storm."""
        fn = self._samplers.get(sp)
        if fn is None:
            import jax

            from ray_tpu.models.decode_common import (
                make_vocab_tail_mask, sample_token)

            tail = make_vocab_tail_mask(self.cfg)

            def sampler(lg, kk):
                return sample_token(lg, kk, sp.temperature, tail,
                                    sp.top_k, sp.top_p)

            fn = self._samplers[sp] = jax.jit(sampler)
        return fn

    def _hit_stop(self, out) -> bool:
        """Host-side stop matching over the GENERATED tokens (the
        prompt can never trigger a stop)."""
        opt = self.opt
        if opt.eos_id is not None and out[-1] == opt.eos_id:
            return True
        for s in opt.stop_seqs:
            if len(out) >= len(s) and tuple(out[-len(s):]) == s:
                return True
        return False

    def _draft_admit(self, slot, arr) -> None:
        """Mirror a just-admitted request into the draft cache
        pool: full-prompt draft prefill (even when the paged
        target reused a resident prefix — the dense draft pool has
        no prefix cache) + row admit.  The draft's own first-token
        sample is discarded; the TARGET's prefill token is
        authoritative and becomes `cur`."""
        opt = self.opt
        if self._draft_params is None:
            if opt.spec_decode is not None:
                self._spec_rej[slot] = 0
            return
        import jax
        import jax.numpy as jnp

        n = int(arr.shape[0])
        t_pad = -(-n // opt.prefill_bucket) * opt.prefill_bucket
        t_pad = max(n, min(t_pad, self._draft_cfg.max_seq
                           - opt.max_new_tokens))
        padded = np.zeros((1, t_pad), np.int32)
        padded[0, t_pad - n:] = arr
        self._rng, k = jax.random.split(self._rng)
        _tok, row = self._fns.draft_prefill(
            self._draft_params, jnp.asarray(padded),
            jnp.asarray([n], jnp.int32), k)
        self._draft_cache = self._admit(self._draft_cache, row, slot)
        self._spec_rej[slot] = 0

    def _admit_pending(self) -> None:
        """Prefill queued requests into free slots (one batched
        prefill dispatch each; K/V rows land in the pool cache).
        Paged layout: blocks are matched/allocated through the
        pager first — a request the pool cannot hold yet goes back
        to the queue HEAD and admission pauses until a retirement
        frees blocks."""
        import jax
        import jax.numpy as jnp

        opt = self.opt
        phase = self._phases.phase
        while len(self._queue):
            free = [i for i, s in enumerate(self._slots) if s is None]
            if not free:
                return
            ((arr, rec, sp), fut), = self._queue.pop(1)
            if isinstance(arr, HandoffCursor):
                # disaggregated handoff package from a prefill
                # replica — block-table splice, never a prefill
                if not self._admit_one_handoff(arr, rec, fut, free[0]):
                    return      # pool exhausted — retry later
                continue
            n = int(arr.shape[0])
            if n == 0 or n + opt.max_new_tokens > self.cfg.max_seq:
                self._telemetry.record_reject(
                    rec, reason=f"prompt length {n}",
                    label="oversized")
                if not fut.done():
                    fut.set_exception(ValueError(
                        f"prompt length {n} invalid for "
                        f"max_seq={self.cfg.max_seq} with "
                        f"max_new_tokens={opt.max_new_tokens}"))
                continue
            slot = free[0]
            if self._pager is not None:
                if not self._admit_one_paged(arr, rec, sp, fut, slot):
                    return          # pool exhausted — retry later
                continue
            # this prefill is fenced at once: not with waves queued
            # before it, whose tokens would wait for it
            self._drain()
            # pad up to the bucket so the prefill program compiles
            # once per bucket; never past the decode headroom
            t_pad = -(-n // opt.prefill_bucket) * opt.prefill_bucket
            t_pad = max(n, min(t_pad, self.cfg.max_seq - opt.max_new_tokens))
            self._telemetry.record_admit(rec, slot, t_pad)
            padded = np.zeros((1, t_pad), np.int32)
            padded[0, t_pad - n:] = arr
            launch = self._launch(
                "prefill",
                self._prefill if sp is None else self._fns.prefill_raw,
                len(self._decoding()), req=rec["id"], bucket=t_pad,
                prefix_len=0, n_tail=n)
            with phase("rng_split"):
                self._rng, k = jax.random.split(self._rng)
            with phase("prefill_dispatch",
                       **_span_attrs(launch)) as dispatch:
                if sp is not None:
                    # override path: logits-returning twin + the
                    # per-sp sampler (default requests keep the
                    # fused single-dispatch program)
                    logits, row = self._fns.prefill_raw(
                        self.params, jnp.asarray(padded),
                        jnp.asarray([n], jnp.int32))
                    tok = self._sampler_for(sp)(logits, k)
                else:
                    tok, row = self._prefill(
                        self.params, jnp.asarray(padded),
                        jnp.asarray([n], jnp.int32), k)
            # int() is the engine's existing host fence for the
            # prefill result; the timestamp behind it is the TTFT
            with phase("prefill_fence", seq=launch["seq"]) as fence:
                first = int(np.asarray(tok)[0])
            launch["dispatch"] = (dispatch.t0, dispatch.t1)
            self._landed(launch, (fence.t0, fence.t1))
            self._telemetry.record_first_token(rec)
            if opt.max_new_tokens <= 1 or self._hit_stop([first]):
                self._telemetry.record_finish(rec, n_tokens=1)
                if not fut.done():
                    fut.set_result(np.concatenate(
                        [arr, np.asarray([first], np.int32)]))
                continue
            self._cache = self._admit(self._cache, row, slot)
            self._cur[slot] = first
            self._slots[slot] = {"prompt": arr, "out": [first],
                                 "fut": fut, "rec": rec, "sp": sp}
            self._draft_admit(slot, arr)

    def _reserve_blocks(self, arr, rec, sp, fut, tokens, ctx, t_kv0):
        """The pager's half of a paged admission, from `t_kv0`:
        prefix match, allocation, host-tier restore, COW fork.
        Returns (blocks, prefix_len), or None when the pool cannot
        hold the request yet (it is back at the queue's head)."""
        import jax
        import jax.numpy as jnp

        import time as _time

        opt = self.opt
        pager = self._pager
        n = int(arr.shape[0])
        pager.set_request(rec["id"],
                          ctx.trace_id if ctx is not None else None,
                          tenant=rec.get("tenant"))
        ev0 = pager.evictions
        # spec decode: reserve k blocks' worth of verify-overshoot
        # headroom so rejected draft K/V writes land in blocks this
        # row owns, never one the pager re-hands out
        need = pager.blocks_needed(
            n, opt.max_new_tokens,
            headroom=opt.spec_decode.k if opt.spec_decode is not None
            else 0)
        prefix_len, matched = pager.match_prefix(tokens)
        alloc = pager.allocate(need - len(matched))
        if alloc is None:
            pager.release(matched)
            pager.set_request(None)
            self._telemetry.record_requeue(
                rec, need=need, reason="pool_exhausted")
            self._queue.push_front((arr, rec, sp), fut)
            return None
        blocks = matched + alloc
        # tiered host-RAM KV cache: second-chance lookup — full
        # blocks the HBM prefix match missed may survive in the
        # host tier.  Restore each hit into a freshly-allocated
        # block with one H2D install, then bump prefix_len so the
        # tail prefill skips those tokens exactly as it does for
        # HBM-resident prefixes (content-addressed keys make the
        # restored rows the rows re-prefill would have written, so
        # outputs stay bit-identical to the dense oracle).  Probed
        # only after allocation succeeds — a requeued admission
        # must not double-count tier probes.
        pairs = pager.tier_lookup(tokens, len(matched))
        if pairs:
            t_f0 = _time.perf_counter()
            # one padded dispatch for the whole chain (the
            # program's shape is fixed at maxn, pre-compiled at
            # init).  The id/stack staging buffers persist across
            # restores: pad entries target the null write sink
            # (block 0), whose content is garbage by contract, so
            # stale rows left from an earlier restore need no
            # re-zeroing.
            ids, ek, ev = self._tier_stage
            ids[:] = 0
            ids[:len(pairs)] = alloc[:len(pairs)]
            for i, (_, e) in enumerate(pairs):
                ek[i] = e["k"]
                ev[i] = e["v"]
            self._cache = self._fns.install_blocks(
                self._cache, jnp.asarray(ids), jnp.asarray(ek),
                jnp.asarray(ev))
            # fence so the h2d bucket times the transfer, not the
            # dispatch (the trainwatch h2d discipline)
            jax.block_until_ready(self._cache)
            t_f1 = _time.perf_counter()
            pager.tier.note_h2d(t_f1 - t_f0)
            restored = pager.note_tier_restore(pairs, alloc)
            prefix_len += restored
            self._telemetry.record_kv_fetch(
                rec, t_f0, t_f1, blocks=len(pairs),
                tokens=restored,
                bytes=sum(int(e["bytes"]) for _, e in pairs))
        wb = prefix_len // opt.kv_block_size
        if wb < len(matched):
            # the tail's first write lands inside a matched block
            try:
                new_blk, src = pager.ensure_private(blocks[wb])
            except MemoryError:
                pager.release(blocks)
                pager.set_request(None)
                self._telemetry.record_requeue(
                    rec, need=need, reason="cow_exhausted")
                self._queue.push_front((arr, rec, sp), fut)
                return None
            if src is not None:
                blocks[wb] = new_blk
                self._cache = self._copy_block(
                    self._cache, np.int32(src), np.int32(new_blk))
                self._telemetry.record_cow()
        pager.set_request(None)
        self._telemetry.record_kv_reserve(
            rec, t_kv0, _time.perf_counter(), blocks=len(blocks),
            hit_blocks=len(matched),
            evicted=pager.evictions - ev0)
        # tier-restored blocks count as reuse hits (served from
        # cache, just a slower tier), mirroring the pager's own
        # hit/miss accounting in note_tier_restore
        reused = len(matched) + len(pairs)
        self._telemetry.record_prefix_reuse(
            reused, pager.blocks_needed(n, 0) - reused)
        return blocks, prefix_len

    def _admit_one_paged(self, arr, rec, sp, fut, slot) -> bool:
        """Admit one request through the block pager: match the
        longest resident prompt prefix, allocate the remaining
        blocks up front (decode never allocates), COW-fork the
        write-boundary block if it is shared, then prefill only
        the unmatched tail.  Returns False when the pool cannot
        hold the request yet (request requeued at the head)."""
        import jax
        import jax.numpy as jnp

        opt = self.opt
        pager = self._pager
        phase = self._phases.phase
        n = int(arr.shape[0])
        tokens = arr.tolist()
        ctx = rec.get("ctx")
        with phase("kv.reserve") as reserve:
            reserved = self._reserve_blocks(
                arr, rec, sp, fut, tokens, ctx, reserve.t0)
        if reserved is None:
            return False
        blocks, prefix_len = reserved
        n_tail = n - prefix_len
        row_bt = np.zeros((self.cfg.max_seq // opt.kv_block_size,), np.int32)
        row_bt[:len(blocks)] = blocks
        if opt.prefill_chunk_tokens is not None \
                and n_tail > opt.prefill_chunk_tokens:
            # chunked streaming admission: blocks are reserved
            # (and COW-forked) exactly as the one-shot path above,
            # but the prefill itself runs as block-sized chunks
            # from the engine loop (_prefill_chunk_step) so decode
            # waves interleave with a long prompt instead of
            # stalling behind one giant dispatch
            t_pad = -(-opt.prefill_chunk_tokens // opt.prefill_bucket) \
                * opt.prefill_bucket
            self._telemetry.record_admit(rec, slot, t_pad)
            self._slots[slot] = {
                "state": "prefill", "prompt": arr, "out": [],
                "fut": fut, "rec": rec, "sp": sp, "blocks": blocks,
                "row_bt": row_bt,
                "cursor": ChunkCursor(
                    total=n, chunk_tokens=opt.prefill_chunk_tokens,
                    filled=prefix_len)}
            if opt.spec_decode is not None:
                self._spec_rej[slot] = 0
            if self._recurrent and prefix_len:
                self._cache = self._fns.restore_state(
                    self._cache,
                    np.int32(pager.snapshots.entry_of(
                        tuple(tokens[:prefix_len]))), np.int32(slot))
            self._telemetry.record_kv_stats(pager.stats())
            return True
        t_pad = -(-n_tail // opt.prefill_bucket) * opt.prefill_bucket
        t_pad = max(n_tail, min(t_pad, self.cfg.max_seq))
        self._telemetry.record_admit(rec, slot, t_pad)
        tail_toks = np.zeros((1, t_pad), np.int32)
        tail_toks[0, t_pad - n_tail:] = arr[prefix_len:]
        first = self._launch(
            "prefill",
            self._paged_prefill if sp is None
            else self._fns.paged_prefill_raw,
            len(self._decoding()), req=rec["id"], bucket=t_pad,
            prefix_len=prefix_len, n_tail=n_tail, slot=slot)
        with phase("rng_split"):
            self._rng, k = jax.random.split(self._rng)
        with phase("prefill_dispatch", **_span_attrs(first)) as dispatch:
            state = self._state_arg(tokens, prefix_len, n_tail)
            if sp is not None:
                logits, self._cache = self._fns.paged_prefill_raw(
                    self.params, self._cache,
                    jnp.asarray(tail_toks), jnp.asarray(row_bt),
                    np.int32(prefix_len), np.int32(n_tail),
                    np.int32(slot), state)
                tok = self._sampler_for(sp)(logits, k)
            else:
                tok, self._cache = self._paged_prefill(
                    self.params, self._cache,
                    jnp.asarray(tail_toks), jnp.asarray(row_bt),
                    np.int32(prefix_len), np.int32(n_tail),
                    np.int32(slot), k, state)
            counters = self._counters()
        st = {"prompt": arr, "out": [], "due": 1, "fut": fut,
              "rec": rec, "sp": sp, "blocks": blocks}
        first.update(dispatch=(dispatch.t0, dispatch.t1), tok=tok, st=st,
                     tokens=tokens, experts=counters)
        if self._prefill_attention is not None:
            first["attn"] = self._prefill_attention(
                self.cfg, t_pad, prefix_len, n_tail)
        if sp is None and self._flight and self._chains():
            # decode waves are in flight: this prefill is queued
            # behind them and fenced in its turn (_land); the next
            # wave, behind it and the prefills admitted with it,
            # finds the row's first token on the device, so the
            # chip does not wait for the host to fence a prefill
            # and come back
            self._slots[slot] = st
            self._flight.append(first)
            self._joins[slot] = tok
        else:
            self._drain()
            self._land_first(first)
        return True

    def _land_first(self, item) -> None:
        """Fence a paged prefill and book its first token: the
        second half of `_admit_one_paged`, at once where nothing
        was in flight, else in the prefill's turn."""
        opt = self.opt
        pager = self._pager
        slot, st, tokens = item["slot"], item["st"], item["tokens"]
        arr, rec, fut, blocks = (st["prompt"], st["rec"], st["fut"],
                                 st["blocks"])
        ctx = rec.get("ctx")
        # int() is the engine's existing host fence for the
        # prefill result; the timestamp behind it is the TTFT
        with self._phases.phase("prefill_fence",
                                seq=item["seq"]) as fence:
            first = int(np.asarray(item["tok"])[0])
            self._book_counters("prefill", item["experts"])
            if "attn" in item:
                self._telemetry.record_prefill_attn(*item["attn"])
        self._landed(item, (fence.t0, fence.t1))
        st["due"] -= 1
        self._telemetry.record_first_token(rec)
        # the prompt's full blocks now hold exactly its K/V —
        # index them so later prompts can skip this work.
        # Re-bracketed in the request context: registration is
        # where kvscope books re-prefill waste (a previously
        # evicted key coming back), and the booking must carry
        # this request's tenant/trace
        pager.set_request(rec["id"],
                          ctx.trace_id if ctx is not None else None,
                          tenant=rec.get("tenant"))
        waste = pager.register_prefix(tokens, blocks)
        pager.set_request(None)
        if waste:
            self._telemetry.note_kv_waste(rec, waste)
        if opt.max_new_tokens <= 1 or self._hit_stop([first]):
            self._telemetry.record_finish(rec, n_tokens=1)
            if not fut.done():
                fut.set_result(np.concatenate(
                    [arr, np.asarray([first], np.int32)]))
            self._slots[slot] = None
            self._retire_paged_row(slot, blocks)
            return
        if opt.role == "prefill":
            # disaggregated serving: the request's decode belongs
            # to a decode replica — export the filled block rows,
            # resolve the future with a HandoffCursor package, and
            # free this replica's row/blocks (registered full
            # blocks park in the LRU, keeping the prefix warm)
            self._handoff_out(slot, arr, rec, st["sp"], fut, blocks, first)
            return
        self._cur[slot] = first
        st["out"].append(first)
        self._slots[slot] = st
        self._draft_admit(slot, arr)
        self._telemetry.record_kv_stats(pager.stats())

    def _state_arg(self, tokens, prefix_len, n_tail, chunk=False):
        """What a recurrent family's paged prefill is told beside
        the K/V arguments (models/jamba_decode.py
        jamba_paged_prefill `state`), None for the other families:
        where the slot's state starts, and the snapshot this
        prefill leaves.  The state starts from zeros, from the
        snapshot `match_prefix` trimmed the match to (it holds the
        state after exactly `prefix_len` tokens), or, for a chunk
        of a streamed prompt, from the slot's own rows (the
        previous chunk's, or the snapshot `restore_state` put
        there at admission).  The snapshot is of the prompt's
        deepest block boundary that leaves a token to prefill; the
        prefill (or chunk) that walks over it writes it."""
        if not self._recurrent:
            return None
        bs = self.opt.kv_block_size
        snaps = self._pager.snapshots
        tokens = tuple(tokens)
        if not prefix_len:
            source = dc.STATE_FROM_ZERO
        elif chunk:
            source = dc.STATE_FROM_SLOT
        else:
            source = snaps.entry_of(tokens[:prefix_len])
        boundary = (len(tokens) - 1) // bs * bs
        entry = dc.NO_SNAPSHOT
        if prefix_len < boundary <= prefix_len + n_tail:
            entry = snaps.reserve(tokens[:boundary])
        return np.asarray([source, entry, boundary], np.int32)

    def _tier_save(self, blk) -> tuple:
        """The pager's block-saver callback (serve/kv_tier.py):
        D2H gather of one pool block's K/V rows at eviction time.
        One jitted save_block dispatch slices K and V together and
        device_get pulls both to host in one transfer pair
        (gathering shards on a mesh-sharded cache, so the stored
        copy is always the full replicated block; the jitted
        install_blocks program re-distributes it under the cache's
        shardings on restore).  The copy is timed into the tier's
        d2h bucket trainwatch-style — the tier itself never reads
        a clock."""
        import time as _time

        import jax

        t0 = _time.perf_counter()
        k_rows, v_rows = jax.device_get(
            self._fns.save_block(self._cache, np.int32(blk)))
        self._pager.tier.note_d2h(_time.perf_counter() - t0)
        return k_rows, v_rows

    def _retire_paged_row(self, slot, blocks) -> None:
        """Free a finished/errored row's blocks.  The row's table
        is pointed at the null block FIRST: an idle row's decode
        step still scatter-writes (masked garbage), which must
        never land in a block the pager may re-hand out."""
        self._cache = self._clear_row(self._cache, np.int32(slot))
        self._pager.release(blocks)
        self._telemetry.record_kv_stats(self._pager.stats())

    def _handoff_out(self, slot, arr, rec, sp, fut, blocks, first) -> None:
        """Prefill-role park: export the request's filled block
        rows and resolve its future with a `HandoffCursor` package
        the router forwards to a decode replica.  The fast path
        keeps the rows on device (same-process handoff is a
        device-side gather the install splices straight back); the
        staged path pulls them to host so the package can cross a
        process/host boundary as a D2H→H2D hop.  Either way the
        rows are the EXACT bytes prefill wrote — the decode-side
        splice re-creates the monolithic engine's post-prefill
        cache state bit-for-bit.  This replica's row and blocks
        are freed immediately; registered full blocks park in the
        pager LRU, so the prefix index stays warm for
        prefix-affinity admissions."""
        import time as _time

        import jax
        import jax.numpy as jnp

        opt = self.opt
        n = int(arr.shape[0])
        n_blk = -(-n // opt.kv_block_size)
        ids = self._handoff_ids
        ids[:] = 0
        ids[:n_blk] = blocks[:n_blk]
        t0 = _time.perf_counter()
        k_rows, v_rows = self._fns.kv_handoff_export(
            self._cache, jnp.asarray(ids))
        if opt.handoff_staged:
            k_rows, v_rows = jax.device_get((k_rows, v_rows))
            path = "staged"
        else:
            # fence so the export window is real device time, not
            # just the dispatch (the tier d2h discipline)
            jax.block_until_ready(k_rows)
            path = "fast"
        t1 = _time.perf_counter()
        nbytes = self._pager.bytes_per_block * n_blk
        # the decode replica's telemetry record is pre-populated
        # from this meta so the merged request anatomy keeps ONE
        # unbroken clock: router enqueue → prefill → handoff →
        # decode, with the critical path still summing to e2e
        meta = {
            "prompt_len": n,
            "enqueue": rec["enqueue"],
            "engine_enqueue": rec["engine_enqueue"],
            "admit": rec["admit"],
            "first_token": rec["first_token"],
            "bucket": rec["bucket"],
            "requeues": rec.get("requeues", 0),
            "requeue_ts": rec.get("requeue_ts"),
            "kv_reserve": rec.get("kv_reserve"),
            "kv_fetch": rec.get("kv_fetch"),
            "prefill_chunks": rec.get("prefill_chunks"),
            "tenant": rec.get("tenant"),
            "ctx": rec.get("ctx"),
        }
        pkg = HandoffCursor(
            prompt=arr, first_token=int(first), n_tokens=n,
            n_blocks=n_blk, k_rows=k_rows, v_rows=v_rows,
            nbytes=nbytes, path=path, t_export0=t0, t_export1=t1,
            meta=meta, sampling=sp)
        self._telemetry.record_handoff_out(
            rec, blocks=n_blk, nbytes=nbytes, path=path)
        self._retire_paged_row(slot, blocks)
        if not fut.done():
            fut.set_result(pkg)

    def _admit_one_handoff(self, pkg, rec, fut, slot) -> bool:
        """Decode-role admission of a prefilled handoff package:
        allocate a fresh block chain, splice the exported rows +
        table/pos/start into this replica's pool in one donated
        dispatch, and enter decode at the package's first token.
        `pos = prompt_len`, `start = 0` — exactly the state
        `paged_prefill` leaves — so the first decode step here is
        bit-identical to the monolithic engine by construction.
        Returns False when the pool cannot hold the chain yet
        (package requeued at the head, admission pauses)."""
        import time as _time

        import jax
        import jax.numpy as jnp

        opt = self.opt
        pager = self._pager
        arr = pkg.prompt
        n = int(pkg.n_tokens)
        ctx = rec.get("ctx")
        pager.set_request(rec["id"],
                          ctx.trace_id if ctx is not None else None,
                          tenant=rec.get("tenant"))
        need = pager.blocks_needed(
            n, opt.max_new_tokens,
            headroom=opt.spec_decode.k if opt.spec_decode is not None
            else 0)
        alloc = pager.allocate(need)
        if alloc is None:
            pager.set_request(None)
            self._telemetry.record_requeue(
                rec, need=need, reason="handoff_pool_exhausted")
            self._queue.push_front((pkg, rec, pkg.sampling), fut)
            return False
        n_blk = int(pkg.n_blocks)
        ids = self._handoff_ids
        ids[:] = 0
        ids[:n_blk] = alloc[:n_blk]
        row_bt = np.zeros((self.cfg.max_seq // opt.kv_block_size,), np.int32)
        row_bt[:need] = alloc
        k_rows, v_rows = self._to_engine(
            (jnp.asarray(pkg.k_rows), jnp.asarray(pkg.v_rows)))
        launch = self._launch(
            "handoff", self._fns.kv_handoff_install,
            len(self._decoding()), req=rec["id"], fused=True)
        t_splice = _time.perf_counter()
        self._cache = self._fns.kv_handoff_install(
            self._cache, jnp.asarray(ids), k_rows, v_rows,
            np.int32(slot), jnp.asarray(row_bt), np.int32(n))
        # fence: the handoff window must time the transfer+splice,
        # not the dispatch (the tier-restore h2d discipline)
        jax.block_until_ready(self._cache)
        t_done = _time.perf_counter()
        self._landed(launch, (t_splice, t_done))
        pkg.installed = True
        # index the imported full blocks so later prompts sharing
        # the prefix hit HERE — the router's prefix-affinity stage
        # then skips prefill entirely for them
        pager.note_handoff_import(arr.tolist(), alloc)
        pager.set_request(None)
        self._telemetry.record_kv_handoff(
            rec, pkg.t_export0, t_done, blocks=n_blk,
            nbytes=int(pkg.nbytes), path=pkg.path)
        self._telemetry.record_admit_handoff(rec, slot)
        first = int(pkg.first_token)
        self._cur[slot] = first
        self._slots[slot] = {"prompt": arr, "out": [first],
                             "fut": fut, "rec": rec,
                             "sp": pkg.sampling, "blocks": alloc}
        self._draft_admit(slot, arr)
        self._telemetry.record_kv_stats(pager.stats())
        return True

    def _prefill_chunk_step(self, candidates) -> None:
        """Run AT MOST ONE chunk of pending prefill — the engine
        loop alternates `decode wave → one chunk → decode wave`.
        Fairness is round-robin over the slots mid-prefill
        (`candidates`), so one 32k prompt cannot consume
        consecutive chunk windows while another long prompt waits.

        Each chunk is the existing paged_prefill program with
        prefix_len = tokens already filled — prior chunks are
        literally resident prefix blocks — so the chunked result
        is bit-identical to one-shot prefill by construction, and
        the program compiles once per prefill_bucket-padded chunk
        shape.  Between chunks the row is PARKED (null block
        table): decode waves scatter-write masked garbage into
        every row at its pos, and those writes must land in the
        null block, never in this row's half-filled real blocks;
        the next chunk re-installs row_bt/pos/start absolutely.

        The chunk is one leaf phase, ``prefill_chunk``, dispatch and
        fence together: its launch is ``fused``."""
        opt = self.opt
        # next candidate strictly after the cursor, cyclically
        i = min(candidates,
                key=lambda s: ((s - self._chunk_rr) % opt.max_slots)
                or opt.max_slots)
        self._chunk_rr = i
        st = self._slots[i]
        cur = st["cursor"]
        filled = cur.filled
        c = cur.next_chunk()
        t_pad = -(-c // opt.prefill_bucket) * opt.prefill_bucket
        t_pad = max(c, min(t_pad, self.cfg.max_seq))
        launch = self._launch(
            "chunk",
            self._paged_prefill if st["sp"] is None
            else self._fns.paged_prefill_raw,
            len(self._decoding()), req=st["rec"]["id"], bucket=t_pad,
            prefix_len=filled, n_tail=c, slot=i, fused=True)
        with self._phases.phase("prefill_chunk",
                                **_span_attrs(launch)) as leaf:
            self._run_chunk(launch)
        self._landed(launch, (leaf.t0, leaf.t1))

    def _run_chunk(self, launch) -> None:
        """The chunk `launch` describes (its slot's next ``n_tail``
        prompt tokens behind the ``prefix_len`` it holds, padded to
        ``bucket``): dispatch, fence, and what follows a prompt's
        last chunk."""
        import time as _time

        import jax
        import jax.numpy as jnp

        opt = self.opt
        i, filled, c, t_pad = (launch["slot"], launch["prefix_len"],
                               launch["n_tail"], launch["bucket"])
        st = self._slots[i]
        arr = st["prompt"]
        n = int(arr.shape[0])
        cur = st["cursor"]
        last = filled + c >= n
        chunk_toks = np.zeros((1, t_pad), np.int32)
        chunk_toks[0, t_pad - c:] = arr[filled:filled + c]
        t0 = _time.perf_counter()
        if last:
            self._rng, k = jax.random.split(self._rng)
        else:
            # intermediate chunks discard their sample, so the
            # fused program runs under a constant key — the
            # engine RNG stream stays identical to a one-shot
            # admission (exactly one split, at the final chunk)
            k = self._dummy_key
        first = None
        state = self._state_arg(arr.tolist(), filled, c, chunk=True)
        if st["sp"] is not None:
            logits, self._cache = self._fns.paged_prefill_raw(
                self.params, self._cache, jnp.asarray(chunk_toks),
                jnp.asarray(st["row_bt"]), np.int32(filled),
                np.int32(c), np.int32(i), state)
            if last:
                tok = self._sampler_for(st["sp"])(logits, k)
                first = int(np.asarray(tok)[0])
            else:
                # host fence so the chunk window is real device
                # time, mirroring the one-shot path's int()
                np.asarray(logits[0, 0])
        else:
            tok, self._cache = self._paged_prefill(
                self.params, self._cache, jnp.asarray(chunk_toks),
                jnp.asarray(st["row_bt"]), np.int32(filled),
                np.int32(c), np.int32(i), k, state)
            counters = self._counters()
            # the chunk's host fence (the one-shot path's int());
            # intermediate chunks discard the value
            first = int(np.asarray(tok)[0])
            self._book_counters("prefill", counters)
        if self._prefill_attention is not None:
            launch["attn"] = self._prefill_attention(
                self.cfg, t_pad, filled, c)
            self._telemetry.record_prefill_attn(*launch["attn"])
        t1 = _time.perf_counter()
        cur.advance(c)
        self._telemetry.record_prefill_chunk(
            st["rec"], t0, t1, tokens=c, bucket=t_pad, last=last)
        # journal the fill under this request's id/trace, same
        # bracketing idiom as the admission reservation window
        ctx = st["rec"].get("ctx")
        self._pager.set_request(
            st["rec"]["id"],
            ctx.trace_id if ctx is not None else None,
            tenant=st["rec"].get("tenant"))
        self._pager.note_fill(c, partial=not last)
        self._pager.set_request(None)
        if not last:
            self._cache = self._clear_row(self._cache, np.int32(i))
            return
        rec, fut, blocks = st["rec"], st["fut"], st["blocks"]
        self._telemetry.record_first_token(rec)
        # registration under the request context: kvscope books
        # re-prefill waste (previously-evicted keys returning)
        # against this request's tenant
        self._pager.set_request(
            rec["id"], ctx.trace_id if ctx is not None else None,
            tenant=rec.get("tenant"))
        waste = self._pager.register_prefix(arr.tolist(), blocks)
        self._pager.set_request(None)
        if waste:
            self._telemetry.note_kv_waste(rec, waste)
        if opt.max_new_tokens <= 1 or self._hit_stop([first]):
            self._telemetry.record_finish(rec, n_tokens=1)
            if not fut.done():
                fut.set_result(np.concatenate(
                    [arr, np.asarray([first], np.int32)]))
            self._slots[i] = None
            self._retire_paged_row(i, blocks)
            return
        if opt.role == "prefill":
            # chunked long prompts hand off too: the last chunk's
            # filled rows move wholesale, so a 32k prompt never
            # decodes on the prefill replica it streamed through
            self._slots[i] = None
            self._handoff_out(i, arr, rec, st["sp"], fut, blocks, first)
            return
        self._cur[i] = first
        st["state"] = "decode"
        st["out"] = [first]
        self._draft_admit(i, arr)
        self._telemetry.record_kv_stats(self._pager.stats())

    def _finish_slot(self, i, st) -> None:
        """Retire a finished slot NOW — the freed slot (and its
        paged blocks) is admissible in the same engine wave."""
        self._telemetry.record_finish(st["rec"], n_tokens=len(st["out"]))
        if not st["fut"].done():
            # st["out"] is a python int list — no device fetch
            tail = np.asarray(st["out"], np.int32)
            st["fut"].set_result(np.concatenate([st["prompt"], tail]))
        self._slots[i] = None           # slot freed NOW
        if self._pager is not None:
            self._retire_paged_row(i, st["blocks"])

    def _mixed_step(self, key):
        """One decode step when any active slot overrides the
        engine SamplingParams: the logits-twin program once, then
        one jitted sampler dispatch per DISTINCT SamplingParams
        among active slots, rows gathered host-side."""
        import jax
        import jax.numpy as jnp

        opt = self.opt
        logits, self._cache = self._fns.pool_logits(
            self.params, self._cache, jnp.asarray(self._cur))
        toks = np.zeros((opt.max_slots,), np.int32)
        groups: Dict[Any, list] = {}
        for i, st in enumerate(self._slots):
            if st is None or st.get("state") == "prefill":
                continue
            groups.setdefault(st["sp"] or self._default_sp, []).append(i)
        for sp, rows in groups.items():
            key, kk = jax.random.split(key)
            full = np.asarray(self._sampler_for(sp)(logits, kk))
            for r in rows:
                toks[r] = full[r]
        return toks

    def _spec_round(self) -> int:
        """One speculative round over the whole slot pool: draft
        proposes k tokens per row, ONE target verify dispatch
        checks all k+1 positions, accepted tokens are emitted and
        the caches advance by exactly the kept count.  Returns the
        number of tokens emitted (for step telemetry)."""
        import time as _time

        import jax
        import jax.numpy as jnp

        from ray_tpu.models.decode_common import ngram_propose

        opt = self.opt
        t_round = _time.perf_counter()
        kd = opt.spec_decode.k
        qprobs = None
        if self._draft_params is not None:
            self._rng, dk = jax.random.split(self._rng)
            if self._spec_sampled:
                drafts, qprobs, self._draft_cache = \
                    self._fns.draft_propose(
                        self._draft_params, self._draft_cache,
                        jnp.asarray(self._cur),
                        jnp.asarray(self._spec_rej), dk)
            else:
                drafts, self._draft_cache = \
                    self._fns.draft_propose(
                        self._draft_params, self._draft_cache,
                        jnp.asarray(self._cur),
                        jnp.asarray(self._spec_rej), dk)
            drafts = np.asarray(drafts)
        else:
            # host-side n-gram draft over each request's own
            # history: zero extra weights, zero extra dispatches
            drafts = np.zeros((opt.max_slots, kd), np.int32)
            for i, st in enumerate(self._slots):
                if st is None or st.get("state") == "prefill":
                    continue
                drafts[i] = ngram_propose(
                    st["prompt"].tolist() + st["out"], kd,
                    order=opt.spec_decode.ngram_order)
        block = np.concatenate([self._cur[:, None], drafts], axis=1)
        self._rng, vk = jax.random.split(self._rng)
        if self._spec_sampled:
            out_toks, n_acc, self._cache = self._fns.spec_verify(
                self.params, self._cache, jnp.asarray(block), vk,
                qprobs)
        else:
            out_toks, n_acc, self._cache = self._fns.spec_verify(
                self.params, self._cache, jnp.asarray(block), vk)
        # the round's one deliberate host fence (same role as the
        # plain engine's np.asarray(toks))
        out_toks = np.asarray(out_toks)
        n_acc = np.asarray(n_acc)
        t_done = _time.perf_counter()
        round_dur = t_done - t_round
        total = 0
        for i, st in enumerate(self._slots):
            if st is None or st.get("state") == "prefill":
                # mid-prefill rows are parked (null block table):
                # the pool-wide verify dispatch covers them but
                # their outputs are discarded
                continue
            n = int(n_acc[i])
            self._telemetry.record_spec(st["rec"], proposed=kd,
                                        accepted=n,
                                        dur_s=round_dur)
            finished = False
            emitted = 0
            for t in out_toks[i, :n + 1]:
                st["out"].append(int(t))
                total += 1
                emitted += 1
                if len(st["out"]) >= opt.max_new_tokens \
                        or self._hit_stop(st["out"]):
                    finished = True
                    break
            # one dispatch emitted `emitted` tokens for this row —
            # they share the round-end timestamp in the ITL trail
            self._telemetry.record_token(st["rec"], n=emitted, now=t_done)
            # the correction token is always the row's new `cur`
            # (it has no K/V yet — exactly a fresh sampled token)
            self._cur[i] = out_toks[i, n]
            self._spec_rej[i] = 0 if finished else kd - n
            if finished:
                self._finish_slot(i, st)
        return total

    def _decoding(self) -> dict:
        """The rows a decode wave samples for: slot -> its state."""
        return {i: st for i, st in enumerate(self._slots)
                if st is not None and st.get("state") != "prefill"}

    def _mixed(self) -> bool:
        """Whether a decoding row overrides the engine's
        SamplingParams (the wave then samples by groups)."""
        return any(st["sp"] is not None for st in self._decoding().values())

    def _waves(self) -> list:
        """The decode waves in flight, oldest first."""
        return [w for w in self._flight if w["kind"] == "decode"]

    def _chains(self) -> bool:
        """Whether the next decode wave can be queued behind what
        is in flight and read the newest wave's tokens on the
        device: every row decoding now was decoding in that wave or
        has its first token waiting on the device (`_joins`; any
        other row that joined since has it on the host, in `_cur`),
        the wave is the plain one, and some row outlives
        what is in flight by its count (a wave for rows that all
        end there would be a step for nothing).  A row that ends in
        a wave in flight, by its count or by a stop token the host
        sees only when that wave lands, is stepped by the waves
        behind it: those writes land in blocks it reserved or in
        the null block, before `clear_row` and before any later
        tenant's prefill, and the tokens are dropped (`_land`)."""
        max_new = self.opt.max_new_tokens
        waves = self._waves()
        if not waves or self.opt.spec_decode is not None \
                or self._mixed():
            return False
        rows, before = self._decoding(), waves[-1]["stepped"]
        return (all(before.get(i) is st or i in self._joins
                    for i, st in rows.items())
                and any(len(st["out"]) + st.get("due", 0) < max_new
                        for st in rows.values()))

    def _depth(self) -> int:
        """How many decode waves to leave in flight when the host
        goes to fence the oldest: as many as fit in `_AHEAD_S` on
        the chip by what the last waves took.  None where one wave
        is longer than that (the chip is then fenced after every
        wave, and a request admitted next starts at once: a long
        step hides the host by itself), none before a wave has been
        timed, and none while a chunked prompt streams in (its
        chunk is fenced between two waves)."""
        if not self._wave_s or any(
                st is not None and st.get("state") == "prefill"
                for st in self._slots):
            return 0
        step_s = sorted(self._wave_s)[len(self._wave_s) // 2]
        return min(_AHEAD_MAX, int(_AHEAD_S / max(step_s, 1e-4)))

    def _wave(self) -> None:
        """Dispatch one plain decode wave and leave it in flight.
        Where waves before it still are, this one reads the
        newest one's tokens where they are, on the device, and is
        queued behind it BEFORE the host fences and emits any of
        them: the chip goes from one step to the next while the
        host works, or is held up (`_chains` says when that is
        sound, `_depth` how far ahead).  The first tokens of the
        prefills admitted since, not yet fenced, are put into
        their slots' places on the device (`_joins`)."""
        import jax
        import jax.numpy as jnp

        phase = self._phases.phase
        if self.opt.temperature > 0.0:
            with phase("rng_split"):
                self._rng, k = jax.random.split(self._rng)
        else:
            # every decoding row is greedy: the wave's sampler is
            # an argmax and reads no key, so none is drawn (the
            # engine RNG advances on the waves that sample and at
            # each admission, as before)
            k = self._dummy_key
        waves = self._waves()
        rows = self._decoding()
        item = self._launch("decode", self._pool_step, len(rows))
        with phase("decode_dispatch", **_span_attrs(item)) as wave:
            toks = waves[-1]["toks"] if waves \
                else jnp.asarray(self._cur)
            for slot, tok in self._joins.items():
                toks = self._fns.join_token(toks, np.int32(slot), tok)
            self._joins.clear()
            toks, self._cache = self._pool_step(
                self.params, self._cache, toks, k)
            counters = self._counters()
        walked = 0
        for st in rows.values():
            due = st.get("due", 0)
            # the row's position in this wave, from the lengths the
            # host holds: its prompt, its tokens landed and in flight
            # (the first of them came out of the prefill)
            walked += -(-(len(st["prompt"]) + len(st["out"]) + due - 1)
                        // self.opt.kv_block_size)
            st["due"] = due + 1
        item.update(dispatch=(wave.t0, wave.t1), toks=toks, stepped=rows,
                    experts=counters)
        if self._pager is not None:
            item["walk"] = (walked, len(rows) * (
                self.cfg.max_seq // self.opt.kv_block_size))
            item["reach"] = self._reserved_by_reach()
        self._flight.append(item)

    def _reserved_by_reach(self):
        """(bytes the resident requests hold reserved in pool blocks,
        in per-slot windows, and what every layer at full reach would
        reserve for them), now: `Telemetry.record_kv_reach`'s
        arguments."""
        reach = self._reach
        tokens = self._pager.blocks_in_use * self.opt.kv_block_size
        resident = sum(st is not None for st in self._slots)
        return (tokens * reach["pool_bytes_per_token"],
                resident * reach["window_bytes_per_slot"],
                tokens * reach["full_reach_bytes_per_token"])

    def _land(self) -> None:
        """Fence what has been in flight longest.  A decode wave:
        emit its tokens to the rows it sampled for that are still
        there.  A prefill: book its first token."""
        item = self._flight.popleft()
        if item["kind"] != "decode":
            self._land_first(item)
            return
        stepped = item["stepped"]
        with self._phases.phase("decode_fence", seq=item["seq"]) as fence:
            # the wave's one host fence
            toks = np.asarray(item["toks"])
            self._book_counters("decode", item["experts"])
            if "walk" in item:
                self._telemetry.record_kv_walk(*item["walk"])
                self._telemetry.record_kv_reach(*item["reach"])
        self._landed(item, (fence.t0, fence.t1))
        rows = {i: st for i, st in stepped.items()
                if self._slots[i] is st}
        if not rows:
            return      # every row it stepped has ended since
        for st in rows.values():
            st["due"] -= 1
        # a step's walltime: from its dispatch, or from the wave
        # before it landing where it was queued behind that one
        took = fence.t1 - max(item["dispatch"][0], self._t_landed)
        self._telemetry.record_step(len(rows), took, now=fence.t1)
        self._wave_s.append(took)
        self._t_landed = fence.t1
        self._emit(rows, toks, fence.t1)

    def _drain(self) -> None:
        """Land everything in flight, oldest first."""
        while self._flight:
            self._land()
        self._joins.clear()     # their tokens are in `_cur` now

    def _emit(self, rows, toks, t_wave) -> None:
        """One wave's tokens to their rows; a row that is done is
        retired now."""
        max_new = self.opt.max_new_tokens
        with self._phases.phase("emit"):
            for i, st in rows.items():
                st["out"].append(int(toks[i]))
                self._telemetry.record_token(st["rec"], now=t_wave)
                self._cur[i] = toks[i]
                if len(st["out"]) >= max_new \
                        or self._hit_stop(st["out"]):
                    self._finish_slot(i, st)

    async def _step(self) -> bool:
        """One iteration's work, inside the open
        ``raytpu.engine.step``: admit, one decode wave (or one
        speculative round), the per-wave hooks, at most one chunk
        of pending prefill.  Every device call and every host
        chore sits in a leaf phase (_private/scopes.py
        ENGINE_PHASES).  False when admission left nothing active
        (every queued request was rejected or finished in its
        prefill): the loop then goes round without yielding."""
        import asyncio

        import jax

        opt = self.opt
        phase = self._phases.phase
        with phase("admit"):
            self._admit_pending()
        if self._flight and not self._chains():
            self._drain()
        prefilling = [
            i for i, s in enumerate(self._slots)
            if s is not None and s.get("state") == "prefill"]
        n_active = sum(s is not None for s in self._slots)
        if not n_active:
            self._give_up()         # waves whose rows all ended
            return False
        n_decode = n_active - len(prefilling)
        if self._chaos is not None and n_decode:
            delay_s = self._chaos.token_delay_s(self._replica_label)
            if delay_s > 0:
                # chaos token delay: the loop still heartbeats but
                # its requests go token-silent — only the stall
                # sweep sees this
                await asyncio.sleep(delay_s)
        # step walltime: dispatch + the np.asarray host fence the
        # engine already performs, read off the phases' own stamps
        # — no second perf_counter pair, no extra device sync
        if n_decode and opt.spec_decode is not None:
            launch = self._launch("spec", self._fns.spec_verify,
                                  n_decode, fused=True)
            with phase("spec_round", **_span_attrs(launch)) as rnd:
                n_tokens = self._spec_round()
            self._landed(launch, (rnd.t0, rnd.t1))
            self._telemetry.record_step(
                n_decode, rnd.t1 - rnd.t0, n_tokens=n_tokens)
        elif n_decode:
            if self._mixed():
                launch = self._launch("mixed", self._fns.pool_logits,
                                      n_decode, fused=True)
                with phase("rng_split"):
                    self._rng, k = jax.random.split(self._rng)
                with phase("decode_dispatch",
                           **_span_attrs(launch)) as wave:
                    toks = self._mixed_step(k)   # fences inside
                self._landed(launch, (wave.t0, wave.t1))
                self._telemetry.record_step(
                    n_decode, wave.t1 - wave.t0, now=wave.t1)
                self._emit(self._decoding(), toks, wave.t1)
            else:
                self._wave()
                depth = self._depth()
                while len(self._waves()) > depth:
                    self._land()
        with phase("hooks"):
            if self._telemetry.slo is not None:
                # throttled burn-rate watchdog: breach / storm
                # transitions postmortem-dump the flight record
                self._telemetry.slo.check()
            if self._health is not None:
                # throttled liveness sweep: healthy replicas' waves
                # age their peers' heartbeats even while the
                # router is quiet
                self._health.maybe_probe()
            if self._pager is not None:
                # kvscope occupancy ring: one pool snapshot per
                # wave (host counters only, no device sync) — the
                # timeline a postmortem replays
                self._pager.sample_occupancy()
        if prefilling:
            self._prefill_chunk_step(prefilling)
        return True

    async def _engine(self):
        """The scheduler loop: admit → one pooled decode step (or
        one speculative draft+verify round) over the decoding
        slots → retire finished slots → at most ONE chunk of
        pending chunked prefill → yield (so new requests enqueue
        mid-generation).  The decode-wave/chunk alternation is the
        chunked-prefill scheduler: a long prompt costs the other
        slots one chunk window per wave, never a full prefill."""
        import asyncio

        phase = self._phases.phase
        while True:
            try:
                if self._chaos is not None and \
                        self._chaos.frozen(self._replica_label):
                    # chaos freeze: poll without processing and —
                    # crucially — without heartbeating, exactly
                    # what a wedged host looks like to healthwatch
                    self._held_from = None
                    await asyncio.sleep(self._chaos.freeze_poll_s)
                    continue
                if self._health is not None:
                    # one liveness stamp per wave (a dict store)
                    self._health.heartbeat(self._replica_label)
                if not len(self._queue) and all(
                        s is None for s in self._slots):
                    # nothing queued, nothing running: park
                    self._give_up()
                    self._held_from = None
                    self._wake.clear()
                    if self._health is not None:
                        # parked-idle is not a failure: the probe
                        # skips idle replicas until the next
                        # heartbeat re-arms the clock
                        self._health.note_idle(self._replica_label)
                    await self._wake.wait()
                    continue
                # one raytpu.engine.step span per iteration with
                # work in it; its leaf phases partition it
                self._iteration += 1
                with self._phases.step(n=self._iteration) as step:
                    if self._held_from is None:
                        self._held_from = step.t0
                    if await self._step():
                        with phase("yield") as let_go:
                            # callers enqueue mid-flight here
                            await asyncio.sleep(0)
                        # how long they waited for it: the loop ran
                        # from the last yield (or its waking) to this
                        self._telemetry.record_hold(
                            let_go.t0 - self._held_from)
                        self._held_from = let_go.t1
                continue
            except Exception as e:  # noqa: BLE001 - fail loudly
                # crash postmortem: the journal around the failure
                # is exactly what the flight recorder exists for —
                # dump BEFORE unwinding mutates engine state
                self._telemetry.flightrec.record(
                    "engine_crash", error=repr(e)[:200])
                try:
                    self._telemetry.flightrec.dump(
                        reason="engine_crash",
                        context={"error": repr(e)[:500]})
                except Exception:  # noqa: BLE001 - dump best-effort
                    pass
                self._give_up()
                self._held_from = None
                for i, st in enumerate(self._slots):
                    if st is not None:
                        self._telemetry.record_error(st["rec"], error=repr(e))
                        if not st["fut"].done():
                            st["fut"].set_exception(e)
                        if self._pager is not None \
                                and "blocks" in st:
                            self._pager.release(st["blocks"])
                    self._slots[i] = None
                for (arr, rec, _sp), fut in self._queue.pop(len(self._queue)):
                    self._telemetry.record_error(rec, error=repr(e))
                    if not fut.done():
                        fut.set_exception(e)
            # after a crash: yield so callers see their exceptions
            await asyncio.sleep(0)

    async def __call__(self, prompt, sampling=None, *, tenant=None,
                       enqueue_ts=None, trace=None):
        """`tenant` / `enqueue_ts` / `trace` are the fleet-router
        hooks (serve/router.py): the router backdates `enqueue_ts`
        to the instant the request entered ITS queue, so this
        engine's telemetry charges router wait to the request's
        TTFT/e2e series, `tenant` tags the record for per-class
        SLO slicing, and `trace` is the tracebus TraceContext born
        at router submit (a fresh engine-origin context is minted
        when absent).  Direct callers omit all three."""
        import asyncio

        opt = self.opt
        sp = None
        if sampling is not None:
            if not isinstance(sampling, SamplingParams):
                raise ValueError(
                    "sampling must be a SamplingParams, got "
                    f"{type(sampling).__name__}")
            if opt.spec_decode is not None:
                raise ValueError(
                    "per-request sampling overrides are not "
                    "supported with spec_decode (the verify "
                    "program bakes in ONE sampling config; build "
                    "a separate deployment per config)")
            if sampling != self._default_sp:
                sp = sampling
        if self._wake is None:
            self._wake = asyncio.Event()
        if self._engine_task is None or self._engine_task.done():
            self._engine_task = asyncio.get_running_loop(
            ).create_task(self._engine())
        # host-side prompt normalization (python ints, no device fetch)
        # graftcheck: disable=blocking-call-in-async(host-side int normalization)
        arr = np.asarray(prompt, np.int32).reshape(-1)
        if opt.admission_policy is not None:
            # the control loop: telemetry percentiles feed the
            # shed decision BEFORE the request costs the engine
            # anything.  The HBM-headroom gate needs a FRESH
            # ledger (engine_stats serves the last composed one):
            # refresh only when that gate is armed — the device
            # allocator query stays off the default admit path
            if getattr(opt.admission_policy, "min_headroom_bytes",
                       None) is not None \
                    and getattr(self, "_pager", None) is not None:
                self._telemetry.record_kv_scope(self._compose_kv_scope())
            shed = opt.admission_policy.decide(
                self._telemetry.engine_stats(), len(self._queue))
            if shed is not None:
                rec = self._telemetry.record_enqueue(
                    int(arr.shape[0]), now=enqueue_ts,
                    tenant=tenant, ctx=trace)
                self._telemetry.record_reject(
                    rec, reason=f"load shed: {shed}",
                    label=f"shed_{shed}")
                raise OverloadedError(
                    f"request shed ({shed}): engine over SLO "
                    f"with {len(self._queue)} queued")
        rec = self._telemetry.record_enqueue(
            int(arr.shape[0]), now=enqueue_ts, tenant=tenant,
            ctx=trace)
        fut = self._queue.put((arr, rec, sp))
        self._wake.set()
        return await fut

    async def admit_prefilled(self, pkg):
        """Second-stage entry point for disaggregated serving: the
        fleet router forwards a prefill replica's `HandoffCursor`
        package here.  The package's telemetry meta seeds a record
        that keeps the request's original enqueue/admit/TTFT
        clock, so the merged anatomy spans both replicas with one
        unbroken critical path.  Decode starts from the package's
        first token after the block splice — no prefill runs on
        this engine for the request."""
        import asyncio

        opt = self.opt
        if opt.role == "prefill":
            raise ValueError(
                "admit_prefilled needs a decode-capable engine "
                "(role='decode' or 'both'); this replica is "
                "role='prefill'")
        if self._pager is None:
            raise ValueError("admit_prefilled requires kv_layout='paged'")
        if not isinstance(pkg, HandoffCursor):
            raise ValueError(
                "admit_prefilled takes a HandoffCursor, got "
                f"{type(pkg).__name__}")
        if pkg.sampling is not None and opt.spec_decode is not None:
            raise ValueError(
                "per-request sampling overrides are not "
                "supported with spec_decode (the verify program "
                "bakes in ONE sampling config)")
        if self._wake is None:
            self._wake = asyncio.Event()
        if self._engine_task is None or self._engine_task.done():
            self._engine_task = asyncio.get_running_loop(
            ).create_task(self._engine())
        rec = self._telemetry.record_enqueue_handoff(pkg.meta)
        fut = self._queue.put((pkg, rec, pkg.sampling))
        self._wake.set()
        return await fut

    def shutdown_engine(self) -> None:
        """Stop the background engine task (direct-instance
        drivers — traffic generator, bench — call this so their
        event loop can close cleanly; serve replicas die with
        their actor process and never need it)."""
        task, self._engine_task = self._engine_task, None
        if task is not None and not task.done():
            task.cancel()

    def _compose_kv_scope(self):
        """The full engine_stats()["kv_scope"] block: the pager's
        occupancy/forensics half plus the unified HBM ledger
        (pool bytes + live allocator view + graftcheck's audited
        per-program peak budget → headroom_bytes per chip).  The
        budget term is cached after the first lookup — graftcheck
        import cost is paid once per deployment."""
        from ray_tpu._private.device_stats import \
            device_memory_stats
        from ray_tpu.serve.kvscope import (
            hbm_ledger, serve_program_budget_bytes)

        pager = self._pager
        block = pager.kv_scope_stats()
        budget = self._kvscope_budget
        if budget is None:
            budget = self._kvscope_budget = serve_program_budget_bytes()
        mesh = self.mesh
        devices = (list(mesh.devices.flat) if mesh is not None else None)
        pool_per_chip = (pager.bytes_per_block * pager.num_blocks
                         // pager.tensor_shards)
        block["hbm_ledger"] = hbm_ledger(
            pool_bytes_per_chip=pool_per_chip,
            device_stats=device_memory_stats(devices),
            program_budget_bytes=budget)
        return block
