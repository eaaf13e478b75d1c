"""@serve.batch: transparent request batching inside a replica.

Reference analog: python/ray/serve/batching.py (@serve.batch collects
concurrent calls into one vectorized invocation).  TPU rationale is
stronger than the reference's GPU one: a jitted model compiled for
batch N amortizes dispatch and fills the MXU, so the replica should see
lists, not single requests.

Usage (async methods only — batching needs an event loop to park
pending callers on):

    @serve.deployment
    class Model:
        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.01)
        async def __call__(self, inputs: List[np.ndarray]):
            return model_apply(self.params, np.stack(inputs))

Each caller awaits its own element of the returned list.
"""

from __future__ import annotations

import asyncio
import functools
from dataclasses import dataclass
from typing import Any, Callable, List, Optional


@dataclass
class ChunkCursor:
    """Progress cursor for chunked streaming prefill (serve/engine.py):
    a queued long prompt is admitted once but filled over several
    block-aligned ``paged_prefill`` calls interleaved with decode
    waves, and the engine's slot record carries this cursor between
    waves.  ``filled`` counts prompt tokens already resident in KV
    blocks (including any reused prefix), so the next chunk's program
    call gets ``prefix_len == filled``."""

    total: int          # prompt length in tokens
    chunk_tokens: int   # scheduler budget per prefill turn
    filled: int = 0     # tokens already written to KV blocks
    chunks_done: int = 0

    @property
    def remaining(self) -> int:
        return self.total - self.filled

    @property
    def done(self) -> bool:
        return self.filled >= self.total

    def next_chunk(self) -> int:
        """Token count for the next prefill call (last one may be
        short)."""
        return min(self.chunk_tokens, self.remaining)

    def advance(self, n: int) -> None:
        self.filled += n
        self.chunks_done += 1


@dataclass
class HandoffCursor:
    """State of one disaggregated prefill→decode KV handoff
    (serve/engine.py + serve/router.py two-stage dispatch): a prefill
    replica that finishes a request's last chunk resolves its future
    with this cursor instead of generated tokens, and the router
    forwards it to the chosen decode replica, whose admission path
    installs the exported block rows and resumes decoding at
    ``first_token``.

    ``k_rows``/``v_rows`` are the filled KV block rows gathered by the
    prefill engine's ``kv_handoff_export`` program — jax device arrays
    on the same-process fast path, host numpy after the D2H hop on the
    staged path (``path`` records which).  ``meta`` carries the
    prefill-side telemetry timing (enqueue/admit/first-token/chunk
    windows) so the decode replica's record decomposes exactly like a
    monolithic engine's, plus the new ``handoff_ms`` leg."""

    prompt: Any                # np.int32 prompt token array
    first_token: int           # sampled at the prefill replica's last chunk
    n_tokens: int              # prompt tokens resident in the exported rows
    n_blocks: int              # filled block rows exported (leading rows)
    k_rows: Any = None         # stacked K rows, shape (maxn, L, bs, H, hd)
    v_rows: Any = None         # stacked V rows, same shape
    nbytes: int = 0            # payload footprint (both stacks)
    path: str = "fast"         # "fast" device copy | "staged" D2H→H2D
    t_export0: float = 0.0     # export dispatch start (prefill side)
    t_export1: float = 0.0     # export fence end (prefill side)
    installed: bool = False    # decode side flips this after the splice
    meta: Any = None           # telemetry meta for record_enqueue_handoff
    sampling: Any = None       # per-request SamplingParams override

    @property
    def done(self) -> bool:
        return self.installed


class _BatchQueue:
    def __init__(self, fn: Callable, max_batch_size: int,
                 batch_wait_timeout_s: float):
        self.fn = fn
        self.max_batch = max_batch_size
        self.timeout = batch_wait_timeout_s
        self._pending: List = []  # (arg, future)
        self._flush_task: Optional[asyncio.Task] = None

    async def submit(self, instance, arg):
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self._pending.append((arg, fut))
        if len(self._pending) >= self.max_batch:
            self._flush(instance)
        elif self._flush_task is None or self._flush_task.done():
            self._flush_task = loop.create_task(
                self._delayed_flush(instance))
        return await fut

    async def _delayed_flush(self, instance):
        await asyncio.sleep(self.timeout)
        self._flush(instance)

    def _flush(self, instance) -> None:
        if not self._pending:
            return
        batch, self._pending = self._pending, []
        if self._flush_task is not None and not self._flush_task.done():
            self._flush_task.cancel()
            self._flush_task = None
        asyncio.get_running_loop().create_task(
            self._run(instance, batch))

    async def _run(self, instance, batch) -> None:
        args = [a for a, _ in batch]
        futs = [f for _, f in batch]
        try:
            if instance is None:
                results = await self.fn(args)
            else:
                results = await self.fn(instance, args)
            if not isinstance(results, (list, tuple)) or \
                    len(results) != len(args):
                raise TypeError(
                    f"@serve.batch function must return a list of "
                    f"{len(args)} results (one per request), got "
                    f"{type(results).__name__}")
            for fut, res in zip(futs, results):
                if not fut.done():
                    fut.set_result(res)
        except Exception as e:  # noqa: BLE001 - propagate to every caller
            for fut in futs:
                if not fut.done():
                    fut.set_exception(e)


class RequestQueue:
    """FIFO admission queue for slot-based continuous batching
    (serve/engine.py): callers enqueue one request and await its future;
    the scheduler pops up to n pending requests whenever cache slots
    free up.  The complement of @serve.batch — that collects FIXED
    batches and runs them to completion, this hands out work as
    capacity appears mid-flight."""

    def __init__(self):
        self._pending: List = []  # (arg, future)

    def put(self, arg) -> "asyncio.Future":
        fut = asyncio.get_running_loop().create_future()
        self._pending.append((arg, fut))
        return fut

    def pop(self, n: int) -> List:
        """Up to n oldest (arg, future) pairs, removed from the queue."""
        taken, self._pending = self._pending[:n], self._pending[n:]
        return taken

    def push_front(self, arg, fut) -> None:
        """Return a popped (arg, future) pair to the HEAD of the queue
        — used when admission pops a request but cannot place it yet
        (e.g. the KV block pool is exhausted until a retirement), so
        FIFO order survives the retry."""
        self._pending.insert(0, (arg, fut))

    def __len__(self) -> int:
        return len(self._pending)


class OverloadedError(Exception):
    """Raised to a caller whose request was load-shed at admission
    (AdmissionPolicy said the engine cannot meet its SLOs).  Callers
    should back off and retry; proxies map this to HTTP 503."""


class AdmissionPolicy:
    """SLO-driven load shedding: the control loop closing serve
    telemetry back into admission decisions.

    The continuous engine consults ``decide(stats, queue_depth)``
    before enqueueing each request, passing its own ``engine_stats()``
    snapshot.  A request is shed (reason string returned) when:

      * ``queue_depth >= max_queue_depth`` — backlog bound; or
      * observed p95 queue wait exceeds ``queue_wait_slo_ms`` while a
        backlog exists — admitted requests are already waiting longer
        than the SLO, so new ones cannot meet it; or
      * observed p95 TTFT exceeds ``ttft_slo_ms`` while a backlog
        exists; or
      * the kvscope HBM ledger's ``min_headroom_bytes`` (worst chip:
        bytes_limit − max(live allocator bytes, KV pool + audited
        program peak)) has fallen below ``min_headroom_bytes`` —
        admitting more work risks a device OOM, which no amount of
        queueing recovers from.

    The percentile gates only fire with a backlog (``queue_depth >
    0``): an idle engine with bad historical percentiles must accept
    work, or it could shed forever on stale history.  The headroom
    gate fires regardless of backlog — exhausted HBM does not heal by
    admitting the request that would exhaust it — but is inert when
    the ledger reports no measurable headroom (CPU backends, dense
    engines).  ``None`` for any threshold disables that gate; the
    default policy (all None except a generous queue bound) never
    sheds in small test runs."""

    def __init__(self, *, max_queue_depth: Optional[int] = None,
                 queue_wait_slo_ms: Optional[float] = None,
                 ttft_slo_ms: Optional[float] = None,
                 min_headroom_bytes: Optional[int] = None):
        self.max_queue_depth = max_queue_depth
        self.queue_wait_slo_ms = queue_wait_slo_ms
        self.ttft_slo_ms = ttft_slo_ms
        self.min_headroom_bytes = min_headroom_bytes

    def decide(self, stats, queue_depth: int) -> Optional[str]:
        """None = admit; otherwise the shed reason (metric label)."""
        if self.max_queue_depth is not None \
                and queue_depth >= self.max_queue_depth:
            return "queue_full"
        if self.min_headroom_bytes is not None:
            ledger = (stats.get("kv_scope") or {}).get("hbm_ledger") \
                or {}
            headroom = ledger.get("min_headroom_bytes")
            if headroom is not None \
                    and headroom < self.min_headroom_bytes:
                return "hbm_headroom"
        if queue_depth > 0:
            qw = (stats.get("queue_wait_ms") or {}).get("p95")
            if self.queue_wait_slo_ms is not None and qw is not None \
                    and qw > self.queue_wait_slo_ms:
                return "queue_wait_slo"
            ttft = (stats.get("ttft_ms") or {}).get("p95")
            if self.ttft_slo_ms is not None and ttft is not None \
                    and ttft > self.ttft_slo_ms:
                return "ttft_slo"
        return None

    def describe(self) -> dict:
        return {"max_queue_depth": self.max_queue_depth,
                "queue_wait_slo_ms": self.queue_wait_slo_ms,
                "ttft_slo_ms": self.ttft_slo_ms,
                "min_headroom_bytes": self.min_headroom_bytes}


def batch(_func: Optional[Callable] = None, *, max_batch_size: int = 8,
          batch_wait_timeout_s: float = 0.01):
    """Decorator turning `async def f(self, item)` call sites into
    batched `f(self, [items])` invocations (reference: serve.batch)."""

    def wrap(fn: Callable):
        if not asyncio.iscoroutinefunction(fn):
            raise TypeError("@serve.batch requires an async function")
        # queue lives ON the instance (unique attr per decorated method):
        # an id()-keyed side table would leak queues and could alias a
        # recycled instance address to a dead instance's pending batch
        attr = f"__serve_batch_queue_{fn.__qualname__}"
        free_queue: List[Optional[_BatchQueue]] = [None]

        @functools.wraps(fn)
        async def wrapper(*args, **kwargs):
            if kwargs:
                raise TypeError("@serve.batch calls take one positional "
                                "argument")
            if len(args) == 2:       # bound method: (self, item)
                instance, item = args
            elif len(args) == 1:     # free function: (item,)
                instance, item = None, args[0]
            else:
                raise TypeError("@serve.batch function must take exactly "
                                "one request argument")
            if instance is None:
                q = free_queue[0]
                if q is None:
                    q = free_queue[0] = _BatchQueue(
                        fn, max_batch_size, batch_wait_timeout_s)
            else:
                q = getattr(instance, attr, None)
                if q is None:
                    q = _BatchQueue(fn, max_batch_size,
                                    batch_wait_timeout_s)
                    setattr(instance, attr, q)
            return await q.submit(instance, item)

        wrapper._ray_tpu_serve_batch = True
        return wrapper

    return wrap(_func) if _func is not None else wrap
