"""Batched D2SD serving engine: continuous slot-refill batching over a
pluggable KV storage layer.

Requests queue up and are served FIFO through a fixed-size batch of row
*slots* over one typed :class:`~repro.core.state.EngineState`:

* **Per-slot prefill** — each request is prefilled independently into its
  row via :func:`~repro.core.state.install_row` (a batch-1 prefill merged
  in with :meth:`EngineState.adopt_row` under a donated ``jit``, so the
  splice lowers to an in-place row write instead of a full-state copy),
  letting one running batch mix arbitrary prompt lengths AND arbitrary
  ``max_new`` budgets; there are no uniform-prompt-length waves.
* **Early-exit masking** — before every decode cycle the engine pushes a
  per-row ``active`` mask into the state; rows whose request already hit
  its budget (or whose slot is idle) draft a degenerate root-only tree and
  commit nothing, so they stop mutating KV / feature caches and stop
  polluting acceptance statistics (disable with ``early_exit=False``).
* **Slot refill** — the moment a request finishes, it retires into
  ``done`` and the FIFO head of the queue is prefilled into the vacated
  row, keeping the batch full under sustained traffic (disable with
  ``refill=False`` to get drain-the-wave batching for A/B comparison; see
  ``benchmarks/serving_bench.py``).

KV memory (``cache_impl``):

* ``dense`` — every slot reserves the worst-case ``max_len`` of the wave's
  candidate set for its whole lifetime.
* ``paged`` — a :class:`~repro.models.kvcache.PagePool` (engine-lifetime
  by default, see *Pool scope* below) backs the target global-attention
  KV and both drafter feature caches.
  **Admission accounts in pages**: a request needs
  ``ceil(cache_needed / page_size)`` pages and is adopted iff that many
  pages are free — not iff a dense ``max_len`` row is. **Retire frees its
  pages** back to the pool, and **slot refill is copy-free**: install
  allocates pages, prefills straight into them through a pool-sharing
  batch-1 view, and patches one page-table row (see
  :func:`~repro.core.state.row_template`). Per-request token output is
  identical across both impls (asserted by the serving bench).

Pool scope (``pool_scope``, paged only — the borrowed-pool contract):

* ``engine`` (default) — the engine allocates ONE :class:`PagePool` for
  its whole lifetime, sized once by the engine-global rule
  (:meth:`ServingEngine._pool_budget`: the worst-case *concurrent* live
  set plus ``pool_headroom`` × that for prefix retention, or an explicit
  ``pool_pages`` override). Waves are *borrowers*, not owners: each
  ``start_wave`` builds its page tables against the shared pool, the
  device pool buffers are captured at wave turnover and re-installed
  into the next wave's state (:func:`~repro.core.state.capture_pools` /
  :func:`~repro.core.state.adopt_pools`), and a new wave's initial set
  is capped to what the pool can grant (later arrivals wait for refill
  admission). Eviction pressure is engine-global: free pages plus the
  radix cache's evictable pages, regardless of which wave cached them.
* ``wave`` — legacy per-wave pools (allocated in ``start_wave``, dropped
  with the wave; every cached prefix dies at turnover). Kept as the A/B
  reference for the serving bench and parity tests.

Prefix cache (``prefix_cache=True``, paged only):

* a :class:`~repro.serving.prefix_cache.PrefixCache` — a radix tree over
  retired requests' committed token strings whose nodes own refcounted
  page runs in the pool. With the default engine-lifetime pool the tree
  OUTLIVES waves: wave N+1's prompts hit prefixes committed in wave N
  (the resident-server fast path; see ``--suite resident``). Admission
  matches each prompt against the tree; on a hit the matched prefix's
  full pages are spliced read-only into the new row's page table
  (refcount bumped) and only the uncached suffix is prefilled
  (``install_row(prefix_hit=...)`` — token-identical to a cold install).
  A match ending mid-page first copies the shared tail page to a fresh
  page (COW: a page with refcount > 1 is never written). Retiring a
  request inserts its committed prefix back into the tree (private pages
  donated); under pool pressure LRU unpinned leaves are evicted.
  Requires an all-global-attention target: sliding-window rolling
  buffers and recurrent states cannot be reconstructed from shared
  pages.

Prompt-length bucketing (``bucket_sizes``, default ``"auto"`` = the
pow-2 :data:`DEFAULT_BUCKETS` ladder; pass ``None`` for exact-length
installs): install prefills are padded to a small set of length buckets
(real length masked via ``true_len``), so the donated install jit
compiles O(buckets) instead of O(distinct prompt/suffix lengths) under
naturally varying traffic; ``install_traces`` in stats counts the
distinct shapes actually traced.

Cycle API (overlap contract): :meth:`ServingEngine.dispatch_cycle`
launches one decode cycle and returns immediately (JAX async dispatch);
:meth:`complete_cycle` blocks on its results, banks tokens, and retires —
the ONLY host/device sync boundary. Between the two, the host owns the
overlap window: :meth:`admit_idle` fills idle slots from the queue while
the device decodes, collapsing same-length-bucket admission groups into
single batched :func:`~repro.core.state.install_rows` dispatches; the
install's anchor token is never read back inline (pending-anchor
deferral, flushed at the next retire boundary). The synchronous
:meth:`step` is dispatch + complete back-to-back; the async front-end
(``serving/frontend.py``) drives the split form. Timestamps and
per-request SLA events go through an injected
:class:`~repro.serving.metrics.Clock` / ``MetricsRecorder``
(``serving/metrics.py``), shared by both drivers.
Aggregate stats track tokens actually committed per request
(``min(filled, max_new)``), acceptance ``alpha`` over *active* row-cycles
only and ``accepted`` draft tokens wired from the verify backends'
``n_acc``, ``wasted_row_cycles``, the KV-memory counters
(``refill_copy_bytes`` — accounting model of bytes written per install,
:func:`~repro.core.state.refill_copy_bytes` — plus ``pool_pages`` /
``pool_peak_pages`` and the per-cycle mean ``pool_utilization``), and the
prefix-cache counters (``prefix_hits`` / ``prefix_misses`` /
``prefix_hit_tokens`` / ``prefill_tokens_saved`` / ``cow_copies`` /
``prefix_evictions``).

Mesh residency (sharded resident serving): construct the engine inside a
``use_sharding(mesh, ...)`` context (``launch/serve.py --mesh-model`` /
``--kv-seq-axis``) and ONE engine spans the mesh. The invariant is
**page identity is global, page bytes are per-shard**: every host-side
structure above — allocator free list, refcounts, radix tree, page
tables, admission accounting, ``_pool_budget`` — is unchanged and counts
GLOBAL pages, while each page's payload bytes are laid out along the
``kv_seq`` mesh axis (``page_size // kv_shards`` slots of every page per
shard; :func:`~repro.models.kvcache.shard_pool`). The bundle's weights
are replicated onto every device of the mesh at construction
(:func:`~repro.distributed.sharding.replicate_onto`). Decode's paged
cascade verify runs under ``shard_map`` with the per-shard cache
contribution merged by one float32 LSE ``psum``
(:func:`~repro.distributed.spdecode.sharded_paged_cache_attend`), so
per-request tokens are identical to the single-device engine (asserted
by ``tests/test_sharded_serving.py`` and ``--suite sharded``). The
borrowed-pool contract is shard-preserving: :func:`capture_pools` /
``engine_init(pools=...)`` hand the SAME device buffers (and hence
their kv_seq layout) across wave turnover, zero-copy. The engine
captures the construction-time mesh context and re-enters it around
every device-facing call (the context is threadlocal and the async
front-end drives the engine from a worker thread), and threads
``sharding.mesh_tag()`` as a static cache-splitter into every jit so
sharded and unsharded engines coexist in one process. Stats gain
``kv_shards``, ``pool_shard_slots`` (per-shard slot capacity:
``pool_pages * page_size / kv_shards``) and ``decode_collective_bytes``
(accounting model of the bytes the verify psum moves per decode cycle).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pipeline as pl
from repro.core.state import (EngineState, capture_pools, cow_copy_page,
                              install_row, install_rows, refill_copy_bytes)
from repro.distributed import sharding as sh
from repro.distributed import spdecode
from repro.models import kvcache as kvc
from repro.serving.metrics import Clock, MetricsRecorder, MonotonicClock
from repro.serving.prefix_cache import PrefixCache, PrefixHit


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray          # [P]
    max_new: int
    out: Optional[np.ndarray] = None
    n_cycles: int = 0
    latency_s: float = 0.0
    t_start: float = 0.0


@dataclasses.dataclass
class Wave:
    """One running batch: typed engine state + per-slot request books."""
    requests: List[Optional[Request]]   # slot -> live request (None = idle)
    state: EngineState
    bufs: np.ndarray            # [B, cap] committed tokens (slot 0 = anchor)
    filled: np.ndarray          # [B] tokens committed so far
    targets: np.ndarray         # [B] per-request max_new (0 for idle slots)
    t0: float
    cycles: int = 0
    pool: Optional[kvc.PagePool] = None        # paged mode (BORROWED when
    #                                            pool_scope="engine")
    row_pages: Optional[List[List[int]]] = None  # slot -> PRIVATE pages
    cache: Optional[PrefixCache] = None        # prefix_cache=True only
    row_tables: Optional[List[Optional[np.ndarray]]] = None  # host copies
    row_hits: Optional[List[Optional[PrefixHit]]] = None
    trunc: Optional[np.ndarray] = None  # [B] output buf overflowed (bool)
    evictions0: int = 0                 # cache.evictions at wave start
    # slots whose install-produced anchor token has not been read back to
    # bufs yet — materialized lazily at the next safe host-sync boundary
    # (_flush_anchors), so an overlapped install never forces a device sync
    pending_anchor: Set[int] = dataclasses.field(default_factory=set)

    @property
    def done(self) -> bool:
        return all(r is None for r in self.requests)


#: default install-prefill length buckets (pow-2 ladder; longer prompts
#: round up to a multiple of the largest bucket)
DEFAULT_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


class ServingEngine:
    def __init__(self, bundle: pl.SpecBundle, batch_size: int = 8,
                 seed: int = 0, early_exit: bool = True,
                 refill: bool = True, cache_impl: str = "dense",
                 page_size: int = 64, prefix_cache: bool = False,
                 bucket_sizes="auto", pool_scope: str = "engine",
                 pool_pages: Optional[int] = None,
                 pool_headroom: float = 1.0,
                 clock: Optional[Clock] = None,
                 recorder: Optional[MetricsRecorder] = None):
        assert cache_impl in ("dense", "paged"), cache_impl
        assert pool_scope in ("engine", "wave"), pool_scope
        if pool_pages is not None and not (cache_impl == "paged"
                                           and pool_scope == "engine"):
            raise ValueError(
                "pool_pages only sizes the engine-lifetime pool "
                "(cache_impl='paged', pool_scope='engine'); per-wave "
                "pools are sized per wave by the engine-global rule")
        if prefix_cache:
            if cache_impl != "paged":
                raise ValueError(
                    "prefix_cache=True requires cache_impl='paged': "
                    "cross-request sharing is a page-table splice")
            kinds = set(bundle.target_cfg.pattern_for_depth())
            if kinds != {"global"}:
                raise ValueError(
                    "prefix_cache=True requires an all-global-attention "
                    "target: sliding-window rolling buffers and recurrent "
                    f"states cannot be rebuilt from shared pages ({kinds})")
        if cache_impl == "paged" and not early_exit:
            # a retired slot's pages return to the pool but its stale page
            # table survives until refill; without early-exit masking the
            # idle row would keep committing KV through that table into
            # pages the allocator may have granted to a live request —
            # silent cross-request corruption. The legacy all-rows-run
            # configuration exists only for dense A/B benchmarking.
            raise ValueError(
                "cache_impl='paged' requires early_exit=True: idle slots "
                "must be masked so they cannot write through stale page "
                "tables into freed (reallocated) pages")
        # mesh residency: capture the ambient sharding context ONCE at
        # construction. One engine spans the whole mesh — pool payloads
        # are laid out along the kv_seq axis (page bytes per-shard, page
        # IDENTITY global: the host allocator / radix tree / page tables
        # below never see the mesh). Every device-facing call site
        # re-enters the context via _mesh_scope so the engine keeps
        # working when driven from another thread (the async front-end's
        # worker: sharding._CTX is threadlocal).
        self._mesh = sh.active_mesh()
        self._rules = dict(sh._CTX.rules) if self._mesh is not None else None
        self._fsdp = sh.fsdp_enabled()
        self._shard_tag = sh.mesh_tag()
        self.kv_shards = spdecode.kv_seq_shards()
        if cache_impl == "paged" and page_size % self.kv_shards != 0:
            raise ValueError(
                f"page_size={page_size} must be divisible by the kv_seq "
                f"mesh axis size ({self.kv_shards}): page payloads are "
                f"split WITHIN the page — each shard owns "
                f"page_size // n_shards slots of every page")
        if self._mesh is not None:
            # weights live replicated on every device of the mesh, placed
            # once here, not moved by every sharded call
            bundle = sh.replicate_onto(bundle, self._mesh)
        self.bundle = bundle
        self.batch_size = batch_size
        self.early_exit = early_exit
        self.refill = refill
        self.cache_impl = cache_impl
        self.page_size = page_size
        self.prefix_cache = prefix_cache
        self.pool_scope = pool_scope
        self._pool_pages_cfg = pool_pages
        self.pool_headroom = float(pool_headroom)
        # engine-lifetime pool + radix tree (paged, pool_scope="engine"):
        # created at the first start_wave, borrowed by every wave after
        self.pool: Optional[kvc.PagePool] = None
        self.cache: Optional[PrefixCache] = None
        self._pools = None      # device pool buffers retained between waves
        # "auto" -> the pow-2 ladder; None / () -> exact-length installs
        # (one donated-install trace per distinct prompt/suffix length)
        if bucket_sizes == "auto":
            bucket_sizes = DEFAULT_BUCKETS
        self.bucket_sizes = (tuple(sorted(bucket_sizes))
                             if bucket_sizes else None)
        # every engine timestamp goes through the injected clock (the sync
        # drain loop and the async front-end share one timing source, so
        # their wall_s / SLA numbers are directly comparable); the engine
        # also charges simulated work to it (tick "cycle" per dispatched
        # decode cycle, "install" per install dispatch) — a no-op on the
        # real MonotonicClock, deterministic cost on a VirtualClock
        self.clock = clock if clock is not None else MonotonicClock()
        self.recorder = recorder
        self.queue: List[Request] = []
        self.done: List[Request] = []
        self.key = jax.random.PRNGKey(seed)
        self._next_uid = 0
        self.wave: Optional[Wave] = None
        # shares pipeline's module-level trace cache across engine
        # instances; shard_tag splits that cache between sharded and
        # unsharded engines living in one process (jit keys on avals,
        # not on the threadlocal mesh context the trace reads)
        self._cycle = lambda s, k: pl._cycle_jit(self.bundle, s, k,
                                                 collect_stats=False,
                                                 shard_tag=self._shard_tag)
        self.stats = {"tokens": 0, "cycles": 0, "accepted": 0,
                      "wall_s": 0.0, "waves": 0, "alpha": 0.0,
                      "wasted_row_cycles": 0, "refills": 0,
                      "refill_copy_bytes": 0, "installs": 0,
                      "install_traces": 0, "install_calls": 0,
                      "pool_pages": 0, "pool_peak_pages": 0,
                      "pool_utilization": 0.0,
                      "prefix_hits": 0, "prefix_misses": 0,
                      "prefix_hit_tokens": 0, "prefill_tokens_saved": 0,
                      "cow_copies": 0, "prefix_evictions": 0,
                      "prefix_cached_pages": 0,
                      "kv_shards": self.kv_shards,
                      "pool_shard_slots": 0,
                      "decode_collective_bytes": 0,
                      "warm_cycle_s": 0.0, "warm_cycles": 0}
        self._alpha_num = 0
        self._alpha_den = 0
        self._util_sum = 0.0
        self._util_samples = 0
        # steady-state per-cycle wall durations: dispatch->complete deltas
        # of every cycle EXCEPT each wave's first (trace/compile-dominated
        # at tiny scale — wall_s keeps the all-in number, warm_cycle_s is
        # the median of these)
        self._warm_durs: List[float] = []
        self._install_shapes = set()
        # per-cycle decode-collective payload (bytes moved by the verify
        # LSE psum per cycle), learned from the first fresh decode trace
        self._cycle_payload = 0

    @contextlib.contextmanager
    def _mesh_scope(self):
        """Re-enter the construction-time sharding context around a
        device-facing call. The context is threadlocal; the async
        front-end drives the engine from a worker thread that never saw
        the caller's ``use_sharding`` block."""
        if self._mesh is None:
            yield
        else:
            with sh.use_sharding(self._mesh, self._rules, fsdp=self._fsdp):
                yield

    def submit(self, prompt: np.ndarray, max_new: int,
               t_arrival: Optional[float] = None) -> int:
        # Monotonic uid: len(queue)+len(done) would collide once a wave
        # drains the queue mid-run.
        uid = self._next_uid
        self._next_uid += 1
        self.queue.append(Request(uid, np.asarray(prompt, np.int32),
                                  max_new))
        if self.recorder is not None:
            # open-loop drivers pass the trace arrival time so TTFT counts
            # from when the client sent the request, not from this call
            self.recorder.on_arrival(uid, t=t_arrival)
        return uid

    def _next_wave(self) -> List[Request]:
        # FIFO: the wave anchors on the oldest queued request. (Re-sorting
        # by prompt length let sustained short-prompt traffic starve an
        # early long-prompt request forever; per-slot prefill removed the
        # uniform-length constraint that motivated the sort.)
        take = self.queue[: self.batch_size]
        if self.pool is not None and take:
            # engine-lifetime pool: a NEW wave's initial set must fit the
            # fixed pool even after the radix tree gives back everything
            # it can — requests beyond the budget stay queued and enter
            # through refill admission (_fits) instead. Between waves
            # nothing is pinned, so the budget is the whole pool.
            g = self.bundle.spec.gamma
            budget = self.pool.free_pages + (
                self.cache.evictable_pages() if self.cache is not None
                else 0)
            kept: List[Request] = []
            acc = 0
            for r in take:
                n = self._pages_needed(r, g)
                if acc + n > budget:
                    break
                kept.append(r)
                acc += n
            if not kept:
                raise RuntimeError(
                    f"request uid={take[0].uid} needs "
                    f"{self._pages_needed(take[0], g)} pages but the "
                    f"engine-lifetime pool can grant at most {budget} of "
                    f"{self.pool.n_pages}; raise pool_pages / "
                    f"pool_headroom (or use pool_scope='wave')")
            take = kept
        self.queue = self.queue[len(take):]
        return take

    def _pool_budget(self, need: List[int], b: int) -> int:
        """Engine-global pool sizing rule (the single source of truth for
        BOTH pool scopes): the worst-case *concurrent* live set — the
        ``b`` largest candidate page needs — plus ``pool_headroom`` × that
        for prefix retention when the radix cache is on. Refill candidates
        are deliberately NOT summed in: they run in slots the live set
        vacates, so counting their full needs on top of the live set (the
        old ``sum(need)`` rule) double-counted them; only their retired
        prefixes — bounded by the headroom — need extra pages.

        Mesh residency: the budget counts GLOBAL pages — one allocation
        decision, P-way placement. Each page's payload bytes are split
        along the ``kv_seq`` mesh axis (``page_size // kv_shards`` slots
        of every page per shard), so the per-device budget this global
        count implies is ``pool bytes / kv_shards``; ``pool_shard_slots``
        in :attr:`stats` reports the per-shard slot capacity directly.
        Page identity (allocator, refcounts, radix tree, page tables)
        never shards."""
        live = sum(need[:b])
        if not self.prefix_cache:
            return live
        return live + int(np.ceil(self.pool_headroom * live))

    # ------------------------------------------------------ step API ------
    def start_wave(self, width: Optional[int] = None) -> bool:
        """Allocate + prefill the next running batch. False if queue empty.

        ``width`` (open-loop serving): build the wave with this many rows
        even if fewer requests are visible right now — the extra rows
        start idle (masked, sentinel page tables) and are filled later by
        refills / :meth:`admit_idle`. Without it the wave is exactly as
        wide as the initial batch, which is right for drain-loop replay
        (everything submitted up front) but starves an open-loop server:
        a wave started at the first arrival would be 1 row wide and
        chain-refill would keep that single row busy forever."""
        assert self.wave is None, "finish the active wave first"
        g = self.bundle.spec.gamma
        if (self.cache_impl == "paged" and self.pool_scope == "engine"
                and self.pool is None and self.queue):
            # allocate the engine-lifetime pool ONCE (explicit pool_pages
            # override, or the engine-global rule over the WHOLE visible
            # queue — the b largest needs anywhere in it, so a large
            # request submitted behind small ones still fits when its
            # turn comes); every later wave borrows the pool, so cached
            # prefixes survive turnover. Only a request larger than
            # anything visible at sizing time can fail admission later
            # (_next_wave raises with guidance).
            need0 = sorted((self._pages_needed(r, g) for r in self.queue),
                           reverse=True)
            b0 = min(self.batch_size, len(self.queue))
            n_pages = (self._pool_pages_cfg
                       if self._pool_pages_cfg is not None
                       else self._pool_budget(need0, b0))
            self.pool = kvc.PagePool(n_pages, self.page_size)
            if self.prefix_cache:
                self.cache = PrefixCache(self.pool)
        reqs = self._next_wave()
        if not reqs:
            return False
        b = (len(reqs) if width is None
             else min(self.batch_size, max(width, len(reqs))))
        # size caches for the wave plus the next batch of likely refill
        # candidates — not the whole queue, or one huge queued request
        # would inflate every slot's KV/feature allocation; requests that
        # don't fit simply wait for the next wave (see _fits)
        cand = reqs + self.queue[: self.batch_size]
        cap = max(self._bufs_needed(r, g) for r in cand)
        pool = None
        row_pages = None
        cache = None
        if self.cache_impl == "paged":
            # page-granular sizing: the table is as wide as the largest
            # candidate needs (capped at the pool — no row can ever hold
            # more), while the POOL is sized by _pool_budget: worst-case
            # concurrent set + prefix-retention headroom, never a per-
            # candidate sum. Engine scope reuses the engine pool; wave
            # scope (legacy A/B reference) builds a fresh one per wave.
            need = sorted((self._pages_needed(r, g) for r in cand),
                          reverse=True)
            if self.pool_scope == "engine":
                pool, cache = self.pool, self.cache
            else:
                pool = kvc.PagePool(self._pool_budget(need, b),
                                    self.page_size)
                if self.prefix_cache:
                    cache = PrefixCache(pool)
            pool_pages = pool.n_pages
            mp = min(need[0], pool_pages)
            row_pages = [[] for _ in range(b)]
            # all rows start unallocated: table rows hold the growth-stable
            # sentinel until _install patches them
            table = np.full((b, mp), kvc.PAGE_SENTINEL, np.int32)
            # borrowed-pool contract: retained device pool buffers (from
            # capture_pools at the last turnover) go straight into init —
            # pages the radix tree kept hold their KV across the turnover
            # and the transient pool-sized zero allocation the old
            # init-then-adopt_pools sequence paid is never materialized.
            # Drop our reference: the wave's first donated install
            # consumes the state. engine_init runs under the mesh scope:
            # fresh pool buffers are device_put along kv_seq at birth
            # (adopted buffers pass through untouched — zero-copy).
            with self._mesh_scope():
                state = pl.engine_init(self.bundle, b, mp * self.page_size,
                                       cache_impl="paged",
                                       page_size=self.page_size,
                                       pool_pages=pool_pages,
                                       page_table=table,
                                       pools=self._pools)
            self._pools = None
            # lifetime max, matching pool_peak_pages' scope — a small
            # leftover wave must not shrink the reported pool below the
            # peak measured in an earlier, larger wave
            self.stats["pool_pages"] = max(self.stats["pool_pages"],
                                           pool_pages)
            self.stats["pool_shard_slots"] = max(
                self.stats["pool_shard_slots"],
                pool_pages * (self.page_size // self.kv_shards))
        else:
            max_len = max(self._cache_needed(r, g) for r in cand)
            with self._mesh_scope():
                state = pl.engine_init(self.bundle, b, max_len)
        state = state.replace(active=jnp.zeros((b,), bool))
        self.wave = Wave(requests=[None] * b, state=state,
                         bufs=np.zeros((b, cap), np.int32),
                         filled=np.zeros((b,), np.int64),
                         targets=np.zeros((b,), np.int64),
                         t0=self.clock.now(), pool=pool,
                         row_pages=row_pages,
                         cache=cache, row_tables=[None] * b,
                         row_hits=[None] * b, trunc=np.zeros((b,), bool),
                         evictions0=cache.evictions if cache else 0)
        # two passes: install EVERY initial request before the first retire.
        # A retire can chain-refill from beyond the pool-sizing candidate
        # window; interleaving it with the initial installs could hand those
        # refills pages the pool only guarantees for the initial set.
        # Same-bucket initial installs collapse into batched install_rows
        # calls (one dispatch + one batch-K prefill per length group).
        self._install_group(list(enumerate(reqs)))
        for i in range(b):
            if (self.wave.requests[i] is not None
                    and self.wave.filled[i] >= self.wave.targets[i]):
                # satisfied by the prefill alone (max_new <= 1): retire
                # (and possibly refill) without paying a decode cycle
                self._retire(i)
        if self.wave.done:
            self._finish_wave()
        return True

    def _bucket(self, n: int) -> int:
        """Pad a prefill length to its bucket (identity when disabled)."""
        if self.bucket_sizes is None:
            return n
        for b in self.bucket_sizes:
            if b >= n:
                return b
        top = self.bucket_sizes[-1]
        return -(-n // top) * top

    def _prep_install(self, slot: int, r: Request) -> int:
        """Host-side admission work for one install: prefix-cache match,
        page allocation, table splice, COW. Returns the matched prefix
        length (0 = cold install; dense mode is always 0).

        Split from the device dispatch so :meth:`_install_group` can prep
        a whole admission group FIRST (in pick order — each lookup sees
        the radix tree exactly as the previous prep left it) and then
        batch the dispatches by the ACTUAL outcome (suffix bucket ×
        warm/cold), prefix hits included."""
        w = self.wave
        if self.cache_impl != "paged":
            return 0
        prompt = np.asarray(r.prompt, np.int32)
        g = self.bundle.spec.gamma
        n_total = self._pages_needed(r, g)
        hit = w.cache.lookup(prompt) if w.cache is not None else None
        if hit is not None:
            w.cache.acquire(hit)        # pin shared pages + COW source
        n_new = n_total - (len(hit.shared) if hit else 0)
        if w.pool.free_pages < n_new and w.cache is not None:
            w.cache.evict_for(n_new)
        pages = w.pool.alloc(n_new)
        if pages is None and hit is not None:
            # tight pool: the admission guarantee (_fits) is for the
            # miss shape — give the hit back and install cold
            w.cache.release_partial(hit)
            w.cache.release(hit)
            hit = None
            w.cache.evict_for(n_total)
            pages = w.pool.alloc(n_total)
        assert pages is not None, "admission control must guarantee pages"
        w.row_pages[slot] = pages
        shared = hit.shared if hit else []
        w.row_tables[slot] = w.pool.row_table(shared + pages,
                                              w.state.max_pages)
        if hit is not None:
            if hit.partial is not None:
                # COW: duplicate the shared partial tail page into the
                # row's first private page BEFORE any write lands there
                # (a page with refcount > 1 is never written)
                w.state = cow_copy_page(w.state, hit.partial, pages[0])
                self.stats["cow_copies"] += 1
            w.cache.release_partial(hit)
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += hit.length
            # tokens the suffix prefill actually skips relative to a
            # cold install — measured in BUCKETED lengths, so padding
            # that a cold install would have paid anyway counts as
            # saved and padding the suffix re-pays is deducted
            self.stats["prefill_tokens_saved"] += (
                self._bucket(len(prompt))
                - self._bucket(len(prompt) - hit.length))
        elif w.cache is not None:
            self.stats["prefix_misses"] += 1
        w.row_hits[slot] = hit
        return hit.length if hit else 0

    def _install(self, slot: int, r: Request,
                 prefix_len: Optional[int] = None) -> None:
        """Prefill ``r`` into ``slot`` of the running batch (slot refill).

        The donated :func:`install_row` consumes the old wave state, so
        the splice / page writes happen in place — no full-state copy in
        either impl. Paged mode additionally allocates the request's
        pages via :meth:`_prep_install` (freed again by :meth:`_retire`);
        with the prefix cache on, the prompt is first matched against the
        radix tree: the matched prefix's full pages are spliced read-only
        into the row's table, a mid-page match tail is COW-copied, and
        only the uncached suffix is prefilled. ``prefix_len`` short-
        circuits the prep when :meth:`_install_group` already ran it.
        """
        w = self.wave
        self.key, sub = jax.random.split(self.key)
        if prefix_len is None:
            prefix_len = self._prep_install(slot, r)
        hit = w.row_hits[slot] if w.row_hits is not None else None
        row_table = (w.row_tables[slot] if self.cache_impl == "paged"
                     else None)
        prompt = np.asarray(r.prompt, np.int32)
        suffix = prompt[prefix_len:]
        s = len(suffix)
        true_len = None
        if self.bucket_sizes is not None:
            pad = self._bucket(s)
            suffix = np.concatenate(
                [suffix, np.zeros((pad - s,), np.int32)])
            true_len = s
        # full donated-install trace key: suffix shape + warm/cold + the
        # wave geometry the state shapes derive from (a new wave with a
        # different batch / capacity / pool size retraces even for an
        # already-seen suffix length)
        self._install_shapes.add(
            (1, len(suffix), hit is not None, w.state.batch, w.state.max_len,
             w.pool.n_pages if w.pool is not None else 0))
        self.stats["install_traces"] = len(self._install_shapes)
        self.stats["refill_copy_bytes"] += refill_copy_bytes(w.state, s)
        self.stats["installs"] += 1
        self.stats["install_calls"] += 1
        if self.recorder is not None:
            self.recorder.on_admit(r.uid)
        with self._mesh_scope():
            w.state = install_row(self.bundle, w.state, slot, suffix,
                                  key=sub,
                                  temperature=self.bundle.spec.temperature,
                                  row_table=row_table,
                                  prefix_hit=prefix_len if hit else None,
                                  true_len=true_len,
                                  shard_tag=self._shard_tag)
        self.clock.tick("install")
        self._book_install(slot, r)

    def _book_install(self, slot: int, r: Request) -> None:
        """Host bookkeeping shared by single and batched installs. The
        anchor token (the request's FIRST generated token, produced by the
        install's prefill) is NOT read back here — reading it would block
        the host on the device stream and kill install/decode overlap.
        The slot is marked pending and the anchor lands in ``bufs`` at the
        next retire boundary (:meth:`_flush_anchors`)."""
        w = self.wave
        w.bufs[slot] = 0
        w.pending_anchor.add(slot)
        w.filled[slot] = 1
        w.targets[slot] = r.max_new
        w.requests[slot] = r
        w.trunc[slot] = False
        r.t_start = self.clock.now()
        r.n_cycles = 0
        if self.recorder is not None:
            # first token exists once the dispatched install completes —
            # stamped here at dispatch, after charging the install tick
            self.recorder.on_first_token(r.uid)

    def _flush_anchors(self) -> None:
        """Materialize pending install anchors into ``bufs``.

        The single deferred host read of the overlap design: called before
        a cycle dispatch consumes (donates) the state, and at retire
        boundaries before banked outputs are assembled. One blocking
        ``np.asarray`` covers every install since the last flush."""
        w = self.wave
        if w is None or not w.pending_anchor:
            return
        anchors = np.asarray(w.state.anchor)
        for slot in w.pending_anchor:
            w.bufs[slot, 0] = int(anchors[slot])
        w.pending_anchor.clear()

    def _install_group(self, picks: List[Tuple[int, Request]]) -> None:
        """Install (slot, request) picks, collapsing same-suffix-bucket
        groups into ONE batched :func:`install_rows` dispatch each —
        prefix-cache hits included.

        The batched path requires greedy anchors (temperature 0: argmax
        is key-independent, so one shared PRNG key is token-identical to
        per-request keys); sampling picks fall back to the single-slot
        :meth:`_install`. With the radix cache on, all host-side prep
        (lookup / page alloc / COW splice) runs FIRST in pick order —
        each lookup sees the tree exactly as the previous prep left it,
        so an earlier pick's eviction can't invalidate a later pick's
        planned group — then picks group by their ACTUAL outcome:
        (suffix bucket, warm/cold). Warm rows with different prefix
        lengths share one batch (``install_rows(prefix_hits=[K])`` takes
        a per-row start vector); mixed warm/cold groups are disallowed
        by the state layer, hence the cold/warm key split.
        """
        if self.bundle.spec.temperature > 0 or len(picks) <= 1:
            for slot, r in picks:
                self._install(slot, r)
            return
        prepped = [(slot, r, self._prep_install(slot, r))
                   for slot, r in picks]
        groups: Dict[Tuple[int, bool],
                     List[Tuple[int, Request, int]]] = {}
        for slot, r, pfx in prepped:
            key = (self._bucket(len(r.prompt) - pfx), pfx > 0)
            groups.setdefault(key, []).append((slot, r, pfx))
        for (pad, warm), grp in sorted(groups.items()):
            if len(grp) == 1:
                slot, r, pfx = grp[0]
                self._install(slot, r, prefix_len=pfx)
            else:
                self._install_batch(grp, pad, warm)

    def _install_batch(self, grp: List[Tuple[int, Request, int]], pad: int,
                       warm: bool = False) -> None:
        """One donated batch-K install for K same-suffix-bucket requests
        (already prepped by :meth:`_prep_install`; all cold or all warm —
        warm rows may carry different prefix lengths)."""
        w = self.wave
        self.key, sub = jax.random.split(self.key)
        k = len(grp)
        row_tables = None
        if self.cache_impl == "paged":
            row_tables = np.stack([w.row_tables[slot]
                                   for slot, _, _ in grp])
        prompts = np.zeros((k, pad), np.int32)
        true = np.zeros((k,), np.int32)
        pfx = np.zeros((k,), np.int32)
        for i, (slot, r, p0) in enumerate(grp):
            sfx = np.asarray(r.prompt, np.int32)[p0:]
            prompts[i, : len(sfx)] = sfx
            true[i] = len(sfx)
            pfx[i] = p0
            self.stats["refill_copy_bytes"] += refill_copy_bytes(
                w.state, len(sfx))
            if self.recorder is not None:
                self.recorder.on_admit(r.uid)
        self._install_shapes.add(
            (k, pad, warm, w.state.batch, w.state.max_len,
             w.pool.n_pages if w.pool is not None else 0))
        self.stats["install_traces"] = len(self._install_shapes)
        self.stats["installs"] += k
        self.stats["install_calls"] += 1
        true_len = true if self.bucket_sizes is not None else None
        with self._mesh_scope():
            w.state = install_rows(self.bundle, w.state,
                                   np.array([s for s, _, _ in grp],
                                            np.int32),
                                   prompts, key=sub,
                                   temperature=self.bundle.spec.temperature,
                                   row_tables=row_tables, true_len=true_len,
                                   prefix_hits=pfx if warm else None,
                                   shard_tag=self._shard_tag)
        # ONE dispatch for the whole group: one simulated install charge
        self.clock.tick("install")
        for slot, r, _ in grp:
            self._book_install(slot, r)

    # ---- sizing: single source of truth for allocation and admission ----
    @staticmethod
    def _bufs_needed(r: Request, g: int) -> int:
        """Output-buffer slots: budget + worst-case overshoot + anchor."""
        return r.max_new + g + 1

    @staticmethod
    def _cache_needed(r: Request, g: int) -> int:
        """KV/feature-cache positions: prompt + budget + draft headroom
        (the same sizing rule as ``generate``'s default max_len)."""
        return len(r.prompt) + r.max_new + 2 * g + 8

    def _pages_needed(self, r: Request, g: int) -> int:
        return kvc.pages_for(self._cache_needed(r, g), self.page_size)

    def _fits(self, r: Request, reserved_pages: int = 0) -> bool:
        """Can ``r`` be adopted into the current wave's allocation?
        Paged mode admits on free *pages*, not a per-slot max_len row;
        with the prefix cache on, LRU-evictable (unpinned) cached pages
        count as available — the check is deliberately for the MISS
        shape, so an install can always fall back to cold if the pool is
        too tight to honor its hit. ``reserved_pages``: pages already
        promised to co-admitted requests whose installs have not
        allocated yet (admit_idle picks a group before installing it)."""
        w = self.wave
        g = self.bundle.spec.gamma
        if self._bufs_needed(r, g) > w.bufs.shape[1]:
            return False
        if self.cache_impl == "paged":
            n = self._pages_needed(r, g)
            avail = w.pool.free_pages - reserved_pages
            if w.cache is not None:
                avail += w.cache.evictable_pages()
            return n <= w.state.max_pages and n <= avail
        return self._cache_needed(r, g) <= w.state.max_len

    def _host_active(self) -> np.ndarray:
        """[B] rows holding a request that still wants tokens."""
        w = self.wave
        return np.array([r is not None and w.filled[i] < w.targets[i]
                         for i, r in enumerate(w.requests)])

    def dispatch_cycle(self):
        """Launch ONE decode cycle on device WITHOUT waiting for it.

        Returns an opaque handle for :meth:`complete_cycle` (None when no
        wave is running). JAX async dispatch means the call returns as
        soon as the cycle is enqueued; the host is then free to do
        admission work — match queued prompts, allocate pages, dispatch
        installs for idle slots (:meth:`admit_idle`) — while the device
        decodes. Pending install anchors are flushed FIRST: the cycle
        donates (invalidates) the state they live in.
        """
        w = self.wave
        if w is None:
            return None
        self._flush_anchors()
        b = len(w.requests)
        active = self._host_active()
        # push the mask: with early_exit, finished/idle rows cost nothing
        # and commit nothing; without it they keep running full cycles
        # (legacy behavior, kept for A/B benchmarking)
        w.state = w.state.replace(
            active=jnp.asarray(active) if self.early_exit
            else jnp.ones((b,), bool))
        self.key, sub = jax.random.split(self.key)
        n0 = len(spdecode.PAYLOAD_TRACE)
        with self._mesh_scope():
            w.state, out = self._cycle(w.state, sub)
        if len(spdecode.PAYLOAD_TRACE) > n0:
            # a fresh decode trace under a mesh just recorded the bytes
            # its verify LSE-merge collectives move per cycle (one entry
            # per sharded paged-attend layer); bank the per-cycle sum
            self._cycle_payload = sum(spdecode.PAYLOAD_TRACE[n0:])
        self.stats["decode_collective_bytes"] += self._cycle_payload
        w.cycles += 1
        self.clock.tick("cycle")
        if w.pool is not None:
            self._util_sum += w.pool.pages_in_use / max(w.pool.n_pages, 1)
            self._util_samples += 1
        # stats: only rows that were actively serving a request count
        # toward acceptance; the rest are wasted batch capacity
        self.stats["wasted_row_cycles"] += int(b - active.sum())
        return active, out, self.clock.now()

    def complete_cycle(self, handle) -> bool:
        """Block on a dispatched cycle's results, bank tokens, retire.

        The ``np.asarray`` reads below are the wave's ONLY device-sync
        boundary: everything dispatched since the handle was created (the
        cycle itself plus any overlapped installs) completes before the
        banked streams are touched. Returns True while any slot still has
        an unfinished request; False once the wave has closed — including
        the case where ``start_wave`` already finished it outright (a
        burst of ``max_new <= 1`` requests satisfied by their prefills).
        """
        w = self.wave
        if handle is None or w is None:
            return False
        active, out, t_disp = handle
        toks = np.asarray(out["tokens"])            # retire-boundary sync
        n_out = np.asarray(out["n_out"])
        if w.cycles > 1:
            # steady-state sample: the wave's first cycle carries the
            # trace/compile cost and is excluded (wall_s still counts it)
            self._warm_durs.append(self.clock.now() - t_disp)
        cap = w.bufs.shape[1]
        self._alpha_num += int(n_out[active].sum())
        self._alpha_den += int(active.sum())
        # real accepted-draft counts straight from the verify backends
        self.stats["accepted"] += int(np.asarray(out["n_acc"])[active].sum())
        for i in range(len(w.requests)):
            r = w.requests[i]
            if r is None:
                continue
            if active[i]:
                m = min(int(n_out[i]), cap - int(w.filled[i]))
                if m > 0:
                    w.bufs[i, w.filled[i]: w.filled[i] + m] = toks[i, :m]
                if m < int(n_out[i]):
                    # committed tokens fell off the output buffer: the
                    # banked stream no longer mirrors the cache contents,
                    # so this row must not seed the prefix tree
                    w.trunc[i] = True
                w.filled[i] = min(w.filled[i] + int(n_out[i]), cap)
                r.n_cycles += 1
            if w.filled[i] >= w.targets[i] or r.n_cycles > r.max_new + 8:
                self._retire(i)
        if w.done:
            self._finish_wave()
            return False
        return True

    def step(self) -> bool:
        """Run ONE decode cycle synchronously (dispatch + complete
        back-to-back) and bank its tokens. Finished requests retire
        immediately and (with ``refill``) their slot adopts the FIFO head
        of the queue via a per-slot prefill."""
        return self.complete_cycle(self.dispatch_cycle())

    def admit_idle(self) -> int:
        """Mid-flight admission: fill IDLE slots from the queue while a
        dispatched cycle is still decoding on device (the overlap window).

        The synchronous engine refills only at the retire moment — a slot
        that goes idle because the queue happened to be empty right then
        stays idle until the wave ends. Called between
        :meth:`dispatch_cycle` and :meth:`complete_cycle`, this admits
        bursty arrivals that landed since: the host groups same-bucket
        prompts, allocates their pages, and dispatches batched installs
        (:func:`~repro.core.state.install_rows`) that the device executes
        after the in-flight cycle — idle slots start producing one cycle
        later instead of one WAVE later. Safe without a sync because an
        idle slot is inactive in the running cycle (mask snapshot taken
        at dispatch) and installs touch only that row + freshly allocated
        pages. Returns the number of requests admitted.
        """
        w = self.wave
        if w is None or not self.refill or not self.queue:
            return 0
        g = self.bundle.spec.gamma
        picks: List[Tuple[int, Request]] = []
        reserved = 0
        for slot in range(len(w.requests)):
            if w.requests[slot] is not None:
                continue
            if not self.queue or not self._fits(self.queue[0], reserved):
                break
            r = self.queue.pop(0)
            picks.append((slot, r))
            if self.cache_impl == "paged":
                # reserve against concurrent picks: _fits sees the pool
                # before these installs allocate their pages
                reserved += self._pages_needed(r, g)
        if not picks:
            return 0
        self._install_group(picks)
        self.stats["refills"] += len(picks)
        for slot, r in picks:
            if w.requests[slot] is not None \
                    and w.filled[slot] >= w.targets[slot]:
                # satisfied by the prefill alone (max_new <= 1)
                self._retire(slot)
        return len(picks)

    def _retire(self, slot: int) -> None:
        w = self.wave
        while True:
            # retire boundary: the banked stream (incl. any pending install
            # anchor — a chain-refilled max_new<=1 request retires straight
            # from its prefill) must be materialized before r.out is cut
            self._flush_anchors()
            r = w.requests[slot]
            r.out = w.bufs[slot, : r.max_new].copy()
            r.latency_s = self.clock.now() - r.t_start
            self.done.append(r)
            # count tokens actually committed: a cycle-cap bailout can
            # retire a request with filled < max_new, which must not
            # inflate tokens_per_s
            committed = int(min(w.filled[slot], r.max_new))
            self.stats["tokens"] += committed
            if self.recorder is not None:
                self.recorder.on_done(r.uid, committed)
            w.requests[slot] = None
            w.targets[slot] = 0
            if w.pool is not None:
                donated = set()
                if w.cache is not None and not w.trunc[slot]:
                    # seed the radix tree with this request's committed
                    # string (prompt + every banked token except the last
                    # anchor, which was never written to cache); private
                    # pages covering the new suffix are DONATED to the
                    # tree, the rest are freed below
                    committed = np.concatenate(
                        [np.asarray(r.prompt, np.int32),
                         w.bufs[slot, : max(int(w.filled[slot]) - 1, 0)]])
                    hit = w.row_hits[slot]
                    donated = w.cache.insert(
                        committed, w.row_tables[slot],
                        private=set(w.row_pages[slot]),
                        min_donate_idx=len(hit.shared) if hit else 0)
                if w.row_hits[slot] is not None:
                    # drop this row's read refs on the shared prefix pages
                    w.cache.release(w.row_hits[slot])
                    w.row_hits[slot] = None
                leftover = [p for p in w.row_pages[slot] if p not in donated]
                if leftover:
                    # free before the refill below so the incoming request
                    # can reuse this row's pages immediately
                    w.pool.free(leftover)
                w.row_pages[slot] = []
                w.row_tables[slot] = None
            if not (self.refill and self.queue
                    and self._fits(self.queue[0])):
                return
            self._install(slot, self.queue.pop(0))
            self.stats["refills"] += 1
            if w.filled[slot] < w.targets[slot]:
                return
            # adopted request was satisfied by its prefill alone
            # (max_new <= 1): keep draining the queue into this slot

    def _finish_wave(self) -> None:
        w = self.wave
        self._flush_anchors()
        dt = self.clock.now() - w.t0
        self.stats["cycles"] += w.cycles * len(w.requests)
        self.stats["wall_s"] += dt
        self.stats["waves"] += 1
        self.stats["alpha"] = (self._alpha_num / self._alpha_den
                               if self._alpha_den else 0.0)
        if self._warm_durs:
            self.stats["warm_cycle_s"] = float(np.median(self._warm_durs))
            self.stats["warm_cycles"] = len(self._warm_durs)
        if w.pool is not None:
            self.stats["pool_peak_pages"] = max(
                self.stats["pool_peak_pages"], w.pool.peak_in_use)
            self.stats["pool_utilization"] = (
                self._util_sum / self._util_samples
                if self._util_samples else 0.0)
        if w.cache is not None:
            # delta since wave start: an engine-lifetime cache accumulates
            # evictions across waves and must not be re-counted per wave
            self.stats["prefix_evictions"] += w.cache.evictions - w.evictions0
            self.stats["prefix_cached_pages"] = w.cache.cached_pages
        if w.pool is not None and self.pool_scope == "engine":
            # borrowed-pool contract: harvest the device pool buffers so
            # the next wave's state re-adopts them (cached prefix pages
            # keep their KV across the turnover)
            self._pools = capture_pools(w.state)
        self.wave = None

    # ----------------------------------------------------- drain loop -----
    def run(self) -> Dict:
        """Synchronous drain loop (dispatch + complete back-to-back).

        ``wall_s`` accumulates per-wave deltas of the injected
        :class:`~repro.serving.metrics.Clock` — monotonic wall time by
        default, deterministic simulated time under a ``VirtualClock`` —
        the same timing source the async front-end uses, so sync and
        overlapped numbers are directly comparable."""
        while self.queue or self.wave is not None:
            if self.wave is None and not self.start_wave():
                break
            # start_wave can finish a wave outright (all-max_new<=1 burst)
            while self.wave is not None and self.step():
                pass
        s = dict(self.stats)
        s["tokens_per_s"] = (s["tokens"] / s["wall_s"]
                             if s["wall_s"] else 0.0)
        return s
