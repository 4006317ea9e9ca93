"""Continuous-batching serving engine over the fused SALR kernel path.

Replaces the per-batch serve loop with a slot-based decode batch
(DESIGN.md §7): a fixed set of ``n_slots`` cache rows each hold one
in-flight request at its own absolute position, so one jitted
``decode_step`` advances every active request per tick and finished
requests free their slot without recompiling anything.  Prompts are
right-padded to a small set of bucket lengths so prefill JITs a handful
of shapes; padding is *exact* (bitwise on CPU) for every mixer:

  * full-context attention / MLA: the padded tail is causally invisible
    during prefill and masked (then overwritten) by the per-slot decode
    position;
  * recurrent mixers (rglru, mlstm, slstm): masked-state prefill --
    ``prefill(logit_index=...)`` turns pad positions into identity state
    transitions, so the cached state equals an exact-length prefill's;
  * rolling-window attention (attn_local): the ring cache is built from
    the last ``window`` REAL positions per row, so padding never evicts
    prompt tokens;
  * MoE: routing is per-token (length-invariant), so co-batched slots
    and pad tokens cannot perturb another token's expert choices.

There is consequently no arch rejection list: every registered config,
including modality-frontend and encoder-decoder stacks (a frontend arch
carries its precomputed frontend embeddings on the ``Request``), serves
through this engine with per-request tokens bitwise identical to
``greedy_generate``.

The scheduler interleaves admission (prefill) and decode ticks over a
queue of requests with arrival times: each tick admits up to
``max_prefills_per_tick`` arrived requests into free slots, then runs
one decode step for the whole slot batch.  Accounting covers TTFT,
tok/s, queue depth, slot occupancy, admission wait, and per-bucket
prefill counts on a virtual clock fed by the measured wall time of the
jitted calls (idle gaps fast-forward to the next arrival instead of
sleeping).  Under ``jax.profiler.trace`` each tick also leaves spans on
the profiler's clock, nested as the calls nest: ``engine.step`` holds
``engine.admit`` (``rid=`` the request's id; children
``engine.page_plan``, ``engine.prefill``, ``engine.admit_wait``) and
``engine.decode`` (``engine.decode_dispatch``, ``engine.decode_wait``,
``engine.emit``), and every page-table upload is an
``engine.page_table_upload``.  ``h2d_uploads`` counts the host arrays
the engine sends to the device.  ``reset()`` clears ALL scheduling
state and every metric accumulator -- warm reruns start from a clean
clock while keeping the compiled callables and cache buffers.

KV storage is PAGED when the resolved plan's decode route says so
(``plan.route("decode").kv == "paged"``, the resolver default — the
engine applies the plan, it does not pick layouts itself): pageable
cache leaves (full-context GQA incl. int8, MLA latents) live in global
page pools of ``page_size``-position blocks with a per-slot
``page_table``, so HBM cost follows the pages actually allocated, not
``n_slots * max_ctx`` worst-case rings.  A host-side reference-counted
``PagePool`` hands out pages at admission (ceil(positions/page_size)
per request) and a ``RadixCache`` — a page-granularity radix tree over
prompt token ids — lets later requests reuse full prompt pages a
previous request already prefilled: the hit prefix is gathered into a
dense batch=1 cache and only the prompt SUFFIX is prefilled
(continuation prefill, ``M.prefill(prefix_cache=, pos_offset=)``),
which is where the TTFT win comes from.  Admission becomes
memory-pressure-aware: the FIFO head is admitted only while free pages
suffice (after trying LRU eviction of unreferenced radix leaves);
shared pages are never freed while any request or the tree still
references them.  Decode rides the Pallas paged-attention kernels
(``kernels/paged_attention.py``), whose per-slot math is bitwise equal
to the dense reference, so the greedy-parity contract above survives
the layout change.  Prefix sharing is enabled per-arch only when every
mixer is pageable (no rings/recurrent state/frontend/enc-dec) and the
plan's DECODE route keeps the KV pool native (a re-gathered int8/NF4
prefix would attend over dequantized values where the original prefill
attended over raw ones — not bitwise); paging itself applies to any
arch's pageable leaves at whatever precision the decode route names
(``plan.kv_dtype("decode")`` sizes the pools; a native prefill cache is
quantized on insert, so mixed plans pay quantization once per position).

All forwards run a phase-aware execution plan resolved ONCE at engine
construction (``core.execplan.resolve_plan``): the prefill ticks run the
plan's prefill routes, the decode ticks its decode routes.  With the
default ``backend="kernel"`` every compressed linear dispatches to its
fused Pallas op, and MoE layers take the kernel route the plan's
crossover table selects for each phase's token count — grouped ragged
GEMM at prefill scale, the decode-specialized masked grid (or the dense
oracle) at slot-batch scale.  The kernel MoE routes are bitwise
identical per token (models/moe.py), so a phase split cannot perturb the
co-batching independence the slot batch relies on.  Per-phase routes are
reported truthfully: ``metrics()["moe_route_prefill"]`` /
``["moe_route_decode"]`` for MoE archs, plus a ``plan`` echo.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import execplan
from repro.models import model as M
from repro.models.moe import moe_route_description as _moe_desc
from repro.train.step import make_decode_step, make_prefill_step


# ----------------------------------------------------------------- config

@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine shape/scheduling parameters."""
    n_slots: int = 4              # decode batch rows (max in-flight requests)
    max_ctx: int = 64             # per-slot cache capacity (prefix + prompt
    #                               + generated positions)
    buckets: tuple = ()           # prefill JIT lengths; () -> powers of two
    backend: str = "kernel"       # execution-plan backend for all forwards
    # resolved ExecutionPlan override; None -> resolve_plan(cfg,
    # backend=backend, phase_tokens={prefill: largest bucket,
    # decode: n_slots}) at engine construction
    plan: Optional[execplan.ExecutionPlan] = None
    max_prefills_per_tick: int = 1
    pad_id: int = 0
    # paged KV layout (used when the plan's decode route says kv="paged")
    page_size: int = M.PAGE_SIZE  # cache positions per pool page
    # total pool pages INCLUDING the reserved null page 0; None sizes the
    # pool so every slot can hold max_ctx (n_slots * ceil(max_ctx /
    # page_size) + 1) — shrink it to serve more slots than dense HBM
    # would allow and let admission block on page pressure instead
    n_pages: Optional[int] = None
    prefix_sharing: bool = True   # radix prefix cache (eligible archs)


def default_buckets(max_ctx: int, lo: int = 8) -> tuple:
    """Powers of two in [lo, max_ctx] (plus max_ctx when not a power)."""
    out, b = [], lo
    while b < max_ctx:
        out.append(b)
        b *= 2
    out.append(max_ctx)
    return tuple(dict.fromkeys(out))


def pick_bucket(length: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= length."""
    bs = sorted(buckets)
    i = bisect.bisect_left(bs, length)
    if i == len(bs):
        raise ValueError(f"prompt length {length} exceeds largest prefill "
                         f"bucket {bs[-1]}")
    return bs[i]


# --------------------------------------------------------------- requests

@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    prompt: tuple                 # token ids
    max_new_tokens: int
    arrival: float = 0.0          # seconds on the engine clock
    # precomputed frontend embeddings (frontend_len, d_model) -- required
    # for modality-frontend / encoder-decoder archs, None otherwise
    frontend: Optional[np.ndarray] = None


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: list
    arrival: float
    admitted_at: float
    first_token_at: float
    finished_at: float

    @property
    def ttft(self) -> float:
        return self.first_token_at - self.arrival

    @property
    def latency(self) -> float:
        return self.finished_at - self.arrival


@dataclasses.dataclass
class _Active:
    req: Request
    result: RequestResult
    slot: int
    pages: Optional[list] = None  # pool pages this request references


# ------------------------------------------------------- paged KV bookkeeping

class PagePool:
    """Host-side reference-counted page allocator over a global pool.

    Page 0 is the reserved null page (the scatter/stream target of dead
    page-table entries) and is never handed out.  A page's refcount is
    the number of active requests reading it plus one if the radix tree
    holds it; it returns to the free list only at refcount zero, so
    admission pressure can never reclaim a page something still reads."""

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self.refs = np.zeros((n_pages,), np.int32)
        self._free = list(range(n_pages - 1, 0, -1))  # pop() -> lowest first

    @property
    def n_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[list]:
        """n fresh pages at refcount 1, or None if the pool can't cover."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self.refs[p] = 1
        return pages

    def incref(self, pages) -> None:
        for p in pages:
            assert self.refs[p] > 0, f"incref on free page {p}"
            self.refs[p] += 1

    def decref(self, pages) -> list:
        freed = []
        for p in pages:
            self.refs[p] -= 1
            assert self.refs[p] >= 0, f"decref underflow on page {p}"
            if self.refs[p] == 0:
                self._free.append(p)
                freed.append(p)
        return freed


class _RadixNode:
    __slots__ = ("children", "key", "page", "parent", "last_used")

    def __init__(self, key=None, page=None, parent=None):
        self.children: dict = {}
        self.key = key
        self.page = page
        self.parent = parent
        self.last_used = 0


class RadixCache:
    """Page-granularity radix tree over prompt token ids.

    A node is one FULL page keyed by its page_size-token tuple (child
    edges are exact-page matches, so lookup is a straight walk).
    Holding a node counts as one pool reference on its page; eviction
    drops least-recently-used LEAVES whose page the tree alone
    references (refcount 1) — a page an active request still reads is
    skipped, it merely leaves the tree when evicted later and is freed
    by the request's own decref."""

    def __init__(self, pool: PagePool):
        self.pool = pool
        self.root = _RadixNode()
        self._clock = 0

    def match(self, page_keys) -> list:
        """Longest-prefix match; returns the hit pages (touches LRU)."""
        self._clock += 1
        node, pages = self.root, []
        for key in page_keys:
            child = node.children.get(key)
            if child is None:
                break
            child.last_used = self._clock
            pages.append(child.page)
            node = child
        return pages

    def insert(self, page_keys, pages) -> None:
        """Register a prompt's full-page path.  A key already present is
        only LRU-touched — the caller's duplicate private page stays
        request-owned and is freed at finish."""
        self._clock += 1
        node = self.root
        for key, page in zip(page_keys, pages):
            child = node.children.get(key)
            if child is None:
                child = _RadixNode(key=key, page=page, parent=node)
                node.children[key] = child
                self.pool.incref([page])      # the tree's own reference
            child.last_used = self._clock
            node = child

    def evict(self, n: int) -> int:
        """Free up to n pages by dropping LRU leaves at refcount 1.
        Returns the number actually freed (0 when every leaf is still
        referenced by an active request)."""
        freed = 0
        while freed < n:
            victim = None
            stack = [self.root]
            while stack:
                nd = stack.pop()
                for ch in nd.children.values():
                    if ch.children:
                        stack.append(ch)
                    elif self.pool.refs[ch.page] == 1 and (
                            victim is None or ch.last_used < victim.last_used):
                        victim = ch
            if victim is None:
                return freed
            del victim.parent.children[victim.key]
            self.pool.decref([victim.page])
            freed += 1
        return freed


# ----------------------------------------------------------------- engine

class ContinuousBatchingEngine:
    """Slot-based continuous batching over one model's decode cache.

    Drive it either with ``run(requests)`` (drains the queue, returns
    results + aggregate metrics) or ``submit`` + repeated ``step()``
    (tests / external loops).
    """

    def __init__(self, cfg: ArchConfig, params, ecfg: EngineConfig = None,
                 time_fn: Callable[[], float] = time.perf_counter):
        ecfg = ecfg or EngineConfig()
        kinds = {k for g in cfg.layer_groups for k in g.pattern}
        if "attn_local" in kinds and ecfg.max_ctx < cfg.window:
            # not an arch restriction, a cache-shape one: the prefill
            # ring is always `window` wide, so the slot cache must be at
            # least that wide for insert_cache_slot's shapes to line up
            raise ValueError(
                f"{cfg.name}: max_ctx={ecfg.max_ctx} is smaller than the "
                f"rolling-attention window {cfg.window}; size max_ctx >= "
                f"window")
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.prefix = cfg.decode_prefix_len
        self.buckets = tuple(sorted(
            ecfg.buckets or default_buckets(ecfg.max_ctx - self.prefix)))
        self._time = time_fn

        # ONE plan resolution per engine: prefill ticks run at bucket
        # scale (batch 1 x largest bucket bounds the crossover lookup),
        # decode ticks advance n_slots tokens.  greedy_generate parity
        # references must be handed THIS plan so both sides take
        # identical routes (launch/serve.py).
        self.plan = ecfg.plan or execplan.resolve_plan(
            cfg, backend=ecfg.backend,
            phase_tokens={"prefill": max(self.buckets),
                          "decode": ecfg.n_slots})
        prefill = make_prefill_step(cfg, plan=self.plan)
        decode = make_decode_step(cfg, plan=self.plan)

        # KV layout comes from the PLAN, not an engine knob: the
        # resolver routes decode to paged storage, the engine applies it
        self.paged = self.plan.kv_layout("decode") == "paged"
        self.page_size = ecfg.page_size
        self.max_pages = -(-ecfg.max_ctx // ecfg.page_size)
        self.n_pages = (ecfg.n_pages if ecfg.n_pages is not None
                        else ecfg.n_slots * self.max_pages + 1)
        # radix sharing needs every mixer's prompt state pageable (rings,
        # recurrent state and enc-dec/frontend prefixes are per-slot) and
        # an unquantized DECODE cache (see module docstring) — the plan's
        # decode kv_dtype is authoritative, not the cfg-wide knob
        self.sharable = (self.paged and ecfg.prefix_sharing
                         and kinds <= set(M.PAGEABLE_KINDS)
                         and not cfg.frontend and not cfg.encoder_groups
                         and self.plan.kv_dtype("decode") == "native")

        def prefill_fn(params, tokens, logit_index, frontend, prefix_cache,
                       pos_offset):
            logits, cache = prefill(params, {"tokens": tokens,
                                             "logit_index": logit_index,
                                             "frontend": frontend,
                                             "prefix_cache": prefix_cache},
                                    pos_offset)
            tok0 = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return tok0, cache

        def decode_fn(params, cache, tokens, pos):
            logits, cache = decode(params, cache, tokens, pos)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return nxt, cache

        if self.paged:
            def insert_fn(cache, rcache, slot, start):
                return M.insert_paged_cache_slot(cache, rcache, slot, start)
        else:
            def insert_fn(cache, rcache, slot, start):
                del start
                return M.insert_cache_slot(cache, rcache, slot)

        # the slot cache is donated on the hot paths: self.cache is
        # rebound to the result each call, so the old buffers would
        # otherwise be a full KV-cache copy per decode tick
        self._prefill = jax.jit(prefill_fn, static_argnums=(5,))
        self._decode = jax.jit(decode_fn, donate_argnums=(1,))
        self._insert = jax.jit(insert_fn, donate_argnums=(0,))
        self._gather = jax.jit(
            lambda cache, page_row: M.gather_prefix_cache(cache, cfg,
                                                          page_row))

        # the slot/pool cache is what DECODE reads, so it is allocated at
        # the decode route's KV precision; a native-precision prefill
        # cache headed into a quantized pool is quantized at insert
        kv_dt = self.plan.kv_dtype("decode")
        if self.paged:
            self.cache = M.init_paged_slot_cache(
                cfg, ecfg.n_slots, ecfg.max_ctx,
                page_size=ecfg.page_size, n_pages=self.n_pages,
                kv_dtype=kv_dt)
        else:
            self.cache = M.init_slot_cache(cfg, ecfg.n_slots, ecfg.max_ctx,
                                           kv_dtype=kv_dt)
        self.reset()

    def reset(self) -> None:
        """Clear ALL scheduling state and metric accumulators, keep the
        compiled callables and cache buffers (stale cache rows are
        masked or overwritten by design), so a warm engine serves a
        fresh trace without recompiling and without any accounting
        leakage from the previous run."""
        n = self.ecfg.n_slots
        self.slots: list = [None] * n         # Optional[_Active] per slot
        self._last_tok = np.zeros((n,), np.int32)
        self._pos = np.zeros((n,), np.int32)
        self.pending: list = []               # sorted by (arrival, rid)
        self.results: dict = {}
        self.now = 0.0
        self._queue_depths: list = []
        self._occupancy: list = []
        self._admit_waits: list = []          # per-request queue wait (s)
        self._bucket_counts: dict = {}        # prefill bucket -> count
        self.n_prefills = 0
        self.n_decode_ticks = 0
        self.h2d_uploads = 0
        # paged-KV state: fresh pool/radix (deterministic allocation
        # order), all page-table rows to the null page
        self.pool = PagePool(self.n_pages) if self.paged else None
        self.radix = RadixCache(self.pool) if self.paged else None
        self.n_evictions = 0
        self._pages_per_req: list = []
        self._shared_prompt_tokens = 0
        self._total_prompt_tokens = 0
        if self.paged:
            self._page_table = np.zeros((n, self.max_pages), np.int32)
            self.cache["page_table"] = jnp.asarray(self._page_table)

    # ---------------------------------------------------- spans and uploads

    @contextlib.contextmanager
    def _span(self, name: str, clock: bool = False, **meta):
        """``name`` as a profiler span; a ``clock`` span (device-bound
        work) also advances the virtual clock by its ``time_fn`` time."""
        with jax.profiler.TraceAnnotation(name, **meta):
            t0 = self._time() if clock else 0.0
            yield
            if clock:
                self.now += self._time() - t0

    def _to_device(self, x, dtype=None) -> jax.Array:
        """One host -> device array, counted in ``h2d_uploads``."""
        self.h2d_uploads += 1
        return jnp.asarray(x, dtype)

    def _upload_page_table(self) -> None:
        with jax.profiler.TraceAnnotation("engine.page_table_upload"):
            self.cache["page_table"] = self._to_device(self._page_table)

    # ------------------------------------------------------------- intake

    def submit(self, req: Request) -> None:
        length = len(req.prompt)
        bucket = pick_bucket(length, self.buckets)
        last_pos = self.prefix + length + req.max_new_tokens - 1
        if max(self.prefix + bucket, last_pos) > self.ecfg.max_ctx:
            raise ValueError(
                f"request {req.rid}: prefix {self.prefix} + prompt {length} "
                f"+ {req.max_new_tokens} new tokens does not fit "
                f"max_ctx={self.ecfg.max_ctx}")
        if self.paged:
            worst = max(self.prefix + bucket, last_pos + 1)
            need = -(-worst // self.page_size)
            if need > self.n_pages - 1:
                raise ValueError(
                    f"request {req.rid}: needs {need} pages but the pool "
                    f"holds {self.n_pages - 1} (page 0 is reserved); it "
                    f"could never be admitted")
        if self.cfg.frontend or self.cfg.encoder_groups:
            want = (self.cfg.frontend_len, self.cfg.d_model)
            got = None if req.frontend is None \
                else tuple(np.shape(req.frontend))
            if got != want:
                raise ValueError(
                    f"request {req.rid}: {self.cfg.name} needs precomputed "
                    f"frontend embeddings of shape {want} on "
                    f"Request.frontend, got {got}")
        bisect.insort(self.pending, (req.arrival, req.rid, req))

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def free_slots(self) -> list:
        return [i for i, s in enumerate(self.slots) if s is None]

    # ---------------------------------------------------------- scheduler

    def _page_keys(self, prompt) -> list:
        ps = self.page_size
        return [tuple(prompt[i * ps:(i + 1) * ps])
                for i in range(len(prompt) // ps)]

    def _page_plan(self, req: Request):
        """Sharing + allocation plan: (hit_pages, n_new, bucket, lp).

        ``hit_pages`` are radix pages covering the first ``lp`` prompt
        tokens — clamped so at least one suffix token remains to
        prefill (the admission needs its logits) and so the suffix
        bucket still fits the slot's page-table extent.  ``n_new`` is
        the fresh-page count covering max(prefill write extent, prompt +
        generation)."""
        length = len(req.prompt)
        ps = self.page_size
        hit: list = []
        if self.sharable:
            hit = self.radix.match(self._page_keys(req.prompt))
            usable = min(len(hit), (length - 1) // ps)
            cap = self.max_pages * ps
            while usable and (usable * ps
                              + pick_bucket(length - usable * ps,
                                            self.buckets) > cap):
                usable -= 1
            hit = hit[:usable]
        lp = len(hit) * ps
        bucket = pick_bucket(length - lp, self.buckets)
        total_pos = max(self.prefix + lp + bucket,
                        self.prefix + length + req.max_new_tokens)
        n_total = min(-(-total_pos // ps), self.max_pages)
        return hit, n_total - len(hit), bucket, lp

    def _pages_available(self, req: Request) -> bool:
        """Can the FIFO head be admitted right now?  Tries LRU radix
        eviction to cover the shortfall; never touches referenced pages."""
        if not self.paged:
            return True
        hit, n_new, _, _ = self._page_plan(req)
        if n_new > self.pool.n_free:
            # shield the head's own hit path: a tree-only hit page is
            # otherwise a legal eviction victim, which would invalidate
            # the plan we just computed
            self.pool.incref(hit)
            self.n_evictions += self.radix.evict(n_new - self.pool.n_free)
            self.pool.decref(hit)
        return n_new <= self.pool.n_free

    def _admit(self, req: Request, slot: int) -> None:
        length = len(req.prompt)
        hit: list = []
        lp = 0
        with self._span("engine.page_plan"):
            if self.paged:
                # step() already verified feasibility via _pages_available;
                # the re-match returns the same pages (nothing mutated since)
                hit, n_new, bucket, lp = self._page_plan(req)
                new_pages = self.pool.alloc(n_new)
                assert new_pages is not None, "admission without free pages"
                self.pool.incref(hit)     # this request's ref on shared pages
                pages = hit + new_pages
                row = np.zeros((self.max_pages,), np.int32)
                row[:len(pages)] = pages
                self._page_table[slot] = row
                self._upload_page_table()
                self._pages_per_req.append(len(pages))
                self._shared_prompt_tokens += lp
                self._total_prompt_tokens += length
            else:
                pages = None
                bucket = pick_bucket(length, self.buckets)
        suffix = req.prompt[lp:]
        padded = np.full((1, bucket), self.ecfg.pad_id, np.int32)
        padded[0, :len(suffix)] = np.asarray(suffix, np.int32)
        fe = (None if req.frontend is None
              else self._to_device(req.frontend)[None])
        # queue wait is time spent pending, not the request's own prefill
        self._admit_waits.append(max(0.0, self.now - req.arrival))
        with self._span("engine.prefill", clock=True):
            if lp:
                # continuation prefill: gather the shared pages into a
                # dense batch=1 prefix, prefill only the suffix at offset lp
                prefix_cache = self._gather(self.cache,
                                            self._to_device(hit, jnp.int32))
                tok0, rcache = self._prefill(
                    self.params, self._to_device(padded),
                    self._to_device(len(suffix) - 1, jnp.int32), fe,
                    prefix_cache, lp)
            else:
                tok0, rcache = self._prefill(
                    self.params, self._to_device(padded),
                    self._to_device(self.prefix + length - 1, jnp.int32), fe,
                    None, 0)
            self.cache = self._insert(self.cache, rcache,
                                      self._to_device(slot, jnp.int32),
                                      self._to_device(lp, jnp.int32))
        with self._span("engine.admit_wait", clock=True):
            tok0 = int(tok0[0])
            jax.block_until_ready(jax.tree_util.tree_leaves(self.cache)[0])
        self.n_prefills += 1
        self._bucket_counts[bucket] = self._bucket_counts.get(bucket, 0) + 1
        if self.sharable:
            # register this prompt's FULL pages (hit + freshly prefilled;
            # partial tail/generation pages never enter the tree)
            keys = self._page_keys(req.prompt)
            self.radix.insert(keys, pages[:len(keys)])

        res = RequestResult(rid=req.rid, tokens=[tok0], arrival=req.arrival,
                            admitted_at=self.now, first_token_at=self.now,
                            finished_at=float("nan"))
        act = _Active(req=req, result=res, slot=slot, pages=pages)
        self._last_tok[slot] = tok0
        self._pos[slot] = self.prefix + length
        self.slots[slot] = act
        if len(res.tokens) >= req.max_new_tokens:
            self._finish(act)

    def _finish(self, act: _Active) -> None:
        act.result.finished_at = self.now
        self.results[act.req.rid] = act.result
        self.slots[act.slot] = None           # slot reusable immediately
        if self.paged and act.pages is not None:
            # pages at refcount zero (generation tail, unshared prompt)
            # return to the pool; tree-held pages stay until evicted.
            # The slot's table row drops to the null page so its stale
            # decode writes can never corrupt a reallocated page.
            self.pool.decref(act.pages)
            self._page_table[act.slot] = 0
            self._upload_page_table()

    def _decode_tick(self) -> None:
        with self._span("engine.decode_dispatch", clock=True):
            tokens = self._to_device(self._last_tok[:, None])
            pos = self._to_device(self._pos)
            nxt, self.cache = self._decode(self.params, self.cache, tokens,
                                           pos)
        with self._span("engine.decode_wait", clock=True):
            nxt = np.asarray(nxt)                 # blocks on the step
        self.n_decode_ticks += 1
        self._occupancy.append(self.n_active)
        with self._span("engine.emit"):
            for slot, act in enumerate(self.slots):
                if act is None:
                    continue
                act.result.tokens.append(int(nxt[slot]))
                self._last_tok[slot] = nxt[slot]
                self._pos[slot] += 1
                if len(act.result.tokens) >= act.req.max_new_tokens:
                    self._finish(act)

    def step(self) -> bool:
        """One scheduler tick: admit arrived requests into free slots,
        then advance every active slot by one token.  Returns False when
        fully drained (nothing active, nothing pending)."""
        with self._span("engine.step"):
            self._queue_depths.append(len(self.pending))
            admitted = 0
            while (self.pending and self.slots.count(None)
                   and self.pending[0][0] <= self.now
                   and admitted < self.ecfg.max_prefills_per_tick):
                if not self._pages_available(self.pending[0][2]):
                    break             # head-of-line blocks on page pressure
                _, _, req = self.pending.pop(0)
                with self._span("engine.admit", rid=req.rid):
                    self._admit(req, self.free_slots()[0])
                admitted += 1
            if self.n_active:
                with self._span("engine.decode"):
                    self._decode_tick()
                return True
            if self.pending:                  # idle: jump to next arrival
                self.now = max(self.now, self.pending[0][0])
                return True
            return False

    def run(self, requests: Optional[Sequence[Request]] = None):
        """Drain the queue; returns ({rid: RequestResult}, metrics)."""
        for r in requests or ():
            self.submit(r)
        while self.step():
            pass
        return self.results, self.metrics()

    # ------------------------------------------------------------ metrics

    def metrics(self) -> dict:
        done = list(self.results.values())
        total_tok = sum(len(r.tokens) for r in done)
        ttfts = sorted(r.ttft for r in done) or [float("nan")]
        return {
            "requests": len(done),
            "total_tokens": total_tok,
            "wall_s": self.now,
            "tok_s": total_tok / self.now if self.now > 0 else float("nan"),
            "ttft_mean_s": float(np.mean(ttfts)),
            "ttft_p50_s": ttfts[len(ttfts) // 2],
            "ttft_max_s": ttfts[-1],
            "queue_depth_mean": (float(np.mean(self._queue_depths))
                                 if self._queue_depths else 0.0),
            "queue_depth_max": max(self._queue_depths, default=0),
            "slot_occupancy_mean": (float(np.mean(self._occupancy))
                                    if self._occupancy else 0.0),
            "admission_wait_mean_s": (float(np.mean(self._admit_waits))
                                      if self._admit_waits else 0.0),
            "prefills_per_bucket": dict(sorted(self._bucket_counts.items())),
            "n_prefills": self.n_prefills,
            "n_decode_ticks": self.n_decode_ticks,
            "h2d_uploads": self.h2d_uploads,
            "n_slots": self.ecfg.n_slots,
            "buckets": self.buckets,
            "kv_layout": "paged" if self.paged else "dense",
            "page_size": self.page_size if self.paged else 0,
            "n_pages": self.n_pages if self.paged else 0,
            "pages_free": self.pool.n_free if self.paged else 0,
            "pages_per_request_mean": (float(np.mean(self._pages_per_req))
                                       if self._pages_per_req else 0.0),
            "prefix_hit_rate": (self._shared_prompt_tokens
                                / self._total_prompt_tokens
                                if self._total_prompt_tokens else 0.0),
            "evictions": self.n_evictions,
            # an explicit EngineConfig.plan supersedes the backend knob;
            # echoing the unused knob would misreport the run
            "backend": (self.ecfg.backend if self.ecfg.plan is None
                        else "custom-plan"),
            "plan": self.plan.describe(),
            # resolved precision per phase (what actually ran, not what
            # the cfg asked for — an explicit plan overrides the knobs)
            "precision": {ph: {"repr": self.plan.base_repr(ph),
                               "kv_dtype": self.plan.kv_dtype(ph)}
                          for ph in ("prefill", "decode", "train")},
            **({"moe_route_prefill": _moe_desc(self.cfg,
                                               self.plan.route("prefill"),
                                               self.params),
                "moe_route_decode": _moe_desc(self.cfg,
                                              self.plan.route("decode"),
                                              self.params)}
               if self.cfg.n_experts else {}),
        }
