"""Continuous-batching multi-tenant decode loop.

Batch layout: ``slots`` decode rows, grouped into tiles of ``tile``
consecutive rows. Each tile is bound to at most one resident adapter slot;
the int32 ``[n_tiles]`` routing vector (``tile_gid``) is a *runtime* input
to the jitted decode step, so admission / recycling / adapter re-binding
never recompile — the grouped LoRA kernel gathers each tile's (A, B) pair
into VMEM by scalar-prefetched index (``kernels/lora_grouped.py``), and the
per-slot KV cache (``model.init_cache(per_slot=True)``) holds every row at
its own position.

Scheduling is step-granular continuous batching: at each step the admission
pass (FIFO with skip-ahead) places queued requests into compatible tiles,
then one ``decode_step`` advances every active row — prompt rows consume
their next prompt token (prefill-as-decode), generation rows feed back the
previously sampled token. Finished rows recycle immediately: pages return
to the :class:`~repro.serve.paged.PagedKVAllocator`, the adapter pin drops,
and an emptied tile unbinds so its adapter becomes evictable.

Admission gates, in order:
1. a compatible tile (same adapter with a free row, or a fully-idle tile);
2. KV pages for ``len(prompt) + max_new`` tokens (reserved up front — an
   admitted request can never die of allocator exhaustion mid-decode);
3. optional analytic memory headroom: ``mem_budget_mb`` against
   ``benchmarks/memsim.serve_residency`` (weights + resident adapters +
   live KV pages + decode working set).

Determinism: every row's math is independent of its neighbours (per-row
attention mask/positions, per-row adapter gather, greedy argmax), and a
row's cache lines are zeroed at assignment — so a request's token stream
depends only on its own prompt and adapter, not on arrival interleaving or
slot placement (asserted in tests/test_serving.py).
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.policy import STRUCTURED, ExecutionPolicy
from repro.models import model as model_lib
from repro.serve.paged import PagedKVAllocator
from repro.serve.store import AdapterStore, StoreFull
from repro.telemetry import DISABLED as _NO_TELEMETRY
from repro.telemetry import AdmissionEvent
from repro.telemetry.metrics import CounterGroup, MetricRegistry

log = logging.getLogger("repro.serve")


@dataclasses.dataclass(frozen=True)
class Request:
    rid: str                  #: unique request id
    adapter: str              #: tenant/adapter uid (AdapterStore key)
    prompt: Tuple[int, ...]   #: prompt token ids (fed prefill-as-decode)
    max_new: int              #: tokens to generate after the prompt


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    pending: List[int] = dataclasses.field(default_factory=list)
    last: int = 0
    out: List[int] = dataclasses.field(default_factory=list)


def _reset_slot(cache, b: int):
    """Zero slot ``b``'s rows across every cache leaf (fresh assignment —
    no state leaks from the row's previous occupant). Stacked leaves are
    ``[L, B, ...]`` (slot axis 1); the unstacked ``block0``/``enc_out``
    entries carry the slot axis at 0."""
    out = {}
    for key, sub in cache.items():
        ax = 0 if key in ("block0", "enc_out") else 1
        idx = (slice(None),) * ax + (b,)
        out[key] = jax.tree_util.tree_map(
            lambda l: l.at[idx].set(jnp.zeros_like(l[idx])), sub)
    return out


class ContinuousBatcher:
    """Multi-tenant continuous-batching decoder over an AdapterStore.

    ``register_adapter`` publishes a tenant's (A, B) tree to the host-side
    registry (the offload tier); the store pulls it into HBM residency on
    first admission and LRU-evicts it when unpinned and cold.
    """

    def __init__(self, cfg, store: AdapterStore, *, slots: int = 8,
                 tile: int = 2, max_len: int = 128, page_size: int = 16,
                 policy: ExecutionPolicy = STRUCTURED,
                 mem_budget_mb: Optional[float] = None,
                 weights_fmt: str = "bf16", rank: Optional[int] = None,
                 telemetry=None):
        if slots % tile:
            raise ValueError(f"slots ({slots}) must be a multiple of the "
                             f"tile size ({tile})")
        self.cfg = cfg
        self.store = store
        self.slots = slots
        self.tile = tile
        self.n_tiles = slots // tile
        self.max_len = max_len
        self.policy = policy
        self.mem_budget_mb = mem_budget_mb
        self.weights_fmt = weights_fmt
        self.rank = rank if rank is not None else cfg.lora.rank
        self.cache = model_lib.init_cache(cfg, slots, max_len, per_slot=True)
        self.alloc = PagedKVAllocator(slots * max_len // page_size, page_size)
        self.tile_adapter: List[Optional[str]] = [None] * self.n_tiles
        self.tile_gid = np.zeros(self.n_tiles, np.int32)
        self._rows = [_Slot() for _ in range(slots)]
        self._registry: Dict[str, object] = {}
        self.queue: List[Request] = []
        self.results: Dict[str, List[int]] = {}
        self.counters = CounterGroup(
            "serve", ("admitted", "completed", "steps", "prefill_tokens",
                      "decoded_tokens", "rejected_pages",
                      "rejected_headroom", "rejected_tiles",
                      "rejected_store"))
        # one namespaced registry over the three formerly-private counter
        # dicts (serve.* / store.* / pages.*); a telemetry object shares its
        # registry (and records spans + admission events), otherwise the
        # batcher owns a local one — snapshot via .metrics()
        self._tel = telemetry if telemetry is not None else _NO_TELEMETRY
        self.registry = (telemetry.registry if telemetry is not None
                         else MetricRegistry())
        self.registry.register_group(self.counters)
        self.registry.register_group(self.store.counters)
        self.registry.register_group(self.alloc.counters)
        self._jstep = jax.jit(
            lambda p, c, t, g: model_lib.decode_step(
                p, cfg, c, t, policy=policy, adapter_tiles=g))

    # -- tenant registry ----------------------------------------------------

    def register_adapter(self, uid: str, adapters) -> None:
        self._registry[uid] = adapters

    # -- metrics ------------------------------------------------------------

    def metrics(self) -> Dict[str, object]:
        """Unified namespaced snapshot (serve.* / store.* / pages.*) —
        what ``benchmarks/serving.py`` reports."""
        return self.registry.snapshot()

    def _reject(self, req: Request, reason: str) -> bool:
        self.counters[f"rejected_{reason}"] += 1
        if self._tel.enabled:
            self._tel.emit(AdmissionEvent(
                action="reject", rid=req.rid, adapter=req.adapter,
                reason=reason, step=self.counters["steps"]))
        return False

    # -- admission ----------------------------------------------------------

    def submit(self, req: Request) -> None:
        if len(req.prompt) + req.max_new > self.max_len:
            raise ValueError(f"request {req.rid!r} needs "
                             f"{len(req.prompt) + req.max_new} tokens but "
                             f"max_len is {self.max_len}")
        if req.adapter not in self._registry:
            raise KeyError(f"adapter {req.adapter!r} not registered")
        self.queue.append(req)

    def _tile_rows(self, t: int) -> range:
        return range(t * self.tile, (t + 1) * self.tile)

    def _find_tile(self, uid: str) -> Optional[int]:
        for t, bound in enumerate(self.tile_adapter):
            if bound == uid and any(self._rows[b].req is None
                                    for b in self._tile_rows(t)):
                return t
        for t, bound in enumerate(self.tile_adapter):
            if bound is None:
                return t
        return None

    def _headroom_ok(self, extra_adapter: bool, extra_tokens: int) -> bool:
        if self.mem_budget_mb is None:
            return True
        from repro.runtime.degrade import _import_memsim
        try:
            memsim = _import_memsim()
        except ImportError:
            return True          # stripped deployment: cannot validate
        resident = min(self.store.resident + (1 if extra_adapter else 0),
                       self.store.capacity)
        pages = self.alloc.used_pages + self.alloc.pages_for(extra_tokens)
        r = memsim.serve_residency(
            self.cfg, rank=self.rank, resident_adapters=resident,
            kv_pages=pages, page_size=self.alloc.page_size,
            batch=self.slots, weights_fmt=self.weights_fmt)
        return r["total_mb"] <= self.mem_budget_mb

    def _try_place(self, req: Request) -> bool:
        t = self._find_tile(req.adapter)
        if t is None:
            return self._reject(req, "tiles")
        if not self.store.can_admit(req.adapter):
            return self._reject(req, "store")
        total = len(req.prompt) + req.max_new
        if not self._headroom_ok(
                self.store.lookup(req.adapter) is None, total):
            return self._reject(req, "headroom")
        if not self.alloc.reserve(req.rid, total):
            return self._reject(req, "pages")
        try:
            slot = self.store.acquire(req.adapter,
                                      self._registry[req.adapter])
        except StoreFull:
            self.alloc.free(req.rid)
            return self._reject(req, "store")
        if self.tile_adapter[t] is None:
            self.tile_adapter[t] = req.adapter
        self.tile_gid[t] = slot
        b = next(i for i in self._tile_rows(t) if self._rows[i].req is None)
        with self._tel.span("serve/reset_slot"):
            self.cache = _reset_slot(self.cache, b)
        self._rows[b] = _Slot(req=req, pending=list(req.prompt))
        self.counters["admitted"] += 1
        if self._tel.enabled:
            self._tel.emit(AdmissionEvent(
                action="admit", rid=req.rid, adapter=req.adapter,
                step=self.counters["steps"]))
        return True

    def _admit(self) -> None:
        still = []
        for req in self.queue:          # FIFO with skip-ahead
            if not self._try_place(req):
                still.append(req)
        self.queue = still

    def _recycle(self, b: int) -> None:
        row = self._rows[b]
        self.alloc.free(row.req.rid)
        self.store.release(row.req.adapter)
        self.results[row.req.rid] = row.out
        self._rows[b] = _Slot()
        t = b // self.tile
        if all(self._rows[i].req is None for i in self._tile_rows(t)):
            self.tile_adapter[t] = None   # adapter now evictable
        self.counters["completed"] += 1
        if self._tel.enabled:
            self._tel.emit(AdmissionEvent(
                action="complete", rid=row.req.rid, adapter=row.req.adapter,
                step=self.counters["steps"]))

    # -- decode -------------------------------------------------------------

    @property
    def active(self) -> int:
        return sum(r.req is not None for r in self._rows)

    def step(self) -> bool:
        """Admit, then advance every active row by one token. Returns False
        when there is nothing to do (no active rows, empty queue)."""
        tel = self._tel
        with tel.span("serve/admission"):
            self._admit()
        if self.active == 0:
            return False
        toks = np.zeros((self.slots, 1), np.int32)
        for b, row in enumerate(self._rows):
            if row.req is not None:
                toks[b, 0] = row.pending[0] if row.pending else row.last
        # prompt and generation rows share one step (prefill-as-decode)
        with tel.span("serve/dispatch"):
            logits, self.cache = self._jstep(
                self.store.params, self.cache, jnp.asarray(toks),
                jnp.asarray(self.tile_gid))
        with tel.span("serve/argmax_sync"):
            nxt = np.asarray(jnp.argmax(logits[:, 0], -1))
        self.counters["steps"] += 1
        done = []
        for b, row in enumerate(self._rows):
            if row.req is None:
                continue
            if row.pending:
                row.pending.pop(0)
                self.counters["prefill_tokens"] += 1
                if row.pending:
                    continue          # still prefilling; logits unused
            row.last = int(nxt[b])
            row.out.append(row.last)
            self.counters["decoded_tokens"] += 1
            if len(row.out) >= row.req.max_new:
                done.append(b)
        for b in done:
            self._recycle(b)
        return True

    def run(self, requests: Sequence[Request] = (),
            max_steps: int = 100_000) -> Dict[str, List[int]]:
        """Drain ``requests`` (plus anything already queued/active) to
        completion; returns {rid: generated tokens} for all completions
        (``self.results`` accumulates across calls)."""
        for r in requests:
            self.submit(r)
        for _ in range(max_steps):
            if not self.step():
                break
        if self.queue or self.active:
            raise RuntimeError(
                f"serve loop stalled: {len(self.queue)} queued, "
                f"{self.active} active after {self.counters['steps']} steps "
                f"(requests too large for the slot/page budget?)")
        return self.results
