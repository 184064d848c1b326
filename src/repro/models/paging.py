"""Paged KV cache: fixed-size blocks, free-list allocation, CoW sharing.

The dense slot cache (models/cache.py) gives every slot a contiguous
``max_len`` KV extent, so HBM residency scales with the worst-case
request and identical system prompts are stored once per user. This
module replaces that with **paged** storage (DESIGN.md §12):

- Every attention layer's KV lives in a **page pool** — ``n_pages``
  fixed-size pages of ``page_size`` positions each, int8 values plus the
  per-(position, head) f32 scale vectors paging along with the data.
  A *logical* page id indexes the same physical page in every layer's
  pool, so one block table per slot serves the whole stack.
- Each slot owns a **block table** row ``(pages_per_slot,)`` of page
  ids. Decode locates the write/read page by ``pos // page_size`` —
  pure gather/scatter indirection, no copies (layers/attention.py).
- Pages are **refcounted**: requests that declare a shared prompt
  prefix map the prefix's full pages read-only (refcount > 1) and the
  divergence page is forked copy-on-write. The divergence point is
  known at admission (the suffix differs from the first non-shared
  token), so the serving engine forks **eagerly at commit** — the
  boundary page is committed from the request's own scratch;
  :meth:`PageAllocator.fork` implements the general lazy rule and is
  the contract the property tests pin down.
- **Page 0 is the reserved null page**: free decode lanes and masked
  commit chunks scatter there, unwritten block-table entries point
  there, and it is excluded from integrity fault attribution. It is
  never allocated and never read as live data.

Prefill runs against a **raw bf16 batch-1 scratch cache** (plain
``init_cache(cfg, 1, max_len, kv_quant=False)``) in fixed-token chunks;
:func:`paged_commit` quantizes the finished scratch once and scatters it
into the pools. Quantization is per-(position, head), so it commutes
with chunking — the committed bytes are identical to a monolithic
prefill's, which is what keeps the paged engine token-bit-identical to
the dense oracle.

Integrity (DESIGN.md §8) moves from per-slot to **per-page** checksums:
:func:`paged_checksums` folds pool leaves to the page axis and
slot-metadata leaves (len / step / block table) to the slot axis, so
at-rest corruption pins to a physical page; the engine maps the page
back to the slots (and prefix-registry entries) holding it and contains
exactly those.

Host-side bookkeeping (:class:`PageAllocator`, :class:`SlotPager`,
:class:`PrefixRegistry`) is plain Python — page placement is decided on
host, device code only ever sees block tables. Under tensor-parallel
serving the pools shard head-parallel exactly like the dense KV leaves
(same leaf names, ``sharding.tp.TPContext.cache_specs``); page ids are
global, so one host allocator drives every shard.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.models.cache import quantize_kv
from repro.models.config import ModelConfig


class PagingError(RuntimeError):
    """Page bookkeeping violation: double free, unknown page, pool
    exhaustion, or a slot assignment clashing with a live tenant."""


# ---------------------------------------------------------------------------
# Host-side bookkeeping
# ---------------------------------------------------------------------------


class PageAllocator:
    """Free-list page allocator with refcounts and CoW fork.

    Page ids run ``1..n_pages-1``; page 0 is the reserved null page and
    is never handed out. ``alloc`` returns a page with refcount 1;
    ``retain``/``release`` move the count, and the page returns to the
    free list exactly when the count hits zero (unless quarantined).
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise PagingError(
                f"n_pages={n_pages}: need at least 2 (page 0 is the reserved "
                "null page)"
            )
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        # LIFO free list, lowest ids first out — keeps traces reproducible.
        self._free = list(range(self.n_pages - 1, 0, -1))
        self._ref: dict[int, int] = {}
        self._quarantined: set[int] = set()
        self.peak_used = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return len(self._ref)

    @property
    def quarantined_pages(self) -> int:
        return len(self._quarantined)

    def refcount(self, pid: int) -> int:
        return self._ref.get(pid, 0)

    def alloc(self) -> int:
        if not self._free:
            raise PagingError(
                f"KV page pool exhausted ({self.n_pages} pages, "
                f"{len(self._quarantined)} quarantined); admission should "
                "have checked free-page capacity"
            )
        pid = self._free.pop()
        self._ref[pid] = 1
        self.peak_used = max(self.peak_used, len(self._ref))
        return pid

    def retain(self, pid: int) -> None:
        if pid not in self._ref:
            raise PagingError(f"retain of non-live page {pid}")
        self._ref[pid] += 1

    def release(self, pid: int) -> None:
        if pid not in self._ref:
            raise PagingError(f"release of non-live page {pid} (double free?)")
        self._ref[pid] -= 1
        if self._ref[pid] == 0:
            del self._ref[pid]
            if pid not in self._quarantined:
                self._free.append(pid)

    def fork(self, pid: int) -> tuple[int, bool]:
        """Copy-on-write: declare intent to *write* page ``pid``.

        Exclusively held (refcount 1): returns ``(pid, False)`` — write
        in place. Shared: the caller's reference moves to a fresh page
        and the shared bytes stay untouched for the other holders —
        returns ``(new_pid, True)`` and the caller must copy the page
        bytes before diverging.
        """
        if pid not in self._ref:
            raise PagingError(f"fork of non-live page {pid}")
        if self._ref[pid] == 1:
            return pid, False
        # alloc before dropping the shared reference: pool exhaustion must
        # leave the refcounts untouched (the caller keeps its old handle)
        new_pid = self.alloc()
        self._ref[pid] -= 1
        return new_pid, True

    def quarantine(self, pid: int) -> None:
        """Permanently retire a page (repeat integrity offender). Takes
        effect immediately if free, else when its refcount drains."""
        if pid == 0 or pid in self._quarantined:
            return
        self._quarantined.add(pid)
        if pid in self._free:
            self._free.remove(pid)


class SlotPager:
    """Per-slot page assignment on top of :class:`PageAllocator`.

    Tracks which page ids each slot's block table holds and which of
    them the slot *owns* (allocated for it) versus maps *shared*
    (retained from a prefix-registry entry, never written by this slot).
    """

    def __init__(self, allocator: PageAllocator, n_slots: int, pages_per_slot: int):
        self.allocator = allocator
        self.n_slots = int(n_slots)
        self.pages_per_slot = int(pages_per_slot)
        self._pages: dict[int, list[int]] = {}
        self._owned: dict[int, list[bool]] = {}

    def pages_needed(self, extent: int) -> int:
        return -(-int(extent) // self.allocator.page_size)

    def holds(self, slot: int) -> bool:
        return slot in self._pages

    def pages(self, slot: int) -> list[int]:
        return list(self._pages.get(slot, ()))

    def owned_pages(self, slot: int) -> list[int]:
        return [
            p for p, own in zip(self._pages.get(slot, ()), self._owned.get(slot, ()))
            if own
        ]

    def assign(
        self, slot: int, shared_ids: Iterable[int], n_total: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """Map ``shared_ids`` read-only and allocate the rest for ``slot``.

        Returns ``(table, write_mask)``: the block-table row padded with
        the null page to ``pages_per_slot``, and the owned-page mask the
        commit scatter uses (shared pages are never written).
        """
        if slot in self._pages:
            raise PagingError(f"slot {slot} already holds pages; release first")
        shared = list(shared_ids)
        if n_total > self.pages_per_slot or len(shared) > n_total:
            raise PagingError(
                f"slot {slot}: {n_total} pages requested "
                f"({len(shared)} shared) > pages_per_slot={self.pages_per_slot}"
            )
        for pid in shared:
            self.allocator.retain(pid)
        ids = shared + [self.allocator.alloc() for _ in range(n_total - len(shared))]
        owned = [False] * len(shared) + [True] * (n_total - len(shared))
        self._pages[slot] = ids
        self._owned[slot] = owned
        table = np.zeros((self.pages_per_slot,), np.int32)
        table[: len(ids)] = ids
        mask = np.zeros((self.pages_per_slot,), bool)
        mask[: len(ids)] = owned
        return table, mask

    def release(self, slot: int) -> None:
        for pid in self._pages.pop(slot, []):
            self.allocator.release(pid)
        self._owned.pop(slot, None)

    def slots_holding(self, pid: int) -> list[int]:
        return [s for s, ids in self._pages.items() if pid in ids]


@dataclasses.dataclass
class PrefixEntry:
    tokens: np.ndarray
    length: int
    page_ids: tuple
    scratch: object  # immutable raw scratch tree at `length` tokens
    hits: int = 0


class PrefixRegistry:
    """Shared-prefix registry: prompt-prefix bytes -> retained pages +
    the raw scratch snapshot at the prefix boundary.

    A hit maps the prefix's **full** pages read-only into the new slot
    and resumes chunked prefill from the snapshot, so the prefix is
    neither recomputed nor re-stored. Entries are LRU-ordered; the
    engine evicts under page pressure (capacity check) and the registry
    self-bounds at ``capacity`` entries.
    """

    def __init__(self, allocator: PageAllocator, capacity: int = 64):
        self.allocator = allocator
        self.capacity = int(capacity)
        self._entries: dict[bytes, PrefixEntry] = {}
        self.evictions = 0

    @staticmethod
    def key(tokens, tag=None) -> bytes:
        """Registry key: the prefix token bytes, scoped by ``tag`` — the
        engine passes its runtime precision dial, because a prefix
        prefilled at one tier is NOT bit-identical to the same tokens
        prefilled at another and must never be reused across tiers."""
        base = np.ascontiguousarray(np.asarray(tokens, np.int32)).tobytes()
        if tag is None:
            return base
        return repr(tag).encode() + b"|" + base

    def __len__(self) -> int:
        return len(self._entries)

    def peek(self, tokens, tag=None) -> Optional[PrefixEntry]:
        """Lookup without the LRU touch or hit count — the admission
        capacity check uses this to size the page ask without committing
        to the hit."""
        return self._entries.get(self.key(tokens, tag))

    def lookup(self, tokens, tag=None) -> Optional[PrefixEntry]:
        k = self.key(tokens, tag)
        entry = self._entries.pop(k, None)
        if entry is None:
            return None
        self._entries[k] = entry  # LRU touch
        entry.hits += 1
        return entry

    def register(self, tokens, page_ids: Iterable[int], scratch, tag=None) -> bool:
        k = self.key(tokens, tag)
        if k in self._entries:
            return False
        ids = tuple(page_ids)
        for pid in ids:
            self.allocator.retain(pid)
        self._entries[k] = PrefixEntry(
            tokens=np.asarray(tokens, np.int32).copy(),
            length=int(np.asarray(tokens).size),
            page_ids=ids,
            scratch=scratch,
        )
        while len(self._entries) > self.capacity:
            self.evict_oldest()
        return True

    def evict_oldest(self, protect: Optional[bytes] = None) -> bool:
        """Evict the least-recently-used entry (page pressure). ``protect``
        exempts one key — the entry the admission in progress is about to
        hit must not be evicted out from under it."""
        for k in self._entries:
            if k != protect:
                self._drop(k)
                return True
        return False

    def drop_page(self, pid: int) -> int:
        """Invalidate every entry mapping ``pid`` (integrity fault on a
        shared page); future admissions re-prefill. Returns #dropped."""
        doomed = [k for k, e in self._entries.items() if pid in e.page_ids]
        for k in doomed:
            self._drop(k)
        return len(doomed)

    def clear(self) -> None:
        for k in list(self._entries):
            self._drop(k)

    def _drop(self, k: bytes) -> None:
        entry = self._entries.pop(k)
        for pid in entry.page_ids:
            self.allocator.release(pid)
        self.evictions += 1


# ---------------------------------------------------------------------------
# Device-side cache tree + jitted helpers
# ---------------------------------------------------------------------------

_POOL_KEYS = frozenset({"k_q", "k_scale", "v_q", "v_scale"})


def _check_kinds(cfg: ModelConfig) -> None:
    bad = [k for k in cfg.layer_kinds() if k not in ("dense", "moe")]
    if bad:
        raise ValueError(
            f"paged KV requires full-attention layers only, got kinds {sorted(set(bad))}: "
            "windowed ring buffers and SSM/recurrent state stay dense"
        )


def _paged_block(cfg: ModelConfig, n_slots: int, pages_per_slot: int,
                 n_pages: int, page_size: int):
    shape = (n_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    return {
        "len": jnp.zeros((n_slots,), jnp.int32),
        "block_table": jnp.zeros((n_slots, pages_per_slot), jnp.int32),
        "k_q": jnp.zeros(shape, jnp.int8),
        "k_scale": jnp.zeros(shape[:-1], jnp.float32),
        "v_q": jnp.zeros(shape, jnp.int8),
        "v_scale": jnp.zeros(shape[:-1], jnp.float32),
    }


def paged_init_cache(
    cfg: ModelConfig, n_slots: int, max_len: int, page_size: int, n_pages: int
):
    """Paged decode cache: same layers/periods/tail scaffold as
    ``models.cache.init_cache`` so the transformer layer walk (scanned or
    unrolled) is unchanged, but every attention block holds page pools +
    a block table instead of dense per-slot extents.

    ``max_len % page_size == 0`` is required: the gathered per-slot view
    is then exactly ``max_len`` positions, which keeps the decode
    reduction grid — and therefore every emitted token — bit-identical
    to the dense engine's.
    """
    _check_kinds(cfg)
    if max_len % page_size:
        raise ValueError(f"max_len={max_len} not divisible by page_size={page_size}")
    pages_per_slot = max_len // page_size
    if n_pages < pages_per_slot + 1:
        raise ValueError(
            f"n_pages={n_pages} cannot hold even one slot "
            f"({pages_per_slot} pages) plus the null page"
        )

    def block():
        return _paged_block(cfg, n_slots, pages_per_slot, n_pages, page_size)

    kinds = cfg.layer_kinds()
    step = jnp.zeros((n_slots,), jnp.int32)
    if not cfg.scan_layers:
        return {"step": step, "layers": [block() for _ in kinds]}
    period = cfg.period if cfg.period else (kinds[0],)
    plen = len(period)
    n_full = cfg.n_layers // plen
    tail_kinds = kinds[n_full * plen:]

    def one_period():
        return {f"b{j}_{kind}": block() for j, kind in enumerate(period)}

    periods = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[one_period() for _ in range(n_full)]
    ) if n_full > 0 else {}
    tail = [block() for _ in tail_kinds]
    return {"step": step, "periods": periods, "tail": tail}


def _map_blocks(fn, step, cache, *others):
    """Rebuild the cache scaffold applying ``fn(block, *other_blocks,
    stacked=...)`` to each attention block group."""
    out = {"step": step}
    if "layers" in cache:
        out["layers"] = [
            fn(blk, *(o["layers"][i] for o in others), stacked=False)
            for i, blk in enumerate(cache["layers"])
        ]
        return out
    out["periods"] = {
        name: fn(blk, *(o["periods"][name] for o in others), stacked=True)
        for name, blk in cache["periods"].items()
    }
    out["tail"] = [
        fn(blk, *(o["tail"][i] for o in others), stacked=False)
        for i, blk in enumerate(cache["tail"])
    ]
    return out


def paged_commit(cache, scratch, slot, page_ids, write_mask, length):
    """Quantize a finished raw prefill scratch and scatter it into pages.

    ``scratch``: batch-1 raw cache (``init_cache(cfg, 1, max_len,
    kv_quant=False)``) holding ``length`` prefilled positions.
    ``page_ids``/``write_mask``: ``(pages_per_slot,)`` block-table row
    and owned-page mask from :meth:`SlotPager.assign` — masked (shared /
    unused) chunks scatter to the null page 0, so read-only prefix pages
    are never touched. Quantization is per-(position, head)
    (:func:`repro.models.cache.quantize_kv`), so committing chunk-wise
    prefilled state yields byte-identical pages to a monolithic prefill.
    """
    page_ids = jnp.asarray(page_ids, jnp.int32)
    write_mask = jnp.asarray(write_mask, jnp.bool_)
    tgt = jnp.where(write_mask, page_ids, 0)

    def commit_block(pblk, sblk, *, stacked):
        ps = pblk["k_q"].shape[stacked + 1]

        def chunks(x, scales):
            # drop the batch-1 axis, split positions into (pages, page_size)
            x = x[:, 0] if stacked else x[0]
            if scales:
                *l, s_, h = x.shape
                return x.reshape(*l, s_ // ps, ps, h)
            *l, s_, h, d = x.shape
            return x.reshape(*l, s_ // ps, ps, h, d)

        kq, ks = quantize_kv(sblk["k"])
        vq, vs = quantize_kv(sblk["v"])
        out = dict(pblk)
        for key, val, sc in (
            ("k_q", kq, False), ("k_scale", ks, True),
            ("v_q", vq, False), ("v_scale", vs, True),
        ):
            ch = chunks(val, sc).astype(pblk[key].dtype)
            pool = pblk[key]
            out[key] = pool.at[:, tgt].set(ch) if stacked else pool.at[tgt].set(ch)
        if stacked:
            out["len"] = pblk["len"].at[:, slot].set(length)
            out["block_table"] = pblk["block_table"].at[:, slot].set(page_ids)
        else:
            out["len"] = pblk["len"].at[slot].set(length)
            out["block_table"] = pblk["block_table"].at[slot].set(page_ids)
        return out

    with jax.named_scope("paged_commit"):
        step = cache["step"].at[slot].set(length)
        return _map_blocks(commit_block, step, cache, scratch)


def clear_slot(cache, slot):
    """Neutralize a released slot's device state: zero its length and
    point its whole block table at the null page, so the free lane's
    garbage decode writes land on page 0 instead of pages that may since
    have been reallocated to another tenant."""

    def clear_block(blk, *, stacked):
        out = dict(blk)
        if stacked:
            out["len"] = blk["len"].at[:, slot].set(0)
            out["block_table"] = blk["block_table"].at[:, slot].set(0)
        else:
            out["len"] = blk["len"].at[slot].set(0)
            out["block_table"] = blk["block_table"].at[slot].set(0)
        return out

    return _map_blocks(clear_block, cache["step"].at[slot].set(0), cache)


def select_paged(cache_a, cache_b, take_slots, take_pages):
    """Mixed-tier merge for paged caches (DESIGN.md §10/§12): slot
    metadata (len/step/block table) selects per slot like
    ``select_slots``, pool leaves select per **physical page** —
    ``take_pages`` marks the pages owned by slots whose post-step state
    should come from ``cache_b``. Decode writes touch only the writer
    slot's current owned page (shared prefix pages are read-only), so a
    page-granular select is exact; page 0 takes either side's garbage."""
    take_slots = jnp.asarray(take_slots, jnp.bool_)
    take_pages = jnp.asarray(take_pages, jnp.bool_)

    def sel(a, b, axis, mask):
        m = mask.reshape(tuple(a.shape[i] if i == axis else 1 for i in range(a.ndim)))
        return jnp.where(m, b, a)

    def sel_block(ablk, bblk, *, stacked):
        off = 1 if stacked else 0
        return {
            key: sel(ablk[key], bblk[key], off,
                     take_pages if key in _POOL_KEYS else take_slots)
            for key in ablk
        }

    step = sel(cache_a["step"], cache_b["step"], 0, take_slots)
    return _map_blocks(sel_block, step, cache_a, cache_b)


def paged_checksums(cache):
    """Integrity fold of a paged cache: ``(page_sums, slot_sums)``.

    ``page_sums``: ``(n_pages,)`` uint32, every byte of every pool leaf
    folded to the physical-page axis — one flipped bit in page ``p``'s
    values or scales moves ``page_sums[p]`` and only it (single-flip
    sound, like ``cache_slot_checksums``). ``slot_sums``: ``(n_slots,)``
    uint32 over len/step/block-table metadata. The engine maps a dirty
    page back to the slots and registry entries holding it; page 0 is
    excluded from attribution on host (free lanes legitimately scatter
    garbage there every step).
    """

    def fold(leaf, axis):
        b = jax.lax.bitcast_convert_type(leaf, jnp.uint8).astype(jnp.uint32)
        return jnp.sum(b, axis=tuple(i for i in range(b.ndim) if i != axis))

    page_total = None
    slot_total = fold(cache["step"], 0)
    blocks = []
    if "layers" in cache:
        blocks = [(blk, False) for blk in cache["layers"]]
    else:
        blocks = [(blk, True) for blk in cache["periods"].values()]
        blocks += [(blk, False) for blk in cache["tail"]]
    for blk, stacked in blocks:
        off = 1 if stacked else 0
        for key, leaf in blk.items():
            if key in _POOL_KEYS:
                f = fold(leaf, off)
                page_total = f if page_total is None else page_total + f
            else:
                slot_total = slot_total + fold(leaf, off)
    return page_total, slot_total


def quantize_scratch(scratch):
    """Quantize a raw (bf16) prefill cache into the int8 ``kv_quant``
    layout; non-attention blocks (SSM/recurrent) pass through untouched.

    Per-(position, head) quantization makes the result independent of
    the chunk schedule that filled the scratch — and having **every**
    prefill (monolithic and chunked, dense and paged) run raw and
    quantize here, after the fact, means one compiled prefill program
    serves them all, which is what the engine's token-bit-parity
    contract rests on (DESIGN.md §12)."""

    def quant_block(blk, *, stacked):
        del stacked
        if "k" not in blk:
            return blk
        kq, ks = quantize_kv(blk["k"])
        vq, vs = quantize_kv(blk["v"])
        return {"len": blk["len"], "k_q": kq, "k_scale": ks, "v_q": vq, "v_scale": vs}

    return _map_blocks(quant_block, scratch["step"], scratch)


def page_nbytes(cache) -> int:
    """Bytes one logical page occupies across every layer's pools (int8
    values + f32 scales, K and V). ``peak_used_pages * page_nbytes`` is
    the resident-KV metric the ``paged_serving`` bench gates."""
    total = 0
    if "layers" in cache:
        blocks = [(blk, False) for blk in cache["layers"]]
    else:
        blocks = [(blk, True) for blk in cache["periods"].values()]
        blocks += [(blk, False) for blk in cache["tail"]]
    for blk, stacked in blocks:
        stack = blk["k_q"].shape[0] if stacked else 1
        n_pages = blk["k_q"].shape[1 if stacked else 0]
        for key in _POOL_KEYS:
            leaf = blk[key]
            total += stack * (leaf.size // (n_pages * stack)) * leaf.dtype.itemsize
    return total
