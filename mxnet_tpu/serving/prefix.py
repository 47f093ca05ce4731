"""Prefix cache: radix trie over page-aligned token blocks + refcounted
page adoption (vLLM's shared-page observation, SGLang's RadixAttention
trie, adapted to an encoder-decoder engine).

Why roots are EXACT prompts here. In a decoder-only engine any shared
token prefix shares KV. This engine is encoder-decoder: the prompt runs
through a BIDIRECTIONAL encoder, so a shared *prompt prefix* does NOT
determine the cross-attention memory (later prompt tokens change every
position's encoding) — source-side prefix reuse would be unsound. What
IS causally invariant is the decode side: the target sequence
([BOS] + re-sent history + emitted tokens) attends causally, so its KV
pages are determined by (exact prompt, target tokens so far). The trie
therefore maps an **exact prompt** to a root and, under each root, a
radix tree of page-aligned **target-token blocks** mapping to physical
page ids in the ``PagePool`` (multi-turn requests that re-send their
history adopt those pages instead of re-prefilling them).

A root holds a **row number** and no array: the row of the batcher's
device-resident store that keeps the prompt's cross-attention frames and
their valid length (a root hit skips the encoder entirely — the dominant
prefill cost). The batcher fills the row from the retiring slot's cross
buffers and copies it into an adopter's slot, device to device, one
compiled program either way (``ContinuousBatcher._store_rows`` /
``_apply_prefix_hits``); no frame reaches the host. The trie hands rows
out (``add_root``) and takes them back when a root goes (LRU,
``flush``); what a row holds is the batcher's business. The store's
bytes, fixed at build: ``MXTPU_PREFIX_MAX_ROOTS x mem_len x heads x head
size x 2 (K, V) x layers x bytes of the cache dtype`` (64 x 128 x 16 x
64 x 2 x 6 x 2 = 201 MB for Transformer-big in bf16, widest bucket 128).

Sharing protocol (see ``PagePool``): every cached page carries one cache
reference; adopters map it read-only via ``adopt_ref``. Pages are
append-only logs, and adopted FULL blocks sit entirely below the
adopter's first write position, so they are never written. A partially
matched block is never adopted in place — the batcher copy-on-writes it
into a fresh page (one admission-group-batched device scatter,
``ContinuousBatcher._apply_prefix_hits``) and the adopter appends
there. Page
content beyond the matched length is garbage that the causal mask
(q_offset) provably never reads.

Eviction: nodes are LRU-stamped on every match/insert touch.
``evict(need)`` releases least-recently-used leaf pages whose only
remaining reference is the cache's (releasing those actually frees
memory); the batcher calls it under the admission free-page watermark
and before resorting to preemption. ``MXTPU_PREFIX_MAX_PAGES`` caps the
trie's page footprint and ``MXTPU_PREFIX_MAX_ROOTS`` its root count,
which is the store's row count (whole LRU roots evict when over, and
their rows are handed out again).

All public methods take the cache lock and do pure bookkeeping — no
device dispatch, no blocking call ever runs under it (lock-order pass).

Env knobs: ``MXTPU_PREFIX_CACHE`` (default on), ``MXTPU_PREFIX_MAX_PAGES``
(0 = unbounded), ``MXTPU_PREFIX_MAX_ROOTS``, ``MXTPU_PREFIX_AFFINITY``
(router prefix-affinity placement), ``MXTPU_PREFIX_DIGEST_MAX`` (digest
entries a health response carries).
"""

from __future__ import annotations

import os
import threading
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..base import MXNetError

__all__ = ["PrefixCache", "PrefixHit", "prompt_digest",
           "prefix_cache_enabled", "prefix_max_pages", "prefix_max_roots",
           "prefix_affinity_enabled", "prefix_digest_max"]

_FALSY = ("0", "false", "off", "no")


def prefix_cache_enabled(default: bool = True) -> bool:
    """``MXTPU_PREFIX_CACHE``: prefix caching on/off (default on)."""
    v = os.environ.get("MXTPU_PREFIX_CACHE", "").strip().lower()
    if not v:
        return default
    return v not in _FALSY


def prefix_max_pages(default: int = 0) -> int:
    """``MXTPU_PREFIX_MAX_PAGES``: cap on pages the trie may hold
    references to (0 = unbounded; the free-page watermark still evicts
    under memory pressure either way)."""
    v = os.environ.get("MXTPU_PREFIX_MAX_PAGES", "").strip()
    try:
        return max(int(v), 0) if v else default
    except ValueError:
        return default


def prefix_max_roots(default: int = 64) -> int:
    """``MXTPU_PREFIX_MAX_ROOTS``: distinct prompts the trie keeps a
    root for, each pinning one row of the batcher's device store of
    cross-attention frames; LRU roots evict whole over the cap."""
    v = os.environ.get("MXTPU_PREFIX_MAX_ROOTS", "").strip()
    try:
        return max(int(v), 1) if v else default
    except ValueError:
        return default


def prefix_affinity_enabled(default: bool = True) -> bool:
    """``MXTPU_PREFIX_AFFINITY``: router prefers replicas whose health
    digest already holds the request's prompt (default on)."""
    v = os.environ.get("MXTPU_PREFIX_AFFINITY", "").strip().lower()
    if not v:
        return default
    return v not in _FALSY


def prefix_digest_max(default: int = 32) -> int:
    """``MXTPU_PREFIX_DIGEST_MAX``: max root digests a health response
    advertises (most recently used first)."""
    v = os.environ.get("MXTPU_PREFIX_DIGEST_MAX", "").strip()
    try:
        return max(int(v), 1) if v else default
    except ValueError:
        return default


def prompt_digest(prompt_ids) -> int:
    """Stable cross-process digest of a prompt (crc32 over the int32
    token bytes — Python ``hash()`` is salted per process and useless
    on the wire)."""
    return zlib.crc32(np.asarray(prompt_ids, np.int32).tobytes()) & 0xFFFFFFFF


def _tokens(ids) -> Tuple[int, ...]:
    """Token ids as the hashable tuple the trie keys on."""
    return tuple(int(t) for t in np.asarray(ids).reshape(-1))


class _Node:
    """One cached page: the target-token block it holds and its children
    (keyed by their block tuples). Only full (page_size) blocks may have
    children — a partial tail is by construction a leaf."""

    __slots__ = ("tokens", "page", "children", "touch")

    def __init__(self, tokens: Tuple[int, ...], page: int, touch: int):
        self.tokens = tokens
        self.page = int(page)
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.touch = touch


class _Root:
    """One exact prompt: the row of the batcher's device store that
    holds its cross-attention frames (the encoder output this prompt
    maps to) + the target-block radix tree."""

    __slots__ = ("key", "digest", "row", "children", "touch")

    def __init__(self, key: Tuple[int, ...], row: int, touch: int):
        self.key = key
        self.digest = prompt_digest(key)
        self.row = row
        self.children: Dict[Tuple[int, ...], _Node] = {}
        self.touch = touch


class PrefixHit:
    """Match result: how much of the target prefix is served from cache.

    ``matched`` target positions [0, matched) are covered: ``full_pages``
    (adopt read-only, in depth order) plus optionally ``cow`` =
    ``(src_page, used)`` — copy ``src_page`` and treat its first ``used``
    entries as valid. ``row`` is the root's row of the batcher's device
    store: the cross frames there replace the encoder pass entirely.
    """

    __slots__ = ("matched", "full_pages", "cow", "row")

    def __init__(self, matched, full_pages, cow, row):
        self.matched = matched
        self.full_pages = full_pages
        self.cow = cow
        self.row = row


class PrefixCache:
    """Radix-trie prefix cache over one ``PagePool``.

    The cache and the pool share a refcount ledger: every node's page
    carries one ``cache_acquire`` reference for exactly as long as the
    node exists (``PagePool.check_invariants(cache_pages=cache.pages())``
    proves exactness). All mutation happens on the batcher's scheduler
    thread or health/stat readers — every public method locks.
    """

    def __init__(self, pool, page_size: int,
                 max_pages: Optional[int] = None,
                 max_roots: Optional[int] = None,
                 enabled: Optional[bool] = None):
        self._pool = pool
        self.page_size = int(page_size)
        self.max_pages = prefix_max_pages() if max_pages is None \
            else int(max_pages)
        self.max_roots = prefix_max_roots() if max_roots is None \
            else int(max_roots)
        self.enabled = prefix_cache_enabled() if enabled is None \
            else bool(enabled)
        self._lock = threading.Lock()
        self._roots: Dict[Tuple[int, ...], _Root] = {}
        # store rows no root pins (popped lowest first)
        self._free_rows = list(range(self.max_roots - 1, -1, -1))
        self._clock = 0
        self._pages = 0  # nodes (== cached pages) currently held
        self.stats = {"hits": 0, "misses": 0, "tokens_saved": 0,
                      "inserts": 0, "evicted_pages": 0, "evicted_roots": 0,
                      "flushes": 0}

    # ------------------------------------------------------------- queries
    def __len__(self) -> int:
        with self._lock:
            return len(self._roots)

    @property
    def total_pages(self) -> int:
        with self._lock:
            return self._pages

    def pages(self) -> set:
        """Every page id the trie currently references (invariant
        checks; O(nodes))."""
        with self._lock:
            out: set = set()
            for root in self._roots.values():
                stack = list(root.children.values())
                while stack:
                    n = stack.pop()
                    out.add(n.page)
                    stack.extend(n.children.values())
            return out

    def digests(self, limit: Optional[int] = None) -> List[int]:
        """Root digests, most recently touched first — the compact
        prefix advertisement the health verb carries."""
        limit = prefix_digest_max() if limit is None else int(limit)
        with self._lock:
            roots = sorted(self._roots.values(), key=lambda r: -r.touch)
            return [r.digest for r in roots[:limit]]

    def has_root(self, prompt_ids) -> bool:
        """True when this exact prompt has a trie root."""
        key = _tokens(prompt_ids)
        with self._lock:
            return key in self._roots

    def hit_rate(self) -> float:
        with self._lock:
            n = self.stats["hits"] + self.stats["misses"]
            return self.stats["hits"] / n if n else 0.0

    def snapshot(self) -> dict:
        with self._lock:
            out = dict(self.stats)
            out["roots"] = len(self._roots)
            out["pages"] = self._pages
            n = out["hits"] + out["misses"]
            out["hit_rate"] = out["hits"] / n if n else 0.0
            return out

    # ------------------------------------------------------------ matching
    def match(self, prompt_ids, target_ids) -> Optional[PrefixHit]:
        """Longest cached cover of ``target_ids`` (the decode-side
        [BOS] + re-sent history) under the exact-prompt root. At most
        ``len(target_ids) - 1`` positions match — the final position's
        forward pass must run to produce the first-token logits. Returns
        None (and counts a miss) when the prompt has no root."""
        if not self.enabled:
            return None
        key = _tokens(prompt_ids)
        target = _tokens(target_ids)
        ps = self.page_size
        with self._lock:
            root = self._roots.get(key)
            if root is None:
                self.stats["misses"] += 1
                return None
            self._clock += 1
            root.touch = self._clock
            node: object = root
            depth = 0
            full_pages: List[int] = []
            cow = None
            while True:
                limit = len(target) - 1 - depth * ps
                if limit <= 0:
                    break
                best, best_lcp = None, 0
                for tokens, child in node.children.items():
                    want = target[depth * ps: depth * ps + len(tokens)]
                    lcp = 0
                    for a, b in zip(tokens, want):
                        if a != b:
                            break
                        lcp += 1
                    if lcp > best_lcp:
                        best, best_lcp = child, lcp
                if best is None or best_lcp == 0:
                    break
                if best_lcp == len(best.tokens) == ps and ps <= limit:
                    best.touch = self._clock
                    full_pages.append(best.page)
                    node = best
                    depth += 1
                    continue
                used = min(best_lcp, limit)
                if used > 0:
                    best.touch = self._clock
                    cow = (best.page, used)
                break
            matched = depth * ps + (cow[1] if cow else 0)
            self.stats["hits"] += 1
            # savings: the skipped encoder pass (prompt tokens) plus the
            # target positions adopted instead of re-prefilled
            self.stats["tokens_saved"] += len(key) + matched
            return PrefixHit(matched, tuple(full_pages), cow, root.row)

    # ----------------------------------------------------------- insertion
    def add_root(self, prompt_ids) -> Optional[int]:
        """Give an exact prompt that has no root one and return the
        store row it pins, for the caller to fill before the next
        ``match`` (both run on the scheduler thread). None when the
        prompt has its root already: two slots retiring one prompt take
        one row. Over the cap the least-recently-used roots go first,
        and the new root may take a row just freed."""
        if not self.enabled:
            return None
        key = _tokens(prompt_ids)
        with self._lock:
            if key in self._roots:
                return None
            self._clock += 1
            while len(self._roots) >= self.max_roots:
                lru = min(self._roots, key=lambda k: self._roots[k].touch)
                self._drop_root_locked(lru)
                self.stats["evicted_roots"] += 1
            root = _Root(key, self._free_rows.pop(), self._clock)
            self._roots[key] = root
            return root.row

    def insert(self, prompt_ids, target_ids, pages) -> int:
        """Register a slot's computed prefix under its prompt's root:
        ``target_ids`` are the cached decode-side tokens (positions
        [0, len)), ``pages`` the slot's pages in depth order. A prompt
        with no root (``add_root``) is skipped — nothing to serve a
        future encoder-skip from. Existing blocks are deduplicated; new
        ones take a cache reference on their page. Returns how many
        pages were newly cached."""
        if not self.enabled:
            return 0
        key = _tokens(prompt_ids)
        target = _tokens(target_ids)
        pages = [int(p) for p in pages]
        ps = self.page_size
        with self._lock:
            root = self._roots.get(key)
            if root is None:
                return 0
            self._clock += 1
            root.touch = self._clock
            node: object = root
            added = 0
            depth = 0
            while (depth + 1) * ps <= len(target) and depth < len(pages):
                blk = target[depth * ps:(depth + 1) * ps]
                child = node.children.get(blk) \
                    or self._extend_locked(node, blk, pages[depth])
                if child is None:
                    child = _Node(blk, pages[depth], self._clock)
                    self._pool.cache_acquire((pages[depth],))
                    node.children[blk] = child
                    self._pages += 1
                    added += 1
                child.touch = self._clock
                node = child
                depth += 1
            tail = target[depth * ps:]
            if tail and depth < len(pages):
                child = node.children.get(tail) \
                    or self._extend_locked(node, tail, pages[depth])
                if child is None:
                    self._pool.cache_acquire((pages[depth],))
                    node.children[tail] = _Node(tail, pages[depth],
                                                self._clock)
                    self._pages += 1
                    added += 1
            if added:
                self.stats["inserts"] += added
            if self.max_pages and self._pages > self.max_pages:
                self._evict_lru_locked(self._pages - self.max_pages,
                                       require_sole_ref=False)
            return added

    @staticmethod
    def _extend_locked(node, blk, page):
        """The slot that donated a partial tail kept filling that same
        page (no COW — it owned it), so a longer block over the SAME
        page supersedes the shorter node: re-key it in place rather
        than double-acquiring its page."""
        for key, child in node.children.items():
            if child.page == int(page) and len(key) < len(blk) \
                    and blk[:len(key)] == key:
                del node.children[key]
                child.tokens = blk
                node.children[blk] = child
                return child
        return None

    # ------------------------------------------------------------ eviction
    def evict(self, need_pages: int) -> int:
        """Free up to ``need_pages`` pool pages by releasing LRU leaf
        nodes whose page the cache alone still references (releasing
        those actually returns memory). Returns pages freed."""
        with self._lock:
            return self._evict_lru_locked(need_pages, require_sole_ref=True)

    def _leaves_locked(self):
        """[(touch, parent, key, node)] for every leaf node."""
        out = []
        for root in self._roots.values():
            stack = [(root, k, n) for k, n in root.children.items()]
            while stack:
                parent, key, n = stack.pop()
                if n.children:
                    stack.extend((n, k, c) for k, c in n.children.items())
                else:
                    out.append((n.touch, parent, key, n))
        return out

    def _evict_lru_locked(self, need: int, require_sole_ref: bool) -> int:
        freed = 0
        dropped = 0
        while dropped < need or (not require_sole_ref
                                 and self._pages_over_cap_locked()):
            leaves = self._leaves_locked()
            if require_sole_ref:
                leaves = [e for e in leaves
                          if self._pool.ref(e[3].page) == 1]
            if not leaves:
                break
            leaves.sort(key=lambda e: e[0])
            _, parent, key, node = leaves[0]
            del parent.children[key]
            self._pages -= 1
            freed += self._pool.cache_release((node.page,))
            self.stats["evicted_pages"] += 1
            dropped += 1
        return freed

    def _pages_over_cap_locked(self) -> bool:
        return bool(self.max_pages) and self._pages > self.max_pages

    def _drop_root_locked(self, key):
        root = self._roots.pop(key)
        self._free_rows.append(root.row)
        stack = list(root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            self._pool.cache_release((n.page,))
            self._pages -= 1

    def flush(self) -> int:
        """Drop everything (weights swapped or state poisoned): every
        cache reference is released and every store row freed; pages
        still mapped by live slots stay alive under their own
        references. Returns roots dropped."""
        with self._lock:
            n = len(self._roots)
            for key in list(self._roots):
                self._drop_root_locked(key)
            self.stats["flushes"] += 1
            return n

    def check_invariants(self):
        """Trie-side audit: the page ledger matches the tree and no node
        holds the trash page or a duplicate reference."""
        with self._lock:
            seen: set = set()
            count = 0
            for root in self._roots.values():
                stack = list(root.children.values())
                while stack:
                    n = stack.pop()
                    if n.page in seen:
                        raise MXNetError(
                            f"trie references page {n.page} twice")
                    if n.page == 0:
                        raise MXNetError("trie references the trash page")
                    if len(n.tokens) < self.page_size and n.children:
                        raise MXNetError(
                            "partial-tail trie node has children")
                    seen.add(n.page)
                    count += 1
                    stack.extend(n.children.values())
            if count != self._pages:
                raise MXNetError(
                    f"trie page ledger {self._pages} != {count} nodes")
            rows = [r.row for r in self._roots.values()] + self._free_rows
            if sorted(rows) != list(range(self.max_roots)):
                raise MXNetError(
                    f"store rows pinned and free are not 0.."
                    f"{self.max_roots - 1} once each: {sorted(rows)}")
