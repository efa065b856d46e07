"""Paged KV-cache management for continuous-batching decode.

Reference capability: the paged/block KV cache behind
`paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu`
(block tables, per-sequence lengths, block reuse across requests). Host
side this is pure bookkeeping — :class:`PageAllocator` keeps a free list
of page ids and a block table per live sequence — while the device side
is two functional updates: scatter new K/V into the page pool
(`.at[...]` — XLA lowers to dynamic-update-slice / scatter on TPU), and
the Pallas `paged_attention` kernel reading through the table.

A transformer with L layers shares ONE allocator (the page structure is
identical per layer) across L per-layer pools — see
`paddle_tpu/inference/serving.py`. :class:`PagedKVCache` bundles an
allocator with a single pool for the one-layer case.
"""

from __future__ import annotations

import math
import threading
import warnings

import jax.numpy as jnp
import numpy as np

from ..observability import metrics as _om
from ..ops.paged_attention import paged_attention, paged_attention_xla

__all__ = ["PageAllocator", "PagedKVCache", "quantize_kv_int8"]


def quantize_kv_int8(x):
    """Symmetric per-head int8 quantization of K/V tokens over the
    last (head_dim) axis.

    ``x`` is ``[..., D]`` float K/V; returns ``(q, scale)`` where ``q``
    is int8 with the same shape and ``scale`` is ``x.shape[:-1]`` f32 —
    one scale per head per token slot, so every page slot's
    ``(int8, scale)`` pair is written exactly once by its own token
    write and later writes to OTHER slots of the page can never skew
    it. Dequantization is ``q.astype(f32) * scale[..., None]`` — done
    inside the paged kernels' kv loop, so pages live in HBM at half
    (bf16) / a quarter (f32) of their float bytes.

    Pure jnp — safe under jit/trace (the serving mixed program calls
    it per page write).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    # multiply by the f32 reciprocal instead of dividing by 127: XLA
    # strength-reduces constant divides to reciprocal multiplies under
    # jit, so an eager divide and a compiled one differ by 1 ulp —
    # writing the multiply keeps the scale bitwise identical across
    # eager, jit and the fused kernel's in-Pallas quantizer
    scale = jnp.maximum(amax, 1e-8) * jnp.float32(1.0 / 127.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127) \
        .astype(jnp.int8)
    return q, scale


class PageAllocator:
    """Free-list page allocator + per-sequence block tables.

    Pages are **refcounted** so a page can be shared by several owners:
    a live sequence whose prompt prefix was already prefilled can
    reference the cached prefix pages (see
    :mod:`paddle_tpu.inference.prefix_cache`) instead of re-prefilling
    them, and a prefix cache can keep pages alive after the sequence
    that wrote them retired. A page returns to the free list only when
    its last reference drops. Writing into a shared page goes through
    :meth:`ensure_writable` — copy-on-write: the writer gets a private
    copy and the shared original stays immutable for its other owners.

    With ``slots`` every admitted sequence also holds one of that many
    sequence SLOTS until it is released: the index of what a model
    keeps a sequence whatever its length (a recurrent state, a ring of
    window pages). :meth:`admit` raises ``MemoryError`` when none is
    free; ``import_table`` (a sequence resumed from a host tier) takes
    none, since a slot's contents are not pages.
    """

    def __init__(self, num_pages, page_size, max_pages_per_seq=None,
                 slots=0):
        self.num_pages = num_pages
        self._free_slots = list(range(int(slots) - 1, -1, -1))
        self._slots: dict[int, int] = {}    # seq_id -> slot
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq or num_pages
        self._free = list(range(num_pages - 1, -1, -1))
        self._free_set = set(self._free)
        self._refs: dict[int, int] = {}     # page -> refcount (allocated)
        self._tables: dict[int, list[int]] = {}
        self._lens: dict[int, int] = {}
        # copy-on-write accounting: ensure_writable() copies are counted
        # so the page-aligned prefix-cache design (which should never
        # trigger one in the natural flow) stays observable
        self.cow_count = 0
        self._m_cow = _om.counter(
            "kv_page_cow_total",
            "copy-on-write page copies triggered by a write into a "
            "shared page")
        # double-free accounting: release() is idempotent (cancellation
        # racing a natural completion must not corrupt the free list),
        # but every ignored release is counted — a growing count means
        # a caller's lifecycle bookkeeping is wrong
        self.double_free_count = 0
        self._m_double_free = _om.counter(
            "kv_page_double_free_total",
            "release() calls ignored because the sequence or page was "
            "already free")
        # free-list mutations are check-then-pop; the serving engine's
        # admission backoff explicitly supports a second thread driving
        # step()/burst, so allocate/free must be atomic or a race leaks
        # popped pages (and escapes the MemoryError contract)
        self._lock = threading.Lock()

    @property
    def free_pages(self):
        return len(self._free)

    def live_sequences(self):
        return sorted(self._tables)

    def slot_of(self, seq_id):
        """The sequence slot ``seq_id`` holds (allocators with slots)."""
        return self._slots[seq_id]

    @property
    def slots_held(self):
        return len(self._slots)

    def admit(self, seq_id, n_tokens, shared_pages=None):
        """Reserve pages for a new sequence of ``n_tokens`` (prefill).

        ``shared_pages`` (optional) is a list of already-allocated pages
        holding the sequence's prefix K/V — typically a prefix-cache
        match. They become the leading entries of the block table with
        their refcount bumped (shared, not owned), and only the
        remaining ``need - len(shared_pages)`` pages are drawn from the
        free list."""
        shared = list(shared_pages or ())
        with self._lock:
            if seq_id in self._tables:
                raise ValueError(f"sequence {seq_id} already admitted")
            need = max(1, math.ceil(n_tokens / self.page_size))
            if need > self.max_pages_per_seq:
                raise ValueError(
                    f"{n_tokens} tokens needs {need} pages > "
                    f"max_pages_per_seq ({self.max_pages_per_seq})")
            if len(shared) > need:
                raise ValueError(
                    f"{len(shared)} shared prefix pages exceed the "
                    f"{need} pages {n_tokens} tokens need")
            for p in shared:
                if p in self._free_set or p not in self._refs:
                    raise ValueError(
                        f"shared page {p} is not allocated; a prefix "
                        f"match must hold a live reference")
            if need - len(shared) > len(self._free):
                raise MemoryError(
                    f"paged cache exhausted: need {need - len(shared)} "
                    f"pages, {len(self._free)} free")
            if self._free_slots:
                self._slots[seq_id] = self._free_slots.pop()
            elif self._slots:
                raise MemoryError("every sequence slot is held")
            for p in shared:
                self._refs[p] += 1
            self._tables[seq_id] = shared + [
                self._pop_free() for _ in range(need - len(shared))]
            self._lens[seq_id] = n_tokens
            return list(self._tables[seq_id])

    def _pop_free(self):
        # caller holds self._lock
        p = self._free.pop()
        self._free_set.discard(p)
        self._refs[p] = 1
        return p

    def extend(self, seq_id, n_tokens=1):
        """Grow a sequence by ``n_tokens`` (decode), allocating pages as
        page boundaries are crossed. Returns the previous length (the
        write offset of the first new token)."""
        with self._lock:
            table, ln = self._tables[seq_id], self._lens[seq_id]
            new_len = ln + n_tokens
            need = max(1, math.ceil(new_len / self.page_size))
            if need > self.max_pages_per_seq:
                raise ValueError(
                    f"sequence {seq_id} exceeds max_pages_per_seq")
            while len(table) < need:
                if not self._free:
                    raise MemoryError("paged cache exhausted on extend")
                table.append(self._pop_free())
            self._lens[seq_id] = new_len
            return ln

    def rollback(self, seq_id, n_tokens):
        """Shrink a live sequence by its LAST ``n_tokens`` — the
        speculative-decoding rejection path: draft tokens were
        tentatively written past the committed length, verification
        rejected a suffix of them, and the pages that existed only for
        that suffix must return to the pool before the next step.

        The length cursor moves back and table-tail pages wholly past
        the new length drop one reference (``decref`` semantics: a
        page another owner still holds — impossible for natural draft
        tails, but the contract stays refcount-correct — survives for
        them). Rejected K/V left in a *kept* page is invisible: reads
        mask by the rolled-back ``kv_len``, and the next extend()
        overwrites those slots. Returns pages freed to the pool."""
        n_tokens = int(n_tokens)
        if n_tokens <= 0:
            return 0
        with self._lock:
            ln = self._lens[seq_id]
            if n_tokens > ln:
                raise ValueError(
                    f"cannot roll back {n_tokens} tokens of sequence "
                    f"{seq_id} (length {ln})")
            table = self._tables[seq_id]
            new_len = ln - n_tokens
            need = max(1, math.ceil(new_len / self.page_size))
            freed = 0
            while len(table) > need:
                p = table.pop()
                if p in self._free_set or p not in self._refs:
                    self.double_free_count += 1
                    self._m_double_free.inc()
                    warnings.warn(
                        f"rollback of sequence {seq_id} found page {p} "
                        f"already free; skipping", RuntimeWarning,
                        stacklevel=2)
                    continue
                if self._decref_locked(p):
                    freed += 1
            self._lens[seq_id] = new_len
            return freed

    def release(self, seq_id):
        """Drop a finished sequence's references; pages whose LAST
        reference this was return to the free list (shared prefix pages
        a cache or another sequence still holds stay allocated).

        Idempotent: releasing an unknown / already-released sequence —
        or a table entry that somehow already sits in the free list —
        is a no-op counted by ``double_free_count`` (and the
        ``kv_page_double_free_total`` metric) with a
        :class:`RuntimeWarning`, so a cancellation racing a natural
        completion can never corrupt the free list by double-inserting
        page ids."""
        with self._lock:
            table = self._tables.pop(seq_id, None)
            if table is None:
                self.double_free_count += 1
                self._m_double_free.inc()
                warnings.warn(
                    f"release of unknown or already-released sequence "
                    f"{seq_id} ignored", RuntimeWarning, stacklevel=2)
                return
            self._lens.pop(seq_id, None)
            slot = self._slots.pop(seq_id, None)
            if slot is not None:
                self._free_slots.append(slot)
            for p in table:
                if p in self._free_set or p not in self._refs:
                    self.double_free_count += 1
                    self._m_double_free.inc()
                    warnings.warn(
                        f"page {p} of sequence {seq_id} already free; "
                        f"skipping double insert", RuntimeWarning,
                        stacklevel=2)
                    continue
                self._decref_locked(p)

    def _decref_locked(self, p):
        # caller holds self._lock and proved p is allocated
        self._refs[p] -= 1
        if self._refs[p] <= 0:
            del self._refs[p]
            self._free.append(p)
            self._free_set.add(p)
            return True
        return False

    def incref(self, page):
        """Take an extra reference on an allocated page (a prefix cache
        pinning a freshly prefilled page)."""
        with self._lock:
            if page in self._free_set or page not in self._refs:
                raise ValueError(f"cannot incref free page {page}")
            self._refs[page] += 1

    def decref(self, page):
        """Drop one reference; frees the page at zero. Returns True if
        the page went back to the free list. Decref of an already-free
        page is the same counted no-op as a double release."""
        with self._lock:
            if page in self._free_set or page not in self._refs:
                self.double_free_count += 1
                self._m_double_free.inc()
                warnings.warn(
                    f"decref of free page {page} ignored",
                    RuntimeWarning, stacklevel=2)
                return False
            return self._decref_locked(p=page)

    def page_ref(self, page):
        """Current refcount of a page (0 = free)."""
        with self._lock:
            return self._refs.get(page, 0)

    def export_table(self, seq_id):
        """Host-tier export snapshot: ``(pages, n_tokens)`` of a live
        sequence, copied under the allocator lock. The snapshot is only
        as stable as the caller's own serialization — the serving
        engine exports while holding its engine lock, so no extend /
        release can race the D2H copy that follows. Raises
        :class:`KeyError` for unknown sequences."""
        with self._lock:
            if seq_id not in self._tables:
                raise KeyError(seq_id)
            return list(self._tables[seq_id]), self._lens[seq_id]

    def import_table(self, seq_id, n_tokens):
        """Admit a RESUMED sequence against freshly drawn, exclusively
        owned pages — never prefix-shared ones: the H2D restore scatter
        overwrites every slot of every page, and a shared page must
        stay immutable for its other owners (the restore path does not
        go through :meth:`ensure_writable`). Same refcount/double-free
        contract as :meth:`admit`: each page starts at refcount 1 and
        :meth:`release` is the idempotent inverse."""
        return self.admit(seq_id, n_tokens)

    def take_pages(self, n):
        """Draw ``n`` standalone pages, refcount 1 each, owned by the
        caller (the host-tier prefix-promotion path; hand them to a
        prefix cache or give them back with :meth:`decref`). Raises
        :class:`MemoryError` when the free list is short — atomically:
        either all ``n`` pages are drawn or none are."""
        with self._lock:
            if n > len(self._free):
                raise MemoryError(
                    f"paged cache exhausted: need {n} standalone "
                    f"pages, {len(self._free)} free")
            return [self._pop_free() for _ in range(n)]

    def ensure_writable(self, seq_id, pos):
        """Copy-on-write guard for a K/V write at token position
        ``pos``: if the page holding ``pos`` is shared (refcount > 1),
        allocate a private replacement, swap it into this sequence's
        block table and drop one reference on the original. Returns
        ``(old_page, new_page)`` when a copy is needed — the caller
        must copy the page's device content old -> new before writing —
        or ``None`` when the page is already exclusively owned.

        With page-aligned prefix caching this never fires in the
        natural flow (a sequence's own writes always land past its
        shared prefix, in pages it owns), but the contract keeps a
        shared page immutable no matter what the caller does."""
        with self._lock:
            table = self._tables[seq_id]
            idx = pos // self.page_size
            p = table[idx]
            if self._refs.get(p, 0) <= 1:
                return None
            if not self._free:
                raise MemoryError(
                    "paged cache exhausted on copy-on-write")
            new = self._pop_free()
            table[idx] = new
            self._refs[p] -= 1
            self.cow_count += 1
            self._m_cow.inc()
            return (p, new)

    def context_len(self, seq_id):
        return self._lens[seq_id]

    def page_positions(self, seq_id, start, count):
        """(page_ids, offsets) numpy arrays for token positions
        ``start .. start+count`` of a sequence — the scatter target for a
        K/V write."""
        table = self._tables[seq_id]
        pos = np.arange(start, start + count)
        page_ids = np.asarray([table[p] for p in pos // self.page_size])
        return page_ids, pos % self.page_size

    def batch_views(self, seq_ids, width=None, fill_page=0):
        """(block_tables [B, width], context_lens [B]) for a batch — the
        kernel inputs. Unused tail entries point at ``fill_page``."""
        width = width or max(len(self._tables[s]) for s in seq_ids)
        tables = np.full((len(seq_ids), width), fill_page, np.int32)
        lens = np.zeros((len(seq_ids),), np.int32)
        for i, s in enumerate(seq_ids):
            t = self._tables[s]
            tables[i, :len(t)] = t
            lens[i] = self._lens[s]
        return jnp.asarray(tables), jnp.asarray(lens)


class PagedKVCache(PageAllocator):
    """One layer's K/V pool bundled with its own allocator."""

    def __init__(self, num_pages, page_size, num_kv_heads, head_dim,
                 dtype=jnp.bfloat16, max_pages_per_seq=None):
        super().__init__(num_pages, page_size, max_pages_per_seq)
        # head-major [P, Hk, page, D]: the layout the Pallas kernel tiles
        shape = (num_pages, num_kv_heads, page_size, head_dim)
        self.k_pages = jnp.zeros(shape, dtype)
        self.v_pages = jnp.zeros(shape, dtype)

    def write(self, seq_id, k, v, start=None):
        """Scatter ``[S, Hk, D]`` new K/V at position ``start`` (default:
        end of already-written context minus the new tokens — i.e. the
        tokens just accounted by admit/extend)."""
        k = jnp.asarray(getattr(k, "_data", k), self.k_pages.dtype)
        v = jnp.asarray(getattr(v, "_data", v), self.v_pages.dtype)
        s = k.shape[0]
        if start is None:
            start = self._lens[seq_id] - s
        page_ids, offs = self.page_positions(seq_id, start, s)
        # k is [S, Hk, D]; target (page_ids[s], h, offs[s], :) — the
        # [S,1]/[1,Hk] index arrays broadcast to [S, Hk] scatter sites
        hidx = np.arange(self.k_pages.shape[1])[None, :]
        self.k_pages = self.k_pages.at[
            page_ids[:, None], hidx, offs[:, None]].set(k)
        self.v_pages = self.v_pages.at[
            page_ids[:, None], hidx, offs[:, None]].set(v)

    def attend(self, seq_ids, q, scale=None, use_pallas=True):
        """Decode-step attention for ``q [B, H, D]`` over the batch's
        pages; rows of ``q`` correspond to ``seq_ids``."""
        tables, lens = self.batch_views(seq_ids)
        fn = paged_attention if use_pallas else paged_attention_xla
        return fn(q, self.k_pages, self.v_pages, tables, lens, scale=scale)
