"""The KV cache memory manager of the serving plane.

:class:`BlockKVCache`: a fixed pool of
``[num_blocks, heads, block_size, head_dim]`` KV *blocks* per layer,
a per-request host-side block table mapping logical positions to
physical blocks (vLLM/PagedAttention-style), a ref-counted
:class:`BlockAllocator`, and a prefix cache keyed on a rolling hash
of the token prefix so a shared system prompt prefills once and its
blocks are *referenced* (copy-on-write at the boundary block) by
every subsequent request. A request pays ``ceil(need/block_size)``
blocks instead of a full ``max_len`` row, minus whatever prefix it
shares — the memory unlock for high-concurrency serving.

The physical half of :class:`BlockKVCache` — the block arrays, the
ref-counted :class:`BlockAllocator` and the prefix cache — lives in a
:class:`BlockPool` so several caches can share one pool handle:
co-located prefill and decode engine roles (``serving/disagg.py``)
splice a request's block table from one cache into another as pure
host-side bookkeeping (``export_row``/``import_row`` — an ownership
transfer, zero ref changes), while engines on distinct pools copy the
committed blocks through the destination allocator (``adopt_row``).
Either way ``BlockAllocator.leaked()`` stays exact across the handoff.

Layer kinds. A model whose layers do not all keep the same rows declares
its kinds (``serving/seam.py``): the first keeps every row of a request and
is the cache described above; each further kind has a ``window`` and needs
only the last ``window`` rows (:class:`_WindowKind`: its own pool, bounded
by ``max_slots x (window + a block)``, its own tables, blocks taken as a
request's rows reach them and returned to the free list once they lie
wholly behind the window, while the request lives). ``arrays()`` then
hands the steps one pool pair a *model* layer, in the model's order, the
tables go as one array a kind, and ``allocator`` / ``blocks_free`` /
``blocks_used`` answer for all kinds together.

More than K and V a token, or something else. The kind that keeps every
row may keep further arrays a token beside the pair
(``seam.CacheKind.extra``: a learned sparse attention's indexer keys): one
more array a layer, ``[num_blocks, width, block_size]`` with no head axis,
after (k, v) in the layer's tuple (:class:`BlockPool`). It has no table and
no allocator of its own: a block id means the same rows in all three, so
admission, release, the trash block, donation, ``set_arrays`` and
``rebuild`` carry it with the pair. A kind with NO KV heads keeps no pair at
all (a latent-attention layer's one vector a token): its layers' tuples
are the ``extra`` arrays alone, under the same table and allocator.

Recurrent state. A kind of layer that keeps a fixed-size record a request
whatever its context (``seam.StateKind``) has no blocks at all
(:class:`_StateKind`): ``[max_slots, ...]`` arrays a layer, indexed by the
request's row, handed to the steps, consumed and rebound with the pools
(``arrays()`` / ``set_arrays()`` / ``rebuild_pools()``). Its entry of the
tables is the row index of each row of a dispatch. Admission is by row: a
request that got a row has its state's room. Nothing of it can leak.

Every buffer keeps a fixed shape so the batched decode step has a
single signature and compiles exactly once; admitting or retiring a
request is bookkeeping, never a recompile.

Row lifecycle: allocate at admission -> the
bucketed prompt pass populates KV rows and sets the valid length ->
per-step in-place writes inside the compiled decode (``advance``: +1
per plain decode token, +K+1 per speculative verify) -> ``rollback``
of the rejected draft tail (the verify step writes K+1 rows
optimistically; only the accepted prefix stays committed) -> release
(EOS/max-tokens). Stale row contents need no scrubbing — the position
mask already excludes them, and the next write at the rolled-back
offset overwrites them.
"""

from __future__ import annotations

from bisect import insort
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax


class BlockAllocator:
    """Ref-counted free-list allocator over a fixed pool of KV blocks.

    Physical block ids are plain ints; the free list is kept sorted so
    allocation order is a pure function of the alloc/free history —
    the engine equivalence tests replay exact schedules and rely on
    identical block assignment across runs. A block's refcount goes
    above 1 only via the prefix cache (:meth:`ref` on a shared prefix
    block); :meth:`deref` returns it to the free list when the count
    drops to zero. lora.py's :class:`~paddle_tpu.serving.LoRAPool`
    reuses this allocator over adapter pages — same free-list
    determinism, same leak accounting.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self.refcount = np.zeros(num_blocks, np.int32)
        self._free = list(range(num_blocks))

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.num_blocks - len(self._free)

    def alloc(self) -> Optional[int]:
        """Claim the lowest free block at refcount 1, or None if empty."""
        if not self._free:
            return None
        blk = self._free.pop(0)
        self.refcount[blk] = 1
        return blk

    def ref(self, blk: int):
        """Take an additional reference on an allocated block."""
        if self.refcount[blk] < 1:
            raise ValueError(f"block {blk} is free; cannot ref")
        self.refcount[blk] += 1

    def deref(self, blk: int):
        """Drop one reference; the block is reclaimed at zero."""
        if self.refcount[blk] < 1:
            raise ValueError(f"block {blk} is free; cannot deref")
        self.refcount[blk] -= 1
        if self.refcount[blk] == 0:
            insort(self._free, blk)

    def leaked(self) -> int:
        """Blocks still referenced — for the chaos suite's leak check
        (after every request releases, only permanent refs remain)."""
        return int((self.refcount > 0).sum())


class AllocatorView:
    """The allocators of a cache with several layer kinds, read as one:
    blocks free, used and still referenced over all kinds. A pool's trash
    block is a permanent reference; the view counts ONE in all (the first
    kind's), as a single-kind cache has one, so ``leaked() - 1`` is what a
    leak check reads for either."""

    def __init__(self, allocators):
        self.allocators = list(allocators)

    @property
    def num_blocks(self) -> int:
        return sum(a.num_blocks for a in self.allocators)

    @property
    def num_free(self) -> int:
        return sum(a.num_free for a in self.allocators)

    @property
    def num_used(self) -> int:
        return sum(a.num_used for a in self.allocators)

    def leaked(self) -> int:
        return sum(a.leaked() for a in self.allocators) \
            - (len(self.allocators) - 1)


class _PrefixEntry:
    """One cached full block of a prompt prefix.

    Chained: ``key`` is the rolling hash up to and including this
    block's tokens, ``parent_block`` the physical block this entry
    pinned when published (None for a chain head). ``tokens`` is kept
    to verify against hash collisions before any reuse.
    """

    __slots__ = ("key", "parent_block", "block", "tokens")

    def __init__(self, key, parent_block: Optional[int], block: int,
                 tokens: Tuple[int, ...]):
        self.key = key
        self.parent_block = parent_block
        self.block = block
        self.tokens = tokens


def prefix_chain_keys(prompt: Sequence[int], block_size: int) -> List[int]:
    """Rolling-hash chain keys for each *full* block of ``prompt`` —
    the same ``hash((parent_key, chunk))`` chain :class:`BlockKVCache`
    publishes prefix entries under, exposed so a router can keep a
    fleet-wide prefix index (prefix-affinity routing) without touching
    any pool's internals."""
    bs = int(block_size)
    keys: List[int] = []
    key = None
    for i in range(len(prompt) // bs):
        chunk = tuple(int(t) for t in prompt[i * bs:(i + 1) * bs])
        key = hash((key, chunk))
        keys.append(key)
    return keys


class BlockPool:
    """The shareable physical half of :class:`BlockKVCache`: the
    per-layer block arrays, the ref-counted :class:`BlockAllocator`
    and the rolling-hash prefix cache, plus the pool-global counters.

    Several caches may hold one pool (co-located prefill/decode engine
    roles): each cache keeps its own row state (block tables, lengths,
    free rows) while allocation, prefix sharing and the arrays all
    land here — ``set_arrays`` through any sharing cache replaces the
    arrays every other cache reads.

    Ownership of ``layers`` is linear. A compiled paged step is handed
    them (``arrays()``), **consumes** them (they are donated: the rows
    are written in place and the handed arrays are deleted) and returns
    their successors, which ``set_arrays`` binds. So nobody keeps a
    pool array across a step: every reader (demotion, promotion,
    adoption, a co-located engine) reads ``layers`` at use, and engines
    that share one pool step one after another. ``epoch`` counts
    :meth:`rebuild` calls, so a sharing engine sees that the contents
    its rows pointed at are gone.
    """

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 block_size: int = 16, num_blocks: int = 2,
                 dtype=None, kv_dtype: str = "f32", extra=()):
        import jax.numpy as jnp
        if kv_dtype not in ("f32", "bf16", "int8"):
            raise ValueError(
                f"kv_dtype must be 'f32', 'bf16' or 'int8', got {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        if dtype is None:
            dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16,
                     "int8": jnp.int8}[kv_dtype]
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks={num_blocks} leaves no usable block after "
                f"reserving the trash block")
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        shape = (self.num_blocks, num_heads, self.block_size, head_dim)
        # what a token keeps beside K and V (``seam.CacheKind.extra``):
        # ``(name, width)`` -> one more array a layer, ``[blocks, width,
        # block_size]``, after the pair; every method here that walks a
        # layer's tuple (COW, adoption, rebuild) takes them along. A kind
        # with no KV heads has NO pair (a latent-attention layer's one
        # vector a token): its layers' tuples are these arrays alone
        self.extra = tuple((str(n), int(w)) for n, w in extra)
        self.pair = 2 if self.num_heads else 0
        if not self.pair and not self.extra:
            raise ValueError("a cache kind with no KV heads keeps at least "
                             "one array a token (CacheKind.extra)")
        if self.extra and kv_dtype == "int8":
            raise ValueError("an int8 pool keeps (k, v, scales) only: a "
                             "kind with extra arrays has a float pool")
        if kv_dtype == "int8":
            # 4-tuple layers: int8 code pools + per-block-per-head
            # absmax scales (ops.attention_ops.block_scatter_write_quant
            # is the only writer; the structural 2-vs-4 tuple width is
            # what the model forward dispatches on)
            sshape = (self.num_blocks, num_heads)
            self.layers: List[Tuple[jax.Array, ...]] = [
                (jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                 jnp.zeros(sshape, jnp.float32),
                 jnp.zeros(sshape, jnp.float32))
                for _ in range(num_layers)]
        else:
            self.layers = [
                tuple(jnp.zeros(shape, dtype) for _ in range(self.pair))
                + tuple(jnp.zeros((self.num_blocks, w, self.block_size),
                                  dtype) for _, w in self.extra)
                for _ in range(num_layers)]
        self.epoch = 0
        self.allocator = BlockAllocator(self.num_blocks)
        trash = self.allocator.alloc()
        assert trash == BlockKVCache.TRASH
        # key -> _PrefixEntry, move_to_end on touch => LRU eviction order
        self._prefix: "OrderedDict[int, _PrefixEntry]" = OrderedDict()
        self.prefix_hits = 0       # token-weighted: shared tokens reused
        self.prefix_misses = 0     # prompt tokens prefilled from scratch
        self.blocks_allocated_total = 0  # fresh allocs (bench: bytes/request)

    @property
    def extra_bytes(self) -> Dict[str, int]:
        """Bytes each extra array takes over all layers, by name, as the
        device holds them."""
        return {name: sum(int(layer[self.pair + i].nbytes)
                          for layer in self.layers)
                for i, (name, _) in enumerate(self.extra)}

    def alloc_block(self) -> Optional[int]:
        """Fresh block, evicting idle prefix-cache entries if needed."""
        blk = self.allocator.alloc()
        while blk is None and self._evict_one_prefix():
            blk = self.allocator.alloc()
        return blk

    def _drop_entry(self, ent: _PrefixEntry):
        del self._prefix[ent.key]
        self.allocator.deref(ent.block)
        if ent.parent_block is not None:
            self.allocator.deref(ent.parent_block)

    def _evict_one_prefix(self) -> bool:
        """Drop the least-recently-used cache-only prefix entry.

        Only entries whose block sits at refcount 1 (held solely by the
        cache) are evictable; entries a live request still references
        are skipped. A chain parent carries a pin from each cached
        child, so eviction proceeds leaf-first regardless of LRU order.
        """
        for key in list(self._prefix):
            ent = self._prefix[key]
            if self.allocator.refcount[ent.block] == 1:
                self._drop_entry(ent)
                return True
        return False

    def release_blocks(self, blocks: Sequence[int]):
        """Drop one reference per block — how an aborted handoff
        record (exported but never adopted) returns its ownership."""
        for blk in blocks:
            self.allocator.deref(int(blk))

    def flush_prefix_cache(self):
        """Drop every cached prefix ref (tests / memory pressure).
        Live requests keep their own refs; only cache refs drop."""
        for key in list(self._prefix):
            self._drop_entry(self._prefix[key])

    def rebuild(self):
        """Zeroed arrays in place of ones a failed step consumed (same
        shapes, dtypes and shardings: a deleted array still knows
        them). Every row's KV is gone with them, so the prefix cache is
        flushed and ``epoch`` moves; the callers shed what they had
        running."""
        import jax.numpy as jnp
        self.layers = [
            tuple(jnp.zeros(a.shape, a.dtype, device=a.sharding)
                  for a in layer) for layer in self.layers]
        self.flush_prefix_cache()
        self.epoch += 1


class _WindowKind:
    """The layers of one kind that need only a request's last ``window``
    rows: a pool of their own, ``max_slots x budget + 1`` blocks
    (``budget`` = the blocks ``window`` rows can touch + the one being
    written), a table of their own (logical entries as the unbounded
    kind's: entry ``j`` is the block of rows ``[j x bs, (j + 1) x bs)``;
    entries behind the window or not reached yet are the trash block), and
    per row the range ``[lo, hi)`` of entries it holds.

    :meth:`hold` is the one mover: told the position a row writes next, it
    returns the blocks wholly behind that position's window to the free
    list and takes blocks ahead, up to ``budget`` and to the row's
    reservation. A row never holds more than ``budget`` blocks, so the
    pool cannot run dry while a row is free: admission reserves
    ``min(budget, blocks of the whole request)`` and never fails on this
    kind."""

    def __init__(self, kind, max_slots: int, blocks_per_row: int,
                 block_size: int, kv_dtype: str):
        self.kind = kind
        self.window = int(kind.window)
        self.budget = -(-self.window // block_size) + 1
        self.pool = BlockPool(len(kind.layers), kind.kv_heads,
                              kind.head_dim, block_size=block_size,
                              num_blocks=max_slots * self.budget + 1,
                              kv_dtype=kv_dtype)
        self.tables = np.full((max_slots, blocks_per_row),
                              BlockKVCache.TRASH, np.int32)
        self.lo = np.zeros(max_slots, np.int64)
        self.hi = np.zeros(max_slots, np.int64)
        self.cap = np.zeros(max_slots, np.int64)  # blocks of the request
        self.reserved = 0           # blocks promised to admitted rows
        self.freed_behind = 0       # blocks returned while their row lived

    @property
    def usable(self) -> int:
        return self.pool.num_blocks - 1

    def admit(self, row: int, need: int, length: int) -> bool:
        """Reserve for a request of ``need`` rows and hold what a prompt
        of ``length`` rows leaves for the next position. False (nothing
        taken) if the reservation would over-commit the pool."""
        cap = -(-int(need) // self.pool.block_size)
        if self.reserved + min(cap, self.budget) > self.usable:
            return False
        self.reserved += min(cap, self.budget)
        self.cap[row] = cap         # lo = hi = 0: release() left them so
        self.hold(row, length, count=False)
        return True

    def hold(self, row: int, length: int, count: bool = True) -> bool:
        """``row`` writes position ``length`` next. -> whether its table
        changed."""
        bs = self.pool.block_size
        lo = max(int(length) - self.window + 1, 0) // bs
        hi = min(int(self.cap[row]), lo + self.budget)
        old_lo, old_hi = int(self.lo[row]), int(self.hi[row])
        if (lo, hi) == (old_lo, old_hi):
            return False
        for j in range(old_lo, min(lo, old_hi)):
            self.pool.allocator.deref(int(self.tables[row, j]))
            self.tables[row, j] = BlockKVCache.TRASH
            self.freed_behind += bool(count)
        for j in range(max(old_hi, lo), hi):
            blk = self.pool.allocator.alloc()
            if blk is None:
                raise RuntimeError(
                    f"window kind {self.kind.name!r}: no block for row "
                    f"{row} (reserved {self.reserved} of {self.usable})")
            self.tables[row, j] = blk
        self.lo[row], self.hi[row] = lo, max(hi, lo)
        return True

    def release(self, row: int):
        for j in range(int(self.lo[row]), int(self.hi[row])):
            self.pool.allocator.deref(int(self.tables[row, j]))
            self.tables[row, j] = BlockKVCache.TRASH
        self.reserved -= min(int(self.cap[row]), self.budget)
        self.lo[row] = self.hi[row] = self.cap[row] = 0

    @property
    def live_blocks(self) -> int:
        return self.pool.allocator.num_used - 1


class _StateKind:
    """The layers of one kind that keep a fixed-size record a request
    (``seam.StateKind``): per layer a tuple of ``[max_slots, *shape]``
    arrays, row ``r`` the record of the request in cache row ``r``. A
    prefill dispatch writes the rows it admitted whole (so a row needs no
    zeroing at admission), the decode step rewrites every row, and a row
    with no request holds garbage that nothing reads. ``layers`` is owned
    linearly, as a :class:`BlockPool`'s is."""

    def __init__(self, kind, max_slots: int):
        self.kind = kind
        self.max_slots = int(max_slots)
        self.rebuild()
        #: the kind's entry of the decode step's tables: row r is row r
        self.rows = np.arange(self.max_slots, dtype=np.int32)

    def rebuild(self):
        import jax.numpy as jnp
        self.layers = [
            tuple(jnp.zeros((self.max_slots,) + tuple(shape), dtype)
                  for shape, dtype in self.kind.arrays)
            for _ in self.kind.layers]

    @property
    def nbytes(self) -> int:
        import jax.numpy as jnp
        return sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                   for shape, dtype in self.kind.arrays) \
            * len(self.kind.layers) * self.max_slots

    def pick(self, rows: Sequence[int], n: int):
        """The rows of a prefill dispatch of ``n`` rows: the cache rows of
        the admitted, ``max_slots`` (out of range: the write is dropped)
        for the padding."""
        out = np.full(n, self.max_slots, np.int32)
        out[:len(rows)] = list(rows)
        return out


class BlockKVCache:
    """Block-paged KV storage + ref-counted allocator + prefix cache.

    Geometry: one ``[num_blocks, heads, block_size, head_dim]`` (k, v)
    pair per layer; a request's logical positions ``[0, max_len)`` map
    through its row of the host-side ``tables`` array (shape
    ``[max_slots, blocks_per_row]``, np.int32) to physical blocks. The
    tables ship into the compiled steps as a fixed-shape jit *input* —
    remapping blocks never recompiles.

    Physical block 0 is the **trash block**: allocated permanently at
    init, it backs every unassigned table entry and absorbs the
    compiled steps' out-of-range writes (ops.attention_ops routes
    overflow there rather than letting XLA's index clamping corrupt a
    real block). Its contents are garbage by design and the position
    mask guarantees no request ever attends to a row it didn't write
    through its own table.

    Prefix cache: full prompt blocks are published under a rolling
    hash of the token prefix (``hash((parent_key, chunk))`` per
    block). ``acquire`` walks the chain for the longest cached prefix,
    refs the matched blocks instead of re-prefilling them, and
    privatizes the boundary block (copy-on-write) when the shared
    length isn't block-aligned — the suffix prefill would otherwise
    write into a block other requests read. Entries idle at
    refcount 1 (cache-only) are evicted LRU when the pool runs dry.

    The row-level API (``lengths``, ``advance``/``rollback``,
    ``arrays``/``set_arrays``) counts *rows* in ``num_free``/
    ``num_used``; block-level accounting is exposed via
    ``blocks_free``/``blocks_used``.
    """

    TRASH = 0  # physical block 0: permanent ref, padding + overflow sink

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 max_slots: int, max_len: int, block_size: int = 16,
                 num_blocks: int = 0, prefix_cache: bool = True,
                 dtype=None, kv_dtype: str = "f32",
                 pool: Optional[BlockPool] = None,
                 window_kinds: Sequence = (), layer_order=None,
                 state_kinds: Sequence = (), extra=()):
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        if pool is not None:
            # co-located caches share one pool handle: validate the
            # geometry this cache was asked for against what the pool
            # physically is (a mismatched compiled step would silently
            # read the wrong blocks otherwise)
            if pool.num_layers != int(num_layers) or \
                    pool.num_heads != int(num_heads) or \
                    pool.head_dim != int(head_dim):
                raise ValueError(
                    f"shared pool is {pool.num_layers} layers x "
                    f"{pool.num_heads} heads x {pool.head_dim} dims; "
                    f"cache wants {num_layers}x{num_heads}x{head_dim}")
            if num_blocks > 0 and int(num_blocks) != pool.num_blocks:
                raise ValueError(
                    f"shared pool has {pool.num_blocks} blocks; cannot "
                    f"resize to {num_blocks} through a sharing cache")
            if int(block_size) != pool.block_size:
                raise ValueError(
                    f"shared pool block_size={pool.block_size} != "
                    f"requested {block_size}")
            if kv_dtype != pool.kv_dtype:
                raise ValueError(
                    f"shared pool kv_dtype={pool.kv_dtype!r} != "
                    f"requested {kv_dtype!r}")
            if tuple(extra) != pool.extra:
                raise ValueError(
                    f"shared pool keeps {pool.extra} beside K and V; "
                    f"cache wants {tuple(extra)}")
            self.pool = pool
        else:
            block_size = int(block_size)
            if block_size < 1:
                raise ValueError(
                    f"block_size must be >= 1, got {block_size}")
            if num_blocks <= 0:
                # worst case every slot is full-length, +1 trash block
                num_blocks = (self.max_slots *
                              (-(-self.max_len // block_size)) + 1)
            self.pool = BlockPool(num_layers, num_heads, head_dim,
                                  block_size=block_size,
                                  num_blocks=num_blocks, dtype=dtype,
                                  kv_dtype=kv_dtype, extra=extra)
        self.blocks_per_row = -(-self.max_len // self.pool.block_size)
        self.tables = np.full((self.max_slots, self.blocks_per_row),
                              self.TRASH, np.int32)
        self.lengths = np.zeros(self.max_slots, np.int32)
        # bumped by every write to ``tables`` (_bind_row): a holder of
        # a device copy re-sends it when this moved, and only then
        self.tables_version = 0
        self._nblocks = np.zeros(self.max_slots, np.int32)  # owned per row
        self._free_rows = list(range(self.max_slots))
        self.prefix_cache_enabled = bool(prefix_cache)
        # the bounded kinds beside the kind above; ``_order[l]`` = (kind,
        # index in the kind) of model layer ``l``, kind 0 the unbounded one
        self._windows = [
            _WindowKind(k, self.max_slots, self.blocks_per_row,
                        self.pool.block_size, self.pool.kv_dtype)
            for k in window_kinds]
        # the kinds with no blocks: a fixed-size record a row
        self._states = [_StateKind(k, self.max_slots) for k in state_kinds]
        self._order = list(layer_order) \
            if self._windows or self._states else None
        if self._windows and (prefix_cache or pool is not None):
            raise ValueError(
                "a cache with window kinds has no prefix cache and no "
                "shared pool: a layer that forgets rows cannot lend them")
        if self._states and (prefix_cache or pool is not None):
            raise ValueError(
                "a cache with recurrent state has no prefix cache and no "
                "shared pool: a shared prefix would need the state at its "
                "last row, which no block holds")

    @classmethod
    def for_model(cls, spec, max_slots: int, max_len: int, *,
                  block_size: int, num_blocks: int, prefix_cache: bool,
                  kv_dtype: str, pool: Optional[BlockPool] = None):
        """The cache of a served model (``serving/seam.py``): the first
        kind sized by ``num_blocks``, each window kind by ``max_slots``,
        its window and ``block_size``."""
        first, rest = spec.cache_kinds[0], spec.cache_kinds[1:]
        if first.window or any(not k.window for k in rest):
            raise ValueError("the first cache kind keeps every row, the "
                             "others have a window")
        if any(k.extra for k in rest):
            raise ValueError("only the kind that keeps every row keeps "
                             "arrays beside K and V")
        order = {}
        for ki, kind in enumerate(spec.cache_kinds + spec.state_kinds):
            for i, layer in enumerate(kind.layers):
                order[layer] = (ki, i)
        return cls(len(first.layers), first.kv_heads, first.head_dim,
                   max_slots, max_len, block_size=block_size,
                   num_blocks=num_blocks, prefix_cache=prefix_cache,
                   kv_dtype=kv_dtype, pool=pool, window_kinds=rest,
                   layer_order=[order[l] for l in sorted(order)],
                   state_kinds=spec.state_kinds, extra=first.extra)

    # -- pool delegation ---------------------------------------------
    # the physical state lives in self.pool so sharing caches observe
    # every array replacement and every counter bump; these
    # properties keep the long-standing cache-level API intact

    @property
    def kv_dtype(self) -> str:
        return self.pool.kv_dtype

    @property
    def block_size(self) -> int:
        return self.pool.block_size

    @property
    def num_blocks(self) -> int:
        return self.pool.num_blocks

    @property
    def layers(self):
        return self.pool.layers

    @layers.setter
    def layers(self, value):
        self.pool.layers = value

    @property
    def allocator(self):
        """The allocator; of a cache with window kinds, all of them read
        as one (:class:`AllocatorView`)."""
        if not self._windows:
            return self.pool.allocator
        return AllocatorView([self.pool.allocator]
                             + [w.pool.allocator for w in self._windows])

    @property
    def _prefix(self) -> "OrderedDict[int, _PrefixEntry]":
        return self.pool._prefix

    @property
    def prefix_hits(self) -> int:
        return self.pool.prefix_hits

    @prefix_hits.setter
    def prefix_hits(self, value: int):
        self.pool.prefix_hits = value

    @property
    def prefix_misses(self) -> int:
        return self.pool.prefix_misses

    @prefix_misses.setter
    def prefix_misses(self, value: int):
        self.pool.prefix_misses = value

    @property
    def blocks_allocated_total(self) -> int:
        return self.pool.blocks_allocated_total

    @blocks_allocated_total.setter
    def blocks_allocated_total(self, value: int):
        self.pool.blocks_allocated_total = value

    # -- geometry ----------------------------------------------------

    def blocks_needed(self, length: int) -> int:
        return -(-int(length) // self.block_size)

    def blocks_live(self) -> int:
        """Table entries the rows' committed lengths stand on:
        ``ceil(length / block_size)`` summed over the rows (a released
        row's length is 0)."""
        return int(np.sum(-(-self.lengths // self.block_size)))

    @property
    def blocks_free(self) -> int:
        return self.allocator.num_free

    @property
    def blocks_used(self) -> int:
        return self.allocator.num_used

    # row-level view
    @property
    def num_free(self) -> int:
        return len(self._free_rows)

    @property
    def num_used(self) -> int:
        return self.max_slots - len(self._free_rows)

    # -- allocation --------------------------------------------------

    def _alloc_block(self) -> Optional[int]:
        return self.pool.alloc_block()

    def _drop_entry(self, ent: _PrefixEntry):
        self.pool._drop_entry(ent)

    def _evict_one_prefix(self) -> bool:
        return self.pool._evict_one_prefix()

    def _match_prefix(self, prompt: Sequence[int]) -> List[_PrefixEntry]:
        """Longest chain of cached full blocks covering the prompt."""
        if not self.prefix_cache_enabled:
            return []
        bs = self.block_size
        matched: List[_PrefixEntry] = []
        key = None
        for i in range(len(prompt) // bs):
            chunk = tuple(prompt[i * bs:(i + 1) * bs])
            key = hash((key, chunk))
            ent = self._prefix.get(key)
            if ent is None or ent.tokens != chunk:
                break
            matched.append(ent)
        return matched

    def _bind_row(self, row: int, blocks: Sequence[int]):
        """Point ``row``'s table at ``blocks`` (none: every entry on the
        trash block). The one place ``tables`` is written, so
        ``tables_version`` moves exactly when a table did."""
        self.tables[row] = self.TRASH
        self.tables[row, :len(blocks)] = blocks
        self._nblocks[row] = len(blocks)
        self.tables_version += 1

    def acquire(self, prompt: Sequence[int],
                need: int) -> Optional[Tuple[int, int]]:
        """Admit a request: reserve a row plus blocks for ``need``
        logical positions (prompt + worst-case generation), reusing
        cached prefix blocks where possible.

        Returns ``(row, shared_tokens)`` — ``shared_tokens`` prompt
        positions already hold valid KV and the prefill may skip them
        (always < len(prompt): the last prompt token is recomputed for
        its logits) — or None when rows or blocks run out. All-or-
        nothing: on block exhaustion every ref/alloc taken is unwound
        so a shed admission leaks nothing.
        """
        if need > self.max_len:
            raise ValueError(
                f"request needs {need} positions > max_len={self.max_len}")
        if not self._free_rows:
            return None
        nblocks = self.blocks_needed(need)
        matched = self._match_prefix(prompt)
        # cap shared coverage: the final prompt token's logits seed
        # generation, so at least one position must run through prefill
        shared = min(len(matched) * self.block_size, len(prompt) - 1)
        nshared = shared // self.block_size  # fully reusable blocks
        taken: List[int] = []   # fresh allocs to unwind on failure
        reffed: List[int] = []  # prefix refs to unwind on failure
        blocks: List[int] = []
        for ent in matched[:nshared]:
            self.pool.allocator.ref(ent.block)
            self._prefix.move_to_end(ent.key)
            reffed.append(ent.block)
            blocks.append(ent.block)
        cow = shared % self.block_size != 0
        for _ in range(nblocks - nshared):
            blk = self._alloc_block()
            if blk is None:
                for b in taken:
                    self.pool.allocator.deref(b)
                for b in reffed:
                    self.pool.allocator.deref(b)
                return None
            taken.append(blk)
            blocks.append(blk)
        if taken and self.kv_dtype == "int8":
            # a reclaimed block's stale absmax scale would distort every
            # fresh row quantized into it (scales only grow); zeroing it
            # restarts the block's grid AND makes its leftover codes
            # dequantize to exact 0 — and runs before the COW copy so a
            # boundary block still inherits its source's scale below
            idx = np.asarray(taken, np.int32)
            self.layers = [
                (k, v, ks.at[idx].set(0.0), vs.at[idx].set(0.0))
                for k, v, ks, vs in self.layers]
        if cow:
            # boundary block is partially shared: copy the cached
            # block's rows into the freshly allocated private block so
            # the suffix prefill can write the remainder in place
            # (generic over the layer tuple: int8 layers also carry the
            # scale arrays, which copy the same way)
            src = matched[nshared].block
            dst = blocks[nshared]
            self.layers = [
                tuple(a.at[dst].set(a[src]) for a in layer)
                for layer in self.layers]
        row = self._free_rows[0]
        admitted = []
        for w in self._windows:
            if not w.admit(row, need, len(prompt)):
                for a in admitted:
                    a.release(row)
                for b in taken:
                    self.pool.allocator.deref(b)
                return None
            admitted.append(w)
        self._free_rows.pop(0)
        # counted here, not in _alloc_block: a failed acquire unwinds
        # its allocs, and those must not inflate the bytes/request bench
        self.blocks_allocated_total += len(taken)
        self._bind_row(row, blocks)
        self.lengths[row] = 0
        if shared:
            self.prefix_hits += shared
            self.prefix_misses += len(prompt) - shared
        else:
            self.prefix_misses += len(prompt)
        return row, shared

    def release_row(self, row: int):
        """Retire a request: deref every block its table row owns."""
        n = int(self._nblocks[row])
        for blk in self.tables[row, :n]:
            self.pool.allocator.deref(int(blk))
        for w in self._windows:
            w.release(row)
        self._bind_row(row, ())
        self.lengths[row] = 0
        insort(self._free_rows, row)

    def insert_prefix(self, row: int, prompt: Sequence[int]):
        """Publish a just-prefilled prompt's full blocks into the
        prefix cache so later requests can reference them. Blocks
        gain a cache ref; entries already present are just touched."""
        if not self.prefix_cache_enabled:
            return
        bs = self.block_size
        key = None
        for i in range(len(prompt) // bs):
            chunk = tuple(prompt[i * bs:(i + 1) * bs])
            parent = key
            key = hash((key, chunk))
            ent = self._prefix.get(key)
            if ent is not None:
                if ent.tokens != chunk:
                    break  # hash collision: leave the incumbent alone
                self._prefix.move_to_end(key)
                continue
            blk = int(self.tables[row, i])
            if blk == self.TRASH:
                break
            self.pool.allocator.ref(blk)
            pin = None
            if parent is not None and parent in self._prefix:
                # children pin their parent so chains evict leaf-first
                pin = self._prefix[parent].block
                self.pool.allocator.ref(pin)
            self._prefix[key] = _PrefixEntry(key, pin, blk, chunk)

    def flush_prefix_cache(self):
        """Drop every cached prefix ref (tests / memory pressure).
        Live requests keep their own refs; only cache refs drop."""
        self.pool.flush_prefix_cache()

    @property
    def prefix_entries(self) -> int:
        return len(self._prefix)

    def match_prefix_blocks(self, prompt: Sequence[int]) -> int:
        """How many full leading blocks of ``prompt`` this pool's
        prefix cache already holds — a read-only probe (no LRU touch,
        no refs) for prefix-affinity routing verification."""
        return len(self._match_prefix(prompt))

    # -- cross-cache handoff (disaggregated prefill/decode) ----------

    def export_row(self, row: int) -> Dict[str, object]:
        """Detach a row for handoff: the returned record *owns* the
        row's block references (no deref happens here — ownership
        transfers from the row to the record), and the row itself is
        freed for the next admission. The record must eventually be
        passed to :meth:`import_row`/:meth:`adopt_row` on the
        destination cache, or its refs dropped via
        ``record["pool"].release_blocks(record["blocks"])`` — else
        ``leaked()`` rightly reports the blocks as lost."""
        if self._windows or self._states:
            raise ValueError("a row of a cache with window kinds or "
                             "recurrent state is not handed off: "
                             "disaggregation is refused for such a model "
                             "at the engine's construction")
        n = int(self._nblocks[row])
        rec = {
            "blocks": [int(b) for b in self.tables[row, :n]],
            "length": int(self.lengths[row]),
            "pool": self.pool,
            # a record from before a BlockPool.rebuild() points at
            # zeroed blocks: the adopter sheds it
            "epoch": self.pool.epoch,
        }
        self._bind_row(row, ())
        self.lengths[row] = 0
        insort(self._free_rows, row)
        return rec

    def import_row(self, rec: Dict[str, object]) -> Optional[int]:
        """Adopt an exported record whose blocks live in *this* pool:
        a pure host-side table splice — zero ref changes, the record's
        ownership moves to the new row. Returns the row, or None when
        no row is free (the record keeps its refs; retry later)."""
        if rec["pool"] is not self.pool:
            raise ValueError(
                "import_row requires a record from the same BlockPool; "
                "use adopt_row for cross-pool handoff")
        blocks = rec["blocks"]
        if len(blocks) > self.blocks_per_row:
            raise ValueError(
                f"record spans {len(blocks)} blocks > blocks_per_row="
                f"{self.blocks_per_row}")
        if not self._free_rows:
            return None
        row = self._free_rows.pop(0)
        self._bind_row(row, blocks)
        self.lengths[row] = int(rec["length"])
        return row

    def adopt_row(self, rec: Dict[str, object]) -> Optional[int]:
        """Adopt an exported record from a *different* pool: allocate
        fresh blocks here (all-or-nothing) and copy the committed
        blocks' contents across. Returns the row, or None when rows or
        blocks run out (the record keeps its source refs; retry or
        abort). On success the caller still owns the source refs and
        must drop them via ``rec["pool"].release_blocks(...)``."""
        src_pool: BlockPool = rec["pool"]  # type: ignore[assignment]
        if src_pool is self.pool:
            raise ValueError(
                "adopt_row is for cross-pool handoff; use import_row "
                "when the record already lives in this pool")
        if src_pool.num_layers != self.pool.num_layers or \
                src_pool.num_heads != self.pool.num_heads or \
                src_pool.head_dim != self.pool.head_dim or \
                src_pool.block_size != self.pool.block_size or \
                src_pool.kv_dtype != self.pool.kv_dtype:
            raise ValueError("cannot adopt blocks across pools with "
                             "different geometry or kv_dtype")
        blocks = [int(b) for b in rec["blocks"]]  # type: ignore[union-attr]
        length = int(rec["length"])  # type: ignore[arg-type]
        if len(blocks) > self.blocks_per_row:
            raise ValueError(
                f"record spans {len(blocks)} blocks > blocks_per_row="
                f"{self.blocks_per_row}")
        if not self._free_rows:
            return None
        taken: List[int] = []
        for _ in blocks:
            blk = self._alloc_block()
            if blk is None:
                for b in taken:
                    self.pool.allocator.deref(b)
                return None
            taken.append(blk)
        if taken and self.kv_dtype == "int8":
            # same stale-scale hazard as acquire(): zero the reclaimed
            # blocks' scales first, then the copy below overwrites the
            # committed ones with the source's real scales
            idx = np.asarray(taken, np.int32)
            self.layers = [
                (k, v, ks.at[idx].set(0.0), vs.at[idx].set(0.0))
                for k, v, ks, vs in self.layers]
        # only blocks holding committed KV carry data worth moving;
        # trailing reservation blocks are uninitialized by contract
        ncommit = min(len(blocks), self.blocks_needed(length))
        if ncommit:
            src_idx = np.asarray(blocks[:ncommit], np.int32)
            dst_idx = np.asarray(taken[:ncommit], np.int32)
            self.layers = [
                tuple(a.at[dst_idx].set(sa[src_idx])
                      for a, sa in zip(layer, src_layer))
                for layer, src_layer in zip(self.layers, src_pool.layers)]
        row = self._free_rows.pop(0)
        self.blocks_allocated_total += len(taken)
        self._bind_row(row, taken)
        self.lengths[row] = length
        return row

    # -- per-step bookkeeping ---------------------------------------

    def commit_prefill(self, row: int, length: int):
        """The prompt pass populated this row's blocks up to
        ``length`` (via the compiled step's table-routed writes)."""
        if length > int(self._nblocks[row]) * self.block_size:
            raise ValueError(
                f"row {row}: prefill length {length} exceeds reserved "
                f"blocks ({self._nblocks[row]} x {self.block_size})")
        self.lengths[row] = int(length)
        self._slide(row)

    def advance(self, row: int, n: int = 1):
        """Advance a row's valid length by ``n`` freshly written rows
        (1 for a plain decode token, K+1 after a speculative verify —
        committed optimistically, then trimmed via :meth:`rollback`)."""
        ln = int(self.lengths[row]) + int(n)
        if ln > int(self._nblocks[row]) * self.block_size:
            raise ValueError(
                f"row {row}: advancing by {n} overflows reserved blocks "
                f"({self._nblocks[row]} x {self.block_size} rows, at "
                f"{self.lengths[row]})")
        self.lengths[row] = ln
        self._slide(row)

    def ahead_lengths(self, rows: Sequence[int],
                      prefilled: Sequence[Tuple[int, int]] = ()
                      ) -> np.ndarray:
        """The lengths a step takes that is dispatched while what was
        dispatched before it is not committed yet: ``rows``, the rows
        the step before it writes, stand one further than
        :attr:`lengths` says, and the window kinds are moved to that
        position first, as :meth:`advance` would have left them (it then
        finds them there). A block they return here may still be read by
        the uncommitted step: it was dispatched with the table that held
        it, and whoever takes the block next writes it behind that step
        on the device. ``prefilled`` is ``(row, length)`` of each row a
        prefill dispatch writes whose :meth:`commit_prefill` is still to
        come: it stands at that length, where :meth:`acquire` left its
        window kinds."""
        lengths = self.lengths.copy()
        for row in rows:
            lengths[row] += 1
            for w in self._windows:
                if w.hold(row, int(lengths[row])):
                    self.tables_version += 1
        for row, length in prefilled:
            lengths[row] = length
        return lengths

    def _slide(self, row: int):
        """The window kinds follow ``row``'s new length: blocks wholly
        behind the window of the position it writes next go back to their
        free list, blocks ahead are taken."""
        for w in self._windows:
            if w.hold(row, int(self.lengths[row])):
                self.tables_version += 1

    def rollback(self, row: int, n: int):
        """Rewind over ``n`` rejected speculative rows. Blocks stay
        reserved (worst-case reservation at admission), so a rollback
        across a block boundary is pure length arithmetic — the stale
        rows sit past the valid length behind the position mask."""
        if n < 0 or n > int(self.lengths[row]):
            raise ValueError(
                f"row {row}: cannot roll back {n} rows from length "
                f"{self.lengths[row]}")
        self.lengths[row] = int(self.lengths[row]) - int(n)

    def arrays(self):
        """The per-layer block pools, as fed to the steps: (k, v)
        tuples, or (k, v, k_scale, v_scale) for int8 pools. A paged
        step consumes what it is fed (:class:`BlockPool`): bind its
        returned pools with :meth:`set_arrays` before anything reads
        the pool again. With window kinds: one pool pair a model layer, in
        the model's order, whichever kind's pool holds it."""
        if self._order is None:
            return list(self.layers)
        pools = [self.layers] + [w.pool.layers for w in self._windows] \
            + [st.layers for st in self._states]
        return [pools[k][i] for k, i in self._order]

    def set_arrays(self, layers):
        """Adopt a compiled step's returned pools (generic over the
        2- or 4-wide layer tuples), each to the kind that lent it."""
        layers = [tuple(layer) for layer in layers]
        if self._order is None:
            self.layers = layers
            return
        split = [[] for _ in range(1 + len(self._windows)
                                   + len(self._states))]
        for (k, _), layer in zip(self._order, layers):
            split[k].append(layer)
        self.layers = split[0]
        for w, got in zip(self._windows, split[1:]):
            w.pool.layers = got
        for st, got in zip(self._states, split[1 + len(self._windows):]):
            st.layers = got

    def rebuild_pools(self):
        """Zeroed pools (every kind's) in place of ones a failed step
        consumed: :meth:`BlockPool.rebuild`."""
        self.pool.rebuild()
        for w in self._windows:
            w.pool.rebuild()
        for st in self._states:
            st.rebuild()

    # -- the tables as the steps take them ---------------------------

    def tables_arg(self):
        """A copy of the block tables (the cache writes its own in
        place): the array, or with window kinds one array a kind."""
        if self._order is None:
            return self.tables.copy()
        return (self.tables.copy(),) \
            + tuple(w.tables.copy() for w in self._windows) \
            + tuple(st.rows for st in self._states)

    def table_rows(self, rows: Sequence[int], n: int):
        """The tables of ``rows`` as the first of ``n`` rows of a prefill
        dispatch (the others on the trash block), shaped like
        :meth:`tables_arg`."""
        def pick(tables):
            out = np.full((n, tables.shape[1]), self.TRASH, np.int32)
            out[:len(rows)] = tables[list(rows)]
            return out
        if self._order is None:
            return pick(self.tables)
        return (pick(self.tables),) \
            + tuple(pick(w.tables) for w in self._windows) \
            + tuple(st.pick(rows, n) for st in self._states)

    def kind_stats(self) -> Dict[str, int]:
        """Blocks a request holds now, by kind, and the blocks the window
        kinds returned behind their windows while their request lived."""
        out = {"kv_blocks_live_full": self.pool.allocator.num_used - 1}
        if self._windows:
            out["kv_blocks_live_window"] = sum(w.live_blocks
                                               for w in self._windows)
            out["window_blocks_freed"] = sum(w.freed_behind
                                             for w in self._windows)
        return out

    @property
    def state_bytes(self) -> int:
        """Bytes of recurrent state the cache holds (all rows, live or
        not: the arrays are allocated whole)."""
        return sum(st.nbytes for st in self._states)

    @property
    def state_rows_live(self) -> int:
        """Rows whose recurrent state belongs to a request now."""
        return self.num_used if self._states else 0
