"""Paged KV cache: a block-pool allocator over preallocated HBM arenas.

The dense per-request decode cache (`GPTModel.init_cache`) reserves
`max_seq_len` positions for every request up front — at serving batch
sizes almost all of it is padding, and admission is all-or-nothing.
PagedAttention (vLLM, SOSP '23) showed the fix: carve the cache into
fixed-size BLOCKS in one shared physical arena, give each request a
block TABLE mapping logical positions to physical blocks, and
allocate/free blocks at token granularity. Utilization becomes
~100% - half a block per request, and eviction is O(blocks) pointer
surgery instead of buffer copies.

Since the prefix-sharing round the pool is REFCOUNTED: a block may be
referenced by several requests at once (copy-on-write sharing — the
RadixAttention insight, SGLang 2024), and by the `PrefixIndex`, which
retains fully-written prompt blocks after their writer finished so
later requests with the same token prefix skip recomputing them.
Sharing rules:

- a FULL block (every position holds prompt K/V) is immutable: any
  number of requests may reference it (`incref`), and each release is
  a `free` that merely drops one reference;
- a PARTIAL tail block is forked before its holder writes into it
  (`ServingEngine._cow_fork` copies the rows device-side into a fresh
  private block) — a writer never mutates a block someone else can
  read;
- eviction of cached-but-unreferenced blocks is LRU over the index's
  refcount-0 LEAVES (`PrefixIndex.evict`), layered UNDER the existing
  evict-by-recompute preemption, which only ever releases a request's
  own references.

Three layers, split host/device:

- `BlockPool` — the HOST-side allocator: free list + per-block holder
  lists (refcount == number of holders) + the cached set (blocks the
  `PrefixIndex` retains even at refcount 0). Pure Python,
  deterministic (LIFO free list) so a seeded request schedule replays
  bit-identically. Block 0 is RESERVED as the null block: padded batch
  slots and masked prefill tails write their garbage there, so the
  compiled step needs no branches.
- `PrefixIndex` — a block-granular radix/trie over token-id chunks:
  each edge is one block's worth of token ids, each node the physical
  block holding that chunk's K/V. Admission matches a prompt against
  it and starts prefill at the first uncached token.
- `PagedKVCache` — the DEVICE-side arenas: per layer, what that
  layer's `CacheKind` declares a token keeps. Full K/V (`kv_kind`): K
  and V as `[num_blocks, block_size, hidden]` jnp arrays (the flat
  [*, n*h] minor layout the fused decode kernels require — see
  ops/pallas_decode.py). Latent (`latent_kind`): ONE arena of
  `[num_blocks, block_size, width]` whose row is key and value at once
  (multi-head latent attention). A layer whose memory is by REQUEST
  and not by token (`state_kind`: a recurrent state; `window_kind`: the
  K and V of the last `window` positions, a ring) keeps arenas of
  `[rows + 1, ...]` instead, one row a request handed out by the
  `RowPool`; the prefix index cannot share such a layer's memory, so
  the engine builds none for a model that has one. The arrays are
  handed to the engine's compiled step functions, updated
  functionally, and stored back; `swap()` is the single mutation
  point so donation stays sound.

The attention over the K/V layout is
`ops.pallas_decode.paged_decode_attention` (decode) and
`ops.pallas_decode.flash_prefill_chunk` (chunked prefill); over the
latent layout `ops.pallas_mla.mla_paged_decode` and `mla_prefill_chunk`.
All four leave the arenas in HBM and copy the live pages of a tile of
128-512 rows themselves, through the block table, up to the last
position attended: a table entry past it is never read.
"""
import math

import jax.numpy as jnp

__all__ = ["BlockPool", "BlockLeakError", "CacheKind", "PagedKVCache",
           "NULL_BLOCK", "NULL_ROW", "PrefixIndex", "RowPool",
           "StaleIndexError", "kv_kind", "latent_kind", "state_kind",
           "window_kind"]


class BlockLeakError(AssertionError):
    """`BlockPool.assert_quiesced` found blocks still referenced: some
    path (cancel, deadline expiry, eviction, engine restart, finish)
    dropped a request without returning its references to the pool.
    Blocks the PrefixIndex retains at refcount 0 are the CACHE, not a
    leak — only live references count."""


class StaleIndexError(RuntimeError):
    """The `PrefixIndex` is bound to a pool that is no longer the
    scheduler's pool: physical block ids in the index are invalid
    after an arena rebuild (warm restart / drain), and serving a
    request from them would splice another tenant's K/V into its
    attention. The engine must `flush()` + `bind()` the index whenever
    it rebuilds the arenas; this error is the tripwire for the path
    that forgot (tools/serving_smoke.py --selfcheck proves it fires)."""


# physical block 0 is never allocated: it is the write target for
# padded batch slots and masked prefill tails (their values are
# garbage by construction and never read back)
NULL_BLOCK = 0

# row 0 of a request-row arena is never handed out: a decode slot that
# holds no request reads and writes it
NULL_ROW = 0

_UNSET = object()


class BlockPool:    # guarded by: ServingEngine._mu
    """Refcounted free-list allocator over `num_blocks` physical blocks
    (block 0 reserved). Any free block serves any request — paging
    means fragmentation cannot strand capacity — and the LIFO
    discipline makes allocation deterministic under a replayed
    schedule.

    Block states:
    - FREE: on the free list;
    - HELD: one or more holders (`alloc` starts a block at one
      reference; `incref` adds sharers; `free` drops one reference
      each);
    - CACHED: retained by the `PrefixIndex` (`mark_cached`), possibly
      at refcount 0 — not allocatable, not a leak, reclaimed by index
      eviction (`release_cached`).
    """

    def __init__(self, num_blocks):
        if num_blocks < 2:
            raise ValueError(
                f"BlockPool needs >= 2 blocks (one is the reserved null "
                f"block), got {num_blocks}")
        self.num_blocks = int(num_blocks)
        # LIFO stack; low ids allocated first for readable tests
        self._free = list(range(self.num_blocks - 1, NULL_BLOCK, -1))
        self._holders = {}        # block id -> [owner tag, ...] (refcount)
        self._cached = set()      # blocks the PrefixIndex retains

    @property
    def capacity(self):
        """Allocatable blocks (the null block is not capacity)."""
        return self.num_blocks - 1

    @property
    def num_free(self):
        return len(self._free)

    @property
    def num_used(self):
        """Blocks with at least one live reference. Cached blocks at
        refcount 0 are NOT used (they are reclaimable cache), so the
        quiesce invariant `num_used == 0` stays meaningful under
        prefix sharing."""
        return len(self._holders)

    @property
    def num_cached(self):
        """Cached blocks with no live reference (the reclaimable
        prefix-cache footprint)."""
        return sum(1 for b in self._cached if b not in self._holders)

    @property
    def num_shared(self):
        """Blocks referenced by more than one holder right now — the
        `serving.prefix_blocks_shared` gauge, and the quantity the
        quiesce record must report as zero."""
        return sum(1 for h in self._holders.values() if len(h) > 1)

    def utilization(self):
        return (self.capacity - len(self._free)) / self.capacity

    def can_alloc(self, n):
        return len(self._free) >= n

    def alloc(self, n, owner=None):
        """Allocate `n` blocks for `owner` (one reference each).
        Returns the block-id list, or None when the pool cannot satisfy
        the request (the caller decides whether to evict cache entries
        or preempt; a partial allocation is never made)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if len(self._free) < n:
            return None
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._holders[b] = [owner]
        return blocks

    def incref(self, blocks, owner=None):
        """Add `owner` as a holder of each block — the prefix-cache hit
        path: a request referencing already-computed blocks. Blocks
        must be live (held or cached); a free block has no content to
        share."""
        for b in blocks:
            if b == NULL_BLOCK:
                raise ValueError("incref of the reserved null block")
            holders = self._holders.get(b)
            if holders is None:
                if b not in self._cached:
                    raise ValueError(
                        f"incref of free/unallocated block {b}")
                self._holders[b] = [owner]
            elif owner in holders:
                raise ValueError(
                    f"owner {owner!r} already holds block {b}")
            else:
                holders.append(owner)

    def free(self, blocks, owner=_UNSET):
        """Drop ONE reference per block (finish/eviction/cancel
        reclaim). A block's last release returns it to the free list —
        unless the PrefixIndex retains it, in which case it parks as
        reclaimable cache. `owner` names whose reference to drop; when
        omitted it defaults to the sole holder (the pre-sharing calling
        convention) and a SHARED block refuses the ambiguity."""
        for b in blocks:
            if b == NULL_BLOCK:
                raise ValueError("attempt to free the reserved null block")
            holders = self._holders.get(b)
            if holders is None:
                if b in self._free:
                    raise ValueError(f"double free of block {b}")
                raise ValueError(f"free of unallocated block {b}")
            if owner is _UNSET:
                if len(holders) > 1:
                    raise ValueError(
                        f"free of shared block {b} (holders "
                        f"{list(holders)}) needs an explicit owner")
                holders.pop()
            else:
                if owner not in holders:
                    raise ValueError(
                        f"free of block {b}: {owner!r} is not a holder "
                        f"(holders {list(holders)})")
                holders.remove(owner)
            if not holders:
                del self._holders[b]
                if b not in self._cached:
                    self._free.append(b)

    def refcount(self, block):
        return len(self._holders.get(block, ()))

    def is_cached(self, block):
        return block in self._cached

    def is_private(self, block, owner):
        """True when `owner` is the SOLE reference and the index does
        not retain the block — the write-safety predicate: only a
        private block may be written in place; anything else must be
        forked first (copy-on-write)."""
        return (self._holders.get(block) == [owner]
                and block not in self._cached)

    def holders_of(self, block):
        """The full holder set of `block` (tuple, insertion order)."""
        return tuple(self._holders.get(block, ()))

    def owner_of(self, block):
        """The holder set of `block`: None when unheld, the sole owner
        tag when exactly one holder (the pre-sharing contract), else
        the tuple of every holder — leak reports under sharing must
        name ALL of them."""
        holders = self._holders.get(block)
        if not holders:
            return None
        if len(holders) == 1:
            return holders[0]
        return tuple(holders)

    def mark_cached(self, block):
        """The PrefixIndex retains `block`: it survives its holders'
        release (at refcount 0 it parks as reclaimable cache instead of
        returning to the free list)."""
        if block == NULL_BLOCK:
            raise ValueError("cannot cache the reserved null block")
        if block not in self._holders and block not in self._cached:
            raise ValueError(
                f"mark_cached of free/unallocated block {block}")
        self._cached.add(block)

    def release_cached(self, block):
        """The PrefixIndex dropped `block` (eviction or flush): when no
        request still references it, it returns to the free list."""
        if block not in self._cached:
            raise ValueError(f"release_cached of uncached block {block}")
        self._cached.discard(block)
        if block not in self._holders:
            self._free.append(block)

    def assert_quiesced(self):
        """Every block must be unreferenced — the leak check a quiesced
        engine (all requests terminal) runs at drain end, at drill
        quiesce, and at test teardown. Blocks the PrefixIndex retains
        at refcount 0 are cache, not a leak. Raises `BlockLeakError`
        naming EVERY holder of each leaked block (a block with refs>1
        names the full holder set, so the leak report stays actionable
        under copy-on-write sharing)."""
        if not self._holders:
            return
        by_owner = {}
        for b, holders in self._holders.items():
            for owner in holders:
                by_owner.setdefault(owner, []).append(b)
        detail = "; ".join(
            f"owner {owner!r} holds blocks {sorted(blocks)}"
            for owner, blocks in sorted(by_owner.items(), key=str))
        shared = {b: tuple(h) for b, h in self._holders.items()
                  if len(h) > 1}
        if shared:
            detail += "; shared (refs>1): " + ", ".join(
                f"block {b} held by {list(h)}"
                for b, h in sorted(shared.items()))
        raise BlockLeakError(
            f"{self.num_used} KV block(s) still referenced at quiesce: "
            f"{detail}")


class _PrefixNode:
    """One cached block: the trie edge into it is `chunk` (its
    block_size token ids, possibly only partially valid for the LAST
    tokens of a prompt — sharing still only ever reads the positions
    the matching prompt covers)."""

    __slots__ = ("chunk", "block", "children", "parent", "last_used")

    def __init__(self, chunk, block, parent):
        self.chunk = chunk
        self.block = block
        self.children = {}        # chunk tuple -> _PrefixNode
        self.parent = parent
        self.last_used = 0


class PrefixIndex:    # guarded by: ServingEngine._mu
    """Block-granular radix index over token-id chunks.

    Each trie edge is one FULL block of token ids; the node at its end
    names the physical block whose K/V rows hold exactly those tokens
    at those positions. Matching walks full-block chunks, then — for
    the remainder — takes the child sharing the longest common token
    prefix: its block is referenced PARTIALLY (the first `t` rows),
    which is what makes "start prefill at the first uncached token"
    literal rather than block-rounded. A match is always capped at
    `len(tokens) - 1` so at least one position is computed live (the
    next-token logits must come from somewhere).

    The index holds no references of its own — it RETAINS blocks via
    `BlockPool.mark_cached`, and `evict` reclaims LRU leaves whose
    refcount is 0 (a leaf some request still references is pinned:
    evicting it mid-decode is impossible by construction).

    Every mutating/reading entry point takes the caller's pool and
    verifies it is the bound pool: after an arena rebuild the physical
    ids here are fiction, and `StaleIndexError` is the tripwire for an
    engine path that rebuilt without `flush()` + `bind()`.
    """

    def __init__(self, block_size, pool=None):
        self.block_size = int(block_size)
        self._pool = pool
        self._root_children = {}  # chunk tuple -> _PrefixNode
        self._nodes = 0
        self._clock = 0           # LRU tick

    def bind(self, pool):
        """(Re)bind to the live pool — must follow every arena
        rebuild, after `flush()`."""
        self._pool = pool

    def _check(self, pool):
        if pool is not self._pool:
            raise StaleIndexError(
                "PrefixIndex is bound to a stale BlockPool: the arenas "
                "were rebuilt without flushing the index (its physical "
                "block ids no longer name this pool's storage)")

    @property
    def num_blocks(self):
        return self._nodes

    def _touch(self, node):
        self._clock += 1
        node.last_used = self._clock

    def match(self, tokens, pool):
        """Longest cached prefix of `tokens` -> (block ids, n_cached).

        Full-chunk matches walk the trie; the remainder may match the
        leading rows of one more cached block (the partial-tail case —
        the caller's first write into that block must copy-on-write
        fork it). `n_cached <= len(tokens) - 1` always, so prefill has
        at least one live position to compute logits from. The caller
        increfs the returned blocks for the requesting owner."""
        self._check(pool)
        tokens = list(tokens)
        bs = self.block_size
        blocks = []
        children = self._root_children
        pos = 0
        limit = len(tokens) - 1
        while pos + bs <= limit:
            chunk = tuple(tokens[pos:pos + bs])
            node = children.get(chunk)
            if node is None:
                break
            blocks.append(node.block)
            self._touch(node)
            children = node.children
            pos += bs
        # partial tail: the child sharing the longest common prefix
        # with the remaining tokens (capped so >= 1 token stays live)
        remainder = tokens[pos:pos + bs]
        best, best_t = None, 0
        for chunk, node in children.items():
            t = 0
            for a, b in zip(remainder, chunk):
                if a != b:
                    break
                t += 1
            t = min(t, limit - pos)
            if t > best_t:
                best, best_t = node, t
        if best is not None:
            blocks.append(best.block)
            self._touch(best)
            pos += best_t
        return blocks, pos

    def insert(self, tokens, blocks, pool):
        """Register `blocks[i]` as the cached K/V of the i-th FULL
        chunk of `tokens`. Idempotent: an existing node for a chunk
        keeps its block (the physical copies are interchangeable — the
        K/V of a token prefix is position-determined), and the caller's
        duplicate block simply stays private to it."""
        self._check(pool)
        tokens = list(tokens)
        bs = self.block_size
        n = min(len(blocks), len(tokens) // bs)
        children = self._root_children
        parent = None
        for i in range(n):
            chunk = tuple(tokens[i * bs:(i + 1) * bs])
            node = children.get(chunk)
            if node is None:
                node = _PrefixNode(chunk, blocks[i], parent)
                children[chunk] = node
                self._nodes += 1
                pool.mark_cached(blocks[i])
            self._touch(node)
            parent = node
            children = node.children

    def _leaves(self):
        out = []
        stack = list(self._root_children.values())
        while stack:
            node = stack.pop()
            if node.children:
                stack.extend(node.children.values())
            else:
                out.append(node)
        return out

    def evict(self, n, pool):
        """Reclaim up to `n` blocks: LRU over refcount-0 LEAVES only —
        an interior node's block backs every cached suffix under it,
        and a leaf some request references is pinned (`refcount > 0`),
        which is what makes evicting a shared leaf under a mid-decode
        reader impossible. Returns the number of blocks actually
        returned to the free list.

        One trie walk per call: the evictable leaves go into a heap,
        and dropping a leaf only re-examines its parent (the single
        node the eviction can newly expose as a leaf) — nothing else
        mutates mid-call, so the walk never repeats."""
        self._check(pool)
        import heapq
        import itertools
        tie = itertools.count()
        heap = [(leaf.last_used, next(tie), leaf)
                for leaf in self._leaves()
                if pool.refcount(leaf.block) == 0]
        heapq.heapify(heap)
        freed = 0
        while freed < n and heap:
            _, _, leaf = heapq.heappop(heap)
            self._drop(leaf, pool)
            freed += 1
            parent = leaf.parent
            if parent is not None and not parent.children and \
                    pool.refcount(parent.block) == 0:
                heapq.heappush(heap,
                               (parent.last_used, next(tie), parent))
        return freed

    def _drop(self, node, pool):
        if node.parent is None:
            del self._root_children[node.chunk]
        else:
            del node.parent.children[node.chunk]
        self._nodes -= 1
        pool.release_cached(node.block)

    def flush(self):
        """Drop every entry, releasing the retained blocks back to the
        bound pool — MANDATORY before an arena rebuild (warm restart)
        and at drain quiesce: physical ids do not survive either."""
        pool = self._pool
        stack = list(self._root_children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if pool is not None:
                pool.release_cached(node.block)
        self._root_children = {}
        self._nodes = 0


class CacheKind:
    """What one layer keeps in the cache: one or two arenas. There are
    two families.

    Paged by TOKEN (`widths`): one arena of `[num_blocks, block_size, w]`
    for each `w`; a token costs a row of each and the block pool hands
    the pages out.

    Rows by REQUEST (`request_rows`): one arena of `[rows + 1, *shape]`
    in `dtype` for each `(shape, dtype)`; a request costs one row of
    each whatever its length (a recurrent layer's state), row 0 is the
    null row, and the `RowPool` hands the rows out.

    The engine sizes, forks and swaps arenas by this alone; what the
    numbers mean is the layer's business."""

    def __init__(self, name, widths=(), request_rows=(), window=0):
        self.name = str(name)
        self.window = int(window)   # positions a ring holds; 0: no ring
        self.widths = tuple(int(w) for w in widths)
        self.request_rows = tuple(
            (tuple(int(n) for n in shape), jnp.dtype(dtype))
            for shape, dtype in request_rows)
        if bool(self.widths) == bool(self.request_rows):
            raise ValueError("a cache kind is paged by token (widths) or "
                             "keeps rows by request (request_rows)")
        if not 1 <= len(self.widths) + len(self.request_rows) <= 2:
            raise ValueError("a cache kind holds one or two arenas a layer")

    @property
    def by_request(self):
        return bool(self.request_rows)

    @property
    def row_width(self):
        """Numbers a token costs in a layer of this kind."""
        return sum(self.widths)

    @property
    def request_bytes(self):
        """Bytes a request costs in a layer of this kind, whatever its
        length."""
        return sum(math.prod(shape) * dtype.itemsize
                   for shape, dtype in self.request_rows)

    def __repr__(self):
        return f"CacheKind({self.name!r}, {self.widths or self.request_rows})"


def kv_kind(hidden):
    """Full attention: a K and a V row of kv_heads * head_dim each."""
    return CacheKind("kv", (hidden, hidden))


def latent_kind(width):
    """Latent attention: one row (compressed K/V and the shared rotary
    key) that is key and value at once."""
    return CacheKind("latent", (width,))


def state_kind(*request_rows):
    """A recurrent layer: what a request keeps between tokens, each a
    `(shape, dtype)`, e.g. a convolution's tail and the state itself."""
    return CacheKind("state", request_rows=request_rows)


def window_kind(width, window, dtype="bfloat16"):
    """A layer that attends over its last `window` positions only: a
    ring of `window` K rows and one of V rows a request, whatever the
    request's length. Position p lives in row `p % window`; which rows
    are valid follows from the positions a step works on (a chunk that
    starts at position 0 starts from an empty ring whatever the row
    held), so a ring is never cleared."""
    ring = ((int(window), int(width)), dtype)
    return CacheKind("window", request_rows=(ring, ring), window=window)


class RowPool:    # guarded by: ServingEngine._mu
    """The rows 1..n of the request-row arenas (row 0 is the null row).
    A request takes one when it is admitted to prefill and gives it back
    when it is released or preempted; admission bounds running +
    prefilling by `max_slots`, which is `n`, so a row is always free.
    `names` are the by-request kinds the model's layers keep ("state",
    "window"): the `serving.<name>_rows_*` counters are written under
    each."""

    def __init__(self, n, names=("state",)):
        self.capacity = int(n)
        self.names = tuple(names)
        self._free = list(range(self.capacity, NULL_ROW, -1))   # LIFO
        self._owner = {}          # row -> owner tag

    @property
    def num_live(self):
        return len(self._owner)

    def take(self, owner=None):
        if not self._free:
            raise RuntimeError(
                f"RowPool: all {self.capacity} request rows are taken")
        row = self._free.pop()
        self._owner[row] = owner
        return row

    def give(self, row):
        if row not in self._owner:
            raise ValueError(f"give of request row {row} nobody holds")
        del self._owner[row]
        self._free.append(row)

    def assert_quiesced(self):
        if self._owner:
            raise BlockLeakError(
                f"{len(self._owner)} request row(s) still held at "
                f"quiesce: {sorted(self._owner.items())}")


class PagedKVCache:
    """Per-layer arenas, as each layer's `CacheKind` declares: `k[l]` is
    the layer's first arena, `v[l]` its second or None where the kind
    has one (a latent layer). A kind paged by token gets
    `[num_blocks, block_size, width]` in the cache's dtype: the minor
    dim stays flat so the paged pallas kernels can stream blocks without
    a reshape copy (the same constraint as the dense decode cache — see
    GPTModel.init_cache). A kind that keeps rows by request gets
    `[request_rows + 1, *shape]` in the dtype it declares.
    """

    def __init__(self, kinds, num_blocks, block_size, dtype="bfloat16",
                 request_rows=0):
        self.kinds = tuple(kinds)
        self.num_layers = len(self.kinds)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.dtype = jnp.dtype(dtype)
        self.request_rows = int(request_rows)

        def arenas(kind):
            if kind.by_request:
                return [jnp.zeros((self.request_rows + 1,) + shape, dt)
                        for shape, dt in kind.request_rows]
            return [jnp.zeros((self.num_blocks, self.block_size, width),
                              self.dtype) for width in kind.widths]

        pairs = [(arenas(kind) + [None])[:2] for kind in self.kinds]
        self.k = tuple(first for first, _ in pairs)
        self.v = tuple(second for _, second in pairs)
        # memory-observatory tagging (telemetry/mem_obs): the live HBM
        # ledger attributes these arenas to the 'kv' bucket by querying
        # this provider FRESH each snapshot (swap() replaces the
        # arrays, so identities tagged once would rot). Weakref-owned:
        # the engine's restart protocol builds a NEW cache and drops
        # this one — registration must not keep the donated arenas
        # alive.
        try:
            from ..telemetry import mem_obs
            mem_obs.register_provider(
                "kv_cache.arenas", "kv", self,
                lambda cache: cache.arenas())
        except Exception:
            pass

    def arenas(self):
        """Every arena there is, K first."""
        return list(self.k) + [a for a in self.v if a is not None]

    @property
    def nbytes(self):
        return sum(a.nbytes for a in self.arenas())

    @staticmethod
    def block_bytes(kinds, block_size, dtype):
        """Bytes one block costs over all layers (those that keep rows
        by request cost a block nothing)."""
        return sum(kind.row_width for kind in kinds) * int(block_size) \
            * jnp.dtype(dtype).itemsize

    @staticmethod
    def request_bytes(kinds):
        """Bytes one request row costs over all layers."""
        return sum(kind.request_bytes for kind in kinds)

    def fresh(self):
        """An empty cache of the same layout (after a failed step the
        donated arenas are suspect)."""
        return PagedKVCache(self.kinds, self.num_blocks, self.block_size,
                            dtype=self.dtype, request_rows=self.request_rows)

    def swap(self, new_k, new_v):
        """Install the updated arenas returned by a compiled step. The
        old arrays may have been DONATED to that step — they must never
        be read again, which is why this is the one mutation point."""
        self.k = tuple(new_k)
        self.v = tuple(new_v)

    @staticmethod
    def blocks_for_tokens(n_tokens, block_size):
        """Blocks needed to hold `n_tokens` positions."""
        return -(-int(n_tokens) // int(block_size))
