"""Preallocated, mesh-sharded paged KV cache for the serving engine.

Layout: one K and one V pool per model, ``[L, P, H, page_size, D]``
(layers, pool pages, heads, slots per page, head dim) — head-major so a
model-parallel mesh shards dim 2 (heads) over the ``model`` axis. One
page id addresses the same page row in EVERY layer and every head
shard, so the allocator is mesh- and layer-agnostic, and decode
attention (head-independent) needs no collective.

Page 0 is RESERVED as the trash page: the allocator never hands it out,
schedulers pad dead page-table entries with it, and inactive batch rows
write their (masked) K/V there. That turns "row is padding" into plain
data flow — no dynamic shapes, no per-row programs.

Allocation is host-side (scheduling is host-side anyway): a free list of
page ids plus a per-page REFERENCE COUNT. `allocate` hands a page out
with one reference; `retain` adds references (prefix-cache sharing);
`free` drops one reference per occurrence and returns the page to the
free list only at zero. Dropping more references than are held —
duplicates within one call included — raises with the offending page
id instead of silently corrupting the free list. The device arrays are
functional jax values — the engine rebinds them after every compiled
prefill/decode call (donated, so XLA updates in place).

**Prefix registry** (`PrefixCache`): a radix-style tree over full
prompt pages. Each node is keyed by the chain (parent node, exact page
token ids) — Python's dict hashing gives the "chained content hash"
with full-key verification, so two different prefixes can never
collide into the same cached page. A registered page carries one
registry-owned reference; new requests whose prompt walks an existing
chain `retain` those pages and skip re-prefilling them. Determinism
makes this sound: given fixed weights, a page's K/V (int8 quantization
included — pinned by test) is a pure function of the token prefix, so
any request's pages are interchangeable with the original's.

**Int8 pages** (``kv_cache_dtype: "int8"``): each pool becomes a
`QuantizedPages` pytree — the int8 data pool plus a per-page SCALE pool
``[L, P, H, page_size]`` (one bf16 scale per head-slot, stored page-row
aligned so the decode kernel resolves both through the same page-table
LUT). K/V vectors quantize symmetrically per (head, slot) at write time;
the decode-attention kernel dequantizes at the DMA boundary
(`ops/pallas/decode_attention.py`). A resident token costs
``2·L·H·(D + 2)`` bytes instead of ``2·L·H·D·2`` at bf16 — ~1.94× more
sessions at a fixed pool budget for D = 64 (bf16 scales deliberately:
fp32 would cost D + 4 and cap the ratio at 1.88×).

**Latent pages** (``latent_width``; a model whose attention is latent,
MLA): ONE pool ``[L, P, page_size, row]`` with no head axis, held as
``k`` (``v`` is None): a token's row is its compressed K/V
``[c_kv | rot(k_r)]``, which every head reads, in front of the zeros that
fill the row's last lane tile (``row`` = `latent_row_width`: 640 for 576;
a TPU lays an array with a ragged last dim out with another dim
innermost, and every kernel call would copy the pool). The allocator,
the reference counts and the page tables are the ones every kind
shares.

**Recurrent state** (`StateCache`; a model with state-space or
delta-rule layers): a cache kind WITHOUT pages. A sequence owns one fixed
SLOT for its life, which holds, for every such layer, the recurrent state
(float32) and the last K - 1 rows of the convolution's input, in the
shapes the MODEL names (`GPTNeoXConfig.state_shapes`): a Mamba layer's
scan state `[N, sub, lanes]` as `ops.pallas.ssm.state_tile` lays the
channels out (358,400 B a layer a sequence at 16 x 5,120), a Gated
DeltaNet layer's matrix a value head `[n_v, d_k, d_v]` beside rows of its
`[q | k | v]` channels (2,146,304 B at 32 x 128 x 128). A prefill writes the
slot with the state after the prompt's last real token, computed from a
zero state (a slot's old content is never read: a slot handed on starts
from zero), every decode step updates it in place, the scheduler gives
it back at the request's end. Slot 0 is the trash slot, as page 0 is the
trash page: padding rows of a batch name it."""

import math

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import jax.numpy as jnp

from ..parallel.mesh import MODEL_AXIS


class QuantizedPages:
    """Int8 page pool + its per-page scale pool, as a pytree node: the
    engine's compiled calls donate/rebind it like a plain pool array
    (both leaves ride every jit/scan/vmap unchanged)."""

    __slots__ = ("data", "scale")

    def __init__(self, data, scale):
        self.data = data
        self.scale = scale

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def shape(self):
        return self.data.shape

    def __repr__(self):
        return (f"QuantizedPages(shape={tuple(self.data.shape)}, "
                f"scale={tuple(self.scale.shape)})")


jax.tree_util.register_pytree_node(
    QuantizedPages,
    lambda qp: ((qp.data, qp.scale), None),
    lambda _, children: QuantizedPages(*children))


KV_QMAX = 127.0


def quantize_kv(vec):
    """Symmetric per-vector int8 quantization over the trailing (head
    dim) axis: returns (q int8, scale fp32 [...]) with
    ``dequant = q · scale[..., None]``. Zero vectors keep scale 1."""
    amax = jnp.max(jnp.abs(vec.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / KV_QMAX, 1.0)
    q = jnp.clip(jnp.round(vec.astype(jnp.float32) / scale[..., None]),
                 -KV_QMAX, KV_QMAX).astype(jnp.int8)
    return q, scale


def pages_for_tokens(n_tokens, page_size):
    """Pages needed to hold n_tokens (ceil division)."""
    return -(-int(n_tokens) // int(page_size))


class PagedKVCache:
    """The pooled K/V store plus its free-list allocator.

    ``num_pages`` includes the reserved trash page 0, so the usable pool
    is ``num_pages - 1`` pages = ``(num_pages - 1) * page_size`` tokens
    per layer.
    """

    def __init__(self, num_layers, num_pages, num_heads, page_size,
                 head_dim, dtype=jnp.bfloat16, mesh=None, latent_width=0):
        if num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is the reserved trash "
                f"page), got {num_pages}")
        self.num_layers = int(num_layers)
        self.num_pages = int(num_pages)
        self.num_heads = int(num_heads)
        self.page_size = int(page_size)
        self.head_dim = int(head_dim)
        self.dtype = dtype
        self.quantized = jnp.dtype(dtype) == jnp.int8
        self.latent_width = int(latent_width)
        if self.latent_width and self.quantized:
            raise ValueError("latent pages have no int8 variant")
        self.mesh = mesh
        self.sharding = None
        self.scale_sharding = None
        if mesh is not None and MODEL_AXIS in mesh.axis_names and \
                mesh.shape[MODEL_AXIS] > 1:
            if self.num_heads % mesh.shape[MODEL_AXIS]:
                raise ValueError(
                    f"num_heads {self.num_heads} must divide over the "
                    f"'{MODEL_AXIS}' mesh axis "
                    f"({mesh.shape[MODEL_AXIS]} shards)")
            self.sharding = NamedSharding(
                mesh, P(None, None, MODEL_AXIS, None, None))
            self.scale_sharding = NamedSharding(
                mesh, P(None, None, MODEL_AXIS, None))
        self.reset_pools()
        # free list: every page except the trash page, low ids first so
        # tests are deterministic
        self._free = list(range(self.num_pages - 1, 0, -1))
        # reference counts for allocated pages (absent = free); the
        # prefix registry and co-reading requests hold extra references
        self._refcount = {}
        # optional `PrefixCache` (set by its constructor): allocation
        # shortfalls reclaim LRU unshared registry pages before failing
        self.prefix_cache = None

    def _make_pool(self):
        if self.latent_width:
            from ..ops.pallas.decode_attention import latent_row_width
            return jnp.zeros((self.num_layers, self.num_pages,
                              self.page_size,
                              latent_row_width(self.latent_width)),
                             self.dtype)
        shape = (self.num_layers, self.num_pages, self.num_heads,
                 self.page_size, self.head_dim)
        data = jnp.zeros(shape, self.dtype)
        if self.sharding is not None:
            data = jax.device_put(data, self.sharding)
        if not self.quantized:
            return data
        # unit scales on the zero pool: dequant of the trash page stays
        # exact zero, and a scale of 0 could never be divided back in.
        # bf16 scales: the scale's relative rounding (2^-9) is noise
        # under the int8 mantissa (2^-7), and fp32 scales would eat the
        # capacity win at head_dim 64 (128/68 = 1.88× vs 128/66 = 1.94×)
        scale = jnp.ones(shape[:-1], jnp.bfloat16)
        if self.scale_sharding is not None:
            scale = jax.device_put(scale, self.scale_sharding)
        return QuantizedPages(data, scale)

    def data_array(self, pool):
        """The raw data leaf of a pool (the array itself when the cache
        is not quantized) — liveness checks poke this."""
        return pool.data if isinstance(pool, QuantizedPages) else pool

    def reset_pools(self):
        """Rebuild the K/V device pools zeroed, keeping the allocator
        state. The serving engine's quarantine path calls this when a
        compiled step died MID-EXECUTION with the pools donated (the
        buffers are consumed and unusable); the engine then re-prefills
        every running sequence, so the zeroed contents are never
        read."""
        self.k = self._make_pool()
        self.v = None if self.latent_width else self._make_pool()

    # -- allocator (host-side) --------------------------------------------

    @property
    def num_free(self):
        return len(self._free)

    @property
    def tokens_capacity(self):
        return self.num_free * self.page_size

    def allocate(self, n):
        """Pop n pages from the free list (each carrying ONE
        reference), or None when fewer remain (all-or-nothing: a
        partial grab would deadlock admission). A shortfall first asks
        the prefix registry to reclaim LRU unshared pages."""
        n = int(n)
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > len(self._free) and self.prefix_cache is not None:
            self.prefix_cache.reclaim(n - len(self._free))
        if n > len(self._free):
            return None
        if n == 0:
            return []
        pages, self._free = self._free[-n:][::-1], self._free[:-n]
        for p in pages:
            self._refcount[p] = 1
        return pages

    def retain(self, pages):
        """Add one reference to each page (prefix-cache sharing: the
        new reader frees through the ordinary `free` path). Pages must
        be currently allocated."""
        pages = [int(p) for p in pages]
        for p in pages:
            if p not in self._refcount:
                raise ValueError(
                    f"cannot retain page {p}: not currently allocated")
        for p in pages:
            self._refcount[p] += 1

    def refcount(self, page):
        """Current reference count of a page (0 = free)."""
        return self._refcount.get(int(page), 0)

    def free(self, pages):
        """Drop one reference per occurrence; a page returns to the
        free list at zero. Raises — BEFORE mutating anything — when a
        call would take any page below zero references: duplicates
        within one call and double-frees across calls both name the
        offending page id (free-list corruption was silent before)."""
        counts = {}
        for p in pages:
            p = int(p)
            if p <= 0 or p >= self.num_pages:
                raise ValueError(f"page {p} is not an allocatable id")
            counts[p] = counts.get(p, 0) + 1
        for p, n in counts.items():
            held = self._refcount.get(p, 0)
            if n > held:
                raise ValueError(
                    f"double free of page {p}: {n} release(s) in one "
                    f"call against {held} held reference(s)")
        for p, n in counts.items():
            left = self._refcount[p] - n
            if left:
                self._refcount[p] = left
            else:
                del self._refcount[p]
                self._free.append(p)

    def bytes_per_token(self):
        """K + V bytes of cache one token occupies across all layers
        (int8 pools count the per-slot bf16 scale)."""
        itemsize = jnp.dtype(self.dtype).itemsize
        if self.latent_width:
            # what the pool holds: the row with its padding
            return self.num_layers * self.k.shape[-1] * itemsize
        per_head = self.head_dim * itemsize + (2 if self.quantized else 0)
        return 2 * self.num_layers * self.num_heads * per_head


class StateCache:
    """The recurrent-state pools and their slot allocator: `conv`
    [layers, slots, *conv_shape] in `dtype` (the convolution's last rows)
    and `ssm` [layers, slots, *state_shape] float32 (the recurrent state
    of whatever kind: a scan's, a delta rule's), the two shapes a layer a
    sequence as the model names them (`GPTNeoXConfig.state_shapes`),
    `num_slots` including the reserved trash slot 0."""

    def __init__(self, num_layers, num_slots, conv_shape, state_shape,
                 dtype):
        if num_slots < 2:
            raise ValueError(f"num_slots must be >= 2 (slot 0 is the "
                             f"reserved trash slot), got {num_slots}")
        self.num_layers, self.num_slots = int(num_layers), int(num_slots)
        self.dtype = dtype
        lead = (self.num_layers, self.num_slots)
        self._conv_shape = lead + tuple(int(d) for d in conv_shape)
        self._ssm_shape = lead + tuple(int(d) for d in state_shape)
        self.reset_pools()
        self._free = list(range(self.num_slots - 1, 0, -1))
        self._held = set()

    def reset_pools(self):
        self.conv = jnp.zeros(self._conv_shape, self.dtype)
        self.ssm = jnp.zeros(self._ssm_shape, jnp.float32)

    @property
    def num_free(self):
        return len(self._free)

    @property
    def in_use(self):
        return len(self._held)

    def allocate(self):
        """A free slot, or None when every one is held."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._held.add(slot)
        return slot

    def free(self, slot):
        if slot not in self._held:
            raise ValueError(f"double free of state slot {slot}")
        self._held.remove(slot)
        self._free.append(slot)

    def bytes_per_sequence(self):
        """Bytes of recurrent state one sequence holds, all layers."""
        return self.num_layers * (
            math.prod(self._conv_shape[2:]) *
            jnp.dtype(self.dtype).itemsize +
            math.prod(self._ssm_shape[2:]) * 4)


class _PrefixNode:
    """One registered full page: keyed under its parent by the page's
    exact token ids, so the (parent, key) chain IS the chained content
    hash — dict lookup hashes it, equality verifies it."""

    __slots__ = ("key", "page", "parent", "children", "last_used")

    def __init__(self, key, page, parent):
        self.key = key
        self.page = page
        self.parent = parent
        self.children = {}
        self.last_used = 0


class PrefixCache:
    """Radix-style page-granular prefix registry over a `PagedKVCache`.

    A completed prefill registers each FULL prompt page as a chain node
    (`register`); a new prompt walks the tree (`lookup`) and shares the
    longest matching page chain via refcounts — prefill then starts at
    the first divergent page. The registry holds one reference per
    registered page, so pages outlive the request that built them;
    `reclaim` releases least-recently-used UNSHARED leaves back to the
    allocator when the pool runs short (or past ``max_pages``), and
    `clear` drops everything (weight hot-swap / pool loss: the cached
    K/V no longer matches what a forward pass would produce).

    Host-side and deterministic: recency is a logical tick counter, not
    wall clock, so the same request stream always caches and reclaims
    the same pages."""

    def __init__(self, cache, max_pages=None):
        self.cache = cache
        self.page_size = cache.page_size
        if max_pages is not None and int(max_pages) < 1:
            raise ValueError(
                f"prefix_cache max_pages must be >= 1, got {max_pages}")
        self.max_pages = None if max_pages is None else int(max_pages)
        self.stats = {"lookups": 0, "hits": 0, "pages_shared": 0,
                      "saved_prefill_tokens": 0, "registered_pages": 0,
                      "reclaimed_pages": 0}
        self._root = _PrefixNode(None, None, None)
        self._pages = 0
        self._tick = 0
        cache.prefix_cache = self

    @staticmethod
    def page_key(tokens):
        """The canonical node key for one page's worth of tokens."""
        return tuple(int(t) for t in tokens)

    def _touch(self, node):
        self._tick += 1
        node.last_used = self._tick

    def lookup(self, tokens):
        """Longest registered page chain covering a prefix of `tokens`,
        capped so at least ONE token is left to prefill (prefill always
        samples the first generated token). Returns the node chain
        (possibly empty); the caller retains the pages."""
        ps = self.page_size
        limit = max((len(tokens) - 1) // ps, 0)
        node = self._root
        chain = []
        for i in range(limit):
            child = node.children.get(
                self.page_key(tokens[i * ps:(i + 1) * ps]))
            if child is None:
                break
            chain.append(child)
            node = child
        for n in chain:
            self._touch(n)
        return chain

    def register(self, parent, keys, pages):
        """Extend the chain under `parent` (None = root) with full
        pages: `keys[i]` is `page_key(...)` of the page's tokens,
        `pages[i]` the request-owned page holding their K/V. A key
        already registered keeps the EXISTING node/page (the request's
        copy stays request-owned and frees normally); a new key retains
        the page for the registry. Returns the deepest node."""
        node = parent if parent is not None else self._root
        for key, page in zip(keys, pages):
            child = node.children.get(key)
            if child is None:
                self.cache.retain([page])
                child = _PrefixNode(key, int(page), node)
                node.children[key] = child
                self._pages += 1
            self._touch(child)
            node = child
        self.stats["registered_pages"] = self._pages
        if self.max_pages is not None and self._pages > self.max_pages:
            self.reclaim(self._pages - self.max_pages)
        return node

    def _lru_leaves(self):
        out = []
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            else:
                out.append(n)
        out.sort(key=lambda n: n.last_used)
        return out

    def reclaim(self, n_pages):
        """Release up to `n_pages` least-recently-used UNSHARED leaf
        pages back to the allocator (refcount 1 = registry-only; a
        page some in-flight request still reads is never reclaimed —
        "eviction skips shared pages"). Interior nodes become leaves as
        their children go, so a whole cold chain drains back-to-front.
        Returns the number reclaimed."""
        reclaimed = 0
        while reclaimed < int(n_pages):
            leaf = next((l for l in self._lru_leaves()
                         if self.cache.refcount(l.page) == 1), None)
            if leaf is None:
                break
            leaf.parent.children.pop(leaf.key)
            self.cache.free([leaf.page])
            self._pages -= 1
            reclaimed += 1
        self.stats["registered_pages"] = self._pages
        self.stats["reclaimed_pages"] += reclaimed
        return reclaimed

    def clear(self):
        """Drop every chain and release the registry's references.
        Pages still shared with in-flight requests stay allocated until
        those requests free them — only the registry's claim ends."""
        stack = list(self._root.children.values())
        pages = []
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            pages.append(n.page)
        if pages:
            self.cache.free(pages)
        self._root.children.clear()
        self._pages = 0
        self.stats["registered_pages"] = 0
