"""Paged KV cache (vLLM-style) in JAX — device-resident decode metadata.

Storage: ``BlockPool`` owns the per layer-stacked pools ``k/v: [L,
num_blocks + 1, Hkv, block, D]`` in kernel-native layout (the Pallas
paged-decode kernel and the jnp fallback both read ``[page, Hkv, block, D]``
tiles without a transpose) plus the host-side ``BlockAllocator``.  Physical
block ``num_blocks`` is a trash page: padded batch slots scatter their dummy
K/V there, so the fused decode step needs no masking branches.

A ``PagedKVCache`` is one replica's *view* of a pool: per-slot block tables,
sequence lengths, and SSM state.  ``PagedKVCache.create`` builds a private
pool (single-replica engines, unchanged seed behavior);
``PagedKVCache.from_pool`` attaches to a shared pool so N replicas of a
``ClusterRuntime`` partition one device allocation instead of each reserving
a max-size cache.  A shared view carries a block ``quota`` — its slice of
the pool — so one replica cannot starve the others.  Enforcement is by
*reservation*: ``admit(slot, prompt_len, total_tokens)`` reserves the
sequence's full lifetime block count (prompt + decode growth) against both
the view quota and the pool, so later ``extend`` calls always draw from
already-reserved capacity and in-quota decode can never exhaust a sibling
replica's share.  The allocator stays the single source of truth for
physical ownership.

The host-side ``BlockAllocator`` remains the source of truth for block
ownership; ``block_table``/``seq_lens`` (host numpy) mirror it for the
scheduler.  Device-resident copies ``block_table_dev [max_seqs + 1,
max_blocks_per_seq]`` and ``seq_lens_dev [max_seqs + 1]`` are synced
*incrementally* — one small scatter on admit / page-crossing / release —
never re-uploaded wholesale per step.  Row ``max_seqs`` is the trash slot
(points at the trash page) used to pad decode batches to bucket sizes.

Migration primitives (``repro.serving.migration`` builds on these):
``disown_slot`` removes a sequence from a view's accounting *without*
returning its blocks to the allocator, so a sibling view over the same pool
can ``adopt_slot`` them — a deployment switch then moves a sequence's KV by
re-registering page ownership instead of copying (zero tokens recomputed).
``copy_blocks`` is the jitted pool-to-pool page gather/scatter for
migrations that cross pools; ``gather_tokens`` + ``scatter_tokens`` re-
layout a sequence between pools whose page geometry differs.

``gather_dense`` survives only for the legacy dense-gather decode path and
parity tests; the serving decode path consumes pages directly.

Prefix sharing (``repro.serving.prefixcache`` builds on these): ``admit``
can attach already-resident pages by refcount (``shared_blocks``) and
copy-on-write a partially-matched page (``cow_src``); such pages are
*counted once* — they never enter a view's reservation or ``used_blocks``,
and every teardown path (``release_slot``/``disown_slot``/migration)
decrefs through ``BlockAllocator.release`` instead of freeing, so a page
survives as long as any sequence or the cache index references it.  A
``PrefixCache`` attached to ``BlockPool.prefix_cache`` is consulted under
allocation pressure (``BlockPool.reclaim``) to evict cold cached pages to
host memory — see the prefixcache module docstring for hashing granularity,
the refcount lifecycle, and the eviction policy.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


class BlockAllocator:
    """Host-side free-list of physical blocks (+ copy-on-write ready refcounts)."""

    def __init__(self, num_blocks: int):
        self.free = list(range(num_blocks - 1, -1, -1))
        self.refs = np.zeros(num_blocks, np.int32)
        # blocks held by more than one owner (prefix sharing): they occupy
        # physical capacity outside any single sequence's reservation, so
        # reservation headroom must subtract them — see n_free_blocks
        self.pinned = 0

    def alloc(self, n: int) -> list[int]:
        if len(self.free) < n:
            raise MemoryError(f"KV pool exhausted (need {n}, "
                              f"have {len(self.free)})")
        out = [self.free.pop() for _ in range(n)]
        for b in out:
            self.refs[b] = 1
        return out

    def release(self, blocks: list[int]) -> None:
        for b in blocks:
            self.refs[b] -= 1
            if self.refs[b] == 1:
                self.pinned -= 1
            if self.refs[b] <= 0:
                self.refs[b] = 0
                self.free.append(b)

    def share(self, blocks: list[int]) -> None:
        """Prefix sharing: bump refcounts (copy-on-write on append)."""
        for b in blocks:
            self.refs[b] += 1
            if self.refs[b] == 2:
                self.pinned += 1

    @property
    def n_free(self) -> int:
        return len(self.free)


class BlockPool:
    """Device K/V block pool + allocator, shareable across replica caches.

    Replica caches read and functionally update ``pool.k`` / ``pool.v``
    through their ``PagedKVCache.k`` properties; because a host scheduler
    steps replicas sequentially, every view always sees the latest arrays.
    """

    def __init__(self, cfg: ModelConfig, num_blocks: int,
                 block_size: int = 16, dtype=jnp.float32, head_pad: int = 1,
                 mesh=None, kv_spec=None, rules: dict | None = None):
        """``mesh`` + ``kv_spec`` (a ``PartitionSpec`` over the pool layout
        ``[L, P + 1, Hkv, page, D]`` — see ``launch.sharding.pool_pspecs``)
        place the pool sharded over one serving replica's device mesh:
        KV heads over tp, layers over pp.  Block ids and the allocator are
        untouched — a page is a page whatever its head sharding.  ``rules``
        (the plan's logical-axis rules) additionally shard replica SSM state
        created by ``PagedKVCache.from_pool``."""
        self.cfg = cfg
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.dtype = dtype
        self.head_pad = head_pad
        self.mesh = mesh
        self.kv_spec = kv_spec
        self.rules = rules or {}
        self.k = self.v = None
        if cfg.has_attn:
            # head_pad > 1 (the Pallas kernel path) pads head_dim once at
            # allocation so the per-step kernel call never re-pads the pool
            d_pool = -(-cfg.head_dim // head_pad) * head_pad
            shape = (cfg.n_layers, num_blocks + 1, cfg.n_kv_heads,
                     block_size, d_pool)
            self.k = jnp.zeros(shape, dtype)
            self.v = jnp.zeros(shape, dtype)
            if mesh is not None:
                sh = NamedSharding(mesh, kv_spec if kv_spec is not None
                                   else P())
                self.k = jax.device_put(self.k, sh)
                self.v = jax.device_put(self.v, sh)
        self.allocator = BlockAllocator(num_blocks)
        self.reserved = 0           # blocks promised to admitted sequences
        self.prefix_cache = None    # set by PrefixCache.__init__ when enabled

    def reclaim(self, n: int) -> None:
        """Make room for an ``n``-block allocation by evicting cold cached
        pages to the host tier (no-op without a prefix cache, or when the
        free list already covers the request)."""
        if self.prefix_cache is not None and self.allocator.n_free < n:
            self.prefix_cache.reclaim(n)

    @property
    def trash_page(self) -> int:
        return self.num_blocks

    @property
    def placement(self):
        """Device placement + sharding identity: two pools with equal
        placement can exchange pages by the jitted same-mesh copy; unequal
        placements must go through ``reshard_blocks``."""
        if self.mesh is None:
            return None
        return (self.mesh, self.kv_spec)

    @property
    def page_nbytes(self) -> int:
        """Device bytes one K+V page holds across all layers: one page in
        both k and v is [L, Hkv, block, D] at pool dtype (0 for attn-free
        archs).  Telemetry and the prefix cache's host tier both size
        transfers with this."""
        if self.k is None:
            return 0
        per = int(np.prod(self.k.shape[2:])) * self.k.dtype.itemsize
        return 2 * per * int(self.k.shape[0])


@dataclasses.dataclass
class PagedKVCache:
    cfg: ModelConfig
    block_size: int
    num_blocks: int             # pool-wide physical block count
    max_seqs: int
    max_blocks_per_seq: int
    pool: BlockPool             # owns k/v [L, num_blocks + 1, Hkv, block, D]
    ssm: jax.Array | None       # [L, max_seqs + 1, ...] (+1 = trash row)
    conv: jax.Array | None
    block_table: np.ndarray     # host [max_seqs, max_blocks_per_seq] int32
    seq_lens: np.ndarray        # host [max_seqs] int32
    block_table_dev: jax.Array  # device [max_seqs + 1, max_blocks_per_seq]
    seq_lens_dev: jax.Array     # device [max_seqs + 1]
    seq_blocks: dict            # slot -> list[int]
    quota: int | None = None    # shared pool: this view's block budget
    used_blocks: int = 0
    reserved_blocks: int = 0    # admitted sequences' lifetime reservations
    seq_reserved: dict = dataclasses.field(default_factory=dict)
    seq_shared: dict = dataclasses.field(default_factory=dict)
    # slot -> leading prefix-cache pages attached by refcount (counted once
    # pool-wide: excluded from this view's used/reserved accounting)

    @classmethod
    def create(cls, cfg: ModelConfig, num_blocks: int = 256,
               block_size: int = 16, max_seqs: int = 16,
               max_blocks_per_seq: int = 64, dtype=jnp.float32,
               head_pad: int = 1, mesh=None, kv_spec=None,
               rules: dict | None = None) -> "PagedKVCache":
        """Single-replica cache over a private pool."""
        pool = BlockPool(cfg, num_blocks, block_size, dtype, head_pad,
                         mesh=mesh, kv_spec=kv_spec, rules=rules)
        return cls.from_pool(pool, max_seqs, max_blocks_per_seq, quota=None)

    @classmethod
    def from_pool(cls, pool: BlockPool, max_seqs: int,
                  max_blocks_per_seq: int,
                  quota: int | None = None) -> "PagedKVCache":
        """A replica view over a (possibly shared) pool.

        ``quota`` caps how many pool blocks this view may hold at once; None
        means the whole pool (private-pool behavior).
        """
        cfg = pool.cfg
        L = cfg.n_layers
        ssm = conv = None
        if cfg.has_ssm:
            from repro.models.ssm import conv_channels
            ssm = jnp.zeros((L, max_seqs + 1, cfg.ssm_heads, cfg.ssm_head_dim,
                             cfg.ssm_state), jnp.float32)
            conv = jnp.zeros((L, max_seqs + 1, cfg.ssm_conv_width - 1,
                              conv_channels(cfg)), pool.dtype)
        # device tables start pointing at the trash page so un-admitted /
        # padded rows gather zeros and scatter into the trash page
        table_dev = jnp.full((max_seqs + 1, max_blocks_per_seq),
                             pool.trash_page, jnp.int32)
        lens_dev = jnp.zeros((max_seqs + 1,), jnp.int32)
        if pool.mesh is not None:
            # metadata replicates across the replica mesh; SSM state shards
            # by head (tp) / layer (pp) per the plan rules
            rep = NamedSharding(pool.mesh, P())
            table_dev = jax.device_put(table_dev, rep)
            lens_dev = jax.device_put(lens_dev, rep)
            r = pool.rules
            if ssm is not None:
                ssm = jax.device_put(ssm, NamedSharding(
                    pool.mesh,
                    P(r.get("layers"), None, r.get("ssm_heads"), None, None)))
                conv = jax.device_put(conv, NamedSharding(
                    pool.mesh, P(r.get("layers"), None, None, None)))
        return cls(cfg, pool.block_size, pool.num_blocks, max_seqs,
                   max_blocks_per_seq, pool, ssm, conv,
                   np.zeros((max_seqs, max_blocks_per_seq), np.int32),
                   np.zeros(max_seqs, np.int32),
                   table_dev, lens_dev, {}, quota)

    # -- pool delegation ------------------------------------------------------

    @property
    def k(self) -> jax.Array | None:
        return self.pool.k

    @k.setter
    def k(self, value) -> None:
        self.pool.k = value

    @property
    def v(self) -> jax.Array | None:
        return self.pool.v

    @v.setter
    def v(self, value) -> None:
        self.pool.v = value

    @property
    def allocator(self) -> BlockAllocator:
        return self.pool.allocator

    @property
    def n_free_blocks(self) -> int:
        """Blocks this view may still *reserve* (quota- and pool-limited).

        ``pinned`` blocks (multi-owner shared prefix pages) sit outside
        every sequence reservation but still occupy physical capacity, so
        they come off the pool headroom; *cold* cached pages do not — they
        are evicted on demand (``BlockPool.reclaim``), which is exactly how
        the prefix cache oversubscribes HBM.
        """
        n = (self.pool.num_blocks - self.pool.reserved
             - self.pool.allocator.pinned)
        if self.quota is not None:
            n = min(n, self.quota - self.reserved_blocks)
        return n

    def _blocks(self, tokens: int) -> int:
        return (tokens + self.block_size - 1) // self.block_size

    @property
    def trash_slot(self) -> int:
        """Device table/lens row used to pad decode batches to bucket size."""
        return self.max_seqs

    # -- slot lifecycle -------------------------------------------------------

    def admit(self, slot: int, prompt_len: int,
              total_tokens: int | None = None,
              shared_blocks: tuple | list = (),
              cow_src: int | None = None) -> None:
        """Admit one sequence: allocate its prompt blocks now and *reserve*
        its full lifetime block count (``total_tokens``, defaulting to just
        the prompt) so quota-respecting decode growth can never fail.

        ``shared_blocks`` are prefix-cache pages covering the sequence's
        leading full pages: attached by refcount (``allocator.share``), not
        allocated, and excluded from this view's reservation — a shared page
        costs the pool once no matter how many sequences read it.
        ``cow_src`` names a cached page the sequence diverges *inside*; its
        contents are copied into the first freshly-allocated (private) page
        so writes never touch the shared original.
        """
        n = self._blocks(prompt_len)
        s = len(shared_blocks)
        fresh = n - s
        reserve = max(n, self._blocks(total_tokens or prompt_len)) - s
        self.pool.reclaim(fresh)
        new_blocks = self.allocator.alloc(fresh)
        self.allocator.share(list(shared_blocks))
        if cow_src is not None:
            copy_blocks(self.pool, self.pool, [cow_src], [new_blocks[0]])
        blocks = list(shared_blocks) + new_blocks
        self.used_blocks += fresh
        self.reserved_blocks += reserve
        self.pool.reserved += reserve
        self.seq_reserved[slot] = reserve
        if s:
            self.seq_shared[slot] = s
        self.seq_blocks[slot] = blocks
        self.block_table[slot, :] = 0
        self.block_table[slot, :n] = blocks
        self.seq_lens[slot] = prompt_len
        # incremental device sync: one row scatter per admission
        row = np.full(self.max_blocks_per_seq, self.num_blocks, np.int32)
        row[:n] = blocks
        self.block_table_dev = self.block_table_dev.at[slot].set(
            jnp.asarray(row))
        self.seq_lens_dev = self.seq_lens_dev.at[slot].set(prompt_len)

    def can_admit(self, prompt_len: int, total_tokens: int | None = None,
                  headroom_blocks: int = 2,
                  shared_blocks: tuple | list = ()) -> bool:
        """With ``total_tokens`` (prompt + expected decode growth) the check
        is a firm reservation; without it, legacy prompt + headroom.

        ``shared_blocks`` (prefix-cache pages the admission would attach)
        are already resident, so they shrink the need — but any of them
        still *cold* (single-ref) leaves the evictable set on attach and
        must be paid for out of headroom once, by its first sharer; without
        that term a pool full of hot shared pages could approve more
        reservations than physical blocks can realize."""
        s = len(shared_blocks)
        refs = self.allocator.refs
        pin = sum(1 for b in shared_blocks if refs[b] == 1)
        if total_tokens is not None:
            need = max(self._blocks(prompt_len),
                       self._blocks(total_tokens)) - s + pin
            return self.n_free_blocks >= need
        return (self.n_free_blocks
                >= self._blocks(prompt_len) - s + pin + headroom_blocks)

    def extend(self, slot: int) -> None:
        """Ensure capacity for one more token (``extend_for(slot, 1)``)."""
        self.extend_for(slot, 1)

    def extend_for(self, slot: int, n_tokens: int,
                   sync_device: bool = True) -> tuple | None:
        """Ensure page capacity for the next ``n_tokens`` decode tokens.

        The horizon pre-extend: before a fused multi-step decode dispatch,
        every block the loop will write through the block table (positions
        ``len .. len + n_tokens - 1``) is allocated here in one host pass,
        so the device loop never needs host allocation mid-horizon.  The
        host length advances here (the dispatch is committed — a horizon
        always completes); the device ``seq_lens_dev`` row advances inside
        the fused loop itself, keeping the two in lockstep without
        per-sequence transfers.

        ``sync_device=True`` scatters the new table entries to the device
        mirror immediately; with ``False`` the pending update
        ``(slot, first_col, new_blocks)`` is returned instead (or None),
        so a batch caller can fuse all slots' syncs into ONE device scatter
        via ``apply_table_updates``.
        """
        new_len = int(self.seq_lens[slot]) + n_tokens
        n_have = len(self.seq_blocks[slot])
        need = (new_len + self.block_size - 1) // self.block_size
        update = None
        if need > n_have:
            if need > self.max_blocks_per_seq:
                raise MemoryError("sequence exceeds max_blocks_per_seq")
            # reservations cover only this sequence's *private* pages —
            # shared prefix pages are counted once pool-wide
            s = self.seq_shared.get(slot, 0)
            short = (need - s) - max(self.seq_reserved.get(slot, 0),
                                     n_have - s)
            if short > 0:
                # growth beyond the admission reservation (legacy
                # prompt-only admits): extend the reservation, but never
                # into another view's quota
                if (self.quota is not None
                        and self.reserved_blocks + short > self.quota):
                    raise MemoryError("replica KV quota exceeded")
                if (self.pool.reserved + self.pool.allocator.pinned + short
                        > self.pool.num_blocks):
                    raise MemoryError("KV pool fully reserved")
                self.reserved_blocks += short
                self.pool.reserved += short
                self.seq_reserved[slot] = need - s
            grow = need - n_have
            self.pool.reclaim(grow)
            new_blocks = self.allocator.alloc(grow)
            self.used_blocks += grow
            self.seq_blocks[slot].extend(new_blocks)
            self.block_table[slot, n_have:need] = new_blocks
            if sync_device:
                # incremental sync: one row-slice scatter per page crossing
                self.block_table_dev = self.block_table_dev.at[
                    slot, n_have:need].set(jnp.asarray(new_blocks, jnp.int32))
            else:
                update = (slot, n_have, new_blocks)
        self.seq_lens[slot] = new_len
        return update

    def apply_table_updates(self, updates: list[tuple]) -> None:
        """Fuse deferred ``extend_for`` device syncs into one scatter: the
        whole decode batch's page crossings cost a single dispatch."""
        if not updates:
            return
        rows, cols, vals = [], [], []
        for slot, start, blocks in updates:
            rows.extend([slot] * len(blocks))
            cols.extend(range(start, start + len(blocks)))
            vals.extend(blocks)
        self.block_table_dev = self.block_table_dev.at[
            jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32)].set(
            jnp.asarray(vals, jnp.int32))

    def release_slot(self, slot: int) -> None:
        blocks = self.seq_blocks.pop(slot, [])
        # decref, not free: shared prefix pages (and any page the cache
        # index holds) survive until their last reference drops
        self.allocator.release(blocks)
        s = self.seq_shared.pop(slot, 0)
        self.used_blocks -= len(blocks) - s
        reserve = self.seq_reserved.pop(slot, len(blocks) - s)
        self.reserved_blocks -= reserve
        self.pool.reserved -= reserve
        self.seq_lens[slot] = 0
        self.block_table[slot, :] = 0
        self.block_table_dev = self.block_table_dev.at[slot].set(
            self.num_blocks)
        self.seq_lens_dev = self.seq_lens_dev.at[slot].set(0)

    def release_all(self) -> None:
        """Return every block this view holds to the pool (replica teardown)."""
        for slot in list(self.seq_blocks):
            self.release_slot(slot)

    # -- ownership transfer (page handoff between views) -----------------------

    def disown_slot(self, slot: int) -> tuple[list[int], int]:
        """Remove a sequence from this view's accounting *without* releasing
        its blocks to the allocator.

        Returns ``(blocks, seq_len)``.  The caller now owns the pages (the
        allocator still counts them allocated); they must end in either
        ``adopt_slot`` on a sibling view of the same pool or
        ``release_orphan_blocks``, or the pool leaks.
        """
        blocks = self.seq_blocks.pop(slot)
        seq_len = int(self.seq_lens[slot])
        s = self.seq_shared.pop(slot, 0)
        self.used_blocks -= len(blocks) - s
        reserve = self.seq_reserved.pop(slot, len(blocks) - s)
        self.reserved_blocks -= reserve
        self.pool.reserved -= reserve
        self.seq_lens[slot] = 0
        self.block_table[slot, :] = 0
        self.block_table_dev = self.block_table_dev.at[slot].set(
            self.num_blocks)
        self.seq_lens_dev = self.seq_lens_dev.at[slot].set(0)
        return blocks, seq_len

    def can_adopt(self, n_blocks: int, total_tokens: int,
                  n_shared: int = 0) -> bool:
        return (self.n_free_blocks
                >= max(n_blocks, self._blocks(total_tokens)) - n_shared)

    def adopt_slot(self, slot: int, blocks: list[int], seq_len: int,
                   total_tokens: int | None = None,
                   n_shared: int = 0) -> None:
        """Adopt already-allocated pool blocks into a slot of this view.

        The inverse of ``disown_slot``: block data stays where it is; only
        ownership accounting and the (host + device) block table move.  The
        blocks must belong to this view's pool.  ``n_shared`` leading blocks
        are prefix-cache pages the sequence holds by refcount — counted once
        pool-wide, so they stay out of this view's used/reserved totals.
        """
        n = len(blocks)
        if n > self.max_blocks_per_seq:
            raise MemoryError("adopted sequence exceeds max_blocks_per_seq")
        reserve = max(n, self._blocks(total_tokens or seq_len)) - n_shared
        if not self.can_adopt(n, total_tokens or seq_len, n_shared=n_shared):
            raise MemoryError(
                f"cannot adopt {n} blocks (reserve {reserve}): view has "
                f"{self.n_free_blocks} free")
        self.used_blocks += n - n_shared
        self.reserved_blocks += reserve
        self.pool.reserved += reserve
        self.seq_reserved[slot] = reserve
        if n_shared:
            self.seq_shared[slot] = n_shared
        self.seq_blocks[slot] = list(blocks)
        self.block_table[slot, :] = 0
        self.block_table[slot, :n] = blocks
        self.seq_lens[slot] = seq_len
        row = np.full(self.max_blocks_per_seq, self.num_blocks, np.int32)
        row[:n] = blocks
        self.block_table_dev = self.block_table_dev.at[slot].set(
            jnp.asarray(row))
        self.seq_lens_dev = self.seq_lens_dev.at[slot].set(seq_len)

    # -- device views ----------------------------------------------------------

    def write_prefill(self, slot: int, k_seq: jax.Array, v_seq: jax.Array
                      ) -> None:
        """k_seq/v_seq: [L, S, Hkv, D] from prefill; scattered into pages."""
        S = k_seq.shape[1]
        bs = self.block_size
        n = (S + bs - 1) // bs
        pad = n * bs - S
        dpad = self.k.shape[-1] - k_seq.shape[-1]
        if pad or dpad:
            k_seq = jnp.pad(k_seq, ((0, 0), (0, pad), (0, 0), (0, dpad)))
            v_seq = jnp.pad(v_seq, ((0, 0), (0, pad), (0, 0), (0, dpad)))
        kb = k_seq.reshape(k_seq.shape[0], n, bs, *k_seq.shape[2:])
        vb = v_seq.reshape(v_seq.shape[0], n, bs, *v_seq.shape[2:])
        kb = jnp.swapaxes(kb, 2, 3)          # [L, n, Hkv, bs, D] native
        vb = jnp.swapaxes(vb, 2, 3)
        idx = jnp.asarray(self.seq_blocks[slot], jnp.int32)
        self.k, self.v = _write_pages()(self.k, self.v, idx, kb, vb)

    def write_token(self, slots: np.ndarray, k_new: jax.Array,
                    v_new: jax.Array, positions: np.ndarray) -> None:
        """k_new/v_new: [L, B, Hkv, D] for one token per active slot."""
        blk = self.block_table[slots, positions // self.block_size]
        off = positions % self.block_size
        blk = jnp.asarray(blk)
        off = jnp.asarray(off)
        # pool is [L, P, Hkv, block, D]: non-adjacent advanced indices put
        # the batch dim first, so updates arrive as [B, L, Hkv, D]
        dpad = self.k.shape[-1] - k_new.shape[-1]
        if dpad:
            k_new = jnp.pad(k_new, ((0, 0),) * 3 + ((0, dpad),))
            v_new = jnp.pad(v_new, ((0, 0),) * 3 + ((0, dpad),))
        kv = jnp.moveaxis(k_new, 0, 1).astype(self.k.dtype)
        vv = jnp.moveaxis(v_new, 0, 1).astype(self.v.dtype)
        self.k = self.k.at[:, blk, :, off].set(kv)
        self.v = self.v.at[:, blk, :, off].set(vv)

    def gather_dense(self, slots: np.ndarray, max_len: int
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Materialize [L, B, max_len, Hkv, D] dense caches (legacy
        dense-gather decode path and parity tests only — the serving decode
        path reads pages in place via the block table)."""
        bs = self.block_size
        n_blocks = (max_len + bs - 1) // bs
        table = jnp.asarray(self.block_table[slots, :n_blocks])   # [B, n]
        k = self.k[:, table]          # [L, B, n, Hkv, bs, D]
        v = self.v[:, table]
        L, B = k.shape[0], k.shape[1]
        k = jnp.swapaxes(k, 3, 4)     # [L, B, n, bs, Hkv, D]
        v = jnp.swapaxes(v, 3, 4)
        k = k.reshape(L, B, n_blocks * bs, *k.shape[4:])[:, :, :max_len]
        v = v.reshape(L, B, n_blocks * bs, *v.shape[4:])[:, :, :max_len]
        D = self.cfg.head_dim
        k, v = k[..., :D], v[..., :D]   # drop kernel head_pad columns
        lens = jnp.asarray(self.seq_lens[slots])
        return k, v, lens


def write_pages(k, v, idx, kb, vb):
    """Pages ``idx`` of the pools k/v [L, P, Hkv, bs, D] set to kb/vb."""
    return (k.at[:, idx].set(kb.astype(k.dtype)),
            v.at[:, idx].set(vb.astype(v.dtype)))


@functools.cache
def _write_pages():
    """``write_pages`` jitted with the pools donated off the CPU, so a
    prefill's pages are written in place: an eager scatter would hold a
    second copy of each pool while it runs."""
    donate = (0, 1) if jax.default_backend() != "cpu" else ()
    return jax.jit(write_pages, donate_argnums=donate)


# --------------------------------------------------------------------------
# Pool-to-pool page movement (cross-pool KV migration).
# --------------------------------------------------------------------------


@jax.jit
def _copy_blocks_dev(src_k, src_v, dst_k, dst_v, src_idx, dst_idx):
    dst_k = dst_k.at[:, dst_idx].set(src_k[:, src_idx])
    dst_v = dst_v.at[:, dst_idx].set(src_v[:, src_idx])
    return dst_k, dst_v


def copy_blocks(src: BlockPool, dst: BlockPool,
                src_blocks: list[int], dst_blocks: list[int]) -> None:
    """Jitted page gather/scatter between two pools of the same geometry.

    The index vectors are padded to a power-of-two length against each
    pool's trash page, so the number of distinct compilations is
    O(log max_blocks), not one per migrated sequence size.
    """
    if (src.block_size != dst.block_size
            or src.k.shape[2:] != dst.k.shape[2:]):
        raise ValueError("copy_blocks needs matching page geometry; use "
                         "relayout_blocks")
    n = len(src_blocks)
    if n != len(dst_blocks):
        raise ValueError("src/dst block lists differ in length")
    if n == 0:
        return
    cap = 1 << max(0, n - 1).bit_length()
    src_idx = np.full(cap, src.trash_page, np.int32)
    dst_idx = np.full(cap, dst.trash_page, np.int32)
    src_idx[:n] = src_blocks
    dst_idx[:n] = dst_blocks
    dst.k, dst.v = _copy_blocks_dev(src.k, src.v, dst.k, dst.v,
                                    jnp.asarray(src_idx), jnp.asarray(dst_idx))


def gather_tokens(pool: BlockPool, blocks: list[int], seq_len: int
                  ) -> tuple[jax.Array, jax.Array]:
    """Materialize one sequence's K/V as dense [L, S, Hkv, D] (head_pad
    columns dropped) — the relayout path between mismatched geometries."""
    idx = jnp.asarray(blocks, jnp.int32)
    k = pool.k[:, idx]                       # [L, n, Hkv, bs, D]
    v = pool.v[:, idx]
    L, n, H, bs, D = k.shape
    k = jnp.swapaxes(k, 2, 3).reshape(L, n * bs, H, D)[:, :seq_len]
    v = jnp.swapaxes(v, 2, 3).reshape(L, n * bs, H, D)[:, :seq_len]
    d = pool.cfg.head_dim
    return k[..., :d], v[..., :d]


def scatter_tokens(pool: BlockPool, blocks: list[int],
                   k_seq: jax.Array, v_seq: jax.Array) -> None:
    """Scatter dense [L, S, Hkv, D] K/V into the given pool pages
    (re-chunking to this pool's page size; pads head_dim to its head_pad)."""
    S = k_seq.shape[1]
    bs = pool.block_size
    n = (S + bs - 1) // bs
    if n != len(blocks):
        raise ValueError(f"{S} tokens need {n} blocks, got {len(blocks)}")
    pad = n * bs - S
    dpad = pool.k.shape[-1] - k_seq.shape[-1]
    if pad or dpad:
        k_seq = jnp.pad(k_seq, ((0, 0), (0, pad), (0, 0), (0, dpad)))
        v_seq = jnp.pad(v_seq, ((0, 0), (0, pad), (0, 0), (0, dpad)))
    kb = jnp.swapaxes(k_seq.reshape(k_seq.shape[0], n, bs, *k_seq.shape[2:]),
                      2, 3)
    vb = jnp.swapaxes(v_seq.reshape(v_seq.shape[0], n, bs, *v_seq.shape[2:]),
                      2, 3)
    idx = jnp.asarray(blocks, jnp.int32)
    pool.k = pool.k.at[:, idx].set(kb.astype(pool.k.dtype))
    pool.v = pool.v.at[:, idx].set(vb.astype(pool.v.dtype))


def relayout_blocks(src: BlockPool, dst: BlockPool,
                    src_blocks: list[int], dst_blocks: list[int],
                    seq_len: int) -> None:
    """Move one sequence between pools whose page geometry differs
    (block_size and/or kernel head_pad): dense gather then re-chunked
    scatter, entirely on device."""
    k, v = gather_tokens(src, src_blocks, seq_len)
    scatter_tokens(dst, dst_blocks, k, v)


def reshard_blocks(src: BlockPool, dst: BlockPool,
                   src_blocks: list[int], dst_blocks: list[int],
                   seq_len: int) -> None:
    """Move one sequence between pools that live on *different meshes /
    head shardings* (per-replica sharded serving) — the migration path a
    deployment switch between replicas of unlike (tp, pp) takes.

    The page data rides the existing relayout route: dense gather on the
    source mesh, an explicit cross-mesh ``device_put`` hop onto the
    destination's devices, a KV-head fix when the two replicas run
    different head-padded configs (a padded source keeps its real heads
    first, so the pad columns slice off; a padded destination's extra heads
    are zero rows only padded q heads ever attend), then the re-chunked
    scatter into the destination's (head-sharded) pages.  Zero tokens are
    recomputed — only bytes move.
    """
    k, v = gather_tokens(src, src_blocks, seq_len)
    src_h, dst_h = k.shape[2], dst.cfg.n_kv_heads
    if src_h > dst_h:
        k, v = k[:, :, :dst_h], v[:, :, :dst_h]
    elif src_h < dst_h:
        hp = ((0, 0), (0, 0), (0, dst_h - src_h), (0, 0))
        k, v = jnp.pad(k, hp), jnp.pad(v, hp)
    if dst.mesh is not None:
        tgt = NamedSharding(dst.mesh, P())
    else:
        tgt = jax.devices()[0]
    k, v = jax.device_put(k, tgt), jax.device_put(v, tgt)
    scatter_tokens(dst, dst_blocks, k, v)
