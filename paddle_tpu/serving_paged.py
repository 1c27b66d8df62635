"""Paged (block-table) KV cache for the continuous-batching engine.

The contiguous engine reserves ``max_slots × max_len`` cache positions
regardless of actual request lengths — one long request dictates every
slot's allocation, and ``ticks_per_sync`` strands up to k−1 positions per
retirement (serving.py documents the waste).  This module replaces the
per-slot rows with the vLLM/"ragged paged attention" discipline (PAPERS.md),
re-shaped for XLA's static-shape model:

- ONE physical pool of ``num_blocks`` fixed-size blocks per layer,
  ``(L, num_blocks + 1, block_size, nh, hd)`` — block 0 is a reserved TRASH
  block that absorbs inactive slots' parked stale writes (never read);
- a per-slot BLOCK TABLE ``(S, max_len // block_size)`` int32 mapping
  logical positions to pool blocks.  The table is a **traced operand**, not
  a program constant: allocation patterns never recompile — decode compiles
  one program per power-of-two LENGTH BUCKET (≤ log2(max_len/block_size)
  programs; see _decode_prog_all), prefill one per prompt bucket;
- blocks are allocated LAZILY, right before each decode sync, so persistent
  HBM scales with tokens actually resident, admission is independent of
  ``max_new_tokens``, and retirement frees every block immediately;
- when the pool runs dry mid-decode the YOUNGEST request is preempted
  (blocks freed, request requeued at the front and rerun from scratch —
  greedy decoding regenerates the identical prefix, so outputs stay
  oracle-exact; streaming callbacks see the replayed tokens again).

Device-side the engine stays a pure serving-layer construct: the decode
program wraps the pool + (length-bucketed, inactive-zeroed) table as a
``PagedKV`` pytree and runs the exact same shared tick as the contiguous
engine — decode_step's layer scan slices pool and table together,
``write_cache`` scatters straight into pool blocks, and
``cached_attention`` densifies ONE layer's table-selected blocks at a
time (a transient ``(S, C·block_size, nh, hd)`` view per layer, where C
is the smallest power-of-two block count covering the deepest active
clock; there is no all-layer view and no scatter-back pass).  The
gather/scatter pattern survives only in the single-slot prefill/segment
programs.  Collapsing the per-layer transient entirely needs a Pallas
paged-attention kernel that walks the table in-kernel (the PAPERS.md
design), the designated TPU hot-path follow-up.

No reference counterpart: the reference snapshot serves static batches only
(SURVEY §2.3); paged serving is beyond-reference capability.
"""

from __future__ import annotations

import collections
import logging
import math
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .serving import ContinuousBatchingEngine
from .jit.bucketing import pow2_bucket, pow2_grid, select_bucket
from .kv_store import KVPage, chain_hex
from .telemetry import (PART_CALL, PART_KEY, PART_OPERANDS, PART_STATS,
                        PHASE_ADMIT, PHASE_DISPATCH, PHASE_PACK, PHASE_SYNC,
                        PHASE_UNPACK)
from .models._decode import (PagedKV, apply_repetition_penalty,
                             build_pools, greedy_verify, seed_presence,
                             spec_leaves, suppress_eos, suppress_eos_rows,
                             tokens_per_row)
from .ops.ragged_paged_attention import grouped_rows

__all__ = ["PagedContinuousBatchingEngine",
           "RaggedPagedContinuousBatchingEngine"]


# ---------------------------------------------------------------------------
# KV-page transport: pool block <-> host page (paddle_tpu/kv_store.py)
# ---------------------------------------------------------------------------
# ONE compiled program per pool-leaf signature for ALL block ids (the id
# is a traced operand, never a static index) — tiering/migration adds a
# fixed pair of tiny programs per engine config, zero per-block families.
# Module-level jit: these live OUTSIDE the engines' program caches, so
# engine compile counters (the zero-in-serve-compile pins) are untouched;
# the kvio warmup task pre-compiles them for warmed engines.

@partial(jax.jit, donate_argnums=(0,))
def _kv_block_put(pool, block, bid):
    """Write one block's content at pool[:, bid] (pool donated — the
    update is in place, no transient pool copy)."""
    return jax.lax.dynamic_update_slice_in_dim(
        pool, block[:, None].astype(pool.dtype), bid, axis=1)


@jax.jit
def _kv_block_get(pool, bid):
    """Read one block's content pool[:, bid] (device-side; the caller
    batches the host fetch across leaves)."""
    return jax.lax.dynamic_slice_in_dim(pool, bid, 1, axis=1)[:, 0]


class PagedContinuousBatchingEngine(ContinuousBatchingEngine):
    """Continuous batching over a paged KV cache (see module docstring).

    ``block_size`` must divide ``max_len`` and every prompt bucket.
    ``num_blocks`` defaults to the contiguous-equivalent pool
    (``max_slots × max_len / block_size``); size it smaller to cap HBM —
    the engine then admits/preempts against the real budget.
    """

    # the bucketed prefill / decode programs below read one K and one V
    # pool; the ragged engine's tick carries whatever the model states
    _KV_PROGRAMS = True

    def __init__(self, model, params, max_slots: int, max_len: int,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 enable_prefix_cache: bool = False, kv_store=None, **kw):
        if kw.get("mesh") is not None:
            raise NotImplementedError(
                "paged engine v1 is single-mesh (TP serving uses the "
                "contiguous engine)")
        self.prefix_caching = bool(enable_prefix_cache)
        # tiered page store (paddle_tpu/kv_store.py): prefix-cache
        # eviction DEMOTES pages into it instead of dropping, and
        # admission lookups that miss HBM RESTORE from it device-side —
        # host-side only, program signatures identical with or without
        if kv_store is not None and not self.prefix_caching:
            raise ValueError(
                "kv_store needs enable_prefix_cache=True — pages are "
                "addressed by prefix-cache chain digests")
        self.model = model             # _require_kv_layout names it
        self.cache_spec = model.cache_spec()
        if self._KV_PROGRAMS:
            self._require_kv_layout(type(self).__name__)
        if kv_store is not None:
            self._require_kv_layout("kv_store")
        self.kv_store = kv_store
        self._kv_meta = None           # kv_page_meta() computes it once
        # a block is ``block_size`` ROWS of every leaf on the table and
        # names ``bs`` POSITIONS: the same number unless the model states
        # a leaf that pages by chunk (``CacheLeaf.tokens_per_row``) — the
        # allocator, the table's width and the pack count in positions
        self.block_rows = int(block_size)
        if self.block_rows < 1:
            raise ValueError("block_size must be >= 1")
        self.bs = self.block_rows * tokens_per_row(self.cache_spec)
        if self.prefix_caching:
            self._require_token_leaves("enable_prefix_cache")
        if max_len % self.bs:
            raise ValueError(f"block_size ({self.bs}) must divide "
                             f"max_len ({max_len})")
        self.MB = max_len // self.bs
        self.NB = (int(num_blocks) if num_blocks is not None
                   else int(max_slots) * self.MB)
        if self.NB < 1:
            raise ValueError("num_blocks must be >= 1")
        super().__init__(model, params, max_slots, max_len, **kw)
        if self.tracer is not None:     # static, so said once, not a tick
            leaves = [leaf.nbytes for leaf in jax.tree.leaves(self.caches)]
            how = {}
            if not self._token_leaves():
                # how each leaf is addressed, where the spec says so
                how["leaf_rows"] = [
                    f"slot/{leaf.slot_rows}" if leaf.slot_rows
                    else f"table/{leaf.tokens_per_row}"
                    for leaf in spec_leaves(self.cache_spec)]
            self.tracer.emit(
                "cache", engine=type(self).__name__,
                layout=self.cache_spec.layout, pool_bytes=sum(leaves),
                leaf_bytes=leaves, **how)      # in the spec's order
        bad = [b for b in self.buckets if b % self.block_rows]
        if bad:
            raise ValueError(f"block_size ({self.block_rows}) must divide "
                             f"every prompt bucket; doesn't divide {bad}")
        # block 0 is trash; real ids are 1..NB
        self._free = list(range(self.NB, 0, -1))      # pop() -> 1, 2, …
        self._table = np.zeros((self.S, self.MB), np.int32)
        self._nblk = np.zeros(self.S, np.int32)       # leading real blocks
        self._admit_seq = np.zeros(self.S, np.int64)  # preemption (LIFO)
        self._seq = 0
        # prefix cache: a block is free / referenced (refs > 0) / CACHED
        # (refs == 0 but registered under its content chain — evictable).
        # Chain key = (pad, padded prompt tokens through this block): the
        # pad length shifts logical positions, so identical token blocks at
        # different pads have different k/v and must not collide.
        self._refs = {}                               # bid -> refcount
        self._prefix_cache = collections.OrderedDict()  # chain -> bid (LRU)
        self._key_of = {}                             # bid -> chain
        # allocator counters live in the per-engine registry (serving.py
        # builds it) so metrics()/prometheus/tick deltas share one source;
        # the public names below stay readable attributes via properties

    _TICK_COUNTERS = (ContinuousBatchingEngine._TICK_COUNTERS
                      + ("blocks_allocated", "blocks_released",
                         "preemptions", "prefix_hits"))

    @property
    def preemptions(self) -> int:
        return int(self._stats.value("preemptions"))

    @property
    def prefix_hits(self) -> int:
        return int(self._stats.value("prefix_hits"))

    @property
    def prefix_blocks_reused(self) -> int:
        return int(self._stats.value("prefix_blocks_reused"))

    @property
    def blocks_high_water(self) -> int:
        return int(self._stats.value("blocks_high_water"))

    def _tick_gauges(self):
        return {"blocks_in_use": self.blocks_in_use}

    # ------------------------------------------------------------ storage --

    def _build_pool(self, spec):
        """Block pools for one model's cache spec (models/_decode.py
        ``CacheSpec``): every leaf it states, as ``(layers, NB + 1,
        block_size) + tail``.  The paged-speculative composition builds a
        second set for the draft — SAME allocator and tables, different
        pool storage."""
        return build_pools(spec, (self.NB + 1, self.block_rows),
                           slots=self.S)

    def _alloc_caches(self):
        return self._build_pool(self.cache_spec)

    def _require_kv_layout(self, what: str):
        """The tiered KV store moves pages of one K and one V entry per
        head; a model that caches anything else is refused by name."""
        if self.cache_spec.layout != "kv":
            raise NotImplementedError(
                f"{what} is written for the K/V cache layout; "
                f"{type(self.model).__name__} caches "
                f"{self.cache_spec.layout!r} leaves (docs/CACHE_SPEC.md)")

    def _token_leaves(self) -> bool:
        """Every leaf one row per position on the block table, and no
        boundary a pack must respect: what the prefix cache and the
        speculative rollback count in."""
        spec = self.cache_spec
        return not spec.row_boundary and all(
            leaf.tokens_per_row == 1 and not leaf.slot_rows
            for leaf in spec_leaves(spec))

    def _require_token_leaves(self, what: str):
        """Prefix sharing and draft verification are keyed on blocks of
        per-token rows; a model with a leaf that does not page, or one that
        pages by chunk, is refused by name."""
        if not self._token_leaves():
            raise NotImplementedError(
                f"{what} is written for leaves of one row per token on the "
                f"block table; {type(self.model).__name__} caches "
                f"{self.cache_spec.layout!r} leaves, of which one does not "
                f"page or pages by chunk (docs/CACHE_SPEC.md)")

    def _paged_sig_suffix(self):
        from .core.flags import flag
        # the kernel-dispatch flags are baked into compiled programs at
        # trace time — key them so set_flags() takes effect on the next
        # program fetch instead of being silently ignored.  ONE helper for
        # every paged signature (the spec composition included): a flag
        # added here reaches all of them
        return ("paged", self.bs, self.NB,
                bool(flag("FLAGS_use_pallas_kernels")),
                bool(flag("FLAGS_paged_attn_interpret")))

    @property
    def _sig(self):
        return (ContinuousBatchingEngine._sig.fget(self)
                + self._paged_sig_suffix())

    # --------------------------------------------------------- allocator --

    @property
    def blocks_in_use(self) -> int:
        return self.NB - len(self._free)

    def _evictable_count(self) -> int:
        """Cached prefix blocks with no live pins — allocatable on demand
        (ONE definition for the allocator, metrics, and the ragged pack
        builder)."""
        return sum(1 for b in self._prefix_cache.values()
                   if self._refs.get(b, 0) == 0)

    def _alloc_blocks(self, n: int):
        """Take ``n`` fresh blocks (refs = 1 each) from the free list,
        evicting least-recently-used UNREFERENCED cached blocks as needed.
        TRANSACTIONAL: returns None (nothing taken) when free + evictable
        can't cover ``n`` — partial growth on a slot that then isn't
        admitted would strand blocks outside every tracked set and
        livelock the preemption loop."""
        if n <= 0:
            return []
        evictable = [c for c, b in self._prefix_cache.items()
                     if self._refs.get(b, 0) == 0]
        if n > len(self._free) + len(evictable):
            return None
        out = []
        ev = iter(evictable)                      # LRU-first (OrderedDict)
        while len(out) < n:
            if self._free:
                out.append(self._free.pop())
            else:
                chain = next(ev)
                bid = self._prefix_cache.pop(chain)
                del self._key_of[bid]
                if self.kv_store is not None:
                    # eviction DEMOTES instead of dropping: the page
                    # moves down the tier ladder (HBM -> DRAM -> disk)
                    self._demote_page(chain, bid)
                out.append(bid)
        for bid in out:
            self._refs[bid] = 1
        self._stats.add("blocks_allocated", len(out))
        return out

    def _pin(self, bid: int):
        """Take one reference on a cached prefix block.  A 0→1 pin is
        allocator TRAFFIC — the block leaves the evictable set — and
        counts ``blocks_allocated``, mirroring ``_release``'s count at
        1→0: ``blocks_allocated == blocks_released`` holds at quiescence
        with prefix hits and cancels interleaved (the fuzz pins it)."""
        self._refs[bid] += 1
        if self._refs[bid] == 1:
            self._stats.add("blocks_allocated")

    def _release(self, bid: int):
        self._refs[bid] -= 1
        if self._refs[bid] == 0:
            self._stats.add("blocks_released")    # unpinned (maybe cached)
            if bid not in self._key_of:
                self._free.append(bid)            # cached blocks linger

    def _ensure_blocks(self, slot: int, upto: int) -> bool:
        """Grow the slot's table to cover logical positions [0, upto);
        transactional via _alloc_blocks."""
        need = -(-int(upto) // self.bs)
        have = int(self._nblk[slot])
        got = self._alloc_blocks(need - have)
        if got is None:
            return False
        for i, bid in enumerate(got):
            self._table[slot, have + i] = bid
        self._nblk[slot] = max(have, need)
        self._stats.set("blocks_high_water", max(self.blocks_high_water,
                                                 self.blocks_in_use))
        return True

    def _free_slot_blocks(self, slot: int):
        n = int(self._nblk[slot])
        for b in self._table[slot, :n][::-1]:
            self._release(int(b))
        self._table[slot] = 0
        self._nblk[slot] = 0

    # ------------------------------------------------------ prefix cache --

    def _chain_keys(self, ids, pad, nblocks):
        """The chain key for each of the first ``nblocks`` prompt blocks:
        a ROLLING blake2b-256 over (pad, tokens through block i).
        O(1)-sized keys and O(P) total work per admission — nested token
        tuples would make every dict operation on the TTFT path re-hash
        the whole prefix (O(P^2) per admission).  blake2b rather than
        sha1: prompt tokens are attacker-controlled in a shared
        multi-tenant cache, and a chosen-prefix sha1 collision would
        silently map one tenant's cached k/v blocks into another's
        attention context (ADVICE r5)."""
        import hashlib

        def h(data):
            return hashlib.blake2b(data, digest_size=32).digest()

        out = []
        digest = h(str(pad).encode())
        for i in range(nblocks):
            block = np.asarray(ids[i * self.bs:(i + 1) * self.bs],
                               np.int64).tobytes()
            digest = h(digest + block)
            out.append(digest)
        return out

    def _lookup_prefix(self, ids, pad, P):
        """Longest cached chain of FULL prompt blocks, capped at
        P/bs - 1 so the last prompt block is always recomputed (its
        forward pass yields the first-token hidden state for free).

        With a ``kv_store`` attached, a chain that misses HBM but hits a
        lower tier (host DRAM / disk) is RESTORED device-side right here
        — before any fill tick — so the caller sees it as a plain HBM
        hit and the admitted request's stream is token-identical to the
        cold-recompute oracle (tests/test_kv_store.py pins it)."""
        chains = self._chain_keys(ids, pad, P // self.bs - 1)
        F, bids = 0, []
        if self.kv_store is None:
            for chain in chains:
                bid = self._prefix_cache.get(chain)
                if bid is None:
                    break
                self._prefix_cache.move_to_end(chain)     # LRU touch
                bids.append(bid)
                F += 1
            return F, bids
        # store-aware walk: a restore ALLOCATES a block, and allocation
        # can evict other refs-0 cached blocks — TEMP-PIN every matched
        # block for the walk's duration so a later restore can never
        # evict an earlier match out from under the caller.  The pins
        # are released before returning (the caller re-pins immediately,
        # single-threaded), and each 0->1/1->0 pair counts allocator
        # traffic symmetrically — blocks_allocated == blocks_released
        # still holds at quiescence (the fuzz pins it).
        held = []
        try:
            for chain in chains:
                bid = self._prefix_cache.get(chain)
                if bid is not None:
                    self._prefix_cache.move_to_end(chain)     # LRU touch
                    self._pin(bid)
                else:
                    bid = self._restore_page(chain)
                    if bid is None:
                        break
                held.append(bid)
                bids.append(bid)
                F += 1
        finally:
            for bid in held:
                self._release(bid)
        return F, bids

    # ---------------------------------------------------- tiered kv store --

    def attach_kv_store(self, store):
        """Attach (or with None detach) a
        :class:`~paddle_tpu.kv_store.TieredKVStore`: prefix-cache
        eviction demotes pages into it, admission lookups restore from
        it, and the gateway's migration path delivers cross-replica
        pages through it (docs/KV_TIERING.md)."""
        if store is not None:
            self._require_kv_layout("attach_kv_store")
        if store is not None and not self.prefix_caching:
            raise ValueError(
                "kv_store needs enable_prefix_cache=True — pages are "
                "addressed by prefix-cache chain digests")
        self.kv_store = store
        return store

    def kv_page_meta(self):
        """Portable page signature (JSON-able): block size, the PROMPT
        BUCKET ladder, and each pool leaf's dtype and per-block shape —
        int8 pools list their fp32 scale planes as just another leaf.
        The bucket ladder matters: chain digests are seeded with the
        bucket-dependent pad, so engines with different ladders derive
        DIFFERENT chains for the same prompt — their pages would never
        restore; carrying the ladder makes the migration dest-picker
        reject the mismatch up front and fall back cleanly.  Two
        engines exchange pages iff their metas match.  Computed ONCE
        (it is a constant of the engine config): restores sit on the
        TTFT-critical admission path, one tree-flatten per block would
        tax exactly what the tier speeds up."""
        self._require_kv_layout("kv_page_meta")
        if self._kv_meta is None:
            leaves, _ = jax.tree.flatten(self.caches)
            self._kv_meta = ["kv1", self.bs, list(self.buckets),
                             [[str(leaf.dtype),
                               [int(leaf.shape[0])]
                               + [int(s) for s in leaf.shape[2:]]]
                              for leaf in leaves]]
        return self._kv_meta

    def _gather_page(self, bid: int):
        """One block's k/v for every pool leaf, device -> host (one
        batched fetch, not one sync per leaf)."""
        leaves, _ = jax.tree.flatten(self.caches)
        vals = [_kv_block_get(leaf, jnp.int32(bid)) for leaf in leaves]
        return tuple(jax.device_get(vals))

    def _scatter_page(self, bid: int, payload):
        """Write one page's leaves into pool block ``bid`` (donated
        in-place updates; ONE fixed program per leaf signature)."""
        leaves, treedef = jax.tree.flatten(self.caches)
        new = [_kv_block_put(leaf, jnp.asarray(arr), jnp.int32(bid))
               for leaf, arr in zip(leaves, payload)]
        self.caches = jax.tree.unflatten(treedef, new)

    def _demote_page(self, chain, bid: int):
        """Move one evicted block's content into the attached store (ONE
        host sync per demotion — the explicit price of keeping the page
        instead of dropping it).  A failing store degrades to the
        pre-store behaviour (page dropped, recompute stays correct)."""
        try:
            page = KVPage(chain, self._gather_page(bid),
                          self.kv_page_meta())
            self.kv_store.put(page)
        except Exception:  # noqa: BLE001 — a broken store must never
            # take the allocator down; dropping the page is always safe
            logging.getLogger(__name__).exception(
                "kv_store demotion failed; page dropped")
            return
        self._stats.add("kvstore_demoted_blocks")
        if self.tracer is not None:
            self.tracer.emit("kvstore", what="demote",
                             chain=chain_hex(chain)[:16],
                             bytes=page.nbytes,
                             engine=type(self).__name__)

    def _restore_page(self, chain) -> Optional[int]:
        """Restore one lower-tier page into a freshly allocated HBM
        block; returns the block id (held at refs=1 by the allocation —
        the caller releases) or None on a store miss / dry pool."""
        page = self.kv_store.lookup(chain, meta=self.kv_page_meta())
        if page is None or isinstance(page.payload, bytes):
            return None
        got = self._alloc_blocks(1)
        if got is None:
            return None          # pool dry: the page stays in the store
        bid = got[0]
        self._scatter_page(bid, page.payload)
        self._prefix_cache[chain] = bid
        self._key_of[bid] = chain
        self._stats.add("kvstore_restored_blocks")
        if self.tracer is not None:
            self.tracer.emit("kvstore", what="restore",
                             chain=chain_hex(chain)[:16],
                             bytes=page.nbytes,
                             engine=type(self).__name__)
        return bid

    def flush_prefix(self) -> int:
        """Demote every UNREFERENCED cached prefix block to the attached
        store and free it from HBM — the operator / bench primitive
        behind the warm-lower-tier A/B (``gpt_kv_tier``) and the smoke
        gate's demote→evict→restore round trip.  Pinned blocks (live
        requests) stay.  Returns the demoted block count."""
        if self.kv_store is None:
            raise ValueError("flush_prefix needs an attached kv_store")
        n = 0
        for chain, bid in list(self._prefix_cache.items()):
            if self._refs.get(bid, 0) != 0:
                continue                   # pinned by a live request
            self._prefix_cache.pop(chain)
            del self._key_of[bid]
            self._demote_page(chain, bid)
            self._free.append(bid)
            n += 1
        return n

    def export_prefix_pages(self, prompt) -> list:
        """The migration source's primitive: the prompt's resident KV
        pages (the bucket's first ``P/bs - 1`` blocks, chain order —
        the cap ``_lookup_prefix`` restores to; the last bucket block is
        always recomputed by the consumer, so its page would only burn
        transfer budget and destination DRAM) as portable
        :class:`~paddle_tpu.kv_store.KVPage` objects.  Walks HBM first,
        then the attached store; stops at the first miss (pages past a
        hole are unreachable by the chain walk anyway).  Empty when
        prefix caching is off or nothing is resident."""
        if not self.prefix_caching:
            return []
        prompt = [int(t) for t in prompt]
        if not prompt:
            return []
        try:
            P = select_bucket(len(prompt), self.buckets)
        except ValueError:
            return []
        pad = P - len(prompt)
        ids = [0] * pad + prompt
        meta = self.kv_page_meta()
        pages = []
        for chain in self._chain_keys(ids, pad,
                                      max(P // self.bs - 1, 0)):
            bid = self._prefix_cache.get(chain)
            if bid is not None:
                pages.append(KVPage(chain, self._gather_page(bid), meta))
                continue
            if self.kv_store is not None:
                page = self.kv_store.lookup(chain, meta=meta)
                if page is not None:
                    pages.append(page)
                    continue
            break
        return pages

    def prefix_index(self):
        """PUBLIC tier map ``{chain_hex: tier}`` (serving.py contract):
        HBM-resident chains first, the attached store's DRAM/disk tiers
        merged under them."""
        idx = {chain_hex(c): "hbm" for c in self._prefix_cache}
        if self.kv_store is not None:
            for c, tier in self.kv_store.index().items():
                idx.setdefault(chain_hex(c), tier)
        return idx

    def prefix_match(self, prompt):
        """PUBLIC tier-aware affinity read (serving.py contract): pure —
        no LRU touch, no pin, no restore.  ``hbm`` counts the leading
        blocks already device-resident; ``total`` counts leading blocks
        resident in ANY tier (a restore away from warm)."""
        out = {"hbm": 0, "total": 0, "tiers": []}
        if not self.prefix_caching:
            return out
        prompt = [int(t) for t in prompt]
        if not prompt:
            return out
        try:
            P = select_bucket(len(prompt), self.buckets)
        except ValueError:
            return out
        pad = P - len(prompt)
        ids = [0] * pad + prompt
        leading_hbm = True
        for chain in self._chain_keys(ids, pad,
                                      max(P // self.bs - 1, 0)):
            if chain in self._prefix_cache:
                tier = "hbm"
            else:
                tier = (self.kv_store.tier_of(chain)
                        if self.kv_store is not None else None)
                if tier is None:
                    break
            if tier != "hbm":
                leading_hbm = False
            if leading_hbm:
                out["hbm"] += 1
            out["total"] += 1
            out["tiers"].append(tier)
        return out

    def _warmup_kvio(self):
        """Compile the page gather/scatter programs (one fixed pair per
        pool-leaf signature, module-level jit cache — NOT engine program
        families): a round trip through the TRASH block, which is never
        read, writing back the very bytes just gathered — live state is
        value-identical.  Warmed engines restore/migrate pages with zero
        in-serve compiles."""
        self._scatter_page(0, self._gather_page(0))

    def _register_prompt_blocks(self, slot, ids, pad, P):
        """Publish the slot's (now content-final) prompt blocks into the
        prefix cache.  Prompt blocks are immutable from here on: buckets
        are block-aligned, so decode growth starts in a FRESH block and
        never writes inside [0, P) — sharing needs no copy-on-write.
        First writer wins on races (a loser's block stays private)."""
        if not self.prefix_caching:
            return
        for i, chain in enumerate(self._chain_keys(ids, pad,
                                                   P // self.bs)):
            bid = int(self._table[slot, i])
            if chain not in self._prefix_cache and \
                    bid not in self._key_of:
                self._prefix_cache[chain] = bid
                self._key_of[bid] = chain

    def _retire(self, slot: int):
        super()._retire(slot)
        self._free_slot_blocks(slot)

    def _release_cancelled_slot(self, slot: int):
        """Cancel's resource seam: release the slot's blocks exactly as
        retirement would — decode growth frees outright, cached prompt
        blocks drop their pin (refcount) and linger evictable, so
        ``blocks_allocated == blocks_released`` holds at quiescence with
        cancels interleaved (the allocator fuzz pins it)."""
        self._free_slot_blocks(slot)
        super()._release_cancelled_slot(slot)

    def _preempt_one(self) -> bool:
        """Evict the YOUNGEST in-flight request (active or still filling),
        free its blocks, and requeue it at the front for a from-scratch
        rerun.  Greedy decoding regenerates the identical prefix, so the
        exactness contract holds; sampled runs redraw from the engine key.

        Streaming consumers see the replayed prefix again: before the
        rerun, ``on_token(request_id, None, False)`` is invoked once as
        the documented replay/reset signal (``token is None`` == discard
        everything streamed for this request so far; see add_request)."""
        cands = [(int(self._admit_seq[s]), s)
                 for s in np.flatnonzero(self._active)]
        cands += [(int(self._admit_seq[s]), s) for s in self._filling]
        if not cands:
            return False
        _, victim = max(cands)
        if victim in self._filling:
            req = self._filling.pop(victim)["req"]
        else:
            req = self._slot_req[victim]
            self._slot_req[victim] = None
            self._active[victim] = False
        req.generated = []
        req.first_token_at = None
        self._queue.insert(0, req)
        self._free_slot_blocks(victim)
        self._stats.add("preemptions")
        if self.tracer is not None:
            self.tracer.request_event(req.id, "preempted",
                                      slot=int(victim))
        if req.on_token is not None:
            try:
                req.on_token(req.id, None, False)      # replay/reset signal
            except Exception:  # noqa: BLE001 — same contract as _record:
                # a user callback must not desync the scheduler
                logging.getLogger(__name__).exception(
                    "on_token replay signal failed for request %d", req.id)
        return True

    # ---------------------------------------------------------- programs --

    def _build_prefill(self, P: int):
        model = self.model
        track = self._track
        V = model.config.vocab_size
        tail = self._first_token_tail()
        bs = self.bs
        nblk = P // bs

        @partial(jax.jit, donate_argnums=(1, 2, 7))
        def run(params, pool_ck, pool_cv, ids, pad_len, blkrow, key,
                presence, slot, planes):
            h, (ck, cv) = model.prefill(params, ids, P,
                                        pad_lens=pad_len[None])

            def put(pool, new):                      # new: (L, 1, P, …)
                r = new.reshape((new.shape[0], nblk, bs) + new.shape[3:])
                return pool.at[:, blkrow].set(r.astype(pool.dtype))

            pool_ck = jax.tree.map(put, pool_ck, ck)
            pool_cv = jax.tree.map(put, pool_cv, cv)
            if track:
                row = seed_presence(ids, V, pad_len[None])
                presence = jax.lax.dynamic_update_slice(
                    presence, row, (slot, 0))
            tok, presence = tail(params, h[:, -1:], presence, slot, key,
                                 planes)
            return pool_ck, pool_cv, tok, presence

        return run

    def _build_seg(self, seg: int, first: bool, last: bool):
        model = self.model
        track = self._track
        V = model.config.vocab_size
        tail = self._first_token_tail()
        bs = self.bs
        suffix_prefill = self._suffix_prefill

        @partial(jax.jit, donate_argnums=(1, 2, 7))
        def run(params, pool_ck, pool_cv, toks, t0, pad, slot, presence,
                key, tabrow, planes):
            h, (pool_ck, pool_cv) = suffix_prefill(
                model, params, (pool_ck, pool_cv), toks, t0, pad, tabrow,
                bs)

            if track:
                if first:
                    presence = jax.lax.dynamic_update_slice(
                        presence, jnp.zeros((1, V), bool), (slot, 0))
                valid = t0 + jnp.arange(seg) >= pad
                row = presence[slot].at[toks[0]].max(valid)
                presence = jax.lax.dynamic_update_slice(
                    presence, row[None], (slot, 0))
            tok = jnp.int32(0)
            if last:
                tok, presence = tail(params, h[:, -1:], presence, slot, key,
                                 planes)
            return pool_ck, pool_cv, tok, presence

        return run

    def _cached_prefill_prog(self, P: int, F: int):
        return self._cached_prog(("cpre", P, F, self._sig),
                                 lambda: self._build_cached_prefill(P, F))

    @staticmethod
    def _suffix_prefill(m, prm, pools, toks, t0, pad, tabrow, bs):
        """ONE model's chunk prefill over its pools: gather the slot's
        table view, embed+decode the ``toks`` (1, n) chunk at positions
        [t0, t0+n) through the chunk path (attending to everything the
        table already holds), scatter the span back.  ``t0`` may be a
        TRACED scalar (segment programs reuse one compilation across
        positions) or static (cached-prefill suffixes).  Shared by the
        plain and speculative cached-prefill AND segment programs so the
        mechanics cannot drift."""
        def take(p):
            g = p[:, tabrow]
            g = g.reshape((g.shape[0], g.shape[1] * g.shape[2])
                          + g.shape[3:])
            return g[:, None]

        ck_s = jax.tree.map(take, pools[0])
        cv_s = jax.tree.map(take, pools[1])
        h = m._embed_chunk(prm, toks[0], t0, pad_lens=pad[None])
        h, (ck_s, cv_s) = m.decode_step(prm, h, (ck_s, cv_s), t0,
                                        pad_lens=pad[None])
        span = t0 + jnp.arange(toks.shape[1])
        pb = tabrow[jnp.minimum(span // bs, tabrow.shape[0] - 1)]
        off = span % bs

        def put(pool, v):
            chunk = v[:, 0, span]
            return pool.at[:, pb, off].set(chunk.astype(pool.dtype))
        return h, (jax.tree.map(put, pools[0], ck_s),
                   jax.tree.map(put, pools[1], cv_s))

    def _build_cached_prefill(self, P: int, F: int):
        """Admission prefill with the first F blocks already cached: embed
        and run ONLY the suffix [F·bs, P) through the chunk-decode path,
        attending to the shared prefix k/v through the slot's table; the
        suffix's last position yields the first-token hidden state.  One
        program per (bucket, F) — the program count stays bounded by
        sum over buckets of P/bs."""
        model = self.model
        track = self._track
        V = model.config.vocab_size
        tail = self._first_token_tail()
        bs = self.bs
        t0 = F * bs
        suffix_prefill = self._suffix_prefill

        @partial(jax.jit, donate_argnums=(1, 2, 7))
        def run(params, pool_ck, pool_cv, ids, pad, tabrow, key, presence,
                slot, planes):
            h, (pool_ck, pool_cv) = suffix_prefill(
                model, params, (pool_ck, pool_cv), ids[:, t0:], t0, pad,
                tabrow, bs)
            if track:
                # the presence row seeds from the FULL prompt — shared
                # prefix tokens count for the repetition penalty too
                row = seed_presence(ids, V, pad[None])
                presence = jax.lax.dynamic_update_slice(
                    presence, row, (slot, 0))
            tok, presence = tail(params, h[:, -1:], presence, slot, key,
                                 planes)
            return pool_ck, pool_cv, tok, presence

        return run

    def _decode_prog_all(self):
        """Decode programs are LENGTH-BUCKETED: each sync gathers only the
        first C table columns — the smallest power-of-two cover of the
        deepest active clock — so the transient view AND the attention
        width scale with actual sequence length, not max_len.  At most
        log2(MB) compiled decode programs."""
        C = self._view_cols()
        return self._cached_prog(("decode", C, self._sig),
                                 lambda: self._build_decode_cols(C))

    def _view_cols(self) -> int:
        k = self.ticks_per_sync
        # active clocks only: parked fillers sit at max_len - k by design
        # and must not inflate the bucket (their writes land in trash
        # regardless of C — the table's parked columns are 0 there)
        ts = self._t[self._active] if self._active.any() else [0]
        need = -(-int(max(ts) + k) // self.bs)
        return pow2_bucket(need, self.MB)

    def _build_decode_cols(self, C: int):
        k_ticks = self.ticks_per_sync
        tick = self._make_decode_tick()
        L = self.model.config.num_layers

        @partial(jax.jit, donate_argnums=(1, 2, 9))
        def run(params, pool_ck, pool_cv, table, toks, ts, pads, active,
                key, presence, emitted0, planes):
            # C table columns cover every active row (host-chosen bucket);
            # INACTIVE rows are pre-zeroed so their parked-clock writes —
            # whose clamped column lookup could alias a filling prompt's
            # real block — land in the trash block instead
            tb = jnp.where(active[:, None], table[:, :C], 0)
            tb = jnp.broadcast_to(tb[None], (L,) + tb.shape)
            pkv_ck = PagedKV(pool_ck, tb)
            pkv_cv = PagedKV(pool_cv, tb)
            # the pool flows through the SAME shared tick as the dense
            # engine: decode_step's layer scan slices pool+table together,
            # write_cache scatters straight into pool blocks, and
            # cached_attention densifies one layer at a time (transient
            # 1/L of the old pre-gathered view; no scatter-back pass)
            (pkv_ck, pkv_cv, _, _, presence), toks_out = jax.lax.scan(
                lambda c, i: tick(c, i, params, ts, pads, active, emitted0,
                                  planes),
                (pkv_ck, pkv_cv, toks, key, presence),
                jnp.arange(k_ticks))
            return pkv_ck.pool, pkv_cv.pool, toks_out, presence

        return run

    # --------------------------------------------------------- scheduling --

    def add_request(self, prompt, max_new_tokens: int, on_token=None,
                    trace_ctx=None, due_at=None, **sampling) -> int:
        """Queue a prompt (the base-engine contract, plus the paged
        engine's preemption semantics).  ``trace_ctx`` threads through to
        the base engine's tracer binding (end-to-end request tracing);
        a preempted request keeps its rid, so its replay events stay on
        the same trace span.

        PREEMPTION AND STREAMING: when the block pool runs dry the
        youngest in-flight request is preempted and rerun from scratch.
        An ``on_token`` consumer is told via a single
        ``on_token(request_id, None, False)`` call — ``token is None`` is
        the documented replay/reset signal: discard everything streamed
        for the request so far; the rerun re-delivers the stream from the
        first token.  Greedy (and deterministic per-request-config) rows
        regenerate the identical prefix; SAMPLED rows redraw from the
        engine key on replay, so a preempted sampling request's rerun is
        a different — still correctly distributed — stream.  Consumers
        needing replay-stable sampled streams should buffer until
        ``done`` or size ``num_blocks`` so preemption cannot occur."""
        prompt_l = [int(t) for t in prompt]
        if prompt_l:
            P = select_bucket(len(prompt_l), self.buckets)
            need = self._positions_needed(P, int(max_new_tokens))
            worst = -(-need // self.bs)
            # a request that exceeds max_len outright belongs to the base
            # validation (its error names the real limit); the pool guard
            # covers only requests the cache COULD hold
            if need <= self.max_len and worst > self.NB:
                raise ValueError(
                    f"request needs up to {worst} blocks; the pool has "
                    f"{self.NB} — raise num_blocks or lower "
                    f"max_new_tokens")
        return super().add_request(prompt_l, max_new_tokens,
                                   on_token=on_token, trace_ctx=trace_ctx,
                                   due_at=due_at, **sampling)

    def _admit(self):
        free = self._free_slots()
        while self._queue and free:
            slot = free[0]
            req = self._queue[0]
            P = select_bucket(len(req.prompt), self.buckets)
            pad = P - len(req.prompt)
            ids = [0] * pad + req.prompt
            chunked = (self.prefill_chunk is not None
                       and P > self.prefill_chunk)
            # prefix-cache path: map the cached chain, compute only the
            # suffix (which also bypasses chunking when the residual work
            # fits one chunk — the head-of-line cost IS the suffix)
            F, hit = (self._lookup_prefix(ids, pad, P)
                      if self.prefix_caching else (0, []))
            suffix = P - F * self.bs
            use_cached = F > 0 and (self.prefill_chunk is None
                                    or suffix <= self.prefill_chunk)
            if use_cached:
                for bid in hit:                   # pin before eviction runs
                    self._pin(bid)
                fresh = self._alloc_blocks(suffix // self.bs)
                if fresh is None:
                    for bid in hit:
                        self._release(bid)
                    break                          # defer admission (FIFO)
                free.pop(0)
                self._queue.pop(0)
                self._seq += 1
                self._admit_seq[slot] = self._seq
                self._table[slot, :F] = hit
                for i, bid in enumerate(fresh):
                    self._table[slot, F + i] = bid
                self._nblk[slot] = P // self.bs
                self._stats.set("blocks_high_water",
                                max(self.blocks_high_water,
                                    self.blocks_in_use))
                self._set_planes(slot, req)
                self._note("prefill_tokens", suffix)
                self._run_cached_prefill(slot, req, P, pad, ids, F)
                self._stats.add("prefix_hits")
                self._stats.add("prefix_blocks_reused", F)
                continue
            # whole-bucket admission needs its P/bs blocks NOW; chunked
            # admission grows per segment.  A dry pool defers admission
            # (FIFO preserved) — decoding slots retire and free blocks.
            if not chunked and not self._ensure_blocks(slot, P):
                break
            free.pop(0)
            self._queue.pop(0)
            self._seq += 1
            self._admit_seq[slot] = self._seq
            self._set_planes(slot, req)
            if chunked:
                # same clock-parking discipline as the contiguous engine;
                # the parked strip's table entry stays at trash (0) while
                # the slot fills, so stale decode writes land in trash
                self._t[slot] = self.max_len - self.ticks_per_sync
                self._filling[slot] = {"req": req, "ids": ids, "pad": pad,
                                       "P": P, "seg": 0,
                                       "nseg": P // self.prefill_chunk}
                continue
            self._note("prefill_tokens", P)
            self._run_admission_prefill(slot, req, P, pad, ids)

    def _run_cached_prefill(self, slot, req, P, pad, ids, F):
        """Prefix-hit admission: compute only the suffix (seam — the
        speculative composition fills BOTH pools' suffixes)."""
        run = self._cached_prefill_prog(P, F)
        ck, cv, tok0, self._presence = run(
            self.params, self.caches[0], self.caches[1],
            jnp.asarray([ids], jnp.int32), jnp.int32(pad),
            jnp.asarray(self._table[slot]), self._next_key(),
            self._presence, jnp.int32(slot), self._plane_operands())
        self.caches = (ck, cv)
        self._register_prompt_blocks(slot, ids, pad, P)
        self._activate(slot, req, P, pad, int(tok0))

    def _run_admission_prefill(self, slot, req, P, pad, ids):
        """Whole-bucket admission prefill for one slot (blocks already
        ensured).  The speculative composition overrides this with its
        dual-pool program; the scheduling loop above stays shared."""
        run = self._prefill_prog(P)
        blkrow = jnp.asarray(self._table[slot, :P // self.bs])
        ck, cv, tok0, self._presence = run(
            self.params, self.caches[0], self.caches[1],
            jnp.asarray([ids], jnp.int32), jnp.int32(pad), blkrow,
            self._next_key(), self._presence, jnp.int32(slot),
            self._plane_operands())
        self.caches = (ck, cv)
        self._register_prompt_blocks(slot, ids, pad, P)
        self._activate(slot, req, P, pad, int(tok0))

    def _fill_segments(self):
        seg = self.prefill_chunk
        for slot, st in list(self._filling.items()):
            if slot not in self._filling:      # preempted below mid-loop
                continue
            i, first = st["seg"], st["seg"] == 0
            last = i == st["nseg"] - 1
            if not self._ensure_blocks(slot, (i + 1) * seg):
                # pool dry: normally this prompt just stalls while decode
                # flows and retirements free blocks — but with NO active
                # decoder nothing will ever free them (fillers jointly
                # wedged); evict the youngest in-flight request so the
                # oldest filler is guaranteed to make progress
                if not self._active.any():
                    self._preempt_one()
                continue
            tok0 = self._run_fill_segment(slot, st, i, first, last)
            self._note("prefill_tokens", seg)
            if last:
                del self._filling[slot]
                self._register_prompt_blocks(slot, st["ids"], st["pad"],
                                             st["P"])
                # the ONLY host-device sync of the whole fill: non-last
                # segments return the device dummy unconverted so segment
                # programs pipeline under async dispatch
                self._activate(slot, st["req"], st["P"], st["pad"],
                               int(tok0))
            else:
                st["seg"] += 1

    def _run_fill_segment(self, slot, st, i, first, last):
        """Run ONE prefill segment's device program (seam — the
        speculative composition fills both pools).  Returns the
        first-token value as a DEVICE array (dummy 0 unless ``last``);
        the fill loop converts once at activation."""
        seg = self.prefill_chunk
        toks = jnp.asarray([st["ids"][i * seg:(i + 1) * seg]], jnp.int32)
        run = self._seg_prog(seg, first, last)
        ck, cv, tok0, self._presence = run(
            self.params, self.caches[0], self.caches[1], toks,
            jnp.int32(i * seg), jnp.int32(st["pad"]), jnp.int32(slot),
            self._presence, self._next_key(),
            jnp.asarray(self._table[slot]), self._plane_operands())
        self.caches = (ck, cv)
        return tok0                        # device value; caller converts

    def _prepare_decode(self) -> bool:
        k = self.ticks_per_sync
        # grow each active slot's table to cover this sync's [t, t+k) span,
        # OLDEST first (preemption victims are youngest-first, so the FIFO
        # head always makes progress — no livelock)
        order = sorted(np.flatnonzero(self._active),
                       key=lambda s: int(self._admit_seq[s]))
        for slot in order:
            while (self._active[slot]
                   and not self._ensure_blocks(int(slot),
                                               int(self._t[slot]) + k)):
                if not self._preempt_one():
                    raise RuntimeError(
                        "block pool exhausted with nothing to preempt")
        return bool(self._active.any())

    def _decode_extra_operands(self):
        return (jnp.asarray(self._table),)

    # ------------------------------------------------------------- warmup --

    def _warmup_tasks(self):
        """Paged grid: the shared prefill/seg enumeration (base class —
        this engine overrides only the dispatch helpers) plus ONE decode
        program per table-width bucket — pow2_grid(MB) is the exact set
        _view_cols can select, so warmup covers every decode width
        serving can dispatch.  Prefix-hit admission families ((bucket,
        depth) cached-prefill programs) are compiled on demand: their
        grid is data-dependent (sum over buckets of P/bs programs) and a
        miss there costs one suffix program, not a storm."""
        from .jit.aot import WarmupTask
        tasks = self._prefill_seg_tasks()
        for C in pow2_grid(self.MB):
            tasks.append(WarmupTask(f"decode:{C}",
                                    partial(self._warmup_decode_cols, C)))
        if self.prefix_caching:
            # kvio rides EVERY prefix-caching grid, not just stores:
            # a store-less prefill-role replica still gathers pages at
            # export time and must not pay that compile in serve
            tasks.append(WarmupTask("kvio", self._warmup_kvio))
        return tasks

    def _warmup_prefill(self, P: int):
        run = self._prefill_prog(P)
        ck, cv = self._alloc_caches()
        jax.block_until_ready(run(
            self.params, ck, cv, jnp.zeros((1, P), jnp.int32),
            jnp.int32(0), jnp.zeros(P // self.bs, jnp.int32),
            self._warmup_key(), self._scratch_presence(), jnp.int32(0),
            self._plane_operands()))

    def _warmup_seg(self, first: bool, last: bool):
        seg = self.prefill_chunk
        run = self._seg_prog(seg, first, last)
        ck, cv = self._alloc_caches()
        jax.block_until_ready(run(
            self.params, ck, cv, jnp.zeros((1, seg), jnp.int32),
            jnp.int32(0), jnp.int32(0), jnp.int32(0),
            self._scratch_presence(), self._warmup_key(),
            jnp.zeros(self.MB, jnp.int32), self._plane_operands()))

    def _warmup_decode_cols(self, C: int):
        run = self._cached_prog(("decode", C, self._sig),
                                lambda: self._build_decode_cols(C))
        ck, cv = self._alloc_caches()
        z = jnp.zeros(self.S, jnp.int32)
        jax.block_until_ready(run(
            self.params, ck, cv, jnp.zeros((self.S, self.MB), jnp.int32),
            z, z, z, jnp.zeros(self.S, bool), self._warmup_key(),
            self._scratch_presence(), z, self._plane_operands()))

    METRICS_SCHEMA = {
        "blocks_in_use": ("gauge", float),
        "blocks_high_water": ("gauge", float),
        "blocks_allocated": ("counter", float),
        "blocks_released": ("counter", float),
        "preemptions": ("counter", float),
        # present only with enable_prefix_cache=True:
        "blocks_cached": ("gauge", float),
        "prefix_hits": ("counter", float),
        "prefix_blocks_reused": ("counter", float),
        # present only with an attached kv_store (tiered page store):
        "kvstore_restored_blocks": ("counter", float),
        "kvstore_demoted_blocks": ("counter", float),
    }

    def metrics(self):
        m = super().metrics()
        m["blocks_in_use"] = float(self.blocks_in_use)
        m["blocks_high_water"] = float(self.blocks_high_water)
        m["blocks_allocated"] = float(self._stats.value("blocks_allocated"))
        m["blocks_released"] = float(self._stats.value("blocks_released"))
        m["preemptions"] = float(self.preemptions)
        if self.prefix_caching:
            m["blocks_cached"] = float(self._evictable_count())
            m["prefix_hits"] = float(self.prefix_hits)
            m["prefix_blocks_reused"] = float(self.prefix_blocks_reused)
        if self.kv_store is not None:
            m["kvstore_restored_blocks"] = float(
                self._stats.value("kvstore_restored_blocks"))
            m["kvstore_demoted_blocks"] = float(
                self._stats.value("kvstore_demoted_blocks"))
        return m


def _packed_shapes(T: int, C: int, S: int):
    """The ragged tick's ONE int32 operand, field by field in the order the
    buffer holds them: tokens, row-to-sequence and row positions (``T``
    rows each), the block table's first ``C`` columns (``S`` x ``C``,
    row-major), then pads, sample rows, sample-active (0/1) and emitted
    counts (``S`` each) — ``3 T + S C + 4 S`` words."""
    return ((T,), (T,), (T,), (S, C), (S,), (S,), (S,), (S,))


def _packed_fields(buf, T: int, C: int, S: int):
    """``buf`` cut into the fields of ``_packed_shapes``.  Both sides of
    the boundary cut with this: a NumPy buffer gives writable views, which
    the host fills; the traced operand gives static slices, which cost the
    program nothing."""
    fields, at = [], 0
    for shape in _packed_shapes(T, C, S):
        fields.append(buf[at:at + math.prod(shape)].reshape(shape))
        at += math.prod(shape)
    return fields


def _pack_operands(T: int, C: int, S: int, *arrays):
    """One round's host arrays as the one buffer ``_packed_fields`` cuts:
    ``arrays`` in its order, each of its field's shape or a scalar to fill
    it with (the mask lands as 0/1)."""
    buf = np.empty(sum(map(math.prod, _packed_shapes(T, C, S))), np.int32)
    for view, src in zip(_packed_fields(buf, T, C, S), arrays, strict=True):
        view[...] = src
    return buf


class RaggedPagedContinuousBatchingEngine(PagedContinuousBatchingEngine):
    """Continuous batching where the WHOLE scheduler tick is ONE compiled
    mixed-batch program (the "ragged paged attention" serving step,
    arxiv 2604.15464 / PAPERS.md).

    The parent engine compiles a prefill program per (bucket, prefix
    depth) plus a separate decode family — prefill and decode tokens can
    never share a step, and every new bucket pays a fresh compile.
    This engine instead packs every step into ONE flattened ragged token
    batch of at most ``token_budget`` rows:

    - every ACTIVE decode slot contributes its 1 next-token row;
    - the remaining budget is filled with admission-prefill chunks
      (oldest request first) at whatever granularity fits — chunking is
      inherent, so there is no ``prefill_chunk`` knob and no per-bucket
      program family;
    - the model runs the pack through ``decode_ragged`` (k/v scattered
      straight into pool blocks, attention via the ragged Pallas kernel
      or its gather fallback), then ONE (S,)-row sampler draws the next
      token for each decode slot and each prompt that completed this
      step.

    Compiled-program count: one program per (token_budget, table-width
    bucket) — at most log2(max_len/block_size) + 1 programs, regardless
    of prompt buckets, prefix depths, or arrival patterns — and ONE
    narrow program more: a round that carries decode rows only (no
    prefill chunk, no verify rows: at most one row a slot) runs the same
    tick built at ``narrow_rows`` rows — ``max_slots`` rounded up to a
    multiple of 8 — where the budget is over twice that and the model
    takes it (``ragged_narrow_rounds``, beside its ``decode_ragged``).
    The host knows what a pack holds before it dispatches, so the row
    count is a key of the program, as the table width is, and never a
    branch inside one.  The narrow program is built at the widest table
    only: the kernels walk a row's own length, so a decode round costs
    the same at any width, and a program per width would cost a compile
    each.  Because only packed rows are computed, there are no parked
    clocks and no inactive-row trash gating.

    The allocator (lazy growth, prefix cache, deferral, youngest-first
    preemption) is inherited unchanged from the paged engine; prompts
    longer than the budget simply span several steps, stalling — not
    failing — when the pool runs dry.  ``ticks_per_sync`` is fixed at 1:
    the budget knob amortizes dispatch instead (one step can carry a
    whole prompt plus every decoder).  Outputs stay oracle-exact vs solo
    ``generate()`` (greedy / deterministic configs), fp32 and int8 pools
    alike.
    """

    _KV_PROGRAMS = False

    def __init__(self, model, params, max_slots: int, max_len: int,
                 token_budget: Optional[int] = None, draft_model=None,
                 draft_params=None, draft_k: int = 4, **kw):
        if kw.get("prefill_chunk") is not None:
            raise ValueError(
                "the ragged engine chunks prefill via token_budget; "
                "prefill_chunk is the bucketed engines' knob")
        if int(kw.pop("ticks_per_sync", 1)) != 1:
            raise NotImplementedError(
                "ragged engine v1 syncs every step — amortize dispatch "
                "with token_budget, not ticks_per_sync")
        if not hasattr(model, "decode_ragged"):
            raise NotImplementedError(
                f"{type(model).__name__} has no decode_ragged path; the "
                f"ragged engine needs the model-side ragged chunk support "
                f"(models/gpt.py) — use PagedContinuousBatchingEngine")
        # ---- speculative decoding INSIDE the ragged tick (ISSUE 13) ----
        # a draft model folds draft proposal + target verification into
        # the SAME one-program-per-(token_budget, table-width) pack; set
        # before super().__init__ — _sig and the program cache key on it
        self.draft_model = draft_model
        self.draft_params = draft_params
        self.K = int(draft_k)
        if draft_model is not None:
            dc = draft_model.config
            if dc.vocab_size != model.config.vocab_size:
                raise ValueError(
                    f"draft vocab ({dc.vocab_size}) != target vocab "
                    f"({model.config.vocab_size})")
            if max_len > dc.max_position_embeddings:
                raise ValueError(
                    f"max_len {max_len} exceeds the DRAFT's "
                    f"max_position_embeddings "
                    f"({dc.max_position_embeddings})")
            if self.K < 1:
                raise ValueError("draft_k must be >= 1")
            if not hasattr(draft_model, "decode_ragged"):
                raise NotImplementedError(
                    f"{type(draft_model).__name__} has no decode_ragged "
                    f"path — the ragged spec step ingests the pack into "
                    f"the draft pool through it")
            if draft_model.cache_spec().layout != "kv":
                raise NotImplementedError(
                    f"the draft's proposal scan reads a K/V pool; "
                    f"{type(draft_model).__name__} caches "
                    f"{draft_model.cache_spec().layout!r} leaves")
            # the greedy speculative contract (models/_decode.py): the
            # acceptance rule compares ARGMAX predictions, so sampling
            # and the logits processors are out of scope — exactly the
            # legacy spec engines' v1 scope, now enforced here
            if kw.get("per_request_sampling"):
                raise NotImplementedError(
                    "ragged speculation is greedy-only; "
                    "per_request_sampling is the plain engines' knob")
            if not kw.get("greedy", True):
                raise NotImplementedError(
                    "ragged speculation is greedy-only (the acceptance "
                    "rule is the longest argmax-matching prefix)")
            if float(kw.get("repetition_penalty", 1.0)) != 1.0 \
                    or int(kw.get("min_new_tokens", 0) or 0) != 0:
                raise NotImplementedError(
                    "ragged speculation does not support "
                    "repetition_penalty/min_new_tokens yet")
        super().__init__(model, params, max_slots, max_len, **kw)
        if draft_model is not None:
            self._require_token_leaves("draft_model")
        rows_per_slot = (self.K + 1) if draft_model is not None else 1
        tb = (int(token_budget) if token_budget is not None
              else int(max_slots) * rows_per_slot + max(self.buckets))
        if tb < int(max_slots):
            raise ValueError(
                f"token_budget ({tb}) must cover every decode slot "
                f"(max_slots={max_slots})")
        self.token_budget = tb
        # the row count of the decode-only rounds' program (0: none).  The
        # fused draft+verify program keeps one width
        narrow = -(-int(max_slots) // 8) * 8
        self.narrow_rows = narrow if (
            draft_model is None and tb > 2 * narrow
            and getattr(model, "ragged_narrow_rounds", True)) else 0
        # per-slot speculation flag (set at admission from the request's
        # effective spec budget) + the add_request validation seam
        self._spec_slot = np.zeros(self.S, bool)
        self._pending_spec: Optional[bool] = None
        if draft_model is not None:
            # the draft keeps its own block POOL but shares the target's
            # tables and allocator: one allocation covers both models'
            # k/v for a position (the paged-spec composition's design,
            # now on the unified engine)
            self.draft_caches = self._build_pool(draft_model.cache_spec())

    @property
    def ragged_steps(self) -> int:
        return int(self._stats.value("ragged_steps"))

    @property
    def narrow_steps(self) -> int:
        """Steps of decode rows only that ran the ``narrow_rows``-row
        program."""
        return int(self._stats.value("narrow_steps"))

    @property
    def mixed_steps(self) -> int:
        """Steps that carried prefill AND decode rows."""
        return int(self._stats.value("mixed_steps"))

    @property
    def spec_rounds(self) -> int:
        """Steps that carried at least one slot's draft+verify rows."""
        return int(self._stats.value("spec_rounds"))

    @property
    def tokens_drafted(self) -> int:
        return int(self._stats.value("tokens_drafted"))

    @property
    def tokens_accepted(self) -> int:
        return int(self._stats.value("tokens_accepted"))

    @property
    def acceptance_rate(self) -> float:
        return self.tokens_accepted / max(self.tokens_drafted, 1)

    @property
    def _sig(self):
        base = PagedContinuousBatchingEngine._sig.fget(self)
        if self.draft_model is None:
            return base
        d = self.draft_model.config
        # the draft's architecture signature rides the program-cache key;
        # _cached_prog additionally pins draft IDENTITY (weakref) — the
        # config tuple alone is not a complete architecture signature
        return base + ("rspec", self.K,
                       (type(self.draft_model).__name__, d.num_layers,
                        d.hidden_size, d.vocab_size,
                        getattr(d, "kv_cache_dtype", None)))

    def _cached_prog(self, cache_key, build):
        """Draft-identity-checked program cache (the legacy spec engines'
        pattern): compiled closures capture the draft model object, so an
        engine over the same target but a different draft instance must
        rebuild, never reuse.  Draft-less engines use the base cache."""
        if self.draft_model is None:
            return super()._cached_prog(cache_key, build)
        import weakref
        progs = self.model.__dict__.setdefault("_serving_programs", {})
        entry = progs.get(cache_key)
        if entry is not None:
            ref, cached = entry
            if ref() is self.draft_model:
                return self._note_prog(cache_key, True, cached)
        run = build()
        # bare program in the cache, wrapper only on the local return
        # (same tracer-lifetime reasoning as the base _cached_prog)
        progs[cache_key] = (weakref.ref(self.draft_model), run)
        return self._note_prog(cache_key, False, run)

    def _positions_needed(self, P: int, mnt: int) -> int:
        spec = (self._pending_spec if self._pending_spec is not None
                else self.draft_model is not None)
        if self.draft_model is not None and spec:
            # budget 1 completes at admission prefill — no round, no
            # slack; otherwise the LAST round can start at t = P + mnt -
            # 2 and write its full K+1-wide verify chunk
            return P if mnt == 1 else P + mnt + self.K - 1
        return super()._positions_needed(P, mnt)

    def add_request(self, prompt, max_new_tokens: int, on_token=None,
                    trace_ctx=None, spec: Optional[bool] = None,
                    due_at=None, **sampling) -> int:
        """The base contract plus the per-request speculative budget:
        ``spec=None`` (default) speculates iff the engine has a draft
        model; ``spec=False`` opts this request out (plain greedy decode
        rows — it shares every tick with speculating neighbours);
        ``spec=True`` requires a draft.  The flag only changes HOW FAST
        the request decodes, never its tokens (greedy contract)."""
        if spec and self.draft_model is None:
            raise ValueError(
                "add_request(spec=True) needs an engine constructed "
                "with draft_model=/draft_params=")
        eff = (self.draft_model is not None) if spec is None else bool(spec)
        self._pending_spec = eff
        try:
            rid = super().add_request(prompt, max_new_tokens,
                                      on_token=on_token,
                                      trace_ctx=trace_ctx, due_at=due_at,
                                      **sampling)
        finally:
            self._pending_spec = None
        self._queue[-1].spec = eff     # base add_request just appended it
        return rid

    def _set_planes(self, slot, req):
        super()._set_planes(slot, req)
        self._spec_slot[slot] = bool(getattr(req, "spec", False))

    # --------------------------------------------------------- scheduling --

    def _admit(self):
        """Admission reserves a slot and (on a prefix hit) pins the cached
        chain — NO device work and NO block allocation happen here; the
        prompt's rows flow into subsequent ragged steps as budget and
        blocks allow."""
        free = self._free_slots()
        while self._queue and free:
            slot = free.pop(0)
            req = self._queue.pop(0)
            P = select_bucket(len(req.prompt), self.buckets)
            pad = P - len(req.prompt)
            ids = [0] * pad + req.prompt
            F, hit = (self._lookup_prefix(ids, pad, P)
                      if self.prefix_caching else (0, []))
            if F:
                for bid in hit:                   # pin before eviction runs
                    self._pin(bid)
                self._table[slot, :F] = hit
                self._nblk[slot] = F
                self._stats.add("prefix_hits")
                self._stats.add("prefix_blocks_reused", F)
            self._seq += 1
            self._admit_seq[slot] = self._seq
            self._set_planes(slot, req)
            self._pad[slot] = pad
            self._t[slot] = 0
            if self._track:
                # presence seeds from the FULL prompt at admission (shared
                # prefix tokens count for the penalty even though their
                # rows are never recomputed) — a host-built row, not a
                # compiled program family
                V = self.model.config.vocab_size
                row = np.zeros((1, V), bool)
                # clip == the device scatter's out-of-vocab clamping
                # (seed_presence); numpy fancy indexing would raise and
                # leave the slot half-admitted
                row[0, np.clip(np.asarray(req.prompt, np.int64),
                               0, V - 1)] = True
                self._presence = jax.lax.dynamic_update_slice(
                    self._presence, jnp.asarray(row), (slot, 0))
            self._filling[slot] = {"req": req, "ids": ids, "pad": pad,
                                   "P": P, "filled": F * self.bs}

    def _build_pack(self):
        """Assemble one step's flattened ragged pack: all active decode
        rows first (block coverage grown via _prepare_decode, preempting
        the youngest when dry), then prefill chunks oldest-first into the
        remaining budget (a dry pool shrinks the chunk — the filler
        stalls while decode retirements free blocks).  Returns None when
        there is nothing to run.

        With a draft model, a speculating slot claims K extra rows right
        after its next-token row — the verify chunk [prev, d_0..d_{K-1}]
        at kv positions [t, t+K].  The draft TOKEN VALUES are filled
        in-program (the host cannot know them); only row metadata is
        packed here.  Speculation is per-slot OPPORTUNISTIC: a tight
        budget, a dry pool, or missing cache room degrades the slot to a
        plain decode row for this step — never stalls it."""
        T = self.token_budget
        if self._active.any():
            self._prepare_decode()        # table growth + preemption loop
        toks = np.zeros(T, np.int32)
        row_seq = np.full(T, -1, np.int32)
        row_pos = np.full(T, -1, np.int32)
        sample_rows = np.zeros(self.S, np.int32)
        sample_active = np.zeros(self.S, bool)
        spec_row0 = np.zeros(self.S, np.int32)
        spec_active = np.zeros(self.S, bool)
        K = self.K if self.draft_model is not None else 0
        n = 0
        dec_slots = []
        act = [int(s) for s in np.flatnonzero(self._active)]
        for idx, slot in enumerate(act):
            toks[n] = self._tok[slot]
            row_seq[n] = slot
            row_pos[n] = self._t[slot]
            sample_rows[slot] = n
            sample_active[slot] = True
            dec_slots.append(slot)
            n += 1
            remaining = len(act) - idx - 1    # slots still owed 1 row
            t = int(self._t[slot])
            if (K and self._spec_slot[slot]
                    and t + K + 1 <= self.max_len
                    and n + K + remaining <= T
                    and self._ensure_blocks(slot, t + K + 1)):
                for j in range(K):
                    row_seq[n] = slot
                    row_pos[n] = t + 1 + j
                    n += 1
                spec_row0[slot] = n - K
                spec_active[slot] = True
        fill_adv = {}
        for slot in sorted(self._filling,
                           key=lambda s: int(self._admit_seq[s])):
            if n >= T:
                break
            st = self._filling[slot]
            want = min(st["P"] - st["filled"], T - n)
            edge = self.cache_spec.row_boundary
            if edge:
                # the model's boundary, counted from the first real row:
                # a pack holds no rows of one sequence from both sides
                done = max(st["filled"] - st["pad"], 0)
                want = min(want, st["pad"] + (done // edge + 1) * edge
                           - st["filled"])
            have = int(self._nblk[slot])
            if have * self.bs < st["filled"] + want:
                # grant what the pool can actually cover in ONE
                # transactional request (one prefix-cache scan, not one
                # per block) — a dry pool shrinks the chunk and the
                # filler stalls while decode retirements free blocks
                grantable = len(self._free) + self._evictable_count()
                need = -(-(st["filled"] + want) // self.bs) - have
                take = min(need, grantable)
                if take > 0:
                    self._ensure_blocks(slot, (have + take) * self.bs)
            m = min(want, int(self._nblk[slot]) * self.bs - st["filled"])
            if m <= 0:
                continue
            for k in range(m):
                toks[n] = st["ids"][st["filled"] + k]
                row_seq[n] = slot
                row_pos[n] = st["filled"] + k
                n += 1
            fill_adv[slot] = m
            if st["filled"] + m == st["P"]:
                # the prompt's last row yields the first-token hidden state
                sample_rows[slot] = n - 1
                sample_active[slot] = True
        if n == 0:
            # jointly wedged fillers with no decoder: nothing will ever
            # free blocks — evict the youngest so the oldest progresses
            # (the chunked-prefill discipline); rows are empty, so no
            # packed state is invalidated by the eviction
            if self._filling and self._preempt_one():
                return self._build_pack()
            return None
        need_cols = -(-(int(row_pos[:n].max()) + 1) // self.bs)
        C = pow2_bucket(need_cols, self.MB)
        if dec_slots and fill_adv:
            self._stats.add("mixed_steps")
        return (toks, row_seq, row_pos, C, sample_rows, sample_active,
                dec_slots, fill_adv, spec_row0, spec_active)

    def _step_impl(self):
        """One scheduler round = ONE device program: admit, pack, run the
        ragged step, unpack sampled tokens (decode slots advance;
        completed prompts activate with their first token).  With a
        draft model the same round runs the fused draft+verify program
        instead — still one compiled program per (token_budget,
        table-width) bucket.  The round is five phases, each bracketed
        by ``tracer.phase`` when a tracer is attached (telemetry.PHASES).

        A plain round crosses to the device once each way: its host
        arrays go in as one int32 buffer (``_packed_fields``), the
        sampling key stays on the device (the tick takes ``self._key``
        and returns the next), and the tokens come back as one vector,
        the model's ``tick_stats`` behind them where it names any.

        Which program a round runs is chosen here, from the pack: one
        with no prefill chunk (and so, without a draft, at most one row a
        slot, packed first) goes to the narrow program, its operands cut
        to ``narrow_rows`` rows, at the widest table; every other pack to
        the (token_budget, C) program."""
        phase = self._phases()
        with phase(PHASE_ADMIT):
            self._admit()
        with phase(PHASE_PACK):
            pack = self._build_pack()
            if pack is None:
                return
            (toks, row_seq, row_pos, C, sample_rows, sample_active,
             dec_slots, fill_adv, spec_row0, spec_active) = pack
            T = self.token_budget
            narrow = bool(self.narrow_rows) and not fill_adv
            if narrow:
                T, C = self.narrow_rows, self.MB
            if self.tracer is not None:
                self._note_pack(dec_slots, fill_adv, spec_active, T)
        if self.draft_model is not None:
            return self._run_spec_pack(phase, toks, row_seq, row_pos, C,
                                       sample_rows, dec_slots, fill_adv,
                                       spec_row0, spec_active)
        with phase(PHASE_DISPATCH) as part:     # three parts partition it
            part(PART_OPERANDS)
            # the round crosses to the chip ONCE: every host array in one
            # buffer (_packed_fields), handed to the call as it is — the
            # call's own argument handling makes the one transfer (a
            # jax.device_put in front of it cost 0.3-0.6 ms a round more
            # on the chip's host: PERF.md section 6, PR 43)
            packed = _pack_operands(
                T, C, self.S, toks[:T], row_seq[:T], row_pos[:T],
                self._table[:, :C], self._pad, sample_rows, sample_active,
                [len(self._slot_req[s].generated) if self._active[s] else 0
                 for s in range(self.S)])
            planes = self._plane_operands()
            part(PART_KEY)
            # the stream's key lives on the device: the tick splits it (as
            # _next_key did, before its own split) and hands the next back
            key = self._key
            part(PART_CALL)
            run = self._ragged_prog(C, T)
            n = len(self.caches)
            out = run(self.params, self.caches, packed, key, self._presence,
                      planes)
            # freed here, as the call's own temporaries were: the phase
            # keeps its extent
            del packed, key, planes
            self.caches, vec, self._presence, self._key = (
                out[:n], out[n], out[n + 1], out[n + 2])
            self._stats.add("ragged_steps")
            if narrow:
                self._stats.add("narrow_steps")
        with phase(PHASE_SYNC) as part:
            # ... and back once: the S tokens, then the model's own
            # counters for this tick where its spec names any
            vec = np.asarray(vec)
            ntok = vec[:self.S]
            names = self.cache_spec.tick_stats
            if names and self.tracer is not None:
                part(PART_STATS)        # the host's side of noting them
                self._tick_note.update(zip(names, vec[self.S:].tolist()))
        with phase(PHASE_UNPACK):
            for slot in dec_slots:
                self._t[slot] += 1
                self._tok[slot] = int(ntok[slot])
                self._record(slot, int(ntok[slot]))
                # room safety net (admission-validated budgets never
                # trigger)
                if self._active[slot] \
                        and int(self._t[slot]) + 1 > self.max_len:
                    self._retire(slot)
            self._advance_fills(fill_adv, ntok)

    def _note_pack(self, dec_slots, fill_adv, spec_active, rows_run):
        """Record the pack on the tick in flight, where it is built
        (``rows_run``: the row count of the program it is dispatched to;
        ``token_budget`` stays the engine's budget, what a round could
        have carried):
        ``rows`` is one ``[rid, rows, kv_end]`` per sequence — a decode
        row ``[rid, 1, t + 1]``, a verify chunk its K + 1 rows, a prefill
        chunk ``[rid, m, last real position + 1]`` (``m`` counts the
        bucket's left-pad rows, which the program runs too) — so the rows
        sum to ``budget_used`` whatever a preemption or a dry pool did to
        the order; ``grouped_rows`` counts those that lie in runs long
        enough for the "kv" kernel's MXU path."""
        K = self.K
        rows = []
        for slot in dec_slots:
            n = K + 1 if spec_active[slot] else 1
            rows.append([self._slot_req[slot].id, n,
                         int(self._t[slot]) + n])
        for slot, m in fill_adv.items():
            st = self._filling[slot]
            rows.append([st["req"].id, m,
                         max(st["filled"] + m - st["pad"], 0)])
        self._tick_note.update(
            decode_rows=len(dec_slots),
            prefill_tokens=int(sum(fill_adv.values())),
            budget_used=sum(r[1] for r in rows),
            token_budget=self.token_budget, rows_run=rows_run, rows=rows)
        # the round's kind, on every span from here on: with a chunk
        # where > 0, decode rows only otherwise
        self.tracer.span_stats(chunk_rows=self._tick_note["prefill_tokens"])
        if self.cache_spec.layout == "kv":
            # how many of them ops/ragged_paged_attention.py takes through
            # the MXU as one operand with their neighbours
            self._tick_note["grouped_rows"] = grouped_rows(rows, rows_run)

    def _advance_fills(self, fill_adv, first_tok):
        """After a step: move every filler on by its chunk; a prompt that
        completed activates with its first token (``first_tok[slot]``)."""
        for slot, m in fill_adv.items():
            st = self._filling[slot]
            st["filled"] += m
            if st["filled"] == st["P"]:
                del self._filling[slot]
                self._register_prompt_blocks(slot, st["ids"], st["pad"],
                                             st["P"])
                self._activate(slot, st["req"], st["P"], st["pad"],
                               int(first_tok[slot]))

    # ---------------------------------------------------------- programs --

    def _ragged_prog(self, C: int, T: Optional[int] = None):
        """ONE program per (token_budget, table-width bucket) — the whole
        mixed admission+decode tick, no per-bucket prefill family — and
        the same tick at ``(narrow_rows, MB)`` for rounds of decode rows
        only."""
        T = self.token_budget if T is None else T
        return self._cached_prog(
            ("ragged_step", T, C, self._sig),
            lambda: self._build_ragged_step(T, C))

    def _build_ragged_step(self, T: int, C: int):
        model = self.model
        track = self._track
        S = self.S
        sample = self._sample
        rp, min_new, eos = self._sample_sig[4:]
        per_request = self.per_request
        row_sample = self._row_sample if per_request else None
        with_stats = bool(self.cache_spec.tick_stats)

        # one int32 operand in (_packed_fields); the outputs are flat — the
        # pools' entries, ONE int32 vector of the S tokens and then the
        # model's tick counters where its spec names any, presence, and
        # the sampling stream's next key
        @partial(jax.jit, donate_argnums=(1, 4))
        def run(params, pools, packed, key, presence, planes):
            (toks, row_seq, row_pos, table, pads, sample_rows, sample_active,
             emitted0) = _packed_fields(packed, T, C, S)
            sample_active = sample_active != 0
            h = model._embed_ragged(params, toks, row_seq, row_pos, pads)
            h, pools, *stats = model.decode_ragged(
                params, h, pools, table, row_seq, row_pos, pads)
            # ONE sampler over S gathered rows: each decode slot's row and
            # each completing prompt's last row (dummy row 0 for the rest
            # — computed, ignored host-side)
            with jax.named_scope("head"):       # logits and the sampler
                h_s = h[0, sample_rows][:, None]        # (S, 1, H)
                l2 = model.decode_logits(params, h_s)[:, -1]
                # the engine's stream, bit for bit: the split _next_key
                # made on the host, then the tick's own
                key, sub = jax.random.split(key)
                _, sub = jax.random.split(sub)
                if per_request:
                    temp, topk, topp, greedy, rpv, mnv, eosv = planes
                    l2 = apply_repetition_penalty(l2, presence, rpv)
                    l2 = suppress_eos_rows(l2, eosv, emitted0 < mnv)
                    ntok = row_sample(l2[:, None, :], sub, temp, topk,
                                      topp, greedy)
                else:
                    if track:
                        l2 = apply_repetition_penalty(l2, presence, rp)
                    if min_new > 0:
                        l2 = suppress_eos(l2, eos, emitted0 < min_new)
                    ntok = sample(l2[:, None, :], sub)
                if track:
                    # prompt tokens were seeded at admission; only SAMPLED
                    # tokens update presence in-program
                    presence = presence.at[jnp.arange(S), ntok].max(
                        sample_active)
            if with_stats:
                ntok = jnp.concatenate([ntok, *stats])
            return (*pools, ntok, presence, key)

        return run

    # ------------------------------------------- speculative ragged step --

    def _run_spec_pack(self, phase, toks, row_seq, row_pos, C,
                       sample_rows, dec_slots, fill_adv, spec_row0,
                       spec_active):
        """Dispatch one fused draft+verify ragged step and unpack: each
        speculating slot advances by its accepted count + 1 (greedy
        contract — outputs equal plain decode by construction), plain
        decode slots and completing prompts advance by their single
        sampled token through the SAME program.  ``phase`` brackets the
        round's last three phases (see ``_step_impl``)."""
        K = self.K
        n_spec = int(spec_active.sum())
        with phase(PHASE_DISPATCH) as part:     # greedy: no key, two parts
            part(PART_OPERANDS)
            operands = (
                jnp.asarray(toks), jnp.asarray(row_seq),
                jnp.asarray(row_pos), jnp.asarray(self._table[:, :C]),
                jnp.asarray(self._pad), jnp.asarray(sample_rows),
                jnp.asarray(spec_row0), jnp.asarray(spec_active),
                jnp.asarray(self._tok), jnp.asarray(self._t))
            part(PART_CALL)
            run = self._ragged_spec_prog(C)
            n = len(self.caches)
            *pools, lead, block = run(
                (self.params, self.draft_params), self.caches,
                self.draft_caches, *operands)
            del operands    # freed here, as the call's temporaries were
            self.caches = tuple(pools[:n])
            self.draft_caches = tuple(pools[n:])
            self._stats.add("ragged_steps")
            if n_spec:
                self._stats.add("spec_rounds")
                self._stats.add("tokens_drafted", n_spec * K)
        with phase(PHASE_SYNC):
            lead = np.asarray(lead)
            block = np.asarray(block)
        with phase(PHASE_UNPACK):
            for slot in dec_slots:
                m = int(lead[slot]) + 1 if spec_active[slot] else 1
                if spec_active[slot]:
                    self._stats.add("tokens_accepted", int(lead[slot]))
                for j in range(m):
                    if not self._active[slot]:
                        break              # retired/cancelled mid-round:
                    self._t[slot] += 1     # discard the round's tail
                    self._tok[slot] = int(block[slot, j])
                    self._record(slot, int(block[slot, j]))
                if self._active[slot]:
                    if int(self._t[slot]) + 1 > self.max_len:
                        self._retire(slot)         # room safety net
                    elif spec_active[slot]:
                        # KV rollback: whole blocks past the accepted
                        # clock held only REJECTED draft pages — return
                        # them to the pool now instead of stranding them
                        # until retirement (self-healing writes make the
                        # next round's fresh blocks safe by construction)
                        self._rollback_blocks(slot)
            # a completing prompt's first token rides block[:, 0] (its
            # lead is 0 through the shared acceptance gather)
            self._advance_fills(fill_adv, block[:, 0])

    def _rollback_blocks(self, slot: int):
        """Free the slot's table columns past the accepted clock — the
        pages that only ever held rejected draft k/v.  Columns holding
        any accepted position are kept; prompt/prefix blocks sit below
        the decode clock and are never touched."""
        keep = -(-int(self._t[slot]) // self.bs)
        have = int(self._nblk[slot])
        if have <= keep:
            return
        for c in range(have - 1, keep - 1, -1):
            self._release(int(self._table[slot, c]))
            self._table[slot, c] = 0
        self._nblk[slot] = keep

    def _ragged_spec_prog(self, C: int):
        """ONE fused draft+verify program per (token_budget, table-width
        bucket) — speculation adds ZERO program families on top of the
        ragged grid (the draft's prompt ingestion rides the same pack)."""
        return self._cached_prog(
            ("ragged_spec", self.token_budget, C, self._sig),
            lambda: self._build_ragged_spec_step(self.token_budget, C))

    def _build_ragged_spec_step(self, T: int, C: int):
        """The whole speculative tick as ONE compiled program: (1) the
        draft proposes K greedy tokens per speculating slot over its
        paged pool (table gated to speculating rows — everyone else's
        writes land in trash); (2) the proposals are scattered into the
        flattened pack at their host-assigned rows; (3) the target runs
        the WHOLE mixed pack (prefill chunks + plain decode rows +
        verify chunks) through decode_ragged; (4) the draft ingests the
        SAME pack — prompt rows keep its pool current (so a draft-less
        admission never exists, and non-spec steps still feed it), and
        the verify rows write d_{K-1}'s k/v (the legacy self-heal, for
        free); (5) greedy verification gathers each slot's K+1 rows and
        applies the shared models/_decode.greedy_verify contract."""
        model, draft = self.model, self.draft_model
        K, S = self.K, self.S
        Ld = draft.config.num_layers

        @partial(jax.jit, donate_argnums=(1, 2))
        def run(params_pair, pools, dpools, toks,
                row_seq, row_pos, table, pads, sample_rows, spec_row0,
                spec_active, dec_tok, dec_t):
            params, dparams = params_pair
            dpool_ck, dpool_cv = dpools     # the draft's layout is "kv"
            # (1) draft proposal scan (S-wide; non-spec rows compute
            # garbage into the trash block via the gated table)
            tb = jnp.where(spec_active[:, None], table, 0)
            tbD = jnp.broadcast_to(tb[None], (Ld,) + tb.shape)
            dkv = (PagedKV(dpool_ck, tbD), PagedKV(dpool_cv, tbD))

            def dstep(carry, i):
                tok, dc = carry
                hh = draft._embed_one(dparams, tok, dec_t + i,
                                      pad_lens=pads)
                hh, dc = draft.decode_step(dparams, hh, dc, dec_t + i,
                                           pad_lens=pads)
                ntok = jnp.argmax(
                    draft.decode_logits(dparams, hh)[:, -1],
                    -1).astype(jnp.int32)
                return (ntok, dc), ntok

            (_, dkv), d = jax.lax.scan(dstep, (dec_tok, dkv),
                                       jnp.arange(K))
            d = d.T                                        # (S, K)
            dpool_ck, dpool_cv = dkv[0].pool, dkv[1].pool
            # (2) scatter proposals into the pack; non-spec rows target
            # index T (out of bounds) and DROP
            drows = jnp.where(spec_active[:, None],
                              spec_row0[:, None] + jnp.arange(K)[None],
                              T)
            toks = toks.at[drows].set(d, mode="drop")
            # (3) one target pass over the whole mixed pack
            h = model._embed_ragged(params, toks, row_seq, row_pos, pads)
            h, pools, *_ = model.decode_ragged(
                params, h, pools, table, row_seq, row_pos, pads)
            # (4) the draft ingests the same pack (prompt currency +
            # d_{K-1} self-heal)
            hd = draft._embed_ragged(dparams, toks, row_seq, row_pos,
                                     pads)
            _, (dpool_ck, dpool_cv) = draft.decode_ragged(
                dparams, hd, (dpool_ck, dpool_cv), table, row_seq,
                row_pos, pads)
            # (5) greedy verification: gather each slot's K+1 rows (non-
            # spec slots gather their single row K+1 times — their lead
            # is forced to 0, so block[:, 0] is plain greedy decode)
            with jax.named_scope("head"):
                grows = sample_rows[:, None] + jnp.arange(K + 1)[None] \
                    * spec_active[:, None].astype(jnp.int32)
                h_s = h[0, grows]                          # (S, K+1, H)
                tpred = jnp.argmax(model.decode_logits(params, h_s),
                                   -1).astype(jnp.int32)   # (S, K+1)
                lead, block = greedy_verify(d, tpred, active=spec_active)
            return (*pools, dpool_ck, dpool_cv, lead, block)

        return run

    # ------------------------------------------------------------- warmup --

    def _warmup_tasks(self):
        """The ragged engine's whole compile grid is ONE program per
        (token_budget, table-width bucket) — pow2_grid(MB) enumerates it
        — and, where the engine has one, the narrow program of the
        decode-only rounds at the widest table, so a warmed engine never
        compiles on the serving path (compile count 0 for ANY arrival
        pattern).  With a draft model the fused draft+verify program
        replaces the plain one bucket for bucket (speculation adds zero
        program families — the draft prefills through the same pack) and
        there is no narrow program."""
        from .jit.aot import WarmupTask
        if self.draft_model is not None:
            tasks = [WarmupTask(f"ragged_spec:{self.token_budget}:{C}",
                                partial(self._warmup_ragged_spec, C))
                     for C in pow2_grid(self.MB)]
        else:
            tasks = [WarmupTask(f"ragged_step:{self.token_budget}:{C}",
                                partial(self._warmup_ragged, C))
                     for C in pow2_grid(self.MB)]
            if self.narrow_rows:
                tasks.append(WarmupTask(
                    f"ragged_step:{self.narrow_rows}:{self.MB}",
                    partial(self._warmup_ragged, self.MB,
                            self.narrow_rows)))
        if self.prefix_caching:
            # same reasoning as the paged grid: export-side gathers on
            # store-less prefill-role replicas are part of the grid too
            tasks.append(WarmupTask("kvio", self._warmup_kvio))
        return tasks

    def _ragged_scratch_args(self, C: int, T: Optional[int] = None):
        """Scratch operand tuple for one table-width bucket's ragged
        program (``T`` rows: the budget, or ``narrow_rows``): fresh pools
        (donated and freed), rows all parked on slot 0 / the trash table
        — values are irrelevant, shapes and dtypes ARE the program
        signature (the purity test lowers through these)."""
        T, S = self.token_budget if T is None else T, self.S
        packed = _pack_operands(     # a host buffer, as a round hands over
            T, C, S, 0, 0, np.minimum(np.arange(T), C * self.bs - 1),
            0, 0, 0, 0, 0)
        return (self.params, self._alloc_caches(), packed,
                self._warmup_key(), self._scratch_presence(),
                self._plane_operands())

    def _warmup_ragged(self, C: int, T: Optional[int] = None):
        run = self._ragged_prog(C, T)
        jax.block_until_ready(run(*self._ragged_scratch_args(C, T)))

    def _ragged_spec_scratch_args(self, C: int):
        """Scratch operands for one fused draft+verify program (fresh
        donated pools for BOTH models; rows parked on slot 0 / trash —
        shapes and dtypes ARE the signature, values are irrelevant)."""
        T, S = self.token_budget, self.S
        z = jnp.zeros(S, jnp.int32)
        return ((self.params, self.draft_params), self._alloc_caches(),
                self._build_pool(self.draft_model.cache_spec()),
                jnp.zeros(T, jnp.int32), jnp.zeros(T, jnp.int32),
                jnp.minimum(jnp.arange(T, dtype=jnp.int32),
                            C * self.bs - 1),
                jnp.zeros((S, C), jnp.int32), z, z, z,
                jnp.zeros(S, bool), z, z)

    def _warmup_ragged_spec(self, C: int):
        run = self._ragged_spec_prog(C)
        jax.block_until_ready(run(*self._ragged_spec_scratch_args(C)))

    _TICK_COUNTERS = (PagedContinuousBatchingEngine._TICK_COUNTERS
                      + ("tokens_drafted", "tokens_accepted"))

    METRICS_SCHEMA = {
        "ragged_steps": ("counter", float),
        "narrow_steps": ("counter", float),
        "mixed_steps": ("counter", float),
        # present only with a draft model (ragged speculation):
        "spec_rounds": ("counter", int),
        "tokens_drafted": ("counter", int),
        "tokens_accepted": ("counter", int),
        "acceptance_rate": ("gauge", float),
        "accepted_tokens_per_s": ("gauge", float),
    }

    def metrics(self):
        m = super().metrics()
        m["ragged_steps"] = float(self.ragged_steps)
        m["narrow_steps"] = float(self.narrow_steps)
        m["mixed_steps"] = float(self.mixed_steps)
        if self.draft_model is not None:
            dt = max(time.monotonic() - self._started, 1e-9)
            m["spec_rounds"] = self.spec_rounds
            m["tokens_drafted"] = self.tokens_drafted
            m["tokens_accepted"] = self.tokens_accepted
            m["acceptance_rate"] = float(self.acceptance_rate)
            m["accepted_tokens_per_s"] = self.tokens_accepted / dt
        return m
