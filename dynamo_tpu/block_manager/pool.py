"""G1 (device/HBM) block pool: allocation, ref-counting, prefix reuse, LRU
eviction, KV event emission.

Reference analogue: lib/llm/src/block_manager/pool.rs:156,457 (active +
inactive pools with sequence-hash reuse matching) and the block lifecycle
Reset→Partial→Complete→Registered (block_manager/block/registry.rs).

States here:

- **free**: on the free list, contents meaningless.
- **active**: ref_count > 0, owned by ≥1 live sequence. A block becomes
  *registered* (hash known, event emitted) once it holds a full block of
  tokens; shared prefix blocks are active with ref_count > 1.
- **cached**: ref_count == 0 but registered — contents retained for
  future prefix hits, evictable LRU-first.

Block id 0 is reserved as the garbage sink for padded writes (model.py
contract) and never allocated.

**A second kind of cache: state slots** (``state_slots`` > 0; a
``block="sala"`` model, engine/sala.py). A lightning layer's state is a matrix
a head, megabytes a sequence, so it cannot ride every block as pages do: the
pool hands out ``state_slots`` slots of the device's state pool (slot 0 is the
sink of padded rows), a **pair** to a running sequence (its live state rests
in ``pair[(position // block_size) % 2]``, so the decode step that opens a
block leaves the block before's end state behind in the other slot) and one to
a **snapshot**, "the state after this sealed block's last token", which a
prefix hit needs beside the block's page (``snapshot_depth``). Snapshots go by
their own LRU (a hit makes one the newest; a branch point, which two chains or
more continue from, goes only when nothing else is left) and with their
block's page; one an admission of this wave resumes from is pinned until the
wave's prefills are dispatched (``unpin_states``): the device's stream is
serial, so what is dispatched later cannot overwrite what an earlier dispatch
still has to read. When each is taken is ``engine/side.py:StateSlots``'s.

**A second lifetime: window blocks** (a ``block="dots3"`` model, engine/dots3.py).
A window layer keeps a sequence's last ``sliding_window`` positions, so its
pages cannot live as long as the sequence does: they are blocks of a second
``BlockPool`` with no event sink (the KV events and the router's view are of
the full-layer chain alone). A block given back (``free_sequence``) stays
cached under its chain's hash if it was registered here, any other is free at
once: at the LRU's warm end, at its cold end (``cold``: the first to go) or
**spared** (``spare``: evicted only when nothing else is left, as a snapshot at
a branch point is; at most a quarter of the pool). A prefix hit is only as
deep as the deepest block that has its full-layer pages **and** whose ``back``
preceding blocks are all cached here (``window_depth``, beside
``snapshot_depth``: one rule, two kinds of second state; the admission claims
those, ``claim``). Which block goes back when and how is
``engine/side.py:WindowBlocks``'s.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable

from dynamo_tpu.kv_router.protocols import KvCacheEvent, StoredBlock

EventSink = Callable[[KvCacheEvent], None]


class NoFreeBlocksError(Exception):
    pass


class _Block:
    __slots__ = ("block_id", "ref_count", "seq_hash", "parent_hash")

    def __init__(self, block_id: int):
        self.block_id = block_id
        self.ref_count = 0
        self.seq_hash: int | None = None
        self.parent_hash: int | None = None


class BlockPool:
    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        event_sink: EventSink | None = None,
        enable_prefix_caching: bool = True,
        state_slots: int = 0,
    ):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_caching = enable_prefix_caching
        self._blocks = [_Block(i) for i in range(num_blocks)]
        self._free: list[int] = list(range(num_blocks - 1, 0, -1))  # pop() → low ids first
        self._cached: dict[int, int] = {}          # seq_hash → block_id (registered)
        self._lru: OrderedDict[int, None] = OrderedDict()  # block_id → None, oldest first
        # Radix fan-out: parent seq_hash → number of REGISTERED children.
        # A hash with >= 2 children is a branch point (shared prefix that
        # several continuations diverge from) — the tier eviction policy
        # protects those blocks from one-off-prompt churn.
        self._children: dict[int, int] = {}
        self._event_sink = event_sink
        self._event_id = 0
        # Mutations run on the engine scheduler thread while snapshot()/
        # metrics run on the asyncio loop thread (kv_events subscribers,
        # load_metrics) — every public method takes this lock.
        self._lock = threading.RLock()
        # prefix-cache observability
        self.hit_blocks = 0
        self.miss_blocks = 0
        # State slots (module docstring): the free ones, the snapshots by
        # block hash (oldest first), those pinned for this wave's prefills.
        self.state_slots = state_slots
        self._state_free: list[int] = list(range(state_slots - 1, 0, -1))
        self._snapshots: OrderedDict[int, int] = OrderedDict()  # seq_hash → slot
        self._state_pinned: set[int] = set()
        self.state_snapshots = {"chunk_end": 0, "decode_boundary": 0}
        self.state_evictions = 0
        # Where the blocks sequences gave back went, and the registered blocks
        # evicted for their page (what a window pool's counters read).
        self.released = {"cached": 0, "free": 0}
        self.evictions = 0
        # Cached blocks the LRU passes over while it holds any other (a window
        # pool's blocks before a shared prompt's end).
        self._spared: set[int] = set()

    # -- events -----------------------------------------------------------

    def set_event_sink(self, sink: EventSink | None) -> None:
        """Late-bind the event sink (workers construct engine-then-
        broadcaster). Events emitted before binding are recoverable via
        snapshot()."""
        self._event_sink = sink

    def _emit(self, event: KvCacheEvent) -> None:
        if self._event_sink is not None:
            self._event_id += 1
            event.event_id = self._event_id
            self._event_sink(event)

    # -- capacity ---------------------------------------------------------

    @property
    def num_free(self) -> int:
        """Blocks obtainable right now (free list + evictable cached)."""
        with self._lock:
            return len(self._free) + len(self._lru)

    @property
    def num_active(self) -> int:
        return self.num_blocks - 1 - self.num_free

    @property
    def usage(self) -> float:
        cap = self.num_blocks - 1
        return self.num_active / cap if cap else 0.0

    @property
    def hit_rate(self) -> float:
        total = self.hit_blocks + self.miss_blocks
        return self.hit_blocks / total if total else 0.0

    # -- allocation -------------------------------------------------------

    def match_prefix(self, seq_hashes: list[int]) -> list[int]:
        """Longest run of leading hashes present in the cache → block ids.
        (Chained hashes: a hit at i implies hits at 0..i-1 had the same
        content, so greedy front-matching is exact.)"""
        if not self.enable_prefix_caching:
            return []
        with self._lock:
            out: list[int] = []
            for h in seq_hashes:
                bid = self._cached.get(h)
                if bid is None:
                    break
                out.append(bid)
            return out

    def allocate_sequence(self, seq_hashes: list[int], total_blocks: int,
                          max_hit: int | None = None) -> tuple[list[int], int]:
        """Allocate ``total_blocks`` for a sequence whose complete-prompt
        block hashes are ``seq_hashes``. Reuses cached prefix blocks, at most
        ``max_hit`` of them (a chain cut back to its deepest state snapshot:
        the blocks past it are computed again, into fresh pages, and count
        as misses).

        → (block_ids, num_hit_blocks). Raises NoFreeBlocksError (nothing
        allocated) if the pool can't satisfy the request."""
        with self._lock:
            hits = self.match_prefix(seq_hashes)[:max_hit]
            need_new = total_blocks - len(hits)
            if need_new > len(self._free) + len(self._lru) - self._lru_overlap(hits):
                raise NoFreeBlocksError(f"need {need_new}, have {self.num_free}")
            # Claim hits first (removes them from the evictable LRU).
            for bid in hits:
                self._ref(bid)
            block_ids = list(hits)
            try:
                for _ in range(need_new):
                    block_ids.append(self._pop_free())
            except NoFreeBlocksError:
                for bid in block_ids:
                    self._unref(bid)
                raise
            self.hit_blocks += len(hits)
            self.miss_blocks += max(0, len(seq_hashes) - len(hits))
            return block_ids, len(hits)

    def allocate_block(self) -> int:
        """One fresh block (decode growth). Raises NoFreeBlocksError."""
        with self._lock:
            return self._pop_free()

    def _lru_overlap(self, hits: list[int]) -> int:
        # hits currently in LRU will leave it on _ref; they don't reduce
        # the evictable supply for the *new* blocks beyond themselves.
        return sum(1 for b in hits if b in self._lru)

    def _pop_free(self) -> int:
        if self._free:
            bid = self._free.pop()
        elif self._lru:
            # Oldest first, a spared one only when nothing else is left.
            bid = next((b for b in self._lru if b not in self._spared), None) if self._spared else None
            if bid is None:
                bid = next(iter(self._lru))
            del self._lru[bid]
            self._evict(bid)
        else:
            raise NoFreeBlocksError("pool exhausted")
        b = self._blocks[bid]
        b.ref_count = 1
        b.seq_hash = None
        b.parent_hash = None
        return bid

    def _evict(self, bid: int) -> None:
        b = self._blocks[bid]
        self._spared.discard(bid)
        if b.seq_hash is not None:
            self.evictions += 1
            self._drop_snapshot(b.seq_hash)
            self._cached.pop(b.seq_hash, None)
            self._emit(KvCacheEvent.removed([b.seq_hash]))
            self._drop_child(b.parent_hash)
            b.seq_hash = None
            b.parent_hash = None

    def _drop_child(self, parent_hash: int | None) -> None:
        if parent_hash is None:
            return
        n = self._children.get(parent_hash, 0) - 1
        if n > 0:
            self._children[parent_hash] = n
        else:
            self._children.pop(parent_hash, None)

    def _ref(self, bid: int) -> None:
        b = self._blocks[bid]
        b.ref_count += 1
        if b.ref_count == 1:
            self._lru.pop(bid, None)
            self._spared.discard(bid)

    def _unref(self, bid: int, cold: bool = False, spare: bool = False) -> None:
        b = self._blocks[bid]
        b.ref_count -= 1
        if b.ref_count > 0:
            return
        if b.seq_hash is not None and self.enable_prefix_caching:
            self._lru[bid] = None  # retained, evictable: the newest, or (cold) the first to go
            self._lru.move_to_end(bid, last=not cold)
            if spare and 4 * len(self._spared) < self.num_blocks:
                self._spared.add(bid)
            self.released["cached"] += 1
        else:
            b.seq_hash = None
            self._free.append(bid)
            self.released["free"] += 1

    # -- window blocks --------------------------------------------------------

    def window_depth(self, seq_hashes: list[int], back: int) -> int:
        """How deep into the chain ``seq_hashes`` (a full-layer hit) a prefix
        hit may go in a model with window layers: the deepest block whose
        ``back`` preceding blocks (all of them, where it has fewer) are cached
        in THIS pool; 0: none, start from zero."""
        with self._lock:
            depth = run = 0
            for i, h in enumerate(seq_hashes):
                run = run + 1 if h in self._cached else 0
                if run >= min(back, i + 1):
                    depth = i + 1
            return depth

    def claim(self, seq_hashes: list[int]) -> list[int]:
        """The cached blocks of ``seq_hashes`` (all cached: ``window_depth``
        said so under the same lock-holder, the scheduler thread), each with
        one more holder."""
        with self._lock:
            bids = [self._cached[h] for h in seq_hashes]
            for bid in bids:
                self._ref(bid)
            return bids

    @property
    def num_cached(self) -> int:
        """Registered blocks no sequence holds (evictable)."""
        with self._lock:
            return len(self._lru)

    # -- state slots --------------------------------------------------------

    def snapshot_depth(self, seq_hashes: list[int]) -> tuple[int, int]:
        """→ (blocks, slot): how deep into the chain of ``seq_hashes`` a
        prefix hit may go, the deepest block that has its page and a state
        snapshot, and the slot that holds it (0, 0: none, start from zero).
        The slot is pinned until ``unpin_states`` and becomes the newest."""
        with self._lock:
            depth, slot = 0, 0
            for i, h in enumerate(seq_hashes):
                if h not in self._cached:
                    break
                if h in self._snapshots:
                    depth, slot = i + 1, self._snapshots[h]
            if depth:
                self._snapshots.move_to_end(seq_hashes[depth - 1])
                self._state_pinned.add(slot)
            return depth, slot

    def _pop_state(self) -> int:
        if self._state_free:
            return self._state_free.pop()
        # Oldest first, a branch point's (a shared prompt's end, which several
        # continuations diverge from) only when nothing else is left.
        for spare_branches in (True, False):
            for h, slot in self._snapshots.items():
                if slot not in self._state_pinned and not (spare_branches and self._children.get(h, 0) >= 2):
                    del self._snapshots[h]
                    self.state_evictions += 1
                    return slot
        raise NoFreeBlocksError("no state slot is free and no snapshot can be evicted")

    def _drop_snapshot(self, seq_hash: int) -> None:
        slot = self._snapshots.get(seq_hash)
        if slot is None or slot in self._state_pinned:
            return  # none, or an admission still reads it: it stays until the LRU takes it
        del self._snapshots[seq_hash]
        self._state_free.append(slot)

    def acquire_state_pair(self) -> tuple[int, int]:
        """Two slots for a sequence that starts to run. Raises
        NoFreeBlocksError (nothing taken) where they cannot be had."""
        with self._lock:
            a = self._pop_state()
            try:
                return a, self._pop_state()
            except NoFreeBlocksError:
                self._state_free.append(a)
                raise

    def take_snapshot(self, seq_hash: int, why: str, replaces: int | None = None) -> int:
        """A slot for the snapshot of the block ``seq_hash``, which the caller's
        next dispatch writes; 0 (the sink: no snapshot) where the block has one
        already or no slot can be had. ``replaces``: the block of the snapshot
        the same prefill took a chunk earlier, which goes (unless it has become
        a branch point or an admission of this wave reads it)."""
        with self._lock:
            if seq_hash in self._snapshots or not self.enable_prefix_caching:
                return 0
            if replaces is not None and self._children.get(replaces, 0) < 2:
                self._drop_snapshot(replaces)
            try:
                slot = self._pop_state()
            except NoFreeBlocksError:
                return 0
            self._snapshots[seq_hash] = slot
            self.state_snapshots[why] += 1
            return slot

    def release_state_pair(self, pair: tuple[int, int], keep: tuple[int, int] | None = None) -> None:
        """A sequence stops running. ``keep`` = (slot of the pair, block hash):
        that slot holds the state after that sealed block and stays as its
        snapshot (unless the block has one); the rest of the pair is free."""
        with self._lock:
            for slot in pair:
                if (keep is not None and slot == keep[0] and keep[1] not in self._snapshots
                        and self.enable_prefix_caching):
                    self._snapshots[keep[1]] = slot
                else:
                    self._state_free.append(slot)

    def unpin_states(self) -> None:
        """The wave's prefills are on the device's queue."""
        with self._lock:
            self._state_pinned.clear()

    @property
    def num_snapshots(self) -> int:
        with self._lock:
            return len(self._snapshots)

    # -- registration (block completion) ----------------------------------

    def register_block(self, bid: int, seq_hash: int, parent_hash: int | None) -> int:
        """A sequence filled this block: record its identity and emit a
        `stored` event. If an identical registered block already exists
        (same hash, concurrent fill), the caller keeps its copy but the
        canonical cache entry stays with the first — returns the canonical
        block id."""
        with self._lock:
            b = self._blocks[bid]
            canonical = self._cached.get(seq_hash)
            if canonical is not None:
                return canonical  # already registered (this block or a twin): no re-emit
            b.seq_hash = seq_hash
            b.parent_hash = parent_hash
            if self.enable_prefix_caching:
                self._cached[seq_hash] = bid
                if parent_hash is not None:
                    self._children[parent_hash] = self._children.get(parent_hash, 0) + 1
                self._emit(KvCacheEvent.stored([StoredBlock(seq_hash, parent_hash)]))
            return bid

    def hash_fanout(self, seq_hash: int) -> int:
        """Registered children of this hash in the radix chain."""
        with self._lock:
            return self._children.get(seq_hash, 0)

    def hash_protected(self, seq_hash: int) -> bool:
        """Should the KV tiers protect this block from churn eviction?
        True for branch points (>= 2 registered children — shared
        prefixes several continuations diverge from, e.g. a system
        prompt) and blocks multiple live sequences currently share."""
        with self._lock:
            if self._children.get(seq_hash, 0) >= 2:
                return True
            bid = self._cached.get(seq_hash)
            return bid is not None and self._blocks[bid].ref_count >= 2

    # -- release ----------------------------------------------------------

    def free_sequence(self, block_ids: list[int], cold: bool = False, spare: bool = False) -> None:
        """Give blocks back. ``cold``: a registered one becomes the first its
        LRU evicts, not the last (a window block its sequence wrote and passed).
        ``spare``: the LRU passes a registered one over while it holds any that
        is not spared (a window block before a shared prompt's end)."""
        with self._lock:
            for bid in block_ids:
                self._unref(bid, cold, spare)

    def snapshot(self) -> list[tuple[int, int | None]]:
        """All currently-registered (hash, parent_hash) pairs in original
        registration order (parents before children — dict insertion
        order). Used to seed a new KV-event subscriber. Thread-safe: may
        be called from the asyncio loop while the engine thread mutates."""
        with self._lock:
            return [(h, self._blocks[bid].parent_hash) for h, bid in self._cached.items()]

    def clear(self) -> int:
        """Drop every cached (ref 0) block — admin /clear_kv_blocks path
        (reference: lib/llm/src/http/service/clear_kv_blocks.rs). Emits a
        `removed` event for exactly the hashes dropped: blocks still
        referenced by running sequences stay registered, so a blanket
        `cleared` would desync remote radix indexers. → count dropped."""
        with self._lock:
            dropped: list[int] = []
            self._spared.clear()
            for bid in list(self._lru):
                self._lru.pop(bid)
                b = self._blocks[bid]
                if b.seq_hash is not None:
                    self._drop_snapshot(b.seq_hash)
                    self._cached.pop(b.seq_hash, None)
                    dropped.append(b.seq_hash)
                    self._drop_child(b.parent_hash)
                    b.seq_hash = None
                    b.parent_hash = None
                self._free.append(bid)
            if dropped:
                self._emit(KvCacheEvent.removed(dropped))
            return len(dropped)
