"""What a block keeps beside its pages: the seam between the scheduler
(engine/engine.py) and a second cache.

A block whose layers keep something the page table does not name has a
**kind**: one object, ``TpuEngine.side`` (None for any other block), that
decides when that cache is taken, covered, registered and given back, and
builds the operand its programs take for it (the runner's ``state=``). The
block's module names the kind's class as ``side_cache``
(``model.block_module``); the scheduler builds it with ``(args, page pool)``
and calls it on its own thread at the events below, and nowhere else. The
kinds there are stand below: ``StateSlots`` (``block="sala"``), ``WindowBlocks``
(``block="dots3"``). A kind imports nothing of the scheduler. Of a sequence it
reads ``tokens``, ``prompt_len``, ``stop``, ``block_seq``, ``registered_blocks``,
``kv_written`` and ``prefix_hit_blocks``, and it owns ``seq.side``: its record
of what the sequence holds, set by ``admit``, None again after ``release``.

The events of a residence, in order (``hashes``: the prompt's matchable block
hashes; a position is a token's index in the sequence):

- ``max_hit(hashes) -> (depth, record)``, before pages are allocated: how
  deep a prefix hit may go (``BlockPool.allocate_sequence``'s ``max_hit``).
- ``admit(seq, record, hashes, n_hit)``, after: take what ``seq`` runs in.
  Where it cannot, it gives back what it took, leaves ``seq.side`` None and
  raises NoFreeBlocksError; the scheduler frees the pages.
- ``prefill_rows(rows, Bp, W) -> [Bp, width] | None``: the operand of one
  prefill dispatch of ``Bp`` rows behind a table ``W`` blocks wide; ``rows``
  are its real ones, ``(seq, start, end)`` (a chunk is one row, and its
  program gets row 0).
- ``decode_rows(batch, pos0, B, K, W) -> [B, width] | None``: the operand of
  one decode dispatch of ``K`` steps from ``pos0`` in the ``B``-row program.
- ``release(seq)``: ``seq`` stops running (finished, failed or preempted), its
  sealed blocks registered first. Once a residence.
- ``COUNTERS`` / ``GAUGES`` (name: help; every worker registers every kind's,
  ``KINDS``), ``bind_metrics(gauges)`` (its series at 0 from the start) and
  ``feed(gauges, feed)`` once a scheduler step.
"""

from __future__ import annotations

import numpy as np

from dynamo_tpu.block_manager.pool import BlockPool, NoFreeBlocksError
from dynamo_tpu.ops import dsa
from dynamo_tpu.ops.sparse_attention import SparseSizes, choice_counts


class SideCache:
    """The events one kind answers and the other lets pass."""

    GAUGES: dict[str, str] = {}

    @staticmethod
    def pool_options(args) -> dict:
        """What the page pool is built with for this kind (BlockPool's keywords)."""
        return {}

    def end_wave(self) -> None:
        """Every prefill of an admission wave is dispatched."""

    def registered(self, seq, index: int, block) -> None:
        """``seq``'s sealed block ``index`` was registered with the page pool."""

    def cover_decode(self, seq, first_pos: int, last_pos: int) -> bool:
        """``seq``'s next decode dispatch writes ``[first_pos, last_pos]``;
        False where the kind's pool has no block for them."""
        return True


class _Slots:
    """A running sequence's: the pair its state lives in, the snapshot slot its
    prefill resumes from (0: from zero), the block of the snapshot its own
    prefill took last, where its cached pages ended if deeper than any
    snapshot (tokens; ``pages`` blocks before ``admit``), its length at
    admission and the highest position a dispatch has written state for."""

    __slots__ = ("pair", "src", "chunk", "diverge", "pages", "plen", "hi")

    def __init__(self, src: int, pages: int):
        self.src, self.pages, self.chunk = src, pages, None


class StateSlots(SideCache):
    """A ``block="sala"`` model's: the lightning layers' state slots, which
    ``block_manager/pool.py`` hands out of the page pool (its docstring has the
    mechanism). Here: when a sequence takes its pair and its snapshots, the
    ``state_slots`` rows of engine/sala.py's programs, and the
    ``engine_state_*`` / ``engine_sparse_*`` books."""

    def __init__(self, args, pool: BlockPool):
        self.args, self.pool = args, pool
        self._sparse_sizes = SparseSizes.of(args.model)
        self.stats: dict[str, int] = {"snapshot": 0, "zero": 0, "cached_tokens": 0, "recomputed_tokens": 0,
                                      "chosen": 0, "visible": 0, "dense": 0}

    @staticmethod
    def pool_options(args) -> dict:
        return {"state_slots": args.state_slots}

    def max_hit(self, hashes: list[int]) -> tuple[int, _Slots]:
        # A hit is only as deep as the chain's deepest state snapshot.
        n_pages = len(self.pool.match_prefix(hashes))
        depth, src = self.pool.snapshot_depth(hashes)
        return depth, _Slots(src, n_pages)

    def admit(self, seq, rec: _Slots, hashes: list[int], n_hit: int) -> None:
        bs, plen = self.args.block_size, len(seq.tokens)
        rec.pair = self.pool.acquire_state_pair()
        rec.plen, rec.hi = plen, plen - 1
        rec.diverge = rec.pages * bs if rec.pages > n_hit else 0
        seq.side = rec
        st = self.stats
        st["snapshot" if n_hit else "zero"] += 1
        st["cached_tokens"] += rec.pages * bs
        st["recomputed_tokens"] += (rec.pages - n_hit) * bs

    def _prefill_row(self, seq, start: int, end: int) -> tuple[int, ...]:
        """``seq``'s row of a prefill dispatch over ``[start, end)``
        (engine/sala.py's ``state_slots``): its state is read from the snapshot
        it resumes from at admission's start, from its own pair in a later
        chunk; up to two snapshots are taken: where its cached pages ended (a
        shared prompt's end, which the next to share it resumes from) and at
        the last block the chunk seals, in place of the one its chunk before
        took (a sequence's own older snapshots are worth nothing beside its
        newest)."""
        bs, rec = self.args.block_size, seq.side
        pair = rec.pair
        first = start == seq.prefix_hit_blocks * bs
        src = rec.src if first else pair[((start - 1) // bs) % 2]
        snaps: list[int] = []
        for at in dict.fromkeys((rec.diverge, end // bs * bs)):
            slot = 0
            if start < at <= end:
                sealed = seq.block_seq.blocks[at // bs - 1].sequence_hash
                if at == rec.diverge:  # a branch point: it stays
                    slot = self.pool.take_snapshot(sealed, "chunk_end")
                else:
                    slot = self.pool.take_snapshot(sealed, "chunk_end", replaces=rec.chunk)
                    rec.chunk = sealed if slot else rec.chunk
            snaps += [slot, at - start if slot else 0]
        snaps += [0, 0] * (2 - len(snaps) // 2)
        return (src, pair[((end - 1) // bs) % 2], *snaps)

    def _count_choices(self, lengths, table_blocks: int | None = None) -> None:
        """The sparse counters of one dispatch, whose query positions see
        ``lengths`` positions each: a decode window's, or a prefill's behind
        a page table ``table_blocks`` wide."""
        counts = choice_counts(np.asarray(lengths), self._sparse_sizes, table_blocks)
        for key, n in zip(("chosen", "visible", "dense"), counts):
            self.stats[key] += n

    def prefill_rows(self, rows: list[tuple], Bp: int, W: int) -> np.ndarray:
        state = np.zeros((Bp, 6), np.int32)
        for r, (seq, start, end) in enumerate(rows):
            state[r] = self._prefill_row(seq, start, end)
        self._count_choices(np.concatenate([np.arange(a + 1, e + 1) for _, a, e in rows]), W)
        return state

    def end_wave(self) -> None:
        self.pool.unpin_states()  # every snapshot the wave resumes from has its reader queued

    def decode_rows(self, batch: list, pos0: list[int], B: int, K: int, W: int) -> np.ndarray:
        """The rows' pairs, and the books: how far each row's state has been
        written, the boundaries its steps cross, what its positions choose."""
        bs, max_len = self.args.block_size, self.args.max_model_len
        state = np.zeros((B, 3), np.int32)
        lengths = []
        for i, (seq, p0) in enumerate(zip(batch, pos0)):
            # The last position anyone will want the state after: the token
            # before the last the request may have. A finished sequence's
            # zombie steps past it leave its pair, and its snapshot, alone.
            stop = min(seq.prompt_len + (seq.stop.max_tokens or max_len), max_len) - 2
            rec = seq.side
            state[i] = (*rec.pair, stop)
            last = min(p0 + K - 1, stop)
            rec.hi = max(rec.hi, last)
            self.pool.state_snapshots["decode_boundary"] += sum(
                1 for p in range(p0, last + 1) if p % bs == 0)
            lengths += range(p0 + 1, p0 + K + 1)
        self._count_choices(lengths)
        return state

    def release(self, seq) -> None:
        """The pair goes back, but for the slot that holds the state after
        ``seq``'s last sealed block, which stays as that block's snapshot (the
        blocks the last window sealed are registered by now: a snapshot is
        worth what its block's pages are). That is the slot of block b where
        the state after b's last position was written by this residence (a
        decode step, or the prefill's last position) and no later dispatch, a
        zombie window's among them, has written the slot again:
        ``plen <= (b + 1) bs <= hi + 1`` and ``hi < (b + 2) bs``."""
        rec, seq.side = seq.side, None
        bs, keep = self.args.block_size, None
        n = min(len(seq.tokens), seq.kv_written, seq.registered_blocks * bs)
        b = n // bs - 1
        if (b >= 0 and seq.block_seq is not None
                and rec.plen <= (b + 1) * bs <= rec.hi + 1 and rec.hi < (b + 2) * bs):
            keep = (rec.pair[b % 2], seq.block_seq.blocks[b].sequence_hash)
        self.pool.release_state_pair(rec.pair, keep)

    COUNTERS = {
        "engine_state_snapshots_total":
            "State snapshots taken (block='sala'), by why: chunk_end = a "
            "prefill chunk ended on a block boundary and left a second copy "
            "of its state; decode_boundary = a decode step opened a block and "
            "left the block before's end state behind in the sequence's pair",
        "engine_state_resumes_total":
            "Admissions of a model with a state pool, by where the lightning "
            "layers' state came from: snapshot = a cached block's, zero = "
            "position 0",
        "engine_state_cached_tokens_total":
            "Prompt tokens an admission of such a model found pages for in "
            "the prefix cache, whether or not a snapshot let it start there",
        "engine_state_recomputed_tokens_total":
            "Of those, the tokens past the chain's deepest snapshot: cached "
            "pages the prefill computed again to rebuild the state",
        "engine_state_snapshot_evictions_total":
            "Snapshots evicted by their own LRU to free a state slot",
        "engine_sparse_blocks_chosen_total":
            "Blocks the sparse layers' attention went over, a query position "
            "(prefill tokens and decode steps; every sparse layer and KV head "
            "of a position counts the same, so a position counts once), as "
            "the programs were dispatched: a decode step its chosen table, "
            "the top-k past dense_len and every visible block under it; a "
            "prefill that has a row past dense_len every page of its table's "
            "width, the choice being a mask there, any other its visible "
            "blocks",
        "engine_sparse_blocks_visible_total":
            "Blocks those positions could see (their context in blocks)",
        "engine_sparse_dense_rows_total":
            "Of those positions, the ones at or under dense_len, which took "
            "the dense path",
    }

    def bind_metrics(self, gauges: dict) -> None:
        for origin in ("snapshot", "zero"):
            gauges["engine_state_resumes_total"].inc(0, **{"from": origin})
        for why in self.pool.state_snapshots:
            gauges["engine_state_snapshots_total"].inc(0, why=why)

    def feed(self, gauges: dict, feed) -> None:
        st = self.stats
        gauges["kv_pool_bytes"].set(self.args.state_pool_bytes(), kind="state")
        for origin in ("snapshot", "zero"):
            feed("engine_state_resumes_total", st[origin], **{"from": origin})
        for why, n in self.pool.state_snapshots.items():
            feed("engine_state_snapshots_total", n, why=why)
        feed("engine_state_cached_tokens_total", st["cached_tokens"])
        feed("engine_state_recomputed_tokens_total", st["recomputed_tokens"])
        feed("engine_state_snapshot_evictions_total", self.pool.state_evictions)
        feed("engine_sparse_blocks_chosen_total", st["chosen"])
        feed("engine_sparse_blocks_visible_total", st["visible"])
        feed("engine_sparse_dense_rows_total", st["dense"])


class _Held:
    """A running sequence's: the window pool's block of each of its blocks it
    holds one for (index in the sequence -> block id), which of them it
    claimed as a hit, the deepest of those that another chain branches off
    behind (-1: none), and the block its cached full-layer pages ended at
    (``pages``) where the window blocks before it were gone (``diverge``, else
    0: a shared prompt's end, kept for the next)."""

    __slots__ = ("blocks", "claimed", "branch", "diverge", "pages")

    def __init__(self, pages: int):
        self.pages = pages


class WindowBlocks(SideCache):
    """A ``block="dots3"`` model's: the window layers' pages, blocks of a
    second ``BlockPool`` with a lifetime of its own (``block_manager/pool.py``'s
    docstring has the mechanism). Here: which blocks a sequence holds and how
    each goes back, the window tables of engine/dots3.py's programs (their
    ``state_slots`` rows), and the ``engine_window_*`` / ``engine_dsa_*`` books."""

    def __init__(self, args, pages: BlockPool):
        self.args, self.cfg, self.pages = args, args.model, pages
        self.pool = BlockPool(args.window_blocks, args.block_size, enable_prefix_caching=args.prefix_caching)
        self.stats: dict[str, int] = {"deepest": 0, "cut_back": 0, "miss": 0, "recomputed_tokens": 0,
                                      "chosen": 0, "visible": 0, "dense": 0, "steps": 0, "walk_steps": 0}

    def max_hit(self, hashes: list[int]) -> tuple[int, _Held]:
        # A hit is only as deep as the deepest block whose window blocks are resident.
        n_pages = len(self.pages.match_prefix(hashes))
        return self.pool.window_depth(hashes[:n_pages], self.args.window_back_blocks), _Held(n_pages)

    def admit(self, seq, rec: _Held, hashes: list[int], n_hit: int) -> None:
        bs, plen, n_pages = self.args.block_size, len(seq.tokens), rec.pages
        first = max(0, n_hit - self.args.window_back_blocks)
        rec.blocks = dict(zip(range(first, n_hit), self.pool.claim(hashes[first:n_hit])))
        rec.claimed = set(rec.blocks)
        # The deepest block of the claim that a chain other than this one
        # continues from: a shared prompt's end (a document's, where sessions
        # start over). The blocks up to it go back spared (``_release``).
        rec.branch = max((j for j in range(first, n_hit) if self.pages.hash_fanout(
            hashes[j]) - (j + 1 < n_pages) >= 1), default=-1)
        rec.diverge = n_pages if n_pages > n_hit else 0
        seq.side = rec
        try:  # the first chunk's blocks now: an admission that cannot have them waits
            self._cover(seq, n_hit * bs, min(plen, n_hit * bs + self.args.max_prefill_tokens) - 1)
        except NoFreeBlocksError:
            self._release(seq, list(rec.blocks), final=True)
            seq.side = None
            raise
        if n_pages:
            self.stats["deepest" if n_hit == n_pages else "cut_back" if n_hit else "miss"] += 1
            self.stats["recomputed_tokens"] += (n_pages - n_hit) * bs

    def _cover(self, seq, first_pos: int, last_pos: int, decoding: bool = False) -> None:
        """``seq``'s next dispatch writes positions ``[first_pos, last_pos]``:
        give back the window blocks behind the window of ``first_pos`` (no
        later program of ``seq`` reads them, and the device's stream is serial,
        so whoever gets one writes it after every reader dispatched so far) and
        take blocks up to ``last_pos``'s. The blocks before the end of a shared
        prompt (``diverge``) stay until they are registered, which a
        chunked prefill's are when it is over: given back before, they would
        be free, and the next to share the prompt would find nothing. So does,
        ``decoding``, a block the windows in flight have sealed and no drain has
        registered yet: its tokens are not on the host, so neither is its hash;
        it goes, cached, a window or two later. Raises NoFreeBlocksError where
        the pool cannot give a block."""
        bs, back, rec = self.args.block_size, self.args.window_back_blocks, seq.side
        lo = max(0, first_pos - (self.cfg.sliding_window - 1)) // bs
        held = rec.blocks
        kept = range(max(rec.diverge - back, seq.registered_blocks), rec.diverge)
        self._release(seq, [i for i in held if i < lo and i not in kept
                            and not (decoding and i >= seq.registered_blocks)])
        for i in range(max(lo, max(held, default=-1) + 1), last_pos // bs + 1):
            held[i] = self.pool.allocate_block()

    def _release(self, seq, indices: list[int], final: bool = False) -> None:
        """Give the window blocks at ``indices`` of ``seq`` back: to the warm
        end of the pool's LRU what may be resumed from (everything where the
        sequence stops, ``final``; what it claimed as a hit; the blocks before a
        shared prompt's end), to the cold end what it wrote and passed. What it
        claimed up to a block that another chain continues from
        (``branch``) the pool's eviction spares while anything else is
        left: the boundary a turn resumed from is touched again within a think
        time, a document's only when the next session starts over on it, and
        between two of those the sessions' turns push it out of a plain LRU
        (PERF.md section 6, PR 50)."""
        back, rec = self.args.window_back_blocks, seq.side
        shared = [i for i in indices if i <= rec.branch]
        warm = [i for i in indices if i > rec.branch and (
            final or i in rec.claimed or rec.diverge - back <= i < rec.diverge)]
        cold = [i for i in indices if i not in warm and i not in shared]
        self.pool.free_sequence([rec.blocks.pop(i) for i in cold], cold=True)
        self.pool.free_sequence([rec.blocks.pop(i) for i in warm])
        self.pool.free_sequence([rec.blocks.pop(i) for i in shared], spare=True)
        rec.claimed.difference_update(indices)

    def _row(self, seq, first_pos: int, width: int) -> np.ndarray:
        """``seq``'s window table for a dispatch whose first position is
        ``first_pos`` (engine/dots3.py's ``state_slots`` row): the index of the
        window's first block, then the blocks from it on."""
        lo = max(0, first_pos - (self.cfg.sliding_window - 1)) // self.args.block_size
        row = np.zeros((1 + width,), np.int32)
        row[0] = lo
        for i, bid in seq.side.blocks.items():
            if 0 <= i - lo < width:
                row[1 + i - lo] = bid
        return row

    def prefill_rows(self, rows: list[tuple], Bp: int, W: int) -> np.ndarray:
        state = np.zeros((Bp, self.args.state_operand_width), np.int32)
        for r, (seq, start, end) in enumerate(rows):
            self._cover(seq, start, end - 1)
            state[r] = self._row(seq, start, self.args.window_prefill_width)
        return state

    def registered(self, seq, index: int, block) -> None:
        if index in seq.side.blocks:
            self.pool.register_block(seq.side.blocks[index], block.sequence_hash, block.parent_sequence_hash)

    def cover_decode(self, seq, first_pos: int, last_pos: int) -> bool:
        try:
            self._cover(seq, first_pos, last_pos, decoding=True)
        except NoFreeBlocksError:
            return False
        return True

    def decode_rows(self, batch: list, pos0: list[int], B: int, K: int, W: int) -> np.ndarray:
        """The rows' window tables (``cover_decode`` has covered them), and what
        the full layers' rows choose and how each step attends it at a table
        of ``W`` blocks: the engine_dsa_* books."""
        state = np.zeros((B, 1 + self.args.window_table_width), np.int32)
        for i, (seq, p0) in enumerate(zip(batch, pos0)):
            state[i] = self._row(seq, p0, self.args.window_table_width)
        ws, topk = self.stats, self.cfg.index_topk
        seen = np.asarray(pos0)[:, None] + np.arange(1, K + 1)[None, :]  # what each row's steps see
        ws["visible"] += int(seen.sum())
        ws["chosen"] += int(np.minimum(seen, topk).sum())
        ws["dense"] += int((seen <= topk).sum())
        for lengths in seen.T:  # a step's rows, as the program sees them (engine/dots3.py:decode_step_impl)
            if lengths.max() > topk:
                ws["steps"] += 1
                ws["walk_steps"] += bool(dsa.walk_is_cheaper(lengths, B, W * self.args.block_size, topk))
        return state

    def release(self, seq) -> None:
        """Every window block ``seq`` holds goes back (the sealed ones are
        registered by now), as the boundary its next turn resumes from."""
        self._release(seq, list(seq.side.blocks), final=True)
        seq.side = None

    COUNTERS = {
        "engine_dsa_chosen_tokens_total":
            "Cached tokens the full layers of a block='dots3' model attended, "
            "a decode row and step (every full layer of a step counts the "
            "same, so a step counts once): index_topk where the row sees more, "
            "every visible token where it does not",
        "engine_dsa_visible_tokens_total":
            "Cached tokens those decode rows could see (their context)",
        "engine_dsa_dense_rows_total":
            "Of those decode rows, the ones at or under index_topk visible "
            "tokens, whose choice is every token",
        "engine_dsa_decode_steps_total":
            "Decode steps of a block='dots3' model some row of which saw more "
            "than index_topk tokens: the full layers chose and attended a set",
        "engine_dsa_decode_walk_steps_total":
            "Of those steps, the ones whose chosen sets were attended as a "
            "mask over the walk of the rows' own pages (ops/dsa.py: "
            "walk_is_cheaper of the step's lengths, as the program evaluates "
            "it); the rest gathered their chosen rows",
        "engine_window_blocks_released_total":
            "Window-pool blocks (block='dots3': the window layers' pages) "
            "that sequences gave back, by where they went: cached = sealed and "
            "registered, kept under the chain's hash in the window pool's own "
            "LRU; free = at once",
        "engine_window_resume_total":
            "Admissions of such a model that found cached full-layer pages, "
            "by how deep they could resume: deepest = at the last cached "
            "block (the window blocks before it were resident); cut_back = at "
            "an earlier block; miss = from position 0",
        "engine_window_resume_recomputed_tokens_total":
            "Tokens of cached full-layer pages those admissions computed "
            "again, through every layer, for want of window blocks",
        "engine_window_pool_evictions_total":
            "Cached window-pool blocks evicted for their page",
    }
    GAUGES = {
        "engine_window_pool_used_blocks":
            "Window-pool blocks running sequences hold",
        "engine_window_pool_cached_blocks":
            "Window-pool blocks no sequence holds that stay cached under "
            "their chain's hash (evictable)",
    }

    def bind_metrics(self, gauges: dict) -> None:
        for outcome in ("deepest", "cut_back", "miss"):
            gauges["engine_window_resume_total"].inc(0, outcome=outcome)
        for to in self.pool.released:
            gauges["engine_window_blocks_released_total"].inc(0, to=to)

    def feed(self, gauges: dict, feed) -> None:
        ws, wp = self.stats, self.pool
        gauges["kv_pool_bytes"].set(self.args.window_pool_bytes(), kind="window")
        for outcome in ("deepest", "cut_back", "miss"):
            feed("engine_window_resume_total", ws[outcome], outcome=outcome)
        feed("engine_window_resume_recomputed_tokens_total", ws["recomputed_tokens"])
        for to, n in wp.released.items():
            feed("engine_window_blocks_released_total", n, to=to)
        feed("engine_window_pool_evictions_total", wp.evictions)
        gauges["engine_window_pool_used_blocks"].set(wp.num_active)
        gauges["engine_window_pool_cached_blocks"].set(wp.num_cached)
        feed("engine_dsa_chosen_tokens_total", ws["chosen"])
        feed("engine_dsa_visible_tokens_total", ws["visible"])
        feed("engine_dsa_dense_rows_total", ws["dense"])
        feed("engine_dsa_decode_steps_total", ws["steps"])
        feed("engine_dsa_decode_walk_steps_total", ws["walk_steps"])


KINDS = (StateSlots, WindowBlocks)  # every worker's catalog holds every kind's series
