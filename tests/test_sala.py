"""The MiniCPM-SALA block (ISSUE 45) on the CPU at a toy size with seeded
weights: the program against the benchmark's plain reference (logits, never
tokens), the lightning state through chunks, snapshots, prefix hits, decode
across block boundaries and a preempted sequence's return, the sparse layers'
choice against the reference's, the block manager's snapshot policy, and what
refuses the block.

Tolerances. ``TOL``: float32 on both sides; what differs is the order of sums
(the program's chunked scan and one-step update against the reference's dense
decay-weighted scores; paged against dense attention), so a few float32 ulps of
logits whose largest is about 1: 2e-5. A wrong state, a wrong choice or a
missing compressed key moves a logit by 1e-3 to 1e-1 (the controls below)."""

import asyncio
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model_maps, references
from dynamo_tpu.block_manager.pool import BlockPool, NoFreeBlocksError
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine import sala
from dynamo_tpu.engine.config import EngineArgs
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.ops import lightning, sparse_attention as sparse
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.metrics import MetricsRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "chipbench", "configs", "rehearse-sala-tiny.json")) as f:
    DOC = json.load(f)
DOC = {**DOC, "served": {**DOC["served"], "dtype": "float32"}}
DOC_INT8 = {**DOC, "served": {**DOC["served"], "quant": "int8"}}
CFG = model_maps.model_config(DOC)
REF = references.load("minicpm_sala")
BS = 8
SP = sparse.SparseSizes.of(CFG)
TOL = dict(rtol=0, atol=2e-5)
STATE_TOL = dict(rtol=1e-4, atol=1e-4)  # a state's entries reach tens; float32 sums in another order
TABLE = jnp.arange(1, 17, dtype=jnp.int32)  # 16 blocks: 128 positions


@pytest.fixture(scope="module", autouse=True)
def highest_precision():
    """The CPU's default float32 product is exact enough; this only pins it."""
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return sala.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)


@pytest.fixture(scope="module")
def ref_params():
    return REF.weights(DOC, 0)


def prompt(n: int, seed: int = 0) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(10, CFG.vocab_size, n)]


def fresh_cache(slots: int = 8):
    return sala.init_kv_cache(CFG, 32, BS, jnp.float32, state_slots=slots)


def prefill(params, cache, toks, start: int, upto: int, slots, impl="xla", table=TABLE):
    """Positions [start, upto) of ``toks`` through the single prefill; ``slots`` = (src, dst, snap):
    the snapshot, where one is asked for, is taken at the chunk's last block boundary."""
    t_pad = -(-(upto - start) // 8) * 8
    chunk = jnp.zeros((t_pad,), jnp.int32).at[:upto - start].set(jnp.asarray(toks[start:upto], jnp.int32))
    snap_at = upto // BS * BS - start if slots[2] else 0
    return sala.prefill(CFG, params, cache, chunk, table, start, upto, attn_impl=impl,
                        state_slots=jnp.asarray((*slots, snap_at, 0, 0), jnp.int32))[:2]


def decode(params, cache, toks, first: int, pair, impl="xla", table=TABLE, stop=10**6) -> tuple[list, object]:
    """Teacher-forced decode of ``toks[first:]`` through the cache → logits at each, and the cache
    (``stop``: the last position whose state is wanted)."""
    out = []
    for pos in range(first, len(toks)):
        lg, cache, _ = sala.decode_step(
            CFG, params, cache, jnp.asarray([toks[pos]], jnp.int32), jnp.asarray([pos], jnp.int32),
            table[None], jnp.asarray([True]), attn_impl=impl, state_slots=jnp.asarray([(*pair, stop)], jnp.int32))
        out.append(np.asarray(lg[0]))
    return out, cache


# -- the program against the reference ---------------------------------------------


@pytest.mark.parametrize("quant,impl", [("none", "xla"), ("none", "pallas_interpret"),
                                        ("int8", "xla"), ("int8", "pallas_interpret")])
def test_prefill_then_decode_through_the_cache_gives_the_references_logits(quant, impl):
    """61 tokens prefilled (past dense_len 32, so the later queries choose
    their blocks), 39 decoded through the pages, the compressed keys and the
    state pool, against the reference's one forward pass over all 100: float32
    and weight-only int8 (the same int8 numbers on both sides), the XLA forms
    and the kernels in interpret mode."""
    doc = DOC_INT8 if quant == "int8" else DOC
    weights = sala.init_params(CFG, jax.random.PRNGKey(0), jnp.float32, quant=quant)
    ref_weights = REF.weights(doc, 0)
    for a, b in zip(jax.tree.leaves(weights), jax.tree.leaves(ref_weights)):
        assert a.dtype == b.dtype and bool((a == b).all())  # the benchmark's copy of the initialiser agrees
    toks = prompt(100)
    want = np.asarray(REF.forward(doc, ref_weights, toks))
    lg, cache = prefill(weights, fresh_cache(), toks, 0, 61, (0, 2, 0), impl)   # 60 // 8 = 7: pair[1]
    np.testing.assert_allclose(np.asarray(lg), want[60], **TOL)
    got, _ = decode(weights, cache, toks, 61, (1, 2), impl)
    np.testing.assert_allclose(np.stack(got), want[61:], **TOL)


def test_a_chunked_prefill_equals_a_single_one(params, ref_params):
    """40 + 21 tokens in two dispatches, the second reading the state the first
    left in a snapshot slot, against 61 at once: the same logits, the same K, V
    and compressed keys, the same state."""
    toks = prompt(61, seed=3)
    lg1, one = prefill(params, fresh_cache(), toks, 0, 61, (0, 2, 0))
    _, two = prefill(params, fresh_cache(), toks, 0, 40, (0, 1, 5))
    lg2, two = prefill(params, two, toks, 40, 61, (5, 2, 0))
    np.testing.assert_allclose(np.asarray(lg2), np.asarray(lg1), **TOL)
    for name in ("kv", "ckeys"):
        a, b = np.asarray(getattr(one, name))[:, 1:9], np.asarray(getattr(two, name))[:, 1:9]
        np.testing.assert_allclose(b.reshape(2, -1, CFG.kv_size)[:, :61 if name != "ckeys" else 30],
                                   a.reshape(2, -1, CFG.kv_size)[:, :61 if name != "ckeys" else 30], **TOL)
    np.testing.assert_allclose(np.asarray(two.state[:, 2]), np.asarray(one.state[:, 2]), **STATE_TOL)
    np.testing.assert_allclose(np.asarray(two.state[:, 5]), np.asarray(two.state[:, 1]))  # the snapshot is the copy
    want = np.asarray(REF.forward(DOC, ref_params, toks))
    np.testing.assert_allclose(np.asarray(lg2), want[60], **TOL)


def test_a_resume_without_its_state_is_told_apart(params, ref_params):
    """The control of the tolerance: the second chunk resumed from a zeroed slot
    misses the reference by orders of magnitude more than ``TOL``."""
    toks = prompt(61, seed=3)
    _, cache = prefill(params, fresh_cache(), toks, 0, 40, (0, 1, 0))
    lg, _ = prefill(params, cache, toks, 40, 61, (6, 2, 0))  # slot 6 was never written
    want = np.asarray(REF.forward(DOC, ref_params, toks))
    assert np.abs(np.asarray(lg) - want[60]).max() > 100 * TOL["atol"]


def test_decode_leaves_the_block_befores_state_behind(params):
    """The step that opens a block reads one slot of the pair and writes the
    other: what it read stays, the state after the block before's last token,
    and equals what a prefill that stops there computes."""
    toks = prompt(50, seed=4)
    _, cache = prefill(params, fresh_cache(), toks, 0, 37, (0, 1, 0))           # 36 // 8 = 4: pair[0]
    _, cache = decode(params, cache, toks, 37, (1, 2))                          # crosses 40 and 48
    _, upto48 = prefill(params, fresh_cache(), toks, 0, 48, (0, 3, 0))
    np.testing.assert_allclose(np.asarray(cache.state[:, 2]), np.asarray(upto48.state[:, 3]), **STATE_TOL)
    _, upto50 = prefill(params, fresh_cache(), toks, 0, 50, (0, 3, 0))          # 49 // 8 = 6: pair[0]
    np.testing.assert_allclose(np.asarray(cache.state[:, 1]), np.asarray(upto50.state[:, 3]), **STATE_TOL)
    # A finished sequence's zombie steps (past ``stop``) leave the pair alone.
    before = np.asarray(cache.state)
    _, cache = decode(params, cache, toks + prompt(10, seed=5), 50, (1, 2), stop=49)
    np.testing.assert_array_equal(np.asarray(cache.state)[:, 1:], before[:, 1:])


def test_two_sequences_sharing_a_sealed_page_read_the_same_compressed_keys(params):
    """A compressed key lives in the page that holds its last token, so a
    sealed page never depends on what follows it: two sequences with the same
    first 24 tokens and different continuations write the same compressed keys
    into blocks 0-2, and a third that only borrows those pages (a prefix hit)
    gets the logits of one that computed them."""
    shared, tail_a, tail_b = prompt(24, seed=5), prompt(30, seed=6), prompt(30, seed=7)
    _, a = prefill(params, fresh_cache(), shared + tail_a, 0, 54, (0, 1, 0))
    _, b = prefill(params, fresh_cache(), shared + tail_b, 0, 54, (0, 1, 0))
    np.testing.assert_array_equal(np.asarray(a.ckeys[:, 1:4]), np.asarray(b.ckeys[:, 1:4]))
    assert np.abs(np.asarray(a.ckeys[:, 4]) - np.asarray(b.ckeys[:, 4])).max() > 1e-3
    # b's table borrows a's first three pages and a's snapshot of block 2.
    _, a = prefill(params, fresh_cache(), shared + tail_a, 0, 24, (0, 1, 5))
    borrowed = jnp.concatenate([TABLE[:3], jnp.arange(20, 33, dtype=jnp.int32)])
    lg_hit, _ = prefill(params, a, shared + tail_b, 24, 54, (5, 2, 0), table=borrowed)
    lg_own, _ = prefill(params, fresh_cache(), shared + tail_b, 0, 54, (0, 1, 0))
    np.testing.assert_allclose(np.asarray(lg_hit), np.asarray(lg_own), **TOL)


# -- the ops -------------------------------------------------------------------------


def _plain_recurrence(q, k, v, s0):
    """S_t = lambda S_{t-1} + k_t^T v_t; o_t = q_t S_t, a token at a time, in float64."""
    H = q.shape[1]
    lam = np.exp(-np.exp2(-8.0 * np.arange(1, H + 1) / H))
    s, out = np.asarray(s0, np.float64).copy(), []
    for t in range(q.shape[0]):
        s = lam[:, None, None] * s + np.einsum("hd,he->hde", k[t], v[t])
        out.append(np.einsum("hd,hde->he", q[t], s))
    return np.stack(out), s


@pytest.mark.parametrize("T,n_valid", [(24, 24), (24, 13), (256, 200)])
def test_the_chunked_scan_is_the_recurrence(T, n_valid):
    """Chunks of gcd(T, 128) tokens against the plain recurrence, padding
    tokens neither decaying the state nor adding to it (T 256 is two chunks of
    128, where the fastest head's decay underflows inside a chunk)."""
    rng = np.random.RandomState(T + n_valid)
    q, k, v = (rng.standard_normal((1, T, 4, 32)).astype(np.float32) for _ in range(3))
    s0 = rng.standard_normal((1, 4, 32, 32)).astype(np.float32)
    snap_at = n_valid // 8 * 8  # the last boundary of blocks of 8: a chunk's end where chunks are 8
    o, s, snap = lightning.lightning_prefill(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(s0),
                                             jnp.asarray([n_valid], jnp.int32), jnp.asarray([[snap_at, 8]], jnp.int32),
                                             chunk=8 if T == 24 else 128)
    want_o, want_s = _plain_recurrence(q[0, :n_valid], k[0, :n_valid], v[0, :n_valid], s0[0])
    np.testing.assert_allclose(np.asarray(o)[0, :n_valid], want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s)[0], want_s, rtol=2e-4, atol=2e-4)
    if T == 24:
        for j, at in enumerate((snap_at, 8)):
            _, want_snap = _plain_recurrence(q[0, :at], k[0, :at], v[0, :at], s0[0])
            np.testing.assert_allclose(np.asarray(snap)[0, j], want_snap, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("interpret", [False, True])
def test_the_step_kernel_updates_the_pool_in_its_slots(interpret):
    """One step of three rows: each reads its read slot, writes its write slot
    (another where a block opens), leaves every other slot of the pool as it
    was; the XLA form and the kernel in interpret mode against the recurrence."""
    rng = np.random.RandomState(1)
    q, k, v = (rng.standard_normal((3, 4, 32)).astype(np.float32) for _ in range(3))
    pool = rng.standard_normal((2, 6, 4, 32, 32)).astype(np.float32)
    read, write = jnp.asarray([1, 2, 0], jnp.int32), jnp.asarray([1, 4, 0], jnp.int32)
    fn = lightning.lightning_decode if interpret else lightning.lightning_decode_xla
    kw = dict(interpret=True) if interpret else {}
    o, out = fn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(pool), jnp.int32(1), read, write, **kw)
    for row, (r, w) in enumerate([(1, 1), (2, 4)]):
        want_o, want_s = _plain_recurrence(q[row][None], k[row][None], v[row][None], pool[1, r])
        np.testing.assert_allclose(np.asarray(o)[row], want_o[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out)[1, w], want_s, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(out)[0], pool[0])          # the other layer
    np.testing.assert_array_equal(np.asarray(out)[1, [2, 3, 5]], pool[1, [2, 3, 5]])  # read-only and untouched slots


def test_compressed_keys_are_means_of_two_strides():
    rng = np.random.RandomState(2)
    k, before = rng.standard_normal((1, 16, 4)).astype(np.float32), rng.standard_normal((1, 2, 4)).astype(np.float32)
    got = np.asarray(sparse.compress_keys(jnp.asarray(k), jnp.asarray(before), 2))
    whole = np.concatenate([before, k], axis=1)[0]
    want = np.stack([whole[2 * c:2 * c + 4].mean(axis=0) for c in range(8)])
    np.testing.assert_allclose(got[0], want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("t", [20, 31, 32, 50, 77])
def test_the_choice_is_the_references_past_dense_len_and_the_path_is_dense_under_it(params, ref_params, t):
    """One decode position at ``t``: with at most dense_len 32 positions in view
    the row keeps its own table and length; past it the table holds 5 pages,
    block 0 and the open block among them, ascending, and the logits are the
    reference's (whose choice is made on dense score matrices); a choice rolled
    by one block (the reference's own control) is told apart."""
    toks = prompt(t + 1, seed=8)
    _, cache = prefill(params, fresh_cache(), toks, 0, t, (0, 1, 0))
    q = jnp.asarray(np.random.RandomState(t).standard_normal((1, 2, 2, 32)), jnp.float32)
    pages, lengths = sparse.sparse_select(q, cache.ckeys, 0, TABLE[None], jnp.asarray([t], jnp.int32), SP)
    if t + 1 <= SP.dense_len:
        dense_w = SP.dense_len // BS  # the first 4 entries are the row's own; the walk stops at its length
        np.testing.assert_array_equal(np.asarray(pages)[:, :dense_w], np.broadcast_to(np.asarray(TABLE[:dense_w]), (2, dense_w)))
        assert lengths.tolist() == [t + 1, t + 1]
    else:
        assert pages.shape == (2, 5) and lengths.tolist() == [4 * BS + t % BS + 1] * 2
        for row in np.asarray(pages):
            assert row[0] == TABLE[0] and row[-1] == TABLE[t // BS] and (np.diff(row) > 0).all()
    pair = (1, 2) if ((t - 1) // BS) % 2 == 0 else (2, 1)
    got, _ = decode(params, cache, toks, t, pair)
    want = np.asarray(REF.forward(DOC, ref_params, toks))[t]
    np.testing.assert_allclose(got[0], want, **TOL)
    if t + 1 > SP.dense_len + BS:
        os.environ["SALA_REF_CONTROL"] = "roll_blocks"
        try:
            rolled = np.asarray(REF.forward(DOC, ref_params, toks))[t]
        finally:
            del os.environ["SALA_REF_CONTROL"]
        assert np.abs(rolled - want).max() > 100 * TOL["atol"]


@pytest.mark.parametrize("control", ["bf16_state", "int8_state", "lower_cache"])
def test_the_references_lower_precision_controls_are_told_apart_in_float32(ref_params, control):
    """The chip's controls on the CPU, where the program is float32: the
    recurrence with its state rounded after every token, to bfloat16 (what the
    served pool holds) or to int8 (the precision below it), and that with K, V
    and the compressed keys in int8 too, leave the float32 reference by far
    more than the program does (on the chip, beside bf16 activations, the first
    is in the noise and the other two are ten times the limits: the cell's
    limits file has the readings)."""
    toks = prompt(64, seed=9)
    want = np.asarray(REF.forward(DOC, ref_params, toks))
    os.environ["SALA_REF_CONTROL"] = control
    try:
        rounded = np.asarray(REF.forward(DOC, ref_params, toks))
    finally:
        del os.environ["SALA_REF_CONTROL"]
    assert 50 * TOL["atol"] < np.abs(rounded - want).max() < 0.1


@pytest.mark.parametrize("table_blocks, lengths, read", [
    (None, [8, 32, 33, 100], 1 + 4 + 5 + 5),   # decode: the chosen table, every visible block under dense_len
    (16, [8, 32, 33, 100], 4 * 16),            # a prefill with a row past dense_len: every page of its table, masked
    (16, [8, 32], 1 + 4),                      # a prefill under dense_len: its visible blocks
    (4, [8, 32], 1 + 4),                       # a table that cannot hold a sparse row
])
def test_choice_counts_follow_what_was_dispatched(table_blocks, lengths, read):
    n = np.array(lengths)
    assert sparse.choice_counts(n, SP, table_blocks) == (read, int((-(-n // BS)).sum()), int((n <= 32).sum()))


# -- the block manager's snapshot policy ---------------------------------------------


def sealed_pool(n_blocks: int = 6, slots: int = 6) -> tuple[BlockPool, list[int]]:
    """A pool whose first ``n_blocks`` blocks are one registered chain of hashes 100, 101, ..."""
    pool = BlockPool(16, BS, state_slots=slots)
    ids, _ = pool.allocate_sequence([], n_blocks)
    hashes = list(range(100, 100 + n_blocks))
    for i, (bid, h) in enumerate(zip(ids, hashes)):
        pool.register_block(bid, h, hashes[i - 1] if i else None)
    pool.free_sequence(ids)
    return pool, hashes


def test_a_hit_is_as_deep_as_the_deepest_block_with_pages_and_a_snapshot():
    pool, hashes = sealed_pool()
    assert pool.snapshot_depth(hashes) == (0, 0)                   # pages alone: from zero
    slot = pool.take_snapshot(hashes[2], "chunk_end")
    assert slot and pool.take_snapshot(hashes[2], "chunk_end") == 0   # a block has one snapshot
    assert pool.snapshot_depth(hashes) == (3, slot)                # pages go on to 6; the state stops at 3
    assert pool.snapshot_depth(hashes[:2]) == (0, 0)
    assert pool.snapshot_depth([hashes[0], 999, hashes[2]]) == (0, 0)  # no page, no chain behind it
    deeper = pool.take_snapshot(hashes[4], "chunk_end")
    assert pool.snapshot_depth(hashes) == (5, deeper)
    ids, n_hit = pool.allocate_sequence(hashes, 8, max_hit=5)
    assert n_hit == 5 and pool.miss_blocks == 1                    # the sixth page is computed again
    assert pool.state_snapshots == {"chunk_end": 2, "decode_boundary": 0}


def test_snapshots_go_by_their_own_lru_but_never_one_an_admission_still_reads():
    pool, hashes = sealed_pool(slots=6)                            # 5 slots beside the sink
    slots = [pool.take_snapshot(h, "chunk_end") for h in hashes[:3]]
    assert all(slots)
    assert pool.snapshot_depth(hashes[:1]) == (1, slots[0])        # pinned, and now the newest
    pair = pool.acquire_state_pair()                               # the two free slots
    assert pool.state_evictions == 0 and not set(pair) & set(slots)
    second = pool.acquire_state_pair()                             # evicts the two oldest that are not pinned
    assert set(second) == {slots[1], slots[2]} and pool.state_evictions == 2
    with pytest.raises(NoFreeBlocksError):
        pool.acquire_state_pair()                                  # only the pinned one is left
    pool.unpin_states()
    assert pool.take_snapshot(hashes[3], "chunk_end") == slots[0]  # now it can go
    assert pool.snapshot_depth(hashes) == (4, slots[0])


def test_a_finished_sequence_leaves_one_snapshot_and_a_branch_point_outlives_the_lru():
    pool, hashes = sealed_pool(slots=8)
    shared = pool.take_snapshot(hashes[1], "chunk_end")            # a shared prompt's end ...
    ids, _ = pool.allocate_sequence([], 2)
    pool.register_block(ids[0], 200, hashes[1])                    # ... from which a second chain diverges
    pool.register_block(ids[1], 201, 200)
    assert pool.hash_fanout(hashes[1]) == 2
    # a sequence resumed from its own earlier snapshot, not a branch point, and leaves a deeper one
    own = pool.take_snapshot(hashes[3], "chunk_end")
    pair = pool.acquire_state_pair()
    pool.release_state_pair(pair, keep=(pair[1], hashes[5]))
    assert pool.num_snapshots == 3 and list(pool._snapshots) == [hashes[1], hashes[3], hashes[5]]
    assert pool.snapshot_depth(hashes) == (6, pair[1])
    pool.unpin_states()
    # one that resumed from the branch point and stopped on a block the chain has no snapshot of
    pair2 = pool.acquire_state_pair()
    pool.release_state_pair(pair2, keep=(pair2[0], 201))
    assert pool.num_snapshots == 4 and len(pool._state_free) == 3 and pair2[1] in pool._state_free
    # a block keeps the snapshot it has: the slot offered for a second one goes back
    pair3 = pool.acquire_state_pair()
    pool.release_state_pair(pair3, keep=(pair3[0], 201))
    assert pool.num_snapshots == 4 and len(pool._state_free) == 3
    # under pressure the oldest goes first and the branch point last, though it is the oldest of all
    pool.acquire_state_pair()                                      # two of the three slots still free
    assert pool.state_evictions == 0
    got = pool.acquire_state_pair()                                # the third, and the oldest but the branch point
    assert pool.state_evictions == 1 and own in got and hashes[3] not in pool._snapshots
    pool.acquire_state_pair()                                      # the two kept ones go before the branch point
    assert pool.state_evictions == 3 and pool.snapshot_depth(hashes[:2]) == (2, shared)
    # a page that is evicted takes its snapshot with it
    pool.unpin_states()
    for _ in range(pool.num_free):
        pool.allocate_block()
    assert pool.num_snapshots == 0


def test_a_prefills_chunk_snapshot_takes_the_place_of_the_one_its_chunk_before_took():
    """A sequence's own older snapshots are worth nothing beside its newest: a
    long prefill holds one, not one a chunk, so it never pushes out what idle
    sessions wait on. What stays: a branch point, and one an admission reads."""
    pool, hashes = sealed_pool(slots=8)
    idle = pool.take_snapshot(hashes[5], "chunk_end")              # an idle session's resume point
    first = pool.take_snapshot(hashes[0], "chunk_end")
    second = pool.take_snapshot(hashes[1], "chunk_end", replaces=hashes[0])
    assert first and second == first and list(pool._snapshots) == [hashes[5], hashes[1]]   # the slot it gave back
    assert pool.state_evictions == 0                               # replaced, not evicted
    # the second chunk's has become a branch point meanwhile (another chain parts there): it stays
    ids, _ = pool.allocate_sequence([], 1)
    pool.register_block(ids[0], 300, hashes[1])
    third = pool.take_snapshot(hashes[2], "chunk_end", replaces=hashes[1])
    assert third and list(pool._snapshots) == [hashes[5], hashes[1], hashes[2]]
    # an admission of this wave resumes from the third: the fourth chunk leaves it alone
    assert pool.snapshot_depth(hashes[:3]) == (3, third)
    fourth = pool.take_snapshot(hashes[3], "chunk_end", replaces=hashes[2])
    assert fourth and hashes[2] in pool._snapshots and pool.num_snapshots == 4
    pool.unpin_states()
    assert pool.take_snapshot(hashes[4], "chunk_end", replaces=hashes[3]) == fourth   # the slot it gave back
    assert pool.snapshot_depth(hashes) == (6, idle)


# -- under the block manager and the scheduler -------------------------------------


def greedy(prompt_ids, max_tokens=6, **ktp) -> PreprocessedRequest:
    req = PreprocessedRequest(model="t", token_ids=list(prompt_ids))
    req.sampling.temperature = 0.0
    req.sampling.seed = 0
    req.stop.max_tokens = max_tokens
    req.stop.ignore_eos = True
    if ktp:
        req.kv_transfer_params = ktp
    return req


def engine_args(**kw) -> EngineArgs:
    # Windows of 2 steps: a finished sequence's zombie window then stays inside the
    # block after its last sealed one, as 8 steps do in blocks of 64 (StateSlots.release).
    return EngineArgs(**{**dict(model=CFG, block_size=BS, num_kv_blocks=64, max_num_seqs=4, max_model_len=256,
                                max_prefill_tokens=32, dtype="float32", decode_steps=2), **kw})


async def _tokens(engine, req) -> list[int]:
    return [t async for o in engine.generate(req, Context()) for t in o.get("token_ids", [])]


def reference_greedy(ref_params, toks: list[int], n: int) -> list[int]:
    toks = list(toks)
    for _ in range(n):
        toks.append(int(jnp.argmax(REF.forward(DOC, ref_params, toks)[-1])))
    return toks[-n:]


def test_a_follow_up_turn_resumes_from_a_snapshot_and_recomputes_under_a_block(ref_params):
    """Through the scheduler: a 70-token prompt is prefilled in three chunks
    (snapshots at 32 and 64), answered with 20 tokens; the next turn resends
    all 90 with 13 more and resumes at the snapshot its decode left at the last
    sealed block (88), so fewer than 8 tokens of history are computed again;
    its tokens are the reference's greedy decode and an engine's that never saw
    the first turn. A new session on the first 64 tokens resumes at 64."""
    first = prompt(70, seed=1)

    async def go():
        engine = await TpuEngine(engine_args()).start()
        try:
            a = await _tokens(engine, greedy(first, 20))
            snaps = dict(engine.pool.state_snapshots)
            second = first + a + prompt(13, seed=2)
            b = await _tokens(engine, greedy(second, 8))
            stats = dict(engine.side.stats)
            await _tokens(engine, greedy(first[:64] + prompt(20, seed=3), 4))
            return a, second, b, snaps, stats, dict(engine.side.stats), engine.pool.num_snapshots
        finally:
            await engine.stop()

    a, second, b, snaps, stats, after, kept = asyncio.run(go())
    assert snaps["chunk_end"] == 2 and snaps["decode_boundary"] >= 2
    assert stats["snapshot"] == 1 and stats["zero"] == 1
    assert stats["cached_tokens"] == 88 and stats["recomputed_tokens"] == 0   # 103 - 1 - 88 < 8 tokens of history again
    assert b == reference_greedy(ref_params, second, 8)
    assert after["snapshot"] == 2 and after["cached_tokens"] - stats["cached_tokens"] == 64
    assert after["dense"] == 32  # the first session's first 32 positions; everything else chose (the last resumed at 64)

    async def alone():
        engine = await TpuEngine(engine_args()).start()
        try:
            return await _tokens(engine, greedy(second, 8))
        finally:
            await engine.stop()

    assert asyncio.run(alone()) == b


def test_where_cached_pages_end_without_a_snapshot_the_next_prefill_leaves_one():
    """A shared prompt whose pages are cached but whose snapshots are gone (its
    first session's later chunks took their place): the
    second session computes it again from zero, and leaves a snapshot where its
    cached pages ended, now a branch point (two chains part there) that the LRU
    spares and later snapshots of either chain do not supersede; the third
    session resumes there."""
    shared = prompt(64, seed=11)

    async def go():
        engine = await TpuEngine(engine_args()).start()
        try:
            pool = engine.pool

            async def settled():  # a finished sequence's zombie window still holds its pair
                while len(pool._state_free) + pool.num_snapshots < pool.state_slots - 1:
                    await asyncio.sleep(0.01)

            await _tokens(engine, greedy(shared + prompt(30, seed=12), 4))
            await settled()
            await engine.run_on_engine_thread(lambda: [pool._drop_snapshot(h) for h in list(pool._snapshots)])
            await _tokens(engine, greedy(shared + prompt(30, seed=13), 20))
            second = dict(engine.side.stats)
            from dynamo_tpu.tokens import compute_block_hashes
            end = compute_block_hashes(shared, BS)[-1]
            await settled()

            def squeeze():
                # every free slot and one eviction: not the branch point's, though it is the oldest
                oldest = next(iter(pool._snapshots))
                taken = [pool._pop_state() for _ in range(len(pool._state_free) + 1)]
                pool._state_free.extend(taken)
                return oldest == end and pool.state_evictions == 1 and end in pool._snapshots

            fanout, spared = pool.hash_fanout(end), await engine.run_on_engine_thread(squeeze)
            await _tokens(engine, greedy(shared + prompt(30, seed=14), 4))
            return second, dict(engine.side.stats), fanout, end in pool._snapshots, spared
        finally:
            await engine.stop()

    second, third, fanout, kept, spared = asyncio.run(go())
    assert second["zero"] == 2 and second["cached_tokens"] == 64 and second["recomputed_tokens"] == 64
    assert fanout == 2 and kept and spared
    assert third["snapshot"] == 1 and third["recomputed_tokens"] == 64   # nothing more was computed twice


def test_a_preempted_sequence_returns_through_the_same_path():
    """Three sequences of 40 + 30 tokens want 27 blocks of a pool of 20: one is
    preempted, leaves its pages and a snapshot, returns behind them (or from
    zero where the pressure took them) and finishes with the tokens it has
    alone; every state slot is back in the pool at the end."""
    async def go():
        engine = await TpuEngine(engine_args(num_kv_blocks=20)).start()
        try:
            alone = [await _tokens(engine, greedy(prompt(40, seed=s), 30)) for s in (1, 2, 3)]
            n0 = sum(engine.total_preemptions_by.values())
            together = await asyncio.gather(*(_tokens(engine, greedy(prompt(40, seed=s), 30)) for s in (1, 2, 3)))
            pool = engine.pool  # on the scheduler thread: a stream's last delta is posted before its pair goes back
            slots = await engine.run_on_engine_thread(lambda: len(pool._state_free) + pool.num_snapshots)
            return alone, list(together), sum(engine.total_preemptions_by.values()) - n0, slots, dict(engine.side.stats)
        finally:
            await engine.stop()

    alone, together, n, slots, stats = asyncio.run(go())
    assert together == alone and n > 0
    assert stats["snapshot"] + stats["zero"] == 6 + n
    assert slots == engine_args().state_slots - 1   # nothing leaked: all but the sink


def test_the_worker_says_what_it_runs_and_counts_states_and_choices():
    """Engine level: the start line names the block and int8; ``/metrics`` holds
    every new series from the start, the pools' bytes by kind and the state
    pool beside them."""
    async def go():
        engine = TpuEngine(engine_args(quant="int8"))
        registry = MetricsRegistry()
        engine.bind_metrics(registry)
        await engine.start()
        try:
            line = engine._runner._start_line("")
            a = await _tokens(engine, greedy(prompt(44, seed=1)))  # two chunks: the second's snapshot replaces the first's
            b = await _tokens(engine, greedy(prompt(44, seed=1)))
            await engine.run_on_engine_thread(engine._update_gauges)
            return line, a, b, registry.render()
        finally:
            await engine.stop()

    line, a, b, page = asyncio.run(go())
    assert "quant=int8" in line and " block=sala" in line and "decode=xla" in line
    assert a == b and len(a) == 6
    args = engine_args()
    kinds = args.pool_bytes_per_block()
    assert kinds == {"kv": 2 * 2 * BS * CFG.kv_size * 4, "ckeys": 2 * 4 * CFG.kv_size * 4}
    assert args.state_slots == 2 * 4 + 3 and args.state_pool_bytes() == 11 * 4 * 4 * 32 * 32 * 4
    for kind, per_block in kinds.items():
        assert f'kv_pool_bytes{{kind="{kind}"}} {per_block * args.num_kv_blocks}' in page
    assert f'kv_pool_bytes{{kind="state"}} {args.state_pool_bytes()}' in page
    for series in ('engine_state_resumes_total{from="snapshot"} 1', 'engine_state_resumes_total{from="zero"} 1',
                   'engine_state_snapshots_total{why="chunk_end"} 2', 'engine_state_snapshots_total{why="decode_boundary"}',
                   "engine_state_cached_tokens_total 40", "engine_state_recomputed_tokens_total 0",
                   "engine_state_snapshot_evictions_total 0", "engine_sparse_blocks_chosen_total",
                   "engine_sparse_blocks_visible_total", "engine_sparse_dense_rows_total"):
        assert series in page, series


def test_pool_accounting_counts_every_pool():
    args = engine_args(dtype="bfloat16")
    cache = sala.init_kv_cache(CFG, args.num_kv_blocks, BS, state_slots=args.state_slots)
    assert cache.kv.shape == (2, 64, 2, BS, CFG.kv_size) and cache.block_size == BS and cache.ckeys.shape == (2, 64, 4, CFG.kv_size)
    assert cache.state.shape == (4, 11, 4, 32, 32) and cache.state.dtype == cache.kv.dtype == jnp.bfloat16
    assert cache.kv.nbytes + cache.ckeys.nbytes == args.num_kv_blocks * args.kv_bytes_per_block()
    assert cache.state.nbytes == args.state_pool_bytes()
    # The state pool is sized from bytes as the pages are: what num_kv_blocks pages take, never under the running pairs.
    big = engine_args(dtype="bfloat16", num_kv_blocks=1024)
    assert big.state_slots == 1024 * big.kv_bytes_per_block() // big.state_slot_bytes() == 160
    assert EngineArgs.auto_kv_blocks(1 << 30, big) == int((1 << 30) * 0.9) // (2 * big.kv_bytes_per_block())
    assert sala.segments(CFG) == ((0, 3), (3, 1))  # lightning layers before each sparse layer, and after it
    assert CFG.param_count() == sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: sala.init_params(CFG, jax.random.PRNGKey(0), jnp.float32))))


# -- what refuses the block --------------------------------------------------------


@pytest.mark.parametrize("kw,names", [
    (dict(kv_quant="int8"), "--kv-quant int8"),
    (dict(spec_tokens=2), "speculation"),
    (dict(lora_slots=2), "LoRA"),
    (dict(tp=2), "--tp"),
    (dict(host_kv_blocks=8), "KV tiers"),
    (dict(disk_kv_dir="/nonexistent"), "KV tiers"),
    (dict(block_size=16), "--block-size 16"),
])
def test_engine_args_refuse_what_cannot_carry_the_state_pool(kw, names):
    with pytest.raises(ValueError, match="sala") as e:
        engine_args(**kw)
    assert names in str(e.value)


def test_int8_weights_are_not_refused():
    assert engine_args(quant="int8").quant == "int8"


@pytest.mark.parametrize("what", ["embed", "spec_verify", "extract_pages", "inject_pages", "disaggregated",
                                  "peer_prefix", "migration"])
def test_mechanisms_refuse_the_block_by_name(what):
    if what == "embed":
        with pytest.raises(ValueError, match="embed_impl"):
            M.embed_impl(CFG, {}, jnp.zeros((8,), jnp.int32), jnp.int32(4))
    elif what == "spec_verify":
        with pytest.raises(ValueError, match="spec_verify_impl"):
            M.spec_verify_impl(CFG, 2, "greedy", 0, {}, None, *([None] * 8))
    elif what in ("extract_pages", "inject_pages"):
        from dynamo_tpu.engine.runner import LocalRunner

        runner = LocalRunner(engine_args())
        with pytest.raises(ValueError, match="block='sala'"):
            runner.extract_pages([1]) if what == "extract_pages" else runner.inject_pages([1], None, None)
    else:
        ktp = {"disaggregated": {"do_remote_decode": True}, "peer_prefix": {"peer_prefix": {"num_blocks": 1}}}

        async def go():
            engine = await TpuEngine(engine_args()).start()
            try:
                if what in ktp:
                    outs = [o async for o in engine.generate(greedy(prompt(20), **ktp[what]), Context())]
                    return outs[-1].get("error", "")
                got = await engine.run_on_engine_thread(lambda: engine.migration_begin("any"))
                return got.get("error", "")
            finally:
                await engine.stop()

        assert "state pool" in asyncio.run(go())
