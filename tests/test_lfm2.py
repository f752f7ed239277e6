"""The LFM2-MoE block (ISSUE 37) on the CPU at a toy size with seeded weights:
the program against the benchmark's plain reference, the conv state found by
the block table alone (prefix hits, chunks, packed rows, decode across block
boundaries, a preempted sequence's return), the router's second arithmetic, the
expert layer against a plain loop, and what refuses the block."""

import asyncio
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model_maps, references
from dynamo_tpu.engine import lfm2, longcat
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.metrics import MetricsRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "chipbench", "configs", "rehearse-lfm2-tiny.json")) as f:
    DOC = json.load(f)
DOC = {**DOC, "served": {**DOC["served"], "dtype": "float32"}}
CFG = model_maps.model_config(DOC)
REF = references.load("lfm2_moe")
BS = 8
TOL = dict(rtol=2e-4, atol=2e-4)  # float32 on both sides; the sums differ in order


@pytest.fixture(scope="module")
def params():
    return lfm2.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)


@pytest.fixture(scope="module")
def ref_params():
    return REF.weights(DOC, 0)


def prompt(n: int, seed: int = 0) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(0, CFG.vocab_size, n)]


def pad(xs, n: int) -> jax.Array:
    return jnp.zeros((n,), jnp.int32).at[:len(xs)].set(jnp.asarray(xs, jnp.int32))


def bucket(n: int) -> int:
    return -(-n // 16) * 16


KW = dict(attn_impl="xla", experts="ragged_dot")


def prefill(params, cache, toks, table, start: int, upto: int):
    """Positions [start, upto) of ``toks`` through the single prefill."""
    return lfm2.prefill(CFG, params, cache, pad(toks[start:upto], bucket(upto - start)), table, start, upto, **KW)[:2]


def decode(params, cache, toks, table, first: int) -> list:
    """Teacher-forced decode of ``toks[first:]`` through the cache → logits at each."""
    out = []
    for pos in range(first, len(toks)):
        lg, cache, _ = lfm2.decode_step(
            CFG, params, cache, jnp.asarray([toks[pos]], jnp.int32), jnp.asarray([pos], jnp.int32),
            table[None], jnp.asarray([True]), **KW)
        out.append(lg[0])
    return out


def new_cache():
    return lfm2.init_kv_cache(CFG, 32, BS, jnp.float32)


def test_the_preset_is_the_rehearsal_files_model_and_the_reference_draws_its_weights(params, ref_params):
    tiny = dataclasses.replace(ModelConfig.preset("lfm2-tiny"), name=CFG.name, max_position=CFG.max_position)
    assert tiny == CFG and M.block_module(CFG) is lfm2
    assert CFG.attn_layers == (2,) and CFG.conv_layers == (0, 1, 3, 4, 5) and CFG.expert_layers == (1, 2, 3, 4, 5)
    ours, theirs = jax.tree.leaves(params), jax.tree.leaves(ref_params)
    assert len(ours) == len(theirs) and all(bool(jnp.array_equal(a, b)) for a, b in zip(ours, theirs))
    assert CFG.param_count() == sum(a.size for a in ours)


@pytest.mark.parametrize("mode", ["cold", "chunked", "cached"])
def test_prefill_then_decode_across_block_boundaries_agrees_with_the_reference(params, ref_params, mode):
    """A 37-token prompt (cold, in two chunks, or behind blocks an earlier
    dispatch cached) and 22 decode steps across the boundaries at 40, 48 and
    56: every position's logits are the reference's whole forward pass."""
    toks, plen = prompt(59, seed=3), 37
    cache, table = new_cache(), jnp.arange(1, 17, dtype=jnp.int32)
    if mode == "cold":
        logits, cache = prefill(params, cache, toks, table, 0, plen)
    else:
        _, cache = prefill(params, cache, toks, table, 0, 16)
        if mode == "cached":  # another dispatch wrote the blocks; only the table names them
            cache = jax.tree.map(jnp.copy, cache)
        logits, cache = prefill(params, cache, toks, table, 16, plen)
    got = np.stack([logits] + decode(params, cache, toks, table, plen))
    want = np.asarray(REF.forward(DOC, ref_params, toks))[plen - 1:]
    np.testing.assert_allclose(got, want, **TOL)


def test_a_packed_wave_of_rows_at_their_own_start_pos_agrees_with_the_reference(params, ref_params):
    """Two rows of one dispatch: one from position 0, one behind two cached
    blocks of another prompt; a padding row beside them writes nothing."""
    a, b = prompt(21, seed=4), prompt(30, seed=5)
    cache = new_cache()
    tb = jnp.arange(9, 17, dtype=jnp.int32)
    _, cache = prefill(params, cache, b, tb, 0, 16)
    before = jax.tree.map(np.asarray, cache)
    tables = jnp.stack([jnp.arange(1, 9, dtype=jnp.int32), tb, jnp.zeros((8,), jnp.int32), jnp.zeros((8,), jnp.int32)])
    toks = jnp.stack([pad(a, 32), pad(b[16:], 32), pad([], 32), pad([], 32)])
    logits, cache, hist = lfm2.prefill_batch(
        CFG, params, cache, toks, tables, jnp.asarray([0, 16, 0, 0], jnp.int32),
        jnp.asarray([21, 30, 0, 0], jnp.int32), **KW)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(REF.forward(DOC, ref_params, a))[-1], **TOL)
    np.testing.assert_allclose(np.asarray(logits[1]), np.asarray(REF.forward(DOC, ref_params, b))[-1], **TOL)
    # the shared blocks of row 1 are as they were: nothing wrote a sealed block
    for name in ("kv",):
        np.testing.assert_array_equal(np.asarray(getattr(cache, name))[:, 9:11], getattr(before, name)[:, 9:11])
    np.testing.assert_array_equal(np.asarray(cache.conv)[:, :, 9:11], before.conv[:, :, 9:11])
    E = CFG.num_experts
    assert hist.shape == (len(CFG.expert_layers), E + longcat.HIST_EXTRA)
    assert [int(n) for n in hist[:, E + 2]] == [21 + 14] * len(CFG.expert_layers)  # tokens routed: padding nowhere


@pytest.mark.parametrize("hit_blocks", [1, 2, 4])
@pytest.mark.parametrize("zeroed", [False, True], ids=["state_in_the_block", "control_state_zeroed"])
def test_a_prefix_hit_finds_the_conv_state_in_the_block_before_it(params, ref_params, hit_blocks, zeroed):
    """A 37-token prompt served behind 1, 2 and all-but-one of its blocks,
    cached by another sequence: the first suffix position and the prompt's
    last are the uncached run's, to float32 rounding. The control: with the
    state zeroed in the block before the hit, the first suffix position is
    wrong by orders of magnitude more."""
    toks, plen = prompt(45, seed=6), 37
    cut = hit_blocks * BS
    cache = new_cache()
    shared = jnp.arange(1, 9, dtype=jnp.int32)
    _, cache = prefill(params, cache, toks, shared, 0, cut)  # an earlier sequence cached the blocks
    if zeroed:
        cache = cache._replace(conv=cache.conv.at[:, :, hit_blocks].set(0.0))
    table = jnp.concatenate([shared[:hit_blocks], jnp.arange(20, 28, dtype=jnp.int32)])
    want = np.asarray(REF.forward(DOC, ref_params, toks))
    first, _ = prefill(params, jax.tree.map(jnp.copy, cache), toks, table, cut, cut + 1)  # the cache is donated
    gap = float(np.abs(np.asarray(first) - want[cut]).max())
    if zeroed:
        assert gap > 1e-2, gap  # rounding reads 1e-5
        return
    assert gap < 2e-4, gap
    logits, cache = prefill(params, cache, toks, table, cut, plen)
    got = np.stack([logits] + decode(params, cache, toks, table, plen))
    np.testing.assert_allclose(got, want[plen - 1:], **TOL)


def test_the_router_chooses_on_the_biased_sigmoid_and_weighs_without_the_bias(params):
    lp = params["layers"][CFG.expert_layers[0]]
    xt = jax.random.normal(jax.random.PRNGKey(7), (64, CFG.hidden_size), jnp.float32)
    topi, topw = longcat.route(xt, lp, CFG)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(xt, lp["w_router"], precision="highest")), np.float64)
    biased = s + np.asarray(lp["router_bias"], np.float64)[None]
    want = np.argsort(-biased, axis=-1)[:, :CFG.num_experts_per_token]
    assert (np.sort(np.asarray(topi), axis=-1) == np.sort(want, axis=-1)).all()
    unbiased = np.argsort(-s, axis=-1)[:, :CFG.num_experts_per_token]
    assert (np.sort(want, axis=-1) != np.sort(unbiased, axis=-1)).any()  # the drawn bias moves a choice
    picked = np.take_along_axis(s, np.asarray(topi), axis=-1)
    np.testing.assert_allclose(np.asarray(topw), picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(topw).sum(-1), 1.0, atol=1e-4)


@pytest.mark.parametrize("experts", ["ragged_dot", "gmm_interpret"])
def test_the_expert_layer_is_a_plain_loop_over_the_chosen_experts(params, experts):
    lp = {**params["layers"][CFG.expert_layers[1]], "moe_layer": 0}
    h = jax.random.normal(jax.random.PRNGKey(8), (3, 16, CFG.hidden_size), jnp.float32)
    valid = jnp.ones((3, 16), bool).at[2, 9:].set(False)
    y, hist = longcat.moe(h, valid, lp, CFG, experts)
    topi, topw = (np.asarray(a) for a in longcat.route(h.reshape(-1, CFG.hidden_size), lp, CFG))
    x = np.asarray(h, np.float64).reshape(-1, CFG.hidden_size)
    want = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, w in zip(topi[t], topw[t]):
            g, u = x[t] @ np.asarray(lp["moe_gate"][e], np.float64), x[t] @ np.asarray(lp["moe_up"][e], np.float64)
            want[t] += w * ((g / (1 + np.exp(-g)) * u) @ np.asarray(lp["moe_down"][e], np.float64))
    live = np.asarray(valid).reshape(-1)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, CFG.hidden_size)[live], want[live], rtol=2e-4, atol=2e-4)
    E = CFG.num_experts
    assert int(hist[:E].sum()) == int(live.sum()) * CFG.num_experts_per_token and int(hist[E]) == int(hist[E + 1]) == 0


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
    return EngineArgs(**{**dict(model=CFG, block_size=BS, num_kv_blocks=24, max_num_seqs=4, max_model_len=128,
                                max_prefill_tokens=64, dtype="float32"), **kw})


async def _tokens(engine, req) -> list[int]:
    return [t async for o in engine.generate(req, Context()) for t in o.get("token_ids", [])]


def reference_greedy(ref_params, toks: list[int], n: int) -> list[int]:
    toks = list(toks)
    for _ in range(n):
        toks.append(int(jnp.argmax(REF.forward(DOC, ref_params, toks)[-1])))
    return toks[-n:]


@pytest.mark.parametrize("case", ["prefix_hit", "eviction", "preemption"])
def test_serving_through_the_prefix_cache_eviction_and_preemption(ref_params, case):
    """Through the scheduler: a resent prompt hits its cached blocks, conv
    state and all, and gives the tokens the reference's greedy decode gives;
    evicted blocks are recomputed to the same tokens; a sequence preempted for
    want of blocks returns behind its own sealed blocks and finishes with the
    tokens it would have had."""
    first = prompt(40, seed=1)

    async def go():
        if case == "preemption":  # 3 x (40 + 30) tokens want 27 blocks of a pool of 18
            engine = await TpuEngine(engine_args(num_kv_blocks=18)).start()
            try:
                alone = [await _tokens(engine, greedy(prompt(40, seed=s), 30)) for s in (1, 2, 3)]
                n0 = sum(engine.total_preemptions_by.values())
                together = await asyncio.gather(*(_tokens(engine, greedy(prompt(40, seed=s), 30)) for s in (1, 2, 3)))
                return alone, list(together), sum(engine.total_preemptions_by.values()) - n0, dict(engine.conv_resumes)
            finally:
                await engine.stop()
        engine = await TpuEngine(engine_args()).start()
        try:
            a = await _tokens(engine, greedy(first, 12))
            hits0 = engine.pool.hit_blocks
            if case == "eviction":  # fill the pool with other prompts until the first one's blocks go
                for s in range(10, 16):
                    await _tokens(engine, greedy(prompt(40, seed=s)))
            b = await _tokens(engine, greedy(first, 12))
            return a, b, engine.pool.hit_blocks - hits0, dict(engine.conv_resumes)
        finally:
            await engine.stop()

    a, b, n, resumes = asyncio.run(go())
    assert a == b
    assert resumes["recompute"] == 0
    if case == "prefix_hit":
        assert n == (40 - 1) // BS and resumes == {"cache": 1, "zero": 1, "recompute": 0}
        assert b == reference_greedy(ref_params, first, 12)
    elif case == "eviction":
        assert n < (40 - 1) // BS  # some of the history was gone and was recomputed
    else:
        # n preemptions; each return is one more prefill row, behind what is left of its own sealed
        # blocks (source cache) or, where the pressure took them too, from position 0
        assert n > 0 and resumes["cache"] + resumes["zero"] == 6 + n


def test_the_worker_says_what_it_runs_and_counts_where_conv_state_came_from():
    """Engine level, as ``test-tiny`` is served: the start line names the
    block, the attention paths and the grouped product; ``/metrics`` holds the
    resumes by source (all three from the start), the pool's bytes by kind and
    the routing counters by the model's own layer index."""
    async def go():
        engine = TpuEngine(engine_args())
        registry = MetricsRegistry()
        engine.bind_metrics(registry)
        await engine.start()
        try:
            line = engine._runner._start_line("")
            a = await _tokens(engine, greedy(prompt(40, seed=1)))
            b = await _tokens(engine, greedy(prompt(40, seed=1)))
            await engine.run_on_engine_thread(engine._update_gauges)
            return line, a, b, registry.render()
        finally:
            await engine.stop()

    line, a, b, page = asyncio.run(go())
    assert "decode=xla" in line and " block=lfm2 experts=ragged_dot" in line
    assert a == b and len(a) == 6
    args = engine_args()
    kinds = args.pool_bytes_per_block()
    assert kinds == {"kv": 2 * 1 * BS * CFG.kv_size * 4, "conv": 5 * 2 * CFG.hidden_size * 4}
    assert args.kv_bytes_per_block() == sum(kinds.values())
    for source, n in (("cache", 1), ("zero", 1), ("recompute", 0)):
        assert f'engine_conv_state_resumes_total{{source="{source}"}} {n}' in page, page
    for kind, per_block in kinds.items():
        assert f'kv_pool_bytes{{kind="{kind}"}} {per_block * args.num_kv_blocks}' in page
    assert 'moe_expert_calls_total{program="decode"}' in page
    layers = {line.split('layer="')[1].split('"')[0] for line in page.splitlines()
              if line.startswith("dynamo_tpu_moe_expert_tokens_total{")}
    assert layers <= {str(i) for i in CFG.expert_layers} and layers


def test_pool_accounting_counts_both_pools():
    args = engine_args(dtype="bfloat16")
    cache = lfm2.init_kv_cache(CFG, args.num_kv_blocks, BS)
    assert cache.kv.shape == (1, 24, 2, BS, CFG.kv_size) and cache.block_size == BS
    assert cache.conv.shape == (5, 2, 24, CFG.hidden_size)
    assert cache.kv.nbytes + cache.conv.nbytes == args.num_kv_blocks * args.kv_bytes_per_block()


# -- what refuses the block --------------------------------------------------------


@pytest.mark.parametrize("kw,names", [
    (dict(kv_quant="int8"), "--kv-quant int8"),
    (dict(quant="int8"), "--quant int8"),
    (dict(spec_tokens=2), "speculation"),
    (dict(lora_slots=2), "LoRA"),
    (dict(tp=2), "--tp"),
    (dict(host_kv_blocks=8), "KV tiers"),
    (dict(block_size=7, max_prefill_tokens=63), "--block-size 7"),
])
def test_engine_args_refuse_what_cannot_carry_the_block(kw, names):
    with pytest.raises(ValueError, match="lfm2") as e:
        engine_args(**kw)
    assert names in str(e.value)


@pytest.mark.parametrize("what", ["embed", "spec_verify", "extract_pages", "inject_pages", "transfer", "migration"])
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
        with pytest.raises(ValueError, match="block='lfm2'"):
            runner.extract_pages([1]) if what == "extract_pages" else runner.inject_pages([1], None, None)
    else:
        async def go():
            engine = await TpuEngine(engine_args()).start()
            try:
                if what == "transfer":
                    outs = [o async for o in engine.generate(greedy(prompt(20), peer_prefix={"num_blocks": 1}), Context())]
                    return outs[-1].get("error", "")
                got = await engine.run_on_engine_thread(lambda: engine.migration_begin("any"))
                return got.get("error", "")
            finally:
                await engine.stop()

        assert "conv-state pool" in asyncio.run(go())
