"""Profiler sweep: engine → (batch → ITL/tok_s, prompt_len → TTFT) npz
for the SLA planner's interpolators.

Reference analogue: the reference profiler's profile_sla.py (TP×load sweeps →
npz read by perf_interpolation.py). Run on the serving chip:

  python tools/profile_sweep.py --model llama-1b --out profile_llama1b.npz
  python -m dynamo_tpu.planner --profile profile_llama1b.npz --itl-sla-ms 50 ...
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="llama-1b")
    p.add_argument("--out", default="profile.npz")
    p.add_argument("--batches", default="8,16,32,64,128")
    p.add_argument("--prompt-lens", default="64,128,256,512,1024")
    p.add_argument("--gen-len", type=int, default=96)
    p.add_argument("--decode-steps", type=int, default=32)
    p.add_argument("--cpu", action="store_true")
    return p.parse_args()


async def sweep(args):
    import jax

    from dynamo_tpu.engine.compile_cache import configure_compile_cache
    from dynamo_tpu.engine.config import EngineArgs, ModelConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.planner.interpolate import DecodeInterpolator, PrefillInterpolator, save_profile
    from dynamo_tpu.runtime.engine import Context

    configure_compile_cache()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        model = ModelConfig.preset("test-tiny")
    else:
        model = ModelConfig.preset(args.model)

    batches = [int(b) for b in args.batches.split(",")]
    prompt_lens = [int(b) for b in args.prompt_lens.split(",")]
    max_b = max(batches)
    block_size = 16
    seq_len = max(prompt_lens) + args.gen_len + args.decode_steps
    blocks_per_seq = (seq_len + block_size - 1) // block_size + 1
    eargs = EngineArgs(
        model=model, block_size=block_size,
        num_kv_blocks=max(max_b * blocks_per_seq, 256),
        max_num_seqs=max_b, max_model_len=(blocks_per_seq + 1) * block_size,
        max_prefill_tokens=max(512, max(prompt_lens)),
        dtype="float32" if args.cpu else "bfloat16",
        decode_steps=args.decode_steps,
    )
    engine = await TpuEngine(eargs, seed=0).start()
    rng = np.random.default_rng(0)

    def req(plen: int, gen: int) -> PreprocessedRequest:
        r = PreprocessedRequest(
            model=model.name,
            token_ids=rng.integers(1, model.vocab_size - 1, size=plen).tolist(),
        )
        r.sampling.temperature = 0.0
        r.stop.max_tokens = gen
        r.stop.ignore_eos = True
        return r

    async def run_one(r, rec=None):
        t0 = time.perf_counter()
        n, t_first, t_last = 0, None, None
        async for item in engine.generate(r, Context()):
            if item.get("token_ids"):
                t_last = time.perf_counter()
                t_first = t_first or t_last
                n += len(item["token_ids"])
        if rec is not None:
            rec.append((t0, t_first, t_last, n))
        return n

    # Decode sweep: hold batch occupancy at B, measure steady token rate.
    d_itl, d_tok = [], []
    for B in batches:
        await asyncio.gather(*(run_one(req(64, args.decode_steps + 2)) for _ in range(B)))  # warm
        t0 = time.perf_counter()
        recs: list = []
        await asyncio.gather(*(run_one(req(64, args.gen_len), recs) for _ in range(B)))
        el = time.perf_counter() - t0
        total = sum(r[3] for r in recs)
        tok_s = total / el
        itl_ms = 1000.0 * B / tok_s  # per-sequence inter-token latency at occupancy B
        d_itl.append(itl_ms)
        d_tok.append(tok_s)
        print(f"decode B={B}: {tok_s:.0f} tok/s, itl {itl_ms:.1f} ms", flush=True)

    # Prefill sweep: single-request TTFT per prompt length on idle engine.
    p_ttft, p_tok = [], []
    for plen in prompt_lens:
        await run_one(req(plen, 2))  # warm the bucket
        recs = []
        await run_one(req(plen, 2), recs)
        t0, t_first, _, _ = recs[0]
        ttft_ms = (t_first - t0) * 1000
        p_ttft.append(ttft_ms)
        p_tok.append(plen / (t_first - t0))
        print(f"prefill len={plen}: ttft {ttft_ms:.1f} ms", flush=True)

    await engine.stop()
    save_profile(
        args.out,
        decode=DecodeInterpolator(np.array(batches), np.array(d_itl), np.array(d_tok)),
        prefill=PrefillInterpolator(np.array(prompt_lens), np.array(p_ttft), np.array(p_tok)),
        meta={"model": model.name, "device": "cpu" if args.cpu else "tpu",
              "decode_steps": args.decode_steps},
    )
    print(f"wrote {args.out}")


if __name__ == "__main__":
    asyncio.run(sweep(parse_args()))
