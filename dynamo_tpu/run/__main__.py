"""Single-process launcher: `python -m dynamo_tpu.run --in text|http|batch:F`.

Reference analogue: the `dynamo-run` binary (reference: launch/dynamo-run/
src/opt.rs:7-33 — `in=[http|text|batch] out=<engine>`): smoke-test an
engine end to end without standing up store + worker + frontend. The
whole LLM chain (preprocessor → backend → engine) runs in this one
process; `--in http` serves the full OpenAI surface on localhost.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import signal
import sys

from dynamo_tpu.llm.backend import Backend
from dynamo_tpu.llm.http_service import HttpService
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.pipeline import ModelPipeline
from dynamo_tpu.llm.protocols import ChatCompletionRequest, CompletionRequest
from dynamo_tpu.llm.tokenizer import ByteTokenizer, load_tokenizer, parse_tokenizer_spec
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.metrics import MetricsRegistry


def parse_args(argv=None):
    p = argparse.ArgumentParser(prog="dynamo_tpu.run")
    p.add_argument("--in", dest="input", default="text",
                   help="text | http | batch:<jsonl path>")
    p.add_argument("--engine", choices=["tpu", "mocker"], default="tpu")
    p.add_argument("--preset", default="test-tiny")
    p.add_argument("--model-path", default=None)
    p.add_argument("--tokenizer", default="byte")
    p.add_argument("--max-tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--num-kv-blocks", type=int, default=512)
    p.add_argument("--max-num-seqs", type=int, default=8)
    p.add_argument("--max-model-len", type=int, default=2048)
    p.add_argument("--decode-steps", type=int, default=8)
    p.add_argument("--dtype", default=None, help="default: bfloat16 on TPU, float32 on CPU")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


class LocalPipeline(ModelPipeline):
    """ModelPipeline wired straight to an in-process engine (no router,
    no store): Backend(engine) replaces the network chain."""

    def __init__(self, card, engine, tokenizer):
        super().__init__(namespace="local", card=card, runtime=None)
        self.engine = engine
        self.backend = Backend(engine, tokenizer)

    async def embed(self, token_ids):
        return await self.engine.embed(token_ids)

    async def clear_kv_blocks(self):
        return {"local": self.engine.clear_kv_blocks()}


class LocalManager:
    def __init__(self, pipe: LocalPipeline):
        self.pipe = pipe

    def get(self, model_name: str):
        return self.pipe if model_name == self.pipe.card.name else None

    def list_names(self):
        return [self.pipe.card.name]

    def items(self):
        return [(self.pipe.card.name, self.pipe)]


async def build_pipeline(args) -> LocalPipeline:
    if args.engine == "mocker":
        from dynamo_tpu.mocker.engine import MockerArgs, MockerEngine

        engine = MockerEngine(MockerArgs(block_size=args.block_size,
                                         num_kv_blocks=args.num_kv_blocks))
        tokenizer = ByteTokenizer()
        name = "mock-model"
    else:
        import jax

        from dynamo_tpu.engine.compile_cache import configure_compile_cache
        from dynamo_tpu.engine.config import EngineArgs, ModelConfig
        from dynamo_tpu.engine.engine import TpuEngine

        configure_compile_cache()
        if args.dtype is None:
            # The runner's start line names the platform and dtype chosen.
            args.dtype = "bfloat16" if jax.default_backend() == "tpu" else "float32"
        params = None
        if args.model_path:
            from dynamo_tpu.engine.hub import is_gguf, resolve_model
            from dynamo_tpu.engine.loader import load_model

            args.model_path = resolve_model(args.model_path)
            model, params = load_model(args.model_path, args.dtype)
            if args.tokenizer == "byte":
                prefix = "gguf:" if is_gguf(args.model_path) else "hf:"
                args.tokenizer = prefix + args.model_path
        else:
            model = ModelConfig.preset(args.preset)
        engine = await TpuEngine(EngineArgs(
            model=model, block_size=args.block_size,
            num_kv_blocks=args.num_kv_blocks, max_num_seqs=args.max_num_seqs,
            max_model_len=args.max_model_len, dtype=args.dtype,
            decode_steps=args.decode_steps,
            # response_format token-mask FSMs compile over the SERVING
            # tokenizer's vocabulary (engine/grammar.py).
            grammar_tokenizer=parse_tokenizer_spec(args.tokenizer),
        ), params=params, seed=args.seed).start()
        tokenizer = load_tokenizer(parse_tokenizer_spec(args.tokenizer))
        name = model.name
    card = ModelDeploymentCard(
        name=name,
        tokenizer=parse_tokenizer_spec(args.tokenizer),
        context_length=args.max_model_len,
        kv_cache_block_size=args.block_size,
        eos_token_ids=list(tokenizer.eos_token_ids) or [ByteTokenizer.EOS],
    )
    return LocalPipeline(card, engine, tokenizer)


async def run_text(args, pipe: LocalPipeline) -> None:
    print(f"dynamo_tpu.run: {pipe.card.name} ready. Empty line or Ctrl-D exits.", flush=True)
    loop = asyncio.get_running_loop()
    while True:
        try:
            line = await loop.run_in_executor(None, lambda: input("> "))
        except (EOFError, KeyboardInterrupt):
            break
        if not line.strip():
            break
        req = CompletionRequest.parse({
            "model": pipe.card.name, "prompt": line,
            "max_tokens": args.max_tokens, "temperature": args.temperature,
            "stream": True,
        })
        async for _gen, chunk in pipe.run(req, Context()):
            if chunk is not None:
                text = chunk["choices"][0].get("text") or ""
                print(text, end="", flush=True)
        print(flush=True)


async def run_batch(args, pipe: LocalPipeline, path: str) -> None:
    """Each input line: JSON {"prompt": ...} or raw text. Emits JSONL
    results on stdout (reference: entrypoint/input/batch.rs)."""
    n = 0
    with open(path) as f:
        lines = [ln.rstrip("\n") for ln in f if ln.strip()]
    for ln in lines:
        obj: dict = {}
        try:
            parsed = json.loads(ln)
            prompt = parsed["prompt"] if isinstance(parsed, dict) else str(parsed)
            if isinstance(parsed, dict):
                obj = parsed  # only dicts WITH a prompt contribute overrides
        except (json.JSONDecodeError, KeyError):
            prompt = ln
        # Per-line sampling overrides win over the CLI defaults.
        req = CompletionRequest.parse({
            "model": pipe.card.name, "prompt": prompt,
            "max_tokens": obj.get("max_tokens", args.max_tokens),
            "temperature": obj.get("temperature", args.temperature),
            "top_p": obj.get("top_p"), "seed": obj.get("seed"),
            "stop": obj.get("stop"),
        })
        gen = None
        async for g, _chunk in pipe.run(req, Context()):
            gen = g
        out = gen.final_response()
        print(json.dumps({
            "prompt": prompt,
            "text": out["choices"][0]["text"],
            "finish_reason": out["choices"][0]["finish_reason"],
            "completion_tokens": out["usage"]["completion_tokens"],
        }), flush=True)
        n += 1
    print(f"dynamo_tpu.run: batch done ({n} prompts)", file=sys.stderr, flush=True)


async def run_http(args, pipe: LocalPipeline) -> None:
    http = await HttpService(
        LocalManager(pipe), MetricsRegistry(), host=args.host, port=args.port
    ).start()
    print(f"dynamo_tpu.run: http://{args.host}:{http.port} serving {pipe.card.name}", flush=True)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        with contextlib.suppress(NotImplementedError):
            loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    # Same SIGTERM contract as the distributed frontend: shed new work,
    # finish in-flight streams, then close.
    from dynamo_tpu.runtime.config import global_config

    http.start_draining()
    await http.wait_drained(global_config().runtime.graceful_shutdown_timeout)
    await http.close()


async def async_main(args) -> None:
    pipe = await build_pipeline(args)
    try:
        if args.input == "text":
            await run_text(args, pipe)
        elif args.input == "http":
            await run_http(args, pipe)
        elif args.input.startswith("batch:"):
            await run_batch(args, pipe, args.input[len("batch:"):])
        else:
            raise SystemExit(f"unknown --in {args.input!r} (text | http | batch:<path>)")
    finally:
        stop_fn = getattr(pipe.engine, "stop", None)
        if stop_fn is not None:
            await stop_fn()


def main(argv=None) -> int:
    asyncio.run(async_main(parse_args(argv)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
