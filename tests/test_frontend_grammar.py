"""Frontend e2e for structured output: /v1/chat/completions with
``response_format`` (and the Responses API ``text.format`` mapping)
served end to end by a REAL TpuEngine worker — the full request path
(HTTP parse → preprocessor validation → wire → engine token-mask FSM →
detokenized response) returns parseable, schema-valid JSON; malformed
schemas 400 at the frontend with a typed OpenAI error body."""

import asyncio
import json

import pytest

from dynamo_tpu.engine.grammar import _ByteDfa, compile_response_format_regex
from dynamo_tpu.kv_router.publisher import KvEventBroadcaster, serve_kv_endpoints
from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
from dynamo_tpu.llm.http_service import HttpService
from dynamo_tpu.llm.model_card import ModelDeploymentCard, register_model
from dynamo_tpu.llm.pipeline import RouterSettings
from dynamo_tpu.llm.protocols import OpenAIError, ResponsesRequest
from dynamo_tpu.llm.client import OpenAIClient, OpenAIClientError
from dynamo_tpu.llm.tokenizer import ByteTokenizer
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.push_router import RouterMode

SCHEMA = {"type": "object", "properties": {
    "name": {"type": "string", "maxLength": 8},
    "ok": {"type": "boolean"},
}}
FORCED_SCHEMA = {"type": "object", "properties": {
    "kind": {"const": "record"},
    "ok": {"const": True},
}}
RESPONSE_FORMAT = {"type": "json_schema",
                   "json_schema": {"name": "extract", "schema": SCHEMA}}


def _assert_schema_valid(text: str):
    obj = json.loads(text)
    assert set(obj) == {"name", "ok"}
    assert isinstance(obj["name"], str) and len(obj["name"]) <= 8
    assert isinstance(obj["ok"], bool)


async def _start_stack(url: str):
    from dynamo_tpu.engine.config import EngineArgs, ModelConfig
    from dynamo_tpu.engine.engine import TpuEngine

    rt = await DistributedRuntime.create(store_url=url)
    # Under -n 6 on a loaded machine one 200-token grammar request holds this
    # process 12-15 s, past the default 10 s lease: the worker then leaves
    # discovery and the next request is a 404. The lease is not what is tested.
    rt.config.store.lease_ttl = 120.0
    engine = await TpuEngine(EngineArgs(
        model=ModelConfig(), block_size=4, num_kv_blocks=320, max_num_seqs=8,
        max_model_len=256, max_prefill_tokens=128, dtype="float32",
        decode_steps=4, spec_tokens=8, spec_tree_width=2, spec_gate=0.0,
    )).start()
    broadcaster = KvEventBroadcaster(engine.pool)
    engine.pool.set_event_sink(broadcaster.publish)
    comp = rt.namespace("e2e").component("backend")

    async def gen_handler(payload, ctx):
        async for item in engine.generate(payload, ctx):
            yield item

    await comp.endpoint("generate").serve(gen_handler)
    await serve_kv_endpoints(comp, broadcaster, engine.metrics)
    card = ModelDeploymentCard(
        name="tiny", kv_cache_block_size=4,
        eos_token_ids=[ByteTokenizer.EOS], context_length=256,
    )
    await register_model(rt, "e2e", card)

    frt = await DistributedRuntime.create(store_url=url)
    manager = ModelManager(frt, RouterSettings(mode=RouterMode.ROUND_ROBIN))
    watcher = await ModelWatcher(frt, manager).start()
    http = await HttpService(
        manager, frt.metrics, health=frt.health, host="127.0.0.1", port=0
    ).start()
    return rt, engine, frt, manager, watcher, http


def test_chat_response_format_returns_schema_valid_json():
    async def go():
        rt, engine, frt, manager, watcher, http = await _start_stack(
            "memory://fe_grammar"
        )
        try:
            async with OpenAIClient(f"http://127.0.0.1:{http.port}",
                                    default_model="tiny") as client:
                # json_schema: the completion must parse AND validate
                resp = await client.chat(
                    [{"role": "user", "content": "extract the record"}],
                    max_tokens=160, temperature=0.0, seed=0,
                    response_format=RESPONSE_FORMAT,
                )
                choice = resp["choices"][0]
                assert choice["finish_reason"] == "stop"
                _assert_schema_valid(choice["message"]["content"])

                # a schema of constants leaves the model no choice: the
                # whole completion is forced, whatever the weights emit
                forced = await client.chat(
                    [{"role": "user", "content": "say it"}],
                    max_tokens=64, temperature=0.0, seed=4,
                    response_format={"type": "json_schema", "json_schema": {
                        "name": "forced", "schema": FORCED_SCHEMA}},
                )
                assert forced["choices"][0]["finish_reason"] == "stop"
                assert json.loads(forced["choices"][0]["message"]["content"]) == {
                    "kind": "record", "ok": True}

                # json_object mode: strings have no length bound there, so
                # a random-weight model may ramble to max_tokens. What the
                # mask guarantees is that every served byte keeps the text
                # a prefix of a JSON object, and a finished one parses.
                resp2 = await client.chat(
                    [{"role": "user", "content": "give me json"}],
                    max_tokens=200, temperature=0.0, seed=1,
                    response_format={"type": "json_object"},
                )
                choice2 = resp2["choices"][0]
                text2 = choice2["message"]["content"]
                dfa = _ByteDfa(compile_response_format_regex({"type": "json_object"}))
                state = dfa.walk(dfa.start, text2.encode())
                assert state is not None, f"not a prefix of a JSON object: {text2!r}"
                assert choice2["finish_reason"] in ("stop", "length")
                if choice2["finish_reason"] == "stop":
                    assert dfa.accepting(state)
                    assert isinstance(json.loads(text2), dict)

                # streaming path: concatenated deltas are schema-valid too
                parts = []
                finish = None
                async for chunk in client.chat_stream(
                    [{"role": "user", "content": "extract again"}],
                    max_tokens=160, temperature=0.0, seed=2,
                    response_format=RESPONSE_FORMAT,
                ):
                    d = chunk["choices"][0]["delta"]
                    if d.get("content"):
                        parts.append(d["content"])
                    if chunk["choices"][0].get("finish_reason"):
                        finish = chunk["choices"][0]["finish_reason"]
                assert finish == "stop"
                _assert_schema_valid("".join(parts))

                # malformed schema → 400 with a typed OpenAI error body
                with pytest.raises(OpenAIClientError) as ei:
                    await client.chat(
                        [{"role": "user", "content": "x"}],
                        response_format={"type": "json_schema",
                                         "json_schema": {"schema": {"type": "zzz"}}},
                    )
                assert ei.value.status == 400
                assert "response_format" in ei.value.body["error"]["message"]

                # malformed wire shape → 400 too
                with pytest.raises(OpenAIClientError) as ei2:
                    await client.chat(
                        [{"role": "user", "content": "x"}],
                        response_format={"type": "json_schema"},
                    )
                assert ei2.value.status == 400

                # Responses API: text.format maps to response_format
                # instead of the old 501 rejection
                r3 = await client.responses(
                    "extract the record", max_output_tokens=160,
                    temperature=0.0, seed=3,
                    text={"format": {"type": "json_schema", "name": "extract",
                                     "schema": SCHEMA}},
                )
                assert r3["status"] == "completed"
                _assert_schema_valid(r3["output"][0]["content"][0]["text"])
        finally:
            await http.close()
            await engine.stop()
            await frt.shutdown()
            await rt.shutdown()

    asyncio.run(go())


def test_responses_text_format_protocol_mapping():
    base = {"model": "m", "input": "hi"}
    # noop forms
    assert ResponsesRequest.parse(base).response_format is None
    assert ResponsesRequest.parse(
        {**base, "text": {"format": {"type": "text"}}}
    ).response_format is None
    # json_object
    assert ResponsesRequest.parse(
        {**base, "text": {"format": {"type": "json_object"}}}
    ).response_format == {"type": "json_object"}
    # json_schema flattens name/schema/strict into format
    req = ResponsesRequest.parse(
        {**base, "text": {"format": {"type": "json_schema", "name": "n",
                                     "schema": SCHEMA, "strict": True}}}
    )
    assert req.response_format == {
        "type": "json_schema",
        "json_schema": {"schema": SCHEMA, "name": "n", "strict": True},
    }
    assert req.to_chat().response_format == req.response_format
    # malformed format type is a 400, not a 501
    with pytest.raises(OpenAIError) as ei:
        ResponsesRequest.parse({**base, "text": {"format": {"type": "bogus"}}})
    assert ei.value.status == 400
    # unimplemented text.* options keep their explicit 501 (they were
    # never silently droppable)
    with pytest.raises(OpenAIError) as ei2:
        ResponsesRequest.parse({**base, "text": {"verbosity": "low"}})
    assert ei2.value.status == 501
