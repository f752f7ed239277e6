"""The load generator: one asyncio loop in the harness's own process sends
the plan's requests to ``/v1/completions`` and records what comes back.

Nothing here raises over a request. Timeout, non-200, an ``error`` frame, a
stream that ends without ``finish_reason`` or with another token count than
``max_tokens``: the request's record says ``failed`` and why. A stream still
open when the window closes is ``cut``.

The synthetic tokenizer (``run.py:write_tokenizer``) spells token ``i`` as
``T<hex i>``, so a chunk's text says how many tokens it carries and which.
"""

from __future__ import annotations

import asyncio
import json
import time

import aiohttp

from chipbench.procs import log

CONNECT_TIMEOUT_S = 10.0


def text_token_ids(text: str) -> list[int]:
    """Token ids back from the synthetic tokenizer's text."""
    out = []
    for word in text.split():
        if word[:1] == "T":
            try:
                out.append(int(word[1:], 16))
            except ValueError:
                pass
    return out


def new_record(kind: str, due: float | None, prompt_tokens: int, max_tokens: int) -> dict:
    return {"kind": kind, "due": due, "sent": None, "first": None, "last": None,
            "chunks": [], "status": "cut", "prompt_tokens": prompt_tokens,
            "max_tokens": max_tokens, "usage": None, "finish_reason": None,
            "error": None, "prompt": None, "answer": []}


async def complete(session: aiohttp.ClientSession, url: str, model: str, prompt: list[int],
                   rec: dict, deadline_s: float) -> dict:
    """One streamed completion into ``rec``, which keeps the prompt and the
    token ids that came back: the next turn of a session resends them, and
    ``parity.py`` holds a sample of them against the reference. Never raises,
    except for the cancellation that cuts it at the end of the window."""
    body = {"model": model, "prompt": prompt, "max_tokens": rec["max_tokens"],
            "temperature": 0, "ignore_eos": True, "stream": True}
    rec["prompt"] = prompt
    rec["sent"] = time.monotonic()
    try:
        timeout = aiohttp.ClientTimeout(total=deadline_s, sock_connect=CONNECT_TIMEOUT_S)
        async with session.post(url, json=body, timeout=timeout) as resp:
            if resp.status != 200:
                rec["status"], rec["error"] = "failed", f"HTTP {resp.status}: {(await resp.text())[:200]}"
                return rec
            async for raw in resp.content:
                if not raw.startswith(b"data:"):
                    continue
                payload = raw[5:].strip()
                if payload == b"[DONE]":
                    break
                doc = json.loads(payload)
                if "error" in doc:
                    rec["error"] = json.dumps(doc["error"])[:200]
                    continue
                now = time.monotonic()
                choice = doc["choices"][0]
                text = choice.get("text") or ""
                if text:
                    ids = text_token_ids(text)
                    if rec["first"] is None:
                        rec["first"] = now
                    rec["last"] = now
                    rec["chunks"].append((now, len(ids)))
                    rec["answer"] += ids
                if choice.get("finish_reason"):
                    rec["finish_reason"] = choice["finish_reason"]
                    rec["last"] = rec["last"] or now
                if doc.get("usage"):
                    rec["usage"] = doc["usage"]
        why = judge(rec)
        rec["status"], rec["error"] = ("ok" if why is None else "failed"), why
    except asyncio.CancelledError:
        raise  # cut: status stays "cut"
    except Exception as e:  # noqa: BLE001 - a request's failure is a count, not the run's
        rec["status"], rec["error"] = "failed", f"{type(e).__name__}: {e}"[:200]
    return rec


def judge(rec: dict) -> str | None:
    """Why a finished stream does not count as completed; None if it does."""
    if rec["error"]:
        return rec["error"]
    if rec["finish_reason"] != "length":
        return f"finish_reason {rec['finish_reason']!r}"
    usage = rec["usage"] or {}
    seen = sum(k for _, k in rec["chunks"])
    if usage.get("completion_tokens") != rec["max_tokens"] or seen != rec["max_tokens"]:
        return (f"{usage.get('completion_tokens')} completion tokens in usage, {seen} in "
                f"the text, max_tokens {rec['max_tokens']}")
    if usage.get("prompt_tokens") != rec["prompt_tokens"]:
        return f"usage counts {usage.get('prompt_tokens')} prompt tokens of {rec['prompt_tokens']} sent"
    return None


async def run_open(session, url, model, requests: list[dict], t0: float, seconds: float,
                   records: list[dict]) -> None:
    """Send each request at ``t0 + due`` whatever the server does; stop at
    ``t0 + seconds`` and cut what is still in flight."""
    tasks = []
    for req in requests:
        delay = t0 + req["due"] - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        if time.monotonic() >= t0 + seconds:
            break
        rec = new_record("open", t0 + req["due"], len(req["prompt"]), req["max_tokens"])
        records.append(rec)
        tasks.append(asyncio.ensure_future(
            complete(session, url, model, req["prompt"], rec, seconds + 30.0)))
    await cut_at(tasks, t0 + seconds)


async def run_closed(session, url, model, plan: dict, t0: float, seconds: float,
                     records: list[dict]) -> None:
    """Each client sends its next turn when the last one answered and its
    think time has passed; all stop at ``t0 + seconds``."""
    async def client(script: dict) -> None:
        history = list(script["prefill"])
        for turn in script["turns"]:
            await asyncio.sleep(turn["think_s"])
            if turn["base"] is not None:
                history = list(plan["system_prompts"][turn["base"]])
            prompt = history + turn["new"]
            rec = new_record("closed", None, len(prompt), turn["max_tokens"])
            rec["history_tokens"] = len(history)  # sent before: the prefix cache may hold it
            records.append(rec)
            await complete(session, url, model, prompt, rec, seconds + 30.0)
            # A failed turn leaves a shorter history; the session goes on.
            history = prompt + rec["answer"]
        rec_done.append(1)

    rec_done: list[int] = []
    tasks = [asyncio.ensure_future(client(c)) for c in plan["clients"]]
    await cut_at(tasks, t0 + seconds)
    if rec_done:
        log(f"{len(rec_done)} clients ran out of turns before the window closed")


async def cut_at(tasks: list, t_end: float) -> None:
    delay = t_end - time.monotonic()
    if tasks and delay > 0:
        await asyncio.wait(tasks, timeout=delay)
    for t in tasks:
        if not t.done():
            t.cancel()
    if tasks:
        await asyncio.gather(*tasks, return_exceptions=True)


async def one_shot(session, url, model, prompt: list[int], max_tokens: int,
                   deadline_s: float) -> dict:
    """A warm-up or pre-fill request, outside any window."""
    rec = new_record("closed", None, len(prompt), max_tokens)
    try:
        return await complete(session, url, model, prompt, rec, deadline_s)
    except asyncio.CancelledError:
        return rec


async def get_text(session, url: str, timeout_s: float = 5.0) -> str | None:
    """GET a page; None (never an exception) when the scrape fails."""
    try:
        async with session.get(url, timeout=aiohttp.ClientTimeout(total=timeout_s)) as resp:
            if resp.status != 200:
                return None
            return await resp.text()
    except Exception:  # noqa: BLE001 - a failed scrape is a count
        return None
