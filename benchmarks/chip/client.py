"""One streamed ``/v1/completions`` request over HTTP, timed on this
process's clock (the SSE client of ``chip_smoke.py``, made asynchronous so
one thread drives every stream).  No JAX anywhere near it.
"""

from __future__ import annotations

import asyncio
import json
import re
import time

import aiohttp

# the byte tokenizer renders id >= 256 as " t<id>" and id < 256 as one
# character; an event's token count is read off its text
_TOKEN = re.compile(r" t\d+|.", re.S)


def count_tokens(text: str) -> int:
    return len(_TOKEN.findall(text))


async def stream_completion(session: aiohttp.ClientSession, port: int,
                            model: str, token_ids: list, max_tokens: int,
                            rec: dict) -> dict:
    """Fills ``rec`` with status, events ``[[t, n_tokens], ...]``, done,
    completion_tokens (from the final usage frame), errors, end_t."""
    rec.update(status=None, events=[], done=False, errors=[],
               completion_tokens=None, max_tokens=max_tokens,
               prompt_tokens_sent=len(token_ids))
    body = {"model": model, "prompt": token_ids, "max_tokens": max_tokens,
            "stream": True, "ignore_eos": True, "temperature": 0.0}
    rec["send_t"] = time.monotonic()
    events = rec["events"]
    try:
        async with session.post(
                f"http://127.0.0.1:{port}/v1/completions", json=body) as r:
            rec["status"] = r.status
            if r.status != 200:
                rec["errors"].append((await r.text())[:300])
                return rec
            async for raw in r.content:
                now = time.monotonic()
                line = raw.decode(errors="replace").strip()
                if line.startswith("event:") and "error" in line:
                    rec["errors"].append(line)
                if not line.startswith("data:"):
                    continue
                payload = line[5:].strip()
                if payload == "[DONE]":
                    rec["done"] = True
                    break
                ev = json.loads(payload)
                if "error" in ev:
                    rec["errors"].append(json.dumps(ev)[:300])
                    continue
                ch = (ev.get("choices") or [{}])[0]
                text = ch.get("text")
                if text:
                    events.append([now, count_tokens(text)])
                if ev.get("usage"):
                    rec["completion_tokens"] = ev["usage"].get(
                        "completion_tokens")
    except asyncio.CancelledError:
        rec["cut"] = True
        raise
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
            OSError) as e:
        rec["errors"].append(f"{type(e).__name__}: {e}")
    if events:
        rec["end_t"] = events[-1][0]
        got = sum(n for _, n in events)
        if rec["completion_tokens"] is not None \
                and rec["completion_tokens"] != got:
            # a token that detokenised to nothing, or text that parses two
            # ways: the usage frame is the count; the difference lands on
            # the last event and is reported as untimed
            rec["untimed_tokens"] = rec["completion_tokens"] - got
            events[-1][1] += rec["completion_tokens"] - got
    return rec


def new_session(limit: int = 512) -> aiohttp.ClientSession:
    return aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(limit=limit),
        timeout=aiohttp.ClientTimeout(total=None, sock_read=600))
