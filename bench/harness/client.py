"""The load client: one asyncio loop, streamed chat completions over SSE.

Every request is a :class:`Record`.  Its clock starts when it was *due*,
not when it was sent, so a stall that delays later requests is charged to
them.  All times are ``time.monotonic()`` seconds.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import Dict, List, Optional

import aiohttp


@dataclasses.dataclass
class Record:
    phase: str                   # warmup | seed | measure
    due: float                   # when the generator meant to send it
    asked: int                   # max_tokens == min_tokens
    meta: Dict = dataclasses.field(default_factory=dict)
    sent: Optional[float] = None
    first: Optional[float] = None   # first SSE data event (first token)
    last: Optional[float] = None    # event that carried finish_reason
    ended: Optional[float] = None   # stream closed
    status: Optional[int] = None
    error: Optional[str] = None
    finish_reason: Optional[str] = None
    prompt_tokens: Optional[int] = None
    completion_tokens: Optional[int] = None
    events: int = 0
    done: bool = False              # [DONE] received


def chat_body(model: str, messages: List[Dict], max_tokens: int) -> Dict:
    """Greedy, exactly ``max_tokens`` tokens (random weights: no early EOS),
    streamed, with the usage block on the last chunk."""
    return {
        "model": model, "messages": messages, "max_tokens": max_tokens,
        "min_tokens": max_tokens, "temperature": 0.0, "stream": True,
        "stream_options": {"include_usage": True},
    }


class Client:
    def __init__(self, model: str, request_timeout_s: float = 300.0):
        self.model = model
        self.base_url: Optional[str] = None
        self.records: List[Record] = []
        self._timeout = aiohttp.ClientTimeout(total=request_timeout_s)
        self._session: Optional[aiohttp.ClientSession] = None

    async def __aenter__(self) -> "Client":
        self._session = aiohttp.ClientSession(
            timeout=self._timeout,
            connector=aiohttp.TCPConnector(limit=0),
        )
        return self

    async def __aexit__(self, *exc) -> None:
        await self._session.close()

    def target(self, base_url: str) -> None:
        self.base_url = base_url

    async def chat(self, phase: str, messages: List[Dict], max_tokens: int,
                   due: Optional[float] = None,
                   meta: Optional[Dict] = None) -> Record:
        """Send one streamed chat completion (sleeping until ``due`` first)
        and read it to the end.  Never raises for a failed request: the
        record says what happened."""
        now = time.monotonic()
        rec = Record(phase=phase, due=now if due is None else due,
                     asked=max_tokens, meta=meta or {})
        self.records.append(rec)
        if rec.due > now:
            await asyncio.sleep(rec.due - now)
        body = json.dumps(chat_body(self.model, messages, max_tokens))
        rec.sent = time.monotonic()
        try:
            async with self._session.post(
                self.base_url + "/v1/chat/completions", data=body,
                headers={"content-type": "application/json"},
            ) as resp:
                rec.status = resp.status
                if resp.status != 200:
                    rec.error = (await resp.text())[:300]
                    return rec
                async for raw in resp.content:
                    line = raw.strip()
                    if not line.startswith(b"data:"):
                        continue
                    data = line[5:].strip()
                    now = time.monotonic()
                    if data == b"[DONE]":
                        rec.done = True
                        break
                    event = json.loads(data)
                    if "error" in event:
                        rec.error = json.dumps(event["error"])[:300]
                        continue
                    rec.events += 1
                    if rec.first is None:
                        rec.first = now
                    if event.get("usage"):
                        rec.prompt_tokens = event["usage"]["prompt_tokens"]
                        rec.completion_tokens = (
                            event["usage"]["completion_tokens"])
                    for choice in event.get("choices", []):
                        if choice.get("finish_reason"):
                            rec.finish_reason = choice["finish_reason"]
                            rec.last = now
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
            rec.error = f"{type(e).__name__}: {e}"[:300]
        finally:
            rec.ended = time.monotonic()
        return rec

    async def get_text(self, url: str) -> str:
        async with self._session.get(url) as resp:
            text = await resp.text()
            if resp.status != 200:
                raise RuntimeError(f"GET {url}: {resp.status} {text[:300]}")
            return text

    async def get_json(self, url: str):
        return json.loads(await self.get_text(url))

    async def post_json(self, url: str, body: Dict):
        async with self._session.post(url, json=body) as resp:
            text = await resp.text()
            if resp.status != 200:
                raise RuntimeError(f"POST {url}: {resp.status} {text[:300]}")
            return json.loads(text)
