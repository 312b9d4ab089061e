"""HTTP client of the served path: streamed ``/v1/completions`` with
token-id prompts, and small JSON calls.  The SSE reader is copied from
``dynamo_tpu/bench_serving.py`` (``_body_lines``, ``_sse_request``) and
changed to record when every chunk arrived.  No JAX."""

from __future__ import annotations

import asyncio
import contextlib
import json
import time
from typing import Any, AsyncIterator, Dict, Optional, Tuple

MODEL = "bench"


async def _head(reader: asyncio.StreamReader) -> Tuple[int, Dict[str, str]]:
    status = int((await reader.readline()).split()[1])
    headers: Dict[str, str] = {}
    while True:
        raw = await reader.readline()
        if not raw.strip():
            return status, headers
        k, _, v = raw.decode("latin1").partition(":")
        headers[k.strip().lower()] = v.strip()


async def _body_lines(
    reader: asyncio.StreamReader, headers: Dict[str, str]
) -> AsyncIterator[bytes]:
    """Body lines with the HTTP framing decoded; a chunk may end mid-line."""
    buf = b""
    if headers.get("transfer-encoding", "").lower() == "chunked":
        while True:
            size_line = await reader.readline()
            try:
                size = int(size_line.strip().split(b";")[0], 16)
            except ValueError:
                break
            if size == 0:
                await reader.readline()
                break
            buf += await reader.readexactly(size)
            await reader.readexactly(2)
            while b"\n" in buf:
                line, buf = buf.split(b"\n", 1)
                yield line
    else:
        n = headers.get("content-length")
        buf = await (reader.readexactly(int(n)) if n else reader.read())
        while b"\n" in buf:
            line, buf = buf.split(b"\n", 1)
            yield line
    if buf:
        yield buf


async def call(
    host: str, port: int, method: str, path: str, body: Any = None,
    timeout: float = 600.0,
) -> Tuple[int, bytes]:
    """One plain HTTP call; returns (status, body)."""
    data = b"" if body is None else json.dumps(body).encode()

    async def go():
        reader, writer = await asyncio.open_connection(host, port)
        try:
            writer.write(
                f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(data)}\r\nConnection: close\r\n\r\n".encode()
                + data
            )
            await writer.drain()
            status, headers = await _head(reader)
            out = b"\n".join([l async for l in _body_lines(reader, headers)])
            return status, out
        finally:
            writer.close()
            with contextlib.suppress(Exception):
                await writer.wait_closed()

    return await asyncio.wait_for(go(), timeout)


async def call_json(host, port, method, path, body=None, timeout=600.0) -> Any:
    status, out = await call(host, port, method, path, body, timeout)
    if status != 200:
        raise RuntimeError(f"{method} {path}: HTTP {status}: {out[:300]!r}")
    return json.loads(out)


def _words(text: str) -> int:
    """Tokens in a chunk's text: the benchmark's tokenizer renders every
    token id as one word."""
    return len(text.split())


async def complete(
    host: str, port: int, req: Dict[str, Any], result: Dict[str, Any],
    logprobs: Optional[int] = None,
) -> None:
    """Stream one completion and fill ``result`` as it goes, so that a
    request cancelled at the end of the run keeps what had arrived:
    ``sent``, ``chunks`` [(arrival time, tokens)], ``finished``, ``ok``
    (None while in flight), ``error``, and with ``logprobs`` the per-token
    ``tokens``/``token_logprobs``/``top_logprobs``."""
    payload: Dict[str, Any] = {
        "model": MODEL,
        "prompt": req["prompt"],
        "max_tokens": req["max_tokens"],
        "stream": True,
        "ignore_eos": True,
        "temperature": 0,
    }
    if logprobs is not None:
        payload["logprobs"] = logprobs
        result["lp"] = {"tokens": [], "token_logprobs": [], "top_logprobs": []}
    body = json.dumps(payload).encode()
    result.update(sent=time.monotonic(), chunks=[], finished=None, ok=None, error="")
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(
            b"POST /v1/completions HTTP/1.1\r\nHost: x\r\n"
            b"Content-Type: application/json\r\n"
            + f"Content-Length: {len(body)}\r\n".encode()
            + b"Connection: close\r\n\r\n"
            + body
        )
        await writer.drain()
        status, headers = await _head(reader)
        if status != 200:
            out = b"".join([l async for l in _body_lines(reader, headers)])
            result.update(ok=False, error=f"HTTP {status}: {out[:200]!r}")
            return
        usage_tokens = None
        async for raw in _body_lines(reader, headers):
            line = raw.strip()
            if not line.startswith(b"data:"):
                continue
            data = line[5:].strip()
            if data == b"[DONE]":
                break
            chunk = json.loads(data)
            if "error" in chunk:
                result.update(ok=False, error=str(chunk["error"]))
                return
            usage = chunk.get("usage")
            if usage and usage.get("completion_tokens") is not None:
                usage_tokens = int(usage["completion_tokens"])
            now = time.monotonic()
            for c in chunk.get("choices") or []:
                n = _words(c.get("text") or "")
                if n:
                    result["chunks"].append((now, n))
                lp = c.get("logprobs")
                if lp and logprobs is not None:
                    for k in ("tokens", "token_logprobs", "top_logprobs"):
                        result["lp"][k].extend(lp.get(k) or [])
        got = sum(n for _t, n in result["chunks"])
        result["finished"] = time.monotonic()
        if got != req["max_tokens"] or usage_tokens != req["max_tokens"]:
            result.update(
                ok=False,
                error=f"stream of {got} tokens (usage {usage_tokens}),"
                f" asked {req['max_tokens']}",
            )
        else:
            result["ok"] = True
    except asyncio.CancelledError:
        raise
    except Exception as e:  # the run reports it as a failed request
        result.update(ok=False, error=f"{type(e).__name__}: {e}")
    finally:
        if writer is not None:
            writer.close()
            with contextlib.suppress(Exception):
                await writer.wait_closed()
