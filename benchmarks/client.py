"""The load generator: streaming chat completions over loopback, from one
thread (asyncio), closed loop or open loop.

What is recorded for a request is only what a client can see: when it was
due, when it was sent, the status, the arrival time of every content chunk
of the SSE stream, the usage chunk, the finish reason and ``[DONE]``.  All
arithmetic on these records is in ``metrics.py``.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time

import traffic

RAMP_S = 0.05                  # between the starts of a closed loop's callers


@dataclasses.dataclass
class Record:
    index: int
    due: float                     # host clock; closed loop: when sent
    sent: float = 0.0
    status: int | None = None
    chunks: list = dataclasses.field(default_factory=list)   # arrival times
    text: list = dataclasses.field(default_factory=list)
    prompt_tokens: int | None = None
    completion_tokens: int | None = None
    finish: str | None = None
    done: bool = False             # saw [DONE]
    cut: bool = False              # the benchmark closed it at the window's end
    error: str | None = None
    max_tokens: int = 0
    request_id: str | None = None  # x-request-id: the trace id at /debug/traces


async def _read_headers(reader) -> tuple[int, dict]:
    raw = await reader.readuntil(b"\r\n\r\n")
    lines = raw.decode("latin-1").split("\r\n")
    status = int(lines[0].split(" ", 2)[1])
    headers = {}
    for line in lines[1:]:
        if ":" in line:
            k, v = line.split(":", 1)
            headers[k.strip().lower()] = v.strip()
    return status, headers


async def _body_chunks(reader, headers):
    """Yield the body's bytes as they arrive, transfer framing removed."""
    if headers.get("transfer-encoding", "").lower() == "chunked":
        while True:
            size = int((await reader.readline()).split(b";")[0].strip() or b"0", 16)
            if size == 0:
                return
            yield await reader.readexactly(size)
            await reader.readexactly(2)
    elif "content-length" in headers:
        yield await reader.readexactly(int(headers["content-length"]))
    else:
        while True:
            data = await reader.read(65536)
            if not data:
                return
            yield data


async def stream_chat(host: str, port: int, body: dict, rec: Record) -> Record:
    """One streaming request; fills ``rec``.  Never raises but for
    cancellation, which marks the record ``cut``."""
    payload = json.dumps(body).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        rec.sent = time.time()
        writer.write(
            b"POST /v1/chat/completions HTTP/1.1\r\nhost: bench\r\n"
            b"content-type: application/json\r\nconnection: close\r\n"
            b"content-length: " + str(len(payload)).encode() + b"\r\n\r\n"
            + payload)
        await writer.drain()
        rec.status, headers = await _read_headers(reader)
        rec.request_id = headers.get("x-request-id")
        buf = b""
        async for data in _body_chunks(reader, headers):
            now = time.time()
            buf += data
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                _on_event(event, now, rec)
        if rec.status != 200 and rec.error is None:
            rec.error = buf.decode("utf-8", "replace")[:200] or f"status {rec.status}"
    except asyncio.CancelledError:
        rec.cut = True
        raise
    except (OSError, asyncio.IncompleteReadError, ValueError) as e:
        rec.error = f"{type(e).__name__}: {e}"[:200]
    finally:
        if writer is not None:
            writer.close()
    return rec


def _on_event(event: bytes, now: float, rec: Record) -> None:
    for line in event.split(b"\n"):
        if not line.startswith(b"data:"):
            continue
        data = line[5:].strip()
        if data == b"[DONE]":
            rec.done = True
            continue
        try:
            doc = json.loads(data)
        except ValueError:
            continue
        if "error" in doc:
            rec.error = json.dumps(doc["error"])[:200]
            continue
        if doc.get("usage"):
            rec.prompt_tokens = doc["usage"].get("prompt_tokens")
            rec.completion_tokens = doc["usage"].get("completion_tokens")
        for choice in doc.get("choices") or []:
            piece = (choice.get("delta") or {}).get("content")
            if piece:
                rec.chunks.append(now)
                rec.text.append(piece)
            if choice.get("finish_reason"):
                rec.finish = choice["finish_reason"]


async def _one(host, port, mix, req, overhead, due, records) -> Record:
    rec = Record(index=req.index, due=due, max_tokens=req.max_tokens)
    records.append(rec)
    return await stream_chat(host, port, traffic.body(mix, req, overhead), rec)


async def _closed(host, port, mix, reqs, overhead, t_end, records):
    # callers start RAMP_S apart: independent callers never arrive inside
    # one millisecond, and the program's admission queue (5 by default)
    # answers 503 to the sixth of such a burst
    async def caller(i):
        await asyncio.sleep(i * RAMP_S)
        while time.time() < t_end:
            await _one(host, port, mix, next(reqs), overhead, time.time(),
                       records)

    return [asyncio.ensure_future(caller(i))
            for i in range(int(mix["clients"]))]


async def _open(host, port, mix, reqs, overhead, t0, records, lateness):
    tasks = []

    async def schedule():
        for req in reqs:
            due = t0 + req.due_s
            delay = due - time.time()
            if delay > 0:
                await asyncio.sleep(delay)
            lateness.append(time.time() - due)
            tasks.append(asyncio.ensure_future(
                _one(host, port, mix, req, overhead, due, records)))

    tasks.append(asyncio.ensure_future(schedule()))
    return tasks


async def drive(host: str, port: int, mix: dict, seed: int, seconds: float,
                overhead: int, side_jobs=()) -> dict:
    """Offer the mix for ``seconds``; at the window's end, cut what is
    still in flight.  ``side_jobs`` are coroutine functions ``f(t0, t1)``
    run beside the load (samplers, the profiler capture).  Returns the
    records, the window and, for an open loop, how late each arrival was
    sent."""
    records: list[Record] = []
    lateness: list[float] = []
    reqs = traffic.requests(mix, seed, seconds)
    t0 = time.time()
    t1 = t0 + seconds
    side = [asyncio.ensure_future(job(t0, t1)) for job in side_jobs]
    if mix["loop"] == "open":
        tasks = await _open(host, port, mix, reqs, overhead, t0, records,
                            lateness)
    else:
        tasks = await _closed(host, port, mix, reqs, overhead, t1, records)
    await asyncio.sleep(max(0.0, t1 - time.time()))
    # the list grows while the open loop's scheduler runs: it has ended by now
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    side_out = await asyncio.gather(*side, return_exceptions=True)
    return {"records": records, "t0": t0, "t1": t1, "lateness": lateness,
            "side": side_out}
