"""The benchmark's own load generator: a process of its own that never touches JAX.

``python bench/client.py <plan.json> <records.json>`` reads a plan
(``bench/traffic.py``), sends its warm-up requests, prints ``ready`` with the
ones that failed, then waits on stdin for one line, the
``time.monotonic()`` instant at which the window opens (the clock is the
machine's, so the parent and this process share it), streams every request
over HTTP and writes one record per request: when it was due, when it was
sent, when each token arrived, what the server said. It runs apart from the
server so that parsing its streams does not take the interpreter lock from
the engine's scheduler; how late it ran is in every record.

Open loop: each request is sent at its due time whether or not earlier ones
have finished; the run ends once the window has closed and every request due
in it has its first token (or ``drain_s`` has passed). Closed loop: each of
the plan's clients sends its next request when its last one completed, until
the window closes. Requests still streaming at the end are cancelled and
recorded as unfinished.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time


async def one_request(session, plan: dict, req: dict, t0: float, rec: dict) -> None:
    """Stream one chat completion; fill ``rec`` as events arrive, so that a
    cancelled request keeps what it saw."""
    body = {
        "model": plan["model"], "stream": True, "max_tokens": req["max_tokens"],
        "temperature": plan["temperature"],
        "messages": [{"role": "user", "content": req["content"]}],
    }
    rec["sent_s"] = time.monotonic() - t0
    try:
        async with session.post(plan["url"] + "/v1/chat/completions", json=body) as resp:
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = (await resp.text())[:200]
                return
            async for raw in resp.content:
                if not raw.startswith(b"data: "):
                    continue
                now = time.monotonic() - t0
                data = raw[6:].strip()
                if data == b"[DONE]":
                    rec["done_s"] = now
                    break
                evt = json.loads(data)
                choice = evt["choices"][0]
                piece = choice["delta"].get("content")
                if piece:
                    rec["token_s"].append(now)
                    rec["text"].append(piece)
                if "metrics" in evt:
                    rec["server_ttft_ms"] = evt["metrics"].get("server_ttft_ms")
                    rec["truncated"] = evt["metrics"].get("truncated", False)
                if choice.get("finish_reason"):
                    rec["finish_reason"] = choice["finish_reason"]
                if "usage" in evt:
                    rec["usage"] = evt["usage"]
    except asyncio.CancelledError:
        rec["error"] = rec.get("error") or "cancelled at the end of the run"
        raise
    except Exception as e:  # noqa: BLE001 — any failure is the request's record
        rec["error"] = f"{type(e).__name__}: {e}"


def new_record(req: dict) -> dict:
    return {"id": req["id"], "phase": req["phase"], "client": req.get("client"),
            "due_s": req["due_s"], "prompt_tokens": req["prompt_tokens"],
            "max_tokens": req["max_tokens"], "sent_s": None, "token_s": [],
            "text": [], "done_s": None, "status": None, "error": None,
            "finish_reason": None, "usage": None, "server_ttft_ms": None}


async def run_open(session, plan: dict, t0: float, records: list) -> None:
    window_s, drain_s = plan["window_s"], plan["drain_s"]
    tasks = []

    async def fire(req: dict, rec: dict) -> None:
        await asyncio.sleep(max(t0 + req["due_s"] - time.monotonic(), 0.0))
        await one_request(session, plan, req, t0, rec)

    for req in sorted(plan["requests"], key=lambda r: r["due_s"]):
        rec = new_record(req)
        records.append(rec)
        tasks.append(asyncio.create_task(fire(req, rec)))
    await asyncio.sleep(max(t0 + window_s - time.monotonic(), 0.0))
    # the window has closed: wait for the first token of everything due in it
    deadline = t0 + window_s + drain_s
    while time.monotonic() < deadline:
        waiting = [r for r, t in zip(records, tasks)
                   if r["phase"] == "window" and not r["token_s"] and not t.done()]
        if not waiting:
            break
        await asyncio.sleep(0.02)
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def run_closed(session, plan: dict, t0: float, records: list) -> None:
    start, end = t0 - plan["lead_in_s"], t0 + plan["window_s"]
    by_client: dict = {}
    for req in plan["requests"]:
        by_client.setdefault(req["client"], []).append(req)

    async def client(reqs: list) -> None:
        await asyncio.sleep(max(start - time.monotonic(), 0.0))
        for req in reqs:
            if time.monotonic() >= end:
                return
            rec = new_record(req)
            rec["due_s"] = time.monotonic() - t0   # due when the last one completed
            records.append(rec)
            await one_request(session, plan, req, t0, rec)

    tasks = [asyncio.create_task(client(reqs)) for reqs in by_client.values()]
    await asyncio.sleep(max(end - time.monotonic(), 0.0))
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def main(plan_path: str, out_path: str) -> None:
    import aiohttp

    with open(plan_path) as f:
        plan = json.load(f)
    loop = asyncio.get_running_loop()
    records: list = []
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30, sock_read=1200)
    async with aiohttp.ClientSession(connector=conn, timeout=timeout) as session:
        # warm-up (set-up): one request per prompt bucket of the cell, all at
        # once; the first run of a checkout compiles here
        warm = [new_record(r) for r in plan.get("warm", [])]
        await asyncio.gather(*(one_request(session, plan, req, time.monotonic(), rec)
                               for req, rec in zip(plan.get("warm", []), warm)))
        bad = [f"{r['id']}: {r['error'] or r['status']}" for r in warm
               if r["error"] or len(r["token_s"]) != r["max_tokens"]]
        print("ready" + json.dumps(bad), flush=True)
        line = await loop.run_in_executor(None, sys.stdin.readline)
        t0 = float(line)
        if plan["loop"] == "open":
            await run_open(session, plan, t0, records)
        else:
            await run_closed(session, plan, t0, records)
    for rec in records:
        rec["text"] = "".join(rec["text"])
    with open(out_path, "w") as f:
        json.dump({"t0": t0, "ended_s": time.monotonic() - t0, "records": records}, f)


if __name__ == "__main__":
    asyncio.run(main(sys.argv[1], sys.argv[2]))
