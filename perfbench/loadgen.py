"""The load generator: a process of its own that imports no JAX.

Started by the serving driver as ``python3 perfbench/loadgen.py`` with the
server's port, a traffic mix file, the seed and the window's length. It sends
``POST /generate`` with ``stream=true`` and stamps each ndjson line as it
arrives, on ``time.perf_counter()``.

Line protocol on stdout (one JSON object a line), in order:

- ``{"event": "ramped"}``: offered load is steady (closed loop: every client
  holds a first token; open loop: the ramp is nearly over). In a closed loop the
  window then waits for a ``go`` line on stdin, so that the parent can start a
  trace first; an open loop opens on its own schedule.
- ``{"event": "open", "t": ...}`` / ``{"event": "close", "t": ...}``: the window.
- ``{"event": "result", ...}``: every request's record, ``/stats`` as read at
  the open and at the close, and the server's ``queue_wait`` spans.

Closed loop: at the close, requests in flight are dropped (the connection
closes, the server cancels the slot). Open loop: every request due in the window
is waited for until its first token, up to ``GRACE_S`` seconds past the close.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import aiohttp  # noqa: E402

from perfbench import traffic  # noqa: E402


GRACE_S = 60.0  # how long past the close an open loop waits for a due request's first token
RAMP_TIMEOUT_S = 240.0  # a closed loop's clients all hold a first token by then, or the window opens anyway
TRACE_JOURNAL = 4096  # completed request traces asked of the server after the window
TRACE_SETTLE_S = 1.5  # the server closes a dropped request's trace at its next step or two
TRACE_LEAD_S = 3.0  # an open loop says "ramped" this long before its window, for the profiler to start


def emit(obj: Dict[str, Any]) -> None:
    sys.stdout.write(json.dumps(obj, separators=(",", ":")) + "\n")
    sys.stdout.flush()


class Load:
    def __init__(self, args: argparse.Namespace) -> None:
        self.args = args
        self.mix = traffic.load_mix(args.traffic)
        self.stream = traffic.RequestStream(self.mix, args.seed, args.vocab)
        self.url = f"http://127.0.0.1:{args.port}"
        self.records: List[Dict[str, Any]] = []
        self.closing = False
        self.session: Optional[aiohttp.ClientSession] = None

    async def get_json(self, path: str) -> Any:
        async with self.session.get(self.url + path) as resp:
            return await resp.json()

    async def queue_waits(self) -> List[List[float]]:
        """``[created (unix seconds), wait (ms)]`` for every ``queue_wait`` span
        in the server's journal of completed request traces."""
        try:
            journal = await self.get_json(f"/traces/recent?n={TRACE_JOURNAL}")
        except (aiohttp.ClientError, OSError, ValueError):
            return []  # telemetry off: the metric that reads this stays silent
        return [
            [trace["created_unix"], span["dur_ms"]]
            for trace in journal.get("traces", ())
            for span in trace.get("spans", ())
            if span.get("kind") == "queue_wait" and span.get("dur_ms") is not None
        ]

    async def one(self, index: int, max_new: Optional[int] = None, **tags: Any) -> Dict[str, Any]:
        """Send request ``index`` and stamp its stream; returns its record."""
        req = self.stream.request(index)
        if max_new is not None:
            req["max_new_tokens"] = max_new
        record = {
            "index": index, "prompt_len": len(req["prompt_ids"]), "asked": req["max_new_tokens"],
            "sent": None, "status": None, "token_times": [], "tokens": [], "done": False,
            "error": None, **tags,
        }
        self.records.append(record)
        body = {"prompt_ids": req["prompt_ids"], "max_new_tokens": req["max_new_tokens"],
                "stream": True}
        record["sent"] = time.perf_counter()
        try:
            async with self.session.post(self.url + "/generate", json=body) as resp:
                record["status"] = resp.status
                if resp.status != 200:
                    record["error"] = (await resp.text())[:300]
                    return record
                async for raw in resp.content:
                    now = time.perf_counter()
                    line = json.loads(raw)
                    if "token" in line:
                        record["token_times"].append(now)
                        record["tokens"].append(line["token"])
                    elif line.get("done"):
                        record["done"] = True
                        record["end"] = now
                    elif "error" in line:
                        record["error"] = str(line["error"])[:300]
        except asyncio.CancelledError:
            record["error"] = record["error"] or "dropped_at_close"
            raise
        except (aiohttp.ClientError, OSError, ValueError) as exc:
            record["error"] = f"{type(exc).__name__}: {exc}"[:300]
        return record

    # ------------------------------------------------------------ closed loop

    async def closed_client(self, client: int, rank: int, clients: int) -> None:
        turn = 0
        while not self.closing:
            index = traffic.closed_index(client, turn, clients)
            cut = None
            if turn == 0:
                cut = traffic.first_turn_cut(rank, clients, self.stream.sizes(index)[1])
            record = await self.one(index, cut, client=client, turn=turn)
            if record["status"] != 200 or not record["done"]:
                await asyncio.sleep(0.05)  # a failing server must not be hammered
            turn += 1

    async def run_closed(self) -> Dict[str, Any]:
        clients = int(self.mix["arrival"]["clients"])
        ranks = traffic.client_ranks(self.args.seed, clients)
        tasks = [
            asyncio.ensure_future(self.closed_client(c, int(ranks[c]), clients))
            for c in range(clients)
        ]
        deadline = time.perf_counter() + RAMP_TIMEOUT_S
        while time.perf_counter() < deadline:
            first = [r for r in self.records if r.get("turn") == 0]
            if len(first) == clients and all(r["token_times"] or r["error"] for r in first):
                break
            await asyncio.sleep(0.01)
        emit({"event": "ramped"})
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, sys.stdin.readline)  # "go"
        return await self.window(tasks, wait_first_tokens=False)

    # -------------------------------------------------------------- open loop

    async def run_open(self) -> Dict[str, Any]:
        arrival = self.mix["arrival"]
        ramp_s = float(arrival.get("ramp_s", 0.0))
        due = traffic.arrival_times(self.mix, self.args.seed, self.args.seconds)
        start = time.perf_counter()
        self.open_at = start + ramp_s
        tasks: List[asyncio.Future] = []

        async def offer() -> None:
            for index, at in enumerate(due):
                wait = start + at - time.perf_counter()
                if wait > 0:
                    await asyncio.sleep(wait)
                if self.closing:
                    return
                tasks.append(asyncio.ensure_future(self.one(index, due=start + float(at))))

        offering = asyncio.ensure_future(offer())
        await asyncio.sleep(max(0.0, ramp_s - TRACE_LEAD_S))
        emit({"event": "ramped"})
        await asyncio.sleep(max(0.0, self.open_at - 0.05 - time.perf_counter()))
        result = await self.window(tasks, wait_first_tokens=True, open_at=self.open_at)
        offering.cancel()
        await asyncio.gather(offering, return_exceptions=True)
        return result

    # ------------------------------------------------------------- the window

    async def window(self, tasks: List[asyncio.Future], wait_first_tokens: bool,
                     open_at: Optional[float] = None) -> Dict[str, Any]:
        """One window. An open loop's window is the schedule's own
        (``open_at`` and ``--seconds`` after it, whenever this coroutine gets to
        run), so that every seed has the same number of requests due in it."""
        stats_open = await self.get_json("/stats")
        if open_at is not None:
            await asyncio.sleep(max(0.0, open_at - time.perf_counter()))
        t_open = time.perf_counter() if open_at is None else open_at
        wall_open = time.time() - (time.perf_counter() - t_open)
        emit({"event": "open", "t": t_open})
        await asyncio.sleep(max(0.0, t_open + self.args.seconds - time.perf_counter()))
        t_close = time.perf_counter() if open_at is None else open_at + self.args.seconds
        self.closing = True
        stats_close = await self.get_json("/stats")
        emit({"event": "close", "t": t_close})
        if wait_first_tokens:
            limit = t_close + GRACE_S
            while time.perf_counter() < limit:
                waiting = [
                    r for r in self.records
                    if r.get("due") is not None and t_open <= r["due"] < t_close
                    and not r["token_times"] and r["error"] is None and r["status"] in (None, 200)
                ]
                if not waiting:
                    break
                await asyncio.sleep(0.02)
        for task in list(tasks):
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        await asyncio.sleep(TRACE_SETTLE_S)
        return {
            "event": "result", "t_open": t_open, "t_close": t_close,
            "wall_open": wall_open, "wall_close": wall_open + (t_close - t_open),
            "stats_open": stats_open, "stats_close": stats_close,
            "queue_waits": await self.queue_waits(), "records": self.records,
        }

    async def run(self) -> None:
        timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
        connector = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(timeout=timeout, connector=connector) as session:
            self.session = session
            mode = self.mix["arrival"]["mode"]
            if mode == "closed":
                result = await self.run_closed()
            elif mode == "open":
                result = await self.run_open()
            else:
                raise ValueError(f"unknown arrival mode {mode!r}")
        emit(result)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--traffic", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--vocab", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    asyncio.run(Load(parser.parse_args()).run())


if __name__ == "__main__":
    main()
