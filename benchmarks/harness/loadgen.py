"""The load generator: one process, one event loop, streaming HTTP clients.

    python loadgen.py <job.json>     writes <job.json>.out

Never imports jax or ray_tpu: it is a client. Open loop: every request is
sent when it is due whether or not earlier ones have finished, and is timed
from when it was due; how late each was sent is reported. Closed loop:
`concurrency` clients, each sending the next request of the stream when its
last one completed. All times are CLOCK_MONOTONIC seconds, which every
process of the host shares.
"""
from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import schedule  # noqa: E402

NOW = time.monotonic


class Client:
    def __init__(self, job: dict):
        self.job = job
        self.plan = job["plan"]
        self.url = f"http://127.0.0.1:{job['port']}{job['route']}"
        self.vocab = job["vocab"]
        self.t0 = job["start_at"]  # start of the ramp
        self.w0 = self.t0 + self.plan["ramp_s"]
        self.w1 = self.w0 + self.plan["seconds"]
        self.records: list[dict] = []
        self.prefix_len = int(self.plan["prefix"].get("shared_len", 0))
        self._prefixes: dict[int, list] = {}

    def prompt_for(self, req: dict) -> list[int]:
        own = schedule.prompt_tokens(self.plan["token_seed"], req["idx"], req["prompt_len"], self.vocab)
        if not self.prefix_len:
            return own
        t = req["tenant"]
        if t not in self._prefixes:
            self._prefixes[t] = schedule.tenant_prefix(
                self.plan["token_seed"], t, self.prefix_len, self.vocab)
        return self._prefixes[t] + own

    def prepare(self):
        """First prompts and bodies made before the clock starts, so that the
        loop has nothing to compute when a request is due."""
        self._ready = {}
        for req in self.plan["requests"]:
            tokens = self.prompt_for(req)
            self._ready[req["idx"]] = (tokens, self.body(tokens, req["out_len"]))

    @staticmethod
    def body(tokens: list, out_len: int) -> str:
        return json.dumps({"tokens": tokens, "max_tokens": out_len, "stream": True, "ignore_eos": True})

    async def send(self, session, req: dict, tokens: list, out_len: int, due: float, turn: int = 0,
                   body: str | None = None) -> dict:
        """One streaming request; returns its record (and keeps it)."""
        rec = {"idx": req["idx"], "turn": turn, "phase": req["phase"], "due": due,
               "prompt_len": len(tokens), "out_len": out_len, "status": None,
               "t_first": None, "t_last": None, "n_out": 0, "engine_ttft_s": None,
               "chunks": [], "error": None}
        self.records.append(rec)
        body = body or self.body(tokens, out_len)
        rec["sent"] = NOW()
        got: list[int] = []
        try:
            async with session.post(self.url, data=body) as resp:
                rec["status"] = resp.status
                buf = b""
                async for chunk in resp.content.iter_any():
                    t = NOW()
                    buf += chunk
                    n_new = 0
                    while b"\n\n" in buf:
                        frame, buf = buf.split(b"\n\n", 1)
                        if not frame.startswith(b"data: ") or frame == b"data: [DONE]":
                            continue
                        ev = json.loads(frame[6:])
                        new = ev.get("new_tokens") or []
                        n_new += len(new)
                        got += new
                        if rec["engine_ttft_s"] is None and ev.get("ttft_s") is not None:
                            rec["engine_ttft_s"] = ev["ttft_s"]
                    if n_new:
                        if rec["t_first"] is None:
                            rec["t_first"] = t
                        rec["t_last"] = t
                        rec["n_out"] += n_new
                        rec["chunks"].append((t, n_new))
        except asyncio.CancelledError:
            rec["error"] = "cancelled"
            raise
        except Exception as e:  # a failed request is a result, not a crash
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["done"] = NOW()
        rec["bad_tokens"] = sum(1 for x in got if not (isinstance(x, int) and 0 <= x < self.vocab))
        rec["_tokens"] = got
        return rec

    async def session_of(self, session, req: dict):
        """An open-loop arrival: its first turn when due, later turns think_s
        after the one before completed (each timed from then)."""
        due = self.t0 + req["due"]
        await asyncio.sleep(max(0.0, due - NOW()))
        tokens, body = self._ready[req["idx"]]
        rec = await self.send(session, req, tokens, req["out_len"], due, body=body)
        for j, turn in enumerate(req.get("later_turns") or [], start=1):
            if rec["error"] or rec["status"] != 200:
                return
            tokens = tokens + rec["_tokens"] + schedule.prompt_tokens(
                self.plan["token_seed"], req["idx"] * 1000 + j, turn["add_len"], self.vocab)
            if len(tokens) + turn["out_len"] >= self.job["max_seq"]:
                return
            due = rec["done"] + req["think_s"]
            await asyncio.sleep(max(0.0, due - NOW()))
            rec = await self.send(session, req, tokens, turn["out_len"], due, turn=j)

    async def run_open(self, session):
        tasks = [asyncio.create_task(self.session_of(session, r)) for r in self.plan["requests"]]
        measured = [t for t, r in zip(tasks, self.plan["requests"]) if r["phase"] == "window"]
        await asyncio.wait(measured, timeout=self.w1 + self.job["drain_s"] - NOW())
        for t in tasks:  # ramp, cooldown and anything that overran the drain
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    async def run_closed(self, session):
        stream = iter(self.plan["requests"])
        stop = asyncio.Event()

        async def client():
            while not stop.is_set():
                req = next(stream, None)
                if req is None:
                    self.exhausted = True
                    return
                tokens, body = self._ready[req["idx"]]
                await self.send(session, req, tokens, req["out_len"], NOW(), body=body)

        await asyncio.sleep(max(0.0, self.t0 - NOW()))
        clients = [asyncio.create_task(client()) for _ in range(self.plan["concurrency"])]
        await asyncio.sleep(max(0.0, self.w1 - NOW()))
        stop.set()  # what is in flight runs to its end: the drain
        _, late = await asyncio.wait(clients, timeout=self.job["drain_s"])
        for t in late:
            t.cancel()
        await asyncio.gather(*clients, return_exceptions=True)

    async def main(self) -> dict:
        import aiohttp

        self.exhausted = False
        self.prepare()
        conn = aiohttp.TCPConnector(limit=0)
        timeout = aiohttp.ClientTimeout(total=None, sock_read=120)
        async with aiohttp.ClientSession(connector=conn, timeout=timeout) as session:
            if self.plan["loop"] == "open":
                await self.run_open(session)
            else:
                await self.run_closed(session)
        for r in self.records:
            r.pop("_tokens", None)
        return {"w0": self.w0, "w1": self.w1, "records": self.records,
                "stream_exhausted": self.exhausted}


def main():
    job_path = sys.argv[1]
    with open(job_path) as f:
        job = json.load(f)
    out = asyncio.run(Client(job).main())
    with open(job_path + ".out", "w") as f:
        json.dump(out, f)


if __name__ == "__main__":
    main()
