"""Load generator: keep-alive clients sending ``POST /v1/ppr`` over sockets.

Runs on the same asyncio loop as the server it drives.  Every request is
recorded with the moment it was due, the moment it went out and the moment
its response was read (``time.perf_counter``).  Open-loop latency counts
from when a request was due, so a stalled generator or server shows up in
the latency of every request behind the stall; the generator's own lateness
(sent minus due) is reported beside it.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Dict, List, Optional

from repro.ppr_serving.http.client import AsyncHTTPClient

now = time.perf_counter


@dataclasses.dataclass
class Request:
    vertex: int
    due: float
    sent: float = 0.0
    recv: Optional[float] = None
    status: Optional[int] = None
    payload: Optional[Dict] = None

    @property
    def ok(self) -> bool:
        return self.status == 200


def _body(graph: str, vertex: int, precision, k: int) -> Dict:
    return {"graph": graph, "vertex": int(vertex), "k": int(k),
            "precision": precision}


async def connect(host: str, port: int, n: int) -> List[AsyncHTTPClient]:
    """``n`` clients with their keep-alive connections already open."""
    clients = [AsyncHTTPClient(host, port) for _ in range(n)]
    await asyncio.gather(*(c.request("GET", "/v1/healthz") for c in clients))
    return clients


async def _send(client: AsyncHTTPClient, req: Request, body: Dict) -> None:
    req.sent = now()
    try:
        req.status, _, req.payload = await client.request("POST", "/v1/ppr",
                                                          body)
    except (OSError, asyncio.IncompleteReadError, ValueError):
        pass                      # no status: the request counts as failed
    req.recv = now()


async def closed_loop(clients, graph: str, schedule, deadline: float,
                      on_late=None) -> List[Request]:
    """Each client sends its next request when the last is answered, until
    an answer arrives at or after ``deadline``.  ``on_late`` is called once,
    with that answer's request, when the first such answer arrives."""
    vertices = iter(schedule.vertices)
    out: List[Request] = []
    first_late: List[Request] = []

    async def client_loop(client):
        for v in vertices:
            req = Request(int(v), due=now())
            out.append(req)
            await _send(client, req, _body(graph, v, schedule.precision,
                                           schedule.k))
            if req.recv >= deadline:
                if not first_late:
                    first_late.append(req)
                    if on_late is not None:
                        on_late(req)
                return

    await asyncio.gather(*(client_loop(c) for c in clients))
    return out


async def open_loop(clients, graph: str, schedule, t0: float,
                    give_up_s: float, host: str, port: int) -> List[Request]:
    """Send request i at ``t0 + due_s[i]`` on an idle connection (a new one
    when none is idle); wait for the answers at most ``give_up_s`` past the
    last send.  Requests still unanswered then keep ``recv = None``."""
    idle = list(clients)
    out: List[Request] = []
    tasks = []
    opened: List[AsyncHTTPClient] = []

    async def one(client, req):
        await _send(client, req, _body(graph, req.vertex, schedule.precision,
                                       schedule.k))
        idle.append(client)

    for v, due in zip(schedule.vertices, schedule.due_s):
        due_at = t0 + float(due)
        delay = due_at - now()
        if delay > 0:
            await asyncio.sleep(delay)
        req = Request(int(v), due=due_at)
        out.append(req)
        if idle:
            client = idle.pop()
        else:
            client = AsyncHTTPClient(host, port)
            opened.append(client)
        tasks.append(asyncio.create_task(one(client, req)))
    if tasks:
        _, pending = await asyncio.wait(tasks, timeout=give_up_s)
        for t in pending:
            t.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
    for c in opened:
        await c.close()
    return out
