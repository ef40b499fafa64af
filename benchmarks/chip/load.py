"""Traffic and the client side of a run: request mix, arrival schedule,
an HTTP client for the frontend's streamed ``/generate``, open- and
closed-loop drivers, and the percentile arithmetic.

One general generator reads a traffic file (``traffic/<name>.json``).  The
schedule, which request of which tier and step count arrives when, is
drawn from the file's own ``schedule_seed``: tiers dealt by largest
remainder, step counts and inter-arrival gaps as stratified quantiles of
their distributions, shuffled.  The run's ``--seed`` draws what each
request asks for (its prompt and its seed), so two seeds offer the same
work in the same order, and the spread between runs is the system's.

The HTTP client is the benchmark's own copy of the program's client
(``serving/client.py``), so a change to the program cannot move the clock.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import math
import time

import numpy as np

# ---------------------------------------------------------------------------
# the request mix
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    """One request as the client sends it, and what the client saw."""

    k: int
    prompt: str
    seed: int
    tier: str
    steps: int
    due: float = 0.0  # offset from the window start (open loop)
    sent: float | None = None  # perf_counter
    due_abs: float | None = None  # perf_counter
    first_step: float | None = None
    done: float | None = None
    queue_wait_s: float | None = None
    rid: int | None = None
    digest: str | None = None
    status: str = "pending"  # pending | done | failed | withdrawn
    error: str | None = None

    def payload(self) -> dict:
        return {"task": "txt2img", "prompt": self.prompt, "seed": self.seed,
                "timesteps": self.steps, "quality": self.tier, "stream": True}


def _deal(weights: dict[str, float], n: int) -> list[str]:
    """``n`` labels in the given proportions (largest remainder)."""
    names = sorted(weights)
    total = sum(weights.values())
    exact = [weights[k] / total * n for k in names]
    counts = [math.floor(e) for e in exact]
    for i in sorted(range(len(names)), key=lambda i: counts[i] - exact[i])[: n - sum(counts)]:
        counts[i] += 1
    return [k for k, c in zip(names, counts) for _ in range(c)]


def _steps_quantile(spec: list[dict], u: float) -> int:
    """Inverse CDF of a step-count mixture: components ``{"p", "value"}``
    or ``{"p", "uniform": [lo, hi]}`` (integers, inclusive)."""
    acc = 0.0
    for comp in spec:
        if u < acc + comp["p"] or comp is spec[-1]:
            v = min(max((u - acc) / comp["p"], 0.0), 1.0 - 1e-12)
            if "value" in comp:
                return int(comp["value"])
            lo, hi = comp["uniform"]
            return int(lo + math.floor(v * (hi - lo + 1)))
        acc += comp["p"]
    raise ValueError(f"empty step mixture {spec}")


def step_support(spec: list[dict]) -> list[int]:
    """Every step count a step mixture can draw."""
    out: set[int] = set()
    for comp in spec:
        if "value" in comp:
            out.add(int(comp["value"]))
        else:
            lo, hi = comp["uniform"]
            out.update(range(int(lo), int(hi) + 1))
    return sorted(out)


def make_requests(traffic: dict, seed: int, n: int, tag: str = "") -> list[Request]:
    """``n`` requests of the mix in the order of the file's
    ``schedule_seed``, with unique prompts and request seeds from ``seed``."""
    order = np.random.default_rng(traffic["schedule_seed"])
    tiers = _deal(traffic["tiers"], n)
    u = (np.arange(n) + 0.5) / n
    steps = [_steps_quantile(traffic["steps"], float(x)) for x in u]
    order.shuffle(tiers)
    order.shuffle(steps)
    rng = np.random.default_rng(seed)
    seeds = rng.integers(0, 2**31 - 1, size=n)
    salt = int(rng.integers(0, 2**62))
    return [
        Request(k=k, prompt=f"{tag}request {k} of run {salt:x}", seed=int(seeds[k]),
                tier=tiers[k], steps=int(steps[k]))
        for k in range(n)
    ]


def open_schedule(traffic: dict, seed: int, seconds: float) -> list[Request]:
    """The open-loop requests of one window: ``round(rate * seconds)``
    arrivals whose gaps are the stratified quantiles of an exponential
    distribution, shuffled, and scaled so that the offered rate is
    exactly ``rate``."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    reqs = make_requests(traffic, seed, n)
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    np.random.default_rng((traffic["schedule_seed"], 1)).shuffle(gaps)
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    starts *= seconds / gaps.sum()
    for r, t in zip(reqs, starts):
        r.due = float(t)
    return reqs


# ---------------------------------------------------------------------------
# HTTP: a streamed POST /generate (HTTP/1.1, chunked NDJSON)
# ---------------------------------------------------------------------------


async def _read_head(reader: asyncio.StreamReader) -> tuple[int, dict]:
    parts = (await reader.readline()).decode("latin-1").split()
    if len(parts) < 2:
        raise ConnectionError("malformed status line")
    headers = {}
    while True:
        h = await reader.readline()
        if h in (b"\r\n", b"\n", b""):
            return int(parts[1]), headers
        k, _, v = h.decode("latin-1").partition(":")
        headers[k.strip().lower()] = v.strip()


async def _chunked_lines(reader: asyncio.StreamReader):
    buf = b""
    while True:
        size = int((await reader.readline()).strip() or b"0", 16)
        if size == 0:
            return
        buf += await reader.readexactly(size)
        await reader.readexactly(2)
        while b"\n" in buf:
            line, buf = buf.split(b"\n", 1)
            if line.strip():
                yield line


async def http_json(port: int, method: str, path: str, payload: dict | None = None) -> dict:
    body = json.dumps(payload or {}).encode()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write(_head(method, path, port, body))
        await writer.drain()
        status, headers = await _read_head(reader)
        n = int(headers.get("content-length", "0"))
        data = await reader.readexactly(n) if n else await reader.read()
        out = json.loads(data or b"{}")
        if status >= 400:
            raise ConnectionError(f"HTTP {status}: {out}")
        return out
    finally:
        writer.close()


def _head(method: str, path: str, port: int, body: bytes) -> bytes:
    return (
        f"{method} {path} HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
        f"Content-Type: application/json\r\nContent-Length: {len(body)}\r\n"
        "Connection: close\r\n\r\n"
    ).encode() + body


async def generate(port: int, req: Request, withdraw_when_queued: bool = False) -> None:
    """Send ``req`` and follow its stream to a terminal event, stamping
    the send, the first ``step`` and the ``done`` on the host clock.
    ``withdraw_when_queued`` cancels it as soon as the server queues it."""
    body = json.dumps(req.payload()).encode()
    req.sent = time.perf_counter()
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
    except OSError as e:
        req.status, req.error = "failed", repr(e)
        return
    try:
        writer.write(_head("POST", "/generate", port, body))
        await writer.drain()
        status, _ = await _read_head(reader)
        if status >= 400:
            req.status, req.error = "failed", f"HTTP {status}"
            return
        async for line in _chunked_lines(reader):
            ev = json.loads(line)
            kind = ev.get("event")
            if kind == "queued":
                req.rid = ev.get("rid")
                if withdraw_when_queued:
                    await http_json(port, "POST", "/cancel", {"rid": req.rid})
            elif kind == "step" and req.first_step is None:
                req.first_step = time.perf_counter()
            elif kind == "done":
                req.done = time.perf_counter()
                req.queue_wait_s = ev.get("queue_wait_s")
                req.digest = ev.get("latent_digest")
                req.rid = ev.get("rid", req.rid)
                req.status = "done"
                return
            elif kind in ("cancelled", "error"):
                req.status, req.error = "failed", json.dumps(ev)
                return
        req.status, req.error = "failed", "stream ended without a terminal event"
    except (ConnectionError, OSError, asyncio.IncompleteReadError, ValueError) as e:
        req.status, req.error = "failed", repr(e)
    finally:
        writer.close()


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


async def run_open(port: int, reqs: list[Request], t0: float, seconds: float,
                   drain_s: float) -> None:
    """Send each request at ``t0 + due`` whatever the server does; after
    the window, wait up to ``drain_s`` for every request to end.  One that
    has not ended by then has failed."""
    tasks = []
    for r in reqs:
        r.due_abs = t0 + r.due
        delay = r.due_abs - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(generate(port, r)))
    rest = t0 + seconds - time.perf_counter()
    if rest > 0:
        await asyncio.sleep(rest)
    await _finish(tasks, reqs, t0 + seconds + drain_s)


async def run_closed(port: int, reqs: list[Request], t0: float, seconds: float,
                     outstanding: int, drain_s: float) -> None:
    """Keep ``outstanding`` requests open until the window closes.  Then
    withdraw (close the stream of) every request the engine has not
    started, and wait up to ``drain_s`` for the started ones to end."""
    t1 = t0 + seconds
    queue = iter(reqs)
    tasks: list[asyncio.Task] = []
    by_task: dict[asyncio.Task, Request] = {}

    def launch() -> None:
        r = next(queue)
        r.due_abs = time.perf_counter()
        task = asyncio.create_task(generate(port, r))
        tasks.append(task)
        by_task[task] = r

    for _ in range(outstanding):
        launch()
    open_tasks = set(tasks)
    while time.perf_counter() < t1:
        done, open_tasks = await asyncio.wait(
            open_tasks, timeout=max(t1 - time.perf_counter(), 0.0),
            return_when=asyncio.FIRST_COMPLETED,
        )
        for _ in done:
            if time.perf_counter() < t1:
                launch()
                open_tasks.add(tasks[-1])
    for task in list(open_tasks):
        r = by_task[task]
        if r.first_step is None:
            r.status = "withdrawn"
            if r.rid is not None:
                try:
                    await http_json(port, "POST", "/cancel", {"rid": r.rid})
                except (ConnectionError, OSError):
                    pass
            task.cancel()
    await _finish(tasks, reqs, t1 + drain_s)


async def _finish(tasks: list[asyncio.Task], reqs: list[Request], deadline: float) -> None:
    pending = [t for t in tasks if not t.done()]
    if pending:
        _, late = await asyncio.wait(pending, timeout=max(deadline - time.perf_counter(), 0.0))
        for t in late:
            t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for r in reqs:
        if r.sent is not None and r.status == "pending":
            r.status, r.error = "failed", "did not end within the drain limit"


# ---------------------------------------------------------------------------
# percentiles
# ---------------------------------------------------------------------------


def percentile(values: list[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics; ``inf`` entries (failed requests) sort last."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]):
        return xs[hi] if pos > lo else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
