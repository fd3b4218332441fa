"""Drive the served path from the client side and record what it sees.

The only entries used are the runtime's own: ``ClusterRuntime.submit`` and
``ClusterRuntime.step`` every tick, and at a span boundary ``finish_span``
-> ``Orchestrator.plan_span`` -> ``ClusterRuntime.apply_plan``.  After each
tick the client reads how many tokens each of its requests has (the
runtime's request log), and stamps the new ones with the tick's end on the
host clock: that is when they reached the client.

Submission rule: within one tick, at most one request of each prompt
length is submitted; another of the same length waits for the next tick,
and its latency counts the wait.  The engine batches prompts of one length
into one prefill and compiles a program per (length, batch), so without
the rule a collision in the window would compile there.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from traffic import Req, prompt_tokens


@dataclasses.dataclass
class Flight:
    """One request as the client saw it (times on the host clock)."""
    req: Req
    rid: int
    due: float                  # when it was due to be sent
    sent: float | None = None   # when ``submit`` took it
    arrivals: list = dataclasses.field(default_factory=list)
    done: float | None = None   # when its last token arrived
    client: int | None = None


@dataclasses.dataclass
class Record:
    """What one run saw; the metrics are computed from this alone."""
    flights: list
    t0: float = 0.0             # window opens
    t1: float = 0.0             # window closes
    deadline: float = 0.0       # t0 + the window's seconds
    ticks: int = 0              # ticks inside the window
    kv_used: list = dataclasses.field(default_factory=list)  # (t, share)
    switches: list = dataclasses.field(default_factory=list)
    late_s: list = dataclasses.field(default_factory=list)
    # programs built ({"built": compiled or loaded, "loaded": from the
    # persistent cache}) before the window opened, and inside it
    builds_at_open: dict = dataclasses.field(default_factory=dict)
    builds_in_window: dict = dataclasses.field(default_factory=dict)
    failed: dict = dataclasses.field(default_factory=dict)   # rid -> why
    # page bucket of the batch's longest context -> ticks in the window
    page_buckets: dict = dataclasses.field(default_factory=dict)


class Driver:
    def __init__(self, rt, vocab: int, seed: int, *, pages_per_chip: int,
                 page_size: int, traced: bool = False,
                 clock=time.perf_counter):
        self.rt = rt
        self.vocab = vocab
        self.seed = seed
        self.clock = clock
        self.traced = traced
        self.pages_per_chip = pages_per_chip
        self.page_size = page_size
        self.flights: dict[int, Flight] = {}
        self.inflight: set[int] = set()
        self.queue: list[Flight] = []       # due, not yet submitted
        self.kv_used: list[float] = []
        self.next_rid = 0

    def span(self, name: str):
        """A host span in the profiler's trace (traced runs only)."""
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- one tick ------------------------------------------------------------

    def flight(self, req: Req, due: float, **kw) -> Flight:
        f = Flight(req=req, rid=self.next_rid, due=due, **kw)
        self.next_rid += 1
        self.flights[f.rid] = f
        self.queue.append(f)
        return f

    def submit_due(self, now: float) -> None:
        """Submit every queued request due by ``now``, one per prompt
        length per tick (see the module docstring)."""
        lengths, keep = set(), []
        with self.span("submit"):
            for f in self.queue:
                if f.due > now or f.req.prompt_len in lengths:
                    keep.append(f)
                    continue
                lengths.add(f.req.prompt_len)
                prompt = prompt_tokens(
                    dataclasses.replace(f.req, rid=f.rid), self.vocab,
                    self.seed)
                self.rt.submit(f.rid, prompt, f.req.max_new,
                               type_id=f.req.type_id)
                f.sent = self.clock()
                self.inflight.add(f.rid)
        self.queue = keep

    def tick(self) -> list[Flight]:
        """One ``rt.step()``; returns the flights that completed in it."""
        with self.span("step"):
            self.rt.step()
        now = self.clock()
        done = []
        log = self.rt.request_log
        for rid in list(self.inflight):
            f = self.flights[rid]
            n = len(log[rid].emitted)
            if n > len(f.arrivals):
                f.arrivals.extend([now] * (n - len(f.arrivals)))
            if n >= f.req.max_new:
                f.done = now
                self.inflight.discard(rid)
                done.append(f)
        if self.traced:
            self.kv_used.append((now, self.kv_used_share()))
        return done

    def note_bucket(self, rec: Record) -> None:
        """Count the tick under the page bucket (a power of two, as the
        decode programs are built) of its longest context in flight."""
        ctx = max((self.flights[r].req.prompt_len
                   + len(self.flights[r].arrivals) for r in self.inflight),
                  default=0)
        if ctx:
            b = _pow2(-(-ctx // self.page_size))
            rec.page_buckets[b] = rec.page_buckets.get(b, 0) + 1

    def kv_used_share(self) -> float:
        """1 - free pages / pages of the live replicas."""
        live = [h for h in self.rt.replicas if not h.dead]
        pages = self.pages_per_chip * sum(h.rc.chips for h in live)
        free = sum(s["free_blocks"] for s, h in
                   zip(self.rt.load_stats(), self.rt.replicas) if not h.dead)
        return 1.0 - free / pages

    def shed(self) -> set[int]:
        return set(self.rt.all_shed_rids)

    # -- set-up ----------------------------------------------------------------

    def warm(self, mix: dict, page: int) -> int:
        """Build what the window will run on every live replica, through
        the engines' own ``submit`` and the runtime's ``step``.

        Prefill: one request of every prompt length the mix can send.
        Decode: for each batch bucket the loop can reach (a closed loop
        keeps its batch full: only the bucket of ``max_seqs``; an open
        loop reaches them all) and each page bucket, a wave of that many
        distinct prompt lengths whose longest context falls in the page
        bucket, decoded one step.  Where too few lengths fit a page bucket
        to fill the batch bucket, the ladder for that batch stops: the
        window cannot decode such a batch without repeating a length.
        Requests go to each engine directly, so every replica sees every
        wave; they are not the window's and leave no record.  Returns the
        requests sent."""
        grid = sorted({L for t in mix["types"] for L in range(
            t["prompt"]["min"], t["prompt"]["max"] + 1,
            t["prompt"].get("quantum", 1))})
        engines = [h.engine for h in self.rt.replicas if not h.dead]
        plans = []
        for e in engines:
            top = _pow2(e.max_seqs)
            batches = ([top] if mix["loop"] == "closed" else
                       [1 << i for i in range(top.bit_length())])
            plans.append(_ladder(grid, batches, e.max_seqs, page,
                                 mix["max_context"]))
        sent = 0
        for i in range(max(len(p) for p in plans)):
            for e, p in zip(engines, plans):
                for L in (p[i] if i < len(p) else []):
                    if not e.fits(L, 2):
                        continue
                    req = Req(rid=self.next_rid, type_id=0, prompt_len=L,
                              max_new=2)
                    e.submit(self.next_rid,
                             prompt_tokens(req, self.vocab, self.seed), 2)
                    self.next_rid += 1
                    sent += 1
            while self.rt.pending:
                self.rt.step()
        for e in engines:
            self.warm_table_updates(e)
        return sent

    @staticmethod
    def warm_table_updates(engine) -> None:
        """Before a decode step the engine writes the batch's new pages
        into the device block table in one eager scatter, whose program
        depends on how many pages the batch crossed into (one to the whole
        batch).  Build each by writing row 0's own values back: the table
        does not change."""
        cache = getattr(engine, "cache", None)
        write = getattr(cache, "apply_table_updates", None)
        if write is None:
            return
        for n in range(1, min(engine.max_seqs, cache.block_table.shape[1]) + 1):
            write([(0, 0, [int(b) for b in cache.block_table[0, :n]])])

    # -- the two loops -----------------------------------------------------------

    def run_closed(self, reqs: list[Req], clients: int, seconds: float,
                   builds, on_open=None, on_close=None) -> Record:
        """``clients`` callers; each sends its next request (the next size
        of ``reqs``, cycling) when its last one completes.  The first
        requests are in flight before the window opens.

        When ``seconds`` are up no tick is started; the window closes when
        the tick in progress has ended, on the clock read after it, so it
        holds whole ticks: every token it counts, over all its time."""
        k = 0

        def send(client: int, now: float) -> None:
            nonlocal k
            self.flight(reqs[k % len(reqs)], due=now, client=client)
            k += 1

        now = self.clock()
        for c in range(clients):
            send(c, now)
        first = set(self.flights)
        while any(not self.flights[r].arrivals for r in first):
            self.submit_due(self.clock())
            for f in self.tick():
                send(f.client, f.done)
        rec = Record(flights=[])
        rec.builds_at_open = b0 = builds()
        if on_open is not None:
            on_open()
        with self.span("window"):
            rec.t0 = self.clock()
            rec.deadline = rec.t0 + seconds
            while True:
                now = self.clock()
                if now >= rec.deadline:
                    break
                self.submit_due(now)
                for f in self.tick():
                    send(f.client, f.done)
                self.note_bucket(rec)
                rec.ticks += 1
            rec.t1 = now
        rec.builds_in_window = {k: v - b0[k] for k, v in builds().items()}
        if on_close is not None:
            on_close()
        rec.flights = list(self.flights.values())
        rec.kv_used = self.kv_used
        shed = self.shed()
        rec.failed = {f.rid: "shed" for f in rec.flights if f.rid in shed}
        return rec

    def run_open(self, reqs: list[Req], seconds: float, builds,
                 boundaries: list[tuple[float, object]] = (),
                 on_open=None, on_close=None, grace: float = 60.0) -> Record:
        """Requests sent when due, whatever the server does.  At each
        ``(offset, fn)`` of ``boundaries`` the client calls ``fn()`` (the
        span switch) and records its span.  After the window, ticks go on
        untimed until every request due in the window has its first token
        or has failed, for at most ``grace`` seconds."""
        rec = Record(flights=[])
        todo = list(boundaries)
        rec.builds_at_open = b0 = builds()
        if on_open is not None:
            on_open()
        with self.span("window"):
            rec.t0 = self.clock()
            rec.t1 = rec.deadline = rec.t0 + seconds
            for r in reqs:
                self.flight(r, due=rec.t0 + r.due)
            while True:
                now = self.clock()
                if now >= rec.t1:
                    break
                if todo and now >= rec.t0 + todo[0][0]:
                    _, fn = todo.pop(0)
                    t = self.clock()
                    info = fn()
                    rec.switches.append({"start": t, "end": self.clock(),
                                         **(info or {})})
                    continue
                rec.late_s += [now - f.due for f in self.queue
                               if f.due <= now]
                self.submit_due(now)
                if self.inflight or self.rt.pending:
                    self.tick()
                    self.note_bucket(rec)
                    rec.ticks += 1
                else:
                    nxt = min([f.due for f in self.queue]
                              + [rec.t0 + b for b, _ in todo] + [rec.t1])
                    with self.span("wait"):
                        time.sleep(max(0.0, min(nxt - self.clock(), 0.05)))
        rec.builds_in_window = {k: v - b0[k] for k, v in builds().items()}
        if on_close is not None:
            on_close()
        # untimed: the tail of every request due in the window
        end = rec.t1 + grace
        while self.clock() < end:
            self.submit_due(float("inf"))
            waiting = [f for f in self.flights.values() if not f.arrivals]
            if not waiting:
                break
            shed = self.shed()
            if all(f.rid in shed for f in waiting):
                break
            self.tick()
        rec.flights = list(self.flights.values())
        rec.kv_used = self.kv_used
        shed = self.shed()
        for f in rec.flights:
            if f.rid in shed:
                rec.failed[f.rid] = "shed"
            elif not f.arrivals:
                rec.failed[f.rid] = "no first token within the grace"
        return rec


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _ladder(grid: list, batches: list, max_seqs: int, page: int,
            max_context: int) -> list:
    """The warm-up waves of one replica (see ``Driver.warm``); the last
    wave prefills the lengths no decode wave used."""
    waves = []
    for b in batches:
        n = min(b, max_seqs)
        p = _pow2(-(-max_context // page))
        while p >= 1:
            wave = [L for L in grid if -(-(L + 1) // page) <= p][-n:]
            if not wave or _pow2(len(wave)) != b:
                break
            waves.append(wave)
            p //= 2
    covered = {L for w in waves for L in w}
    return waves + [[L for L in grid if L not in covered]]


def summary(rec: Record) -> dict:
    """Sample counts and how late the generator ran (an earlier line)."""
    late = np.asarray(rec.late_s) if rec.late_s else np.zeros(1)
    return {"requests": len(rec.flights), "ticks_in_window": rec.ticks,
            "window_s": rec.t1 - rec.t0,
            "page_buckets": {str(k): v for k, v in
                             sorted(rec.page_buckets.items())},
            "builds_in_window": rec.builds_in_window,
            "late_p50_ms": float(np.median(late) * 1e3),
            "late_max_ms": float(late.max() * 1e3),
            "failed": len(rec.failed)}
