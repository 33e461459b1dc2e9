"""Drive the program's served path for one cell: record -> publish ->
verified registry ``ReplayChannel`` -> ``Engine``, then a timed window.

The set-up follows the device's own order: the cloud role records and
publishes the cell's two executables; the device boots its channel
(fetch, HMAC-verify, preload, warm); the weights are made on the device
from the seed; the engine is built; warm-up traffic compiles the
executor's eager helpers.  The window then runs the harness's copy of
``Engine.run``'s loop -- ``step_block``, and ``validate`` every
``pipeline_depth`` blocks -- with arrivals submitted between blocks.

Tokens count as delivered when a frontier drain commits them
(``Request.committed`` grows); the loop reads that after every call into
the engine.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.api import Workspace
from repro.core.channel import ExecutionChannel

from bench.harness.traffic import Traffic

KEY = b"bench-registry-signing-key"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
GRACE_S = 60.0          # how long past the window a due first token may take


class CompileLog:
    """Backend compiles and persistent-cache hits/misses while active
    (a copy of ``chip_smoke.CompileLog``)."""

    def __init__(self):
        self.compiles = []            # (function name, seconds)
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event, secs, **kw):
        if event == BACKEND_COMPILE:
            self.compiles.append((kw.get("fun_name", "?"), secs))

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def span(annotate: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if annotate \
        else contextlib.nullcontext()


class TimedChannel(ExecutionChannel):
    """Forwards to the verified channel.  While ``recording``, it times
    each decode dispatch on the host, keeps each block's position input
    (a device array, read once the window has closed) and the decode steps
    each slot still needs, and counts prefills; with ``annotate`` it opens
    the ``prefill`` and ``decode_block`` spans."""

    def __init__(self, inner: ExecutionChannel, annotate: bool):
        self.inner = inner
        self.kind = inner.kind
        self.annotate = annotate
        self.stream = None            # the engine's StreamExecutor
        self.recording = False
        self.keep = False
        self.kept: List[tuple] = []       # (prompt, last_logits), on device
        # slot -> request at the latest decode dispatch: a released slot's
        # rows are rewritten by the next block (it decodes from position 0)
        self.last_ids = None
        self.dispatch_s: List[float] = []
        self.blocks: List[tuple] = []     # (pos device array, steps [B])
        self.prefills: List[int] = []     # prompt lengths

    @property
    def fixed_prompt_len(self):
        return self.inner.fixed_prompt_len

    def prefill(self, params, batch):
        with span(self.annotate, "prefill"):
            out = self.inner.prefill(params, batch)
        if self.recording:
            self.prefills.append(int(batch["tokens"].shape[1]))
        if self.keep:
            self.kept.append((batch["tokens"], out[0]["last_logits"]))
        return out

    def _steps_needed(self) -> np.ndarray:
        st = self.stream
        k, ahead = st.block_k, len(st.inflight)
        steps = np.zeros(st.slots.n_slots, np.int64)
        for i in np.flatnonzero(st.slots.active_mask()):
            r = st.requests[int(st.slots.request_id[i])]
            steps[i] = min(k, max(0, r.max_new - len(r.generated)
                                  - k * ahead))
        return steps

    def decode_block(self, params, tokens, pos, caches):
        self.last_ids = self.stream.slots.request_id.copy()
        steps = self._steps_needed() if self.recording else None
        with span(self.annotate, "decode_block"):
            t0 = time.perf_counter()
            out = self.inner.decode_block(params, tokens, pos, caches)
            dt = time.perf_counter() - t0
        if self.recording:
            self.dispatch_s.append(dt)
            self.blocks.append((pos, steps))
        return out


@dataclasses.dataclass
class Deliveries:
    """What the host received, per request, on the window's clock."""
    due: Dict[int, float] = dataclasses.field(default_factory=dict)
    first: Dict[int, float] = dataclasses.field(default_factory=dict)
    last: Dict[int, float] = dataclasses.field(default_factory=dict)
    seen: Dict[int, int] = dataclasses.field(default_factory=dict)
    gaps: List[tuple] = dataclasses.field(default_factory=list)  # (t, gap)
    tokens: List[tuple] = dataclasses.field(default_factory=list)  # (t, n)
    occupant: Dict[int, int] = dataclasses.field(default_factory=dict)
    #                          slot -> the last request admitted to it


class Loop:
    """The harness's serving loop over one engine and one clock."""

    def __init__(self, eng, annotate: bool):
        self.eng = eng
        self.reqs = eng.stream.requests
        self.annotate = annotate
        self.d = Deliveries()
        self.live: set = set()
        self.blocks = 0
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def submit(self, prompt, max_new: int, due: Optional[float]) -> int:
        with span(self.annotate, "submit"):
            rid = self.eng.submit(list(prompt), max_new)
        self.d.due[rid] = self.now() if due is None else due
        self.d.seen[rid] = 0
        self.live.add(rid)
        return rid

    def observe(self) -> List[int]:
        """Record committed growth; return requests that finished."""
        t, done = self.now(), []
        d = self.d
        ids = self.eng.stream.slots.request_id
        for s in np.flatnonzero(ids >= 0):
            d.occupant[int(s)] = int(ids[s])
        for rid in list(self.live):
            r = self.reqs[rid]
            c, s = r.committed, d.seen[rid]
            if c > s:
                if s == 0:
                    d.first[rid] = t
                else:
                    d.gaps.append((t, t - d.last[rid]))
                d.last[rid] = t
                d.tokens.append((t, c - s))
                d.seen[rid] = c
            if r.done and c >= len(r.generated):
                self.live.discard(rid)
                done.append(rid)
        return done

    def step(self) -> List[int]:
        """One block (and a drain every ``pipeline_depth`` blocks)."""
        with span(self.annotate, "step_block"):
            self.eng.step_block()
        self.blocks += 1
        if self.blocks % self.eng.pipeline_depth == 0:
            with span(self.annotate, "validate"):
                self.eng.validate()
        return self.observe()

    def finish(self):
        with span(self.annotate, "validate"):
            self.eng.validate()
        return self.observe()

    def drain(self, max_blocks: int = 100_000):
        """Serve until idle, as ``Engine.run`` does."""
        b = 0
        while self.eng.stream.has_work() and b < max_blocks:
            self.step()
            b += 1
        self.finish()


@dataclasses.dataclass
class Served:
    """The window's record, on the window's clock (0 = window start)."""
    seconds: float
    deliveries: Deliveries
    counters_before: dict
    counters_after: dict
    lateness: List[float]          # open loop: submit time - due time
    compiles: list                 # backend compiles inside the window
    requests: dict                 # rid -> Request (prompt, generated, ...)
    in_window: List[int]           # requests due inside the window
    unserved: List[int]            # due, never given a first token
    backlog: List[tuple] = dataclasses.field(default_factory=list)
    #                                (t, requests waiting for a slot)


class Session:
    """One cell on the device: executables recorded, published and booted
    once; weights and an engine per seed."""

    def __init__(self, cell, adapter, ref, annotate: bool = False):
        self.cell = cell
        self.adapter = adapter
        self.ref = ref
        self.annotate = annotate
        self.cfg = adapter.program_config(cell.config)
        sh = cell.shape
        self.ws = Workspace(registry=":memory:", key=KEY)
        self.wl = self.ws.workload(
            self.cfg, smoke=False, cache_len=sh["cache_len"],
            block_k=sh["block_k"], batch=sh["slots"], prefill_batch=1,
            seq=cell.traffic["prompt_len"])
        self.boot_s = None
        self.record_s = {}
        self.exe_names = {}
        self.channel: Optional[TimedChannel] = None
        self.eng = None
        self.params = None

    def boot(self):
        """Record and publish (the cloud), then boot the verified channel
        (the device)."""
        for kind in ("prefill", "decode"):
            t0 = time.perf_counter()
            self.wl.publish(self.wl.record(kind))
            self.record_s[kind] = time.perf_counter() - t0
            # jax names a jitted function's module jit_<function name>
            self.exe_names[kind] = "jit_" + self.wl.step(kind)[0].__name__
        t0 = time.perf_counter()
        inner = self.wl.channel()
        self.boot_s = time.perf_counter() - t0
        self.channel = TimedChannel(inner, self.annotate)

    def load(self, seed: int):
        """Weights from the seed, on the device in one call; a fresh
        engine with empty slots."""
        self.release()
        self.params = self.adapter.program_params(
            self.cfg, self.ref, self.cell.config, seed,
            self.wl.param_shardings())
        jax.block_until_ready(self.params)
        sh = self.cell.shape
        self.eng = self.wl.engine(params=self.params, channel=self.channel,
                                  pipeline_depth=sh["pipeline_depth"])
        self.channel.stream = self.eng.stream
        if self.channel.fixed_prompt_len != self.cell.traffic["prompt_len"]:
            raise RuntimeError("the recorded prefill has another prompt "
                               "length than the traffic")

    def release(self):
        """Drop the engine, its caches and the weights; keep the channel."""
        self.eng = None
        self.params = None
        if self.channel is not None:
            self.channel.stream = None
            self.channel.blocks = []
            self.channel.kept = []
        gc.collect()        # the serving stack's objects refer to each other

    def free(self):
        """Drop all of the program's state, executables included."""
        self.release()
        self.channel = None
        self.wl.replayers.clear()
        self.ws = None
        self.wl = None
        gc.collect()

    # -------------------------------------------------------------- serve --
    def warm_open(self, traffic: Traffic) -> Loop:
        """slots + 1 short requests run to completion: every eager helper
        compiles against fresh caches and against the decode step's."""
        sh = self.cell.shape
        loop = Loop(self.eng, self.annotate)
        for r in traffic.warmup(sh["slots"] + 1, 2 * sh["block_k"]):
            loop.submit(r.prompt, r.max_new, None)
        loop.drain()
        return loop

    def serve(self, traffic: Traffic, seconds: float,
              on_window_start=None, on_window_end=None) -> Served:
        """Warm up, then serve ``seconds`` of ``traffic``.  Meanwhile the
        channel keeps every prefill's logits for the check."""
        run = self._serve_closed if traffic.closed else self._serve_open
        self.channel.keep = True
        try:
            return run(traffic, seconds, on_window_start, on_window_end)
        finally:
            self.channel.keep = False

    def _serve_open(self, traffic, seconds, on_start, on_end) -> Served:
        self.warm_open(traffic)
        arrivals = traffic.open_loop(seconds)
        loop = Loop(self.eng, self.annotate)
        if on_start:
            on_start()
        before = dict(self.eng.stats)
        lateness, backlog, i = [], [], 0
        pending = self.eng.stream.pending
        with CompileLog() as cl, span(self.annotate, "window"):
            loop.t0 = time.perf_counter()
            while True:
                now = loop.now()
                if now >= seconds:
                    break
                while i < len(arrivals) and arrivals[i].t <= now:
                    a = arrivals[i]
                    loop.submit(a.prompt, a.max_new, a.t)
                    lateness.append(loop.now() - a.t)
                    i += 1
                if self.eng.stream.has_work():
                    loop.step()
                    backlog.append((loop.now(), len(pending)))
                else:
                    nxt = arrivals[i].t if i < len(arrivals) else seconds
                    time.sleep(max(0.0, min(nxt, seconds) - loop.now()))
            # arrivals due before the close that the loop had no turn for
            for a in arrivals[i:]:
                if a.t < seconds:
                    loop.submit(a.prompt, a.max_new, a.t)
                    lateness.append(loop.now() - a.t)
            in_window = list(loop.d.due)
            # the device finishes the window's blocks inside the span
            loop.finish()
        after = dict(self.eng.stats)
        if on_end:
            on_end()
        unserved = self._after_window(loop, in_window, seconds)
        return Served(seconds, loop.d, before, after, lateness,
                      list(cl.compiles), self.eng.stream.requests,
                      in_window, unserved, backlog)

    def _serve_closed(self, traffic, seconds, on_start, on_end) -> Served:
        """``clients`` callers, each with one request outstanding.  The
        loop runs before the window until clients + 1 + clients / 4
        requests were admitted (warm-up, and the clients' requests ending
        spread out); the window then opens on a loop in progress."""
        clients = int(traffic.mix["clients"])
        gen = traffic.closed_loop()
        loop = Loop(self.eng, self.annotate)
        for _ in range(clients):
            r = next(gen)
            loop.submit(r.prompt, r.max_new, None)
        target = clients + 1 + clients // 4

        def serve_until(stop, count_from):
            while not stop():
                for _rid in loop.step():
                    r = next(gen)
                    rid = loop.submit(r.prompt, r.max_new, None)
                    if count_from is not None:
                        count_from.append(rid)
        serve_until(lambda: self.eng.stats["admitted"] >= target, None)
        loop.finish()       # nothing dispatched before the window runs in it
        if on_start:
            on_start()
        before = dict(self.eng.stats)
        mine: List[int] = []
        with CompileLog() as cl, span(self.annotate, "window"):
            shift = time.perf_counter() - loop.t0
            loop.t0 += shift
            _rebase(loop.d, shift)
            serve_until(lambda: loop.now() >= seconds, mine)
            loop.finish()
        after = dict(self.eng.stats)
        if on_end:
            on_end()
        unserved = self._after_window(loop, mine, seconds)
        return Served(seconds, loop.d, before, after, [], list(cl.compiles),
                      self.eng.stream.requests, mine, unserved)

    def _after_window(self, loop: Loop, due: List[int], seconds: float):
        """Serve on, with no new requests, until every request due in the
        window has its first token (at most ``GRACE_S`` more, counted from
        here: a traced run writes its trace first); return those that
        never got one."""
        waiting = lambda: [r for r in due if r not in loop.d.first]
        deadline = max(seconds, loop.now()) + GRACE_S
        while waiting() and loop.now() < deadline \
                and self.eng.stream.has_work():
            loop.step()
        loop.finish()
        return waiting()


def _rebase(d: Deliveries, shift: float):
    """Move every stamp of ``d`` onto a clock that starts ``shift`` later."""
    for m in (d.due, d.first, d.last):
        for k in m:
            m[k] -= shift
    d.gaps = [(t - shift, g) for t, g in d.gaps]
    d.tokens = [(t - shift, n) for t, n in d.tokens]


def summary(served: Served) -> dict:
    """The window's host-clock numbers (``None`` where nothing to read)."""
    W, d = served.seconds, served.deliveries
    toks = sum(n for t, n in d.tokens if 0.0 <= t <= W)
    ttft = [1e3 * ((d.first[r] if r in d.first else W + GRACE_S)
                   - d.due[r]) for r in served.in_window]
    itl = [1e3 * g for t, g in d.gaps if 0.0 <= t - g and t <= W]
    pct = lambda xs, q: float(np.percentile(xs, q)) if xs else None
    delta = collections.Counter(served.counters_after)
    delta.subtract(served.counters_before)
    return {"tokens": toks, "tokens_per_s": toks / W,
            "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95),
            "itl_p50_ms": pct(itl, 50), "itl_p95_ms": pct(itl, 95),
            "counters": dict(delta)}
