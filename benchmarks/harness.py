"""One run of one cell: build the system under test, warm it, measure.

The path of every measured request is the real one (bench.py's TTFT phase
had the wiring; this is a copy of it made general):

    Client.agent(name).start(...)  ->  kafkad  ->  Worker / Agent
      ->  JaxLocalModelClient  ->  InferenceEngine  ->  token step events
      ->  kafkad  ->  handle.stream()

Nothing calls ``engine.generate`` inside the window.  Set-up does, for the
agreement check and to warm the engine's program shapes.

The window opens by what the run observes, not by a clock: after the
scripted warm-up the cell's own loop runs until JAX has reported no compile
(or cache load) for ``QUIET_S`` seconds, on a cold cache as on a warm one.
A compile event inside an open window abandons that window (the engine
compiles some rare program variants only when the traffic first forms
them): the loop runs on, and the window opens anew after the next
``QUIET_S`` of quiet.  The measured window is the first ``--seconds`` with
no compile event in them; everything before it is set-up.  A window that
can no longer be moved (``open_by``) stays, and one compile event inside it
makes the run incorrect.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import math
import os
import random
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Any

from benchmarks import trace_reduce
from benchmarks.manifest import ROOT, Cell, load_peaks, unregistered
from benchmarks.metrics import Sample, end_to_end, percentile
from benchmarks.reference import agreement
from benchmarks.tokenizer import BenchTokenizer, count_tokens
from benchmarks.traffic import Req, Traffic

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
QUIET_S = 20.0  # the window opens after this long of the cell's loop with no compile event
REQUIET_S = 5.0  # ... and after this long, once a window was abandoned for one
# The last window opens early enough for the run to end inside what the
# contract allows a run (360 s; 1200 s for a run that compiles, which is
# taken to be any run whose set-up before the ramp-in passed COLD_AFTER_S),
# and never later than RAMP_CAP_S after the ramp-in began.
RUN_LIMIT_S, FIRST_RUN_LIMIT_S, COLD_AFTER_S = 360.0, 1200.0, 240.0
RAMP_CAP_S = 300.0
TEARDOWN_S = 10.0
# an open loop's ramp-in comes in blocks of this length, and its window opens
# where one ends (EDGE_S before, so that the next does not begin): every
# window then starts in the same state, however many were given up before it
BLOCK_S, EDGE_S = 10.0, 0.05
now = time.perf_counter


def note(**row: Any) -> None:
    """A line of the run's log on stdout (the result is the LAST line)."""
    print(json.dumps(row), flush=True)


@dataclass
class Compiles:
    """Every backend compile (or cache load) JAX reports: when it ended,
    how long it took, and of which function."""

    stamps: list[tuple[float, float, str]] = field(default_factory=list)

    def listen(self) -> None:
        import jax

        def on(event: str, secs: float, **kw: object) -> None:
            if event == COMPILE_EVENT:
                self.stamps.append((now(), secs, str(kw.get("fun_name", ""))))

        jax.monitoring.register_event_duration_secs_listener(on)

    def between(self, a: float, b: float) -> tuple[int, float]:
        """Events any part of which lies in [a, b)."""
        inside = [s for t, s, _ in self.stamps if t >= a and t - s < b]
        return len(inside), sum(inside)

    def names(self, a: float, b: float) -> list[str]:
        return [name for t, s, name in self.stamps if t >= a and t - s < b]

    def last(self) -> float:
        return self.stamps[-1][0] if self.stamps else -math.inf


def fold_seed(seed: int) -> int:
    """``--seed`` can pass 2**31; JAX keys take 32 signed bits."""
    return int(seed) % (2**31 - 9)


def broker():
    """The in-repo kafkad: the binary the checkout has, else built once
    into the git-ignored .build/native."""
    from calfkit_tpu.mesh.kafka_wire import spawn_kafkad

    try:
        return spawn_kafkad(0)
    except FileNotFoundError:
        build = os.path.join(ROOT, ".build", "native")
        subprocess.run(
            ["make", "-C", os.path.join(ROOT, "native"), f"BIN={build}",
             f"{build}/kafkad", f"{build}/libcrc32c.so"],
            check=True, stdout=subprocess.DEVNULL,
        )
        os.environ["CALFKIT_KAFKAD"] = os.path.join(build, "kafkad")
        os.environ["CALFKIT_CRC32C"] = os.path.join(build, "libcrc32c.so")
        return spawn_kafkad(0)


def history_messages(history: list[tuple[str, str]]) -> list:
    from calfkit_tpu.models.messages import ModelRequest, ModelResponse, TextOutput, UserPart

    out: list = []
    for user, assistant in history:
        out.append(ModelRequest(parts=[UserPart(content=user)]))
        out.append(ModelResponse(parts=[TextOutput(text=assistant)]))
    return out


class Run:
    """State of one run; the readers of per-layer metrics get it as ctx."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 rehearse: bool, t_process: float):
        self.cell, self.seed, self.seconds = cell, seed, float(seconds)
        self.trace, self.rehearse, self.t_process = trace, rehearse, t_process
        self.chips = cell.chips
        self.config = cell.config
        self.arch = cell.arch  # the kind of model: description, weights, reference, counts
        scale = cell.config["rehearsal"]["scale"] if rehearse else 1
        self.traffic = Traffic(cell.traffic, cell.params, seed, scale)
        self.compiles = Compiles()
        self.samples: list[Sample] = []  # requests due inside the window
        self.everything: list[Sample] = []  # those of the ramp-in too
        self.lateness: list[float] = []
        self.spans: list = []
        self.split: dict[str, float] = {}  # set-up seconds by part
        self.counters: dict[str, dict] = {}
        self.pages_peak = 0
        self.pages_total = 0
        self.trace_reduced: dict | None = None
        self.trace_counters: dict | None = None
        self.t0 = self.t_end = math.inf  # set when the window opens
        self.opened = asyncio.Event()  # a window is open ...
        self.moved = asyncio.Event()  # ... or was abandoned, and none is open yet
        self.abandoned: list[dict] = []  # windows given up for a compile event
        self.profiling = False
        self.block_start = math.inf  # open loop: when the ramp block now playing began
        self._tasks: set[asyncio.Task] = set()

    # ------------------------------------------------------------ building
    def build_engine(self):
        import jax

        from calfkit_tpu.inference.engine import InferenceEngine
        from calfkit_tpu.inference.sharding import make_mesh

        self.model_config, self.runtime = self.arch.model(self.config, self.rehearse)
        rt = self.runtime
        self.devices = jax.devices()[: self.chips]
        mesh = make_mesh(tp=rt.tp, dp=rt.dp, devices=self.devices)
        seed = fold_seed(self.seed)
        params = self.arch.params(self.model_config, rt, mesh, seed)  # None: the engine's own
        self.engine = InferenceEngine(self.model_config, rt, params=params, mesh=mesh, seed=seed)
        jax.block_until_ready(self.engine.params)
        self.tokenizer = BenchTokenizer(self.model_config.vocab_size)

    # -------------------------------------------------- set-up on the engine
    async def agreement(self) -> dict:
        """Seeded short prompts, decoded greedily through the engine, against
        the plain float32 reference under the margin rule."""
        spec = self.config["agreement"]
        rng = random.Random(f"{self.seed}/agree")
        vocab = self.model_config.vocab_size
        scale = self.config["rehearsal"]["scale"] if self.rehearse else 1
        prompts = [[rng.randrange(3, vocab) for _ in range(max(4, n // scale))]
                   for n in spec["prompt_tokens"]]

        async def one(prompt):
            return [t async for t in self.engine.generate(
                prompt, max_new_tokens=spec["new_tokens"])]

        outputs = list(await asyncio.gather(*[one(p) for p in prompts]))
        if any(len(o) != spec["new_tokens"] for o in outputs):
            return {"ok": False, "why": "short generation"}
        return await asyncio.to_thread(
            agreement, self.arch.forward_top2, self.engine.params, self.model_config, prompts,
            outputs, float(spec["margin"]), int(spec["min_compared"]),
        )

    async def _direct(self, req: Req, budget: int, system: str | None = None,
                      started: asyncio.Event | None = None) -> int:
        """One model turn straight into the model client (set-up only): the
        same rendering and tokenizer as the served path, and R of them
        gathered reach the engine in one scheduler pass."""
        from calfkit_tpu.engine.model_client import ModelSettings
        from calfkit_tpu.models.messages import ModelRequest, UserPart

        messages = history_messages(req.history) + [
            ModelRequest(parts=[UserPart(content=req.prompt)], instructions=system)
        ]
        n = 0
        stream = self.model.request_stream(messages, ModelSettings(max_tokens=budget))
        try:
            async for _ in stream:
                n += 1
                if started is not None:
                    started.set()
        finally:
            await stream.aclose()
        return n

    def _system_of(self, req: Req) -> str | None:
        return {a.name: a.instructions for a in self.traffic.agents()}.get(req.agent)

    def _window_of(self, needed: int) -> int:
        cap = self.runtime.max_seq_len
        for w in self.runtime.window_buckets:
            if needed <= w <= cap:
                return w
        return cap

    async def warm_shapes(self) -> dict:
        """Run every program shape this cell's traffic reaches in a loaded
        engine, through the model client, in as few dispatches as that
        takes.  For each attention window (held by a long-lived anchor row):
        every prefill bucket x every power-of-two wave width, once with full
        decode dispatches (one wave at a time, nothing waiting) and once
        with short ones (the whole list queued at once: something is always
        waiting, and the rows of the wave before, 9 tokens each, are always
        about to retire).  Multi-chunk buckets meet both a fresh and a
        carried prefill scratch on the way; a closed loop with as many
        callers as slots also gets ``warm_starved``; an open loop, whose engine
        falls idle between arrivals, gets every bucket and wave width once
        more on an IDLE engine (a chunk with no decode row beside it is a
        program of its own).  Whatever else this script misses (the first
        dispatch after idleness) is left to the ramp-in, which lasts until
        no program has compiled for ``QUIET_S``."""
        rt, tr = self.runtime, self.traffic
        chunk, steps = rt.prefill_chunk, rt.decode_steps_per_dispatch
        lo, hi = tr.prompt_range()
        out_hi = max(a.max_tokens for a in tr.agents())
        edges = range((lo - 1) // chunk, (min(hi, rt.max_seq_len - 1) - 1) // chunk + 1)
        buckets = [min((e + 1) * chunk, rt.max_seq_len) for e in edges]
        widest = min(rt.max_prefill_wave, rt.max_batch_size,
                     tr.callers() if tr.loop == "closed" else rt.max_batch_size)
        waves = [r for r in (1, 2, 4, 8, 16, 32) if r <= widest]
        floor = max(lo, -(-int(self.cell.traffic.get("warm_windows_from_tokens", 0)) // tr.scale))
        windows = sorted({self._window_of(n) for n in range(floor, hi + out_hi + 1, 16)}
                         | {self._window_of(hi + out_hi)})
        rng = random.Random(f"{self.seed}/warm")
        spare = next((b for b in (chunk, 2 * chunk) if b <= rt.max_seq_len), chunk)
        tag = [0]

        def plain(bucket: int) -> Req:
            return tr.single(rng, max(bucket - chunk // 2, 8), 2)

        def other(bucket: int) -> Req:
            """A filler request of another bucket than ``bucket``."""
            return plain(spare if spare != bucket else 2 * chunk)

        async def admitted(reqs: list[Req], budget: int) -> list[asyncio.Task]:
            """Submit together; return once every one has its first token
            (the wave has landed and its rows decode)."""
            ups = [asyncio.Event() for _ in reqs]
            tasks = [asyncio.ensure_future(self._direct(r, budget, self._system_of(r), up))
                     for r, up in zip(reqs, ups)]
            waits = [asyncio.ensure_future(up.wait()) for up in ups]
            both = [asyncio.gather(*waits), asyncio.gather(*tasks)]
            for g in both:  # a task cancelled later ends its gather: nothing to report
                g.add_done_callback(lambda f: f.cancelled() or f.exception())
            await asyncio.wait(both, return_when=asyncio.FIRST_COMPLETED)
            for wt in waits:
                wt.cancel()
            return tasks

        dispatched = 0
        for w in windows:
            prev = max([b for b in rt.window_buckets if b < w], default=0)
            top = min(w, rt.max_seq_len)
            anchor_len = prev + max(8, (top - prev) // 8)
            anchors: list[asyncio.Task] = []
            running: list[asyncio.Task] = []

            async def hold() -> None:
                """The anchor row keeps the window at w; a spent one is replaced."""
                if not anchors or anchors[-1].done():
                    anchors.extend(await admitted(
                        [tr.single(rng, anchor_len, 2)], top - anchor_len - 8))

            try:
                for rows in waves:
                    if tr.session:
                        for short in (False, True):
                            tag[0] += 1
                            for step in tr.warm_sessions(chunk, rows, tag[0]):
                                await hold()
                                if short:  # a row about to retire, then one left waiting
                                    running += await admitted([plain(spare)], steps + 1)
                                    step = step + [plain(spare)]
                                running += await admitted(step, tr.asst_len)
                                dispatched += len(step)
                        continue
                    for b in buckets:  # full dispatches: one wave at a time
                        await hold()
                        wave = await admitted([plain(b) for _ in range(rows)], 2)
                        if b > w:  # rows longer than this window: let them go first
                            await asyncio.gather(*wave)
                        running += wave
                    queue = [other(buckets[0])]  # short ones: the list queued at once
                    for b in buckets:
                        if self._bucket(queue[-1]) == b:
                            queue.append(other(b))
                        queue += [plain(b) for _ in range(rows)]
                    queue.append(other(self._bucket(queue[-1])))
                    await hold()
                    await asyncio.gather(*await admitted(queue, steps + 1))
                    dispatched += len(queue) + rows * len(buckets)
                await asyncio.gather(*running)
                if w == windows[-1] and tr.loop == "closed" and tr.callers() >= rt.max_batch_size:
                    await hold()
                    dispatched += await self.warm_starved(admitted, rng, waves[-1])
            finally:
                for task in anchors:
                    task.cancel()
                for task in [*anchors, *running]:
                    with contextlib.suppress(asyncio.CancelledError, Exception):
                        await task
        if tr.loop == "open":  # the engine idle, each time: the wave's chunks run alone
            for rows in waves:
                for b in buckets:
                    await asyncio.gather(*await admitted([plain(b) for _ in range(rows)], 2))
                    dispatched += rows
        return {"buckets": buckets, "waves": waves, "windows": windows,
                "requests": dispatched}

    async def warm_starved(self, admitted, rng: random.Random, widest: int) -> int:
        """Decode-only dispatches with a request left waiting, full and
        short: the state of an engine whose pages (or slots) have run out.
        Requests that each reserve a whole sequence's pages take the pool;
        one more cannot be placed; a row with a short fuse nears its budget
        meanwhile.  Everything is cancelled afterwards."""
        rt, tr = self.runtime, self.traffic
        steps = rt.decode_steps_per_dispatch
        hogs = min((rt.pool_pages() - 1) // rt.pages_per_seq(), rt.max_batch_size)
        fuse = (await admitted([tr.single(rng, 8, 2)], steps * (-(-hogs // widest) + 4)))[0]
        held = [asyncio.ensure_future(self._direct(tr.single(rng, 8, 2), rt.max_seq_len - 64))
                for _ in range(hogs)]
        try:
            await fuse
        finally:
            for task in held:
                task.cancel()
            for task in held:
                with contextlib.suppress(asyncio.CancelledError, Exception):
                    await task
        return hogs + 1

    def _bucket(self, req: Req) -> int:
        chunk = self.runtime.prefill_chunk
        return min(-(-req.prompt_tokens // chunk) * chunk, self.runtime.max_seq_len)

    # ------------------------------------------------------ the served path
    async def issue(self, req: Req, due: float, sample: Sample) -> None:
        """One request through client, broker, worker, agent and engine,
        timed at the client from the moment it was due."""
        try:
            sample.sent = now()
            with self.annotate("bench.send"):
                handle = await self.client.agent(req.agent).start(
                    req.prompt, message_history=history_messages(req.history) or None,
                    timeout=self.traffic.request_timeout_s,
                )
            sample.correlation_id = handle.correlation_id
            pieces: list[str] = []
            final = None
            async for event in handle.stream():
                step = getattr(event, "step", None)
                if step is None:
                    final = event
                elif step.kind == "token":
                    sample.events.append((now(), count_tokens(step.text)))
                    pieces.append(step.text)
                elif step.kind == "inference":
                    sample.realised_prompt_tokens = step.prompt_tokens
                    sample.realised_output_tokens = step.generated_tokens
            sample.done = now()
            if final is None:
                sample.error = "no terminal result"
            else:
                sample.text_ok = "".join(pieces).strip() == str(final.output).strip()
        except asyncio.CancelledError:
            sample.error = sample.error or "not finished when the drain ended"
            raise
        except Exception as e:  # noqa: BLE001 - a failed request is a count, not a crash
            sample.error = f"{type(e).__name__}: {e}"[:200]

    def annotate(self, name: str):
        """A host span on the profiler's clock (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def _spawn(self, coro) -> asyncio.Task:
        task = asyncio.ensure_future(coro)
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)
        return task

    def _block_edge(self, t: float) -> float:
        """Open loop: the first end of a ramp block at or after ``t``."""
        if self.traffic.loop != "open" or self.block_start == math.inf:
            return t
        blocks = max(1, math.ceil((t + EDGE_S - self.block_start) / BLOCK_S))
        return self.block_start + blocks * BLOCK_S - EDGE_S

    def _sample(self, req: Req, due: float) -> Sample:
        sample = Sample(due=due, budget=req.out_tokens, prompt_tokens=req.prompt_tokens)
        self.everything.append(sample)  # the window's are picked by `due` at the end
        return sample

    async def open_loop(self) -> None:
        """The ramp-in is the same arrival process, block after block, until
        a window opens; the window's own schedule then starts at t0.  Where
        the window is abandoned the blocks go on until the next one opens."""
        async def flips(event: asyncio.Event, t: float) -> bool:
            with contextlib.suppress(asyncio.TimeoutError):
                await asyncio.wait_for(event.wait(), max(0.0, t - now()))
            return event.is_set()

        block, attempt = 0, 0
        while True:
            while not self.opened.is_set():
                start = self.block_start = now()
                for req in self.traffic.ramp_block(block, BLOCK_S):
                    if await flips(self.opened, start + req.due_s):
                        break
                    self._spawn(self.issue(req, now(), self._sample(req, now())))
                await flips(self.opened, start + BLOCK_S)
                block += 1
            t0 = self.t0
            self.lateness.clear()
            for req in self.traffic.open_schedule(self.seconds, attempt=attempt):
                due = t0 + req.due_s
                if await flips(self.moved, due):
                    break
                self.lateness.append(now() - due)
                self._spawn(self.issue(req, due, self._sample(req, due)))
            else:
                return
            attempt += 1

    async def caller(self, index: int) -> None:
        for req in self.traffic.caller_stream(index):
            due = now()
            if due >= self.t_end:
                return
            await self.issue(req, due, self._sample(req, due))

    async def sampler(self) -> None:
        """Every 100 ms: pages in use (for the peak) and the program's
        finished spans (its ring holds 2048)."""
        from calfkit_tpu.observability.trace import TRACER

        ledger = getattr(self.engine, "_ledger", None)
        self.pages_total = getattr(ledger, "pages_total", 0)
        while True:
            if ledger is not None:
                self.pages_peak = max(self.pages_peak, ledger.pages_in_use)
            if self.trace:
                self.spans.extend(TRACER.finished())
                TRACER.clear()
            await asyncio.sleep(0.1)

    def snapshot(self) -> dict:
        return self.engine.stats.counters()

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in b if k != "occupancy_hist"}

    async def profile(self) -> None:
        """Trace a few seconds in the middle of the window."""
        import jax

        span = min(float(self.cell.traffic.get("trace_s", 3.0)), self.seconds / 2)
        await asyncio.sleep(max(0.0, self.t0 + (self.seconds - span) / 2 - now()))
        self.profiling = True  # from here on it runs to its end, abandoned window or not
        trace_dir = os.path.join(ROOT, ".bench_trace", f"{self.cell.name}.{os.getpid()}")
        before = self.snapshot()
        t_a = now()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only where annotated
        await asyncio.to_thread(
            lambda: jax.profiler.start_trace(trace_dir, profiler_options=options))
        with jax.profiler.TraceAnnotation("bench.traced_window"):
            await asyncio.sleep(span)
        after = self.snapshot()
        t_b = now()
        await asyncio.to_thread(jax.profiler.stop_trace)
        self.trace_counters = self.delta(before, after)
        try:
            path = trace_reduce.find_xplane(trace_dir)
            events = await asyncio.to_thread(trace_reduce.load_events, path)
            self.trace_reduced = trace_reduce.reduce(events, t_b - t_a)
        finally:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)

    # --------------------------------------------------------------- the run
    async def run(self) -> dict:
        from calfkit_tpu.client import Client
        from calfkit_tpu.engine.model_client import ModelSettings
        from calfkit_tpu.inference.client import JaxLocalModelClient
        from calfkit_tpu.mesh.kafka_wire import KafkaWireMesh
        from calfkit_tpu.nodes import Agent
        from calfkit_tpu.worker import Worker

        self.compiles.listen()
        t = now()
        await asyncio.to_thread(self.build_engine)
        self.split["engine_start_s"] = now() - t
        proc = broker()
        try:
            url = f"127.0.0.1:{proc.kafkad_port}"
            mesh, client_mesh = KafkaWireMesh(url), KafkaWireMesh(url)
            await client_mesh.start()
            try:
                self.model = JaxLocalModelClient(
                    engine=self.engine, tokenizer=self.tokenizer, max_new_tokens=8)
                await self.model.start()
                agents = [
                    Agent(a.name, model=self.model, instructions=a.instructions,
                          model_settings=ModelSettings(max_tokens=a.max_tokens),
                          stream_tokens=True)
                    for a in self.traffic.agents()
                ]
                lanes = self.config.get("worker", {}).get("max_workers")
                async with Worker(agents, mesh=mesh, owns_transport=True,
                                  **({"max_workers": lanes} if lanes else {})):
                    self.client = Client.connect(client_mesh)
                    try:
                        return await self.measure()
                    finally:
                        await self.client.close()
            finally:
                await client_mesh.stop()
                await self.engine.stop()
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()

    async def measure(self) -> dict:
        tr = self.traffic
        t = now()
        c0 = len(self.compiles.stamps)
        agree = await self.agreement()
        self.split["agreement_s"] = now() - t
        note(phase="agreement", **agree)

        t = now()
        warmed = await self.warm_shapes()
        for req in tr.priming():  # through the served path, as a worker's life would
            await self.issue(req, now(), Sample(due=now(), budget=req.out_tokens))
        self.split["warm_up_s"] = now() - t
        n, _ = self.compiles.between(0.0, now())
        note(phase="warm-up", compiles=n - c0, **warmed)

        # ramp-in: the cell's own loop, until it has run QUIET_S with no compile
        ramp_from = now()
        cold = ramp_from - self.t_process > COLD_AFTER_S
        open_by = max(ramp_from + QUIET_S, min(
            ramp_from + RAMP_CAP_S,
            self.t_process + (FIRST_RUN_LIMIT_S if cold else RUN_LIMIT_S)
            - self.seconds - tr.drain_s - TEARDOWN_S))
        sampler = asyncio.ensure_future(self.sampler())
        drivers = ([asyncio.ensure_future(self.open_loop())] if tr.loop == "open" else
                   [asyncio.ensure_future(self.caller(i)) for i in range(tr.callers())])
        while True:
            while now() < open_by:
                quiet_at = self._block_edge(
                    self.compiles.last() + REQUIET_S if self.abandoned
                    else max(ramp_from, self.compiles.last()) + QUIET_S)
                if now() >= quiet_at:
                    break
                await asyncio.sleep(min(0.25, quiet_at - now()))
            self.t0 = now()
            self.t_end = self.t0 + self.seconds
            self.moved.clear()
            self.opened.set()
            profiler = asyncio.ensure_future(self.profile()) if self.trace else None
            begin = self.snapshot()
            # the window stands unless a compile event falls into it while
            # another window can still be opened in time
            while now() < self.t_end:
                await asyncio.sleep(min(0.25, self.t_end - now()))
                if self.compiles.last() >= self.t0 and now() + REQUIET_S <= open_by:
                    break
            else:
                break
            n, secs = self.compiles.between(self.t0, now())
            self.abandoned.append({"after_s": now() - self.t0, "compiles": n, "compile_s": secs,
                                   "programs": self.compiles.names(self.t0, now())})
            self.t0 = self.t_end = math.inf
            self.opened.clear()
            self.moved.set()
            if profiler is not None:
                if not self.profiling:
                    profiler.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await profiler
                self.profiling, self.trace_reduced, self.trace_counters = False, None, None
        end = self.snapshot()
        self.counters["window"] = self.delta(begin, end)
        setup_s = self.t0 - self.t_process
        self.split["ramp_in_s"] = self.t0 - ramp_from
        n_ramp, _ = self.compiles.between(ramp_from, self.t0)
        self.split["compile_s"] = self.compiles.between(0.0, self.t0)[1]
        note(phase="ramp-in", seconds=self.t0 - ramp_from, compiles=n_ramp,
             programs=self.compiles.names(ramp_from, self.t0),
             capped=self.t0 >= open_by, windows_abandoned=self.abandoned)
        self.samples = [s for s in self.everything if self.t0 <= s.due < self.t_end]

        pending = [*drivers, *self._tasks]
        _, late = await asyncio.wait(pending, timeout=tr.drain_s) if pending else (None, set())
        for task in late | self._tasks:
            task.cancel()
        for task in list(late | self._tasks) + drivers:
            with contextlib.suppress(asyncio.CancelledError, Exception):
                await task
        drained_s = now() - self.t_end
        if profiler is not None:
            await profiler
        sampler.cancel()
        with contextlib.suppress(asyncio.CancelledError):
            await sampler
        return self.result(agree, setup_s, drained_s)

    # ------------------------------------------------------------ the result
    def device(self) -> dict:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in self.devices]
        d0 = self.devices[0]
        out = {"platform": d0.platform, "kind": d0.device_kind, "count": len(self.devices),
               "memory_peak_bytes": max((p for p in peaks if p is not None), default=None)}
        if self.trace_reduced:
            out["busy_s"] = self.trace_reduced["busy_s"]
            out["window_s"] = self.trace_reduced["window_s"]
        return out

    def in_flight(self, t: float) -> int:
        """Requests due by ``t`` and not finished by then: the backlog."""
        return sum(1 for s in self.everything
                   if s.due <= t and (s.done is None or s.done > t))

    def result(self, agree: dict, setup_s: float, drained_s: float) -> dict:
        samples = self.samples
        failed = [s for s in samples if not s.ok]
        in_window, in_window_s = self.compiles.between(self.t0, self.t_end)
        finished = [s for s in samples if s.done is not None and s.error is None]
        short = sum(1 for s in finished if s.tokens != s.budget)
        garbled = sum(1 for s in finished if not s.text_ok)
        # every number compared, beside its limit: (what, value, rule, limit)
        decided = agree.get("compared", 0)
        compared = [
            ("agreement: checks cut short (a short generation, a logit not finite)",
             int("why" in agree or not agree.get("finite", True)), "==", 0),
            ("agreement: positions decided by the margin", decided, ">=",
             agree.get("min_compared", 1)),
            ("agreement: decided positions where the served token is not the reference's",
             decided - agree.get("equal", 0), "==", 0),
            ("compile events in the window", in_window, "==", 0),
            ("finished requests that returned other than their budget of tokens", short, "==", 0),
            ("finished requests whose token events do not add up to their final text", garbled,
             "==", 0),
            ("requests due in the window", len(samples), ">=", 1),
        ]
        faults = []
        for what, value, rule, limit in compared:  # the run's last lines on stderr
            passes = value == limit if rule == "==" else value >= limit
            row = f"{what}: {value} (limit {rule} {limit})"
            print(f"benchmarks/run.py: {'ok  ' if passes else 'FAIL'} {row}", file=sys.stderr)
            if not passes:
                faults.append(row)
        correct = not faults
        print(f"benchmarks/run.py: correct={str(correct).lower()}", file=sys.stderr, flush=True)
        prompts = [s.realised_prompt_tokens or s.prompt_tokens for s in samples]
        everything = end_to_end(samples, self.t0, self.seconds, self.chips, setup_s,
                                self.everything)
        note(
            phase="window", seconds=self.seconds, attempted=len(samples), failed=len(failed),
            errors=sorted({s.error for s in failed if s.error})[:5],
            compiles_in_window=in_window, compile_s_in_window=in_window_s,
            programs_in_window=self.compiles.names(self.t0, self.t_end), faults=faults,
            in_flight={"at_start": self.in_flight(self.t0), "at_end": self.in_flight(self.t_end)},
            drained_s=drained_s,
            prompt_tokens={q: percentile(prompts, q) for q in (5, 50, 95, 100)},
            planned_equals_realised=all(
                s.realised_prompt_tokens in (None, s.prompt_tokens) for s in samples),
            output_tokens={q: percentile([s.tokens for s in samples], q) for q in (5, 50, 100)},
            generator_lateness_ms={
                "p50": (percentile(self.lateness, 50) or 0.0) * 1e3,
                "max": max(self.lateness, default=0.0) * 1e3},
            client_metrics={k: v for k, v in everything.items() if k != "setup_s"},
            setup_split_s=self.split, counters=self.counters.get("window"),
            pages_peak=self.pages_peak, pages_total=self.pages_total,
        )
        if self.trace_reduced:
            note(phase="trace", window_s=self.trace_reduced["window_s"],
                 busy_s=self.trace_reduced["busy_s"],
                 module_seconds=self.trace_reduced.get("by_module"),
                 module_runs=self.trace_reduced.get("module_runs"),
                 counters=self.trace_counters)
        device = self.device()
        if self.rehearse:
            return {"rehearsal": True, "platform": device["platform"], "correct": correct,
                    "attempted": len(samples), "failed": len(failed)}
        if self.trace:
            values = {}
            for metric in self.cell.per_layer:
                value = metric.read(self)
                if value is not None:
                    values[metric.name] = {"value": value, "unit": metric.unit}
            note(phase="recorded-only", values={
                m.name: {"value": m.read(self), "unit": m.unit, "moves": m.moves}
                for m in unregistered(self.cell)})  # the log's, never the result's
        else:
            missing = [m.name for m in self.cell.end_to_end if m.name not in everything]
            if missing:
                raise RuntimeError(f"no value for end-to-end metrics {missing}")
            values = {m.name: {"value": everything[m.name], "unit": m.unit}
                      for m in self.cell.end_to_end}
        out = {"correct": correct, "attempted": len(samples), "failed": len(failed),
               "metrics": values, "device": device}
        if self.trace_reduced and self.trace_reduced.get("devices"):
            out["breakdown"] = {
                "device_ops": trace_reduce.top_ops(self.trace_reduced),
                "idle_gaps": self.trace_reduced["idle_gaps"],
            }
        return out

    # what readers use besides the attributes above
    @property
    def peaks(self) -> dict:
        return load_peaks(self.devices[0].device_kind)
