"""The one traffic generator: a mix is a data file of parameters.

A mix file (benchmarks/traffic/<name>.json) states the loop kind, the
arrival law, the length laws and the sharing structure; a cell file
(benchmarks/cells/<cell>.json) fixes the rate or the number of callers.

Every seed gets the SAME sizes, in the SAME order, at the SAME arrival
instants: sizes are the law's stratified quantiles in one fixed order,
arrivals are a canonical draw fixed by the mix, and ``--seed`` draws the
text (and, in the harness, the weights).  Runs of different seeds then do
the same work.  Twice on the chip the seed drew the order, and twice it
changed the work: a window holds some 130 of the sizes, so each seed met
another sample of them, and ``tpot_p95_ms`` read 83 ms under one seed and
96 ms under two others while two runs of one seed differed by 2-3% (PERF.md).

All lengths are tokens as the engine sees them: the benchmark's tokenizer
is one byte, one token, and the chat template's bytes are counted here
(``rendered_tokens``), so a request's text is cut to land on its length.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass, field
from statistics import NormalDist

# bytes the program's fallback chat template (inference/client.py
# render_messages) puts around the parts, plus the BOS token
BOS = 1
USER_OPEN = len("<|user|>\n")
ASSISTANT_OPEN = len("<|assistant|>\n")
TAIL = len("\n<|assistant|>\n")
SYSTEM_WRAP = len("<|system|>\n") + len("\n")
ALPHABET = "abcdefghijklmnopqrstuvwxyz     "


def rendered_tokens(system_len: int, turns: list[tuple[int, int]], user_len: int) -> int:
    """Prompt tokens of a turn: optional system text, earlier (user,
    assistant) turns, the new user part."""
    lines = []
    for u, a in turns:
        lines += [USER_OPEN + u, ASSISTANT_OPEN + a]
    lines.append(USER_OPEN + user_len)
    head = SYSTEM_WRAP + system_len if system_len else 0
    return BOS + head + sum(lines) + (len(lines) - 1) + TAIL


def text(rng: random.Random, n: int) -> str:
    """n bytes of seeded lower-case text; never starts or ends with a space
    (the template strips assistant bodies)."""
    if n <= 0:
        return ""
    body = "".join(rng.choices(ALPHABET, k=n))
    return ("x" + body[1:-1] + "y")[:n] if n > 1 else "x"


def quantiles(law: dict, n: int) -> list[int]:
    """The law's n stratified quantiles: the same multiset for every seed."""
    kind = law["law"]
    us = [(i + 0.5) / n for i in range(n)]
    if kind == "const":
        return [int(law["value"])] * n
    if kind == "uniform":
        lo, hi = law["min"], law["max"]
        return [int(round(lo + u * (hi - lo))) for u in us]
    if kind == "lognormal":
        mu, sigma = math.log(law["median"]), law["sigma"]
        nd = NormalDist()
        vals = [math.exp(mu + sigma * nd.inv_cdf(u)) for u in us]
        return [int(round(min(max(v, law["min"]), law["max"]))) for v in vals]
    if kind == "choice":
        total = float(sum(law["weights"]))
        out, acc, j = [], law["weights"][0] / total, 0
        for u in us:
            while u > acc and j < len(law["values"]) - 1:
                j += 1
                acc += law["weights"][j] / total
            out.append(int(law["values"][j]))
        return out
    raise ValueError(f"unknown length law {kind!r}")


def law_bounds(law: dict) -> tuple[int, int]:
    if law["law"] == "const":
        return int(law["value"]), int(law["value"])
    if law["law"] == "choice":
        return int(min(law["values"])), int(max(law["values"]))
    return int(law["min"]), int(law["max"])


def scaled(law: dict, scale: int) -> dict:
    """The law with every length divided by ``scale`` (CPU rehearsal)."""
    if scale == 1:
        return law
    out = dict(law)
    for key in ("value", "min", "max", "median"):
        if key in out:
            out[key] = max(1, out[key] // scale)
    if "values" in out:
        out["values"] = [max(1, v // scale) for v in out["values"]]
    return out


def arrival_times(arrivals: dict, rate: float, seconds: float) -> list[float]:
    """Arrival instants in [0, seconds): a Poisson process at mean ``rate``
    whose rate is ``burst_factor`` times the base for ``burst_len_s`` in
    every ``burst_every_s``.  Drawn from the mix's own ``canonical_seed`` by
    time rescaling, so they depend on the mix, the rate and the length of
    the run, never on ``--seed``."""
    rng = random.Random(arrivals.get("canonical_seed", 0))
    every = float(arrivals.get("burst_every_s", 0) or 0)
    blen = float(arrivals.get("burst_len_s", 0) or 0)
    factor = float(arrivals.get("burst_factor", 1) or 1)
    offset = float(arrivals.get("burst_offset_s", 0) or 0)
    if every <= 0 or blen <= 0 or factor == 1:
        base, every, blen, factor = rate, 1.0, 0.0, 1.0
    else:
        base = rate * every / (blen * factor + (every - blen))

    def rate_at(t: float) -> float:
        return base * factor if (t - offset) % every < blen else base

    out, t = [], 0.0
    need = rng.expovariate(1.0)  # unit-rate gap still to spend
    while t < seconds:
        r = rate_at(t)
        phase = (t - offset) % every
        edge = (blen - phase) if phase < blen else (every - phase)
        edge = edge if edge > 1e-9 else every  # next change of rate
        if need <= r * edge:
            t += need / r
            if t < seconds:
                out.append(t)
            need = rng.expovariate(1.0)
        else:
            need -= r * edge
            t += edge
    return out


@dataclass(frozen=True)
class AgentSpec:
    name: str
    max_tokens: int
    instructions: str | None = None


@dataclass
class Req:
    agent: str
    prompt: str
    out_tokens: int
    prompt_tokens: int
    history: list[tuple[str, str]] = field(default_factory=list)
    due_s: float | None = None  # open loop: seconds after the window opens


class Traffic:
    """One mix at one seed.  ``scale`` divides every length (rehearsal)."""

    def __init__(self, spec: dict, params: dict, seed: int, scale: int = 1):
        self.spec, self.params, self.seed, self.scale = spec, params, seed, scale
        self.loop = spec["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"unknown loop kind {self.loop!r}")
        self.session = spec.get("session")
        self.out_law = scaled(spec["output_tokens"], scale)
        self.prompt_law = scaled(spec["prompt_tokens"], scale) if "prompt_tokens" in spec else None
        self.drain_s = float(spec.get("drain_s", 30.0))
        self.request_timeout_s = float(spec.get("request_timeout_s", 120.0))
        if self.session:
            s = self.session
            self.sys_len = max(8, s["system_tokens"] // scale)
            self.user_law = scaled(s["user_tokens"], scale)
            self.asst_len = law_bounds(self.out_law)[1]
            self.max_context = s["max_context_tokens"] // scale
            rng = random.Random(f"{seed}/system")
            self.systems = [text(rng, self.sys_len) for _ in range(s["system_prompts"])]

    # ------------------------------------------------------------- agents
    def agents(self) -> list[AgentSpec]:
        """One Agent per output budget (and per system prompt): the budget
        is the Agent's ``model_settings.max_tokens``."""
        if self.session:
            return [AgentSpec(f"sys{i}", self.asst_len, s) for i, s in enumerate(self.systems)]
        lo_hi = sorted(set(quantiles(self.out_law, 64)))
        return [AgentSpec(f"out{n}", n) for n in lo_hi]

    def callers(self) -> int:
        return int(self.params["callers"])

    # ------------------------------------------------------ single requests
    def single(self, rng: random.Random, prompt_tokens: int, out_tokens: int,
               due_s: float | None = None) -> Req:
        body = max(1, prompt_tokens - rendered_tokens(0, [], 0))
        return Req(f"out{out_tokens}", text(rng, body), out_tokens,
                   rendered_tokens(0, [], body), due_s=due_s)

    def _pairs(self, n: int, tag: str) -> list[tuple[int, int]]:
        """n (prompt, output) sizes: both laws' quantiles, each in one fixed
        order, the same for every seed."""
        rng = random.Random(f"0/{tag}")
        prompts, outs = quantiles(self.prompt_law, n), quantiles(self.out_law, n)
        rng.shuffle(prompts)
        rng.shuffle(outs)
        return list(zip(prompts, outs))

    def open_schedule(self, seconds: float, start_s: float = 0.0, attempt: int = 0) -> list[Req]:
        """The requests due in [start_s, seconds), in order of due time.
        ``attempt`` counts the windows opened before this one and given up:
        the same sizes at the same instants, in OTHER text, or the prefix
        cache would serve the second window from the first one's pages."""
        times = arrival_times(self.spec["arrivals"], float(self.params["rate_rps"]),
                              max(seconds, 0.0))
        sizes = self._pairs(len(times), "open")
        rng = random.Random(f"{self.seed}/text/{attempt}" if attempt else f"{self.seed}/text")
        return [self.single(rng, p, o, due_s=t)
                for t, (p, o) in zip(times, sizes) if t >= start_s]

    def ramp_block(self, k: int, block_s: float = 10.0) -> list[Req]:
        """Open loop: block k of the same process, for the ramp-in before
        the window (the harness plays blocks until the window opens)."""
        times = arrival_times(dict(self.spec["arrivals"], canonical_seed=-1 - k),
                              float(self.params["rate_rps"]), block_s)
        sizes = self._pairs(len(times), f"ramp{k}")
        rng = random.Random(f"{self.seed}/ramp-text/{k}")
        return [self.single(rng, p, o, due_s=t) for t, (p, o) in zip(times, sizes)]

    # ----------------------------------------------------------- closed loop
    def caller_stream(self, caller: int):
        """Requests of one closed-loop caller, without end.  Sessions: a
        caller runs session after session; the next turn's history is the
        seeded text, not the model's output, so token counts are exact."""
        if not self.session:
            n = 4096
            sizes = self._pairs(n, "closed")
            rng = random.Random(f"{self.seed}/text/{caller}")
            i = caller
            while True:
                p, o = sizes[i % n]
                yield self.single(rng, p, o)
                i += self.callers()
        users = quantiles(self.user_law, 4096)
        random.Random("0/users").shuffle(users)
        rng = random.Random(f"{self.seed}/text/{caller}")
        k = caller * 977
        session = caller
        while True:
            yield from self.session_turns(
                session % len(self.systems),
                (users[(k + j) % len(users)] for j in range(self.session["turns"])),
                rng,
            )
            k += self.session["turns"]
            session += self.callers()

    def session_turns(self, system: int, user_lens, rng: random.Random):
        """Turns of one session; ends early where the next prompt and its
        answer would pass ``max_context_tokens``."""
        history: list[tuple[str, str]] = []
        for user_len in user_lens:
            lens = [(len(u), len(a)) for u, a in history]
            tokens = rendered_tokens(self.sys_len, lens, user_len)
            if tokens + self.asst_len + 1 > self.max_context:
                return
            user = text(rng, user_len)
            yield Req(f"sys{system}", user, self.asst_len, tokens, history=list(history))
            history.append((user, text(rng, self.asst_len)))

    # --------------------------------------------------------------- set-up
    def priming(self) -> list[Req]:
        """Requests sent once in set-up because the traffic needs them: the
        system prompts, so that the window sees a warm prefix cache as a
        long-running worker has."""
        if not self.session:
            return []
        rng = random.Random(f"{self.seed}/prime")
        return [
            Req(f"sys{i}", text(rng, 8), 2, rendered_tokens(self.sys_len, [], 8))
            for i in range(len(self.systems))
        ]

    def prompt_range(self) -> tuple[int, int]:
        """Least and greatest prompt tokens a request of this mix can have."""
        if self.session:
            lo = rendered_tokens(self.sys_len, [], law_bounds(self.user_law)[0])
            return lo, self.max_context - self.asst_len - 1
        return law_bounds(self.prompt_law)

    def warm_sessions(self, chunk: int, rows: int, tag: int):
        """``rows`` lock-step sessions whose turn lengths visit every prefill
        bucket both just past its lower edge and deeper in it (the two
        prefix-reuse classes a bucket has).  Yields one list of Req a turn."""
        u_lo, u_hi = law_bounds(self.user_law)
        lo, hi = self.prompt_range()
        targets, edge = [lo], (lo // chunk + 1) * chunk
        while edge < hi:
            targets += [edge + max(8, chunk // 12), edge + chunk * 2 // 3]
            edge += chunk
        rngs = [random.Random(f"{self.seed}/warm/{tag}/{r}") for r in range(rows)]
        histories: list[list[tuple[str, str]]] = [[] for _ in range(rows)]
        lens: list[tuple[int, int]] = []
        system = tag % len(self.systems)
        for target in targets:
            user_len = min(max(target - rendered_tokens(self.sys_len, lens, 0), u_lo), u_hi)
            tokens = rendered_tokens(self.sys_len, lens, user_len)
            if tokens + self.asst_len + 1 > self.max_context:
                return
            step = []
            for r in range(rows):
                user = text(rngs[r], user_len)
                step.append(Req(f"sys{system}", user, self.asst_len, tokens,
                                history=list(histories[r])))
                histories[r].append((user, text(rngs[r], self.asst_len)))
            lens.append((user_len, self.asst_len))
            yield step
