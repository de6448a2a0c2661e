"""Arithmetic from request samples to end-to-end metrics.

A metric whose inputs a cell does not have (no sample, no token) is left
out; the harness refuses to print a result that lacks a metric the cell is
bound to report.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field


@dataclass
class Sample:
    """One request as the client saw it.  Times are seconds on the host's
    monotonic clock; ``due`` is when the request was due to be sent."""

    due: float
    sent: float = 0.0
    budget: int = 0  # output tokens asked for
    prompt_tokens: int = 0
    events: list[tuple[float, int]] = field(default_factory=list)  # (t, tokens)
    done: float | None = None  # terminal reply seen
    error: str | None = None
    realised_prompt_tokens: int | None = None  # the program's own count
    realised_output_tokens: int | None = None
    text_ok: bool = True  # token events, in order, add up to the final text
    correlation_id: str | None = None  # joins the program's spans

    @property
    def tokens(self) -> int:
        return sum(n for _, n in self.events)

    @property
    def ok(self) -> bool:
        return (
            self.error is None and self.done is not None and self.text_ok
            and self.tokens == self.budget
            and self.realised_output_tokens in (None, self.budget)
        )

    @property
    def ttft_ms(self) -> float | None:
        return (self.events[0][0] - self.due) * 1e3 if self.events else None

    @property
    def tpot_ms(self) -> float | None:
        """(last token event − first) ÷ (output tokens − 1)."""
        if len(self.events) < 2 or self.tokens < 2:
            return None
        return (self.events[-1][0] - self.events[0][0]) * 1e3 / (self.tokens - 1)


def percentile(values: list[float], q: float) -> float | None:
    """Linear-interpolated percentile (q in 0..100) of unsorted values."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def end_to_end(samples: list[Sample], t0: float, seconds: float, chips: int,
               setup_s: float, everything: list[Sample] | None = None) -> dict[str, float]:
    """Every end-to-end metric the samples support, by name.  Tails are
    over ALL requests due in the window that produced the quantity; the
    rate is all tokens that reached the client inside the window, those of
    requests begun in the ramp-in too (``everything``), over all of its
    seconds."""
    out: dict[str, float] = {"setup_s": setup_s}
    # a request that failed or was cut by the drain still counts with what
    # it delivered: the slowest must not drop out of the tail
    ttft = [s.ttft_ms for s in samples if s.ttft_ms is not None]
    tpot = [s.tpot_ms for s in samples if s.tpot_ms is not None]
    for name, values, q in (
        ("ttft_p50_ms", ttft, 50), ("ttft_p95_ms", ttft, 95), ("tpot_p95_ms", tpot, 95),
    ):
        value = percentile(values, q)
        if value is not None:
            out[name] = value
    inside = sum(
        n for s in (everything if everything is not None else samples)
        for t, n in s.events if t0 <= t < t0 + seconds
    )
    if inside:
        out["out_tok_s_per_chip"] = inside / seconds / chips
    return out


def spread(values: list[float]) -> float:
    """Distance between the quartiles as a share of the median, the way the
    contract measures it (statistics.quantiles, n=4)."""
    import statistics

    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
