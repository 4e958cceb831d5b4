"""Arithmetic of the end-to-end metrics: percentiles, request outcomes, rates.

Everything here is pure Python on numbers the client recorded, so the same
inputs give the same metric in every PR.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence

#: A percentile is reported only with this many samples beyond it
#: (choosing-metrics guide, section 1).
SAMPLES_BEYOND = 10


def percentile(values: Sequence[float], p: float) -> float:
    """The p-th percentile (0..100) by linear interpolation between order
    statistics, as ``numpy.percentile``'s default does."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    rank = (p / 100.0) * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))


def samples_beyond(n: int, p: float) -> float:
    """How many of ``n`` samples lie beyond the p-th percentile."""
    return n * (100.0 - p) / 100.0


def supported(n: int, p: float) -> bool:
    return p <= 50.0 or samples_beyond(n, p) >= SAMPLES_BEYOND


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median — the contract's spread, with ``statistics.quantiles``."""
    import statistics

    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


class Outcome:
    """What the client saw of one request.  Times are seconds on the
    client's monotonic clock."""

    __slots__ = ("index", "due", "sent", "first_token", "last_token",
                 "token_times", "tokens_seen", "usage_prompt",
                 "usage_completion", "asked", "done", "finish", "status",
                 "error", "in_window")

    def __init__(self, index: int, due: float, asked: int):
        self.index = index
        self.due = due
        self.asked = asked
        self.sent: Optional[float] = None
        self.first_token: Optional[float] = None
        self.last_token: Optional[float] = None
        #: (arrival time, tokens in the delta) for every content delta
        self.token_times: List[tuple] = []
        self.tokens_seen = 0
        self.usage_prompt: Optional[int] = None
        self.usage_completion: Optional[int] = None
        self.done = False
        self.finish: Optional[str] = None
        self.status = 0
        self.error: Optional[str] = None
        self.in_window = False

    def failed(self) -> Optional[str]:
        """Why this request counts as failed, or None for a sound one:
        refused, errored, timed out, cut short, or miscounted."""
        if self.error:
            return self.error
        if self.status != 200:
            return f"status {self.status}"
        if not self.done:
            return "stream ended without [DONE]"
        if self.usage_completion is None:
            return "no usage in the stream"
        if self.usage_completion != self.asked:
            return (f"{self.usage_completion} tokens, asked {self.asked} "
                    f"(finish {self.finish})")
        if self.tokens_seen != self.usage_completion:
            return (f"counted {self.tokens_seen} tokens in deltas, usage "
                    f"says {self.usage_completion}")
        if self.first_token is None:
            return "no content delta"
        return None

    def ttft_ms(self) -> float:
        return (self.first_token - self.due) * 1000.0

    def late_ms(self) -> float:
        return ((self.sent if self.sent is not None else self.due)
                - self.due) * 1000.0

    def tpot_ms(self) -> Optional[float]:
        """Mean gap between this request's output tokens."""
        if self.usage_completion is None or self.usage_completion < 2:
            return None
        return ((self.last_token - self.first_token) * 1000.0
                / (self.usage_completion - 1))


#: Requests shorter than this have too few gaps for a per-request mean.
TPOT_MIN_TOKENS = 8


def ttft_values(sample: Iterable[Outcome], timeout_s: float) -> List[float]:
    """TTFT of every request of the sample; a failed one enters at the
    client's timeout instead of vanishing."""
    return [timeout_s * 1000.0 if o.failed() else o.ttft_ms() for o in sample]


def tpot_values(sample: Iterable[Outcome], timeout_s: float) -> List[float]:
    out = []
    for o in sample:
        if o.asked < TPOT_MIN_TOKENS:
            continue
        out.append(timeout_s * 1000.0 if o.failed() else o.tpot_ms())
    return out


def tokens_in_window(outcomes: Iterable[Outcome], t0: float,
                     t1: float) -> int:
    """Output tokens whose delta arrived in [t0, t1), whatever request they
    belong to and whether or not it finished inside."""
    n = 0
    for o in outcomes:
        for when, count in o.token_times:
            if t0 <= when < t1:
                n += count
    return n


def end_to_end(kind_of: Dict[str, dict], sample: List[Outcome],
               everything: List[Outcome], t0: float, t1: float,
               timeout_s: float, say) -> Dict[str, float]:
    """Every end-to-end metric named in ``kind_of`` (name -> its definition
    in ``metrics.json``), each with its sample count on a printed line."""
    out: Dict[str, float] = {}
    for name, spec in kind_of.items():
        kind = spec["kind"]
        if kind == "ttft_percentile":
            vals = ttft_values(sample, timeout_s)
        elif kind == "tpot_percentile":
            vals = tpot_values(sample, timeout_s)
        elif kind == "output_tokens_per_s":
            n = tokens_in_window(everything, t0, t1)
            out[name] = n / (t1 - t0)
            say(f"{name}: {n} output tokens arrived in {t1 - t0:.3f}s")
            continue
        else:
            raise ValueError(f"metric {name}: unknown kind {kind!r}")
        p = float(spec["percentile"])
        if not supported(len(vals), p):
            raise ValueError(
                f"{name}: {len(vals)} samples leave "
                f"{samples_beyond(len(vals), p):.1f} beyond p{p:g}; "
                f"{SAMPLES_BEYOND} are needed")
        out[name] = percentile(vals, p)
        say(f"{name}: p{p:g} over {len(vals)} requests, "
            f"{samples_beyond(len(vals), p):.1f} beyond it")
    return out
