"""One general traffic generator, driven by a mix's data file.

A mix (``traffic/<name>.json``) gives the loop kind, the rate or the number
of clients, the distributions of prompt and output lengths, and what
prompts share.  From it and a seed this module makes the whole plan of a
run before the first request is sent: every request's due time (open loop)
or its place in a client's sequence (closed loop), its prompt text and its
output length.

Every seed offers the same work, in another order of time.  The multiset
of lengths and of gaps between arrivals is fixed by the mix (evenly spaced
quantiles of each distribution, so a run holds the distribution's tail
every time).  They are laid out once, by a fixed shuffle, as one cycle as
long as the window; the seed picks the point of the cycle at which the
window opens, and every word.  So the window of every run holds the whole
multiset exactly once, lead-in and tail included or not, and no seed is
luckier than another in what it is asked to do.  Whether the order is
rotated or shuffled anew made no difference to how far runs spread (5.7 %
against 5.5 % on the median time to first token, PERF.md section 6, PR 24:
the spread is the serve process's, not the order's); the rotation is what
the bounds were measured with.
"""

from __future__ import annotations

import math
import random
import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class Request:
    index: int
    #: seconds from the window's start at which the request is due (open
    #: loop; negative during the lead-in); None in a closed loop
    due: Optional[float]
    prompt: str
    prompt_words: int
    max_tokens: int
    #: index of the shared document the prompt starts with, or None
    document: Optional[int] = None


@dataclass
class Plan:
    loop: str
    lead_s: float
    tail_s: float
    timeout_s: float
    #: open loop: every request in due order
    requests: List[Request] = field(default_factory=list)
    #: closed loop: one sequence per client, and each client's start
    #: offset in seconds from the window's start (negative)
    clients: List[List[Request]] = field(default_factory=list)
    client_starts: List[float] = field(default_factory=list)
    #: prompts sent once during set-up (shared documents), in order
    warm: List[Request] = field(default_factory=list)
    max_context: int = 0

    def all_requests(self) -> List[Request]:
        return self.requests or [r for c in self.clients for r in c]


def _quantile(dist: Dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "uniform":
        return dist["min"] + (dist["max"] - dist["min"]) * u
    if kind == "lognormal":
        z = statistics.NormalDist().inv_cdf(u)
        return math.exp(math.log(dist["median"]) + dist["sigma"] * z)
    if kind == "exponential":
        return -math.log(1.0 - u) * dist.get("mean", 1.0)
    if kind == "gamma":
        # shape 1 / cv^2: cv 1 is the exponential, cv 2 arrivals in clumps
        cv = float(dist.get("cv", 0.0))
        if not cv > 0.0:
            raise ValueError(f"a gamma distribution needs cv > 0, not "
                             f"{dist.get('cv')!r}")
        k = 1.0 / (cv * cv)
        return _gamma_quantile(k, u) * dist.get("mean", 1.0) / k
    raise ValueError(f"unknown distribution {kind!r}")


def _gamma_lower(k: float, x: float) -> float:
    """P(k, x), the regularised lower incomplete gamma function, by its
    series: every term is positive, so it keeps its digits at any x."""
    term = total = 1.0 / k
    a = k
    while term > total * 1e-17:
        a += 1.0
        term *= x / a
        total += term
    return total * math.exp(k * math.log(x) - x - math.lgamma(k))


def _gamma_quantile(k: float, u: float) -> float:
    """The x at which P(k, x) = u for shape ``k`` and scale 1, written out
    because nothing here names SciPy: a bisection on log x, since at
    k = 0.25 a run's quantiles lie between 1e-11 and 10."""
    hi = k + 1.0
    while _gamma_lower(k, hi) < u:
        hi *= 2.0
    lo_t, hi_t = math.log(1e-300), math.log(hi)
    for _ in range(80):
        mid = 0.5 * (lo_t + hi_t)
        if _gamma_lower(k, math.exp(mid)) < u:
            lo_t = mid
        else:
            hi_t = mid
    return math.exp(0.5 * (lo_t + hi_t))


def multiset(dist: Dict, n: int) -> List[float]:
    """``n`` values at the evenly spaced quantiles (i + 0.5) / n, clipped
    to the distribution's ``min`` and ``max`` where it has them."""
    vals = [_quantile(dist, (i + 0.5) / n) for i in range(n)]
    lo = dist.get("min", -math.inf)
    hi = dist.get("max", math.inf)
    return [min(max(v, lo), hi) for v in vals]


def int_multiset(dist: Dict, n: int) -> List[int]:
    return [int(round(v)) for v in multiset(dist, n)]


def zipf_counts(n_items: int, s: float, n: int) -> List[int]:
    """How often each of ``n_items`` appears among ``n`` draws of a
    Zipf(s) popularity, by largest remainder, so the counts are exact."""
    weights = [1.0 / (rank + 1) ** s for rank in range(n_items)]
    total = sum(weights)
    exact = [w / total * n for w in weights]
    counts = [int(math.floor(x)) for x in exact]
    order = sorted(range(n_items), key=lambda i: exact[i] - counts[i],
                   reverse=True)
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def _words(rng: random.Random, n: int, vocab: int) -> str:
    # id 0 doubles as the tokenizer's unknown word; keep clear of it
    return " ".join(f"w{rng.randrange(3, vocab)}" for _ in range(n))


def _shuffled(rng: random.Random, xs: List) -> List:
    xs = list(xs)
    rng.shuffle(xs)
    return xs


def make_plan(mix: Dict, seed: int, seconds: float, vocab: int) -> Plan:
    """The plan of one run.

    The mix fixes one cycle of traffic as long as the window: its lengths,
    its gaps and their order.  The seed picks where in the
    cycle the window starts, and the words.  The lead-in replays the end of
    the cycle before the window and the tail replays its start after it, so
    the window always holds the whole cycle once: the same requests after
    the same predecessors in every run, from another starting point."""
    rng = random.Random(int(seed))
    layout = random.Random(0)  # the cycle's order: the same in every run
    lead = float(mix.get("lead_s", 5.0))
    tail = float(mix.get("tail_s", 0.0))
    plan = Plan(loop=mix["loop"], lead_s=lead, tail_s=tail,
                timeout_s=float(mix.get("request_timeout_s", 60.0)))
    shared = mix.get("shared_prefix")
    documents: List[str] = []
    doc_words: List[int] = []
    if shared:
        doc_words = _shuffled(
            layout, int_multiset(shared["tokens"], int(shared["documents"])))
        documents = [_words(rng, n, vocab) for n in doc_words]
        if shared.get("warm", True):
            plan.warm = [
                Request(index=-1 - d, due=None, prompt=documents[d],
                        prompt_words=doc_words[d], max_tokens=1, document=d)
                for d in range(len(documents))
            ]

    def sizes(n: int) -> List[tuple]:
        """The cycle's (own prompt words, output tokens, document) in the
        mix's order."""
        prompts = _shuffled(layout, int_multiset(mix["prompt_tokens"], n))
        outputs = _shuffled(layout, int_multiset(mix["output_tokens"], n))
        docs: List[Optional[int]] = [None] * n
        if shared:
            pop = shared.get("popularity", {"dist": "zipf", "s": 1.0})
            counts = zipf_counts(len(documents), float(pop["s"]), n)
            docs = _shuffled(layout, [d for d, c in enumerate(counts)
                                      for _ in range(c)])
        return list(zip(prompts, outputs, docs))

    def request(index: int, size: tuple, due: Optional[float]) -> Request:
        own, output, doc = size
        text, words = _words(rng, own, vocab), own
        if doc is not None:
            text = documents[doc] + " " + text
            words += doc_words[doc]
        return Request(index=index, due=due, prompt=text, prompt_words=words,
                       max_tokens=output, document=doc)

    if mix["loop"] == "open":
        n = max(1, int(round(float(mix["rate_rps"]) * seconds)))
        gaps = multiset(mix.get("arrivals", {"dist": "exponential"}), n)
        scale = seconds / sum(gaps)
        gaps = _shuffled(layout, [g * scale for g in gaps])
        cycle = sizes(n)
        start = rng.randrange(n)
        gaps = gaps[start:] + gaps[:start]
        cycle = cycle[start:] + cycle[:start]
        offsets, t = [], 0.0
        for g in gaps:
            offsets.append(t + g / 2.0)  # a request sits inside its gap
            t += g
        first = -int(math.ceil(lead / seconds))
        last = int(math.ceil(tail / seconds))
        for lap in range(first, last + 1):
            for size, offset in zip(cycle, offsets):
                due = offset + lap * seconds
                if -lead <= due < seconds + tail:
                    plan.requests.append(
                        request(len(plan.requests), size, due))
    elif mix["loop"] == "closed":
        clients = int(mix["clients"])
        per_client = int(mix["requests_per_client"])
        cycle = sizes(clients * per_client)
        start = rng.randrange(clients)
        for c in range(clients):
            mine = cycle[(c + start) % clients::clients]
            plan.clients.append([request(c * per_client + i, size, None)
                                 for i, size in enumerate(mine)])
        plan.client_starts = [-lead + lead * c / clients
                              for c in range(clients)]
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    plan.max_context = max(r.prompt_words + r.max_tokens
                           for r in plan.all_requests())
    return plan
