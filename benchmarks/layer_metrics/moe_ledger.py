"""What the routed layers counted, from the program's dispatch ledger: the
``engine.decode_burst`` and ``engine.prefill_segment`` records that started
inside the timed window carry, beside their work, ``moe_assignments`` (real
tokens x experts a token x expert layers), ``moe_held`` (those to experts
this process holds), ``moe_expert_tokens_max`` (the fullest held expert's
tokens, summed over expert layers and decode steps) and
``moe_experts_touched`` (held experts that got a token, summed likewise).
The same numbers grow the ``/metrics`` counters ``engine_moe_*_total``; the
records are read so that a cell's window counters stay the four every cell
reads (tests/benchmarks/test_bm_contract.py).

``what="held_share"``: held over all assignments (%): with 4 chips sharing
a layer, near 25.
``what="imbalance"``: the fullest held expert's tokens over the mean held
expert's, summed over the same layers and steps: ``sum(max) * held_experts
/ sum(held)``; 1.0 is a perfectly even load.

A program that counts nothing (the parent of the PR that added the
counts, a dense model) gives nothing to read.
"""

SPANS = ("engine.decode_burst", "engine.prefill_segment")
KEYS = ("moe_assignments", "moe_held", "moe_expert_tokens_max",
        "moe_experts_touched")


def records(ctx, spans=SPANS):
    """The window's dispatch records that carry the routed layers' counts."""
    t0, t1 = ctx.load.t0 * 1e6, ctx.load.t1 * 1e6
    return [ev["args"] for ev in ctx.spans
            if ev.get("name") in spans and ev.get("ph") == "X"
            and t0 <= ev["ts"] < t1
            and all(k in (ev.get("args") or {}) for k in KEYS)]


def read(ctx, what: str):
    found = records(ctx)
    made = sum(a["moe_assignments"] for a in found)
    held = sum(a["moe_held"] for a in found)
    if not made or not held:
        return None
    if what == "held_share":
        return 100.0 * held / made
    if what == "imbalance":
        fullest = sum(a["moe_expert_tokens_max"] for a in found)
        return fullest * int(ctx.config["num_experts"]) / held
    raise ValueError(f"unknown quantity {what!r}")
