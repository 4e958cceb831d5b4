"""What the block decode passes yielded, from the program's dispatch ledger:
the ``engine.decode_burst`` records that started inside the timed window
carry, for a model that generates by blocks, ``row_passes_denoise`` and
``row_passes_commit`` (passes of real rows through the decode program, by
kind: a denoise pass decides a group of offsets, and since PR 48 writes the
K/V of the block before on its way where that block awaits its commit,
counted by ``row_commits_fused``; a commit pass wrote a block's K/V and
decided nothing: the schedule before PR 48, 0 on every record since) and
``tokens_decided`` (tokens those passes decided that reached a request:
never a forced prompt token, never one past a request's end).  The same
numbers grow ``engine_block_*_total``.

``what="tokens_per_row_pass"``: tokens over row-passes of either kind.  A
row's pass decides ``block_length / denoising_steps`` offsets, so that is
the most it can read: 2 with blocks of 4 filled in 2 passes (1.995 in the
cell: a finished row's last burst and an answer's partial block yield less;
4/3 when a third pass committed each block).  What a schedule that decides
more offsets a pass would move.

A program whose records carry no such counts (any other model; the parent
of the PR that added them) gives nothing to read.
"""

KEYS = ("row_passes_denoise", "row_passes_commit", "tokens_decided")


def read(ctx, what: str):
    if what != "tokens_per_row_pass":
        raise ValueError(f"unknown quantity {what!r}")
    t0, t1 = ctx.load.t0 * 1e6, ctx.load.t1 * 1e6
    found = [ev["args"] for ev in ctx.spans
             if ev.get("name") == "engine.decode_burst"
             and ev.get("ph") == "X" and t0 <= ev["ts"] < t1
             and all(k in (ev.get("args") or {}) for k in KEYS)]
    passes = sum(a["row_passes_commit"] + a["row_passes_denoise"]
                 for a in found)
    if not passes:
        return None
    return sum(a["tokens_decided"] for a in found) / passes
