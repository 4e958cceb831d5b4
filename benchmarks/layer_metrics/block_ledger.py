"""What the block decode passes yielded, from the program's dispatch ledger:
the ``engine.decode_burst`` records that started inside the timed window
carry, for a model that generates by blocks, ``row_passes_denoise`` and
``row_passes_commit`` (passes of real rows through the decode program, by
kind: a denoise pass decides a group of offsets and writes nothing, a commit
pass writes the block's K/V and decides nothing) and ``tokens_decided``
(tokens those passes decided that reached a request: never a forced prompt
token, never one past a request's end).  The same numbers grow
``engine_block_*_total``.

``what="tokens_per_row_pass"``: tokens over row-passes.  With blocks of 4
filled in 2 denoise passes and committed in a third it is at most 4/3; what
a commit fused with the next block's first pass, or a schedule that decides
more offsets a pass, would move.
``what="commit_share"``: commit passes over all row-passes (%): the share of
a row's weight streams that yields no token.

A program whose records carry no such counts (any other model; the parent
of the PR that added them) gives nothing to read.
"""

KEYS = ("row_passes_denoise", "row_passes_commit", "tokens_decided")


def read(ctx, what: str):
    t0, t1 = ctx.load.t0 * 1e6, ctx.load.t1 * 1e6
    found = [ev["args"] for ev in ctx.spans
             if ev.get("name") == "engine.decode_burst"
             and ev.get("ph") == "X" and t0 <= ev["ts"] < t1
             and all(k in (ev.get("args") or {}) for k in KEYS)]
    commit = sum(a["row_passes_commit"] for a in found)
    passes = commit + sum(a["row_passes_denoise"] for a in found)
    if not passes:
        return None
    if what == "tokens_per_row_pass":
        return sum(a["tokens_decided"] for a in found) / passes
    if what == "commit_share":
        return 100.0 * commit / passes
    raise ValueError(f"unknown quantity {what!r}")
