"""The engine's part of generation by blocks (``ModelConfig.block_length``;
models/block_decode.py): the device-side block carry, the decode program
whose every pass decides a group of a row's current block and commits the
block before it where that awaits it, and the host's accounting of a pass
that yields up to ``k`` tokens a row.  A mixin of ``InferenceEngine``; every
other model takes none of it.

**The carry** stays on the device between dispatches, as the one-token
carry does: a row's two blocks of tokens ``[rows, 2 * block]`` (the block
that awaits its commit, then the current one), the current block's first
position ``base``, how many of its offsets are ``decided`` (always ``<
block``), whether the block before it is ``pending`` (fully decided, not
yet in the cache: its first position is ``base - block``), and the penalty
counts.  A pass is ``block_decode_step``; after it a row takes its ``k`` new
tokens, and where they were the block's last group it rolls at once: the
block becomes the pending one, ``base += block``, nothing decided.  So the
next pass on the row decides the next block's first group and writes the
pending block's K/V on the way, and no pass is a commit alone.  A row's
first block (after prefill, after a pool hit, under echo from position 0)
has nothing pending; a row that finishes leaves its last block unwritten.
Rows are out of phase with each other; the host learns each row's phase
from the ``decided``, ``base`` and ``pending`` the program returns beside
the tokens, so it never has to model the device's lead on it.

**Forced outcomes.**  A prompt's whole blocks go through chunk prefill; its
remainder (``n mod block`` tokens) enters the first decode block.  A prompt
token inside the group being decided is that pass's OUTCOME, not its input:
the pass runs with the offset masked and the offset is then set to the
prompt's token.  With a remainder of 2 the first group is skipped whole
(``decided`` starts at 2).  The program reads forced tokens from a plane of
prompt ids ``[rows, max_seq]`` that rides every dispatch, at positions below
``forced_n`` (the prompt's length).  So the log-probability of a token is a
function of the sequence's tokens alone, wherever the prompt ended.

**Echo** is the same mechanism from position 0: an ``echo`` request is not
prefilled at all; its prompt runs through the decode program as forced
outcomes (every group kept, so every prompt token is scored in the pass that
would have decided it) and the blocks are committed as generation's are:
each by the first pass on the block after it.
"""

from __future__ import annotations

import asyncio
import contextlib
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from p2p_llm_tunnel_tpu.engine import sampling
from p2p_llm_tunnel_tpu.models.block_decode import (
    block_decode_step,
    group_size,
)
from p2p_llm_tunnel_tpu.utils.metrics import global_metrics
from p2p_llm_tunnel_tpu.utils.tracing import global_tracer


class BlockDecodeMixin:
    """See the module's text.  ``self._block`` is the model's block length
    (0 for every other model: nothing here is reached)."""

    def _init_block(self, rows: int) -> None:
        cfg, e = self.mcfg, self.ecfg
        if cfg.block_length % max(cfg.denoise_steps, 1):
            raise ValueError(
                f"{cfg.denoise_steps} denoise steps do not divide a block "
                f"of {cfg.block_length}")
        if e.min_prefill_bucket % self._block:
            # a prefix-pool hit has to end on a block boundary
            raise ValueError(
                f"pool blocks of {e.min_prefill_bucket} tokens are not whole "
                f"blocks of {self._block}")
        # Where the row's decode starts, what is decided there, how many
        # positions are forced and from which ids: read at the dispatch
        # that patches the row in (_ov_mask), the last two at every one.
        self._blk_start = np.zeros((rows,), np.int32)
        self._blk_decided0 = np.zeros((rows,), np.int32)
        self._blk_forced = np.zeros((rows,), np.int32)
        self._blk_plane = np.zeros((rows, e.max_seq), np.int32)
        # slot -> the echoed prompt's log-probabilities so far
        self._blk_echo: Dict[int, List[float]] = {}
        self._blk_burst_attrs: Dict[str, int] = {}
        self._dev_decided = self._dev_pending = None

    def _prefill_ids(self, run) -> List[int]:
        """The prompt tokens that prefill computes: all of them, or the
        prompt's whole blocks."""
        ids = run.request.prompt_ids
        if not self._block:
            return ids
        return ids[: len(ids) - len(ids) % self._block]

    def _admit_block_row(self, run) -> None:
        """Host state of a row entering the decode passes (its prompt's
        whole blocks are in the cache, or nothing is: echo)."""
        i, req = run.slot, run.request
        ids, n, k = req.prompt_ids, self._block, group_size(self.mcfg)
        self._blk_plane[i, : len(ids)] = ids
        self._blk_forced[i] = len(ids)
        if req.echo_logprobs:
            # scored from position 0, every group kept
            self._blk_start[i] = self._blk_decided0[i] = 0
            self._blk_echo[i] = [0.0] * len(ids)
            self._logprobs[i] = max(int(req.logprobs), 1)
        else:
            self._blk_echo.pop(i, None)
            rest = len(ids) % n
            self._blk_start[i] = len(ids) - rest
            self._blk_decided0[i] = k * (rest // k)

    # -- the program ------------------------------------------------------

    def _block_decode_fn(
        self, params, kv_cache, tokens, base, decided, pending, counts, bias,
        ov_mask, ov_base, ov_decided, plane, forced_n, samp, key, kv_view,
        steps,
    ):
        """``steps`` chained passes over every row's two blocks.  Returns
        (tokens [B, steps, k], decided, base and pending [B, steps] as each
        pass found them, log-probability data, the carry, cache)."""
        cfg = self.mcfg
        n, k = cfg.block_length, group_size(cfg)
        b = tokens.shape[0]
        s = plane.shape[1]
        base = jnp.where(ov_mask, ov_base, base)
        decided = jnp.where(ov_mask, ov_decided, decided)
        pending = pending & ~ov_mask  # a row patched in has no block behind
        any_pen = jnp.any((samp.freq_pen != 0.0) | (samp.pres_pen != 0.0))
        counts = jax.lax.cond(
            any_pen, lambda: jnp.where(ov_mask[:, None], 0, counts),
            lambda: counts)
        any_lp = jnp.any(samp.logprobs > 0)
        offs = jnp.arange(n)[None, :]
        row_ids = jnp.arange(b)

        def one(carry, _xs):
            toks, base, dec, pend, cnt, cache = carry
            pos = base[:, None] + offs
            forced = pos < forced_n[:, None]
            prompt = jnp.take_along_axis(
                plane, jnp.clip(pos, 0, s - 1), axis=1)
            prev, cur = toks[:, :n], jnp.where(forced, prompt, toks[:, n:])
            logits, cache, *moe = block_decode_step(
                cfg, params, cache, jnp.concatenate([prev, cur], axis=1),
                base, dec, pend, kv_view=kv_view,
                with_stats=self._moe_counts)
            out_tok, out_lp = [], []
            with jax.named_scope("head_sample"):
                for j in range(k):
                    off = dec + j
                    sampled = sampling.sample(
                        logits[:, j], samp, None, counts=cnt,
                        pos=base + off, bias=bias)
                    with jax.named_scope("denoise_select"):
                        at = offs == off[:, None]
                        is_forced = jnp.any(at & forced, axis=1)
                        tok = jnp.where(
                            is_forced, jnp.sum(jnp.where(at, prompt, 0), 1),
                            sampled).astype(jnp.int32)
                        cur = jnp.where(at, tok[:, None], cur)
                    cnt = jax.lax.cond(
                        any_pen,
                        lambda cnt=cnt, tok=tok, is_forced=is_forced:
                        cnt.at[row_ids, tok].add(
                            jnp.where(is_forced, 0, 1)),
                        lambda cnt=cnt: cnt)
                    out_lp.append(jax.lax.cond(
                        any_lp,
                        lambda j=j, tok=tok: sampling.logprob_data(
                            logits[:, j], tok),
                        lambda: sampling.empty_logprob_data(
                            b, logits.shape[-1])))
                    out_tok.append(tok)
            # the block's last group: it awaits its commit from here on, and
            # the row's next pass is the first on the block after it
            full = dec + k >= n
            nxt = (jnp.concatenate(
                       [jnp.where(full[:, None], cur, prev), cur], axis=1),
                   jnp.where(full, base + n, base),
                   jnp.where(full, 0, dec + k), full, cnt, cache)
            lp = jax.tree.map(lambda *a: jnp.stack(a, axis=1), *out_lp)
            return nxt, (jnp.stack(out_tok, axis=1), dec, base, pend, lp, moe)

        (tokens, base, decided, pending, counts, kv_cache), ys = jax.lax.scan(
            one, (tokens, base, decided, pending, counts, kv_cache), None,
            length=steps)
        toks, decs, bases, pends, lps, moe = ys
        # [steps, B, ...] scan stacking -> [B, steps, ...] for the host
        lp_out = jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), lps)
        head = tuple(m.sum(axis=0) for m in moe)
        return head + (jnp.swapaxes(toks, 0, 1), decs.T, bases.T, pends.T,
                       lp_out, tokens, base, decided, pending, counts,
                       kv_cache)

    def _block_warm_args(self, view: int, steps: int):
        """Positional args of the block decode program, aval-identical to
        the live call."""
        rows = self.ecfg.num_slots + 1
        zeros = jnp.zeros((rows,), jnp.int32)
        return (
            self.params, self.kv_cache, self._dev_tokens,
            self._dev_positions, self._dev_decided, self._dev_pending,
            self._dev_counts, self._bias, jnp.zeros((rows,), bool), zeros,
            zeros,
            jnp.zeros((rows, self.ecfg.max_seq), jnp.int32), zeros,
            self._warm_samp(rows), self._key, view, steps,
        )

    # -- dispatch and accounting -------------------------------------------

    def _dispatch_block_decode(self, view: Optional[int],
                               steps: Optional[int]):
        """``_dispatch_decode`` for a model that generates by blocks: one
        burst of ``steps`` passes, not blocking."""
        self._ensure_decode_carry()
        active = self._active_mask
        # inactive rows are parked past the cache at every dispatch, as the
        # one-token carry's are: they write nothing and force nothing
        inactive = ~active
        ov_mask = self._ov_mask | inactive
        ov_base = np.where(inactive, self.ecfg.max_seq, self._blk_start)
        ov_decided = np.where(inactive, 0, self._blk_decided0)
        forced = np.where(active, self._blk_forced, 0)
        view = self._kv_view_bucket() if view is None else view
        steps = self._burst_steps() if steps is None else steps
        slots = self.ecfg.num_slots
        live = int(np.count_nonzero(active[:slots]))
        rec = self._last_dispatch = self._open_dispatch(
            "engine.decode_burst", "decode", view=view, steps=steps,
            live_rows=live, slots=slots, attn="einsum", block=self._block,
        ) if global_tracer.enabled and not self._warming else None
        t_jit0 = time.monotonic()
        with rec.annotation() if rec else contextlib.nullcontext():
            (toks, decs, bases, pends, lp_out, self._dev_tokens,
             self._dev_positions, self._dev_decided, self._dev_pending,
             self._dev_counts, self.kv_cache) = self._jit_decode(
                self.params, self.kv_cache, self._dev_tokens,
                self._dev_positions, self._dev_decided, self._dev_pending,
                self._dev_counts, self._bias, jnp.array(ov_mask),
                jnp.array(ov_base), jnp.array(ov_decided),
                jnp.array(self._blk_plane), jnp.array(forced),
                self._burst_samp(), self._next_key(), view, steps,
            )
        self._note_program("decode", (view, steps),  # tunnelcheck: disable=TC17  the kind is planned where every decode program is: engine.py warmup_plan() enumerates ("decode", (view, steps)) and warms it through _dispatch_decode, which routes here
                           time.monotonic() - t_jit0)
        self._last_burst = (steps, live)
        if not self._warming:
            global_metrics.inc("engine_decode_steps_total", steps)
            global_metrics.inc("engine_decode_row_steps_total", live * steps)
            global_metrics.inc("engine_decode_slot_steps_total",
                               slots * steps)
            self._note_moe("decode", (slots + 1) * 2 * self._block, rec)
            # every pass's `block` queries of the CURRENT block see the
            # prefix below the block and the block itself (the host's
            # positions: the device's carry may lead them by the bursts in
            # flight); a pending block's queries ride the same read of the
            # prefix and add nothing that has to be read
            n = self._block
            base = self._positions[:slots][active[:slots]].astype(
                np.int64) // n * n
            full = int((base + n).sum()) * n * steps * self.mcfg.n_layers
            global_metrics.inc("engine_kv_rows_full_total", full)
            if rec is not None:
                rec.attrs.update(kv_rows_full=full, kv_rows_window=0)
        self._ov_mask[:] = False  # patch consumed by this dispatch
        assign = self._burst_assign()
        if not np.any(np.where(active, self._logprobs, 0)):
            lp_out = None
        outs = (toks, decs, bases, pends, lp_out)
        self._start_host_copy(outs)
        return outs, assign

    async def _process_block_burst(self, outs, assign: List) -> None:
        """Account one fetched burst: per pass and row up to ``k`` tokens
        in position order, and whether the pass wrote the block before
        (``pending`` as the pass found it).  A forced offset (a prompt
        token) is no generated token: under ``echo`` its log-probability is
        kept for the first event; a token past the request's end
        (``max_tokens`` inside a group) is dropped with the freed row."""
        toks, decs, bases, pends, lp_out = outs
        k = group_size(self.mcfg)
        passes = fused = delivered = 0
        for col in range(toks.shape[1]):
            for i in np.nonzero(self._active_mask)[0]:
                run = (self.scheduler.slots[i]
                       if i < self.ecfg.num_slots else None)
                if run is None:  # cancelled/evicted since dispatch
                    self._active_mask[i] = False
                    continue
                if run.request.request_id != assign[i]:
                    continue  # re-admitted: the next burst's
                passes += 1
                fused += int(pends[i, col])
                first = int(bases[i, col]) + int(decs[i, col])
                plen = len(run.request.prompt_ids)
                for j, pos in enumerate(range(first, first + k)):
                    if self.scheduler.slots[i] is not run:
                        break  # finished inside the group
                    if pos < plen:
                        echo = self._blk_echo.get(int(i))
                        if echo is not None and lp_out is not None:
                            echo[pos] = float(lp_out[0][i, col, j])
                        continue
                    if pos != run.cache_len:
                        continue
                    lp_row = None
                    if lp_out is not None:
                        lp_row = tuple(a[i, col, j] for a in lp_out)
                    prompt_lps = None
                    if pos == plen:
                        prompt_lps = self._blk_echo.pop(int(i), None)
                    self._account_token(int(i), int(toks[i, col, j]),
                                        lp_row, prompt_lps)
                    delivered += 1
            # this pass's tokens flush to consumers before the next's
            await asyncio.sleep(0)
        # every pass decides a group, so none is a commit alone: that
        # counter and the records' key stay, at 0, for what reads them
        global_metrics.inc("engine_block_row_passes_total", passes)
        global_metrics.inc("engine_block_commit_row_passes_total", 0)
        global_metrics.inc("engine_block_fused_commits_total", fused)
        global_metrics.inc("engine_block_tokens_decided_total", delivered)
        self._blk_burst_attrs = {
            "row_passes_denoise": passes, "row_passes_commit": 0,
            "row_commits_fused": fused, "tokens_decided": delivered}
