"""``tiny-sdar-moe`` through the engine (the programs alone are
tests/test_block_diffusion.py), against tests/block_diffusion_plain.py: the
engine's block carry for every prompt remainder, ``max_tokens`` that ends
inside a group, a chunk boundary, a prefix-pool hit, a finished stream saved,
``echo``, and rows out of phase with each other.  The API's stream, the
counters and the refusals: tests/test_block_diffusion_api.py.
"""

from __future__ import annotations

import asyncio

from tests.block_diffusion_tiny import (
    BLOCK,
    _check_against_reference,
    _engine,
    _generate,
    _prompt,
)


def test_every_remainder_and_an_end_inside_a_group():
    """(a) Prompts of every ``n mod 4``, through chunk prefill of their
    whole blocks (one and two segments of 16) and the decode passes, with
    ``max_tokens`` odd and even so that a request ends inside a group and
    inside a block; a prompt shorter than a block is not prefilled at all.
    (e) The stream is in position order and as long as asked."""
    eng = _engine()
    jobs = [(_prompt(20 + n, n), new, False)
            for n, new in ((20, 10), (21, 9), (22, 7), (23, 6), (40, 5),
                           (3, 6), (33, 1))]
    for (prompt, new, _), events in zip(jobs, _generate(eng, jobs)):
        assert len(events) == new and events[-1].finish_reason == "length"
        assert all(ev.finish_reason is None for ev in events[:-1])
        _check_against_reference(eng, prompt, events)


def test_a_prefix_pool_hit_ends_on_a_block_boundary():
    """(a) A prompt that shares its first 32 tokens with an earlier one
    restores them from the pool (pool blocks are 16 tokens = 4 blocks, so a
    hit ends where a block ends) and reads like the reference from there."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = _engine()
    assert eng._prefix_block % BLOCK == 0
    base = _prompt(9, 50)
    jobs = [(base, 6, False), (base[:32] + _prompt(10, 13), 7, False),
            (base[:49], 5, False)]
    hit0 = global_metrics.counter("engine_prefix_hit_tokens_total")
    outs = _generate(eng, jobs)
    assert global_metrics.counter("engine_prefix_hit_tokens_total") - hit0 \
        == 32 + 48
    for (prompt, _new, _), events in zip(jobs, outs):
        _check_against_reference(eng, prompt, events)


def test_a_finished_stream_is_saved_as_far_as_it_is_committed():
    """The conversation cache saves a finished stream's whole pool blocks
    below the block of its last token (a pass on a block follows the commit
    of the one before it; the last block may not be committed): a next turn
    that resends the conversation hits them and reads like the reference."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = _engine(conv_cache=True)
    first = _prompt(30, 21)

    async def main():
        await eng.start()
        try:
            said = [ev async for ev in eng.generate(
                first, max_new_tokens=30, logprobs=1, stop_ids=())]
            await asyncio.sleep(0.2)
            hit0 = global_metrics.counter("engine_prefix_hit_tokens_total")
            turn = first + [ev.token_id for ev in said] + _prompt(31, 6)
            events = [ev async for ev in eng.generate(
                turn, max_new_tokens=7, logprobs=3, stop_ids=())]
            return turn, events, global_metrics.counter(
                "engine_prefix_hit_tokens_total") - hit0
        finally:
            await eng.stop()

    turn, events, hit = asyncio.run(asyncio.wait_for(main(), 300))
    # 51 tokens held, the last at position 50 in block 12: 48 committed
    assert hit == 48
    _check_against_reference(eng, turn, events)


def test_echo_runs_the_prompt_through_the_decode_passes():
    """(c) ``echo``: the prompt's tokens are forced outcomes of the same
    passes, scored by the same definition, for every remainder; generation
    goes on from the cache those passes committed."""
    eng = _engine()
    jobs = [(_prompt(40 + n, n), 5, True) for n in (12, 13, 14, 15, 2)]
    for (prompt, _new, _), events in zip(jobs, _generate(eng, jobs)):
        assert len(events) == 5
        _check_against_reference(eng, prompt, events)


def test_rows_out_of_phase_get_what_they_get_alone():
    """(d) Rows admitted at different passes of each other's blocks, with
    different remainders, echoed and not: one dispatch mixes rows with a
    block that awaits its commit and rows without, first and second passes,
    and every row reads like the reference."""
    jobs = [(_prompt(60, 21), 12, False), (_prompt(61, 34), 9, False),
            (_prompt(62, 11), 8, True), (_prompt(63, 19), 11, False)]
    eng = _engine(decode_steps=3)
    for (prompt, new, _), events in zip(jobs, _generate(eng, jobs, True)):
        assert len(events) == new
        _check_against_reference(eng, prompt, events)
