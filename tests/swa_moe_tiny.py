"""What the files of ``tiny-swa-moe``'s tests share (tests/test_swa_moe.py, the
programs; tests/test_swa_moe_engine.py; tests/test_swa_moe_cell.py): the
sizes, the tolerance and the helpers that more than one of them calls.
"""

from __future__ import annotations

import asyncio

import numpy as np

from tests.moe_records import dispatches_closed


ROWS, MAX_SEQ, RING, WINDOW = 4, 96, 16, 8
# float32 program against the float32 reference at `highest`: sums taken in
# another order (a grouped product over sorted rows, a softmax over ring
# slots in another order than positions) differ in the last places of a
# float32.
ATOL = 2e-4


def _prompt(seed, n):
    """Token ids under 250: the engine's default tokenizer has 259."""
    return list(np.random.RandomState(seed).randint(1, 250, size=n))


def _generate(eng, prompts, new=10):
    async def main():
        await eng.start()
        try:
            out = []
            for prompt in prompts:
                events = [ev async for ev in eng.generate(
                    prompt, max_new_tokens=new, logprobs=1, stop_ids=())]
                out.append(([ev.token_id for ev in events],
                            [ev.logprob for ev in events]))
            await dispatches_closed(eng)
            return out
        finally:
            await eng.stop()

    return asyncio.run(asyncio.wait_for(main(), 300))
