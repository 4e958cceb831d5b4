"""What every family's tiny-cell test shares (tests/test_*_cell.py): the cell's
requests asked of an engine in this process.
"""

from __future__ import annotations

import asyncio


def _ask_in_process(eng, seqs):
    """``benchmarks.correctness.ask_engine`` without the tunnel: the same
    requests through ``engine.generate``, the sequences filled alike."""
    from benchmarks import correctness

    async def one(prompt, new, echo):
        events = [ev async for ev in eng.generate(
            prompt, max_new_tokens=new, logprobs=1, echo_logprobs=echo,
            stop_ids=())]
        return ([ev.token_id for ev in events], [ev.logprob for ev in events],
                events[0].prompt_logprobs)

    async def main():
        await eng.start()
        try:
            jobs = []
            for i, seq in enumerate(seqs):
                seq.update(tokens=list(seq["prompt"]), probes=[], system=[],
                           parts=[])
                if seq["group"] == "ladder":
                    jobs += [(i, n, 1, False) for n in correctness._rungs(seq)]
                else:
                    jobs.append((i, len(seq["prompt"]), correctness.NEW_TOKENS,
                                 seq["group"] == "echo"))
            gate = asyncio.Semaphore(correctness.ASK_AT_ONCE)

            async def gated(job):
                async with gate:
                    return await one(seqs[job[0]]["prompt"][:job[1]], job[2],
                                     job[3])

            return jobs, await asyncio.gather(*(gated(j) for j in jobs))
        finally:
            await eng.stop()

    jobs, answers = asyncio.run(asyncio.wait_for(main(), 900))
    for (i, n, asked, echo), (tokens, values, plps) in zip(jobs, answers):
        seq = seqs[i]
        assert len(tokens) == asked
        if echo:
            for t in range(1, n):
                seq["probes"].append((t - 1, seq["prompt"][t]))
                seq["system"].append(plps[t])
                seq["parts"].append("echo_prompt")
        if seq["group"] == "ladder":
            seq["probes"].append((n - 1, tokens[0]))
            seq["system"].append(values[0])
            seq["parts"].append("traffic_prefill")
            continue
        seq["tokens"] = seq["prompt"] + tokens
        for j, (tok, value) in enumerate(zip(tokens, values)):
            seq["probes"].append((n - 1 + j, tok))
            seq["system"].append(value)
            seq["parts"].append(seq["group"] + "_decode")
