"""The inference engine: jitted prefill/decode over slot-batched KV cache.

Continuous batching, TPU-style (SURVEY.md §7 hard-part #1): the KV cache has
``num_slots`` fixed rows; every decode step runs ONE fixed-shape XLA program
over all slots (inactive rows compute but are masked at sampling), so
admission/eviction never recompiles.  Prompts prefill into padded power-of-2
buckets to bound the number of compiled prefill programs.

Async contract: ``generate()`` yields TokenEvents as decode steps finish;
requests admit/evict between steps; blocking XLA calls run in an executor
thread so the tunnel's event loop never stalls.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import contextlib
import functools
import importlib
import os
import threading
import time
from dataclasses import dataclass, replace as dc_replace
from collections import Counter, deque
from typing import AsyncIterator, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from p2p_llm_tunnel_tpu.engine import sampling
from p2p_llm_tunnel_tpu.engine.block_engine import BlockDecodeMixin
from p2p_llm_tunnel_tpu.engine.scheduler import (
    GenRequest,
    MuxController,
    RunningSlot,
    Scheduler,
    TenantOverLimit,
    parse_tenant_weights,
)
from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer, StreamDecoder, Tokenizer
from p2p_llm_tunnel_tpu.models.config import ModelConfig, get_config
from p2p_llm_tunnel_tpu.models.moe import RAGGED as MOE_RAGGED
from p2p_llm_tunnel_tpu.models.moe import grouped_product_branch
from p2p_llm_tunnel_tpu.models.transformer import (
    decode_attention_branch,
    decode_branch_coverage,
    decode_step,
    init_kv_cache,
    init_params,
    prefill_attention_branch,
    prefill_into_cache,
    reads_expert_stack,
)
from p2p_llm_tunnel_tpu.ops.pallas_ssm_step import ELEMENTWISE
from p2p_llm_tunnel_tpu.utils.flight import (
    IterationSplit,
    global_blackbox,
    global_compile_watch,
    global_flight,
    global_gc,
)
from p2p_llm_tunnel_tpu.utils.logging import get_logger
from p2p_llm_tunnel_tpu.utils.metrics import (
    derived_retry_after_s,
    global_metrics,
)
from p2p_llm_tunnel_tpu.utils.slo import global_slo
from p2p_llm_tunnel_tpu.utils.tracing import (
    TraceContext,
    global_tracer,
    new_span_id,
)

log = get_logger(__name__)

#: Queue sentinel distinguishing "engine crashed" from the clean
#: end-of-stream None — consumers raise instead of returning a silently
#: truncated 200.
_CRASHED = object()

#: Queue sentinel for a deadline eviction: the scheduler already reclaimed
#: the slot/queue entry; generate() raises DeadlineExceeded so the response
#: layer can emit a typed timeout instead of a silently truncated stream.
_TIMED_OUT = object()

#: Queue sentinel for a tenant-fair displacement: the scheduler evicted
#: this queued request in an under-share tenant's favor; generate() raises
#: TenantOverLimit so the response layer emits the typed
#: ``tenant_overlimit`` error instead of a silently truncated stream.
_SHED = object()


#: Spill-tier I/O batch cap (ISSUE 16): page-outs per end-of-iteration
#: drain and page-ins per pre-admission splice.  Bounds how much tier
#: traffic one iteration can add to the executor queue — the drains run
#: every iteration, so throughput is paced, not capped.
_SPILL_BATCH = 8


def _program_key(kind: str, shape: Tuple[int, ...]) -> str:
    """Canonical compiled-program key: ``kind[dim,dim,...]`` — the ONE
    spelling shared by the AOT phase, the serial warmup pass, and the
    mid-serve cold-compile check, so readiness bookkeeping cannot split."""
    return f"{kind}[{','.join(str(s) for s in shape)}]"


def _tree_bytes(tree) -> int:
    """Bytes of a pytree's arrays, from shapes and types (no fetch)."""
    return sum(int(x.size) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def chunk_row_ladder(prefill_rows: int) -> Tuple[int, ...]:
    """Row counts a chunk-prefill dispatch can be padded to: the powers
    of two below ``prefill_rows``, then ``prefill_rows`` itself (8 -> 1, 2,
    4, 8; 3 -> 1, 2, 3).  A dispatch takes the smallest rung its width and
    view keep warm (InferenceEngine._chunk_rungs) that holds the rows it
    carries, so one real row is no longer computed as eight."""
    rungs = []
    r = 1
    while r < prefill_rows:
        rungs.append(r)
        r *= 2
    return (*rungs, max(1, prefill_rows))


def device_section(engines) -> Dict[str, object]:
    """The /healthz ``device`` section: what JAX runs on in THIS process
    (the one that holds the chip), what each local device holds, and the
    device ids each of ``engines`` is resident on.  A parent that must
    stay off JAX — chip_smoke.py, a load generator — learns the device
    from here.  ``memory_stats()`` is None on backends that keep no
    allocator statistics (the CPU)."""
    devices = jax.local_devices()
    per_device = []
    for d in devices:
        stats = d.memory_stats() or {}
        per_device.append({
            "id": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        "devices": per_device,
        "engines": [e.resident_devices() for e in engines],
    }


class DeadlineExceeded(Exception):
    """The request's x-tunnel-deadline-ms budget ran out before completion."""

    #: Typed tunnel-error code (protocol.frames.TunnelMessage.typed_error).
    tunnel_code = "timeout"


@dataclass
class EngineConfig:
    model: str = "tiny"
    num_slots: int = 8
    max_seq: int = 256
    dtype: str = "bfloat16"
    seed: int = 0
    min_prefill_bucket: int = 16  # tunnelcheck: disable=TC08  bucket geometry pins the compiled-program set AND the prefix-cache block size (snapshot compat); changing it per-deploy would orphan every banked program/snapshot — programmatic only
    # Decode steps per XLA call (lax.scan with on-device sampling feedback).
    # Every dispatch and every device_get costs a fixed host↔device round
    # trip, so each fetch returns num_slots*decode_steps tokens, not
    # num_slots.  Streaming granularity (SSE burst size) equals
    # decode_steps.  (The value was tuned to a far slower round trip than
    # a directly attached chip has — ROADMAP Speed 5.)
    decode_steps: int = 8
    # Burst size used instead of decode_steps while requests are WAITING
    # (queued behind full slots or arriving mid-burst): a small burst bounds
    # how long an admission can be stuck behind in-flight decode — the TTFT
    # lever (VERDICT r3 item 2).  0 disables adaptation.
    decode_steps_eager: int = 4
    # At most this many rows per batched-prefill call: admissions are
    # chunked to it (pad rows scatter into the scratch slot).  A whole-prompt
    # prefill pads to exactly this many rows, one program per prompt-length
    # bucket; a chunk-prefill dispatch pads to the smallest rung it keeps
    # warm that holds its rows (InferenceEngine._chunk_rungs).
    prefill_rows: int = 8
    # Tensor-parallel degree: shards params/KV-heads over a tp-axis Mesh
    # (parallel/sharding.py); 1 = single chip.  GSPMD inserts the ICI
    # collectives — the decode all-gather path of BASELINE config 4.
    tp: int = 1
    # Sequence-parallel degree for prefill: shards the prompt axis over an
    # sp mesh axis — the long-context path (SURVEY §5).  Decode is
    # unaffected (single-token).
    sp: int = 1
    # SP strategy: "ring" (ppermute KV rotation) | "ulysses" (all_to_all
    # head/sequence swap; supports sliding windows) — models/config.py.
    sp_mode: str = "ring"
    # Expert-parallel degree (MoE models): shards expert weights over an
    # ep mesh axis (models/moe.py); 1 = experts replicated.
    ep: int = 1
    # Optional orbax checkpoint to load instead of random init.
    ckpt_path: Optional[str] = None
    # Weight quantization: "none" | "int8" (weight-only, per-channel) |
    # "w8a8" (also quantize activations dynamically; int8 MXU dots) |
    # "int4" (weight-only, two values packed per byte along the contracted
    # axis, per-group scales — halves the weight stream AGAIN vs int8:
    # ~8.05 -> ~4.2 GB/step for 8B, the dominant decode HBM term).
    # Halves decode HBM traffic and fits 8B-class models on a 16 GB chip.
    quant: str = "none"
    # int4 group size: contracted positions sharing one scale per output
    # channel.  Smaller = more accurate, more scale traffic; 128 matches
    # the GPTQ/AWQ convention and keeps scale overhead at 1/32 of packed q.
    quant_group_size: int = 128
    # KV-cache quantization: "none" | "int8" (per-token-per-head scales) |
    # "int4" (two adjacent tokens packed per byte along the sequence axis,
    # per-token-per-head scales — quarters the KV stream).  Halves (or
    # quarters) the KV read term that dominates long-context decode HBM
    # traffic; dequant fuses into the einsum operand read or runs in VMEM
    # inside the ragged prefill kernel.  Since ISSUE 14 the prefix cache and
    # chunked prefill COMPOSE with int4: every pool page and chunk start
    # is forced to an even (two-tokens-per-byte) boundary, so packed
    # writes cover whole bytes.  Since ISSUE 17 spec_ngram composes too —
    # verify bursts splice covering bytes (quant.splice_packed_rows), so
    # the ``config_fences`` registry is EMPTY.
    kv_quant: str = "none"
    # Ragged grouped flash-prefill kernel (ISSUE 15).  Every chunk-prefill
    # dispatch — mux segment sub-batches AND prefix-cache tails — packs the
    # group's variable-length tail segments into ONE flat-token Pallas launch
    # (ops/pallas_prefill_attention.py): per-block (slot, start, len)
    # descriptors ride scalar prefetch, rope + KV quantization run in
    # VMEM, the cache append is an aliased in-place write, and the
    # attention reads the cache frontier-clamped — so there is no static
    # kv_view argument and no per-(tail, view) program family.  The
    # chunk×view×rows warmup/AOT grid collapses to ONE ragged program
    # (see warmup_plan); token streams stay byte-identical to the chunked
    # path at every kv_quant (tests/test_ragged_prefill.py).  Off by
    # default until chip-measured; CPU hosts run it in interpret mode.
    ragged_prefill: bool = False
    # With quant="int8": ALSO run activations int8 during PREFILL only.
    # Prefill is MXU-compute-bound (hundreds of tokens per row) where int8
    # doubles throughput; decode stays weight-only (it is HBM-bound, w8a8
    # measured at parity there — PERF.md) for best accuracy per token.
    prefill_act_quant: bool = False
    # Automatic prefix caching (engine/prefix_cache.py): prompt KV is saved
    # in blocks of ``min_prefill_bucket`` tokens keyed by content; a new
    # request's longest cached prefix is copied into its slot and only the
    # tail is prefilled (chunk_prefill_into_cache) — the TTFT lever for
    # shared-system-prompt and resent-conversation workloads.
    prefix_cache: bool = False
    # Pool capacity in blocks (block 0 is scratch).  Sized so HBM cost is
    # modest: 128 blocks x 16 tokens of 8B bf16 KV ~= 0.27 GB.
    prefix_pool_blocks: int = 128
    # Directory for prefix-pool snapshots: warm prompt KV (shared system
    # prompts, live conversations) survives serve restarts — loaded at
    # startup when compatible, saved at stop().  None disables (the pool
    # stays memory-only, the pre-r5 behavior).
    prefix_cache_dir: Optional[str] = None
    # How many tail buckets the chunk-prefill path supports: buckets
    # min_prefill_bucket * 2^i for i < prefix_tail_buckets.  Requests whose
    # post-match tail exceeds the largest bucket take the plain full-prefill
    # path instead — each bucket is one compiled program (warmed up front,
    # never on the serving path), and prefix reuse pays most when tails are
    # short anyway.
    prefix_tail_buckets: int = 2  # tunnelcheck: disable=TC08  compiled-program-count knob (one chunk program per tail bucket x view); a CLI surface would invite warmup-bill surprises — programmatic only
    # Prompt-lookup speculative decoding (vLLM's ngram speculator): when
    # > 0, each decode dispatch proposes spec_k continuation tokens by
    # matching the last spec_ngram generated/prompt tokens against the
    # request's own history, verifies them in ONE forward over k+1
    # positions, and emits the longest greedy-matching prefix + 1.
    # Exact-greedy acceptance means output is token-identical to plain
    # decode; repetitive text (code, RAG quotes, resent chat) emits up to
    # spec_k+1 tokens per step.  Stochastic/penalty/logprobs rows fall
    # back to plain behavior automatically.  Off by default (opt-in).
    spec_ngram: int = 0
    spec_k: int = 4
    # Adaptive verify width (ISSUE 17): when > spec_k, the per-iteration
    # burst width K is chosen per dispatch from a windowed per-slot
    # acceptance EMA — slots that keep accepting grow toward spec_k_max,
    # slots that keep rejecting shrink toward 1, bounding wasted verify
    # FLOPs.  K is bucketed to powers of two (plus spec_k_max itself) so
    # warmup_plan() pre-compiles every reachable program.  0 disables
    # (fixed K = spec_k, the pre-ISSUE-17 behavior).
    spec_k_max: int = 0
    # Chunked prefill (vLLM-style prefill/decode interleaving): prompts
    # whose (post-prefix-match) tail exceeds this many tokens advance one
    # fixed-size segment per engine-loop iteration instead of prefilling in
    # a single call — a 2k-token prompt no longer stalls every running
    # decode stream for its whole prefill.  One extra compiled program
    # (the segment width); the LAST segment's logits sample the first
    # token.  0 disables (prompts prefill whole, the pre-r4 behavior).
    prefill_chunk: int = 0
    # Admission control: max requests buffered in the scheduler's waiting
    # queue.  Overflow raises scheduler.QueueFull, which the API maps to
    # HTTP 429 + Retry-After — shedding beats buffering work that cannot
    # finish (goodput, PAPERS.md DistServe/AlignedServe).  0 = unbounded.
    max_waiting: int = 0
    # Decode-stall watchdog: if requests are active but no token is
    # accounted for this many seconds, log an error and mark the engine
    # degraded (surfaced by serve's /healthz).  Detection only — a stalled
    # XLA dispatch cannot be safely interrupted.  0 disables.
    watchdog_budget_s: float = 0.0
    # Iteration-level prefill/decode multiplexing (ISSUE 5; DistServe's
    # goodput argument): each engine-loop iteration dispatches ONE decode
    # burst plus up to a token BUDGET of chunked-prefill segment rows,
    # with the budget adapted by scheduler.MuxController from queue depth,
    # deadline slack, and a decode-stall bound — a full prefill no longer
    # occupies the device for a whole bucket while decode stalls.  Makes
    # prefill_chunk the production path: when it is 0 (and legal), a
    # default segment width is chosen at startup.  With the prefix cache
    # on, admission becomes prefix-GROUPED (AlignedServe): queued requests
    # sharing PrefixIndex block keys prefill the shared prefix ONCE (the
    # FIFO-first member computes it; later members park and fan out from
    # the pool), and tail segments batch through one chunk program per
    # iteration.  Token streams are byte-identical to the non-multiplexed
    # path (tests/test_mux.py).  Off by default HERE (programmatic users
    # keep the legacy rhythm); the serve CLI and bench default it ON.
    mux: bool = False
    # Fixed per-iteration prefill token budget under mux; 0 = adaptive
    # (the MuxController).  The A/B lever for interference experiments.
    mux_budget_tokens: int = 0
    # Tenant-fair admission (ISSUE 7): weighted-fair ordering across
    # tenants (stride scheduling, FIFO within a tenant) plus per-tenant
    # waiting-queue share caps under max_waiting — one hot API key is shed
    # (429 tenant_overlimit) before it can starve the herd.  ON by
    # default: with zero or one tenant present it degenerates exactly to
    # the historical FIFO, so untenanted deployments pay nothing.
    fair_admission: bool = True
    # Fairness weight spec "name=weight,name=weight" (unlisted tenants
    # weigh 1.0): a premium tenant at weight 4 gets 4x the contended queue
    # share and 4x the admission stride of a default tenant.
    tenant_weights: str = ""
    # Cross-request conversation cache (ISSUE 14): when a stream finishes
    # naturally (stop/length), its full-page KV — prompt AND generated
    # tokens — is saved back into the prefix pool keyed by the PrefixIndex
    # chain, so a returning user's turn-N request matches through turn
    # N-1's whole conversation and re-prefills only the new tail.  Needs
    # prefix_cache.  Numerics note (the int8-history nuance's sibling):
    # reused pages hold decode-computed KV, which is not bit-equal to a
    # fresh prefill of the same tokens, so conversation reuse trades exact
    # replay-identity for skipping the whole-history recompute — OFF here
    # by default (programmatic identity tests keep the pre-ISSUE-14
    # behavior); the serve CLI and bench default it ON.
    conv_cache: bool = False
    # Pool page eviction policy: "cost" (default) weighs pages by their
    # recompute cost — the page's full-prefix token count times the live
    # per-token prefill-ms estimate, GreedyDual-style, so a deep
    # conversation's pages outlive a cheap one-shot prompt's under
    # pressure — "lru" restores the plain least-recently-used order.
    prefix_evict: str = "cost"
    # Host-RAM KV spill tier capacity in pages (ISSUE 16); 0 disables.
    # Cold (lowest-GreedyDual-priority) pool pages are paged out
    # asynchronously and an evicted page with a host copy MIGRATES there
    # instead of dying; a returning conversation whose chain continues
    # into the tier is spliced back ahead of admission.  Host cost is
    # spill_pages x per-page KV bytes (kv_quant-scaled).  Needs
    # prefix_cache; fenced off under SPMD like snapshots (the pool leaves
    # are mesh-sharded and per-page host copies would gather the mesh).
    spill_pages: int = 0
    # Page-out trigger: the spill drain runs when the pool's free-block
    # count sinks below this mark, keeping a reserve of pre-paid shadows
    # so eviction under pressure migrates instead of destroys.
    spill_low_water: int = 4  # tunnelcheck: disable=TC08  derived drain-pacing knob (a fraction of prefix_pool_blocks in spirit); one more CLI surface would just invite mis-tuning the hysteresis — programmatic only
    # Disaggregated prefill/decode (ISSUE 20): "both" (classic — every
    # engine does everything), "prefill" (this peer computes prompt KV and
    # EXPORTS finished-prompt pages over the tunnel; it remains a full
    # engine so routing to it still works when the fleet degrades), or
    # "decode" (this peer IMPORTS a prefill peer's pages — spliced through
    # the same two-phase verify path as the spill tier — and enters decode
    # with only the tail to prefill; byte-identical streams).  Either
    # split role needs the prefix cache (pages ARE the transfer unit) and
    # is fenced back to "both" without it — disaggregation is a pure
    # optimization, never a new failure mode.
    role: str = "both"


@dataclass
class TokenEvent:
    token_id: int
    text: str
    finish_reason: Optional[str] = None  # "stop" | "length" on the last event
    # Set when the request asked for logprobs: log P(token) under the raw
    # model distribution, plus the top-N (id, logprob) alternatives.
    logprob: Optional[float] = None
    top_logprobs: Optional[List[Tuple[int, float]]] = None
    # Echo/scoring path (legacy completions): per-PROMPT-token logprobs,
    # attached once on the request's FIRST event (entry 0 has no context
    # and is reported as None by the API).
    prompt_logprobs: Optional[List[float]] = None


@dataclass
class _ActiveRequest:
    queue: "asyncio.Queue[Optional[TokenEvent]]"
    decoder: StreamDecoder
    t_submit: float
    first_token_at: Optional[float] = None
    # When the request won a decode slot — the TTFT decomposition anchor:
    # queue_wait = t_admitted - t_submit, prefill_exec = first_token_at -
    # t_admitted (the latter includes any prefix-dedup park time).
    t_admitted: Optional[float] = None
    # Tracing (ISSUE 6): the propagated context (parent = the serve-side
    # dispatch span), this request's own engine.request span id, the
    # prefix-group park start (waiter side), and the recorded outcome.
    # All None/unused when tracing is off or the trace is sampled out.
    trace: Optional[TraceContext] = None
    trace_span: Optional[str] = None
    t_parked: Optional[float] = None
    finish: Optional[str] = None
    # What engine.prefill_exec says of the request's prefill: prompt tokens
    # reused from the pool, prefill dispatches that carried its rows
    # (counted as their engine.prefill_part spans close), and the loop
    # iteration that admitted it.
    cached_tokens: int = 0
    parts: int = 0
    iter_admitted: int = 0


class _Dispatch:
    """One open dispatch record; built only while the span journal is on.

    ``span`` names the engine-scope span it closes as and the profiler
    annotation around the dispatch call; ``attrs`` carry what the dispatch
    did (``seq``, ``program``, real and padded work); ``parts`` is, for a
    prefill dispatch, each row's (request id, tokens, start, final)."""

    __slots__ = ("span", "t0", "attrs", "parts")

    def __init__(self, span: str, attrs: Dict[str, object], parts=None):
        self.span = span
        self.t0 = time.monotonic()
        self.attrs = attrs
        self.parts = parts

    def annotation(self):
        """The record on the device trace's host plane: it records nothing
        unless a profile is running, and then carries ``seq`` (the join
        key to the journal) and ``mono_us`` (this process's monotonic
        clock at the dispatch, from which a reader fits the offset between
        the journal's clock and the trace's)."""
        work = {k: self.attrs[k] for k in ("steps", "tokens")
                if k in self.attrs}
        return jax.profiler.TraceAnnotation(
            self.span, seq=self.attrs["seq"], mono_us=int(self.t0 * 1e6),
            **work,
        )


#: What a dispatch call is wrapped in while the span journal is off.
_NO_ANNOTATION = contextlib.nullcontext()


#: What the snapshots of recurrent state kept beside the prefix pool's pages
#: may take in all.  Their count follows the pool's tokens (one every
#: ``--prefill-chunk`` of them); that rule was written for a state of 12.8
#: MB a slot (96 snapshots beside 3,072 blocks: 1.23 GB, under this cap).
#: At 76 MB a slot a snapshot costs what 9,300 tokens of pages cost and the
#: rule alone hands the snapshots 19 x the pages' bytes at ANY pool size: a
#: pool that does not trip the thrash detector (2,048 blocks) would ask 4.9
#: GB of them.  With the cap the pages are sized by the traffic and the
#: snapshots by their bytes; pages beyond the newest snapshots' reach match
#: no further than the last boundary that has one.
#: **Provisional**: the one split both served state sizes fit in.  Nothing
#: the engine knows gives it: the pages' bytes would leave the smaller
#: state 7 snapshots of its 96, the device's free memory is not the
#: snapshots' alone (the programs' temporaries come after), and no cell
#: restores a snapshot yet, so none can judge a split.  The cell that shares
#: prefixes sets it, or replaces it with one stated budget for pages and
#: snapshots (ROADMAP Reach 9 h); no option until then (PR 44's review
#: removed ``--state-snapshots``).
STATE_SNAPSHOT_BYTES = 5 << 28


class _CountsFirst:
    """A jitted serving program of a model with routed layers: its FIRST
    output is what those layers counted (models/moe.py STATS), handed to
    ``take`` as the device array it is; callers get the rest, the tuple the
    program returns for any other model."""

    def __init__(self, jitted, take):
        self._jitted, self._take = jitted, take

    def __call__(self, *args):
        out = self._jitted(*args)
        self._take(out[0])
        return out[1:]

    def lower(self, *args):
        return self._jitted.lower(*args)


class InferenceEngine(BlockDecodeMixin):
    """Slot-batched continuous-decode engine over one model."""

    def __init__(
        self,
        model_cfg: Optional[ModelConfig] = None,
        engine_cfg: Optional[EngineConfig] = None,
        params=None,
        tokenizer: Optional[Tokenizer] = None,
        mesh=None,
        param_shardings=None,
    ):
        self.ecfg = engine_cfg or EngineConfig()
        self.tokenizer = tokenizer or ByteTokenizer()
        self.mcfg = model_cfg or get_config(
            self.ecfg.model, vocab_size=self.tokenizer.vocab_size
        )
        # Generation by blocks (engine/block_engine.py); 0: a token a step.
        self._block = self.mcfg.block_length
        if self._block and self.mcfg.mask_token_id >= self.mcfg.vocab_size:
            # a preset's mask id under a smaller tokenizer (tiny-*)
            self.mcfg = dc_replace(
                self.mcfg, mask_token_id=self.mcfg.vocab_size - 1)
        self._refuse_unsupported()
        if self.ecfg.sp_mode not in ("ring", "ulysses"):
            raise ValueError(f"unknown sp_mode {self.ecfg.sp_mode!r}")
        if self.ecfg.sp_mode != "ring" and self.mcfg.sp_mode != self.ecfg.sp_mode:
            # One-directional: a non-default EngineConfig choice
            # promotes into the model config, but an explicitly
            # ulysses model_cfg is never silently reverted to ring.
            self.mcfg = dc_replace(self.mcfg, sp_mode=self.ecfg.sp_mode)
        if jax.default_backend() == "tpu" and self.mcfg.flash_interpret:
            raise ValueError(
                "flash_interpret is the CPU test mode of the Pallas "
                "kernels; on the TPU backend they compile"
            )
        dtype = jnp.dtype(self.ecfg.dtype)
        key = jax.random.PRNGKey(self.ecfg.seed)
        if mesh is None and (
            self.ecfg.tp > 1 or self.ecfg.sp > 1 or self.ecfg.ep > 1
        ):
            from p2p_llm_tunnel_tpu.parallel import make_mesh

            mesh = make_mesh(
                tp=self.ecfg.tp, dp=1, sp=self.ecfg.sp, ep=self.ecfg.ep
            )
        self.mesh = mesh
        if self._decode_reads_rows():
            # Tracing decode's kernel imports Pallas: 1.3 s of Python that
            # the first decode program of the warm-up would wait for.
            # Import it beside the parameters' initialisation instead.
            threading.Thread(
                target=importlib.import_module,
                args=("p2p_llm_tunnel_tpu.ops.pallas_decode_attention",),
                name="import-pallas", daemon=True,
            ).start()
        placed = False  # params already carry their mesh shardings
        t_params = time.monotonic()
        params_source = "injected"
        if params is None:
            params_source = "checkpoint" if self.ecfg.ckpt_path else "random"
            if self.ecfg.ckpt_path:
                from p2p_llm_tunnel_tpu.models.checkpoint import load_checkpoint

                log.info("loading checkpoint from %s", self.ecfg.ckpt_path)
                like = jax.eval_shape(
                    lambda k: init_params(self.mcfg, k, dtype), key
                )
                params = load_checkpoint(self.ecfg.ckpt_path, like=like)
            else:
                params = self._random_params(key, dtype)
                placed = mesh is not None
        if self.ecfg.quant in ("int8", "w8a8"):
            from p2p_llm_tunnel_tpu.models.quant import QTensor, quantize_params

            if not isinstance(params["blocks"]["wq"], QTensor):
                # Loaded/injected bf16 weights: quantize once at startup.
                log.info("quantizing weights to int8 (per-channel, weight-only)")
                params = quantize_params(params)
            if self.ecfg.quant == "w8a8" and not self.mcfg.act_quant:
                # int8 weights AND dynamic int8 activations: QTensor matmuls
                # become native int8 MXU dots (models/quant.py _int8_dot).
                self.mcfg = dc_replace(self.mcfg, act_quant=True)
        elif self.ecfg.quant == "int4":
            from p2p_llm_tunnel_tpu.models.quant import (
                QTensor4, quantize_params_int4,
            )

            if not isinstance(params["blocks"]["wq"], QTensor4):
                log.info(
                    "quantizing weights to packed int4 (group_size=%d)",
                    self.ecfg.quant_group_size,
                )
                params = quantize_params_int4(
                    params, self.ecfg.quant_group_size
                )
            elif params["blocks"]["wq"].group_size != self.ecfg.quant_group_size:
                # Pre-quantized injected tree wins: the config must reflect
                # the weights actually served, or _prefix_snapshot_meta pins
                # a group_size the KV bytes were never computed with.
                actual = params["blocks"]["wq"].group_size
                log.warning(
                    "injected int4 tree uses group_size=%d; overriding "
                    "configured quant_group_size=%d",
                    actual, self.ecfg.quant_group_size,
                )
                self.ecfg = dc_replace(self.ecfg, quant_group_size=actual)
        elif self.ecfg.quant == "a8":
            # Int8 activations over the weights as they are: the
            # benchmark's activations control for a family served
            # unquantised (models/quant.py round_act).
            if not self.mcfg.act_quant:
                self.mcfg = dc_replace(self.mcfg, act_quant=True)
        elif self.ecfg.quant not in ("none", ""):
            raise ValueError(f"unknown quant mode {self.ecfg.quant!r}")
        # Cross-host SPMD serving (PARITY A8): in a multi-process run rank 0
        # broadcasts every dispatch's host inputs and ranks != 0 replay them
        # (spmd_follower_loop).  None in single-process runs — zero overhead.
        from p2p_llm_tunnel_tpu.parallel.spmd_serve import SpmdCoordinator

        self._spmd = SpmdCoordinator.maybe(mesh)
        self._spmd_stop_sent = False
        self._crashed = False
        self._warming = False  # warmup dispatches skip D2H copy enqueue
        if mesh is not None:
            from p2p_llm_tunnel_tpu.parallel.sharding import (
                param_shardings as _pshard,
                shard_params,
            )

            if not placed:
                # Loaded or injected trees live on the host or one device.
                log.info("sharding params over mesh %s", dict(mesh.shape))
                params = shard_params(params, self.mcfg, mesh)
            param_shardings = _pshard(self.mcfg, mesh, params)
        self.params = params
        self.param_shardings = param_shardings
        # Start-up journal (ISSUE 40).  The host's view: the device may
        # still be filling the arrays, and whoever reads them first waits.
        global_compile_watch.add_span(
            "startup.params", t0=t_params, source=params_source,
            quant=self.ecfg.quant or "none", bytes=_tree_bytes(params),
        )
        t_cache = time.monotonic()

        b, s = self.ecfg.num_slots, self.ecfg.max_seq
        # One extra cache row: the scratch slot that padded prefill rows
        # scatter into, so batched prefill never corrupts a live slot.
        rows = b + 1
        self._scratch_slot = b
        if self.ecfg.kv_quant not in ("none", "", "int8", "int4"):
            raise ValueError(f"unknown kv_quant mode {self.ecfg.kv_quant!r}")
        # Routed layers count their assignments on the device; the counts
        # ride each serving program's outputs (_CountsFirst) and reach the
        # host with the tokens: (dispatch record or None, device array).
        self._moe_pending: Deque[Tuple[Optional[_Dispatch], object]] = deque()
        if self.ecfg.prefix_evict not in ("cost", "lru"):
            raise ValueError(
                f"unknown prefix_evict mode {self.ecfg.prefix_evict!r}"
            )
        # Composition-fence registry (ISSUE 14): every knob the engine
        # auto-disables at startup lands here WITH its reason, surfaced as
        # the /healthz "config" section (and the proxy's federated view),
        # so an operator can verify the hero configuration runs unfenced
        # instead of grepping startup logs for warnings.
        self.config_fences: List[Dict[str, str]] = []
        # Conversation-cache scratch (ISSUE 14): finished slots whose KV
        # awaits a batched pool insert this iteration (drained before the
        # next admission can re-prefill the slot), per-rid page-reservation
        # grants, the per-token prefill-ms EMA feeding cost-aware eviction,
        # and last-published index counters (the delta-inc bookkeeping
        # behind the engine_prefix_evictions_total / engine_conv_* series).
        self._conv_pending: List[Tuple[int, List[int]]] = []
        self._page_reserved: Dict[int, int] = {}
        self._prefill_ms_per_token = 0.0
        self._prefix_published: Dict[str, int] = {}
        # Memory-degradation state (ISSUE 16), initialised BEFORE the
        # prefix block below publishes its first gauges: why
        # engine_degraded is set ("stall" | "memory" | "" — the
        # watchdog's progress-clear only touches its own reason), the
        # thrash detector's sliding window of (evict, realloc) deltas,
        # and the in-flight tier-I/O ledger the leak gate reads.
        self.degraded = False
        self.degraded_reason = ""
        self._thrash_window: Deque[Tuple[int, int]] = deque(maxlen=64)
        self._thrash_last: Tuple[int, int] = (0, 0)
        self._spill_inflight = 0
        # Block-paged alignment (ISSUE 14): chunk-prefill writes are
        # legal on the packed sequence axis exactly when every write
        # start and padded width is even (whole bytes — two tokens per
        # byte).  Pool pages (min_prefill_bucket) and chunk segments
        # (prefill_chunk) are forced to even sizes below, which makes
        # every chunk start a page/segment multiple and hence even.
        # Spec-verify — the one consumer with arbitrary-parity starts —
        # splices covering bytes instead (ISSUE 17), so the
        # config_fences registry carries NO kv_quant entry anymore.
        # (The page-alignment pass — chunk rounding + pool-page
        # evenness fences — runs AFTER the mux default below has
        # picked the effective prefill_chunk, so a defaulted odd
        # width cannot dodge it.)
        # A model with window layers as rings (models/swa.py) gets its ring
        # sized here, by the widest chunk-prefill segment this engine will
        # dispatch: the effective chunk is settled further down, by this
        # rule.  0 for every other model.
        self._ring = 0
        if self.mcfg.attn_pattern is not None:
            chunk = self.ecfg.prefill_chunk
            if chunk <= 0 and self.ecfg.mux and self.ecfg.sp <= 1:
                chunk = self._mux_default_chunk()
            self._ring = self.mcfg.ring_default(s, max(chunk, 0))
            self.mcfg = dc_replace(self.mcfg, ring_positions=self._ring)
        self._attn_kinds = tuple(
            k == "window" for k in self.mcfg.attn_kinds)
        # A family whose cache is rows a token AND a recurrent state a slot
        # (models/ssm_moe.py): the leaves that are state, and what a live
        # row's state takes in all its layers (read and written once a
        # decode step, once a prefill segment).
        self._state_keys: Tuple[str, ...] = ()
        self._state_row_bytes = 0
        self._snapshots = None
        # Decode's update of that state in this engine's decode programs,
        # by the model layer's own rule (``state_update_branch``); None for
        # a model without such a state.
        self._state_update: Optional[str] = None
        if self.mcfg.mixer_pattern is not None:
            from p2p_llm_tunnel_tpu.models.ssm_moe import (
                state_bytes_per_slot,
                state_keys,
                state_update_branch,
            )

            self._state_keys = state_keys(self.mcfg)
            self._state_row_bytes = state_bytes_per_slot(self.mcfg, dtype)
            self._state_update = state_update_branch(self.mcfg, self.mesh)

        def make_cache():
            return init_kv_cache(
                self.mcfg, rows, s, dtype, quant=self.ecfg.kv_quant
            )

        if self.mesh is None:
            self.kv_cache = make_cache()
        else:
            from p2p_llm_tunnel_tpu.parallel.sharding import (
                kv_cache_shardings,
            )

            # tp shards the kv-head axis; the slot axis stays whole (the
            # engine's dp axis is 1 — replica routing is a layer above).
            # Built under jit with out_shardings, like the weights: each
            # chip zero-fills its own shard, the whole cache never sits
            # on the default device.
            self.kv_cache = jax.jit(
                make_cache,
                out_shardings=kv_cache_shardings(
                    self.mesh, jax.eval_shape(make_cache)
                ),
            )()
        self.scheduler = Scheduler(
            b, s, max_waiting=self.ecfg.max_waiting,
            tenant_weights=parse_tenant_weights(self.ecfg.tenant_weights),
            fair=self.ecfg.fair_admission,
        )

        if self.ecfg.prefill_chunk > 0 and self.ecfg.sp > 1:
            # Same scope limit as the prefix cache below: the chunk-prefill
            # program has no sequence-parallel attention path, and silently
            # bypassing ring/Ulysses on long prompts would defeat sp's
            # memory scaling exactly where it matters.
            self._fence(
                "prefill_chunk", 0,
                "the chunk-prefill program has no sequence-parallel "
                "attention path (sp>1)",
            )

        # Multiplexing (ISSUE 5): chunked prefill is the production path,
        # so pick a default segment width when none was configured.  Since
        # ISSUE 14 the packed int4 KV cache takes page-aligned chunk
        # writes, so the segment interleave runs under every kv_quant;
        # only sp>1 prefill (no sequence-parallel chunk path) still falls
        # back to budgeted whole-prompt admission waves.
        if self.ecfg.mux and self.ecfg.prefill_chunk <= 0:
            if self.ecfg.sp <= 1:
                self.ecfg = dc_replace(
                    self.ecfg, prefill_chunk=self._mux_default_chunk())
        if self._block:
            # Every prompt's whole blocks go through chunk prefill (the one
            # prefill program of this family), in segments of whole blocks.
            chunk = self.ecfg.prefill_chunk or self._mux_default_chunk()
            chunk = -(-chunk // self._block) * self._block
            if chunk != self.ecfg.prefill_chunk:
                self.ecfg = dc_replace(self.ecfg, prefill_chunk=chunk)
        if self.ecfg.kv_quant == "int4":
            # Page-alignment pass (ISSUE 14), AFTER the mux default above
            # so the EFFECTIVE chunk width is what gets rounded: packed
            # int4 segment writes must cover whole bytes.
            from p2p_llm_tunnel_tpu.models.quant import (
                INT4_PACK_TOKENS,
                page_alignment_violations,
            )

            if self.ecfg.prefill_chunk % INT4_PACK_TOKENS:
                fixed = (self.ecfg.prefill_chunk + INT4_PACK_TOKENS
                         - self.ecfg.prefill_chunk % INT4_PACK_TOKENS)
                log.info(
                    "rounding prefill_chunk %d up to %d: packed int4 KV "
                    "segments must be page-aligned",
                    self.ecfg.prefill_chunk, fixed,
                )
                self.ecfg = dc_replace(self.ecfg, prefill_chunk=fixed)
            if self.ecfg.prefix_cache:
                for why in page_alignment_violations(
                    "int4", self.ecfg.min_prefill_bucket,
                    self.ecfg.prefill_chunk,
                ):
                    self._fence("prefix_cache", False, why)

        # Ragged grouped prefill (ISSUE 15): geometry + kernel-legality
        # gates, AFTER the mux default above so the effective
        # prefill_chunk feeds the block/bucket arithmetic.  The q-block
        # width must divide every chunk start (page multiples AND segment
        # multiples — the ISSUE 14 alignment family), so it is the
        # largest power-of-2 divisor of both units, capped at 128.
        self._ragged_bq = 0
        self._ragged_tot = 0
        self._ragged_row_blocks = 0
        self._ragged_interpret = False
        if self.ecfg.ragged_prefill:
            import math

            unit = self.ecfg.prefill_chunk or self.ecfg.min_prefill_bucket
            div = math.gcd(self.ecfg.min_prefill_bucket, unit)
            bq = next((c for c in (128, 64, 32, 16, 8) if div % c == 0), 0)
            self._ragged_interpret = jax.default_backend() != "tpu"
            if self.ecfg.sp > 1:
                self._fence(
                    "ragged_prefill", False,
                    "the ragged grouped prefill kernel has no "
                    "sequence-parallel attention path (sp>1)",
                )
            elif self.ecfg.tp > 1:
                self._fence(
                    "ragged_prefill", False,
                    "pallas_call is not GSPMD-partitioned: under a tp "
                    "mesh XLA would all-gather the sharded cache (wrap "
                    "in shard_map before enabling, like prefill's "
                    "flash_tp)",
                )
            elif bq == 0:
                self._fence(
                    "ragged_prefill", False,
                    f"no power-of-2 q-block width >= 8 divides both "
                    f"min_prefill_bucket={self.ecfg.min_prefill_bucket} "
                    f"and prefill_chunk={unit} — chunk starts would "
                    f"misalign the grouped cache-append blocks",
                )
            elif not self._ragged_interpret and self.mcfg.head_dim % 128:
                self._fence(
                    "ragged_prefill", False,
                    f"head_dim {self.mcfg.head_dim} does not tile "
                    "(% 128) on the TPU backend",
                )
            elif not self._ragged_interpret and s % 128:
                self._fence(
                    "ragged_prefill", False,
                    f"max_seq {s} does not tile (% 128) on the TPU "
                    "backend",
                )
            else:
                self._ragged_bq = bq
                # One flat-token bucket per dispatch: the widest group
                # the dispatch sites can assemble (prefill_rows rows of
                # the widest per-row tail — a mux segment or the largest
                # prefix tail bucket).  ONE compiled program replaces the
                # whole chunk[t, view] grid; idle iterations pay pad
                # FLOPs in the XLA projections only (the kernel skips
                # pad blocks), which the mux budget keeps filled in
                # steady state.
                per_row = unit
                if self.ecfg.prefix_cache:
                    per_row = max(
                        per_row,
                        self.ecfg.min_prefill_bucket
                        * 2 ** max(0, self.ecfg.prefix_tail_buckets - 1),
                    )
                per_row = -(-per_row // bq) * bq
                self._ragged_tot = self.ecfg.prefill_rows * per_row
                # The kernel's tail grid axis is row-relative: it spans
                # the widest per-row tail, not the whole flat bucket —
                # linear grid growth in group size (the quadratic form
                # made CPU-interpret execution unusable).
                self._ragged_row_blocks = per_row // bq

        # Prefix cache: host index + device block pool + jitted copy ops.
        self._prefix = None
        if self.ecfg.prefix_cache and self.ecfg.sp > 1:
            # chunk_prefill_into_cache has no sequence-parallel attention
            # path; silently bypassing ring/Ulysses on cache hits would
            # defeat sp's memory scaling on exactly the long prompts it
            # exists for.
            self._fence(
                "prefix_cache", False,
                "chunk_prefill_into_cache has no sequence-parallel "
                "attention path (sp>1)",
            )
        if self.ecfg.conv_cache and not self.ecfg.prefix_cache:
            self._fence(
                "conv_cache", False,
                "the conversation cache stores finished streams' KV in "
                "the prefix pool, which prefix_cache=False leaves "
                "uninitialised",
            )
        if self.ecfg.spill_pages > 0 and not self.ecfg.prefix_cache:
            self._fence(
                "spill_pages", 0,
                "the spill tier shadows prefix-pool pages, which "
                "prefix_cache=False leaves uninitialised",
            )
        if self.ecfg.role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"unknown engine role {self.ecfg.role!r} "
                "(both | prefill | decode)"
            )
        if self.ecfg.role != "both" and not self.ecfg.prefix_cache:
            self._fence(
                "role", "both",
                "disaggregated prefill/decode ships prefix-pool pages, "
                "which prefix_cache=False leaves uninitialised",
            )
        if self.ecfg.role != "both" and self.mesh is not None:
            # Same scope limit as the spill tier: pool leaves are
            # mesh-sharded and per-page host copies would gather the mesh
            # on the serving path.
            self._fence(
                "role", "both",
                "pool leaves are mesh-sharded (tp/sp>1); exporting or "
                "splicing per-page host copies would gather the mesh — "
                "same scope limit as the spill tier",
            )
        if self.ecfg.prefix_cache:
            from p2p_llm_tunnel_tpu.engine.prefix_cache import (
                PrefixIndex,
                init_pool,
                make_batch_copy_ops,
                make_spill_ops,
                pool_packed_keys,
            )

            blk = self.ecfg.min_prefill_bucket
            self._prefix_block = blk
            self._prefix_max_blocks = max(1, s // blk)
            # Static tail buckets the chunk program compiles for; longer
            # tails fall back to plain prefill (see prefix_tail_buckets).
            self._chunk_buckets = [
                blk * (2 ** i)
                for i in range(max(1, self.ecfg.prefix_tail_buckets))
                if blk * (2 ** i) <= s
            ]
            if self.ecfg.spill_pages > 0 and self.mesh is not None:
                # Same scope limit as pool snapshots: the pool leaves are
                # mesh-sharded and a per-page host copy would gather the
                # mesh on the serving path.
                self._fence(
                    "spill_pages", 0,
                    "pool leaves are mesh-sharded (tp/sp>1); per-page "
                    "host copies would gather the mesh on the serving "
                    "path — same scope limit as pool snapshots",
                )
            self._prefix = PrefixIndex(
                blk, self.ecfg.prefix_pool_blocks,
                evict=self.ecfg.prefix_evict,
                spill_pages=self.ecfg.spill_pages,
            )
            # (a state a slot is no rows a token: snapshots, below)
            self._pool = init_pool(
                {k: a for k, a in self.kv_cache.items()
                 if k not in self._state_keys},
                blk, self.ecfg.prefix_pool_blocks
            )
            if self.ecfg.prefix_cache_dir:
                from p2p_llm_tunnel_tpu.engine.prefix_cache import (
                    load_pool_snapshot,
                )

                loaded = load_pool_snapshot(
                    self.ecfg.prefix_cache_dir, self._pool, self._prefix,
                    self._prefix_snapshot_meta(),
                )
                if loaded is not None:
                    self._pool = loaded
            if self.mesh is not None:
                from p2p_llm_tunnel_tpu.parallel.sharding import shard_kv_cache

                # Pool leaves are rank-congruent with cache leaves (K axis
                # in the same place), so the cache specs apply verbatim.
                self._pool = shard_kv_cache(self._pool, self.mesh)
            # Per-block resident KV bytes: the pool leaves' total size over
            # capacity — computed from shapes/dtypes once (no device read),
            # and already reflecting the kv_quant mode (quantized pools
            # store packed values + scales, so their leaves are smaller).
            self._prefix_block_bytes = sum(
                int(arr.size) * arr.dtype.itemsize
                for arr in self._pool.values()
            ) // max(1, self.ecfg.prefix_pool_blocks)
            self._publish_prefix_gauges()
            # Row-batched (prefill_rows-wide) copy programs: one dispatch
            # per admission-wave sub-batch, not per request — a 32-row
            # wave of per-request dispatches pays 32 host↔device round
            # trips inside the prefill path.  Under int4 the value
            # leaves move in page-aligned BYTE ranges (block // 2 bytes
            # per page) — the alignment-stable page unit the ISSUE 14
            # pool guarantees.
            self._copy_in, self._copy_out = make_batch_copy_ops(
                blk, self._prefix_max_blocks, self.ecfg.prefill_rows,
                # Derived from the cache's ACTUAL leaf shapes — the same
                # predicate init_pool sizes pages with, so the page unit
                # and the copy unit cannot split.
                packed_keys=pool_packed_keys(self.kv_cache),
                # (no head axis in a row of either family's planes)
                layerwise_keys=frozenset(
                    self._pool if self.mcfg.kv_lora_rank or self._ring
                    or self._state_keys else ()),
                ring_keys=self._ring_keys(),
            )
            if self._state_keys:
                from p2p_llm_tunnel_tpu.engine.prefix_cache import (
                    StateSnapshots,
                    make_state_copy_ops,
                )

                # As many snapshots as the pool's pages can use (+ slot 0,
                # scratch): pages match no further than a boundary that
                # has one, and one is taken every ``prefill_chunk`` tokens
                # of a segmented prompt (_state_snapshot), so the pool's
                # tokens over that spacing; never more than
                # STATE_SNAPSHOT_BYTES of them; plain LRU.
                n_snap = 1 + max(1, min(
                    self.ecfg.prefix_pool_blocks * blk // (
                        self.ecfg.prefill_chunk
                        or self.ecfg.min_prefill_bucket),
                    STATE_SNAPSHOT_BYTES // self._state_row_bytes))
                self._snapshots = StateSnapshots(n_snap)
                self._prefix.snapshots = self._snapshots
                self._snap_pool = {
                    k: jnp.zeros((self.kv_cache[k].shape[0], n_snap)
                                 + self.kv_cache[k].shape[2:],
                                 self.kv_cache[k].dtype)
                    for k in self._state_keys}
                self._state_restore_op, self._state_save_op = (
                    make_state_copy_ops(self._state_keys,
                                        self.ecfg.prefill_rows))
            if self._spmd is not None:
                self._copy_in = self._spmd.wrap("copy_in", self._copy_in, 2)
                self._copy_out = self._spmd.wrap(
                    "copy_out", self._copy_out, 2
                )
            # Host-RAM spill tier (ISSUE 16): jitted single-page tier I/O
            # (traced idx — one compile each, ever), the compatibility pin
            # metadata every page carries across the tier boundary (TC18),
            # the seeded fault schedule (TUNNEL_SPILL_CHAOS), and the
            # in-flight op ledger the loadgen leak gate reads.
            self._page_out_op = self._page_in_op = None
            self._spill_meta: Dict = {}
            self._spill_chaos = None
            # The split roles (ISSUE 20) reuse the spill tier's page I/O
            # ops and pin metadata for wire transfers, so they are built
            # whenever EITHER consumer is configured.
            if self.ecfg.spill_pages > 0 or self.ecfg.role != "both":
                from p2p_llm_tunnel_tpu.transport.chaos import (
                    maybe_spill_chaos,
                )

                self._page_out_op, self._page_in_op = make_spill_ops()
                self._spill_meta = self._prefix_snapshot_meta()
                self._spill_chaos = maybe_spill_chaos()
            # Page reservation (ISSUE 14): admission reserves the pool
            # pages a request's prompt insert will want, evicting
            # (cost-aware) under pressure AT admission time instead of
            # mid-wave.  Grants are released when the insert lands or in
            # generate()'s finally — which runs on EVERY death path
            # (deadline evict, client cancel, owner-death promotion), the
            # leak-gate contract tests/test_paged_pool.py pins.
            self.scheduler.page_reserve = self._reserve_pages

        global_compile_watch.add_span(
            "startup.cache_alloc", t0=t_cache,
            bytes=_tree_bytes(self.kv_cache) + (
                _tree_bytes(self._pool) if self._prefix is not None else 0)
            + (_tree_bytes(self._snap_pool) if self._snapshots is not None
               else 0),
        )
        # Publish the fence registry where /healthz can read it without
        # holding an engine reference (latest engine wins — one serving
        # engine per process is the deployed shape, same contract as the
        # blackbox engine provider).
        global_metrics.set_info("config_fences", list(self.config_fences))
        # ... and the precision the engine was built with (after fencing):
        # a client holding the deployment to its stated types reads it here.
        global_metrics.set_info("config_quant", self.ecfg.quant)
        global_metrics.set_info("config_kv_quant", self.ecfg.kv_quant)
        global_metrics.set_info("config_model", self._model_section())

        # Prefill may run a hotter quant mode than decode (prefill_act_quant):
        # a separate static config for the prefill program only.
        self._prefill_mcfg = self.mcfg
        if (self.ecfg.prefill_act_quant and self.ecfg.quant == "int8"
                and not self.mcfg.act_quant):
            self._prefill_mcfg = dc_replace(self.mcfg, act_quant=True)

        # Host-side per-slot state driving each decode step.
        self._last_token = np.zeros((rows,), np.int32)
        self._positions = np.zeros((rows,), np.int32)
        self._active_mask = np.zeros((rows,), bool)
        self._temp = np.zeros((rows,), np.float32)
        self._top_k = np.zeros((rows,), np.int32)
        self._top_p = np.ones((rows,), np.float32)
        self._freq_pen = np.zeros((rows,), np.float32)
        self._pres_pen = np.zeros((rows,), np.float32)
        self._logprobs = np.zeros((rows,), np.int32)
        self._sample_seed = np.zeros((rows,), np.uint32)
        self._slot_bias_on = np.zeros((rows,), bool)
        if self._block:
            self._init_block(rows)
        self._spec_hist: Dict[int, tuple] = {}
        # Adaptive verify width (ISSUE 17): per-slot windowed acceptance
        # EMA driving _spec_pick_k, the last-64-burst (proposed, accepted)
        # window behind the engine_spec_accept_rate gauge, and the
        # per-iteration (proposed, accepted, k) flight scratch.  Both
        # dicts are dropped with their slot/request (_spec_drop) — the
        # engine_spec_hist_entries gauge is the leak gate.
        self._spec_ema: Dict[int, float] = {}
        self._spec_window: Deque[Tuple[int, int]] = deque(maxlen=64)
        self._flight_spec = (0, 0, 0)

        self._requests: Dict[int, _ActiveRequest] = {}
        # Chunked-prefill state: slot -> (run, next segment start).  FIFO;
        # each loop iteration advances up to prefill_rows of these by ONE
        # prefill_chunk-token segment (see _dispatch_segments).
        self._segmented: Dict[int, Tuple[RunningSlot, int]] = {}
        # Multiplexed-admission state (ecfg.mux; ISSUE 5):
        # - slot-holding whole-prompt rows awaiting a budgeted plain wave
        #   (configs where the chunk path is illegal, e.g. kv_quant=int4);
        # - the in-flight shared-prefix registry: chain key -> owner rid,
        #   plus per-owner bookkeeping and the parked group waiters
        #   (prefix_cache.plan_group_admission / _mux_wake).
        self._pending_plain: List[RunningSlot] = []
        self._inflight_prefix: Dict[bytes, int] = {}
        self._owner_keys: Dict[int, Tuple[RunningSlot, List[bytes]]] = {}
        self._prefix_waiters: List[Tuple[RunningSlot, int]] = []
        # Rids already counted in engine_prefix_dedup_hits_total: the
        # metric counts ADMISSIONS that deduped, so a waiter re-parked
        # behind a promoted owner (its first owner died) must not count
        # twice.  Pruned when the rid proceeds or is dropped — bounded by
        # the currently-parked set.
        self._dedup_counted: set = set()
        self._mux_ctl: Optional[MuxController] = None
        if self.ecfg.mux:
            self._mux_ctl = MuxController(
                self.ecfg.prefill_chunk or self.ecfg.min_prefill_bucket,
                self.ecfg.prefill_rows,
                self.ecfg.mux_budget_tokens,
            )
        self._next_request_id = 1
        self._key = jax.random.fold_in(key, 1)
        self._wake = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self._running = False
        # Serializes stop() (tunnelcheck TC13): the SIGTERM drain path and
        # a test/API teardown can both call it, and the await-task-then-
        # clear sequence must not interleave — the second caller would
        # re-run the snapshot/shutdown tail against torn state.
        self._stop_lock = asyncio.Lock()
        self._stopped = False
        # Watchdog state: monotonic time of the last accounted token (or
        # idle period); degraded flips when the budget is blown while work
        # is active, and clears on the next progress.
        self._last_progress = time.monotonic()
        self._watchdog_task: Optional[asyncio.Task] = None
        self.degraded = False
        # Compile/cold-start profiler (ISSUE 12): the program keys this
        # process has compiled (decode/prefill/chunk/spec grid), the keys
        # the parallel AOT phase compiled (the serial pass's cache-hit
        # evidence), and whether warmup declared the grid complete — a
        # first-seen key AFTER that is a mid-serve cold compile (a hole in
        # the bucket grid, counted + journaled instead of only failing
        # test_warmup_aot).
        self._programs_ready: set = set()
        self._aot_keys: set = set()
        self._warmup_done = False
        # Which attention implementation each program family that RAN in
        # this process took (family -> branches), published beside the
        # fence registry as /healthz config.attention: the model layer's
        # gates pick per shape, and an operator — or chip_smoke.py — must
        # be able to read whether a Pallas kernel or the einsum served.
        self.attention_branches: Dict[str, List[str]] = {}
        global_metrics.set_info("attention_branches", {})
        # Flight-recorder scratch (ISSUE 12): per-iteration observations
        # stashed by the methods that own them (executor-thread dispatchers
        # and the admission path) and read once per iteration by the loop's
        # record.  Plain assignments only — no read-modify-write straddles
        # an await (TC13).
        self._last_mux: Dict[str, object] = {}
        # The dispatch ledger (tracing on only): a per-engine sequence
        # number over every device dispatch of the loop, and the record the
        # most recent dispatch opened — stashed by the dispatch method
        # (executor thread) for the loop to pick up once the executor call
        # it awaited has returned, as _last_burst is.
        self._dispatch_seq = 0
        self._last_dispatch: Optional[_Dispatch] = None
        # Non-idle loop iterations so far (engine.prefill_exec's
        # ``iterations`` is a difference of two readings).
        self._loop_iter = 0
        # Where the running iteration's wall goes (ISSUE 57): the loop makes
        # one an iteration; what it awaits feeds it (_offload, _fetch,
        # _reserve_pages).
        self._split = IterationSplit(global_flight)
        self._flight_admitted = 0
        self._flight_conv = 0
        self._flight_pageouts = 0
        self._flight_pageins = 0
        # Disaggregation accounting (ISSUE 20): export/import run off the
        # loop's iteration rhythm (API/serve-driven), so they ACCUMULATE
        # here on the event loop and _flight_record drains the tallies
        # into the next iteration's row.
        self._pages_shipped_pending = 0
        self._pages_spliced_pending = 0
        self._pages_shipped_total = 0
        self._kv_xfer_inflight = 0
        self._last_burst: Tuple[int, int] = (0, 0)
        # Postmortem black box: this engine contributes the config +
        # scheduler/slot/backlog snapshot to captured bundles (latest
        # engine wins — one serving engine per process is the deployed
        # shape).
        global_blackbox.set_engine_provider(self._blackbox_state)
        # Dedicated single thread for blocking XLA calls: sharing the default
        # executor starves decode when other components run blocking work.
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="engine-xla"
        )

        # kv_view (arg 10) and steps (arg 11) are static: one compiled burst
        # program per (power-of-2 cache-view bucket, burst size).  The view
        # keeps attention HBM reads tracking actual context length instead
        # of max_seq; the two burst sizes trade throughput (big) against
        # admission latency (small, used while requests wait).
        # (the decode program, the carry it donates, its static view and
        # steps)
        decode_fn, donated, static = (
            (self._block_decode_fn, (1, 2, 3, 4, 5, 6), (15, 16))
            if self._block else (self._decode_fn, (1, 2, 3, 4), (11, 12)))
        self._jit_decode = jax.jit(
            decode_fn, donate_argnums=donated, static_argnums=static,
        )
        self._jit_prefill = jax.jit(
            self._prefill_fn, donate_argnums=(1,), static_argnums=(8,)
        )
        self._jit_chunk_prefill = jax.jit(
            self._chunk_prefill_fn, donate_argnums=(1,), static_argnums=(9,)
        )
        # (multi-process SPMD replays dispatches rank by rank and takes the
        # cache as each program's last output: it keeps the plain tuples)
        self._moe_counts = bool(self.mcfg.n_experts) and self._spmd is None
        if self._moe_counts:
            self._jit_decode = _CountsFirst(self._jit_decode, self._take_moe)
            self._jit_prefill = _CountsFirst(self._jit_prefill, self._take_moe)
            self._jit_chunk_prefill = _CountsFirst(
                self._jit_chunk_prefill, self._take_moe)

        self._jit_spec = jax.jit(
            self._spec_verify_fn, donate_argnums=(1,), static_argnums=(6,)
        )

        # Ragged grouped prefill (ISSUE 15): ONE program per flat-token
        # bucket — no static view/tail args (descriptors are runtime
        # operands; block_q/interpret ride the closure).
        self._jit_ragged = jax.jit(
            self._ragged_prefill_fn, donate_argnums=(1,)
        )

        def _embed_pool_fn(params, tokens, valid):
            from p2p_llm_tunnel_tpu.models.transformer import encode_pooled

            return encode_pooled(
                self._prefill_mcfg, params, tokens, valid, mesh=self.mesh
            )

        self._jit_embed = jax.jit(_embed_pool_fn)

        def _set_bias_fn(bias, row, ids, vals):
            # Zero the slot's row, then scatter-add the padded entries —
            # pads are (0, 0.0) so they contribute nothing (OpenAI
            # logit_bias admission; one compile, static entry cap).
            bias = bias.at[row].set(0.0)
            return bias.at[row, ids].add(vals)

        self._jit_set_bias = jax.jit(_set_bias_fn, donate_argnums=(0,))
        if self._spmd is not None:
            # Carries (params + device caches + the bias plane) are spliced
            # by each rank; everything after them is host input, broadcast
            # by rank 0.
            self._jit_decode = self._spmd.wrap("decode", self._jit_decode, 6)
            self._jit_prefill = self._spmd.wrap(
                "prefill", self._jit_prefill, 3
            )
            self._jit_chunk_prefill = self._spmd.wrap(
                "chunk", self._jit_chunk_prefill, 3
            )
            self._jit_set_bias = self._spmd.wrap(
                "set_bias", self._jit_set_bias, 1
            )
            self._jit_spec = self._spmd.wrap("spec", self._jit_spec, 3)
            self._jit_ragged = self._spmd.wrap("ragged", self._jit_ragged, 3)
            self._jit_embed = self._spmd.wrap("embed", self._jit_embed, 1)

        # Per-slot OpenAI logit_bias plane [rows, V] (scratch row included
        # so padded prefill rows can share the program).  ~17 MB at a 128k
        # vocab — kept resident; the sampler's read hides behind a
        # lax.cond on bias_on, so bias-free batches never touch it.
        glob = (self._spmd.globalize if self._spmd is not None
                else (lambda x: x))
        self._bias = glob(
            jnp.zeros((rows, self.mcfg.vocab_size), jnp.float32)
        )

        # Device-side decode carry (created lazily) + host override patch.
        self._dev_tokens = None
        self._dev_positions = None
        self._dev_counts = None  # [rows, V] generated-token counts
        self._ov_mask = np.zeros((rows,), bool)

    def _random_params(self, key, dtype):
        """Random-init weights straight in the serving precision — the
        bf16 tree of an int8/int4 model (2x a v5e's HBM at 8B) never
        exists anywhere.  Under a mesh the build runs in one jit with
        ``out_shardings``, so every chip materializes only its own shard:
        a tree built whole on the default device and ``device_put`` to the
        mesh afterwards needs the full model's bytes on chip 0 first, and
        a 70B ``--tp`` target cannot start that way.  Values do not
        depend on the sharding (partitionable threefry)."""
        if self.ecfg.quant in ("int8", "w8a8"):
            from p2p_llm_tunnel_tpu.models.quant import init_params_quantized

            log.info("initialising %s directly in int8", self.mcfg.name)
            build = functools.partial(init_params_quantized, self.mcfg)
        elif self.ecfg.quant == "int4":
            from p2p_llm_tunnel_tpu.models.quant import (
                init_params_quantized_int4,
            )

            log.info("initialising %s directly in packed int4",
                     self.mcfg.name)

            def build(k):
                return init_params_quantized_int4(
                    self.mcfg, k, self.ecfg.quant_group_size
                )
        else:
            log.info("initialising random params for %s", self.mcfg.name)

            def build(k):
                return init_params(self.mcfg, k, dtype)
        if self.mesh is None:
            return build(key)
        from p2p_llm_tunnel_tpu.parallel.sharding import param_shardings

        log.info("building params sharded over mesh %s",
                 dict(self.mesh.shape))
        shardings = param_shardings(
            self.mcfg, self.mesh, jax.eval_shape(build, key)
        )
        return jax.jit(build, out_shardings=shardings)(key)

    def resident_devices(self) -> List[int]:
        """Ids of the devices holding this engine's weights and KV cache.
        The cache is donated through every dispatch, so it sits where the
        LAST program ran: one id per replica, the whole mesh under tp.
        Read from each array's sharding, which outlives a donation:
        /healthz asks from the serve loop's thread while the engine's
        thread is inside a dispatch, when ``kv_cache`` still names the
        arrays just donated, and ``devices()`` of those raises."""
        leaves = jax.tree.leaves((self.params, self.kv_cache))
        return sorted({d.id for leaf in leaves
                       for d in leaf.sharding.device_set})

    def commit_to(self, device) -> None:
        """Commit every resident device array to ``device`` (data-parallel
        replicas, one engine per chip).  Arrays made under
        ``jax.default_device(d)`` live on ``d`` but are UNcommitted: a jit
        called outside that context runs on device 0 and drags them
        there.  With these operands committed, every later dispatch of
        this engine — whose other inputs are small uncommitted host
        arrays — runs on ``device``."""
        self.params = jax.device_put(self.params, device)
        self.kv_cache = jax.device_put(self.kv_cache, device)
        self._bias = jax.device_put(self._bias, device)
        if self._prefix is not None:
            self._pool = jax.device_put(self._pool, device)

    # -- XLA programs -----------------------------------------------------

    def _decode_fn(
        self, params, kv_cache, tokens, positions, counts, bias, ov_mask,
        ov_tok, ov_pos, samp, key, kv_view, steps,
    ):
        """``decode_steps`` chained steps; sampled tokens feed back on-device.

        ``tokens``/``positions``/``counts`` are the DEVICE-side carry from
        the previous call — the host never needs to read them, which is
        what lets the next burst dispatch while the previous burst's
        sampled block is still in flight back to the host.  ``ov_*``
        patch slots the host changed since
        (admissions): where ov_mask is set, the carry is overridden before
        stepping — including resetting that row's generated-token counts
        and crediting the prefill-sampled first token.

        Returns (sampled [B,k], tokens', positions', counts', cache').
        Slots that finish mid-scan keep computing (their surplus tokens are
        discarded by the host loop); cache writes past max_seq are dropped
        by XLA scatter OOB semantics.

        ``counts`` feeds the OpenAI frequency/presence penalties; both its
        penalty read and per-step update run under a lax.cond inside
        sampling.sample / here, so penalty-free batches (the common case)
        skip the [B,V] traffic.
        """
        b = tokens.shape[0]
        tokens = jnp.where(ov_mask, ov_tok, tokens)
        positions = jnp.where(ov_mask, ov_pos, positions)
        any_pen = jnp.any((samp.freq_pen != 0.0) | (samp.pres_pen != 0.0))

        def reset_counts():
            c = jnp.where(ov_mask[:, None], 0, counts)
            return c.at[jnp.arange(b), ov_tok].add(jnp.where(ov_mask, 1, 0))

        # The [B,V] reset/credit also hides behind the cond: a row admitted
        # during a penalty-free dispatch has stale counts, which only matter
        # if THAT row has penalties — in which case it was active here and
        # any_pen was true.
        counts = jax.lax.cond(any_pen, reset_counts, lambda: counts)

        any_lp = jnp.any(samp.logprobs > 0)

        def one(carry, _xs):
            toks, pos, cnt, cache = carry
            logits, cache, *moe = decode_step(
                self.mcfg, params, cache, toks, pos, kv_view=kv_view,
                mesh=self.mesh, with_stats=self._moe_counts,
            )
            # key=None: sampling randomness is the per-request (seed, pos)
            # stream — the burst key no longer feeds it (and the old split
            # per step was dead weight XLA DCE'd anyway).
            with jax.named_scope("head_sample"):
                sampled = sampling.sample(logits, samp, None, counts=cnt,
                                          pos=pos + 1, bias=bias)
                cnt = jax.lax.cond(
                    any_pen,
                    lambda: cnt.at[jnp.arange(b), sampled].add(1),
                    lambda: cnt,
                )
                lp = jax.lax.cond(
                    any_lp,
                    lambda: sampling.logprob_data(logits, sampled),
                    lambda: sampling.empty_logprob_data(
                        b, logits.shape[-1]),
                )
            return (sampled, pos + 1, cnt, cache), (sampled, lp, moe)

        (tokens, positions, counts, kv_cache), (toks, lps, moe) = jax.lax.scan(
            one, (tokens, positions, counts, kv_cache), None, length=steps
        )
        # [k, ...] scan stacking -> [B, k, ...] row-major for the host.
        lp_out = (
            lps[0].T,                     # chosen logprob [B, k]
            jnp.swapaxes(lps[1], 0, 1),   # top ids [B, k, CAP]
            jnp.swapaxes(lps[2], 0, 1),   # top logprobs [B, k, CAP]
        )
        # ([steps, STATS] counts of the routed layers first, where counted)
        head = tuple(m.sum(axis=0) for m in moe)
        return head + (toks.T, lp_out, tokens, positions, counts, kv_cache)

    def _prefill_fn(self, params, kv_cache, bias, tokens, lengths, slots,
                    samp, key, echo=False):
        """Plain prefill; ``echo`` (STATIC) additionally returns per-prompt-
        token logprobs — the scoring path of the legacy completions API,
        compiled on first use (an explicitly-requested eval feature, not
        the serving default).  One body serves both compiled variants so
        the sampling/logprob handling cannot drift between them."""
        prompt_lps = None
        # padding rows sit on the scratch slot and count for nothing
        stat_rows = slots != self._scratch_slot if self._moe_counts else None
        if echo:
            last_logits, kv_cache, prompt_lps, *moe = prefill_into_cache(
                self._prefill_mcfg, params, tokens, lengths, kv_cache, slots,
                mesh=self.mesh, return_prompt_logprobs=True,
                stat_rows=stat_rows,
            )
        else:
            last_logits, kv_cache, *moe = prefill_into_cache(
                self._prefill_mcfg, params, tokens, lengths, kv_cache, slots,
                mesh=self.mesh, stat_rows=stat_rows,
            )
        # Prefill rows are packed; gather each row's SLOT bias plane.
        first = sampling.sample(last_logits, samp, key, pos=lengths,
                                bias=bias[slots])
        lp = jax.lax.cond(
            jnp.any(samp.logprobs > 0),
            lambda: sampling.logprob_data(last_logits, first),
            lambda: sampling.empty_logprob_data(
                first.shape[0], last_logits.shape[-1]),
        )
        if echo:
            return (*moe, first, lp, prompt_lps, kv_cache)
        return (*moe, first, lp, kv_cache)

    def _chunk_prefill_fn(
        self, params, kv_cache, bias, tokens, lengths, starts, slots, samp,
        key, kv_view,
    ):
        """Tail-only prefill against reused history KV (prefix-cache path).
        ``kv_view`` is static (one compiled program per (tail, view))."""
        from p2p_llm_tunnel_tpu.models.transformer import (
            chunk_prefill_into_cache,
        )

        last_logits, kv_cache, *moe = chunk_prefill_into_cache(
            self._prefill_mcfg, params, tokens, lengths, starts, kv_cache,
            slots, kv_view=kv_view,
            stat_rows=slots != self._scratch_slot if self._moe_counts
            else None,
        )
        if self._block:
            # a clean prompt block's logits decide nothing: the head is
            # dead code here, and the first tokens come from a decode pass
            return (*moe, jnp.zeros(lengths.shape, jnp.int32),
                    sampling.empty_logprob_data(
                        lengths.shape[0], last_logits.shape[-1]), kv_cache)
        with jax.named_scope("head_sample"):
            first = sampling.sample(last_logits, samp, key,
                                    pos=starts + lengths, bias=bias[slots])
            lp = jax.lax.cond(
                jnp.any(samp.logprobs > 0),
                lambda: sampling.logprob_data(last_logits, first),
                lambda: sampling.empty_logprob_data(
                    first.shape[0], last_logits.shape[-1]),
            )
        return (*moe, first, lp, kv_cache)

    def _ragged_prefill_fn(
        self, params, kv_cache, bias, tokens, slot_of, start_of, qoff_of,
        base_of, sample_idx, samp_pos, slots, samp, key,
    ):
        """Ragged GROUPED tail prefill (ISSUE 15): the whole group's
        variable-length segments in one flat-token Pallas launch — the
        chunk program's twin with NO static (tail, view) axes, so one
        compiled program serves every group shape (see warmup_plan).
        ``sample_idx``/``samp_pos``/``slots`` are per-ROW (prefill_rows
        wide): each row's last-real-token logits sample exactly like the
        chunk path's."""
        from p2p_llm_tunnel_tpu.models.transformer import (
            ragged_prefill_into_cache,
        )

        last_logits, kv_cache = ragged_prefill_into_cache(
            self._prefill_mcfg, params, tokens, slot_of, start_of,
            qoff_of, base_of, sample_idx, kv_cache,
            block_q=self._ragged_bq,
            max_row_blocks=self._ragged_row_blocks,
            interpret=self._ragged_interpret,
        )
        first = sampling.sample(last_logits, samp, key, pos=samp_pos,
                                bias=bias[slots])
        lp = jax.lax.cond(
            jnp.any(samp.logprobs > 0),
            lambda: sampling.logprob_data(last_logits, first),
            lambda: sampling.empty_logprob_data(
                first.shape[0], last_logits.shape[-1]),
        )
        return first, lp, kv_cache

    def _spec_verify_fn(self, params, kv_cache, bias, tokens, positions,
                        samp, kv_view):
        """One speculative step over every row: forward carry + k proposals
        at positions [pos .. pos+k] (KV written in place — rejected
        positions hold junk that the NEXT step for that row rewrites before
        any query can attend it), accept the longest greedy-matching
        proposal prefix, emit accepted + 1 tokens.

        Greedy rows accept >0; stochastic rows accept 0 and sample
        position pos+1 from their own (seed, pos) stream — exactly a plain
        decode step.  Returns (emitted [B, k+1], counts [B], cache)."""
        from p2p_llm_tunnel_tpu.models.transformer import (
            spec_verify_into_cache,
        )

        b, t = tokens.shape  # t = 1 + K (the burst width this dispatch)
        k = t - 1
        logits, kv_cache = spec_verify_into_cache(
            self.mcfg, params, tokens, positions, kv_cache,
            kv_view=kv_view,
        )  # [B, t, V]
        if samp.bias_on is not None:
            logits = jax.lax.cond(
                jnp.any(samp.bias_on),
                lambda: logits + bias[:, None, :],
                lambda: logits,
            )
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, t]
        proposals = tokens[:, 1:]  # [B, k]
        match = greedy[:, :k] == proposals
        greedy_row = samp.temperature <= 0.0
        n_acc = jnp.where(
            greedy_row,
            jnp.cumprod(match.astype(jnp.int32), axis=-1).sum(axis=-1),
            0,
        )  # [B]
        # Bonus token at the first mismatch (or the extension on full
        # accept): greedy rows take the verifier's own argmax there;
        # stochastic rows sample position 0's logits with their key
        # stream (bias already folded in above).
        bonus_greedy = jnp.take_along_axis(
            greedy, n_acc[:, None], axis=1
        )[:, 0]
        sampled0 = sampling.sample(
            logits[:, 0], samp, None, pos=positions + 1
        )
        bonus = jnp.where(greedy_row, bonus_greedy, sampled0)
        idx = jnp.arange(t)[None, :]
        prop_pad = jnp.concatenate(
            [proposals, jnp.zeros((b, 1), jnp.int32)], axis=1
        )
        emitted = jnp.where(
            idx < n_acc[:, None], prop_pad,
            jnp.where(idx == n_acc[:, None], bonus[:, None], 0),
        )
        return emitted, n_acc + 1, kv_cache

    # -- lifecycle --------------------------------------------------------

    async def start(self) -> None:
        if self._task is None:
            self._running = True
            self._stopped = False
            global_gc.install()
            self._task = asyncio.create_task(self._loop())
            if self.ecfg.watchdog_budget_s > 0:
                self._watchdog_task = asyncio.create_task(self._watchdog())

    async def _watchdog(self) -> None:
        """Flag (never interrupt) a stalled decode path.

        Runs as its own task because the engine loop itself is what stalls:
        a wedged XLA dispatch blocks the executor thread and the loop's
        run_in_executor await with it.  The watchdog only observes
        host-side state, so it keeps ticking and can mark the engine
        degraded for /healthz while the loop is stuck.
        """
        budget = self.ecfg.watchdog_budget_s
        while self._running:
            await asyncio.sleep(min(1.0, budget / 4))
            busy = bool(self._requests)
            stalled = time.monotonic() - self._last_progress > budget
            if busy and stalled:
                if not self.degraded:
                    # Attribution (ISSUE 12): the flight recorder's phase
                    # marker names the loop phase the stall is wedged in —
                    # a stuck XLA dispatch leaves it at "decode_dispatch",
                    # a fetch hang at "decode_fetch" — so the degraded
                    # verdict says WHERE, not just THAT.
                    phase = global_flight.current_phase()
                    log.error(
                        "decode-stall watchdog: no token accounted in "
                        "%.1fs with %d request(s) in flight; marking "
                        "engine degraded (stalled in loop phase %r)",
                        budget, len(self._requests), phase,
                    )
                    global_metrics.inc("engine_watchdog_stalls_total")
                    self.degraded = True
                    self.degraded_reason = "stall"  # tunnelcheck: disable=TC13  reason ownership protocol: watchdog writes only on the not-degraded -> degraded edge it just took; "memory" trips/clears are owned by the loop's _thrash_tick hysteresis and never race this branch
                    global_metrics.set_info(
                        "engine_degraded_reason", "stall"
                    )
                    global_metrics.set_gauge("engine_degraded", 1.0)
                    # Postmortem black box: snapshot the engine AT the
                    # trip, not minutes later — runs on this task because
                    # the loop itself is what is stuck (capture never
                    # raises past its own logging).
                    global_blackbox.capture("watchdog", attribution=phase)
            elif (self.degraded and not stalled
                    and self.degraded_reason == "stall"):
                # Progress only clears a STALL degradation: a memory
                # trip (ISSUE 16) is owned by the thrash detector's own
                # hysteresis — tokens still flow while the pool thrashes,
                # so "a token landed" proves nothing about memory health.
                log.info("decode-stall watchdog: progress resumed")
                self.degraded = False
                self.degraded_reason = ""
                global_metrics.set_info("engine_degraded_reason", "")
            global_metrics.set_gauge(
                "engine_degraded", 1.0 if self.degraded else 0.0
            )

    async def stop(self) -> None:
        self._running = False
        self._wake.set()
        # Serialized + idempotent (tunnelcheck TC13): SIGTERM drain and a
        # teardown path can call stop() concurrently, and the
        # await-task-then-clear sequences below are read-modify-writes of
        # shared task handles across awaits — the second caller must wait
        # and then find the work already done, not re-await a handle the
        # first caller is mid-way through clearing.
        async with self._stop_lock:
            if self._stopped:
                return
            if self._watchdog_task is not None:
                self._watchdog_task.cancel()
                try:
                    await self._watchdog_task
                except asyncio.CancelledError:
                    pass
                self._watchdog_task = None
            if self.degraded:
                # The verdict in the process-wide registry was this
                # engine's, and nothing is left running that would clear
                # it: a later engine of the process, or a bare run_serve,
                # must not inherit "degraded" from an engine that stopped.
                global_metrics.set_gauge("engine_degraded", 0.0)
                global_metrics.set_info("engine_degraded_reason", "")
            if self._task is not None:
                try:
                    await self._task
                except asyncio.CancelledError:
                    # Either a previously-aborted stop() already propagated
                    # a cancel into the loop task, or OUR caller's cancel
                    # (teardown under wait_for) was just delivered into it
                    # through this await: in both cases the loop is dead,
                    # and finishing the shutdown tail — unblocking parked
                    # consumers, stopping follower ranks, releasing the
                    # executor — beats aborting half-stopped.
                    pass
                except Exception:
                    # Already logged + surfaced to consumers by the loop's
                    # crash containment; stop() stays clean so teardown paths
                    # don't have to handle the crash a second time.
                    pass
                self._task = None
            self._drain_moe(all_of_it=True)
            # Persist warm prompt KV before the executor goes away (reads the
            # pool device arrays; must happen while XLA dispatch still works).
            self.save_prefix_snapshot()
            if (self._spmd is not None and self._spmd.rank == 0
                    and not self._spmd_stop_sent):
                # Release the follower ranks blocked in spmd_follower_loop.
                # Once only: stop() must stay idempotent, and a second stop
                # broadcast would hang rank 0 (followers already exited).
                self._spmd_stop_sent = True
                loop = asyncio.get_running_loop()
                await loop.run_in_executor(self._executor, self._spmd.send_stop)
            # Unblock every in-flight generate() consumer.
            for state in list(self._requests.values()):
                state.queue.put_nowait(None)
            self._executor.shutdown(wait=False)
            # Marked done only once the whole tail ran: a stop() cancelled
            # mid-way (teardown under wait_for) must leave the work
            # re-runnable — flagging up front would turn every retry into
            # a silent no-op with consumers still parked on their queues.
            self._stopped = True

    async def warmup(self) -> None:
        """Pre-compile every decode-burst variant the serving loop can hit:
        (kv-view bucket × burst size).  Run BEFORE serving traffic so no
        compile ever lands inside a request; with the persistent compilation
        cache the cost is one-time per config, not per process.  The dummy
        bursts write NOTHING: every row is idle, and _dispatch_decode parks
        inactive rows' cache-write positions out of range (chunked-prefill
        segments made idle-row junk writes unsafe — see the parking comment
        there)."""
        loop = asyncio.get_running_loop()
        t_warm0 = time.monotonic()
        # JAX's compile events, kept by thread for warm-up's length: each
        # program's record (CompileWatch.note) finds its own among them
        global_compile_watch.listen(True)
        await self._warm_aot_parallel(loop)
        # Serial execute pass DRIVEN BY warmup_plan() — the same
        # enumeration the AOT phase lowered and TC17 checks dispatch
        # sites against, so a kind/shape added to the plan can never be
        # execute-warmed by one phase and missed by the other (with
        # TUNNEL_WARMUP_PAR unset this pass is the ONLY warmer).
        plan = self.warmup_plan()
        t0 = time.monotonic()
        self._warming = True
        try:
            for kind, shape in plan:
                t1 = time.monotonic()
                if kind == "decode":
                    def _one(view=shape[0], k=shape[1]):
                        outs, _ = self._dispatch_decode(view=view, steps=k)
                        jax.block_until_ready(outs[0])
                    await loop.run_in_executor(self._executor, _one)
                elif kind == "spec":
                    def _one_spec(view=shape[0], k=shape[1]):
                        self._dispatch_spec(view=view, k=k)
                        # nothing to process: no rows active during warmup
                    await loop.run_in_executor(self._executor, _one_spec)
                elif kind == "prefill":
                    await loop.run_in_executor(
                        self._executor, self._warm_prefill_program, shape[0]
                    )
                elif kind == "chunk":
                    await loop.run_in_executor(
                        self._executor, self._warm_chunk_program, *shape
                    )
                elif kind == "ragged":
                    await loop.run_in_executor(
                        self._executor, self._warm_ragged_program, shape[0]
                    )
                else:  # a plan kind without a serial warmer is a bug HERE
                    raise RuntimeError(f"unknown warmup-plan kind {kind!r}")
                dt = time.monotonic() - t1
                if dt > 1.0:
                    log.info("warmup %s%s ready in %.1fs",
                             kind, list(shape), dt)
            log.info(
                "warmup: %d planned programs executed in %.1fs",
                len(plan), time.monotonic() - t0,
            )
        finally:
            self._warming = False
        global_compile_watch.add_span("startup.execute", t0=t0)
        if self._prefix is not None:
            # Copy-op programs sit outside the bucket-grid plan (no
            # _program_key kind); warmed here so pool hits never compile
            # on the serving path.
            with global_compile_watch.startup_phase("startup.prefix_warm"):
                await loop.run_in_executor(self._executor, self._warm_prefix)
        # Observability (ISSUE 4): total warmup compile wall time — the
        # set-up a start pays before serving its first request — surfaced
        # by serve's /healthz.  The per-program breakdown (count, slowest,
        # what the compile cache on disk held) is the start-up journal's,
        # in /healthz's ``startup`` section.  From here on a first-seen
        # program key on the serving path is a mid-serve cold compile.
        global_metrics.set_gauge(
            "engine_warmup_compile_s", time.monotonic() - t_warm0
        )
        self._warmup_done = True
        global_compile_watch.listen(False)
        global_compile_watch.add_span("startup.warmup", t0=t_warm0)

    def _note_program(self, kind: str, shape: Tuple[int, ...],
                      seconds: float) -> None:
        """Compile/cold-start profiler (ISSUE 12; any thread): account the
        FIRST execution of program ``(kind, shape)`` in this process.

        During warmup the event lands in the journal as the per-program
        cold-start breakdown (``aot_hit`` when the parallel AOT phase
        already compiled the key, so the serial pass only loaded it; JAX's
        own compile events of the dispatching thread where warm-up opened
        a scope for them).
        After :meth:`warmup` declared the grid complete, a first-seen key
        is a MID-SERVE COLD COMPILE — a hole in the warmup bucket grid
        (the ``test_warmup_aot`` bug class) — counted, journaled cold, and
        stamped on the trace timeline.  ``seconds`` is the dispatch wall,
        which on a first hit is dominated by trace+compile."""
        key = _program_key(kind, shape)
        if key in self._programs_ready:
            return
        self._programs_ready.add(key)
        branch = self._attention_branch(kind, shape)
        if kind == "decode":
            branch = decode_branch_coverage(self.mcfg, branch, self._ring)
        if branch not in self.attention_branches.setdefault(kind, []):
            self.attention_branches[kind].append(branch)
            global_metrics.set_info(
                "attention_branches",
                {k: list(v) for k, v in self.attention_branches.items()},
            )
        cold = self._warmup_done
        global_compile_watch.note(
            program=kind, key=key, shape=list(shape), seconds=seconds,
            phase="serve" if cold else "warmup",
            aot_hit=key in self._aot_keys, cold=cold,
        )
        if cold:
            global_metrics.inc("engine_cold_compiles_total")
            log.warning(
                "cold compile on the serving path: %s took %.1fs — a hole "
                "in the warmup bucket grid (see engine_cold_compiles_total)",
                key, seconds,
            )
            global_tracer.add_event(
                "engine.cold_compile", trace_id=None, track="engine-loop",
                attrs={"key": key, "seconds": round(seconds, 3)},
            )

    def _attention_branch(self, kind: str, shape: Tuple[int, ...]) -> str:
        """The attention implementation program ``(kind, shape)`` traces —
        answered by the model layer's own gate predicates."""
        if kind == "decode":
            return decode_attention_branch(
                self.mcfg, self.mesh, shape[0], self._kv_quant_mode(),
                self.ecfg.max_seq)
        if kind in ("prefill", "prefill_echo"):
            return prefill_attention_branch(
                self._prefill_mcfg, self.mesh, shape[0]
            )
        if kind == "ragged":
            return "pallas-ragged"
        # chunk, and spec (a verify burst is a chunk prefill):
        # ops.attention.history_attention
        return "einsum"

    def _refuse_unsupported(self) -> None:
        """What a family served by a module of its own (latent attention:
        models/mla.py; window rings beside full planes: models/swa.py) does
        not have yet is refused at start-up, by name, instead of served
        wrongly: its weights have no quantiser (experts: models/quant.py),
        its layers no mesh rules (parallel/), and the ragged prefill and
        the speculative verify read one plane of KV heads whose keys and
        values are equally wide."""
        if self.mcfg.mixer_pattern is not None:
            what = ("a recurrent state beside the KV planes, "
                    + ("routed experts" if self.mcfg.n_experts
                       else "a dense MLP a layer"))
        elif self.mcfg.kv_lora_rank:
            what = "latent attention, routed experts"
        elif self.mcfg.attn_pattern is not None:
            what = "window rings beside full planes, routed experts"
        elif self.mcfg.block_length:
            what = "generation by blocks, routed experts"
        else:
            return
        e = self.ecfg
        asked = [
            (e.quant not in ("none", "", "a8"), f"--quant {e.quant}"),
            (e.kv_quant == "int4", "--kv-quant int4"),
            (e.tp > 1, f"--tp {e.tp}"), (e.sp > 1, f"--sp {e.sp}"),
            (e.ep > 1, f"--ep {e.ep}"),
            (e.ragged_prefill, "--ragged-prefill"),
            (e.spec_ngram > 0, "--spec-ngram"),
            (bool(e.ckpt_path), "--ckpt (no converter for this family)"),
        ]
        if self.mcfg.mixer_pattern is not None:
            # (a verify that rejects a draft has to roll the state back; the
            # host tiers, the wire and the snapshot files hold pages only)
            asked += [
                (e.spill_pages > 0,
                 "--spill-pages (no host spill of state snapshots)"),
                (e.role != "both", f"--role {e.role}"),
                (bool(e.prefix_cache_dir),
                 "--prefix-cache-dir (state snapshots are not saved)"),
            ]
        refused = [name for on, name in asked if on]
        if refused:
            raise ValueError(
                f"model {self.mcfg.name!r} ({what}) cannot be served with "
                f"{', '.join(refused)}: "
                "serve it with --quant none on one chip, without the "
                "ragged prefill or speculative decoding"
            )

    def _mux_default_chunk(self) -> int:
        """The segment width multiplexing picks when none was configured.
        128 measured best on the 32-client herd (PERF.md r8): wide enough
        that a shared-prefix owner drains in a few sub-batches, narrow
        enough that one segment's compute stays comparable to a decode
        burst."""
        return max(self.ecfg.min_prefill_bucket,
                   min(128, self.ecfg.max_seq))

    def _ring_keys(self) -> frozenset:
        """The cache leaves that are rings (none but a window-ring
        model's)."""
        if not self._ring:
            return frozenset()
        from p2p_llm_tunnel_tpu.models.swa import RING_KEYS

        return RING_KEYS & frozenset(self.kv_cache)

    def _model_section(self) -> Dict[str, object]:
        """/healthz ``config.model``: the cache's form and the share of the
        published model this process holds."""
        m = self.mcfg
        rows, s = self.ecfg.num_slots + 1, self.ecfg.max_seq
        if self._ring or self._state_keys:
            if self._ring:
                from p2p_llm_tunnel_tpu.models.swa import cache_section
            else:
                from p2p_llm_tunnel_tpu.models.ssm_moe import cache_section

            # two kinds of plane (or planes and a state): what a pooled
            # token and what a slot holds are two statements
            cache = cache_section(m, self.kv_cache)
            if self._state_keys:
                state = cache["kinds"]["state"]
                state["update"] = self._state_update
                if self._snapshots is not None:
                    # (slot 0 of the snapshot arrays is padding's scratch)
                    room = self._snapshots.capacity - 1
                    state["snapshots"] = {
                        "room": room, "held": len(self._snapshots),
                        "bytes_each": self._state_row_bytes,
                        "bytes": room * self._state_row_bytes}
        else:
            cache = {
                "form": "latent" if m.kv_lora_rank else "kv_heads",
                "values_per_token_layer": (
                    m.head_dim if m.kv_lora_rank
                    else 2 * m.n_kv_heads * m.head_dim),
                "bytes_per_token": sum(
                    int(arr.size) * arr.dtype.itemsize
                    for arr in self.kv_cache.values()) // (rows * s),
            }
        first, held = m.experts_held
        generation = {}
        if m.block_length:
            generation = {"generation": {
                "block_length": m.block_length,
                "denoise_steps": m.denoise_steps,
                "remasking": "sequential",
                "mask_token_id": m.mask_token_id,
            }}
        layer = _attention_section(m)
        if m.mixer_pattern is not None:
            # what a pattern layer is made of, and the published multipliers
            # the programs apply (each 1 where the model states none)
            layer = {
                "layer": {"mixers": dict(Counter(m.mixer_kinds)),
                          "mlp_width": m.ffn_dim if m.mixer_mlp else 0},
                "multipliers": {
                    "embedding": m.embed_multiplier,
                    "residual": m.residual_multiplier,
                    "attention_scores": m.query_scale or m.head_dim ** -0.5,
                    "logits_divisor": m.logits_divisor},
                "head": "the embedding" if m.tie_embeddings else "its own",
            }
        return {
            "name": m.name,
            **generation,
            **layer,
            "cache": cache,
            "layers": {"held": m.n_layers,
                       "of": m.published_layers or m.n_layers},
            "experts": {"held": held, "first": first, "of": m.n_experts},
            **self._moe_section(),
            "vocab_rows": {"held": m.vocab_size,
                           "of": m.vocab_size * m.layer_chips},
        }

    def _moe_section(self) -> Dict[str, object]:
        """/healthz ``config.model.expert_products``: the implementation
        the grouped products take in a decode dispatch and in a chunk
        prefill dispatch of ``prefill_rows`` rows (a smaller rung of the
        row ladder may take another: its record says)."""
        if not self.mcfg.n_experts:
            return {}
        e = self.ecfg
        return {"expert_products": {
            "decode": self._moe_branch(
                "decode",
                (e.num_slots + 1) * (2 * self.mcfg.block_length or 1)),
            "chunk_prefill": self._moe_branch(
                "chunk_prefill", e.prefill_rows * e.prefill_chunk)
            if e.prefill_chunk > 0 else None,
        }}

    def _blackbox_state(self) -> dict:
        """Engine section of a postmortem bundle (ISSUE 12): config +
        scheduler/slot/backlog state as plain JSON-able values.  Pure host
        reads — callable even while the loop is wedged in a dispatch,
        which is exactly when the watchdog captures."""
        from dataclasses import asdict

        return {
            "config": asdict(self.ecfg),
            "model": self.mcfg.name,
            "scheduler": self.scheduler.snapshot(),
            "requests_in_flight": len(self._requests),
            "segmented_slots": sorted(self._segmented),
            "pending_plain": len(self._pending_plain),
            "prefix_waiters": len(self._prefix_waiters),
            "inflight_prefix_keys": len(self._inflight_prefix),
            "config_fences": list(self.config_fences),
            "prefix_pool": None if self._prefix is None else {
                "pages_used": self._prefix.used_blocks,
                "pages_free": self._prefix.free_blocks,
                "pages_reserved": self._prefix.reserved_pages,
                "evictions": self._prefix.evictions,
                "conv_pending": len(self._conv_pending),
                "spill_pages": self._prefix.spill_resident,
                "spill_inflight": self._spill_inflight,
                "thrash_reallocs": self._prefix.thrash_reallocs,
            },
            "degraded": self.degraded,
            "degraded_reason": self.degraded_reason,
            "crashed": self._crashed,
            "warmup_done": self._warmup_done,
            "programs_ready": sorted(self._programs_ready),
        }

    def _warmup_views(self) -> List[int]:
        """View buckets warmup precompiles.  ``TUNNEL_WARMUP_VIEW_CAP=<n>``
        is a workload hint — the largest prompt+generated token count any
        request can reach — that drops buckets the traffic cannot hit
        (mirroring _kv_view_bucket's pipelining/spec pad).  Dispatch still
        selects from the FULL bucket list, so an out-of-hint request
        on-demand-compiles instead of breaking; the hint only trades warmup
        time against that risk.  Each program of a 32-layer model takes
        tens of seconds to compile cold, which is why chip_smoke.py sets
        it."""
        views = self._view_buckets()
        cap = int(os.environ.get("TUNNEL_WARMUP_VIEW_CAP", "0") or 0)
        if cap <= 0:
            return views
        need = cap + 2 * self.ecfg.decode_steps + 1
        if self.ecfg.spec_ngram > 0:
            need += self._spec_k_cap()
        if self.ecfg.prefill_chunk > 0 and not self.ecfg.ragged_prefill:
            # Chunk-prefill dispatches pick their view bucket from
            # starts.max() + the PADDED segment width (_dispatch_chunk_rows)
            # — a tail near the context cap reaches cap + prefill_chunk,
            # which EXCEEDS the decode pad whenever the chunk is wider than
            # a burst.  Under mux every admission runs through the chunk
            # program, so missing this term means a cold compile on the
            # serving path the first time a long prompt's tail lands
            # (ISSUE 5 warmup-coverage fix; pinned by test_warmup_aot).
            # The ragged program has no view axis (frontier clamp), so
            # the term — and its extra decode buckets — vanishes with it.
            need = max(need, cap + self.ecfg.prefill_chunk)
        needed = next((v for v in views if v >= need), views[-1])
        return [v for v in views if v <= needed]

    def warmup_plan(self) -> List[Tuple[str, Tuple[int, ...]]]:
        """The compiled-program grid ``warmup()`` must cover, as
        ``(kind, bucket shape)`` pairs — the ONE enumeration shared by
        the parallel AOT phase and the serial execute pass, and the
        static source tunnelcheck TC17 checks dispatch-site program
        kinds against (a kind dispatched but absent here is the
        mid-serve cold-compile class ISSUE 12 made measurable).

        With ``ragged_prefill`` the whole ``chunk[t, view]`` family —
        one program per (tail bucket × kv-view bucket) — collapses to a
        single ``ragged[tot]`` entry: the ragged kernel's frontier clamp
        reads the cache at full length (no view axis) and its flat
        packing erases the tail-bucket axis (ISSUE 15)."""
        views = self._warmup_views()
        steps = {self.ecfg.decode_steps}
        if 0 < self.ecfg.decode_steps_eager < self.ecfg.decode_steps:
            steps.add(self.ecfg.decode_steps_eager)
        # Where decode's attention bounds its own reads (the rows kernel)
        # the view axis is gone: one program a step count, at max_seq.
        decode_views = ([self.ecfg.max_seq] if self._decode_reads_rows()
                        else views)
        plan: List[Tuple[str, Tuple[int, ...]]] = [
            ("decode", (v, k)) for v in decode_views for k in sorted(steps)
        ]
        if self.ecfg.spec_ngram > 0:
            # One verify program per (view, burst width): adaptive K
            # walks the power-of-two ladder (_spec_k_buckets), so every
            # rung must be compiled up front or the first low-acceptance
            # slot cold-compiles mid-serve (pinned by test_warmup_aot's
            # mux+spec herd).
            plan += [("spec", (v, k))
                     for v in views for k in self._spec_k_buckets()]
        plan += [("prefill", (w,)) for w in self._warm_prefill_widths()]
        if self.ecfg.ragged_prefill:
            plan.append(("ragged", (self._ragged_tot,)))
            return plan
        # Chunk-prefill programs are keyed by (rows, tail, view): every
        # rung _dispatch_chunk_rows can pad to (_chunk_rungs), at every
        # width and view.  When ecfg.prefill_chunk matches a prefix-cache
        # tail bucket, the prefix path and the segment path want the
        # IDENTICAL program — dedupe, or two AOT threads compile it
        # concurrently (the persistent cache does not dedupe in-flight
        # compiles).
        widths = set(self._chunk_buckets if self._prefix is not None else ())
        if self.ecfg.prefill_chunk > 0:
            widths.add(self.ecfg.prefill_chunk)
        plan += [
            ("chunk", (rows, t, view))
            for t in sorted(widths)
            for view in views if view >= t
            for rows in self._chunk_rungs(t, view)
        ]
        return plan

    def _chunk_rungs(self, t: int, view: int) -> Tuple[int, ...]:
        """The row counts a chunk-prefill dispatch at width ``t`` and
        kv-view ``view`` pads to — the one rule warmup_plan() compiles by
        and _dispatch_chunk_rows picks by.  Every rung is one more program,
        and a program costs a warm start 0.7-1.2 s of tracing and lowering
        that threads do not share (20 s of compiling cold), against a
        set-up of a minute.  So the rungs go where every prompt passes: the
        segment width at its smallest view, where first segments run
        (start 0 — all there is of a prompt that fits one segment), keeps
        the ladder's two lowest rungs and its top.  Later segments, at the
        larger views, and prefix tails pad to ``prefill_rows`` as before."""
        ladder = chunk_row_ladder(self.ecfg.prefill_rows)
        if t == self.ecfg.prefill_chunk and view == self._chunk_view_bucket(t):
            return ladder[:2] + ladder[2:][-1:]
        return ladder[-1:]

    def _warm_samp(self, rows: int) -> sampling.SamplingParams:
        """Zero-valued sampling plane with the exact dtypes live dispatch
        uses — warm/AOT programs must hash identically to serving ones."""
        return sampling.SamplingParams(
            temperature=jnp.zeros((rows,), jnp.float32),
            top_k=jnp.zeros((rows,), jnp.int32),
            top_p=jnp.ones((rows,), jnp.float32),
            freq_pen=jnp.zeros((rows,), jnp.float32),
            pres_pen=jnp.zeros((rows,), jnp.float32),
            logprobs=jnp.zeros((rows,), jnp.int32),
            seed=jnp.zeros((rows,), jnp.uint32),
            bias_on=jnp.zeros((rows,), bool),
        )

    def _decode_warm_args(self, view: int, steps: int):
        """Positional args for a decode-burst program, aval-identical to
        _dispatch_decode's live call (same shapes/dtypes, zero values)."""
        if self._block:
            return self._block_warm_args(view, steps)
        rows = self.ecfg.num_slots + 1
        return (
            self.params, self.kv_cache, self._dev_tokens,
            self._dev_positions, self._dev_counts, self._bias,
            jnp.zeros((rows,), bool), jnp.zeros((rows,), jnp.int32),
            jnp.zeros((rows,), jnp.int32), self._warm_samp(rows),
            self._key, view, steps,
        )

    def _chunk_warm_args(self, nb: int, t: int, view: int):
        """Positional args for the chunk-prefill program of ``nb`` rows at
        tail ``t`` / kv-view ``view`` against scratch rows."""
        return (
            self.params,
            self.kv_cache,
            self._bias,
            jnp.zeros((nb, t), jnp.int32),
            jnp.ones((nb,), jnp.int32),
            jnp.zeros((nb,), jnp.int32),
            jnp.full((nb,), self._scratch_slot, jnp.int32),
            self._warm_samp(nb),
            self._key,
            view,
        )

    def _warm_prefill_widths(self) -> List[int]:
        """Distinct plain-prefill width buckets from the
        ``TUNNEL_WARMUP_PREFILL_TOKENS="77,83"`` workload hint — prompt
        token counts the workload will prefill (the bench knows its own
        prompts).  Honored by BOTH the parallel AOT phase and the serial
        execute pass, so the hint works even when AOT is skipped (PAR
        unset, SPMD, no persistent cache dir)."""
        hint = os.environ.get("TUNNEL_WARMUP_PREFILL_TOKENS", "")
        widths = set()
        for n in hint.split(","):
            if not n.strip():
                continue
            try:
                widths.add(self._bucket(int(n)))
            except ValueError:
                # Best-effort hint: a malformed entry must not abort engine
                # startup — skip it and warm the rest.
                log.warning(
                    "ignoring malformed TUNNEL_WARMUP_PREFILL_TOKENS "
                    "entry %r", n.strip(),
                )
        return sorted(widths)

    def _warm_prefill_program(self, width: int) -> None:
        """Execute-warm the plain-prefill program at prompt bucket
        ``width`` against scratch rows (executor thread)."""
        t0 = time.monotonic()
        first, _lp, self.kv_cache = self._jit_prefill(
            *self._prefill_warm_args(width)
        )
        jax.block_until_ready(first)
        self._note_program("prefill", (width,), time.monotonic() - t0)

    def _prefill_warm_args(self, width: int):
        """Positional args for the plain batched-prefill program at prompt
        bucket ``width``, aval-identical to _dispatch_prefill_batch's
        non-echo live call."""
        nb = self.ecfg.prefill_rows
        return (
            self.params, self.kv_cache, self._bias,
            jnp.zeros((nb, width), jnp.int32),
            jnp.ones((nb,), jnp.int32),
            jnp.full((nb,), self._scratch_slot, jnp.int32),
            self._warm_samp(nb), self._key,
        )

    def _spec_k_cap(self) -> int:
        """Widest verify burst any dispatch can reach: spec_k, or
        spec_k_max when the adaptive ladder is enabled above it."""
        return max(self.ecfg.spec_k, self.ecfg.spec_k_max)

    def _spec_adaptive(self) -> bool:
        return self.ecfg.spec_k_max > self.ecfg.spec_k

    def _spec_k_buckets(self) -> List[int]:
        """Burst widths K a spec dispatch may select — the program ladder
        warmup_plan() precompiles.  Fixed mode: exactly {spec_k}.
        Adaptive mode: powers of two below spec_k_max plus the cap
        itself, so the EMA can shrink a cold slot to K=1 and grow a
        hot one to the cap without ever leaving the warmed grid."""
        if not self._spec_adaptive():
            return [self.ecfg.spec_k]
        mx = self.ecfg.spec_k_max
        ks = {mx}
        k = 1
        while k < mx:
            ks.add(k)
            k *= 2
        return sorted(ks)

    def _spec_pick_k(self) -> int:
        """This dispatch's burst width: the smallest warmed bucket
        covering every active greedy slot's DESIRED width (acceptance
        EMA × cap, so a slot accepting ~60% of an 8-wide burst asks for
        ~5 and rides the 8 bucket; a slot rejecting everything decays
        toward 1).  The batch takes the max over slots — verify is one
        program over all rows, so the narrowest slot just wastes a few
        verify columns while the EMA converges."""
        buckets = self._spec_k_buckets()
        if not self._spec_adaptive():
            return buckets[-1]
        mx = self.ecfg.spec_k_max
        init = self.ecfg.spec_k / mx
        want = 1
        n = self.ecfg.num_slots
        for i in np.nonzero(self._active_mask[:n])[0]:
            if self._temp[i] <= 0.0:
                ema = self._spec_ema.get(int(i), init)
                want = max(want, int(round(ema * mx)))
        want = min(max(1, want), mx)
        return next(b for b in buckets if b >= want)

    def _spec_drop(self, slot: int) -> None:
        """Release a slot's proposer history + acceptance EMA (eviction,
        deadline, cancel) — the registry must never outlive its request
        (the engine_spec_hist_entries leak gate; loadgen asserts it
        returns to 0 post-run)."""
        self._spec_hist.pop(slot, None)
        self._spec_ema.pop(slot, None)
        global_metrics.set_gauge(
            "engine_spec_hist_entries", len(self._spec_hist)
        )

    def _spec_drop_rid(self, rid: int) -> None:
        """Drop spec state for a request wherever it sits — the cancel
        path knows the rid, not the slot."""
        for i, entry in list(self._spec_hist.items()):
            if entry[0] == rid:
                self._spec_drop(i)

    def _spec_warm_args(self, view: int, k: Optional[int] = None):
        """Positional args for the spec-verify program at burst width
        ``k``, aval-identical to _dispatch_spec's live call."""
        rows = self.ecfg.num_slots + 1
        if k is None:
            k = self.ecfg.spec_k
        return (
            self.params, self.kv_cache, self._bias,
            jnp.zeros((rows, 1 + k), jnp.int32),
            jnp.zeros((rows,), jnp.int32), self._warm_samp(rows), view,
        )

    def _copy_warm_args(self):
        """(copy_in args, copy_out args) against the scratch slot."""
        from p2p_llm_tunnel_tpu.engine.prefix_cache import pad_rows

        pr = self.ecfg.prefill_rows
        slots_i, pids_i, bnos_i = pad_rows(
            [(self._scratch_slot, [0], [0])], pr, self._prefix_max_blocks,
            scratch=None,
        )
        slots_o, pids_o, bnos_o = pad_rows(
            [(self._scratch_slot, [0], [0])], pr, self._prefix_max_blocks,
            scratch=0,
        )
        return (
            (self.kv_cache, self._pool, slots_i, pids_i, bnos_i),
            (self._pool, self.kv_cache, slots_o, pids_o, bnos_o),
        )

    async def _warm_aot_parallel(self, loop) -> None:
        """Phase-A warmup: AOT lower+compile every warm program CONCURRENTLY
        (``TUNNEL_WARMUP_PAR`` threads), then let the serial execute pass
        load the results back from the persistent compilation cache.

        ``.lower(...).compile()`` traces and compiles without executing —
        no donation is consumed and no engine state mutates, so unlike the
        dispatching warmup it is safe to fan out across threads.  XLA
        releases the GIL during compilation, so the host's cores turn ~15
        serial compiles of tens of seconds each into a few parallel
        waves.  Results land in the persistent cache keyed by program
        hash; requires
        ``jax_compilation_cache_dir`` (without it the AOT executables
        would be dropped and every program would compile twice), and is
        skipped under multi-process SPMD where dispatch order must stay
        rank-identical."""
        par = int(os.environ.get("TUNNEL_WARMUP_PAR", "0") or 0)
        if par <= 0 or self._spmd is not None:
            return
        if not jax.config.jax_compilation_cache_dir:
            log.warning(
                "TUNNEL_WARMUP_PAR set but no jax_compilation_cache_dir; "
                "skipping parallel AOT warmup"
            )
            return
        await loop.run_in_executor(self._executor, self._ensure_decode_carry)
        # (label, program kind, bucket shape, lower-thunk): the grid comes
        # from warmup_plan() — the ONE enumeration the serial pass and the
        # TC17 static check share — so the AOT phase can never drift from
        # what dispatch reaches.  None kind for the copy ops, which sit
        # outside the bucket-grid readiness contract.
        lowerers = {
            "decode": lambda shape: self._jit_decode.lower(
                *self._decode_warm_args(*shape)
            ),
            "spec": lambda shape: self._jit_spec.lower(
                *self._spec_warm_args(*shape)
            ),
            "prefill": lambda shape: self._jit_prefill.lower(
                *self._prefill_warm_args(*shape)
            ),
            "chunk": lambda shape: self._jit_chunk_prefill.lower(
                *self._chunk_warm_args(*shape)
            ),
            "ragged": lambda shape: self._jit_ragged.lower(
                *self._ragged_warm_args(*shape)
            ),
        }
        jobs: List[Tuple[str, Optional[str], Tuple[int, ...], object]] = [
            (
                f"{kind}{list(shape)}", kind, shape,
                functools.partial(lowerers[kind], shape),
            )
            for kind, shape in self.warmup_plan()
        ]
        if self._prefix is not None:
            in_args, out_args = self._copy_warm_args()
            jobs.append(
                ("copy_in", None, (), lambda: self._copy_in.lower(*in_args))
            )
            jobs.append(
                ("copy_out", None, (),
                 lambda: self._copy_out.lower(*out_args))
            )

        # _one is, statement for statement, what it was before the journal
        # (ISSUE 40): every form of it that did more, however little and
        # wherever in it, read the phase 1.1-2.5 s longer in a warm 7B
        # start, and nobody knows why (PERF.md, PR 40).  The journal splits
        # a record into Python's part and XLA's inside note(), from what
        # JAX itself reported in this thread.  The copy programs' records
        # are the serial pass's (_warm_prefix).
        def _one(label, kind, shape, thunk):
            t1 = time.monotonic()
            try:
                thunk().compile()
                dt = time.monotonic() - t1
                log.info("warmup aot %s compiled in %.1fs", label, dt)
                if kind is not None:
                    # The per-program cold-start breakdown (ISSUE 12): the
                    # AOT compile carries the real compile seconds; the
                    # serial pass then records an aot_hit load of the
                    # same key (it finds it in _aot_keys).
                    key = _program_key(kind, shape)
                    self._aot_keys.add(key)
                    global_compile_watch.note(
                        program=kind, key=key, shape=list(shape),
                        seconds=dt, phase="aot",
                    )
            except Exception as exc:  # best-effort: serial pass is truth
                log.warning("warmup aot %s failed: %s", label, exc)

        def _all():
            t1 = time.monotonic()
            with concurrent.futures.ThreadPoolExecutor(
                max_workers=par, thread_name_prefix="warm-aot"
            ) as pool:
                futs = [
                    pool.submit(_one, lbl, kind, shape, fn)
                    for lbl, kind, shape, fn in jobs
                ]
                for f in futs:
                    f.result()
            log.info(
                "warmup aot: %d programs in %.1fs (%d threads)",
                len(jobs), time.monotonic() - t1, par,
            )
            global_compile_watch.add_span(
                "startup.aot", t0=t1, threads=par)

        await loop.run_in_executor(self._executor, _all)

    def _ragged_warm_args(self, tot: int):
        """Positional args for the ragged grouped-prefill program at flat
        bucket ``tot``: an all-pad plan whose every block appends junk
        into the scratch slot — aval-identical to _dispatch_ragged_rows'
        live call."""
        from p2p_llm_tunnel_tpu.ops.pallas_prefill_attention import (
            plan_ragged_group,
        )

        slot_of, start_of, qoff_of, _qlen, base_of, _ = plan_ragged_group(
            [], self._ragged_bq, tot, self._scratch_slot
        )
        nb = self.ecfg.prefill_rows
        return (
            self.params, self.kv_cache, self._bias,
            jnp.zeros((tot,), jnp.int32),
            jnp.asarray(slot_of), jnp.asarray(start_of),
            jnp.asarray(qoff_of),
            jnp.asarray(base_of),
            jnp.zeros((nb,), jnp.int32),  # sample_idx
            jnp.zeros((nb,), jnp.int32),  # samp_pos
            jnp.full((nb,), self._scratch_slot, jnp.int32),
            self._warm_samp(nb), self._key,
        )

    def _warm_ragged_program(self, tot: int) -> None:
        """Execute-warm the ragged grouped-prefill program at flat bucket
        ``tot`` against the scratch slot (executor thread)."""
        t0 = time.monotonic()
        first, _lp, self.kv_cache = self._jit_ragged(
            *self._ragged_warm_args(tot)
        )
        jax.block_until_ready(first)
        self._note_program("ragged", (tot,), time.monotonic() - t0)

    def _warm_chunk_program(self, nb: int, t: int, view: int) -> None:
        """Compile the chunk-prefill program of ``nb`` rows at tail width
        ``t`` and kv-view ``view`` against scratch rows (executor thread)."""
        t0 = time.monotonic()
        first, _lp, self.kv_cache = self._jit_chunk_prefill(
            *self._chunk_warm_args(nb, t, view)
        )
        jax.block_until_ready(first)
        self._note_program("chunk", (nb, t, view), time.monotonic() - t0)

    def _chunk_view_bucket(self, need: int) -> int:
        """Smallest kv-view bucket covering ``need`` cache positions —
        same bucket set as decode (_view_buckets), so warmup pre-compiles
        exactly the (tail, view) programs dispatch can pick."""
        for view in self._view_buckets():
            if view >= need:
                return view
        return self.ecfg.max_seq

    def _warm_prefix(self) -> None:
        """Compile the prefix-cache COPY programs against the scratch slot
        so pool hits never compile on the serving path (executor thread).
        The tail-bucket chunk programs the pool path dispatches are part
        of warmup_plan() — the serial pass warms them with the rest of
        the grid (or skips them wholesale under ``ragged_prefill``)."""
        t0 = time.monotonic()
        in_args, _ = self._copy_warm_args()
        self.kv_cache = self._copy_in(*in_args)
        t1 = time.monotonic()
        global_compile_watch.note(
            program="copy", key="copy_in", shape=[], seconds=t1 - t0,
            phase="warmup")
        _, out_args = self._copy_warm_args()
        self._pool = self._copy_out(*out_args)
        global_compile_watch.note(
            program="copy", key="copy_out", shape=[],
            seconds=time.monotonic() - t1, phase="warmup")
        if self._snapshots is not None:
            # the state's two copy programs, scratch slot to scratch
            # snapshot and back
            t2 = time.monotonic()
            self._snap_pool = self._state_save_op(
                self._snap_pool, self.kv_cache, *self._state_rows([], []))
            self.kv_cache = self._state_restore_op(
                self.kv_cache, self._snap_pool, *self._state_rows([], []))
            global_compile_watch.note(
                program="copy", key="state_copy", shape=[],
                seconds=time.monotonic() - t2, phase="warmup")
        if self._page_out_op is not None:
            # Spill-tier I/O programs (ISSUE 16): one round trip through
            # the scratch page compiles both — idx is traced, so these are
            # the only compiles the tier ever pays.
            page = self._page_out_op(self._pool, jnp.int32(0))
            host = {k: np.asarray(v) for k, v in page.items()}
            self._pool = self._page_in_op(self._pool, jnp.int32(0), host)  # tunnelcheck: disable=TC20  warmup compile round-trip: bytes never leave this process, so the page wire contract (verify_page_pin meta/checksum) has no boundary to guard
        log.info(
            "prefix-cache warmup: copy ops compiled in %.1fs",
            time.monotonic() - t0,
        )

    # -- public API -------------------------------------------------------

    def admission_check(self, n: int = 1, tenant: str = "") -> Optional[str]:
        """Pre-flight admission verdict for ``n`` submissions by ``tenant``:
        None (admit), ``"busy"`` (global queue would overflow), or
        ``"tenant_overlimit"`` (the tenant is over its fair share of a
        contended queue).  The typed-error code IS the return value, so the
        API layer can shed before any streaming 200 with the same
        vocabulary the scheduler raises mid-stream.

        ISSUE 16 adds ``"memory"``: both KV tiers exhausted (HBM pool
        fully reserved AND the host spill tier at capacity).  Checked
        before the queue arithmetic — and independent of ``max_waiting``
        — because admitting into a thrashing pool converts every queued
        request into recompute churn, the exact failure the degradation
        contract exists to refuse."""
        if self._memory_exhausted():
            global_metrics.inc("engine_memory_shed_total")
            return "memory"
        mw = self.ecfg.max_waiting
        if mw <= 0:
            return None
        sched = self.scheduler
        # The anonymous "" bucket goes through the SAME arithmetic as any
        # named tenant — the scheduler treats it as one (submit() applies
        # its fair cap and lets it displace); skipping it here would let
        # untagged traffic pass pre-flight only to be shed mid-stream.
        cap = sched.fair_cap(tenant)
        if cap is not None and sched.tenant_queue_depth(tenant) + n > cap:
            return "tenant_overlimit"
        if sched.queue_depth + n > mw:
            # A tenant under its share may displace a monopolist instead
            # of bouncing: only report busy when displacement cannot make
            # enough room for ALL n submissions (displaceable() shares
            # _displace's cap arithmetic — including counting the
            # submitter as active — so this verdict and the submit
            # outcome can never disagree).
            need = sched.queue_depth + n - mw
            if (self.ecfg.fair_admission
                    and sched.displaceable(tenant) >= need):
                return None
            return "busy"
        return None

    def retry_after_s(self) -> float:
        """Advisory Retry-After for a 429, derived from the live queue:
        current depth over the recent admission drain rate (shared
        formula: utils.metrics.derived_retry_after_s).  Published as the
        ``engine_retry_after_s`` gauge on every computation."""
        return derived_retry_after_s(
            self.scheduler.queue_depth, "engine_admissions_total",
            "engine_retry_after_s",
        )

    async def embed(self, prompts: List[List[int]]) -> np.ndarray:
        """Mean-pooled embeddings for a batch of token-id prompts.

        Runs on the XLA executor thread (one program per (rows, width)
        bucket pair; embeddings are not latency-critical, so a first-hit
        compile is acceptable — it never blocks the event loop).  Returns
        [len(prompts), dim] float32."""
        if self._crashed:
            raise RuntimeError(
                "engine loop crashed; restart the serve process"
            )
        loop = asyncio.get_running_loop()
        pr = self.ecfg.prefill_rows
        outs = []
        # Sub-batches of prefill_rows: the same activation bound every
        # serving prefill respects — one 64-input request must not build a
        # [64, max_seq] full-attention program on a serving-sized device.
        for lo in range(0, len(prompts), pr):
            chunk = prompts[lo : lo + pr]
            width = self._bucket(max(len(p) for p in chunk))
            tokens = np.zeros((pr, width), np.int32)
            valid = np.zeros((pr, width), bool)
            for i, p in enumerate(chunk):
                tokens[i, : len(p)] = p
                valid[i, : len(p)] = True

            def run(tokens=tokens, valid=valid):
                out = self._jit_embed(  # tunnelcheck: disable=TC07  one dispatch per prefill_rows-wide sub-batch, not per prompt
                    self.params, jnp.asarray(tokens), jnp.asarray(valid)
                )
                return np.asarray(out)

            out = await loop.run_in_executor(self._executor, run)  # tunnelcheck: disable=TC07  sub-batch granularity as above
            outs.append(out[: len(chunk)])
        return np.concatenate(outs, axis=0)

    async def generate(
        self,
        prompt_ids: List[int],
        max_new_tokens: int = 64,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        freq_pen: float = 0.0,
        pres_pen: float = 0.0,
        logprobs: int = 0,
        echo_logprobs: bool = False,
        stop_ids: Optional[Tuple[int, ...]] = None,
        seed: Optional[int] = None,
        logit_bias: Tuple[Tuple[int, float], ...] = (),
        deadline: Optional[float] = None,
        trace: Optional[TraceContext] = None,
        tenant: str = "",
    ) -> AsyncIterator[TokenEvent]:
        """Submit one request; yields TokenEvents as the batch decodes.

        ``deadline`` is an absolute ``time.monotonic()`` instant: once
        passed, the scheduler evicts the request wherever it is (waiting
        queue or decode slot) and this generator raises DeadlineExceeded.

        ``tenant`` is the fair-admission identity (x-tunnel-tenant): it
        drives weighted-fair ordering, per-tenant queue-share caps
        (TenantOverLimit on overflow/displacement), and the per-tenant
        in-flight/token-rate accounting in utils.metrics.  "" opts out of
        all of it.

        ``trace`` is the propagated trace context (utils/tracing): when
        recording is on and the trace is sampled, the request's lifecycle
        lands in the span journal as an ``engine.request`` span (parent:
        the serve-side dispatch span) with queue-wait / prefill-exec /
        park child spans and first-token / stream-end events.
        """
        if self._crashed:
            raise RuntimeError(
                "engine loop crashed; restart the serve process"
            )
        if len(logit_bias) > self.BIAS_CAP:
            raise ValueError(
                f"logit_bias supports at most {self.BIAS_CAP} entries"
            )
        if deadline is not None and time.monotonic() >= deadline:
            raise DeadlineExceeded("deadline already expired at submit")
        if stop_ids is None:
            stop_ids = (self.tokenizer.eos_id,)
        rid = self._next_request_id
        self._next_request_id += 1
        if seed is None:
            # Auto-seed from the request id: sampling stays reproducible
            # for a fixed submission order AND independent of batch
            # composition (each row's key stream is its own).
            seed = (rid * 2654435761 + self.ecfg.seed) & 0xFFFFFFFF
        req = GenRequest(
            request_id=rid,
            seed=int(seed) & 0xFFFFFFFF,
            logit_bias=tuple(logit_bias),
            prompt_ids=list(prompt_ids),
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            freq_pen=freq_pen,
            pres_pen=pres_pen,
            logprobs=logprobs,
            echo_logprobs=echo_logprobs,
            stop_ids=tuple(stop_ids),
            deadline=deadline,
            tenant=tenant,
        )
        state = _ActiveRequest(
            queue=asyncio.Queue(), decoder=StreamDecoder(self.tokenizer),
            t_submit=time.monotonic(),
        )
        if trace is not None and global_tracer.on(trace.trace_id):
            state.trace = trace
            state.trace_span = new_span_id()
        self._requests[rid] = state
        try:
            displaced = self.scheduler.submit(req)
        except TenantOverLimit:
            self._requests.pop(rid, None)
            global_metrics.tenant_shed(tenant)
            raise
        except Exception:
            self._requests.pop(rid, None)
            raise
        for dreq in displaced:
            # An under-share tenant claimed queue space back from a
            # monopolist: wake the displaced consumer with the typed shed
            # (its scheduler entry is already gone).
            d_state = self._requests.get(dreq.request_id)
            if d_state is not None:
                d_state.queue.put_nowait(_SHED)
            global_metrics.tenant_shed(dreq.tenant)
        global_metrics.tenant_begin(tenant)
        global_metrics.set_gauge("engine_queue_depth", self.scheduler.queue_depth)
        self._wake.set()

        try:
            while True:
                event = await state.queue.get()
                if event is _CRASHED:
                    state.finish = "crashed"
                    raise RuntimeError("engine crashed mid-generation")
                if event is _TIMED_OUT:
                    state.finish = "timeout"
                    raise DeadlineExceeded(
                        "deadline exceeded; request evicted"
                    )
                if event is _SHED:
                    state.finish = "shed"
                    raise TenantOverLimit(
                        "displaced by tenant-fair admission; retry after "
                        "backing off"
                    )
                if event is None:
                    return
                if event.finish_reason is not None:
                    # Recorded BEFORE the yield: a consumer that stops
                    # iterating after the final event closes this generator
                    # at the yield point (GeneratorExit), so a post-yield
                    # assignment would never run and the trace would log a
                    # normal finish as "cancelled".
                    state.finish = event.finish_reason
                yield event
                if event.finish_reason is not None:
                    return
        finally:
            self._requests.pop(rid, None)
            self.scheduler.cancel(rid)
            # Spec proposer history + acceptance EMA must not outlive the
            # request (ISSUE 17 leak gate) — this finally is the one exit
            # path that sees consumer cancels.
            self._spec_drop_rid(rid)
            # Page-reservation release (ISSUE 14): runs on EVERY exit path
            # — finish, deadline evict, client cancel, shed, crash — so an
            # admission-time grant can never outlive its request (the
            # leak-gate contract).  Idempotent: the insert path usually
            # released it already.
            self._release_pages(rid)
            global_metrics.tenant_end(tenant)
            if state.first_token_at is None and state.finish:
                # The request ended SERVER-SIDE (timeout/shed — finish is
                # set; a consumer cancel leaves it None) without ever
                # producing a first token: a bad TTFT event.  Without this,
                # the ttft objective only sees requests that answered —
                # survivorship bias that reads "ok" exactly when a wedged
                # engine makes TTFT unbounded.
                global_slo.record("ttft", False)
            if state.trace is not None:
                # Exactly one engine.request span per generation — this
                # finally runs once on every exit path (finish, deadline,
                # consumer cancel, crash).  Pure host bookkeeping.
                t_end = time.monotonic()
                if state.t_parked is not None:
                    # Still parked behind a prefix owner at exit (deadline
                    # eviction or consumer cancel): close the park span
                    # here, or exactly the slowest traces — the ones whose
                    # wait WAS the park — would lose their dominant sink.
                    global_tracer.add_span(
                        "engine.prefix_park",
                        trace_id=state.trace.trace_id,
                        parent_id=state.trace_span, track="engine",
                        t0=state.t_parked, t1=t_end,
                        attrs={"terminated": state.finish or "cancelled"},
                    )
                    state.t_parked = None
                global_tracer.add_event(
                    "engine.stream_end", trace_id=state.trace.trace_id,
                    parent_id=state.trace_span, track="engine", t=t_end,
                )
                attrs = {"rid": rid, "finish": state.finish or "cancelled"}
                if tenant:
                    # traceview groups its TTFT summary by this attribute
                    # when any request in the capture carries one.
                    attrs["tenant"] = tenant
                global_tracer.add_span(
                    "engine.request", trace_id=state.trace.trace_id,
                    span_id=state.trace_span,
                    parent_id=state.trace.span_id or None, track="engine",
                    t0=state.t_submit, t1=t_end,
                    attrs=attrs,
                )

    # -- engine loop ------------------------------------------------------

    def _emit(self, run: RunningSlot, token_id: int, evicted: bool,
              lp_info=None, prompt_lps=None) -> None:
        rid = run.request.request_id
        state = self._requests.get(rid)
        if state is None:
            return  # consumer went away; scheduler cancel happens in generate()
        if state.first_token_at is None:
            state.first_token_at = time.monotonic()
            ttft_ms = (state.first_token_at - state.t_submit) * 1000.0
            global_metrics.observe("engine_ttft_ms", ttft_ms)
            # SLO feed (ISSUE 9): the same sample scored against the ttft
            # objective's threshold — a no-op while the engine is disabled.
            global_slo.record_latency("ttft", ttft_ms)
            if state.t_admitted is not None:
                # The execution half of the TTFT decomposition (includes
                # any prefix-dedup park time; queue_wait is the other half).
                global_metrics.observe(
                    "engine_prefill_exec_ms",
                    (state.first_token_at - state.t_admitted) * 1000.0,
                )
            if state.trace is not None:
                # The per-request twins of the TTFT histogram split: the
                # two child spans tile [submit, first_token] exactly, so a
                # trace reconstructs the decomposition the aggregate
                # histograms can only report in percentile form.
                tid = state.trace.trace_id
                if state.t_admitted is not None:
                    global_tracer.add_span(
                        "engine.queue_wait", trace_id=tid,
                        parent_id=state.trace_span, track="engine",
                        t0=state.t_submit, t1=state.t_admitted,
                    )
                    global_tracer.add_span(
                        "engine.prefill_exec", trace_id=tid,
                        parent_id=state.trace_span, track="engine",
                        t0=state.t_admitted, t1=state.first_token_at,
                        attrs={
                            "prompt_tokens": len(run.request.prompt_ids),
                            "cached_tokens": state.cached_tokens,
                            "parts": state.parts,
                            # 1: the iteration that admitted it also
                            # fetched its first token
                            "iterations": (self._loop_iter
                                           - state.iter_admitted + 1),
                        },
                    )
                global_tracer.add_event(
                    "engine.first_token", trace_id=tid,
                    parent_id=state.trace_span, track="engine",
                    t=state.first_token_at,
                )
        global_metrics.inc("engine_tokens_total")
        if run.request.tenant:
            # Per-tenant consumption: the /metrics-visible rate AND the
            # stride charge-back that costs a hot tenant future queue
            # priority (Scheduler.charge_tokens).
            global_metrics.tenant_tokens(run.request.tenant)
            self.scheduler.charge_tokens(run.request.tenant, 1)
        is_stop = token_id in run.request.stop_ids
        finish = None
        if evicted:
            finish = "stop" if is_stop else "length"
        text = "" if is_stop else state.decoder.push(token_id)
        logprob = tops = None
        # Stop-token events carry no content (text forced empty), so they
        # get no logprobs entry either — keeps the entries aligned 1:1
        # with content tokens in both stream and non-stream responses.
        if lp_info is not None and run.request.logprobs > 0 and not is_stop:
            chosen, top_ids, top_lps = lp_info
            logprob = float(chosen)
            n = min(run.request.logprobs, len(top_ids))
            tops = [(int(top_ids[j]), float(top_lps[j])) for j in range(n)]
        state.queue.put_nowait(
            TokenEvent(token_id, text, finish, logprob, tops, prompt_lps)
        )

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def _bucket(self, n: int) -> int:
        b = self.ecfg.min_prefill_bucket
        while b < n:
            b *= 2
        return min(b, self.ecfg.max_seq)

    def _count_kv_rows(self, first, n, rec, decode: bool = False) -> None:
        """What a dispatch's attention has to read of the cache by layer kind,
        on the host's accounting (no fetch): a row's queries at ``first[i] ..
        first[i] + n[i] - 1`` see ``p + 1`` positions in a full layer and
        ``min(p + 1, window)`` in a window layer, whose read fetches
        ``window_read``; summed, on the counters and the record alike."""
        first = np.asarray(first, np.int64)
        n = np.broadcast_to(np.asarray(n, np.int64), first.shape)
        lw = sum(self._attn_kinds)
        lf = len(self._attn_kinds) - lw
        rows = dict(full=int((n * first + n * (n + 1) // 2).sum()) * lf,
                    window=0, window_read=0)
        if lw:
            w = int(self.mcfg.sliding_window)
            m = np.clip(w - 1 - first, 0, n)  # queries that see < w positions
            window = int((m * first + m * (m + 1) // 2 + (n - m) * w).sum()) * lw
            rows.update(window=window, window_read=lw * _window_rows_fetched(
                self, first, n) if decode else window)
        for kind, count in rows.items():
            global_metrics.inc(f"engine_kv_rows_{kind}_total", count)
        if rec is not None:
            rec.attrs.update({f"kv_rows_{k}": v for k, v in rows.items()})

    def _count_state(self, rows: int, rec: Optional[_Dispatch]) -> None:
        """What a dispatch reads and writes of the recurrent state (a
        family that has one): ``rows`` row-steps (live rows x steps of a
        decode burst, the real rows of a prefill dispatch), each the state
        of every state-space layer once in and once out, from the host's
        own counts.  Counted in ``engine_state_bytes_total`` and, under
        tracing, on the dispatch's record, so the two always agree."""
        if not self._state_row_bytes:
            return
        nbytes = 2 * rows * self._state_row_bytes
        global_metrics.inc("engine_state_bytes_total", nbytes)
        if rec is not None:
            rec.attrs.update(state_rows=rows, state_bytes=nbytes)

    def _take_moe(self, counts) -> None:
        """A serving program's routed-layer counts, still on the device
        (executor thread, as the dispatch call returns): queued beside the
        dispatch's record until the host has its tokens."""
        if self._warming:
            return
        counts.copy_to_host_async()
        self._moe_pending.append(
            (self._last_dispatch if global_tracer.enabled else None, counts))

    def _drain_moe(self, upto: Optional[_Dispatch] = None,
                   all_of_it: bool = False) -> None:
        """Publish the counts of every dispatch whose outputs are on the
        host.  ``upto``: the dispatch whose tokens have just been fetched
        and whose record is about to close.  Its counts left the device
        with them, and those of every dispatch queued before it earlier, so
        they are taken whether or not each array's own ready flag is up
        yet: an output's flag can trail its program's other outputs by a
        moment, and a record that closed in that moment closed without its
        counts, the counters a dispatch behind (under six test workers:
        ISSUE 46).  ``all_of_it``: the engine is idle or stopping, nothing
        is in flight, and /metrics must not lag the last dispatch."""
        must = len(self._moe_pending) if all_of_it else 0
        if upto is not None and not all_of_it:
            # (a copy: the executor thread appends the next dispatch's
            # while this one's record closes)
            for n, (rec, _counts) in enumerate(list(self._moe_pending)):
                if rec is upto:
                    must = n + 1
                    break
        while self._moe_pending and (
                must > 0 or self._moe_pending[0][1].is_ready()):
            must -= 1
            rec, counts = self._moe_pending.popleft()
            made, held, fullest, touched = (int(n) for n in np.asarray(counts))
            global_metrics.inc("engine_moe_assignments_total", made)
            global_metrics.inc("engine_moe_assignments_held_total", held)
            global_metrics.inc("engine_moe_expert_tokens_max_total", fullest)
            global_metrics.inc("engine_moe_experts_touched_total", touched)
            if rec is not None:
                rec.attrs.update(
                    moe_assignments=made, moe_held=held,
                    moe_expert_tokens_max=fullest, moe_experts_touched=touched)

    def _moe_branch(self, program: str, tokens: int) -> Optional[str]:
        """The grouped expert products' implementation in ``program`` over
        ``tokens`` token positions a layer, by the model layer's own rule
        (models/moe.py ``grouped_product_branch``); None for a model
        without routed layers."""
        m = self.mcfg
        if not m.n_experts:
            return None
        return grouped_product_branch(
            m, self.mesh, tokens, reads_expert_stack(m, program),
            getattr(self.params["blocks"]["moe_up"], "dtype", jnp.bfloat16))

    def _note_moe(self, program: str, tokens: int,
                  rec: Optional[_Dispatch]) -> None:
        """A dispatch of a routed model: its record says which
        implementation the grouped products ran, and /metrics counts the
        dispatches that took the kernel."""
        branch = self._moe_branch(program, tokens)
        if branch is None:
            return
        if branch != MOE_RAGGED:
            global_metrics.inc("engine_moe_kernel_dispatches_total")
        if rec is not None:
            rec.attrs["moe"] = branch

    def _open_dispatch(self, span: str, program: str, parts=None,
                       **work) -> _Dispatch:
        """Open the record of the dispatch about to be made (executor
        thread).  Callers test ``global_tracer.enabled`` first: with the
        journal off no record, attrs dict or parts list is built."""
        self._dispatch_seq += 1
        return _Dispatch(
            span, dict(work, seq=self._dispatch_seq, program=program), parts
        )

    def _open_prefill_dispatch(self, program: str, rows, nb: int,
                               positions: int, **key) -> _Dispatch:
        """A prefill dispatch's record: ``rows`` is [(run, start, segment
        ids, final?)] in row order (``start``: the cache position the
        segment begins at), ``nb`` the rows dispatched (padding included)
        and ``positions`` the token positions the program runs over."""
        return self._open_dispatch(
            "engine.prefill_segment", program,
            parts=[(run.request.request_id, len(seg), start, final)
                   for run, start, seg, final in rows],
            rows=len(rows), rows_padded=nb,
            tokens=sum(len(seg) for _r, _s, seg, _f in rows),
            positions=positions, **key,
        )

    def _open_pool_copy(self, program: str, entries) -> _Dispatch:
        """A batched pool copy's record: ``entries`` is the sub-batch's
        [(slot, pool ids, block numbers)], padded by the copy program to
        ``prefill_rows`` rows of ``_prefix_max_blocks`` blocks."""
        pr = self.ecfg.prefill_rows
        return self._open_dispatch(
            "engine.pool_copy", program, rows=len(entries), rows_padded=pr,
            blocks=sum(len(ids) for _slot, ids, _bnos in entries),
            blocks_padded=pr * self._prefix_max_blocks,
        )

    def _close_pool_copy(self, rec: Optional[_Dispatch]) -> None:
        """Nothing of a pool copy is fetched: its record ends where the
        dispatch call returns."""
        if rec is not None:
            global_tracer.add_span(
                "engine.pool_copy", trace_id=None, track="engine-loop",
                t0=rec.t0, attrs=rec.attrs,
            )

    def _close_prefill_dispatch(self, rec: Optional[_Dispatch]) -> None:
        """The sampled block of a prefill dispatch is on the host: its
        engine-scope record, and over the same interval one
        ``engine.prefill_part`` for each traced request with rows in it."""
        self._drain_moe(rec)
        if rec is None:
            return
        t1 = time.monotonic()
        global_tracer.add_span(
            "engine.prefill_segment", trace_id=None, track="engine-loop",
            t0=rec.t0, t1=t1, attrs=rec.attrs,
        )
        for rid, tokens, start, final in rec.parts:
            state = self._requests.get(rid)
            if state is None or state.trace is None:
                continue
            state.parts += 1
            global_tracer.add_span(
                "engine.prefill_part", trace_id=state.trace.trace_id,
                parent_id=state.trace_span, track="engine",
                t0=rec.t0, t1=t1,
                attrs={"seq": rec.attrs["seq"], "tokens": tokens,
                       "start": start, "final": final},
            )

    def _dispatch_prefill_batch(
        self, runs: List[RunningSlot], t: int,
        hists: Optional[List[int]] = None,
        echo: bool = False,
    ):
        """Non-blocking: dispatch one bucket of admitted prompts as ONE XLA
        call; returns the on-device first-token array WITHOUT fetching it.

        Chunks are dispatched back-to-back and fetched afterwards
        (_admit_pending), so chunk n+1's compute runs under chunk n's
        host↔device round trip instead of after it.  Rows are padded to
        a power of two to bound compile count; pad rows scatter into the
        scratch slot.

        With ``hists`` (prefix-cache path) row i's first ``hists[i]`` tokens
        are already in the cache (copied from the block pool before this
        dispatch, same executor → device order) and only the tail is
        computed, via the chunk-prefill program; ``t`` then buckets the
        TAIL length.
        """
        if hists is not None:
            rows = [
                (run, hist, run.request.prompt_ids[hist:], True)
                for run, hist in zip(runs, hists)
            ]
            return self._dispatch_chunk_rows(rows, t)
        n = len(runs)
        nb = max(self.ecfg.prefill_rows, n)
        tokens = np.zeros((nb, t), np.int32)
        lengths = np.ones((nb,), np.int32)
        slots = np.full((nb,), self._scratch_slot, np.int32)
        temp = np.zeros((nb,), np.float32)
        top_k = np.zeros((nb,), np.int32)
        top_p = np.ones((nb,), np.float32)
        total = 0
        for i, run in enumerate(runs):
            ids = run.request.prompt_ids
            tokens[i, : len(ids)] = ids
            lengths[i] = len(ids)
            slots[i] = run.slot
            temp[i] = run.request.temperature
            top_k[i] = run.request.top_k
            top_p[i] = run.request.top_p
            total += len(ids)
        lps = np.zeros((nb,), np.int32)
        seeds = np.zeros((nb,), np.uint32)
        bias_on = np.zeros((nb,), bool)
        for i, run in enumerate(runs):
            lps[i] = run.request.logprobs
            seeds[i] = run.request.seed
            bias_on[i] = bool(run.request.logit_bias)
        self._apply_logit_bias(runs)
        # Penalties are zero here by construction: the FIRST token has no
        # generated predecessors, so the prefill sampler needs no counts.
        samp = sampling.SamplingParams(
            temperature=jnp.asarray(temp),
            top_k=jnp.asarray(top_k),
            top_p=jnp.asarray(top_p),
            freq_pen=jnp.zeros((nb,), jnp.float32),
            pres_pen=jnp.zeros((nb,), jnp.float32),
            logprobs=jnp.asarray(lps),
            seed=jnp.asarray(seeds),
            bias_on=jnp.asarray(bias_on),
        )
        rec = self._last_dispatch = self._open_prefill_dispatch(
            "prefill_echo" if echo else "prefill",
            [(run, 0, run.request.prompt_ids, True) for run in runs],
            nb, nb * t, t=t,
        ) if global_tracer.enabled else None
        t_jit0 = time.monotonic()
        with rec.annotation() if rec else _NO_ANNOTATION:
            if echo:
                first, lp, plp, self.kv_cache = self._jit_prefill(
                    self.params,
                    self.kv_cache,
                    self._bias,
                    jnp.asarray(tokens),
                    jnp.asarray(lengths),
                    jnp.asarray(slots),
                    samp,
                    self._next_key(),
                    True,
                )
            else:
                plp = None
                first, lp, self.kv_cache = self._jit_prefill(
                    self.params,
                    self.kv_cache,
                    self._bias,
                    jnp.asarray(tokens),
                    jnp.asarray(lengths),
                    jnp.asarray(slots),
                    samp,
                    self._next_key(),
                )
        self._note_program("prefill_echo" if echo else "prefill", (t,),  # tunnelcheck: disable=TC17  echo/scoring prefill is an explicitly-requested eval feature compiled on FIRST USE by design (_prefill_fn docstring) — never on the default serving path, so warming its [t] grid would bill every cold start for a feature most deploys never invoke
                           time.monotonic() - t_jit0)
        global_metrics.inc("engine_prefill_tokens_total", total)
        global_metrics.inc("engine_prefill_positions_total", nb * t)
        self._note_moe("prefill", nb * t, rec)
        self._count_kv_rows(
            [0] * len(runs), [len(r.request.prompt_ids) for r in runs], rec)
        self._count_state(len(runs), rec)
        out = first, (lp if lps.any() else None), plp
        self._start_host_copy(out)
        return out

    def _start_host_copy(self, tree) -> None:
        """Begin the device→host transfer of every array in ``tree``
        without blocking (executor thread, right after dispatch).  The
        copy queues behind the producing computation on the device, so by
        the time the pipelined fetch calls device_get the bytes are
        already host-side.  Without this the transfer starts only AT
        the fetch, un-hidden despite the dispatch/fetch pipelining.
        Warmup dispatches are discarded, never fetched — no copies for
        them."""
        if self._warming:
            return
        jax.tree.map(
            lambda x: x.copy_to_host_async()
            if hasattr(x, "copy_to_host_async") else None,
            tree,
        )

    def _dispatch_chunk_rows(self, rows, t: int):
        """Pack rows of ``(run, start, segment_ids, sample?)`` into ONE
        chunk-prefill dispatch at tail width ``t`` (executor thread) — the
        single home of the padding/scratch-slot/sampling-row packing shared
        by the prefix-cache tail path and chunked-prefill segments.

        Non-sampled rows (mid-prompt segments) get zeroed sampling params;
        the caller discards their returned token.

        With ``ragged_prefill`` the SAME rows route to the ragged grouped
        launch instead (ISSUE 15): one flat-packed program, no ``t``
        bucket and no view specialization — this interception point is
        what lets every chunk consumer (mux segments, prefix tails, the
        non-mux cached wave) share the collapsed program set without
        changing its own routing.
        """
        if self.ecfg.ragged_prefill:
            return self._dispatch_ragged_rows(rows)
        # Smallest view covering every row's history + padded tail: the
        # attention read cost of an admission tracks the live context, not
        # max_seq (VERDICT r4 item 7).
        view = self._chunk_view_bucket(
            max(start for _run, start, _seg, _s in rows) + t)
        # The smallest warmed rung that holds the rows: padding only, never
        # a reason to wait for more rows (callers split at prefill_rows).
        nb = next((r for r in self._chunk_rungs(t, view) if r >= len(rows)),
                  len(rows))
        tokens = np.zeros((nb, t), np.int32)
        lengths = np.ones((nb,), np.int32)
        starts = np.zeros((nb,), np.int32)
        slots = np.full((nb,), self._scratch_slot, np.int32)
        temp = np.zeros((nb,), np.float32)
        top_k = np.zeros((nb,), np.int32)
        top_p = np.ones((nb,), np.float32)
        lps = np.zeros((nb,), np.int32)
        seeds = np.zeros((nb,), np.uint32)
        bias_on = np.zeros((nb,), bool)
        total = 0
        for i, (run, start, seg, sample) in enumerate(rows):
            tokens[i, : len(seg)] = seg
            lengths[i] = len(seg)
            starts[i] = start
            slots[i] = run.slot
            if sample:
                temp[i] = run.request.temperature
                top_k[i] = run.request.top_k
                top_p[i] = run.request.top_p
                lps[i] = run.request.logprobs
                seeds[i] = run.request.seed
                bias_on[i] = bool(run.request.logit_bias)
            total += len(seg)
        self._apply_logit_bias(
            [run for (run, _s, _g, sample) in rows if sample]
        )
        samp = sampling.SamplingParams(
            temperature=jnp.asarray(temp),
            top_k=jnp.asarray(top_k),
            top_p=jnp.asarray(top_p),
            freq_pen=jnp.zeros((nb,), jnp.float32),
            pres_pen=jnp.zeros((nb,), jnp.float32),
            logprobs=jnp.asarray(lps),
            seed=jnp.asarray(seeds),
            bias_on=jnp.asarray(bias_on),
        )
        rec = self._last_dispatch = self._open_prefill_dispatch(
            "chunk_prefill", rows, nb, nb * t, t=t, view=view,
        ) if global_tracer.enabled else None
        t_jit0 = time.monotonic()
        with rec.annotation() if rec else _NO_ANNOTATION:
            first, lp, self.kv_cache = self._jit_chunk_prefill(
                self.params,
                self.kv_cache,
                self._bias,
                jnp.asarray(tokens),
                jnp.asarray(lengths),
                jnp.asarray(starts),
                jnp.asarray(slots),
                samp,
                self._next_key(),
                view,
            )
        self._note_program("chunk", (nb, t, view),
                           time.monotonic() - t_jit0)
        global_metrics.inc("engine_prefill_tokens_total", total)
        global_metrics.inc("engine_prefill_positions_total", nb * t)
        self._note_moe("chunk_prefill", nb * t, rec)
        self._count_kv_rows([start for _r, start, _g, _s in rows],
                            [len(seg) for _r, _s, seg, _f in rows], rec)
        self._count_state(len(rows), rec)
        out = first, (lp if lps.any() else None), None
        self._start_host_copy(out)
        return out

    def _dispatch_ragged_rows(self, rows):
        """Ragged grouped launch (ISSUE 15, executor thread): pack rows of
        ``(run, start, segment_ids, sample?)`` into the flat-token bucket
        and dispatch ONE Pallas-grouped program — the ragged twin of
        :meth:`_dispatch_chunk_rows` with identical row-order outputs, so
        every consumer (_finish_segments, _dispatch_plain_waves) is
        oblivious to which path ran.  Pad waste is bounded by
        ``_ragged_bq - 1`` tokens per row instead of a power-of-2 tail
        bucket, and the single ``(tot,)`` program key replaces the whole
        ``chunk[t, view]`` family."""
        from p2p_llm_tunnel_tpu.ops.pallas_prefill_attention import (
            plan_ragged_group,
        )

        bq = self._ragged_bq
        entries = [
            (run.slot, start, len(seg)) for run, start, seg, _s in rows
        ]
        tot = self._ragged_tot
        need = sum(-(-ln // bq) * bq for _sl, _st, ln in entries)
        if need > tot:
            # Defensive only: every dispatch site caps rows at
            # prefill_rows and per-row tails at the bucket arithmetic
            # _ragged_tot was sized from; a fresh program here would be
            # counted as a mid-serve cold compile (ISSUE 12).
            tot = -(-need // bq) * bq
        slot_of, start_of, qoff_of, _qlen_of, base_of, offs = (
            plan_ragged_group(entries, bq, tot, self._scratch_slot,
                              max_row_blocks=self._ragged_row_blocks)
        )
        tokens = np.zeros((tot,), np.int32)
        nb = max(self.ecfg.prefill_rows, len(rows))
        sample_idx = np.zeros((nb,), np.int32)
        samp_pos = np.zeros((nb,), np.int32)
        slots = np.full((nb,), self._scratch_slot, np.int32)
        temp = np.zeros((nb,), np.float32)
        top_k = np.zeros((nb,), np.int32)
        top_p = np.ones((nb,), np.float32)
        lps = np.zeros((nb,), np.int32)
        seeds = np.zeros((nb,), np.uint32)
        bias_on = np.zeros((nb,), bool)
        total = 0
        for i, ((run, start, seg, sample), off) in enumerate(
            zip(rows, offs)
        ):
            tokens[off : off + len(seg)] = seg
            sample_idx[i] = off + len(seg) - 1
            samp_pos[i] = start + len(seg)
            slots[i] = run.slot
            if sample:
                temp[i] = run.request.temperature
                top_k[i] = run.request.top_k
                top_p[i] = run.request.top_p
                lps[i] = run.request.logprobs
                seeds[i] = run.request.seed
                bias_on[i] = bool(run.request.logit_bias)
            total += len(seg)
        self._apply_logit_bias(
            [run for (run, _s, _g, sample) in rows if sample]
        )
        samp = sampling.SamplingParams(
            temperature=jnp.asarray(temp),
            top_k=jnp.asarray(top_k),
            top_p=jnp.asarray(top_p),
            freq_pen=jnp.zeros((nb,), jnp.float32),
            pres_pen=jnp.zeros((nb,), jnp.float32),
            logprobs=jnp.asarray(lps),
            seed=jnp.asarray(seeds),
            bias_on=jnp.asarray(bias_on),
        )
        rec = self._last_dispatch = self._open_prefill_dispatch(
            "ragged_prefill", rows, nb, tot, t=tot,
        ) if global_tracer.enabled else None
        t_jit0 = time.monotonic()
        with rec.annotation() if rec else _NO_ANNOTATION:
            first, lp, self.kv_cache = self._jit_ragged(
                self.params,
                self.kv_cache,
                self._bias,
                jnp.asarray(tokens),
                jnp.asarray(slot_of),
                jnp.asarray(start_of),
                jnp.asarray(qoff_of),
                jnp.asarray(base_of),
                jnp.asarray(sample_idx),
                jnp.asarray(samp_pos),
                jnp.asarray(slots),
                samp,
                self._next_key(),
            )
        self._note_program("ragged", (tot,), time.monotonic() - t_jit0)
        global_metrics.inc("engine_prefill_tokens_total", total)
        global_metrics.inc("engine_prefill_positions_total", tot)
        self._note_moe("ragged_prefill", tot, rec)
        self._count_kv_rows([start for _r, start, _g, _s in rows],
                            [len(seg) for _r, _s, seg, _f in rows], rec)
        self._count_state(len(rows), rec)
        out = first, (lp if lps.any() else None), None
        self._start_host_copy(out)
        return out

    def _kv_quant_mode(self) -> Optional[str]:
        """The cache's precision as the model layer names it."""
        mode = self.ecfg.kv_quant
        return mode if mode in ("int8", "int4") else None

    def _decode_reads_rows(self) -> bool:
        """Whether decode's attention is the kernel that reads each row of
        the stacked cache up to the row's own position
        (decode_attention_branch's answer at max_seq): it has no use for a
        view, so decode's view ladder is one entry and _dispatch_decode
        passes that one.  Chunk and spec programs read by einsum and keep
        their views."""
        return self._attention_branch(
            "decode", (self.ecfg.max_seq,)) == "pallas-rows"

    def _view_buckets(self) -> List[int]:
        """The full set of kv-view buckets this engine can ever dispatch:
        powers of two from 128 up, clamped to max_seq.  The ONLY bucket
        enumeration — _kv_view_bucket selects from it and warmup()
        pre-compiles exactly it, so they cannot drift (a bucket warmup
        missed would cold-compile on the serving path)."""
        buckets = []
        v = 128
        while v < self.ecfg.max_seq:
            buckets.append(v)
            v *= 2
        buckets.append(self.ecfg.max_seq)
        return sorted(set(buckets))

    def _kv_view_bucket(self) -> int:
        """Smallest bucket covering every active slot.

        The device-side carry can run up to two bursts ahead of the host's
        position accounting (pipelining lag), so pad by 2×decode_steps
        before rounding up."""
        n = self.ecfg.num_slots
        active = self._active_mask[:n]
        need = 1
        if active.any():
            need = int(self._positions[:n][active].max()) + 1
        # (a pass of a model that generates by blocks moves a row by up to a
        # group of tokens, and its base by a block)
        need += 2 * self.ecfg.decode_steps * (
            self.mcfg.block_length // self.mcfg.denoise_steps
            if self._block else 1) + 1 + self._block
        if self.ecfg.spec_ngram > 0:
            # Spec verify writes (and must be able to ATTEND) proposal KV
            # at positions up to pos + K; a view that excludes them would
            # silently break exact-greedy equivalence at bucket
            # boundaries.  Pad by the effective CAP, not the current
            # adaptive K — the per-slot EMA can grow K mid-bucket.
            need += self._spec_k_cap()
        return self._chunk_view_bucket(need)

    def _burst_steps(self) -> int:
        """Full burst normally; the small eager burst while work is waiting
        AND an admission could actually land soon (a slot free, or one
        finishing within the next full burst).  Gating on queue depth alone
        would lock a saturated engine (all slots long-running, queue never
        empty) into small bursts — throughput collapses to the fetch-RTT
        bound with zero admission-latency benefit.

        Under mux, a non-empty prefill BACKLOG (segments, pending plain
        rows, parked group waiters) also selects the eager burst
        unconditionally: backlogged rows advance once per loop iteration,
        so the burst length IS their wait — a full burst between segment
        dispatches was the dominant TTFT term on the CPU herd (PERF.md
        round 8).  The saturation argument above does not apply: the
        backlog drains by iteration count, not by slot availability."""
        eager = self.ecfg.decode_steps_eager
        if not (eager and 0 < eager < self.ecfg.decode_steps):
            return self.ecfg.decode_steps
        if self.ecfg.mux and (self._segmented or self._pending_plain
                              or self._prefix_waiters):
            return eager
        if self.scheduler.queue_depth == 0:
            return self.ecfg.decode_steps
        full = self.ecfg.decode_steps
        for run in self.scheduler.slots:
            if run is None:
                return eager  # free slot: admission is imminent
            if run.request.max_new_tokens - len(run.generated) <= full:
                return eager  # slot finishing within one full burst
        return full

    def _burst_samp(self) -> sampling.SamplingParams:
        """The sampling plane of a decode burst, from the host's per-slot
        state (copies: see _dispatch_decode on aliasing); penalties,
        logprobs and bias count for active rows only."""
        active = self._active_mask
        return sampling.SamplingParams(
            temperature=jnp.array(self._temp),
            top_k=jnp.array(self._top_k),
            top_p=jnp.array(self._top_p),
            freq_pen=jnp.array(np.where(active, self._freq_pen, 0.0)),
            pres_pen=jnp.array(np.where(active, self._pres_pen, 0.0)),
            logprobs=jnp.array(np.where(active, self._logprobs, 0)),
            seed=jnp.array(self._sample_seed),
            bias_on=jnp.array(self._slot_bias_on & active),
        )

    def _burst_assign(self) -> List[Optional[int]]:
        """Which request holds each row of the burst being dispatched (None:
        free, or not yet active), the scratch row last."""
        return [
            run.request.request_id
            if run is not None and self._active_mask[i] else None
            for i, run in enumerate(self.scheduler.slots)
        ] + [None]

    def _dispatch_decode(self, *, view: Optional[int] = None,
                         steps: Optional[int] = None):
        """Non-blocking: dispatch one k-step burst; returns (sampled_device,
        per-row request-id snapshot).

        The carry (tokens/positions) stays on device between calls, so this
        returns in ~1 ms while the previous burst's sampled block is still
        in flight to the host — the pipelining that hides the device_get
        round trip.
        """
        if self._block:
            return self._dispatch_block_decode(view, steps)
        self._ensure_decode_carry()
        # jnp.array (copy=True) — NOT jnp.asarray — for every persistent host
        # array at the dispatch boundary: on the CPU backend asarray zero-copy
        # ALIASES numpy buffers, so mutating them after dispatch (_ov_mask
        # reset below, _account_token while the burst is still queued) would
        # corrupt what the XLA program reads — a load-dependent
        # nondeterminism (verified empirically; r2 flake).
        # Penalties are masked by the ACTIVE set at dispatch: eviction never
        # has to remember to zero per-slot penalty state, and a stale value
        # from a finished request can't keep the [B,V] penalty path enabled
        # for later all-greedy batches.
        active = self._active_mask
        samp = self._burst_samp()
        # INACTIVE rows are parked at position >= max_seq every dispatch:
        # decode_step writes KV at every row's carry position, and a stale
        # carry pointing into a slot that a chunk-prefill segment has
        # already written would silently corrupt that prompt's context
        # (whole-prompt prefill rewrites the full prefix after any junk;
        # segments do not).  OOB scatter positions are dropped by XLA, so
        # parked rows write nothing; activation ov-patches the real
        # position back in.
        inactive = ~self._active_mask
        ov_mask = self._ov_mask | inactive
        park = self.ecfg.max_seq
        ov_pos = np.where(inactive, park, self._positions)
        if view is None:
            # The rows kernel reads the device-side positions: no bucket,
            # and no pad for the carry's lead on the host's accounting.
            view = (self.ecfg.max_seq if self._decode_reads_rows()
                    else self._kv_view_bucket())
        steps = self._burst_steps() if steps is None else steps
        slots = self.ecfg.num_slots
        live = int(np.count_nonzero(active[:slots]))
        attn = self._attention_branch("decode", (view, steps))
        # (warm-up's dummy bursts are no work of the loop's: not counted)
        rec = self._last_dispatch = self._open_dispatch(
            "engine.decode_burst", "decode", view=view, steps=steps,
            live_rows=live, slots=slots, attn=attn,
        ) if global_tracer.enabled and not self._warming else None
        t_jit0 = time.monotonic()
        with rec.annotation() if rec else _NO_ANNOTATION:
            (sampled, lp_out, self._dev_tokens, self._dev_positions,
             self._dev_counts, self.kv_cache) = self._jit_decode(
                self.params,
                self.kv_cache,
                self._dev_tokens,
                self._dev_positions,
                self._dev_counts,
                self._bias,
                jnp.array(ov_mask),
                jnp.array(self._last_token),
                jnp.array(ov_pos),
                samp,
                self._next_key(),
                view,
                steps,
            )
        # First hit of a (view, steps) pair = trace+compile inside that
        # call wall; after warmup that is a grid hole (ISSUE 12).
        self._note_program("decode", (view, steps),
                           time.monotonic() - t_jit0)
        self._last_burst = (steps, live)
        if not self._warming:
            global_metrics.inc("engine_decode_steps_total", steps)
            if attn != "einsum":
                global_metrics.inc("engine_decode_kernel_steps_total", steps)
            global_metrics.inc("engine_decode_row_steps_total", live * steps)
            global_metrics.inc("engine_decode_slot_steps_total",
                               slots * steps)
            self._note_moe("decode", slots + 1, rec)
            # (the device's carry may lead these positions by the bursts
            # in flight)
            self._count_kv_rows(
                self._positions[:slots][active[:slots]], steps, rec, True)
            self._count_state(live * steps, rec)
            if self._state_update is not None:
                if self._state_update != ELEMENTWISE:
                    global_metrics.inc(
                        "engine_decode_state_kernel_steps_total", steps)
                if rec is not None:
                    rec.attrs.update(state_update=self._state_update)
        self._ov_mask[:] = False  # patch consumed by this dispatch
        # Rows must ALSO have been active at dispatch time to be accounted:
        # a chunk-prefilling slot holds its request-id long before its
        # device carry is real, so the burst in flight when its final
        # segment lands would otherwise be credited as its tokens.
        assign = self._burst_assign()
        # Skip the lp arrays in the host fetch when nobody asked: the
        # ~17 KB/burst of zeros would otherwise ride every device_get on a
        # link where transfer time is the bottleneck.
        if not np.any(np.where(active, self._logprobs, 0)):
            lp_out = None
        self._start_host_copy((sampled, lp_out))
        return (sampled, lp_out), assign

    def _prefix_snapshot_meta(self) -> dict:
        """Compatibility pins for a prefix-pool snapshot: any mismatch means
        the cached KV bytes are meaningless for this engine."""
        return {
            "model": self.mcfg.name,
            "dtype": self.ecfg.dtype,
            "quant": self.ecfg.quant,
            # With int4 weights the group size changes the dequantized
            # weights and hence the KV bytes; a snapshot taken under one
            # grouping must not reload under another.
            "group_size": self.ecfg.quant_group_size,
            "kv_quant": self.ecfg.kv_quant,
            "seed": self.ecfg.seed,
            "ckpt_path": self.ecfg.ckpt_path,
            "block": self._prefix_block,
            "capacity": self.ecfg.prefix_pool_blocks,
            # The cache's form: each leaf's per-token shape and type (keys
            # and values of the KV heads, or one latent row; their scales).
            "page": [[key, list(arr.shape[3:]), str(arr.dtype)]
                     for key, arr in sorted(self.kv_cache.items())],
        }

    def save_prefix_snapshot(self) -> None:
        if (self._prefix is None or not self.ecfg.prefix_cache_dir
                or self._spmd is not None):
            # Multi-host: every rank would need a coordinated save/load;
            # skipped (snapshots are a single-host serve convenience).
            return
        from p2p_llm_tunnel_tpu.engine.prefix_cache import save_pool_snapshot

        try:
            save_pool_snapshot(
                self.ecfg.prefix_cache_dir, self._pool, self._prefix,
                self._prefix_snapshot_meta(),
            )
        except OSError as e:
            log.warning("prefix snapshot save failed: %s", e)

    def _ensure_decode_carry(self) -> None:
        """Lazily create the device-side decode carry — shared by rank-0
        dispatch and follower replay so both sides stay shape-identical.
        Under multi-process SPMD the zeros must be GLOBAL arrays (a
        process-local array is rejected at the jit boundary)."""
        if self._dev_tokens is not None:
            return
        rows = self.ecfg.num_slots + 1
        glob = (self._spmd.globalize if self._spmd is not None
                else (lambda x: x))
        self._dev_tokens = glob(jnp.zeros(
            (rows, 2 * self._block) if self._block else (rows,), jnp.int32))
        if self._block:  # (engine/block_engine.py: the carry)
            self._dev_decided = jnp.zeros((rows,), jnp.int32)
            self._dev_pending = jnp.zeros((rows,), bool)
        self._dev_positions = glob(jnp.zeros((rows,), jnp.int32))
        self._dev_counts = glob(
            jnp.zeros((rows, self.mcfg.vocab_size), jnp.int32)
        )

    # -- cross-host SPMD followers (PARITY A8) ----------------------------

    def spmd_follower_step(self) -> bool:
        """Replay ONE broadcast dispatch; False when rank 0 said stop.

        The wrapped jit callables do the receive-side globalization; this
        method only splices in the follower's own device carries and stores
        the carried outputs, mirroring exactly what the rank-0 call sites
        do with theirs."""
        assert self._spmd is not None and self._spmd.rank != 0
        op, args = self._spmd.recv()
        if op == "stop":
            return False
        if op == "decode":
            self._ensure_decode_carry()
            (_s, _lp, self._dev_tokens, self._dev_positions,
             self._dev_counts, self.kv_cache) = self._jit_decode(
                self.params, self.kv_cache, self._dev_tokens,
                self._dev_positions, self._dev_counts, self._bias, *args,
            )
        elif op == "prefill":
            out = self._jit_prefill(
                self.params, self.kv_cache, self._bias, *args
            )
            self.kv_cache = out[-1]
        elif op == "chunk":
            out = self._jit_chunk_prefill(
                self.params, self.kv_cache, self._bias, *args
            )
            self.kv_cache = out[-1]
        elif op == "ragged":
            out = self._jit_ragged(
                self.params, self.kv_cache, self._bias, *args
            )
            self.kv_cache = out[-1]
        elif op == "set_bias":
            self._bias = self._jit_set_bias(self._bias, *args)
        elif op == "spec":
            out = self._jit_spec(
                self.params, self.kv_cache, self._bias, *args
            )
            self.kv_cache = out[-1]
        elif op == "embed":
            self._jit_embed(self.params, *args)
        elif op == "copy_in":
            self.kv_cache = self._copy_in(self.kv_cache, self._pool, *args)
        elif op == "copy_out":
            self._pool = self._copy_out(self._pool, self.kv_cache, *args)
        else:
            raise RuntimeError(f"unknown SPMD op {op!r}")
        return True

    def spmd_follower_loop(self) -> None:
        """Ranks != 0: replay rank 0's dispatch stream until it stops.
        Blocking (broadcast_one_to_all rendezvous); run instead of
        start()/serving on follower hosts."""
        log.info("SPMD follower loop: rank %d", self._spmd.rank)
        n = 0
        while self.spmd_follower_step():
            n += 1
        log.info("SPMD follower loop done after %d ops", n)

    #: Static entry cap of the set-bias program (OpenAI allows 300).
    BIAS_CAP = 320

    def _apply_logit_bias(self, runs) -> None:
        """Write admitted requests' logit_bias rows into the device plane
        (executor thread, before the admission's sampling dispatch).  Slots
        whose previous occupant had a bias are cleared lazily — the common
        bias-free admission costs zero dispatches."""
        for run in runs:
            i = run.slot
            lb = run.request.logit_bias
            if not lb and not self._slot_bias_on[i]:
                continue
            ids = np.zeros((self.BIAS_CAP,), np.int32)
            vals = np.zeros((self.BIAS_CAP,), np.float32)
            for j, (t, v) in enumerate(lb[: self.BIAS_CAP]):
                ids[j] = t
                vals[j] = v
            self._bias = self._jit_set_bias(  # tunnelcheck: disable=TC07  one tiny scatter per BIASED slot only; bias-free admissions skip the body
                self._bias, i, jnp.asarray(ids), jnp.asarray(vals)
            )
            self._slot_bias_on[i] = bool(lb)

    def _admit_one(self, run: RunningSlot) -> None:
        """Set up host slot state after prefill admission."""
        i = run.slot
        req = run.request
        self._active_mask[i] = True
        self._positions[i] = run.cache_len
        self._temp[i] = req.temperature
        self._top_k[i] = req.top_k
        self._top_p[i] = req.top_p
        self._freq_pen[i] = req.freq_pen
        self._pres_pen[i] = req.pres_pen
        self._logprobs[i] = req.logprobs
        self._sample_seed[i] = req.seed
        if self._block:
            self._admit_block_row(run)
        # The device-side carry knows nothing about this slot yet; patch it
        # in at the next dispatch.
        self._ov_mask[i] = True

    #: Proposer search window: the backward n-gram scan is bounded so the
    #: per-step host cost stays O(window), not O(context).
    SPEC_SEARCH_WINDOW = 1024

    def _propose(self, run: RunningSlot, k: int) -> np.ndarray:
        """Prompt-lookup proposal: continuation of the most recent PRIOR
        occurrence of the last spec_ngram tokens in this request's own
        prompt + generation history (bounded backward search).  A bad
        proposal is harmless — the verifier only accepts tokens greedy
        decoding would have produced anyway — so no-match rows just
        propose zeros.

        History is cached per slot and appended incrementally, so a long
        context is not re-materialized every step."""
        out = np.zeros((k,), np.int32)
        n = self.ecfg.spec_ngram
        i = run.slot
        cached = self._spec_hist.get(i)
        if cached is None or cached[0] != run.request.request_id:
            # Slot reused by a new request: its predecessor's acceptance
            # memory must not steer the newcomer's burst width.
            self._spec_ema.pop(i, None)
            cached = (run.request.request_id,
                      [int(t) for t in run.request.prompt_ids], 0)
            self._spec_hist[i] = cached
            global_metrics.set_gauge(
                "engine_spec_hist_entries", len(self._spec_hist)
            )
        rid, hist, consumed = cached
        gen = run.generated
        if consumed < len(gen):
            hist.extend(int(t) for t in gen[consumed:])
            self._spec_hist[i] = (rid, hist, len(gen))
        if len(hist) <= n:
            return out
        tail = hist[-n:]
        lo = max(0, len(hist) - n - self.SPEC_SEARCH_WINDOW)
        for s in range(len(hist) - n - 1, lo - 1, -1):
            if hist[s : s + n] == tail:
                cont = hist[s + n : s + n + k]
                out[: len(cont)] = cont
                break
        return out

    def _spec_usable(self) -> bool:
        """Spec covers rows whose features it supports; any active row
        needing penalties or logprobs sends the whole batch down the plain
        path (those features' device plumbing lives in _decode_fn)."""
        if self.ecfg.spec_ngram <= 0:
            return False
        a = self._active_mask
        if not bool(np.any(a & (self._temp <= 0.0))):
            # No greedy row can accept anything: the spec step would emit
            # exactly 1 token per row at a SYNCHRONOUS dispatch each — far
            # worse than the pipelined k-step burst.  Plain path wins.
            return False
        return not bool(np.any(
            a & ((self._freq_pen != 0.0) | (self._pres_pen != 0.0)
                 | (self._logprobs > 0))
        ))

    def _dispatch_spec(self, *, view: Optional[int] = None,
                       k: Optional[int] = None):
        """(executor thread) One speculative verify step over every row;
        returns ((emitted [R, k+1], counts [R]), request-id snapshot).

        ``k`` is this dispatch's burst width — adaptive mode picks it per
        iteration from the warmed bucket ladder (_spec_pick_k); warmup
        pins it per plan entry.

        Host-carried state (no device carry, no pipelining): the host must
        read per-row counts before it can feed consumers anyway.  The
        device decode carry goes stale here, so the next plain burst gets
        a full override patch."""
        rows = self.ecfg.num_slots + 1
        if k is None:
            k = self._spec_pick_k()
        tokens = np.zeros((rows, 1 + k), np.int32)
        tokens[:, 0] = self._last_token
        for i in np.nonzero(self._active_mask)[0]:
            run = self.scheduler.slots[i] if i < self.ecfg.num_slots else None
            if run is not None:
                tokens[i, 1:] = self._propose(run, k)
        inactive = ~self._active_mask
        park = self.ecfg.max_seq
        positions = np.where(inactive, park, self._positions)
        active = self._active_mask
        samp = sampling.SamplingParams(
            temperature=jnp.array(self._temp),
            top_k=jnp.array(self._top_k),
            top_p=jnp.array(self._top_p),
            freq_pen=jnp.zeros((rows,), jnp.float32),
            pres_pen=jnp.zeros((rows,), jnp.float32),
            logprobs=jnp.zeros((rows,), jnp.int32),
            seed=jnp.array(self._sample_seed),
            bias_on=jnp.array(self._slot_bias_on & active),
        )
        view = self._kv_view_bucket() if view is None else view
        t_jit0 = time.monotonic()
        emitted, counts, self.kv_cache = self._jit_spec(
            self.params,
            self.kv_cache,
            self._bias,
            jnp.array(tokens),
            jnp.array(positions),
            samp,
            view,
        )
        self._note_program("spec", (view, k), time.monotonic() - t_jit0)
        assign = [
            run.request.request_id
            if run is not None and self._active_mask[i] else None
            for i, run in enumerate(self.scheduler.slots)
        ] + [None]
        emitted = np.asarray(emitted)
        counts = np.asarray(counts)
        # Device decode carry is now stale for every row.
        self._ov_mask[:] = True
        return (emitted, counts), assign

    #: Acceptance-EMA blend: new burst weighs 0.2 — ~5-burst memory,
    #: fast enough to shrink K within a few rejected bursts.
    SPEC_EMA_ALPHA = 0.2

    async def _process_spec(self, outs, assign: List) -> None:
        emitted, counts = outs
        k = emitted.shape[1] - 1  # this burst's width
        n_emitted = 0
        n_rows = 0
        proposed = 0
        accepted = 0
        for i in np.nonzero(self._active_mask)[0]:
            run = self.scheduler.slots[i] if i < self.ecfg.num_slots else None
            if run is None:
                self._active_mask[i] = False
                self._spec_drop(int(i))
                continue
            if run.request.request_id != assign[i]:
                continue
            n_rows += 1
            if self._temp[i] <= 0.0 and k > 0:
                # Acceptance bookkeeping is GREEDY rows only: stochastic
                # rows accept 0 by construction, and folding their zeros
                # in would both drag the published rate and strangle the
                # adaptive width for everyone in the batch.
                acc = int(counts[i]) - 1
                proposed += k
                accepted += acc
                prev = self._spec_ema.get(
                    int(i), self.ecfg.spec_k / max(1, self._spec_k_cap()))
                self._spec_ema[int(i)] = (
                    (1.0 - self.SPEC_EMA_ALPHA) * prev
                    + self.SPEC_EMA_ALPHA * (acc / k)
                )
            for j in range(int(counts[i])):
                n_emitted += 1
                self._account_token(int(i), int(emitted[i, j]))
                if not self._active_mask[i]:
                    break  # stop/limit hit mid-acceptance: surplus dropped
            await asyncio.sleep(0)
        if n_rows:
            global_metrics.inc("engine_spec_tokens_total", n_emitted)
            global_metrics.inc(
                "engine_spec_accepted_tokens_total", n_emitted - n_rows
            )
            global_metrics.inc("engine_spec_proposed_tokens_total", proposed)
            self._spec_window.append((proposed, accepted))
            w_prop = sum(p for p, _ in self._spec_window)
            w_acc = sum(a for _, a in self._spec_window)
            global_metrics.set_gauge(
                "engine_spec_accept_rate",
                (w_acc / w_prop) if w_prop else 0.0,
            )
            self._flight_spec = (proposed, accepted, k)

    def _expire_deadlines(self) -> None:
        """Evict deadline-blown requests (queue or slot) and fail their
        consumers with DeadlineExceeded.  Runs once per loop iteration —
        granularity is one burst, which is the finest the engine can evict
        at anyway (a slot frees between dispatches, never inside one)."""
        expired = self.scheduler.expire(time.monotonic())
        for slot, req in expired:
            if slot is not None:
                self._active_mask[slot] = False
                self._spec_drop(slot)
            global_metrics.inc("engine_deadline_timeouts_total")
            log.warning(
                "request %d exceeded its deadline (%s); slot reclaimed",
                req.request_id,
                "waiting" if slot is None else f"slot {slot}",
            )
            state = self._requests.get(req.request_id)
            if state is not None:
                if state.trace is not None:
                    global_tracer.add_event(
                        "engine.deadline_evict",
                        trace_id=state.trace.trace_id,
                        parent_id=state.trace_span, track="engine",
                        attrs={"where": "waiting" if slot is None
                               else f"slot {slot}"},
                    )
                state.queue.put_nowait(_TIMED_OUT)

    def _account_token(self, slot: int, tok: int, lp_info=None,
                       prompt_lps=None) -> None:
        """Record one generated token: scheduler accounting, slot-state
        update for the next decode call, eviction, emission."""
        self._last_progress = time.monotonic()
        out = self.scheduler.record_token(slot, tok)
        evicted = self.scheduler.slots[slot] is None
        if evicted:
            self._active_mask[slot] = False
            self._spec_drop(slot)
            if self._prefix is not None and self.ecfg.conv_cache:
                # Every record_token eviction is a NATURAL finish (stop /
                # length / cache-full; deadline evictions and cancels
                # never route through here).
                # Conversation cache (ISSUE 14): the finished stream's KV
                # covers positions [0, cache_len-1) — the final sampled
                # token was never fed back, so its KV row was never
                # written.  Queue the full-page prefix of that range for
                # the end-of-iteration batched insert; a turn-N+1 prompt
                # that resends this conversation matches through it.
                seq = out.request.prompt_ids + out.generated[:-1]
                if self._block:
                    # generation by blocks: the blocks before the last
                    # token's are committed (a pass on a block follows the
                    # commit of the one before it); that block may not be
                    seq = seq[: len(seq) // self._block * self._block]
                if len(seq) >= self._prefix_block:
                    self._conv_pending.append((slot, seq))
        else:
            self._last_token[slot] = tok
            # The generated token's own position: it is written to the cache
            # by the decode step that consumes it.
            self._positions[slot] = out.cache_len - 1
        self._emit(out, tok, evicted, lp_info, prompt_lps)

    def _prefix_copy_in(self, hits: List[Tuple[int, List[int]]]) -> None:
        """Copy matched pool blocks into the hit slots (executor thread):
        ``hits`` is [(slot, pool_ids)], ONE batched dispatch per
        prefill_rows-wide sub-batch."""
        from p2p_llm_tunnel_tpu.engine.prefix_cache import pad_rows

        pr = self.ecfg.prefill_rows
        for lo in range(0, len(hits), pr):
            entries = [
                (slot, ids, list(range(len(ids))))
                for slot, ids in hits[lo : lo + pr]
            ]
            slots, pids, bnos = pad_rows(
                entries, pr, self._prefix_max_blocks, scratch=None
            )
            rec = (self._open_pool_copy("pool_to_cache", entries)
                   if global_tracer.enabled else None)
            with rec.annotation() if rec else _NO_ANNOTATION:
                self.kv_cache = self._copy_in(  # tunnelcheck: disable=TC07  ONE dispatch per prefill_rows-wide sub-batch: this batching IS the r5 fix
                    self.kv_cache, self._pool, slots, pids, bnos
                )
            self._close_pool_copy(rec)
            if self._snapshots is not None:
                self._state_restore(hits[lo : lo + pr])  # tunnelcheck: disable=TC07  ONE dispatch per prefill_rows-wide sub-batch, behind its rows' copy-in

    def _state_restore(self, hits: List[Tuple[int, List[int]]]) -> None:
        """The hit slots' recurrent state from the snapshots at their
        matches' ends (executor thread, behind the rows' copy-in: one
        dispatch a ``prefill_rows``-wide sub-batch).  A match ends at a
        snapshot by :meth:`PrefixIndex.match`'s rule; its key is the chain
        key of the match's last block."""
        slots, ids = [], []
        for slot, pool_ids in hits:
            prompt = self.scheduler.slots[slot].request.prompt_ids
            key = self._prefix.block_keys(prompt)[len(pool_ids) - 1]
            idx = self._snapshots.lookup(key)
            if idx is None:
                raise RuntimeError(
                    f"slot {slot}: a prefix hit of {len(pool_ids)} blocks "
                    "ends at no state snapshot")
            slots.append(slot)
            ids.append(idx)
            global_metrics.inc("engine_state_restores_total")
            global_metrics.inc("engine_state_bytes_total",
                               self._state_row_bytes)
            if global_tracer.enabled:
                global_tracer.add_event(
                    "engine.state_restore", trace_id=None,
                    track="engine-loop",
                    attrs={"slot": slot, "snapshot": idx,
                           "tokens_skipped":
                           len(pool_ids) * self._prefix_block,
                           "bytes": self._state_row_bytes},
                )
        self.kv_cache = self._state_restore_op(
            self.kv_cache, self._snap_pool, *self._state_rows(slots, ids))

    def _state_rows(self, slots: List[int], ids: List[int]):
        """(slots, snapshot ids) of one state copy dispatch, padded to
        ``prefill_rows`` with the scratch slot and snapshot 0."""
        pad = self.ecfg.prefill_rows - len(slots)
        return (jnp.asarray(slots + [self._scratch_slot] * pad, jnp.int32),
                jnp.asarray(ids + [0] * pad, jnp.int32))

    def _state_snapshot(self, wave: List[Tuple[int, List[int]]]) -> None:
        """Snapshots of the state of the slots of ``[(slot, token ids the
        slot holds)]`` whose ids end on a block boundary that has none yet
        (executor thread, behind the dispatch that brought the slot there:
        the state is then the state after exactly those ids).  The policy:
        a snapshot wherever a prefill dispatch ends on a block boundary,
        that is every ``prefill_chunk`` tokens of a segmented prompt and at
        a prompt's end where that is whole blocks."""
        pr = self.ecfg.prefill_rows
        todo = []
        for slot, ids in wave:
            if not ids or len(ids) % self._prefix_block:
                continue
            key = self._prefix.block_keys(ids)[-1]
            if key in self._snapshots:
                self._snapshots.lookup(key)  # touched
                continue
            todo.append((slot, self._snapshots.allocate(key), len(ids)))
        for lo in range(0, len(todo), pr):
            part = todo[lo : lo + pr]
            for slot, idx, n in part:
                global_metrics.inc("engine_state_snapshots_total")
                global_metrics.inc("engine_state_bytes_total",
                                   self._state_row_bytes)
                if global_tracer.enabled:
                    global_tracer.add_event(
                        "engine.state_snapshot", trace_id=None,
                        track="engine-loop",
                        attrs={"slot": slot, "snapshot": idx, "boundary": n,
                               "bytes": self._state_row_bytes},
                    )
            self._snap_pool = self._state_save_op(
                self._snap_pool, self.kv_cache, *self._state_rows(
                    [slot for slot, _i, _n in part],
                    [idx for _s, idx, _n in part]))

    def _ring_live(self, wave: List[Tuple[int, List[int]]],
                   lead: int = 0) -> List[Tuple[int, List[int]]]:
        """Of ``[(slot, token ids the slot holds)]``, the entries whose
        unsaved blocks the slot's rings still hold whole (all of them where
        no layer is a ring).  A ring of ``R`` holds the last ``R`` positions
        written; ``lead``: positions the device may have written past the
        ids (decode steps dispatched before the host saw the stream end).
        An entry whose first unsaved block has left the ring saves nothing:
        a chain with a hole matches no further than the hole."""
        if not self._ring:
            return wave
        live = []
        for slot, ids in wave:
            missing = self._prefix.missing(ids)
            if missing and (missing[0][0] * self._prefix_block
                            < len(ids) + lead - self._ring):
                continue
            live.append((slot, ids))
        return live

    def _prefix_insert(self, runs: List[RunningSlot],
                       held: Optional[List[int]] = None) -> None:
        """Save the runs' now-prefilled, not-yet-pooled prompt blocks into
        the pool (executor thread), one batched dispatch per prefill_rows.
        Same-wave eviction hazards are handled by
        :func:`prefix_cache.plan_inserts` (see its docstring).

        ``held[i]``: the tokens of run ``i``'s prompt that its slot holds so
        far (all of it when not given).  Where window layers are rings, a
        block can be saved only while the ring still holds it, so a
        segmented prompt is saved segment by segment
        (:meth:`_dispatch_segments`), and a run whose first unsaved block
        has already left the ring (a whole-prompt prefill longer than the
        ring) saves nothing (:meth:`_ring_live`)."""
        from p2p_llm_tunnel_tpu.engine.prefix_cache import (
            pad_rows,
            plan_inserts,
        )

        wave = self._ring_live([
            (run.slot, run.request.prompt_ids if held is None
             else run.request.prompt_ids[: held[i]])
            for i, run in enumerate(runs)])
        if self._snapshots is not None:
            self._state_snapshot(wave)
        entries = plan_inserts(
            self._prefix, wave,
            ms_per_token=self._prefill_ms_per_token or 1.0,
        )
        total = sum(len(ids) for _, ids, _ in entries)
        pr = self.ecfg.prefill_rows
        for lo in range(0, len(entries), pr):
            slots, pids, bnos = pad_rows(
                entries[lo : lo + pr], pr, self._prefix_max_blocks,
                scratch=0,
            )
            rec = (self._open_pool_copy("cache_to_pool",
                                        entries[lo : lo + pr])
                   if global_tracer.enabled else None)
            with rec.annotation() if rec else _NO_ANNOTATION:
                self._pool = self._copy_out(  # tunnelcheck: disable=TC07  ONE dispatch per prefill_rows-wide sub-batch, off the TTFT-critical path
                    self._pool, self.kv_cache, slots, pids, bnos
                )
            self._close_pool_copy(rec)
        if total:
            global_metrics.inc("engine_prefix_saved_blocks_total", total)

    async def _admit_pending(self, loop) -> None:
        """Batched prefill: one XLA call per prompt-length bucket chunk.

        All chunks DISPATCH first (cheap, device queues them), then results
        fetch in dispatch order — so the device computes chunk n+1 while
        chunk n's first-token block rides the RTT back to the host, and the
        earliest arrivals' first tokens emit as soon as their own chunk
        lands rather than after the whole admission wave.

        With the prefix cache on, each admitted prompt is first matched
        against the block pool; matched runs get their history KV copied
        into their slot (dispatched before their prefill, same executor →
        same device order) and are grouped by TAIL-length bucket instead.
        After a run's prefill lands, its uncached full blocks are saved
        back to the pool — off the TTFT-critical path.
        """
        admitted = self.scheduler.admit()
        if not admitted:
            return
        self._note_admission(admitted)
        await self._drain_page_ins(loop, admitted)
        await self._dispatch_plain_waves(loop, admitted)

    def _note_admission(self, admitted: List[RunningSlot]) -> None:
        """Stamp slot-admission time and record the queue-wait half of the
        TTFT decomposition (engine_queue_wait_ms + engine_prefill_exec_ms
        ≈ engine_ttft_ms, ISSUE 5 observability)."""
        now = time.monotonic()
        global_metrics.inc("engine_admissions_total", len(admitted))
        self._flight_admitted += len(admitted)
        for run in admitted:
            st = self._requests.get(run.request.request_id)
            if st is not None and st.t_admitted is None:
                st.t_admitted = now
                st.iter_admitted = self._loop_iter
                global_metrics.observe(
                    "engine_queue_wait_ms", (now - st.t_submit) * 1000.0
                )

    def _note_cached_tokens(self, run: RunningSlot, hist: int) -> None:
        """The request reuses ``hist`` prompt tokens from the pool (what
        engine.prefill_exec's ``cached_tokens`` reports)."""
        state = self._requests.get(run.request.request_id)
        if state is not None:
            state.cached_tokens = hist

    async def _dispatch_plain_waves(
        self, loop, admitted: List[RunningSlot]
    ) -> None:
        """Dispatch one admission wave's prefills (see _admit_pending for
        the pipelining/prefix-match contract).  Callers: the legacy
        admission path (whole wave), the mux echo route, and the mux
        budgeted whole-prompt drain (a bounded batch per iteration)."""
        hist_of: Dict[int, int] = {}
        pool_ids_of: Dict[int, List[int]] = {}
        for run in admitted:
            hist = 0
            # Echo/scoring requests need logits for EVERY prompt position:
            # prefix reuse and segmentation would skip computing them, so
            # they always take the whole-prompt plain path.
            if self._prefix is not None and not run.request.echo_logprobs:
                hist, ids = self._prefix.match(run.request.prompt_ids)
                if hist:
                    pool_ids_of[run.slot] = ids
            hist_of[run.slot] = hist
        # Long tails go to the chunked-prefill queue: they advance one
        # segment per loop iteration (interleaved with decode bursts)
        # instead of stalling this admission wave.  Their prefix copy-in
        # dispatches NOW so it precedes every segment in executor order.
        # (Routed BEFORE the tail-bucket cap below: segments use the
        # prefill_chunk-wide program, so a long tail composes with any
        # history length.)
        if self._block:
            # Generation by blocks has no whole-prompt prefill: an echoed
            # prompt runs through the decode passes as forced outcomes
            # (engine/block_engine.py), every other prompt's whole blocks
            # through the segments below, whatever its length.
            for run in [r for r in admitted if r.request.echo_logprobs]:
                self._admit_one(run)
                admitted.remove(run)
        if self.ecfg.prefill_chunk > 0:
            seg_hits: List[Tuple[int, List[int]]] = []
            for run in list(admitted):
                if run.request.echo_logprobs:
                    continue  # echo: whole-prompt prefill only (see above)
                hist = hist_of[run.slot]
                if (self._block or len(run.request.prompt_ids) - hist
                        > self.ecfg.prefill_chunk):
                    if hist:
                        seg_hits.append((run.slot, pool_ids_of[run.slot]))
                        global_metrics.inc(
                            "engine_prefix_hit_tokens_total", hist
                        )
                        self._note_cached_tokens(run, hist)
                    self._segmented[run.slot] = (run, hist)
                    admitted.remove(run)
            if seg_hits:
                await self._offload(loop, self._prefix_copy_in, seg_hits)
        # Group by (tail bucket, cached?): cached runs use the chunk-prefill
        # program, whose bucket is the tail length.  A matched prefix whose
        # tail exceeds every compiled chunk bucket is dropped back to the
        # plain path — NEVER cold-compile on the serving path.
        groups: Dict[Tuple[int, bool, bool], List[RunningSlot]] = {}
        for run in admitted:
            hist = hist_of[run.slot]
            if hist and (
                len(run.request.prompt_ids) - hist > self._chunk_buckets[-1]
            ):
                hist = hist_of[run.slot] = 0
            if hist:
                global_metrics.inc("engine_prefix_hit_tokens_total", hist)
                self._note_cached_tokens(run, hist)
            t = self._bucket(len(run.request.prompt_ids) - hist)
            echo = bool(run.request.echo_logprobs)
            groups.setdefault((t, hist > 0, echo), []).append(run)
        chunked: List[Tuple[int, bool, bool, List[RunningSlot]]] = []
        pr = self.ecfg.prefill_rows
        for (t, cached, echo), runs in sorted(groups.items()):
            for i in range(0, len(runs), pr):
                chunked.append((t, cached, echo, runs[i : i + pr]))
        dispatched = []
        for t, cached, echo, runs in chunked:
            t0 = time.monotonic()
            if cached:
                await self._offload(  # tunnelcheck: disable=TC07  one copy call per prefill_rows-wide chunk, dispatched before that chunk's prefill (same executor, same device order)
                    loop, self._prefix_copy_in,
                    [(run.slot, pool_ids_of[run.slot]) for run in runs],
                )
            hists = [hist_of[r.slot] for r in runs] if cached else None
            first_dev = await self._offload(  # tunnelcheck: disable=TC07  one dispatch per prefill_rows-wide bucket chunk, back-to-back so chunk n+1 computes under chunk n's RTT
                loop, self._dispatch_prefill_batch, runs, t, hists, echo,
            )
            dispatched.append((runs, first_dev, t0, self._last_dispatch))
        inserts: List[RunningSlot] = []
        for runs, first_dev, t0, rec in dispatched:
            firsts, lp, plp = await self._offload(  # tunnelcheck: disable=TC07  one FETCH per already-dispatched chunk, in dispatch order: the pipelining that overlaps the RTT with compute
                loop, self._fetch, first_dev)
            self._close_prefill_dispatch(rec)
            # Wall time of this chunk's dispatch → result-on-host span, the
            # per-phase timing SURVEY §5 asks for (overlaps siblings').
            wall_ms = (time.monotonic() - t0) * 1000.0
            global_metrics.observe("engine_prefill_ms", wall_ms)
            self._note_prefill_cost(
                sum(len(r.request.prompt_ids) - hist_of.get(r.slot, 0)
                    for r in runs),
                wall_ms,
            )
            for i, (run, first) in enumerate(zip(runs, firsts[: len(runs)])):
                if self.scheduler.slots[run.slot] is not run:
                    # Consumer cancelled while the prefill was in flight;
                    # the slot is already free — drop it.
                    continue
                self._admit_one(run)
                lp_row = None if lp is None else (lp[0][i], lp[1][i], lp[2][i])
                plp_row = None
                if plp is not None:
                    n = len(run.request.prompt_ids)
                    plp_row = [float(x) for x in plp[i][:n]]
                self._account_token(run.slot, int(first), lp_row, plp_row)
                if self._prefix is not None:
                    inserts.append(run)
        # Pool inserts run after EVERY first token of the wave is out —
        # they only pay off future admissions, so they must not sit between
        # a chunk's fetch and the next chunk's (the TTFT-critical path).
        live = [r for r in inserts if self.scheduler.slots[r.slot] is r]
        if live:
            await self._offload(loop, self._prefix_insert, live)
            self._release_pages_for(live)

    # -- multiplexed admission (ISSUE 5) ----------------------------------

    async def _admit_mux(self, loop) -> None:
        """Multiplexed admission: bind waiting requests to slots (FIFO) and
        ROUTE them — echo/scoring requests to the legacy whole-prompt wave
        (they need every prompt position's logits), everything else into
        the prefill backlog — WITHOUT dispatching prefill work here.  The
        backlog drains under the iteration token budget in the main loop,
        interleaved with decode bursts (_mux_budget / _dispatch_segments).

        With the prefix cache on, the wave is grouped by PrefixIndex block
        keys first (prefix_cache.plan_group_admission): a shared
        not-yet-pooled prefix is computed by its FIFO-first requester only;
        later group members park as waiters and fan out from the pool once
        the owner's blocks land (_mux_wake).  The only device work here is
        the BATCHED pool copy-in for already-pooled prefixes — every
        per-request loop body is pure host logic (the TC07 contract).
        """
        admitted = self.scheduler.admit()
        if not admitted:
            return
        self._note_admission(admitted)
        await self._drain_page_ins(loop, admitted)
        echo = [r for r in admitted if r.request.echo_logprobs]
        if echo:
            await self._dispatch_plain_waves(loop, echo)
        rest = [r for r in admitted if not r.request.echo_logprobs]
        if not rest:
            return
        if self._prefix is None:
            if self.ecfg.prefill_chunk > 0:
                for run in rest:
                    self._segmented[run.slot] = (run, 0)
            else:
                self._pending_plain.extend(rest)
            return
        await self._plan_mux_wave(loop, rest)

    async def _plan_mux_wave(self, loop, runs: List[RunningSlot]) -> None:
        """Group ``runs`` (FIFO order) against the pool and the in-flight
        prefix registry; enqueue the owners, park the waiters.  Shared by
        fresh admissions and waiter re-planning (_mux_wake), so a woken
        waiter can itself become the owner of its remaining blocks."""
        from p2p_llm_tunnel_tpu.engine.prefix_cache import (
            plan_group_admission,
        )

        by_rid = {run.request.request_id: run for run in runs}
        owners, waiters = plan_group_admission(
            self._prefix,
            self._inflight_prefix,
            [(run.request.request_id, run.request.prompt_ids)
             for run in runs],
        )
        for rid, owner_rid in waiters:
            self._prefix_waiters.append((by_rid[rid], owner_rid))
            state = self._requests.get(rid)
            if (state is not None and state.trace is not None
                    and state.t_parked is None):
                # Park starts now; the span closes when this request next
                # proceeds through an owners wave (below).  Re-parks behind
                # a promoted owner extend the SAME park span.
                state.t_parked = time.monotonic()
            if rid not in self._dedup_counted:
                self._dedup_counted.add(rid)
                global_metrics.inc("engine_prefix_dedup_hits_total")
        hits: List[Tuple[int, List[int]]] = []
        for rid, hist, pool_ids, keys in owners:
            run = by_rid[rid]
            self._dedup_counted.discard(rid)
            state = self._requests.get(rid)
            if state is not None and state.trace is not None:
                if state.t_parked is not None:
                    # Waiter woken: its owner's blocks are pooled (or it
                    # was promoted to owner) — the park is over.
                    global_tracer.add_span(
                        "engine.prefix_park",
                        trace_id=state.trace.trace_id,
                        parent_id=state.trace_span, track="engine",
                        t0=state.t_parked,
                        attrs={"promoted_owner": bool(keys)},
                    )
                    state.t_parked = None
                if keys:
                    global_tracer.add_event(
                        "engine.prefix_own",
                        trace_id=state.trace.trace_id,
                        parent_id=state.trace_span, track="engine",
                        attrs={"keys": len(keys), "hist_tokens": hist},
                    )
            if keys:
                self._owner_keys[rid] = (run, keys)
            if hist:
                hits.append((run.slot, pool_ids))
                global_metrics.inc("engine_prefix_hit_tokens_total", hist)
                if state is not None:
                    state.cached_tokens = hist
            self._segmented[run.slot] = (run, hist)
        if hits:
            # Dispatched before any of the wave's segments (same executor,
            # same device order), so reused history KV is in place when the
            # first tail segment reads it.
            await self._offload(loop, self._prefix_copy_in, hits)

    async def _mux_wake(self, loop) -> None:
        """Release dead owners' in-flight prefix claims and RE-PLAN waiters
        whose owner finished (its blocks are pooled — _finish_segments
        inserts before this runs) or died mid-prefill (cancel/expiry: the
        first waiter is promoted to owner and computes the prefix itself,
        so a cancelled group head never starves its group).  Runs once per
        loop iteration; pure host work plus at most one batched copy-in
        for the woken waiters' pooled prefixes."""
        for rid, (run, _keys) in list(self._owner_keys.items()):
            seg = self._segmented.get(run.slot)
            alive = (self.scheduler.slots[run.slot] is run
                     and seg is not None and seg[0] is run)
            if not alive:
                self._owner_done(rid)
        if not self._prefix_waiters:
            return
        ready: List[RunningSlot] = []
        still: List[Tuple[RunningSlot, int]] = []
        for run, owner_rid in self._prefix_waiters:
            if self.scheduler.slots[run.slot] is not run:
                # Cancelled/expired while parked; slot reclaimed.
                self._dedup_counted.discard(run.request.request_id)
                continue
            if owner_rid in self._owner_keys:
                still.append((run, owner_rid))
            else:
                ready.append(run)
        self._prefix_waiters = still
        if ready:
            await self._plan_mux_wave(loop, ready)

    def _owner_done(self, rid: int) -> None:
        """Drop a finished/dead owner's claims from the in-flight prefix
        registry so its waiters re-plan at the next _mux_wake."""
        entry = self._owner_keys.pop(rid, None)
        if entry is None:
            return
        for key in entry[1]:
            if self._inflight_prefix.get(key) == rid:
                del self._inflight_prefix[key]

    def _mux_budget(self) -> int:
        """This iteration's prefill budget in SEGMENT ROWS, from the
        controller's token budget (published as engine_mux_budget_tokens).
        The backlog is counted in remaining DISPATCH rows — a half-done
        long prompt contributes its remaining segment count — so a full
        drain budget really drains it.  On the whole-prompt fallback path
        the unit is min_prefill_bucket, so the row count is a proxy
        rather than an exact token bound."""
        chunk = max(1, self._mux_ctl.unit)
        backlog = len(self._pending_plain)
        for run, start in self._segmented.values():
            rest = len(run.request.prompt_ids) - start
            backlog += max(1, -(-rest // chunk))
        n = self.ecfg.num_slots
        active = int(np.count_nonzero(self._active_mask[:n]))
        now = time.monotonic()
        # Every place a not-yet-decoding request can sit: the waiting
        # queue, the segment backlog, pending whole-prompt rows, and
        # parked prefix waiters — a tight deadline in ANY of them must
        # trigger the controller's rescue drain.
        slacks = [
            req.deadline - now
            for req in self.scheduler.waiting
            if req.deadline is not None
        ]
        slacks += [
            run.request.deadline - now
            for run, _start in self._segmented.values()
            if run.request.deadline is not None
        ]
        slacks += [
            run.request.deadline - now
            for run in self._pending_plain
            if run.request.deadline is not None
        ]
        slacks += [
            run.request.deadline - now
            for run, _owner in self._prefix_waiters
            if run.request.deadline is not None
        ]
        min_slack = min(slacks) if slacks else None
        # True decode token cost per row this iteration (ISSUE 17): a
        # spec iteration moves up to K+1 tokens per slot in one pass, so
        # the controller's decode-stall bound must charge it as such.
        drt = 1
        if self._spec_usable() and any(self._active_mask):
            drt = 1 + self._spec_pick_k()
        tokens = self._mux_ctl.budget_tokens(
            queue_depth=self.scheduler.queue_depth,
            backlog_rows=backlog,
            active_rows=active,
            min_slack_s=min_slack,
            decode_row_tokens=drt,
        )
        global_metrics.set_gauge("engine_mux_budget_tokens", tokens)
        # Flight-recorder stash (ISSUE 12): the controller's inputs and
        # verdict for THIS iteration's record (read once by the loop).
        self._last_mux = {
            "backlog_rows": backlog,
            "min_slack_s": (round(min_slack, 3)
                            if min_slack is not None else None),
            "budget_tokens": tokens,
        }
        return tokens // self._mux_ctl.unit

    def _dispatch_segments(self, max_rows: Optional[int] = None):
        """Advance up to ``prefill_rows`` chunked-prefill slots (or the
        iteration's ``max_rows`` budget under mux, whichever is smaller)
        by ONE segment each, as one chunk-prefill call (executor thread).

        Returns (rows, first_dev, t_dispatch, n_tokens, record) where rows
        is [(run, was_final)] in row order, n_tokens counts REAL segment
        tokens and record is the dispatch's open ledger record (None with
        tracing off), or None when nothing is pending.  Every segment pads to the
        same ``prefill_chunk`` bucket — one compiled program a row rung
        (_chunk_rungs); a final (short) segment's pad positions write junk
        KV past the prompt end,
        which decode overwrites before it ever becomes attendable (the
        standard prefill pad argument).
        """
        limit = self.ecfg.prefill_rows
        if max_rows is not None:
            limit = min(limit, max_rows)
        if not self._segmented or limit <= 0:
            return None
        chunk = self.ecfg.prefill_chunk
        picked: List[Tuple[RunningSlot, int]] = []
        for slot in list(self._segmented):
            run, start = self._segmented[slot]
            if self.scheduler.slots[slot] is not run:  # cancelled
                del self._segmented[slot]
                continue
            picked.append((run, start))
            if len(picked) == limit:
                break
        if not picked:
            return None
        chunk_rows = []
        rows: List[Tuple[RunningSlot, bool]] = []
        n_tokens = 0
        for run, start in picked:
            ids = self._prefill_ids(run)
            seg = ids[start : start + chunk]
            final = start + len(seg) >= len(ids)
            if final:
                del self._segmented[run.slot]
            else:
                self._segmented[run.slot] = (run, start + len(seg))
            chunk_rows.append((run, start, seg, final))
            rows.append((run, final))
            n_tokens += len(seg)
        t_dispatch = time.monotonic()
        first_lp = self._dispatch_chunk_rows(chunk_rows, chunk)
        global_metrics.inc("engine_prefill_segments_total", len(rows))
        if (self._ring or self._snapshots is not None) \
                and self._prefix is not None:
            # a ring holds this segment's blocks now and not after the next
            # one, and a slot's recurrent state is the state at this
            # segment's end only until the next: save them here, in device
            # order behind the segment (the final segment's are saved where
            # the run finishes)
            mid = [(run, start + len(seg))
                   for run, start, seg, final in chunk_rows if not final]
            if mid:
                self._prefix_insert([run for run, _n in mid],
                                    [n for _run, n in mid])
        return rows, first_lp, t_dispatch, n_tokens, self._last_dispatch

    async def _offload(self, loop, fn, *args):
        """The loop's ``run_in_executor``: one call on the XLA executor
        thread, its own wall and the loop's wait beyond it summed into the
        running iteration's split (``exec_ms`` / ``lag_ms``).  For what the
        loop task awaits inside an iteration only: a call from another task
        would book its time to an iteration it is no part of."""
        return await self._split.call(loop, self._executor, fn, *args)

    def _fetch(self, dev):
        """Executor thread: a dispatch's results to the host, blocking
        until the device has them: the iteration's ``wait_ms``."""
        began = time.monotonic()
        out = jax.tree.map(np.asarray, jax.device_get(dev))
        self._split.fetched(began)
        return out

    async def _finish_segments(self, loop, seg) -> None:
        """Fetch a segment dispatch's sampled block; activate final rows."""
        rows, first_dev, t_dispatch, n_tokens, rec = seg
        firsts, lp, _plp = await self._offload(loop, self._fetch, first_dev)
        # REAL segment tokens (pad rows and a final short segment's pad
        # positions excluded): inflating the denominator would deflate
        # the per-token estimate and underprice every page for the
        # cost-aware eviction policy.
        self._note_prefill_cost(
            n_tokens, (time.monotonic() - t_dispatch) * 1000.0,
        )
        self._close_prefill_dispatch(rec)
        inserts: List[RunningSlot] = []
        for i, ((run, final), first) in enumerate(
            zip(rows, firsts[: len(rows)])
        ):
            if not final or self.scheduler.slots[run.slot] is not run:
                continue
            self._admit_one(run)
            if not self._block:  # (its first tokens: the first decode pass)
                lp_row = (None if lp is None
                          else (lp[0][i], lp[1][i], lp[2][i]))
                self._account_token(run.slot, int(first), lp_row)
            if self._prefix is not None:
                inserts.append(run)
        if inserts:
            await self._offload(loop, self._prefix_insert, inserts)
            self._release_pages_for(inserts)

    def _trace_burst(self, rec: Optional[_Dispatch]) -> None:
        """Close a decode burst's dispatch record: dispatch -> fetched
        block processed.  Overlapping by construction (burst n+1
        dispatches before burst n is fetched) — the Chrome view shows the
        pipelining directly.  ``rec`` is None when the burst was dispatched
        with tracing off."""
        self._drain_moe(rec)
        if rec is None:
            return
        if self._block:
            rec.attrs.update(self._blk_burst_attrs)
        global_tracer.add_span(
            "engine.decode_burst", trace_id=None, track="engine-loop",
            t0=rec.t0, attrs=rec.attrs,
        )

    def _fence(self, knob: str, off, reason: str) -> None:
        """Auto-disable ``knob`` and RECORD it (ISSUE 14): the fence lands
        in ``config_fences`` — surfaced by /healthz's ``config`` section
        and the proxy's federated view — instead of existing only as a
        startup log line an operator has to grep for."""
        log.warning("%s disabled: %s", knob, reason)
        self.config_fences.append({"knob": knob, "reason": reason})
        self.ecfg = dc_replace(self.ecfg, **{knob: off})

    def _reserve_pages(self, req: GenRequest) -> None:
        """Scheduler admission hook (ISSUE 14): reserve pool pages for the
        request's prompt insert, evicting cost-aware under pressure NOW —
        at admission — rather than thrashing the pool mid-wave.  Pure host
        work (chain hashing + index bookkeeping).  The grant is advisory
        accounting, not strict ownership; what the leak gate pins is that
        every grant is RELEASED — after the insert lands, or in
        generate()'s finally on any death path."""
        if self._prefix is None:
            return
        need = len(self._prefix.missing(req.prompt_ids))
        if need <= 0:
            return
        began, before = time.monotonic(), self._prefix.evictions
        granted = self._prefix.reserve(need)
        if self._prefix.evictions != before:
            self._split.evicted(began, self._prefix.evictions - before)
        if granted:
            self._page_reserved[req.request_id] = granted

    def _release_pages(self, rid: int) -> None:
        """EVENT-LOOP THREAD ONLY: every release site — generate()'s
        finally and the post-insert releases after the executor calls
        return — runs on the loop, so the reserved_pages counter's
        read-modify-write never interleaves across threads (a concurrent
        executor-side release could lose an update and wedge the
        loadgen leak gate's pages_reserved==0 check)."""
        n = self._page_reserved.pop(rid, None)
        if n and self._prefix is not None:
            self._prefix.release(n)

    def _release_pages_for(self, runs: List[RunningSlot]) -> None:
        """Release the admission grants of runs whose prompt insert just
        landed (loop thread, after the executor insert call returned)."""
        for run in runs:
            self._release_pages(run.request.request_id)

    def _note_prefill_cost(self, tokens: int, wall_ms: float) -> None:
        """Per-token prefill-ms EMA (executor thread or loop; plain float
        assignment, single logical writer per sample): the live estimate
        cost-aware eviction weighs pool pages with — a page's recompute
        cost is its full-prefix token count times this."""
        if tokens <= 0 or wall_ms <= 0:
            return
        per = wall_ms / tokens
        ema = self._prefill_ms_per_token
        self._prefill_ms_per_token = per if ema <= 0 else (
            0.8 * ema + 0.2 * per
        )

    def _conv_insert(self, pending: List[Tuple[int, List[int]]]) -> None:
        """Save finished conversations' full-page KV — prompt AND generated
        tokens — into the pool (executor thread, end of the iteration that
        evicted them, so no new admission can have re-prefilled the slot).
        One batched copy_out per prefill_rows sub-batch, exactly the
        prompt-insert path's dispatch discipline (TC07)."""
        from p2p_llm_tunnel_tpu.engine.prefix_cache import (
            pad_rows,
            plan_inserts,
        )

        # (the carry runs at most two bursts ahead of the host, and the
        # stream's last token was never fed back)
        pending = self._ring_live(pending, lead=2 * self.ecfg.decode_steps)
        entries = plan_inserts(
            self._prefix, pending, conv=True,
            ms_per_token=self._prefill_ms_per_token or 1.0,
        )
        total = sum(len(ids) for _, ids, _ in entries)
        pr = self.ecfg.prefill_rows
        for lo in range(0, len(entries), pr):
            slots, pids, bnos = pad_rows(
                entries[lo : lo + pr], pr, self._prefix_max_blocks,
                scratch=0,
            )
            rec = (self._open_pool_copy("cache_to_pool",
                                        entries[lo : lo + pr])
                   if global_tracer.enabled else None)
            with rec.annotation() if rec else _NO_ANNOTATION:
                self._pool = self._copy_out(  # tunnelcheck: disable=TC07  ONE dispatch per prefill_rows-wide sub-batch, off the TTFT-critical path (end of iteration)
                    self._pool, self.kv_cache, slots, pids, bnos
                )
            self._close_pool_copy(rec)
        if total:
            global_metrics.inc("engine_conv_saved_pages_total", total)
            global_metrics.inc("engine_prefix_saved_blocks_total", total)

    async def _drain_conv_inserts(self, loop) -> None:
        """End-of-iteration conversation-cache drain: batch-insert every
        slot _account_token finished this iteration.  MUST run before the
        next iteration's admission — a re-admitted slot's prefill would
        overwrite the KV these pages are copied from (the copy dispatches
        on the same executor as all writes, so device order is already
        safe; this guards the HOST-side wrong-content hazard)."""
        if not self._conv_pending:
            return
        pending, self._conv_pending = self._conv_pending, []
        self._flight_conv = len(pending)
        await self._offload(loop, self._conv_insert, pending)

    def _memory_exhausted(self) -> bool:
        """The ISSUE 16 degradation verdict: BOTH KV tiers exhausted — the
        HBM pool fully reserved by in-flight admissions AND the host spill
        tier at capacity.  Only meaningful with the tier configured:
        without one, HBM pressure is handled by eviction alone (the
        pre-ISSUE-16 behavior, preserved exactly)."""
        pi = self._prefix
        if pi is None or pi.spill_pages <= 0:
            return False
        return (pi.reserved_pages >= pi.capacity - 1
                and pi.spill_resident >= pi.spill_pages)

    async def _drain_spill_outs(self, loop) -> None:
        """End-of-iteration spill drain (ISSUE 16): when the pool's free
        blocks sink below the low-water mark, page the coldest unshadowed
        pages out to host RAM — a bounded batch per iteration, planned on
        the event loop, bytes copied on the executor, committed back on
        the loop (the _release_pages threading contract).  Shadowed pages
        then MIGRATE on eviction instead of dying, so a capacity-cliff
        herd degrades to host-tier hits rather than full re-prefills."""
        pi = self._prefix
        if pi is None or pi.spill_pages <= 0:
            return
        # Proactive cleaner watermark: wake at half-full, not near-empty.
        # The tier only protects a capacity cliff if pages are shadowed
        # BEFORE the eviction burst arrives; gating on a near-empty free
        # list meant the first over-capacity turn evicted a pool of
        # entirely unshadowed pages (the r16 herd's turn-2 transient:
        # 18/80 matches while the cleaner bootstrapped).  Half-full keeps
        # the genuinely quiet period free of tier traffic while giving
        # the cleaner a full turn of shadowing lead time; once everything
        # cold is shadowed, spill_plan returns empty and the drain is a
        # cheap host-side no-op.
        if pi.free_blocks >= max(self.ecfg.spill_low_water,
                                 pi.capacity // 2):
            return
        # Batch scales with the pool so tier bandwidth tracks churn: a
        # capacity-cliff herd evicts O(pool) pages per turn wave, and a
        # fixed batch would shadow only a sliver of them before they die
        # (the r16 80-client experiment measured exactly that at 8/iter).
        batch = max(_SPILL_BATCH, (pi.capacity - 1) // 8)
        plan = pi.spill_plan(batch)
        if not plan:
            return
        self._spill_inflight += len(plan)
        try:
            results = await self._offload(loop, self._spill_copy_out, plan)
        finally:
            self._spill_inflight -= len(plan)
        committed = 0
        for key, payload, checksum in results:
            if payload is None:
                global_metrics.inc("engine_spill_pageout_failures_total")
                continue
            if pi.note_spilled(key, payload, checksum,
                               dict(self._spill_meta)):
                committed += 1
        if committed:
            global_metrics.inc("engine_spill_pageouts_total", committed)
        self._flight_pageouts = committed

    def _spill_copy_out(self, plan) -> List[Tuple[bytes, Optional[Dict], bytes]]:
        """Executor thread: gather each planned page's leaves to host RAM
        and checksum the TRUE bytes.  Chaos faults (TUNNEL_SPILL_CHAOS)
        draw one schedule entry per page: ``fail`` drops the page-out
        (the page simply stays HBM-only), ``stall`` sleeps this thread
        mid-copy (the event loop keeps serving), ``corrupt`` flips one
        stored byte AFTER checksumming so the page-in verification must
        catch it."""
        from p2p_llm_tunnel_tpu.engine.prefix_cache import page_checksum

        t0 = time.monotonic()
        out: List[Tuple[bytes, Optional[Dict], bytes]] = []
        for key, idx in plan:
            fault, stall_s, pos = None, 0.0, 0
            if self._spill_chaos is not None:
                fault, stall_s, pos = self._spill_chaos.draw("pageout")
            if fault == "stall":
                time.sleep(stall_s)
            elif fault == "fail":
                out.append((key, None, b""))
                continue
            page = self._page_out_op(self._pool, jnp.int32(idx))
            payload = {k: np.asarray(v) for k, v in page.items()}
            checksum = page_checksum(payload)
            if fault == "corrupt":
                leaf = sorted(payload)[0]
                payload[leaf] = np.array(payload[leaf], copy=True)
                flat = payload[leaf].reshape(-1).view(np.uint8)
                flat[pos % flat.size] ^= 0xFF
            out.append((key, payload, checksum))
        global_metrics.observe(
            "engine_spill_pageout_ms", (time.monotonic() - t0) * 1000.0
        )
        return out

    async def _drain_page_ins(self, loop, admitted) -> None:
        """Page-in splice for an ADMITTED wave (ISSUE 16): called from
        both admission paths between ``scheduler.admit()`` and the wave's
        pool matches, so host-tier pages continuing an admitted prompt's
        chain land in the pool just-in-time for the match that runs a few
        calls later.  Earlier drafts ran this once per iteration against
        a PEEK of the waiting queue — at herd scale the peek raced the
        arrival stream (requests admitted this iteration but submitted
        after the peek got no splice, re-prefilled their whole history,
        and their bulk inserts evicted the next wave's chains: the r16
        80-client run measured hundreds of splices/turn converting to
        single-digit matches).  Splicing for exactly the admitted set
        closes the race by construction.  A failed/corrupt page-in aborts
        its slot claim and the request simply re-prefills that tail:
        correctness never depends on the tier."""
        pi = self._prefix
        if pi is None or pi.spill_pages <= 0 or pi.spill_resident == 0:
            return
        wave = [run for run in admitted
                if not getattr(run.request, "echo_logprobs", False)]
        if not wave:
            return
        wanted: List[bytes] = []
        seen: set = set()
        protect: set = set()
        # Demand-limited batch: the cap is the wave's own extension
        # demand (rows × their chain length), because every spliced page
        # replaces a full page of tail re-prefill — strictly cheaper
        # than the compute it displaces.  A fixed 8-page cap starved
        # returning turns at herd scale (r16).
        cap = len(wave) * self._prefix_max_blocks
        for run in wave:
            # Protect EVERY admitted prompt's full chain — resident pages
            # past a gap included — before any claim runs: a claim that
            # evicts a page some neighbor in the same wave will match
            # converts that neighbor's splice into churn.
            protect.update(pi.chain_keys(run.request.prompt_ids))
        # Claims honor `protect`, but the wave's own reserve/insert
        # evictions a few calls later do NOT — and a chain untouched
        # since last turn is precisely the LRU tail they harvest.  MRU-
        # touch the wave's residents so "matched this iteration" beats
        # "cold" in eviction order.
        pi.touch_resident(protect)
        for run in wave:
            ext = pi.spill_extension(run.request.prompt_ids)
            if not ext:
                continue
            for _, key in ext:
                if key not in seen:
                    seen.add(key)
                    wanted.append(key)
            if len(wanted) >= cap:
                break
        if not wanted:
            return
        items = pi.page_in_alloc(wanted[:cap], protect=frozenset(protect))
        if not items:
            return
        self._spill_inflight += len(items)
        try:
            results = await self._offload(loop, self._spill_copy_in, items)
        finally:
            self._spill_inflight -= len(items)
        ok_n = 0
        for key, idx, ok in results:
            if ok:
                pi.commit_page_in(key, idx)
                ok_n += 1
            else:
                pi.abort_page_in(key, idx)
                global_metrics.inc("engine_spill_pagein_failures_total")
        if ok_n:
            global_metrics.inc("engine_spill_pageins_total", ok_n)
        self._flight_pageins = ok_n

    def _spill_copy_in(self, items) -> List[Tuple[bytes, int, bool]]:
        """Executor thread: verify + splice host-tier pages into their
        claimed pool slots.  Every page passes the registered tier-
        boundary pin check (:func:`verify_page_pin` — TC18) AND its
        integrity checksum BEFORE any device write; chaos faults draw one
        schedule entry per page (``fail`` aborts the splice outright,
        ``corrupt`` flips a byte of a COPY so the checksum must refuse
        it, ``stall`` sleeps this thread while the loop keeps serving)."""
        from p2p_llm_tunnel_tpu.engine.prefix_cache import (
            PagePinError,
            page_checksum,
            verify_page_pin,
        )

        t0 = time.monotonic()
        out: List[Tuple[bytes, int, bool]] = []
        for key, idx, page in items:
            payload = page.payload
            fault, pos = None, 0
            if self._spill_chaos is not None:
                fault, stall_s, pos = self._spill_chaos.draw("pagein")
                if fault == "stall":
                    time.sleep(stall_s)
                elif fault == "fail":
                    out.append((key, idx, False))
                    continue
                elif fault == "corrupt":
                    leaf = sorted(page.payload)[0]
                    payload = dict(page.payload)
                    payload[leaf] = np.array(payload[leaf], copy=True)
                    flat = payload[leaf].reshape(-1).view(np.uint8)
                    flat[pos % flat.size] ^= 0xFF
            try:
                payload = verify_page_pin(payload, page.meta,
                                          self._spill_meta)
                if page_checksum(payload) != page.checksum:
                    raise PagePinError("spill page checksum mismatch")
            except PagePinError as e:
                log.warning("page-in dropped (%s); falling back to tail "
                            "re-prefill", e)
                out.append((key, idx, False))
                continue
            self._pool = self._page_in_op(
                self._pool, jnp.int32(idx),
                {k: jnp.asarray(v) for k, v in payload.items()},
            )
            out.append((key, idx, True))
        global_metrics.observe(
            "engine_spill_pagein_ms", (time.monotonic() - t0) * 1000.0
        )
        return out

    # -- disaggregated prefill/decode (ISSUE 20) --------------------------

    def disagg_stats(self) -> Dict[str, object]:
        """/healthz ``disagg`` section: role + transfer tallies.  The
        ``xfer_inflight`` gauge is the loadgen leak-gate invariant —
        nonzero after drain means a transfer's executor hop leaked."""
        pi = self._prefix
        return {
            "role": self.ecfg.role,
            "pages_shipped": self._pages_shipped_total,
            "pages_spliced": (
                pi.wire_spliced if pi is not None else 0
            ),
            "xfer_inflight": self._kv_xfer_inflight,
        }

    async def export_kv_pages(self, prompt_ids) -> Optional[Dict]:
        """Export the prompt's RESIDENT chain-prefix pages for a KV_PAGES
        transfer.  Event loop: walk the contiguous resident prefix
        (capped at MAX_KV_PAGES_PER_XFER; pages are a chain prefix, so a
        truncated export just leaves the receiver more tail to prefill);
        executor: gather bytes, pin self-check, checksum.  Returns
        ``{"meta", "pages", "blobs"}`` or None when nothing is resident —
        the orchestrator then ships nothing and the decode peer prefills
        locally, exactly as if this engine did not exist."""
        pi = self._prefix
        if pi is None or self._page_out_op is None:
            return None
        from p2p_llm_tunnel_tpu.protocol.frames import MAX_KV_PAGES_PER_XFER

        keys = pi.chain_keys(prompt_ids)[:MAX_KV_PAGES_PER_XFER]
        if not keys:
            # Prompt shorter than one full block — nothing poolable, so
            # nothing will EVER be shippable; bail without waiting.
            return None
        # The pool insert runs off the TTFT-critical path: the engine loop
        # emits the first token (ending a max_new_tokens=1 probe stream)
        # and only THEN dispatches _prefix_insert on the executor.  An
        # export fired the moment the probe stream ends therefore races
        # the insert by one loop tick — poll briefly for the chain head
        # to land before declaring the pool empty.
        deadline = time.monotonic() + 2.0
        while True:
            pairs: List[Tuple[bytes, int]] = []
            for key in keys:
                idx = pi.id_of(key)
                if idx is None:
                    # The receiver's match() walks from the root, so only
                    # the contiguous resident prefix is worth shipping.
                    break
                pairs.append((key, idx))
            if pairs or time.monotonic() >= deadline:
                break
            await asyncio.sleep(0.02)
        if not pairs:
            return None
        # MRU-touch what we are about to gather so a concurrent insert
        # wave prefers genuinely cold victims (the page-in wave idiom).
        pi.touch_resident([k for k, _ in pairs])
        loop = asyncio.get_running_loop()
        self._kv_xfer_inflight += 1
        global_metrics.set_gauge(
            "engine_kv_xfer_inflight", self._kv_xfer_inflight
        )
        try:
            result = await loop.run_in_executor(
                self._executor, self._export_copy_out, pairs
            )
        finally:
            self._kv_xfer_inflight -= 1
            global_metrics.set_gauge(
                "engine_kv_xfer_inflight", self._kv_xfer_inflight
            )
        n = len(result["pages"])
        total = sum(len(b) for b in result["blobs"])
        self._pages_shipped_total += n
        self._pages_shipped_pending += n
        global_metrics.inc("engine_pages_shipped_total", n)
        global_metrics.inc("engine_page_xfer_bytes_total", total)
        return result

    def _export_copy_out(self, pairs) -> Dict:
        """Executor thread: gather each resident page's leaves to host RAM
        for the wire.  Every payload is re-pinned through
        :func:`verify_page_pin` against this engine's OWN meta before its
        bytes reach the frame codec — the registered tier-boundary idiom
        (TC18/TC20), so an unpinned page can never reach the wire — then
        checksummed so the receiver verifies integrity end to end.  Blob
        layout: leaves in sorted-name order, contiguous C-order bytes
        (the KvPagesManifest contract)."""
        from p2p_llm_tunnel_tpu.engine.prefix_cache import (
            page_checksum,
            verify_page_pin,
        )

        t0 = time.monotonic()
        pages: List[Dict] = []
        blobs: List[bytes] = []
        for key, idx in pairs:
            page = self._page_out_op(self._pool, jnp.int32(idx))
            payload = {k: np.asarray(v) for k, v in page.items()}
            payload = verify_page_pin(
                payload, self._spill_meta, self._spill_meta
            )
            checksum = page_checksum(payload)
            blob = b"".join(
                np.ascontiguousarray(payload[name]).tobytes()
                for name in sorted(payload)
            )
            pages.append({
                "key": key.hex(),
                "checksum": checksum.hex(),
                "nbytes": len(blob),
                "leaves": {
                    name: {
                        "shape": list(arr.shape),
                        "dtype": str(arr.dtype),
                    }
                    for name, arr in payload.items()
                },
            })
            blobs.append(blob)
        global_metrics.observe(
            "engine_page_export_ms", (time.monotonic() - t0) * 1000.0
        )
        return {"meta": dict(self._spill_meta), "pages": pages,
                "blobs": blobs}

    @staticmethod
    def _wire_dtype(name: str):
        """np.dtype for a wire leaf spec, including the ml_dtypes names
        (bfloat16) numpy cannot resolve from a plain string."""
        try:
            return np.dtype(name)
        except TypeError:
            import ml_dtypes

            return np.dtype(getattr(ml_dtypes, name))

    @classmethod
    def _blob_to_payload(cls, spec: Dict, blob: bytes) -> Dict:
        """Reslice one page's wire bytes into per-leaf arrays: sorted leaf
        names, contiguous C-order — the export layout.  Length-checked so
        a short or padded blob fails loudly here, not as a silent
        misaligned splice."""
        payload: Dict[str, np.ndarray] = {}
        off = 0
        leaves = dict(spec["leaves"])
        for name in sorted(leaves):
            shape = [int(d) for d in leaves[name]["shape"]]
            dtype = cls._wire_dtype(str(leaves[name]["dtype"]))
            count = int(np.prod(shape)) if shape else 1
            payload[name] = np.frombuffer(
                blob, dtype=dtype, count=count, offset=off
            ).reshape(shape)
            off += count * dtype.itemsize
        if off != len(blob):
            raise ValueError(
                f"page blob carries {len(blob)} bytes, leaves need {off}"
            )
        return payload

    async def import_kv_pages(self, meta: Dict, pages: List[Dict],
                              blobs: List[bytes]) -> int:
        """Splice a KV_PAGES transfer into the pool: the manifest's pin
        meta is checked against this pool FIRST (typed refusal before any
        allocation), then each page rides the spill tier's two-phase
        path — claim on the loop (``page_in_alloc`` with the wire pages
        offered), ``verify_page_pin`` + checksum on the executor
        (``_spill_copy_in``, unchanged), commit/abort back on the loop.
        Returns pages spliced.  Raises PagePinError on a pin mismatch —
        the serve layer answers the typed ``page_pin`` refusal; anything
        milder (allocation pressure, a failed checksum) degrades to fewer
        splices and the request simply re-prefills the difference."""
        from p2p_llm_tunnel_tpu.engine.prefix_cache import (
            PagePinError,
            _SpillPage,
        )

        pi = self._prefix
        if pi is None or self._page_in_op is None:
            raise PagePinError(
                "this engine has no prefix pool to splice into "
                "(prefix_cache off or role fenced)"
            )
        try:
            # One manifest-level check covers every page (shared meta);
            # per-page verify_page_pin still runs in _spill_copy_in.
            for key, val in self._spill_meta.items():
                if meta.get(key) != val:
                    raise PagePinError(
                        f"KV page pin mismatch on {key!r}: transfer "
                        f"carries {meta.get(key)!r}, engine wants {val!r}"
                    )
        except PagePinError:
            global_metrics.inc("engine_page_refusals_total")
            raise
        offered: Dict[bytes, "_SpillPage"] = {}
        order: List[bytes] = []
        for spec, blob in zip(pages, blobs):
            try:
                key = bytes.fromhex(str(spec["key"]))
                checksum = bytes.fromhex(str(spec["checksum"]))
                payload = self._blob_to_payload(spec, blob)
            except (KeyError, TypeError, ValueError) as e:
                global_metrics.inc("engine_page_refusals_total")
                raise PagePinError(f"malformed KV page: {e}") from e
            # Recompute-cost accounting mirrors a local insert: chain
            # depth x the live per-token prefill estimate, so imported
            # conversation pages compete fairly under cost eviction.
            cost = (len(order) + 1) * pi.block * (
                self._prefill_ms_per_token or 1.0
            )
            offered[key] = _SpillPage(
                payload, checksum, dict(meta), cost=cost
            )
            order.append(key)
        if not offered:
            return 0
        items = pi.page_in_alloc(
            order, protect=frozenset(order), offered=offered
        )
        if not items:
            return 0
        loop = asyncio.get_running_loop()
        self._kv_xfer_inflight += 1
        global_metrics.set_gauge(
            "engine_kv_xfer_inflight", self._kv_xfer_inflight
        )
        try:
            results = await loop.run_in_executor(
                self._executor, self._spill_copy_in, items
            )
        finally:
            self._kv_xfer_inflight -= 1
            global_metrics.set_gauge(
                "engine_kv_xfer_inflight", self._kv_xfer_inflight
            )
        ok_n = 0
        for key, idx, ok in results:
            if ok:
                pi.commit_page_in(key, idx, page=offered[key])
                ok_n += 1
            else:
                pi.abort_page_in(key, idx)
        refused = len(items) - ok_n
        if refused:
            global_metrics.inc("engine_page_refusals_total", refused)
        if ok_n:
            self._pages_spliced_pending += ok_n
            global_metrics.inc("engine_pages_spliced_total", ok_n)
        self._publish_prefix_gauges()
        return ok_n

    def _publish_prefix_gauges(self) -> None:
        """Prefix-pool memory accounting (ISSUE 6/14): pages used/free/
        reserved, resident KV bytes, and the eviction + conversation-cache
        counters (delta-inc from the index's internal tallies).  Host
        arithmetic over the index only — no device traffic.

        ISSUE 16 adds the spill-tier gauges and the memory-thrash
        detector: eviction-rate × reuse-distance over a sliding window of
        these publishes (one per non-idle iteration — the flight ring's
        cadence).  A page re-allocated while still in the recent-eviction
        ring has reuse distance > capacity by construction, so a window
        where most evictions are such re-allocations is the pool churning
        without retaining — degrade loudly instead of thrashing."""
        if self._prefix is None:
            return
        used = self._prefix.used_blocks
        global_metrics.set_gauge("engine_prefix_pool_blocks_used", used)
        global_metrics.set_gauge(
            "engine_prefix_pool_blocks_free", self._prefix.free_blocks
        )
        global_metrics.set_gauge(
            "engine_prefix_pool_kv_bytes", used * self._prefix_block_bytes
        )
        global_metrics.set_gauge(
            "engine_prefix_pool_pages_reserved", self._prefix.reserved_pages
        )
        if self._snapshots is not None:
            global_metrics.set_gauge(
                "engine_state_snapshots", len(self._snapshots))
            global_metrics.set_gauge(
                "engine_state_snapshot_bytes",
                len(self._snapshots) * self._state_row_bytes)
        for metric, attr in (
            ("engine_prefix_evictions_total", "evictions"),
            ("engine_conv_hits_total", "conv_hits"),
            ("engine_conv_hit_tokens_total", "conv_hit_tokens"),
        ):
            now = getattr(self._prefix, attr)
            delta = now - self._prefix_published.get(attr, 0)
            if delta > 0:
                global_metrics.inc(metric, delta)
                self._prefix_published[attr] = now
        if self._prefix.spill_pages > 0:
            resident = self._prefix.spill_resident
            global_metrics.set_gauge("engine_spill_pages", resident)
            global_metrics.set_gauge(
                "engine_spill_bytes", resident * self._prefix_block_bytes
            )
            global_metrics.set_gauge(
                "engine_spill_inflight", self._spill_inflight
            )
        self._thrash_tick()

    def _thrash_tick(self) -> None:
        """One thrash-detector step (loop thread, one per gauge publish):
        window the (eviction, recent-realloc) deltas, trip degraded on a
        churn-dominated window, clear on a quiet one."""
        ev = self._prefix.evictions
        re_alloc = self._prefix.thrash_reallocs
        d_ev = ev - self._thrash_last[0]
        d_re = re_alloc - self._thrash_last[1]
        self._thrash_last = (ev, re_alloc)
        if d_ev or d_re or self._thrash_window:
            self._thrash_window.append((d_ev, d_re))
        window_re = sum(r for _, r in self._thrash_window)
        window_ev = sum(e for e, _ in self._thrash_window)
        threshold = max(8, self._prefix.capacity - 1)
        if (window_re >= threshold and window_ev >= threshold
                and not self.degraded):
            log.error(
                "memory-thrash detector: %d re-allocations of recently "
                "evicted pages across %d evictions in the detector "
                "window; marking engine degraded (reason=memory)",
                window_re, window_ev,
            )
            global_metrics.inc("engine_thrash_trips_total")
            self.degraded = True
            self.degraded_reason = "memory"
            global_metrics.set_gauge("engine_degraded", 1.0)
            global_metrics.set_info("engine_degraded_reason", "memory")
            # Postmortem AT the trip: the flight tail shows the
            # eviction/page-in churn that tripped it, and fabric health
            # routing (proxy degraded-peer handling) steers around this
            # peer while the reason stands.
            global_blackbox.capture(
                "memory", attribution="prefix_pool_thrash"
            )
            self._thrash_window.clear()
        elif (self.degraded and self.degraded_reason == "memory"
                and window_re == 0
                and self._prefix.free_blocks >= self.ecfg.spill_low_water):
            # Hysteresis: a full window with zero re-allocations AND free
            # headroom above the low-water mark — pressure actually
            # subsided, not just paused between admission waves.
            log.info("memory-thrash detector: pressure subsided; "
                     "clearing degraded")
            self.degraded = False
            self.degraded_reason = ""
            global_metrics.set_gauge("engine_degraded", 0.0)
            global_metrics.set_info("engine_degraded_reason", "")

    async def _process_burst(self, outs, assign: List) -> None:
        """Account one fetched token block [R, k] against current occupants.

        ``assign`` snapshots which request held each row at dispatch time:
        rows that were freed or re-admitted since (pipelining lag) carry
        junk tokens for the *old* occupant and are skipped.
        """
        if self._block:
            return await self._process_block_burst(outs, assign)
        sampled, lp_out = outs
        for col in range(sampled.shape[1]):
            for i in np.nonzero(self._active_mask)[0]:
                run = self.scheduler.slots[i] if i < self.ecfg.num_slots else None
                if run is None:  # cancelled/evicted since dispatch
                    self._active_mask[i] = False
                    continue
                if run.request.request_id != assign[i]:
                    continue  # re-admitted: its tokens come from the next burst
                lp_row = None
                if lp_out is not None:
                    lp, top_ids, top_lps = lp_out
                    lp_row = (lp[i, col], top_ids[i, col], top_lps[i, col])
                self._account_token(int(i), int(sampled[i, col]), lp_row)
            # Yield so this column's tokens flush to consumers before the
            # next (keeps SSE pacing smooth within a burst).
            await asyncio.sleep(0)

    def _flight_record(self, plain_rows: int, seg_rows: int,
                       cold0: int) -> None:
        """One flight-recorder row per non-idle loop iteration (ISSUE 12).

        Pure host bookkeeping: reads the scratch the iteration's own
        methods stashed (_last_mux/_last_burst/_flight_admitted) plus
        cheap scheduler state — no device traffic, no allocation beyond
        the record dict, so the ring can stay always-on.  Where the wall
        went (ISSUE 57) is the iteration's split's to say."""
        slots = self.scheduler.slots
        mux = self._last_mux
        # Disagg transfers run off the iteration rhythm (API/serve-driven
        # on the loop thread): drain their accumulators into THIS row so
        # every shipped/spliced page lands in exactly one iteration.
        shipped, self._pages_shipped_pending = self._pages_shipped_pending, 0
        spliced, self._pages_spliced_pending = self._pages_spliced_pending, 0
        backlog = mux.get("backlog_rows")
        if backlog is None:
            # Non-mux iterations: the row-count proxy (no controller ran).
            backlog = (len(self._segmented) + len(self._pending_plain)
                       + len(self._prefix_waiters))
        global_flight.record_iteration(
            queue_depth=self.scheduler.queue_depth,
            backlog_rows=int(backlog),
            min_slack_s=mux.get("min_slack_s"),
            budget_tokens=int(mux.get("budget_tokens", 0) or 0),
            admitted=self._flight_admitted,
            prefill_rows=plain_rows + seg_rows,
            decode_steps=self._last_burst[0],
            decode_rows=self._last_burst[1],
            active_slots=sum(1 for s in slots if s is not None),
            tenants=len({
                run.request.tenant for run in slots if run is not None
            }),
            waiters=len(self._prefix_waiters),
            prefix_blocks_used=(
                self._prefix.used_blocks if self._prefix is not None else 0
            ),
            prefix_pages_reserved=(
                self._prefix.reserved_pages if self._prefix is not None
                else 0
            ),
            conv_inserted=self._flight_conv,
            spill_pages=(
                self._prefix.spill_resident if self._prefix is not None
                else 0
            ),
            spill_pageouts=self._flight_pageouts,
            spill_pageins=self._flight_pageins,
            pages_shipped=shipped,
            pages_spliced=spliced,
            cold_compiles=global_compile_watch.cold_total - cold0,
            # Speculation attribution (ISSUE 17): proposed/accepted verify
            # tokens and the burst width this iteration dispatched, so a
            # flight tail shows whether decode wall bought spec tokens.
            spec_proposed=self._flight_spec[0],
            spec_accepted=self._flight_spec[1],
            spec_k=self._flight_spec[2],
            # Detached-stream count (ISSUE 13): how many of this
            # iteration's generations are filling replay journals with no
            # channel attached — a postmortem's flight tail shows whether
            # the engine was working for parked clients when it wedged.
            streams_detached=int(
                global_metrics.gauge("serve_streams_detached")
            ),
            **self._split.fields(),
        )
        global_gc.publish()

    async def _loop(self) -> None:
        loop = asyncio.get_running_loop()
        log.info(
            "engine loop started: model=%s slots=%d max_seq=%d decode_steps=%d",
            self.mcfg.name, self.ecfg.num_slots, self.ecfg.max_seq,
            self.ecfg.decode_steps,
        )
        sums = ("engine_flight_iterations_total",
                "engine_loop_host_seconds_total",
                "engine_loop_wait_seconds_total",
                "engine_loop_lag_seconds_total",
                "process_gc_pause_seconds_total")
        sums0 = [global_metrics.counter(name) for name in sums]
        # Crash containment: a dispatch exception must surface loudly
        # and unblock every consumer — without this, one bad program
        # (found the hard way: a shape bug in a new sampler input)
        # strands all generate() callers on a queue nobody will feed.
        try:
            # (sampled device array, request-id snapshot, dispatch record)
            in_flight = None
            while self._running:
                if self.scheduler.idle and in_flight is None:
                    # Idle time is not a stall: keep the watchdog anchored
                    # to "now" so the next request's budget starts fresh.
                    # Idle parks record NOTHING — the flight ring holds
                    # iterations that did work, so its tail is dense with
                    # decisions when a postmortem reads it.
                    global_flight.set_phase("idle")
                    global_gc.publish()
                    self._drain_moe(all_of_it=True)
                    self._last_progress = time.monotonic()
                    self._wake.clear()
                    try:
                        await asyncio.wait_for(self._wake.wait(), timeout=0.5)
                    except asyncio.TimeoutError:
                        continue
                    # Woken by a submission: its budget starts now, not at
                    # the idle tick up to half a second (or one blocked
                    # event loop) ago.
                    self._last_progress = time.monotonic()
                    continue

                # Flight recorder (ISSUE 12): per-iteration scratch reset +
                # phase markers.  A wedged dispatch leaves the phase at the
                # stalled step — the watchdog's attribution.  The split
                # (ISSUE 57) times the parts between the markers.
                split = self._split = IterationSplit(global_flight)
                self._loop_iter += 1
                self._flight_admitted = 0  # tunnelcheck: disable=TC13  single-writer contract: only THIS loop task and the admission helpers it awaits touch the per-iteration flight scratch; the reset-here/accumulate-in-_note_admission/read-at-record sequence cannot interleave with another writer
                self._flight_conv = 0
                self._flight_pageouts = 0
                self._flight_pageins = 0
                self._last_burst = (0, 0)
                self._last_mux = {}
                self._flight_spec = (0, 0, 0)
                cold0 = global_compile_watch.cold_total
                plain_rows = 0
                split.enter("admit")
                self._expire_deadlines()
                # The page-in splice (ISSUE 16) runs INSIDE admission —
                # between scheduler.admit() and the wave's matches — for
                # exactly the admitted set; see _drain_page_ins.
                if self.ecfg.mux:
                    await self._admit_mux(loop)
                    await self._mux_wake(loop)
                else:
                    # The legacy admission path prefills the whole wave
                    # inline, so its rows count as this iteration's
                    # prefill work.
                    await self._admit_pending(loop)
                    plain_rows += self._flight_admitted

                global_metrics.set_gauge("engine_batch_occupancy", self.scheduler.occupancy)
                global_metrics.set_gauge("engine_queue_depth", self.scheduler.queue_depth)
                self._publish_prefix_gauges()
                split.enter("prefill_dispatch")

                # Prefill work for this iteration, dispatched before the
                # decode burst.  Non-mux: one prefill_rows-wide segment
                # sub-batch — the pre-ISSUE-5 interleave that bounds how
                # long one big prompt can stall the batch.  Mux: the
                # controller's budgeted slice — pending whole-prompt rows
                # and/or segment rows up to this iteration's token budget.
                segs: List = []
                if self.ecfg.mux:
                    rows_budget = self._mux_budget()
                    if self._pending_plain and rows_budget > 0:
                        take = min(rows_budget, len(self._pending_plain))
                        batch = [
                            r for r in self._pending_plain[:take]
                            if self.scheduler.slots[r.slot] is r
                        ]
                        del self._pending_plain[:take]
                        if batch:
                            await self._dispatch_plain_waves(loop, batch)
                            plain_rows += len(batch)
                        rows_budget -= take
                    # The budget may span several prefill_rows-wide
                    # sub-batches: dispatch them back-to-back (the device
                    # queues them; fetches pipeline in _finish_segments),
                    # so a drain budget costs ONE iteration, not one
                    # iteration per sub-batch.
                    while self._segmented and rows_budget > 0:
                        seg = await self._offload(  # tunnelcheck: disable=TC07  one dispatch per prefill_rows-wide sub-batch of the iteration budget, back-to-back
                            loop, self._dispatch_segments, rows_budget,
                        )
                        if seg is None:
                            break
                        segs.append(seg)
                        rows_budget -= len(seg[0])
                elif self._segmented:
                    seg = await self._offload(loop, self._dispatch_segments)
                    if seg is not None:
                        segs.append(seg)
                seg_rows = sum(len(s[0]) for s in segs)

                if self._spec_usable() and any(self._active_mask):
                    # Speculative step (opt-in): synchronous dispatch+fetch
                    # — counts must be read before consumers can be fed, so
                    # there is no carry to pipeline.  Drain the pipelined
                    # plain burst first (mode switch mid-stream).
                    if in_flight is not None:
                        split.enter("decode_fetch")
                        outs_dev, assign, burst_rec = in_flight
                        outs = await self._offload(
                            loop, self._fetch, outs_dev)
                        split.enter("process")
                        await self._process_burst(outs, assign)
                        self._trace_burst(burst_rec)
                        in_flight = None
                    split.enter("decode_dispatch")
                    spec_out, spec_assign = await self._offload(
                        loop, self._dispatch_spec)
                    split.enter("process")
                    await self._process_spec(spec_out, spec_assign)
                    split.enter("segments")
                    for seg in segs:
                        await self._finish_segments(loop, seg)
                    split.enter("drain")
                    await self._drain_conv_inserts(loop)
                    await self._drain_spill_outs(loop)
                    self._flight_record(plain_rows, seg_rows, cold0)
                    continue

                # Pipeline: dispatch burst n (returns immediately; carry stays
                # on device), THEN fetch+process burst n-1 — the round trip of
                # the fetch overlaps with burst n computing.  Dispatch runs on
                # the XLA executor thread: normally ~1 ms, but a first-hit
                # (view, steps) compile takes tens of seconds, and on the event
                # loop that would stall the tunnel past the transport's 15 s
                # dead-peer timeout.  warmup() precompiles every variant; this
                # is the belt to that suspender for consumers that skip it.
                current = None
                split.enter("decode_dispatch")
                if any(self._active_mask):
                    outs_dev0, assign0 = await self._offload(
                        loop, self._dispatch_decode)
                    current = (outs_dev0, assign0, self._last_dispatch)
                split.enter("decode_fetch")
                if in_flight is not None:
                    outs_dev, assign, burst_rec = in_flight
                    # How long the host waited for the previous burst after
                    # dispatching the next one is the record's wait_ms (0 ≈
                    # the RTT is fully hidden by pipelining).
                    outs = await self._offload(loop, self._fetch, outs_dev)
                    split.enter("process")
                    await self._process_burst(outs, assign)
                    self._trace_burst(burst_rec)
                split.enter("segments")
                for seg in segs:
                    # Fetched after the decode work above, so each segment
                    # sub-batch's device→host RTT rides under real compute
                    # (and under its successor sub-batches').
                    await self._finish_segments(loop, seg)
                # Conversation-cache inserts for slots that finished this
                # iteration — BEFORE the next admission can re-prefill
                # them (ISSUE 14; off the TTFT-critical path by position).
                split.enter("drain")
                await self._drain_conv_inserts(loop)
                # Spill page-outs LAST (ISSUE 16): cold pages copied to
                # the host tier after all of this iteration's serving
                # dispatches are queued — same off-the-critical-path
                # position as the conversation drain.
                await self._drain_spill_outs(loop)
                in_flight = current
                self._flight_record(plain_rows, seg_rows, cold0)
        except Exception:
            log.exception(
                "engine loop crashed; failing %d in-flight requests",
                len(self._requests),
            )
            # Postmortem black box (ISSUE 12): a fatal engine error is the
            # canonical "what just happened" moment — snapshot before the
            # consumers are failed, attributing the phase that raised.
            global_blackbox.capture(
                "crash", attribution=global_flight.current_phase(),
            )
            self._running = False
            self._crashed = True  # generate() rejects new submissions
            for state in list(self._requests.values()):
                state.queue.put_nowait(_CRASHED)
            raise
        finally:
            # However it stops (stop(), a crash, or a serve process whose
            # asyncio.run cancels the task on its way out): the run's log
            # keeps the loop's totals (ISSUE 57).
            global_gc.publish()
            grown = [global_metrics.counter(name) - was
                     for name, was in zip(sums, sums0)]
            log.info(
                "engine loop stopped: %d iterations recorded, host %.3f s, "
                "waiting for the chip %.3f s, event-loop lag %.3f s, the "
                "collector's pauses %.3f s (of the process, parks included)",
                *grown,
            )


def _attention_section(m) -> Dict[str, object]:
    """/healthz ``config.model.attention`` of a window-ring model
    (models/swa.py): what its two kinds of attention layer differ in beside
    their planes; nothing for any other model.  (At the file's end: the
    lines above it are the parent's, ROADMAP Speed 6.)"""
    if m.attn_pattern is None:
        return {}
    kinds = ("full", "window")
    return {"attention": {
        "query_heads": {k: m.heads_of(k) for k in kinds},
        "rotary_columns": {k: m.rotary_of(k) or m.head_dim for k in kinds},
        "gate": m.attn_gate or None,
        "qk_norm": m.qk_norm,
    }}


def _window_rows_fetched(eng, first, steps) -> int:
    """Positions ONE window layer's decode read fetches over a burst, from
    the rows' positions on the host: each live row at ``first[i]`` takes
    ``steps[i]`` steps, and a step fetches the whole ring under the einsum,
    the blocks of the ring's work list under the rows kernel
    (``ops.pallas_decode_attention.ring_run``; a whole block counts, also
    where the row's last is fetched in part).  A prefill dispatch gathers a
    window by position and fetches what it needs.  (At the file's end, as
    ``_attention_section`` is.)"""
    from p2p_llm_tunnel_tpu.models.swa import ring_kernel_decline

    ring = eng._ring
    if not (eng._decode_reads_rows()
            and ring_kernel_decline(eng.mcfg, ring) is None):
        return int(steps.sum()) * ring
    from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import (
        ring_run,
        rows_block,
    )

    block = rows_block(ring, eng.mcfg.kv_heads_of("window"))
    step = np.arange(int(steps.max(initial=0)))[None, :]
    _, blocks = ring_run(first[:, None] + step, ring, block,
                         int(eng.mcfg.sliding_window))
    return int((blocks * (step < steps[:, None])).sum()) * block
