"""Model architecture configs.

Covers the BASELINE.md graduation ladder: a tiny CPU-testable config, the
Gemma-2 2B and Llama-3 8B single-chip targets, and Llama-3 70B for
tensor-parallel v5e-8.  Architectural knobs cover both families:

- llama-style: RMSNorm(w), SwiGLU, GQA, rope, untied head (8B/70B)
- gemma2-style: RMSNorm(1+w), GeGLU, pre+post norms, logit/attn softcap,
  alternating sliding-window attention, tied embeddings
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple


@dataclass(frozen=True)
class YarnRope:
    """``deepseek_yarn`` rope scaling as a published config states it."""
    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    # ``rope_type`` ``yarn`` on a plain (not latent) rope states the number
    # that multiplies sin and cos outright (models/swa.py's full layers); 0:
    # none stated.
    attention_factor: float = 0.0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    ffn_dim: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    act: str = "silu"  # "silu" (llama SwiGLU) | "gelu" (gemma GeGLU)
    tie_embeddings: bool = False
    # gemma2-specific behaviors (all inert when at defaults):
    post_norms: bool = False  # extra RMSNorm after attn/mlp blocks
    attn_softcap: Optional[float] = None
    logit_softcap: Optional[float] = None
    sliding_window: Optional[int] = None
    # "alternating" (gemma-2: even layers local) or "all" (mistral: every
    # layer windowed); ignored when sliding_window is None.
    window_pattern: str = "alternating"
    embed_scale: bool = False  # multiply embeddings by sqrt(dim)
    # qwen2-style additive bias on the Q/K/V projections only (o_proj and
    # MLP stay bias-free); adds bq/bk/bv leaves to the block pytree.
    attn_bias: bool = False
    # attention score scale; None → 1/sqrt(head_dim)
    query_scale: Optional[float] = None
    # Use the Pallas flash kernel for prefill attention when the backend is
    # TPU and shapes tile (T%128==0, head_dim%128==0).  Under a tp mesh the
    # kernel runs per head-shard via shard_map (GSPMD does not
    # auto-partition pallas_call).  Likewise decode's default read of the
    # plain bf16 cache, the rows kernel (transformer.decode_attention_branch).
    # Off: the einsum everywhere, the reference the kernels are held against.
    flash: bool = True
    # Run the flash kernel in Pallas interpret mode even off-TPU — CPU-mesh
    # tests of the shard_map'd kernel path set this.
    flash_interpret: bool = False
    # Assume the TPU backend when gating the Pallas decode kernels, WITHOUT
    # interpret mode: for cross-platform LOWERING only (tests lower and
    # compile the real TPU program from a CPU host).  A program traced with
    # this set must never execute off-TPU.
    flash_force: bool = False
    # W8A8: quantize activations dynamically (per-token int8) so QTensor
    # matmuls run as native int8×int8 MXU dots — set by the engine when
    # EngineConfig.quant == "w8a8".  Inert for non-quantized params.
    act_quant: bool = False
    # Sequence-parallel strategy when the mesh has sp > 1:
    # "ring"    — K/V blocks rotate via ppermute (bandwidth-optimal on the
    #             ICI ring; no sliding-window support)
    # "ulysses" — one all_to_all swaps the sequence shard for a head shard,
    #             plain attention runs over the full context (windows and
    #             pad masks work; needs H and K divisible by sp)
    sp_mode: str = "ring"
    # Mixture-of-experts (mixtral-style): 0 = dense MLP.  With n_experts
    # set, each block's MLP becomes a router + per-expert SwiGLU, top-k
    # routed with renormalized weights; expert weights shard over an
    # ``ep`` mesh axis (expert parallelism — models/moe.py).
    n_experts: int = 0
    n_experts_per_tok: int = 2
    # The routed layer beyond mixtral's (models/moe.py).  ``n_experts`` is
    # always the PUBLISHED count, the router's width.
    moe_ffn_dim: int = 0  # an expert's width; 0 = ffn_dim
    n_shared_experts: int = 0  # SwiGLUs of moe_ffn_dim every token takes
    first_dense_layers: int = 0  # leading layers with a plain ffn_dim MLP
    # "softmax" (mixtral: softmax over all experts, top-k, renormalised) |
    # "sigmoid" (aux-free-bias family: sigmoid scores, the top-k of score +
    # selection bias, weights the unbiased scores over their sum).
    router_score: str = "softmax"
    router_bias: bool = False  # the selection bias ``router_bias`` [L, E]
    routed_scale: float = 1.0  # routed_scaling_factor
    # The share one process holds of a layer that ``layer_chips`` chips
    # divide among them: experts [i * E/n, (i + 1) * E/n) and as many rows
    # of the vocabulary.  ``vocab_size`` counts the rows HELD (the engine
    # sets it from the tokenizer); the published table has layer_chips
    # times as many.  One chip runs its layer without the exchange: tokens
    # routed to absent experts add nothing.
    layer_chips: int = 1
    chip_index: int = 0
    # Layers of the published model when ``n_layers`` is one pipeline
    # stage's (0: the whole model is here).
    published_layers: int = 0
    # Latent attention (MLA): kv_lora_rank > 0 replaces the K/V heads by
    # one cached row of kv_lora_rank + qk_rope_head_dim values a token
    # (models/mla.py).  ``head_dim`` is then that row's width and
    # ``n_kv_heads`` 1.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # RMSNorm(w) over each head's query before rope (latent attention:
    # models/mla.py); in the KV-head family over each query AND key head's
    # columns, weights ``q_norm`` / ``k_norm`` [L, head_dim] (Qwen3's); in
    # the pattern family (models/ssm_moe.py) over the WHOLE width of the
    # query and of the key before the heads are split, weights [La, H * D]
    # and [La, K * D] (OLMo's).
    qk_norm: bool = False
    yarn: Optional[YarnRope] = None
    # Window and full attention layers mixed by a pattern given as data
    # (models/swa.py): ``attn_pattern[l]`` is 1 where layer ``l`` attends to
    # the last ``sliding_window`` positions and 0 where it attends to all.
    # The two kinds differ in more than the mask: ``n_kv_heads`` /
    # ``rope_theta`` are the full layers', ``window_kv_heads`` /
    # ``window_rope_theta`` the window layers'.  Keys and queries are
    # ``head_dim`` wide, values ``v_head_dim``; the leading ``rotary_dim``
    # columns of a head are roped (0: all); values are scaled by
    # ``value_scale`` as they are projected; under ``window_sink`` a learned
    # scalar a head joins the window layers' softmax denominator.  A window
    # layer caches a ring of ``ring_positions`` positions a slot, not
    # ``max_seq`` (0: the engine sizes it, ``ring_default``).
    attn_pattern: Optional[Tuple[int, ...]] = None
    window_kv_heads: int = 0
    window_rope_theta: float = 0.0
    rotary_dim: int = 0
    value_scale: float = 1.0
    window_sink: bool = False
    ring_positions: int = 0
    # What else the two kinds may differ in (Laguna's; each 0 or empty where
    # a model has one answer for both kinds): a window layer's query heads
    # (``heads_of``) and rotary columns (``rotary_of``); under ``yarn`` the
    # FULL layers' rotary columns turn at yarn's frequencies and their sin
    # and cos are multiplied by its ``attention_factor``, the window layers
    # rope plainly; ``attn_gate`` ``"per-head"``: ``sigmoid(W_g h)``, one
    # number a token and query head, multiplies that head's weighted sum
    # before ``W_o``; ``qk_norm`` (above) is in this family an RMSNorm over
    # each query and key head's columns before the rope.
    window_heads: int = 0
    window_rotary_dim: int = 0
    attn_gate: str = ""
    # Generation by masked denoising over blocks (models/block_decode.py):
    # positions are filled ``block_length`` at a time (0: one token a
    # sequence and step).  A block takes ``denoise_steps`` passes that each
    # decide ``block_length / denoise_steps`` offsets in order (sequential
    # remasking; the offsets not yet decided hold ``mask_token_id``); the
    # clean block's K/V are written by the first pass on the block after
    # it, which forwards both.  Attention is block-causal: position ``i``
    # sees ``j`` where ``j // block_length <= i // block_length``.
    block_length: int = 0
    denoise_steps: int = 0
    mask_token_id: int = 0
    # What lies between the products stays float32 and is rounded to the
    # activations' type once, where the next product takes it: the residual
    # stream, the normed stream a router scores, a head's query and key
    # through their norm and rope, the attention output's projection, a
    # routed expert's gate and up values, and the experts' results until
    # their weighted sum.  Every product's operands and the K/V a token
    # caches stay in the activations' type.  With every expert of a layer
    # held, each rounding moves some token's 8th and 9th score past each
    # other (models/swa.py and models/mla.py keep their stream and router
    # so for the same reason); read on the CPU at 7 layers of 64 experts it
    # takes 18 % off the distance to the float32 reference.
    residual_f32: bool = False
    # One mixer a layer by a pattern given as data (models/ssm_moe.py):
    # ``mixer_pattern[l]`` is ``M`` (a Mamba-2 state-space mixer), ``E``
    # (routed experts) or ``*`` (attention), each alone under one norm and
    # one residual (or followed by an MLP: ``mixer_mlp``, below);
    # ``n_layers`` counts them all and no position is encoded.  An ``M``
    # layer has ``ssm_heads`` heads of ``ssm_head_dim``
    # (its inner width their product), ``ssm_groups`` groups that share B
    # and C of ``ssm_state`` values, a causal depthwise convolution over
    # ``ssm_conv`` positions, and a chunked scan of ``ssm_chunk`` positions
    # in prefill; ``ssm_dt_*`` bound the time step its bias is drawn for.
    # Its recurrent state, one ``[heads, head_dim, state]`` array a slot and
    # layer, is held in float32 beside the KV planes (ssm_moe.STATE_DTYPE).
    mixer_pattern: Optional[str] = None
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 128
    ssm_dt_min: float = 0.001
    ssm_dt_max: float = 0.1
    ssm_dt_floor: float = 1e-4
    # A pattern layer that is a mixer AND a feed-forward (models/ssm_moe.py):
    # under ``mixer_mlp`` every layer carries, after its mixer, a dense gated
    # MLP of ``ffn_dim`` (``W_out(act(a) * b)``, ``[a | b] = u W_in``) under
    # a norm and a residual of its own.  Four published multipliers, each
    # the identity where it is left alone: the embedding's rows are scaled
    # by ``embed_multiplier``, what a mixer or an MLP adds to the residual
    # stream by ``residual_multiplier``, the logits divided by
    # ``logits_divisor``; the attention scores' scale is ``query_scale``
    # (above), which such a model states instead of ``head_dim ** -0.5``.
    mixer_mlp: bool = False
    # A pattern layer whose branches are normed AFTER them and not before
    # (OLMo's block): ``x <- x + RMSNorm(mixer(x))``, and the MLP's likewise.
    # The identity at its default: a branch's ``norm`` weight then stands
    # before it.
    norm_after: bool = False
    # ``L`` in ``mixer_pattern``: a gated delta-rule layer (models/delta.py),
    # ``delta_heads`` heads whose state is a ``[delta_key_dim,
    # delta_value_dim]`` matrix, float32, held a slot and layer beside the
    # KV planes as the Mamba-2 state is; a causal depthwise convolution over
    # ``delta_conv`` positions of ``q | k | v`` side by side, no bias;
    # prefill in chunks of ``delta_chunk``; the write strength is ``2 *
    # sigmoid`` under ``delta_neg_eigval`` (else ``sigmoid``).  The time
    # step its bias is drawn for is ``ssm_dt_*``'s.
    delta_heads: int = 0
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    delta_conv: int = 4
    delta_chunk: int = 64
    delta_neg_eigval: bool = False
    embed_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_divisor: float = 1.0
    # The routed layer's experts: gated (three products, ``act(gate) * up``
    # then down) or not (two: ``act(up)`` then down); the shared experts'
    # width where it is not the routed experts' (0: ``moe_ffn_dim``).
    expert_gated: bool = True
    shared_expert_dim: int = 0

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's feed-forward: leading dense ones, then experts."""
        if not self.n_experts:
            return ("dense",) * self.n_layers
        d = min(self.first_dense_layers, self.n_layers)
        return ("dense",) * d + ("moe",) * (self.n_layers - d)

    @property
    def experts_held(self) -> Tuple[int, int]:
        """(first, count) of the experts this process holds."""
        n = self.n_experts // self.layer_chips
        return self.chip_index * n, n

    @property
    def expert_dim(self) -> int:
        return self.moe_ffn_dim or self.ffn_dim

    @property
    def expert_dim_held(self) -> int:
        """An expert's width as its matrices are held: ``expert_dim``, but
        whole lane tiles of 128 where it is wider than one and is not (1856
        is held as 1920: the columns added to the first matrix and the rows
        added to the last are zeros, which add nothing to any result, and a
        lane-tiled array takes those bytes on the chip either way).  The
        grouped kernel's DMA takes whole tiles (ops/pallas_grouped_matmul)."""
        f = self.expert_dim
        return f if f < 128 else -(-f // 128) * 128

    @property
    def q_head_dim(self) -> int:
        if self.kv_lora_rank:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim

    @property
    def attn_kinds(self) -> Tuple[str, ...]:
        """Each layer's attention, ``"full"`` or ``"window"``: the pattern
        where one is given, else what ``sliding_window`` and
        ``window_pattern`` say of a uniform cache."""
        if self.attn_pattern is not None:
            return tuple("window" if k else "full"
                         for k in self.attn_pattern[: self.n_layers])
        if self.mixer_pattern is not None:
            return ("full",) * self.mixer_kinds.count("*")
        if self.sliding_window is None:
            return ("full",) * self.n_layers
        if self.window_pattern == "all":
            return ("window",) * self.n_layers
        return tuple("window" if l % 2 == 0 else "full"
                     for l in range(self.n_layers))

    @property
    def mixer_kinds(self) -> str:
        """The held layers' letters (``mixer_pattern``'s first
        ``n_layers``)."""
        return (self.mixer_pattern or "")[: self.n_layers]

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """What the convolution runs over: x, B and C side by side."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def delta_conv_dim(self) -> int:
        """What a delta layer's convolution runs over: q, k and v side by
        side."""
        return self.delta_heads * (2 * self.delta_key_dim
                                   + self.delta_value_dim)

    def kv_heads_of(self, kind: str) -> int:
        if kind == "window" and self.window_kv_heads:
            return self.window_kv_heads
        return self.n_kv_heads

    def heads_of(self, kind: str) -> int:
        """Query heads of an attention layer of ``kind``."""
        if kind == "window" and self.window_heads:
            return self.window_heads
        return self.n_heads

    def rotary_of(self, kind: str) -> int:
        """Leading columns of a head that a layer of ``kind`` ropes (0: all
        of them)."""
        if kind == "window" and self.window_rotary_dim:
            return self.window_rotary_dim
        return self.rotary_dim

    def ring_default(self, max_seq: int, chunk: int = 0) -> int:
        """Positions a window layer's ring holds a slot: the window and the
        widest chunk-prefill segment, in whole 128s (a segment's padded tail
        must not reach round the ring into the window the next query reads),
        never more than ``max_seq``."""
        if self.ring_positions:
            return min(self.ring_positions, max_seq)
        need = (self.sliding_window or 0) + max(chunk, 128)
        return min(-(-need // 128) * 128, max_seq)


def tiny(vocab_size: int = 512) -> ModelConfig:
    """CPU-testable config: compiles in seconds, exercises GQA + rope."""
    return ModelConfig(
        name="tiny",
        vocab_size=vocab_size,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        ffn_dim=128,
    )


def tiny_gemma(vocab_size: int = 512) -> ModelConfig:
    """Tiny config exercising every gemma2 code path on CPU."""
    return ModelConfig(
        name="tiny-gemma",
        vocab_size=vocab_size,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        ffn_dim=128,
        act="gelu",
        tie_embeddings=True,
        post_norms=True,
        attn_softcap=50.0,
        logit_softcap=30.0,
        sliding_window=8,
        embed_scale=True,
    )


def gemma2_2b() -> ModelConfig:
    return ModelConfig(
        name="gemma2-2b",
        vocab_size=256128,
        dim=2304,
        n_layers=26,
        n_heads=8,
        n_kv_heads=4,
        head_dim=256,
        ffn_dim=9216,
        rope_theta=10000.0,
        norm_eps=1e-6,
        act="gelu",
        tie_embeddings=True,
        post_norms=True,
        attn_softcap=50.0,
        logit_softcap=30.0,
        sliding_window=4096,
        embed_scale=True,
        query_scale=256**-0.5,
    )


def llama3_8b() -> ModelConfig:
    return ModelConfig(
        name="llama3-8b",
        vocab_size=128256,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        ffn_dim=14336,
        rope_theta=500000.0,
        norm_eps=1e-5,
    )


def llama32_1b() -> ModelConfig:
    """Llama-3.2-1B: tied embeddings, GQA 32/8, head_dim 64 — the smallest
    real-checkpoint target (fits any chip; good for the opt-in
    tests/test_real_checkpoint.py smoke)."""
    return ModelConfig(
        name="llama3.2-1b",
        vocab_size=128256,
        dim=2048,
        n_layers=16,
        n_heads=32,
        n_kv_heads=8,
        head_dim=64,
        ffn_dim=8192,
        rope_theta=500000.0,
        norm_eps=1e-5,
        tie_embeddings=True,
    )


def llama32_3b() -> ModelConfig:
    return ModelConfig(
        name="llama3.2-3b",
        vocab_size=128256,
        dim=3072,
        n_layers=28,
        n_heads=24,
        n_kv_heads=8,
        head_dim=128,
        ffn_dim=8192,
        rope_theta=500000.0,
        norm_eps=1e-5,
        tie_embeddings=True,
    )


def mistral_7b() -> ModelConfig:
    """Mistral-7B-v0.1: llama-style with a 4096 sliding window on EVERY
    layer (the arch that popularised windowed attention for serving)."""
    return ModelConfig(
        name="mistral-7b",
        vocab_size=32000,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        ffn_dim=14336,
        rope_theta=10000.0,
        norm_eps=1e-5,
        sliding_window=4096,
        window_pattern="all",
    )


def qwen2_7b() -> ModelConfig:
    """Qwen2-7B: llama-style blocks + QKV bias, large vocab, theta=1e6."""
    return ModelConfig(
        name="qwen2-7b",
        vocab_size=152064,
        dim=3584,
        n_layers=28,
        n_heads=28,
        n_kv_heads=4,
        head_dim=128,
        ffn_dim=18944,
        rope_theta=1000000.0,
        norm_eps=1e-6,
        attn_bias=True,
    )


def tiny_qwen() -> ModelConfig:
    """Tiny config exercising the qwen2 code path (QKV bias) on CPU."""
    return ModelConfig(
        name="tiny-qwen",
        vocab_size=256 + 3,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        ffn_dim=128,
        attn_bias=True,
    )


def llama3_70b() -> ModelConfig:
    return ModelConfig(
        name="llama3-70b",
        vocab_size=128256,
        dim=8192,
        n_layers=80,
        n_heads=64,
        n_kv_heads=8,
        head_dim=128,
        ffn_dim=28672,
        rope_theta=500000.0,
        norm_eps=1e-5,
    )


def tiny_moe(vocab_size: int = 512) -> ModelConfig:
    """Tiny mixture-of-experts config: 4 experts, top-2 — CPU-testable
    coverage for the MoE block and expert-parallel sharding."""
    return ModelConfig(
        name="tiny-moe",
        vocab_size=vocab_size,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        ffn_dim=128,
        n_experts=4,
        n_experts_per_tok=2,
    )


def mixtral_8x7b() -> ModelConfig:
    """Mixtral-8x7B: llama-style attention, 8-expert top-2 SwiGLU MLPs."""
    return ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        ffn_dim=14336,
        rope_theta=1000000.0,
        norm_eps=1e-5,
        sliding_window=None,
        n_experts=8,
        n_experts_per_tok=2,
    )


def sarvam_105b() -> ModelConfig:
    """sarvam-105b as published (huggingface.co/sarvamai/sarvam-105b
    config.json): latent attention, one dense layer, then 128 routed
    experts top-8 with a selection bias and one shared expert."""
    return ModelConfig(
        name="sarvam-105b",
        vocab_size=262144,
        dim=4096,
        n_layers=32,
        n_heads=64,
        n_kv_heads=1,
        head_dim=576,
        ffn_dim=16384,
        rope_theta=10000.0,
        norm_eps=1e-6,
        n_experts=128,
        n_experts_per_tok=8,
        moe_ffn_dim=2048,
        n_shared_experts=1,
        first_dense_layers=1,
        router_score="sigmoid",
        router_bias=True,
        routed_scale=2.5,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        qk_norm=True,
        yarn=YarnRope(factor=40.0, original_max=4096, beta_fast=32.0,
                      beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
    )


def sarvam_105b_ep4s() -> ModelConfig:
    """One chip's share of sarvam-105b: one of 4 chips that share each
    layer (experts 0-31, vocabulary rows 0-65,535) and the first pipeline
    stage (the dense layer and 5 expert layers).  Every width, the router's
    128 outputs, top-8, the bias and 2.5 are as published."""
    return replace(sarvam_105b(), name="sarvam-105b-ep4s", n_layers=6,
                   published_layers=32, vocab_size=65536, layer_chips=4,
                   chip_index=0)


def tiny_mla_moe(vocab_size: int = 512) -> ModelConfig:
    """CPU-testable sarvam-style config: latent 32 + rope 8, yarn on, one
    dense layer then three of 8 experts top-2 + 1 shared (an even depth, as
    the published model's and its share's: two layers share a row of the
    rope-key plane, models/mla.py)."""
    return ModelConfig(
        name="tiny-mla-moe",
        vocab_size=vocab_size,
        dim=64,
        n_layers=4,
        n_heads=4,
        n_kv_heads=1,
        head_dim=40,
        ffn_dim=128,
        norm_eps=1e-6,
        n_experts=8,
        n_experts_per_tok=2,
        moe_ffn_dim=32,
        n_shared_experts=1,
        first_dense_layers=1,
        router_score="sigmoid",
        router_bias=True,
        routed_scale=2.5,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        qk_norm=True,
        yarn=YarnRope(factor=40.0, original_max=16, beta_fast=32.0,
                      beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
    )


def tiny_mla_moe_ep2s(vocab_size: int = 512) -> ModelConfig:
    """tiny-mla-moe as one of 2 chips that share each layer: experts 0-3
    and ``vocab_size`` rows of a table twice as long."""
    return replace(tiny_mla_moe(vocab_size), name="tiny-mla-moe-ep2s",
                   layer_chips=2, chip_index=0)


#: hybrid_layer_pattern of MiMo-V2-Flash: layer 0 full, four window layers,
#: then periods of one full layer and five window layers, a full layer last.
_MIMO_PATTERN = (0, 1, 1, 1, 1) + (0, 1, 1, 1, 1, 1) * 7 + (0,)


def mimo_v2_flash() -> ModelConfig:
    """MiMo-V2-Flash as published (huggingface.co/XiaomiMiMo/MiMo-V2-Flash
    config.json): 9 full and 39 window attention layers (window 128, a sink
    in their softmax, 8 KV heads against 4), keys 192 and values 128 wide,
    64 rotary columns, one dense layer then 256 routed experts top-8 with a
    selection bias and no shared expert.  For shapes and tests of the
    config: no chip holds it."""
    return ModelConfig(
        name="mimo-v2-flash",
        vocab_size=152576,
        dim=4096,
        n_layers=48,
        n_heads=64,
        n_kv_heads=4,
        head_dim=192,
        ffn_dim=16384,
        rope_theta=5000000.0,
        norm_eps=1e-5,
        sliding_window=128,
        n_experts=256,
        n_experts_per_tok=8,
        moe_ffn_dim=2048,
        first_dense_layers=1,
        router_score="sigmoid",
        router_bias=True,
        routed_scale=1.0,
        v_head_dim=128,
        attn_pattern=_MIMO_PATTERN,
        window_kv_heads=8,
        window_rope_theta=10000.0,
        rotary_dim=64,
        value_scale=0.707,
        window_sink=True,
    )


def mimo_v2_flash_ep16s() -> ModelConfig:
    """One chip's share of MiMo-V2-Flash: one of 16 chips that share each
    layer (experts 0-15, vocabulary rows 0-19,071) and the first pipeline
    stage: the dense layer and six routed layers, kinds F SSSS F S (one
    whole period of the pattern).  Every width, the router's 256 outputs,
    top-8 and the bias are as published."""
    return replace(mimo_v2_flash(), name="mimo-v2-flash-ep16s", n_layers=7,
                   published_layers=48, vocab_size=19072, layer_chips=16,
                   chip_index=0)


def tiny_swa_moe(vocab_size: int = 512) -> ModelConfig:
    """CPU-testable MiMo-style config: seven layers F SSSS F S, window 8,
    keys 24 and values 16 wide with 8 rotary columns, 1 (full) and 2
    (window) KV heads, a sink, one dense layer then 8 experts top-2; rings
    of 16 positions, so a prompt of some tens of tokens wraps them."""
    return ModelConfig(
        name="tiny-swa-moe",
        vocab_size=vocab_size,
        dim=64,
        n_layers=7,
        n_heads=4,
        n_kv_heads=1,
        head_dim=24,
        ffn_dim=128,
        rope_theta=5000000.0,
        norm_eps=1e-5,
        sliding_window=8,
        n_experts=8,
        n_experts_per_tok=2,
        moe_ffn_dim=32,
        first_dense_layers=1,
        router_score="sigmoid",
        router_bias=True,
        v_head_dim=16,
        attn_pattern=(0, 1, 1, 1, 1, 0, 1),
        window_kv_heads=2,
        window_rope_theta=10000.0,
        rotary_dim=8,
        value_scale=0.707,
        window_sink=True,
        ring_positions=16,
    )


def laguna_s_2_1() -> ModelConfig:
    """Laguna-S-2.1 as published (huggingface.co/poolside/Laguna-S-2.1
    config.json, ``model_type`` ``laguna``): 48 layers, a full-attention
    layer (48 query heads, yarn on the leading 64 of 128 columns) then three
    window layers (72 query heads, window 512, a plain rope on all 128), 8
    KV heads of 128 in both, a sigmoid gate a query head on attention's
    output; one dense layer of 12288, then 256 routed experts of 1024
    top-10 (over their sum, times 2.5) beside one shared expert of 1024.
    What the config leaves open (sigmoid scores with a selection bias, an
    RMSNorm a head on queries and keys, the shared expert ungated) is the
    family's convention: benchmarks/LAGUNA_MOE.md.  For shapes and tests of
    the config: no chip holds it."""
    return ModelConfig(
        name="laguna-s-2.1",
        vocab_size=100352,
        dim=3072,
        n_layers=48,
        n_heads=48,
        n_kv_heads=8,
        head_dim=128,
        ffn_dim=12288,
        rope_theta=500000.0,
        norm_eps=1e-6,
        sliding_window=512,
        n_experts=256,
        n_experts_per_tok=10,
        moe_ffn_dim=1024,
        n_shared_experts=1,
        shared_expert_dim=1024,
        first_dense_layers=1,
        router_score="sigmoid",
        router_bias=True,
        routed_scale=2.5,
        v_head_dim=128,
        qk_norm=True,
        attn_pattern=(0, 1, 1, 1) * 12,
        window_heads=72,
        window_rope_theta=10000.0,
        rotary_dim=64,
        window_rotary_dim=128,
        attn_gate="per-head",
        yarn=YarnRope(factor=128.0, original_max=8192, beta_fast=32.0,
                      beta_slow=1.0,
                      attention_factor=1.4852030263919618),
    )


def laguna_s_2_1_ep8s() -> ModelConfig:
    """One chip's share of Laguna-S-2.1: one of 8 chips that share each
    layer (experts 0-31, vocabulary rows 0-12,543; attention, the router
    and the shared expert whole on each) and the first pipeline stage: the
    dense layer and seven routed layers, kinds F WWW F WWW (two whole
    periods).  Every width, both head counts, the router's 256 outputs,
    top-10 and 2.5 are as published."""
    return replace(laguna_s_2_1(), name="laguna-s-2.1-ep8s", n_layers=8,
                   published_layers=48, vocab_size=12544, layer_chips=8,
                   chip_index=0)


def tiny_laguna(vocab_size: int = 512) -> ModelConfig:
    """CPU-testable Laguna-style config: eight layers F WWW F WWW, 6 (full)
    and 9 (window) query heads on 3 KV heads of 16 (groups of 2 and 3, head
    counts no power of two), window 8, yarn on a full layer's leading 8
    columns and a plain rope on all 16 of a window layer's, a gate a head,
    QK norm, one dense layer then 16 experts top-3 beside a shared one;
    rings of 16 positions, so a prompt of some tens of tokens wraps
    them."""
    return ModelConfig(
        name="tiny-laguna",
        vocab_size=vocab_size,
        dim=64,
        n_layers=8,
        n_heads=6,
        n_kv_heads=3,
        head_dim=16,
        ffn_dim=128,
        rope_theta=500000.0,
        norm_eps=1e-6,
        sliding_window=8,
        n_experts=16,
        n_experts_per_tok=3,
        moe_ffn_dim=32,
        n_shared_experts=1,
        shared_expert_dim=32,
        first_dense_layers=1,
        router_score="sigmoid",
        router_bias=True,
        routed_scale=2.5,
        v_head_dim=16,
        qk_norm=True,
        attn_pattern=(0, 1, 1, 1) * 2,
        window_heads=9,
        window_rope_theta=10000.0,
        rotary_dim=8,
        window_rotary_dim=16,
        attn_gate="per-head",
        yarn=YarnRope(factor=8.0, original_max=16, beta_fast=32.0,
                      beta_slow=1.0, attention_factor=1.2079441541679836),
        ring_positions=16,
    )


def tiny_laguna_ep2s(vocab_size: int = 512) -> ModelConfig:
    """tiny-laguna as one of 2 chips that share each layer: experts 0-7 and
    ``vocab_size`` rows of a table twice as long."""
    return replace(tiny_laguna(vocab_size), name="tiny-laguna-ep2s",
                   layer_chips=2, chip_index=0)


def sdar_30b_a3b() -> ModelConfig:
    """SDAR-30B-A3B-Chat (JetLM; ``model_type`` ``sdar_moe``): a Qwen3-MoE
    body (QK norm, 128 routed experts of width 768, top-8 of a softmax,
    renormalised, no shared expert; ``intermediate_size`` 6144 is read by
    nothing: every layer is routed) that generates by masked denoising
    over blocks of 4 (the family's ``generate.py``: block length 4; 2
    sequential steps of 2 stand for its confidence threshold)."""
    return ModelConfig(
        name="sdar-30b-a3b",
        vocab_size=151936,
        dim=2048,
        n_layers=48,
        n_heads=32,
        n_kv_heads=4,
        head_dim=128,
        ffn_dim=6144,
        rope_theta=1000000.0,
        norm_eps=1e-6,
        n_experts=128,
        n_experts_per_tok=8,
        moe_ffn_dim=768,
        qk_norm=True,
        block_length=4,
        denoise_steps=2,
        mask_token_id=151669,
        residual_f32=True,
    )


def sdar_30b_a3b_pp7s() -> ModelConfig:
    """The first of seven pipeline stages of SDAR-30B-A3B-Chat: 7 of 48
    layers, each whole (all 128 experts), with the embedding and the whole
    head; every width as published."""
    return replace(sdar_30b_a3b(), name="sdar-30b-a3b-pp7s", n_layers=7,
                   published_layers=48)


def tiny_sdar_moe(vocab_size: int = 512) -> ModelConfig:
    """CPU-testable SDAR-style config: blocks of 4 in 2 steps, 8 experts
    top-2, QK norm, 4 query heads on 2 KV heads."""
    return ModelConfig(
        name="tiny-sdar-moe",
        vocab_size=vocab_size,
        dim=64,
        n_layers=3,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        ffn_dim=128,
        rope_theta=1000000.0,
        norm_eps=1e-6,
        n_experts=8,
        n_experts_per_tok=2,
        moe_ffn_dim=32,
        qk_norm=True,
        block_length=4,
        denoise_steps=2,
        mask_token_id=vocab_size - 1,
        residual_f32=True,
    )


#: hybrid_override_pattern of NVIDIA-Nemotron-3-Nano-30B-A3B.
_NEMOTRON_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def nemotron_3_nano_30b_a3b() -> ModelConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B as published (huggingface.co/nvidia/
    NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json, ``model_type``
    ``nemotron_h``): 52 layers, each one mixer alone: 23 Mamba-2 (64 heads
    of 64, 8 groups, state 128, conv 4), 23 of 128 routed experts of 1856
    top-6 (relu squared, no gate; sigmoid scores, a selection bias, 2.5)
    with a shared expert of 3712, and 6 of attention (32 query / 2 KV heads
    of 128, no rotary).  For shapes and tests of the config: no chip holds
    it."""
    return ModelConfig(
        name="nemotron-3-nano-30b-a3b",
        vocab_size=131072,
        dim=2688,
        n_layers=52,
        n_heads=32,
        n_kv_heads=2,
        head_dim=128,
        ffn_dim=1856,
        norm_eps=1e-5,
        act="relu2",
        n_experts=128,
        n_experts_per_tok=6,
        moe_ffn_dim=1856,
        n_shared_experts=1,
        shared_expert_dim=3712,
        expert_gated=False,
        router_score="sigmoid",
        router_bias=True,
        routed_scale=2.5,
        v_head_dim=128,
        mixer_pattern=_NEMOTRON_PATTERN,
        ssm_heads=64,
        ssm_head_dim=64,
        ssm_groups=8,
        ssm_state=128,
        ssm_conv=4,
        ssm_chunk=128,
        residual_f32=True,
    )


def nemotron_3_nano_30b_a3b_ep2s() -> ModelConfig:
    """One chip's share of NVIDIA-Nemotron-3-Nano-30B-A3B: one of 2 chips
    that share each layer (experts 0-63, vocabulary rows 0-65,535; the
    mixers whole on each) and the first of 4 pipeline stages of 13 layers
    (``MEMEM*EMEMEM*``: 6 Mamba-2, 5 routed, 2 attention).  Every width,
    the router's 128 outputs and top-6 are as published."""
    return replace(nemotron_3_nano_30b_a3b(),
                   name="nemotron-3-nano-30b-a3b-ep2s", n_layers=13,
                   published_layers=52, vocab_size=65536, layer_chips=2,
                   chip_index=0)


def tiny_ssm_moe(vocab_size: int = 512) -> ModelConfig:
    """CPU-testable Nemotron-H-style config: ``MEM*EM*`` (3 Mamba-2 of 4
    heads of 8, 2 groups, state 16, scan chunks of 8; 2 layers of 8 experts
    of 24 top-2 with a shared expert of 48; 2 of attention, 4 query heads
    on 2 KV heads of 16)."""
    return ModelConfig(
        name="tiny-ssm-moe",
        vocab_size=vocab_size,
        dim=64,
        n_layers=7,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        ffn_dim=24,
        norm_eps=1e-5,
        act="relu2",
        n_experts=8,
        n_experts_per_tok=2,
        moe_ffn_dim=24,
        n_shared_experts=1,
        shared_expert_dim=48,
        expert_gated=False,
        router_score="sigmoid",
        router_bias=True,
        routed_scale=2.5,
        v_head_dim=16,
        mixer_pattern="MEM*EM*",
        ssm_heads=4,
        ssm_head_dim=8,
        ssm_groups=2,
        ssm_state=16,
        ssm_conv=4,
        ssm_chunk=8,
        residual_f32=True,
    )


#: layer_types of granite-4.0-h-micro: attention at 5, 15, 25 and 35.
_GRANITE_H_PATTERN = "MMMMM*" + "MMMMMMMMM*" * 3 + "MMMM"


def granite_4_0_h_micro() -> ModelConfig:
    """granite-4.0-h-micro as published (huggingface.co/ibm-granite/
    granite-4.0-h-micro config.json, ``model_type`` ``granitemoehybrid``),
    whole: 40 layers, each a mixer and then a dense gated MLP of 8192 under
    its own norm and residual: 36 Mamba-2 (64 heads of 64, ONE group, state
    128, conv 4, chunks of 256) and 4 of attention (32 query / 8 KV heads
    of 64, no position encoded, scores scaled by 0.015625); the embedding
    times 12, each residual branch times 0.22, the logits over 8, the head
    the embedding.  3,191 M parameters, 6.38 GB in bfloat16: one chip holds
    it."""
    return ModelConfig(
        name="granite-4.0-h-micro",
        vocab_size=100352,
        dim=2048,
        n_layers=40,
        n_heads=32,
        n_kv_heads=8,
        head_dim=64,
        ffn_dim=8192,
        norm_eps=1e-5,
        act="silu",
        tie_embeddings=True,
        query_scale=0.015625,
        v_head_dim=64,
        mixer_pattern=_GRANITE_H_PATTERN,
        ssm_heads=64,
        ssm_head_dim=64,
        ssm_groups=1,
        ssm_state=128,
        ssm_conv=4,
        ssm_chunk=256,
        mixer_mlp=True,
        embed_multiplier=12.0,
        residual_multiplier=0.22,
        logits_divisor=8.0,
        residual_f32=True,
    )


def tiny_ssm_mlp(vocab_size: int = 512) -> ModelConfig:
    """CPU-testable granite-4.0-h-style config: ``MM*M`` (3 Mamba-2 of 4
    heads of 8, ONE group, state 16, scan chunks of 8; 1 of attention, 4
    query heads on 2 KV heads of 16 at a score scale that is not ``16 **
    -0.5``), a gated MLP of 96 after every mixer, multipliers that are not
    1, the head tied to the embedding."""
    return ModelConfig(
        name="tiny-ssm-mlp",
        vocab_size=vocab_size,
        dim=64,
        n_layers=4,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        ffn_dim=96,
        norm_eps=1e-5,
        act="silu",
        tie_embeddings=True,
        query_scale=0.125,
        v_head_dim=16,
        mixer_pattern="MM*M",
        ssm_heads=4,
        ssm_head_dim=8,
        ssm_groups=1,
        ssm_state=16,
        ssm_conv=4,
        ssm_chunk=8,
        mixer_mlp=True,
        embed_multiplier=6.0,
        residual_multiplier=0.4,
        logits_divisor=2.0,
        residual_f32=True,
    )


#: layer_types of Olmo-Hybrid-7B: three delta-rule layers, then attention.
_OLMO_HYBRID_PATTERN = "LLL*" * 8


def olmo_hybrid_7b() -> ModelConfig:
    """Olmo-Hybrid-7B (huggingface.co/allenai/Olmo-Hybrid-7B config.json,
    ``model_type`` ``olmo_hybrid``) as ONE OF TWO PIPELINE STAGES: 16 of the
    32 layers, four whole periods of three gated delta-rule layers (30
    heads, a ``[96, 192]`` float32 state a head, conv 4, chunks of 64, the
    write strength in [0, 2]) and one of full attention (30 query and 30 KV
    heads of 128, an RMSNorm over the whole 3840-wide query and key, no
    position encoded), each followed by a dense gated MLP of 11008; OLMo's
    block: no norm before a branch, one on its output; the head its own.
    Every width, every head and the whole vocabulary: 12 x 215.6 M + 4 x
    185.8 M + 770.7 M = 4,101 M parameters, 8.2 GB in bfloat16."""
    return ModelConfig(
        name="olmo-hybrid-7b",
        vocab_size=100352,
        dim=3840,
        n_layers=16,
        published_layers=32,
        n_heads=30,
        n_kv_heads=30,
        head_dim=128,
        ffn_dim=11008,
        norm_eps=1e-6,
        act="silu",
        tie_embeddings=False,
        qk_norm=True,
        v_head_dim=128,
        mixer_pattern=_OLMO_HYBRID_PATTERN,
        mixer_mlp=True,
        norm_after=True,
        delta_heads=30,
        delta_key_dim=96,
        delta_value_dim=192,
        delta_conv=4,
        delta_chunk=64,
        delta_neg_eigval=True,
        residual_f32=True,
    )


def tiny_delta_mlp(vocab_size: int = 512) -> ModelConfig:
    """CPU-testable Olmo-Hybrid-style config: ``LLL*`` twice (6 delta-rule
    layers of 3 heads, keys 16 and values 24 wide: heads no power of two,
    the two widths apart, neither a lane tile; chunks of 8; 2 of attention,
    3 query heads on 3 KV heads of 16 under a whole-width QK norm), a gated
    MLP of 96 after every mixer, every branch normed after it."""
    return ModelConfig(
        name="tiny-delta-mlp",
        vocab_size=vocab_size,
        dim=48,
        n_layers=8,
        n_heads=3,
        n_kv_heads=3,
        head_dim=16,
        ffn_dim=96,
        norm_eps=1e-6,
        act="silu",
        tie_embeddings=False,
        qk_norm=True,
        v_head_dim=16,
        mixer_pattern="LLL*LLL*",
        mixer_mlp=True,
        norm_after=True,
        delta_heads=3,
        delta_key_dim=16,
        delta_value_dim=24,
        delta_conv=4,
        delta_chunk=8,
        delta_neg_eigval=True,
        residual_f32=True,
    )


def tiny_ssm_moe_ep2s(vocab_size: int = 512) -> ModelConfig:
    """tiny-ssm-moe as one of 2 chips that share each layer: experts 0-3
    and ``vocab_size`` rows of a table twice as long."""
    return replace(tiny_ssm_moe(vocab_size), name="tiny-ssm-moe-ep2s",
                   layer_chips=2, chip_index=0)


def tiny_swa_moe_ep2s(vocab_size: int = 512) -> ModelConfig:
    """tiny-swa-moe as one of 2 chips that share each layer: experts 0-3
    and ``vocab_size`` rows of a table twice as long."""
    return replace(tiny_swa_moe(vocab_size), name="tiny-swa-moe-ep2s",
                   layer_chips=2, chip_index=0)


PRESETS = {
    "tiny": tiny,
    "tiny-ssm-moe": tiny_ssm_moe,
    "tiny-ssm-moe-ep2s": tiny_ssm_moe_ep2s,
    "tiny-ssm-mlp": tiny_ssm_mlp,
    "granite-4.0-h-micro": granite_4_0_h_micro,
    "tiny-delta-mlp": tiny_delta_mlp,
    "olmo-hybrid-7b": olmo_hybrid_7b,
    "nemotron-3-nano-30b-a3b": nemotron_3_nano_30b_a3b,
    "nemotron-3-nano-30b-a3b-ep2s": nemotron_3_nano_30b_a3b_ep2s,
    "tiny-sdar-moe": tiny_sdar_moe,
    "sdar-30b-a3b": sdar_30b_a3b,
    "sdar-30b-a3b-pp7s": sdar_30b_a3b_pp7s,
    "tiny-laguna": tiny_laguna,
    "tiny-laguna-ep2s": tiny_laguna_ep2s,
    "laguna-s-2.1": laguna_s_2_1,
    "laguna-s-2.1-ep8s": laguna_s_2_1_ep8s,
    "tiny-swa-moe": tiny_swa_moe,
    "tiny-swa-moe-ep2s": tiny_swa_moe_ep2s,
    "mimo-v2-flash": mimo_v2_flash,
    "mimo-v2-flash-ep16s": mimo_v2_flash_ep16s,
    "tiny-mla-moe": tiny_mla_moe,
    "tiny-mla-moe-ep2s": tiny_mla_moe_ep2s,
    "sarvam-105b": sarvam_105b,
    "sarvam-105b-ep4s": sarvam_105b_ep4s,
    "tiny-qwen": tiny_qwen,
    "tiny-moe": tiny_moe,
    "mixtral-8x7b": mixtral_8x7b,
    "tiny-gemma": tiny_gemma,
    "gemma2-2b": gemma2_2b,
    "llama3-8b": llama3_8b,
    "llama3-70b": llama3_70b,
    "llama3.2-1b": llama32_1b,
    "llama3.2-3b": llama32_3b,
    "mistral-7b": mistral_7b,
    "qwen2-7b": qwen2_7b,
}


def get_config(name: str, **overrides) -> ModelConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown model preset {name!r}; have {sorted(PRESETS)}")
    cfg = PRESETS[name]()
    if overrides:
        cfg = replace(cfg, **overrides)
    return cfg
