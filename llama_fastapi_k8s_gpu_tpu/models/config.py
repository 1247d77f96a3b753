"""Model architecture config, derived from GGUF metadata.

Mirrors the hparams llama.cpp reads when the reference loads a model
(``Llama(model_path=..., n_ctx=1024)``, reference api.py:24-28).  Covers the
Llama family (Llama-2/3) and Mistral (same graph + optional sliding-window
attention, BASELINE.json config "Mistral-7B ... sliding-window attention
path").
"""

from __future__ import annotations

import dataclasses

from ..gguf import GGUFFile
from ..gguf.constants import NEOX_ROPE_ARCHITECTURES


#: the cache kinds' names (``ModelConfig.cache_kind``; docs/KV_CACHE.md)
RING, WINDOW_SUMMARIES, STATE_RING = "ring", "window+summaries", "state+ring"
LATENT_RING = "latent-ring"
WINDOW_GLOBAL_RING = "window+global-ring"
CONV_RING = "conv-state+ring"
SSM_WINDOW_SHARED = "ssm-state+window+shared-ring"
SSM_RING = "ssm-state+ring"

#: the attention kinds of a layer (``ModelConfig.attn_kinds``)
WINDOW, GLOBAL = "window", "global"

#: the mixer kinds of a ``lfm2moe`` layer (``ModelConfig.mixers``); a
#: ``jamba`` layer is :data:`SSM` or :data:`ATTN`
CONV, ATTN = "conv", "attn"

#: the mixer kinds of a ``phi4flash`` layer beside :data:`WINDOW`: a
#: selective scan, full attention that WRITES the shared leaf, a gated
#: memory unit, attention that only READS the shared leaf
SSM, FULL, GMU, CROSS = "ssm", "full", "gmu", "cross"


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    ffn_dim: int
    n_ctx: int
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    sliding_window: int = 0      # 0 = full causal attention
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # "xla" materializes (S, n_ctx) scores; "pallas" streams K/V through the
    # blockwise flash kernel (ops/pallas/attention.py) on prefill paths
    attn_impl: str = "xla"
    # KV-cache storage dtype: "bf16" (the default two-leaf {k, v} ring) or
    # "int8" (four-leaf {k_q, v_q, k_s, v_s}: int8 values + per-head
    # per-token symmetric f32 scales — ops/pallas/kvquant.py writes them,
    # the attention consumers dequantize in-register).  Static so the cache
    # pytree STRUCTURE is fixed at trace time (docs/KV_CACHE.md).
    kv_dtype: str = "bf16"
    # The feed-forward kind (models/llama.py ``_layer``): ``n_experts == 0``
    # is the dense SwiGLU of width ``ffn_dim``; otherwise a float32 router
    # picks ``n_experts_used`` of ``n_experts`` SwiGLU experts of width
    # ``ffn_dim`` per token (no shared expert, no capacity limit), their
    # softmax-over-all probabilities renormalised over the picked ones only
    # when ``norm_topk_prob``.
    n_experts: int = 0
    n_experts_used: int = 0
    norm_topk_prob: bool = False
    # RMSNorm of Q and K over the WHOLE projection width, before the split
    # into heads and before RoPE (OLMoE; weights ``attn_{q,k}_norm``)
    qk_norm: bool = False
    # RoPE pairs dimension i with i + head_dim/2 (rotate-half, ggml NEOX)
    # instead of 2i with 2i+1: by the file's architecture
    # (gguf/constants.py NEOX_ROPE_ARCHITECTURES)
    rope_neox: bool = False
    # The cache KIND (models/eva.py, docs/KV_CACHE.md): ``eva_window == 0``
    # is the ring of ``n_ctx`` slots; otherwise an exact window of
    # ``eva_window`` positions (blocked, not sliding) plus one summary per
    # ``eva_chunk`` positions of every earlier window (``evabyte``).
    eva_window: int = 0
    eva_chunk: int = 0
    # The output matrix has ``vocab_size * n_pred_heads`` rows; head 0 (the
    # first ``vocab_size``) is the next token, the one a step samples from
    n_pred_heads: int = 1
    # The precision the configuration states (``fp32_skip_add``,
    # ``fp32_logits``): the residual stream and the logits are float32,
    # matmul inputs stay bf16
    fp32_residual: bool = False
    # A stack of TWO layer kinds (models/sala.py; ``minicpm-sala``): the kind
    # of each layer in the published order, ``"lin"`` (linear attention over
    # a decaying float32 state per head) or ``"sp"`` (block-sparse attention
    # on the ring of ``n_kv_heads`` heads); empty for every other file.
    # ``n_heads`` / ``n_kv_heads`` are the sparse layers', ``lin_heads`` the
    # linear layers' (of ``head_dim`` too).  A ``lfm2moe`` file
    # (models/lfm2.py; ``conv_l_cache`` taps) names its layers ``"conv"`` (a
    # gated short convolution, whose cache is the last ``conv_l_cache - 1``
    # inputs of its taps) or ``"attn"`` (GQA on a ring) here too.
    mixers: tuple = ()
    conv_l_cache: int = 0
    lin_heads: int = 0
    # the family's three scalars, as applied: on the embedding, on every
    # branch before it is added to the stream, on the final norm's output
    emb_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # the sparse layers' constants (positions): compressed keys are means of
    # ``sp_kernel`` keys every ``sp_stride``; a query from ``sp_dense_len``
    # on reads ``sp_init_blocks`` first blocks of ``sp_block``, the blocks
    # that hold its last ``sp_window`` positions and the ``sp_topk`` others
    # that score highest
    sp_kernel: int = 0
    sp_stride: int = 0
    sp_block: int = 0
    sp_topk: int = 0
    sp_window: int = 0
    sp_init_blocks: int = 0
    sp_dense_len: int = 0
    # float32 logits from a float head (bf16 inputs), as ``fp32_residual``
    # has them, on a bf16 stream
    fp32_logits: bool = False
    # Latent attention (models/mla.py; ``deepseek2``; 0: none): the query
    # passes a normed latent of ``q_lora_rank``; a position's keys and
    # values are one normed latent of ``kv_lora_rank`` that every head
    # expands to ``qk_nope_dim`` key and ``v_head_dim`` value columns, plus
    # ONE rotated key of ``qk_rope_dim`` shared by all heads.  The cache
    # keeps the latent and the rotated key (``cache_kind`` latent-ring).
    # ``latent_kernel``: a decode step reads the cache through the decode
    # kernel (ops/pallas/attention.py ``latent_attention_decode``).  Set by
    # the engine, never by a file or a user: a TPU whose compiler took the
    # kernel's probe.  ``latent_slice_kernel``: a prefill slice reads it
    # through the slice kernel (``latent_attention_prefill``), set the same
    # way behind that kernel's own probe.  (``attn_impl`` stays ``xla`` for
    # this kind: it names the ring's flash kernel, which serves nothing
    # here; /health ``engine.latent_slice_read`` names the slices' read.)
    latent_kernel: bool = False
    latent_slice_kernel: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # YaRN (factor 0: none) on the rotated part, as published: the inverse
    # frequencies blended between plain and interpolated by a linear ramp
    # between the correction dims of ``beta_fast`` / ``beta_slow`` at
    # ``orig_ctx``; ``attn_mscale`` is what the softmax scale is multiplied
    # by ((0.1 * mscale_all_dim * ln factor + 1) ** 2 where > 1; the
    # cos/sin scale mscale/mscale_all_dim is 1 for every file served)
    # A learned indexer beside the latent attention (``deepseek32``:
    # DeepSeek Sparse Attention; 0: none): ``index_heads`` query heads of
    # ``index_dim`` from the SAME normed query latent score ONE cached index
    # key a position (a second leaf of the latent ring, ``idx``), and a
    # query attends the ``index_topk`` positions of largest score alone
    # (all of them while it has no more).  ``index_norm_eps``: the
    # LayerNorm (weight AND bias) over the index key.
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    index_norm_eps: float = 1e-6
    rope_yarn_factor: float = 0.0
    rope_yarn_orig_ctx: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    attn_mscale: float = 1.0
    # The feed-forward kind is the LAYER's: the first ``n_dense_layers`` are
    # the dense SwiGLU of ``ffn_dim``, the others routed experts of
    # ``expert_ffn_dim`` plus ``n_shared_experts`` shared ones (one matrix
    # of their summed width) on every token.
    n_dense_layers: int = 0
    expert_ffn_dim: int = 0
    n_shared_experts: int = 0
    # The router of such a layer (``route_grouped``): ``expert_gating``
    # softmax | sigmoid over all ``n_experts``; the choice on the scores
    # plus a bias (``exp_probs_b``), inside the ``n_groups_used`` best of
    # ``n_expert_groups`` groups (a group's score: its two largest);
    # weights the unbiased scores, normalised if ``norm_topk_prob``, times
    # ``expert_weights_scale``.
    expert_gating: str = "softmax"
    n_expert_groups: int = 1
    n_groups_used: int = 1
    expert_weights_scale: float = 1.0
    # what is added to the picked scores' sum before the weights are divided
    # by it (``norm_topk_prob``): the file's architecture states it
    expert_weights_eps: float = 1e-20
    # The experts HELD here, of the router's ``n_experts``: ``experts_held``
    # from ``experts_first`` on (0: all).  A pick outside them adds nothing
    # (expert parallelism's share of a layer, without its exchange).
    experts_first: int = 0
    experts_held: int = 0
    # Router outputs that are NOT experts (``longcat-flash``: identity
    # "zero-compute" experts): the router scores ``n_experts +
    # n_zero_experts`` outputs, and a pick ``e >= n_experts`` adds ``w_e``
    # times the layer's own input, wherever the token lives: no weight, no
    # exchange.
    n_zero_experts: int = 0
    # Attention sub-layers a layer (models/mla.py; ``longcat-flash``: 2).
    # A layer of 2 is attention, dense feed-forward, attention, dense
    # feed-forward, with ONE expert branch that reads the first sub-block's
    # normed rows and joins after the second; the latent ring then holds
    # ``n_layers * attn_sublayers`` leaves.
    attn_sublayers: int = 1
    # what the query (after ``W_qb``) and the normed latent (before it is
    # cached) are multiplied by (``mla_scale_q_lora`` / ``mla_scale_kv_lora``:
    # (dim / rank) ** 0.5; 1.0: not scaled)
    q_latent_scale: float = 1.0
    kv_latent_scale: float = 1.0
    # The attention KIND is the layer's (models/hybrid.py; ``exaone-moe``):
    # each layer in the file's order ``"window"`` (causal over the last
    # ``sliding_window`` positions; its cache leaf holds WINDOW slots that
    # wrap) or ``"global"`` (causal over all; a leaf of ``n_ctx`` slots);
    # empty for every other file, whose one ``sliding_window`` is a mask
    # term on a ring of ``n_ctx`` slots.  ``rope_kinds``: the kinds whose
    # layers rotate Q and K (the others attend unrotated).
    attn_kinds: tuple = ()
    rope_kinds: tuple = (WINDOW, GLOBAL)
    # a head's width where the file states it (``attention.key_length``;
    # 0: ``dim // n_heads``) and RMSNorm of Q and K over EACH head's width
    # (``attn_{q,k}_norm`` of ``head_dim``; ``qk_norm`` is over the whole
    # projection)
    head_width: int = 0
    qk_norm_per_head: bool = False
    # the softmax scale where it is not ``head_dim ** -0.5`` (0: that): a
    # ring whose rows hold several narrow heads side by side is read as
    # heads of the row's width, at the narrow heads' scale (models/lfm2.py)
    attn_scale: float = 0.0
    # Layers run several times (``ouro``; models/llama.py ``forward``): the
    # SAME ``n_layers`` layers run ``ut_steps`` passes a token, the final
    # norm after every pass, and pass ``t`` of layer ``l`` keeps its keys
    # and values in a cache leaf of its own (``t * n_layers + l``), so the
    # ring has ``cache_leaves`` leaves and the weights ``n_layers`` rows.
    # ``sandwich_norm``: a second RMSNorm AFTER each sub-block, before its
    # output joins the stream (``post_attention_norm`` / ``post_ffw_norm``).
    # ``exit_threshold``: the cumulative exit mass at which a token would
    # leave the loop; 1.0 (every token runs every pass) is the only one
    # served (``from_gguf`` refuses another by name).
    ut_steps: int = 1
    sandwich_norm: bool = False
    exit_threshold: float = 1.0
    # A ``phi4flash`` file (models/phi4flash.py; ``ssm_d_state`` > 0):
    # ``mixers`` names each layer ``"ssm"`` (Mamba-1: ``ssm_d_inner``
    # channels of ``ssm_d_state`` float32 states each, ``ssm_d_conv``
    # causal depthwise taps, a step size from ``ssm_dt_rank`` columns),
    # ``"window"`` / ``"full"`` (differential attention; the ONE full layer
    # writes the shared K/V leaf), ``"gmu"`` (a gate on the last ssm layer's
    # scan output) or ``"cross"`` (a query on the shared leaf).  Every norm
    # is a LayerNorm with a bias; nothing rotates.  A ``jamba`` file
    # (models/jamba.py) names its layers ``"ssm"`` (the same mixer with an
    # RMSNorm on each of dt, B and C: ``ssm_inner_norms``) or ``"attn"`` (GQA
    # on a ring, unrotated); its norms are RMSNorms.
    ssm_d_inner: int = 0
    ssm_d_state: int = 0
    ssm_d_conv: int = 0
    ssm_dt_rank: int = 0
    ssm_inner_norms: bool = False
    # a prefill slice's scan runs the slice kernel (ops/pallas/ssmscan.py).
    # Set by the engine, never by a file or a user: a TPU whose compiler
    # took the kernel's probe
    ssm_scan_kernel: bool = False
    # The program of a prefill slice that holds NO prompt's last token: it
    # stops after the layer that writes the shared leaf (nothing above it
    # writes a cache, and no logits are read).  Set by the engines' slice
    # plan (``CacheKind.slice_cfg``), never by a file.
    lower_only: bool = False

    @property
    def cache_leaves(self) -> int:
        """Leaves (rows of the stacked cache's leading axis) a sequence's
        ring holds: one an attention sub-layer and pass.  What everything
        that sizes, shards, pages, reports or counts a cache asks, never
        ``n_layers``."""
        return self.n_layers * self.attn_sublayers * self.ut_steps

    @property
    def sm_scale(self) -> float:
        return self.attn_scale or self.head_dim ** -0.5

    @property
    def n_held(self) -> int:
        """Experts whose weights this process holds."""
        return self.experts_held or self.n_experts

    @property
    def head_dim(self) -> int:
        return self.head_width or self.dim // self.n_heads

    @property
    def n_attn_sublayers(self) -> int:
        """Attention sub-layers of the whole stack: the leaves of a latent
        ring (``attn_sublayers`` a layer)."""
        return self.n_layers * self.attn_sublayers

    def n_attn_layers(self, kind: str) -> int:
        return sum(k == kind for k in self.attn_kinds)

    @property
    def window_slots(self) -> int:
        """Slots a window layer's cache leaf holds: the window, filled up
        to the 16 rows a bf16 tile stores by (what the decode kernel's
        block is a multiple of); never ``n_ctx``."""
        return -(-self.sliding_window // 16) * 16

    @property
    def cache_kind(self) -> str:
        """The NAME of the cache kind a sequence of this file holds
        (docs/KV_CACHE.md "Cache kinds"), decided here and nowhere else:
        ``ring``, ``window+summaries`` (models/eva.py), ``state+ring``
        (models/sala.py), ``latent-ring`` (models/mla.py) or
        ``window+global-ring`` (models/hybrid.py) or ``conv-state+ring``
        (models/lfm2.py) or ``ssm-state+window+shared-ring``
        (models/phi4flash.py) or ``ssm-state+ring`` (models/jamba.py);
        models/cache.py ``cache_of`` maps it to the
        kind's object, nothing else tests it."""
        if self.ssm_d_state:
            return SSM_WINDOW_SHARED if FULL in self.mixers else SSM_RING
        if self.conv_l_cache:
            return CONV_RING
        if self.mixers:
            return STATE_RING
        if self.attn_kinds:
            return WINDOW_GLOBAL_RING
        if self.kv_lora_rank:
            return LATENT_RING
        return WINDOW_SUMMARIES if self.eva_window else RING

    def n_layers_of(self, kind: str) -> int:
        return sum(m == kind for m in self.mixers)

    @property
    def n_linear_weights(self) -> int:
        """About how many weights the layers' matrices hold, every expert
        included: what the ``weight_format="auto"`` size test weighs."""
        if self.ssm_d_state:
            # (attention layers of either block: about 3 dim^2 each)
            n_attn = self.n_layers_of(WINDOW) + self.n_layers_of(FULL) \
                + self.n_layers_of(ATTN)
            return self.n_layers * 3 * self.dim * self.ffn_dim \
                + self.n_layers_of(SSM) * 3 * self.dim * self.ssm_d_inner \
                + n_attn * 3 * self.dim * self.dim
        if self.kv_lora_rank or self.attn_kinds or self.conv_l_cache:
            routed = 3 * self.dim * self.expert_ffn_dim * (
                self.n_held + self.n_shared_experts)
            n_dense = self.n_dense_layers if self.attn_sublayers == 1 \
                else self.n_attn_sublayers    # a dense one a sub-layer
            return self.n_attn_sublayers * 4 * self.dim * self.dim \
                + n_dense * 3 * self.dim * self.ffn_dim \
                + (self.n_layers - self.n_dense_layers) * routed
        ffn = 3 * self.dim * self.ffn_dim * max(self.n_experts, 1)
        return self.n_layers * (4 * self.dim * self.dim + ffn)

    @classmethod
    def from_gguf(cls, gf: GGUFFile, n_ctx: int | None = None) -> "ModelConfig":
        arch = gf.require_served()
        h = gf.hparam
        n_heads = int(h("attention.head_count"))
        vocab = h("vocab_size")
        if vocab is None:
            vocab = len(gf.metadata["tokenizer.ggml.tokens"])
        window = int(h("attention.sliding_window", 0) or 0)
        train_ctx = int(h("context_length", 4096))
        n_kv_heads = h("attention.head_count_kv", n_heads)
        mla = {}
        if arch == "lfm2moe":   # one entry a layer, 0 in a conv layer
            mla = _lfm2moe_fields(h, n_heads, n_kv_heads)
            n_kv_heads = max(n_kv_heads)
        if arch == "jamba":     # one entry a layer, 0 in a scan layer
            mla = _jamba_fields(h, n_heads, n_kv_heads)
            n_kv_heads = max(n_kv_heads)
        n_kv_heads = int(n_kv_heads)
        eva = {}
        if arch == "evabyte":
            eva = dict(
                eva_window=int(h("attention.window_size")),
                eva_chunk=int(h("attention.chunk_size")),
                n_pred_heads=int(h("prediction_heads", 1)),
                fp32_residual=True)
            W, C = eva["eva_window"], eva["eva_chunk"]
            if C < 1 or W % C:
                raise ValueError(
                    f"evabyte: attention.window_size {W} is no multiple of "
                    f"attention.chunk_size {C}")
            if n_kv_heads != n_heads:
                raise ValueError(
                    f"evabyte: {n_kv_heads} KV heads for {n_heads} heads: "
                    "the chunk summaries are per head (phi, mu), so the "
                    "block is multi-head only")
        sala = {}
        if arch == "minicpm-sala":
            names = {"minicpm4": "sp", "lightning-attn": "lin"}
            listed = str(h("mixer_types", "")).split(",")
            if any(m not in names for m in listed) \
                    or len(listed) != int(h("block_count")):
                raise ValueError(
                    f"minicpm-sala: mixer_types {listed!r} must name one of "
                    f"{sorted(names)} for each of the {h('block_count')} "
                    "layers")
            sala = dict(
                mixers=tuple(names[m] for m in listed),
                lin_heads=int(h("lightning.head_count", n_heads)),
                emb_scale=float(h("embedding_scale", 1.0)),
                residual_scale=float(h("residual_scale", 1.0)),
                logit_scale=float(h("logit_scale", 1.0)),
                fp32_logits=True,
                **{f"sp_{k}": int(h(f"sparse.{key}")) for k, key in (
                    ("kernel", "kernel_size"), ("stride", "kernel_stride"),
                    ("block", "block_size"), ("topk", "topk"),
                    ("window", "window_size"), ("init_blocks", "init_blocks"),
                    ("dense_len", "dense_len"))})
            ctx = int(n_ctx if n_ctx is not None else min(train_ctx, 4096))
            K, St, B = sala["sp_kernel"], sala["sp_stride"], sala["sp_block"]
            if St < 1 or B % St or K % St or K > B + St:
                raise ValueError(
                    f"minicpm-sala: sparse.block_size {B} and kernel_size "
                    f"{K} must be multiples of kernel_stride {St}, the "
                    "kernel no wider than a block and a stride")
            if ctx % B:
                raise ValueError(
                    f"minicpm-sala: n_ctx {ctx} is no multiple of "
                    f"sparse.block_size {B} (LFKT_MAX_CONTEXT_TOKENS)")
            if sala["lin_heads"] * (int(h("embedding_length")) // n_heads) \
                    != int(h("embedding_length")):
                raise ValueError(
                    "minicpm-sala: lightning.head_count x head width must "
                    "be the embedding length")
        if arch == "deepseek2":
            mla = _deepseek2_fields(h, n_heads)
        if arch == "deepseek32":
            mla = _deepseek32_fields(h, n_heads)
        if arch == "exaone-moe":
            mla = _exaone_moe_fields(h, n_heads, window)
        if arch == "longcat-flash":
            mla = _longcat_fields(h, n_heads, int(h("embedding_length")))
        if arch == "ouro":
            mla = _ouro_fields(h)
        if arch == "phi4flash":
            mla = _phi4flash_fields(h, n_heads, n_kv_heads, window)
        return cls(
            vocab_size=int(vocab),
            dim=int(h("embedding_length")),
            n_layers=int(h("block_count")),
            n_heads=n_heads,
            n_kv_heads=n_kv_heads,
            ffn_dim=int(h("feed_forward_length")),
            n_ctx=int(n_ctx if n_ctx is not None else min(train_ctx, 4096)),
            rope_theta=float(h("rope.freq_base", 10000.0)),
            rms_eps=float(h("attention.layer_norm_rms_epsilon", 1e-5)),
            sliding_window=window,
            tie_embeddings="output.weight" not in gf.tensors,
            n_experts=int(h("expert_count", 0) or 0),
            n_experts_used=int(h("expert_used_count", 0) or 0),
            # llama.cpp's olmoe graph: build_moe_ffn(..., norm_w=false) and
            # build_norm over the whole Qcur/Kcur; deepseek2 reads its key
            norm_topk_prob=mla.pop("norm_topk_prob", False),
            qk_norm=arch == "olmoe",
            qk_norm_per_head=arch in ("exaone-moe", "lfm2moe"),
            rope_neox=arch in NEOX_ROPE_ARCHITECTURES,
            **eva,
            **sala,
            **mla,
        )


def _deepseek2_fields(h, n_heads: int, arch: str = "deepseek2") -> dict:
    """The ``deepseek2`` keys (gguf/constants.py) as ``ModelConfig``
    fields (``arch``: the architecture an error names: ``longcat-flash``
    reads the same keys); a ValueError naming what the block here cannot
    compute."""
    import math

    def need(key):
        v = h(key)
        if v is None:
            raise ValueError(f"{arch}: the file lacks <arch>.{key}")
        return v

    r_q, r_kv = int(h("attention.q_lora_rank", 0) or 0), \
        int(need("attention.kv_lora_rank"))
    d_r = int(need("rope.dimension_count"))
    d_qk, d_v = int(need("attention.key_length")), \
        int(need("attention.value_length"))
    if not r_q:
        raise ValueError(
            f"{arch}: attention.q_lora_rank is 0 (the lite files' plain "
            "query projection): the block here has the query latent only")
    if d_qk <= d_r or d_r % 2:
        raise ValueError(
            f"{arch}: attention.key_length {d_qk} must exceed the even "
            f"rope.dimension_count {d_r} (a head's key is its unrotated "
            "part, then the shared rotated one)")
    yarn = {}
    if str(h("rope.scaling.type", "none")) == "yarn":
        factor = float(need("rope.scaling.factor"))
        # llama.cpp's key holds 0.1 * mscale_all_dim
        log_mul = float(h("rope.scaling.yarn_log_multiplier", 0.0) or 0.0)
        m = log_mul * math.log(factor) + 1.0 if factor > 1 else 1.0
        yarn = dict(
            rope_yarn_factor=factor,
            rope_yarn_orig_ctx=int(need(
                "rope.scaling.original_context_length")),
            rope_yarn_beta_fast=float(h("rope.scaling.yarn_beta_fast", 32.0)),
            rope_yarn_beta_slow=float(h("rope.scaling.yarn_beta_slow", 1.0)),
            attn_mscale=m * m)
    return dict(
        q_lora_rank=r_q, kv_lora_rank=r_kv, qk_nope_dim=d_qk - d_r,
        qk_rope_dim=d_r, v_head_dim=d_v, **_routed_fields(h, arch),
        **yarn)


def _deepseek32_fields(h, n_heads: int) -> dict:
    """The ``deepseek32`` keys (gguf/constants.py) as ``ModelConfig``
    fields: ``deepseek2``'s, and the indexer's three; a ValueError naming
    what the block here cannot compute."""
    arch = "deepseek32"
    fields = _deepseek2_fields(h, n_heads, arch)
    heads, width, topk = (int(h(f"attention.indexer.{key}", 0) or 0)
                          for key in ("head_count", "key_length", "top_k"))
    if min(heads, width, topk) < 1:
        raise ValueError(
            f"{arch}: the file lacks <arch>.attention.indexer.head_count / "
            "key_length / top_k: without its indexer the file is a "
            "deepseek2 one, and says so")
    if width < fields["qk_rope_dim"]:
        raise ValueError(
            f"{arch}: attention.indexer.key_length {width} is narrower than "
            f"rope.dimension_count {fields['qk_rope_dim']}: an index key's "
            "first columns are its rotated part")
    return dict(fields, index_heads=heads, index_dim=width, index_topk=topk,
                index_norm_eps=float(h("attention.indexer.layer_norm_epsilon",
                                       1e-6)))


def _longcat_fields(h, n_heads: int, dim: int) -> dict:
    """The ``longcat-flash`` keys (gguf/constants.py) as ``ModelConfig``
    fields: ``deepseek2``'s attention keys and routed keys, two attention
    sub-layers a layer, router outputs that are identity experts, and the
    two ``mla_scale_*`` factors; a ValueError naming what the block here
    cannot compute."""
    arch = "longcat-flash"
    fields = _deepseek2_fields(h, n_heads, arch)
    kind = str(h("expert_zero_type", "identity"))
    n_zero = int(h("expert_zero_count", 0) or 0)
    if n_zero and kind != "identity":
        raise ValueError(
            f"{arch}: expert_zero_type {kind!r} is not served: a zero "
            "expert here is the identity (a constant or learned-vector one "
            "is ROADMAP B-I 4)")
    if fields["n_dense_layers"] or fields["n_shared_experts"] \
            or fields["n_expert_groups"] > 1 or "rope_yarn_factor" in fields:
        raise ValueError(
            f"{arch}: leading dense layers, a shared expert, router groups "
            "or rope scaling are not this architecture's")
    return dict(
        fields, attn_sublayers=2, n_zero_experts=n_zero,
        q_latent_scale=(dim / fields["q_lora_rank"]) ** 0.5
        if h("attention.scale_q_lora", False) else 1.0,
        kv_latent_scale=(dim / fields["kv_lora_rank"]) ** 0.5
        if h("attention.scale_kv_lora", False) else 1.0)


def _ouro_fields(h) -> dict:
    """The ``ouro`` keys (gguf/constants.py) as ``ModelConfig`` fields; a
    ValueError naming what neither engine can serve."""
    steps = int(h("ut_steps", 1) or 1)
    threshold = float(h("early_exit_threshold", 1.0))
    if steps < 1:
        raise ValueError(f"ouro: ut_steps {steps}: a token runs one pass or more")
    if threshold < 1.0:
        raise ValueError(
            f"ouro: early_exit_threshold {threshold:g} is not served (1.0 "
            "is): a token that leaves the loop early writes no keys and "
            "values into the deeper passes' cache leaves, which every later "
            "token's deeper passes attend to, and neither engine can serve "
            "a leaf with holes yet (ROADMAP B-I 12)")
    return dict(ut_steps=steps, sandwich_norm=True, exit_threshold=threshold,
                head_width=int(h("attention.key_length", 0) or 0))


def _routed_fields(h, arch: str) -> dict:
    """The keys of a stack of leading dense layers, then routed ones with
    a grouped router, shared experts and a HELD share of the experts
    (``deepseek2``, ``exaone-moe``: gguf/constants.py; models/routed.py)."""
    n_exp = int(h("expert_count", 0) or 0)
    groups = int(h("expert_group_count", 1) or 1)
    used_groups = int(h("expert_group_used_count", groups) or groups)
    if n_exp % groups or not 1 <= used_groups <= groups:
        raise ValueError(
            f"{arch}: {n_exp} experts in {groups} groups, "
            f"{used_groups} used")
    k = int(h("expert_used_count", 0) or 0)
    if n_exp and (n_exp // groups < 2 or k > used_groups * (n_exp // groups)):
        raise ValueError(
            f"{arch}: a group's score is its two largest, and "
            f"{k} picks must fit {used_groups} groups of {n_exp // groups}")
    gating = {1: "softmax", 2: "sigmoid"}.get(
        int(h("expert_gating_func", 1) or 1))
    if gating is None:
        raise ValueError(
            f"{arch}: expert_gating_func {h('expert_gating_func')!r} "
            "(1: softmax, 2: sigmoid)")
    first = int(h("expert_held_first", 0) or 0)
    held = int(h("expert_held_count", 0) or 0)
    if held and not 0 <= first <= first + held <= n_exp:
        raise ValueError(
            f"{arch}: experts held {first}..{first + held} of {n_exp}")
    return dict(
        n_dense_layers=int(h("leading_dense_block_count", 0) or 0),
        expert_ffn_dim=int(h("expert_feed_forward_length", 0) or 0),
        n_shared_experts=int(h("expert_shared_count", 0) or 0),
        expert_gating=gating, n_expert_groups=groups,
        n_groups_used=used_groups,
        expert_weights_scale=float(h("expert_weights_scale", 1.0) or 1.0),
        norm_topk_prob=bool(h("expert_weights_norm", False)),
        experts_first=first, experts_held=held)


def _check_kv_head_array(arch: str, kv_heads, n_layers: int, n_heads: int,
                         other: str) -> None:
    """``attention.head_count_kv`` of a file whose mixer kind is the
    layer's: an array with one entry a layer, 0 in a layer of the ``other``
    kind, ONE count in the attention layers; a ValueError naming what is
    not."""
    if not isinstance(kv_heads, (list, tuple)) or len(kv_heads) != n_layers:
        raise ValueError(
            f"{arch}: attention.head_count_kv must be an array with one "
            f"entry for each of the {n_layers} layers (0: a {other} layer)")
    counts = {int(n) for n in kv_heads} - {0}
    if len(counts) != 1 or n_heads % max(counts):
        raise ValueError(
            f"{arch}: the attention layers' KV heads {sorted(counts)} must "
            f"be one count that divides the {n_heads} heads")


def _lfm2moe_fields(h, n_heads: int, kv_heads) -> dict:
    """The ``lfm2moe`` keys (gguf/constants.py) as ``ModelConfig`` fields; a
    ValueError naming what the block here cannot compute."""
    n_layers = int(h("block_count"))
    taps = int(h("shortconv.l_cache", 0) or 0)
    _check_kv_head_array("lfm2moe", kv_heads, n_layers, n_heads, "conv")
    if taps < 2:
        raise ValueError(
            f"lfm2moe: shortconv.l_cache {taps}: a conv layer has two taps "
            "or more (its cache is the inputs of all but the newest)")
    if str(h("rope.scaling.type", "none")) not in ("none", "linear") \
            or float(h("rope.scaling.factor", 1.0) or 1.0) != 1.0:
        raise ValueError("lfm2moe: rope.scaling is not served")
    routed = _routed_fields(h, "lfm2moe")
    if routed["n_shared_experts"] or routed["experts_held"]:
        raise ValueError(
            "lfm2moe: a shared expert or a held share of the experts is not "
            "this architecture's")
    return dict(mixers=tuple(ATTN if int(n) else CONV for n in kv_heads),
                conv_l_cache=taps,
                head_width=int(h("attention.key_length", 0) or 0),
                # the family's router divides by the picked scores' sum + 1e-6
                expert_weights_eps=1e-6, **routed)


def _ssm_sizes(h, arch: str) -> dict:
    """llama.cpp's ``ssm.*`` keys as the ``ssm_d_*`` fields; a ValueError
    naming the ones a file lacks."""
    d_inner, d_state, d_conv, dt_rank = (
        int(h(f"ssm.{key}", 0) or 0) for key in
        ("inner_size", "state_size", "conv_kernel", "time_step_rank"))
    if min(d_inner, d_state, dt_rank) < 1 or d_conv < 2:
        raise ValueError(
            f"{arch}: the file lacks <arch>.ssm.inner_size / state_size / "
            "time_step_rank, or ssm.conv_kernel is under 2 taps")
    return dict(ssm_d_inner=d_inner, ssm_d_state=d_state, ssm_d_conv=d_conv,
                ssm_dt_rank=dt_rank)


def _jamba_fields(h, n_heads: int, kv_heads) -> dict:
    """The ``jamba`` keys (gguf/constants.py) as ``ModelConfig`` fields; a
    ValueError naming what the block here cannot compute."""
    arch = "jamba"
    n_layers = int(h("block_count"))
    _check_kv_head_array(arch, kv_heads, n_layers, n_heads, "scan")
    if all(int(n) for n in kv_heads):
        raise ValueError(
            f"{arch}: attention.head_count_kv names no scan layer (a 0)")
    if int(h("expert_count", 0) or 0) > 1:
        raise ValueError(
            f"{arch}: expert_count {h('expert_count')}: the block here has "
            "the dense feed-forward in every layer (the family's routed "
            "layers are ROADMAP B-I 14)")
    if int(h("attention.sliding_window", 0) or 0):
        raise ValueError(f"{arch}: attention.sliding_window is not served: "
                         "the attention layers here are causal over all")
    return dict(mixers=tuple(ATTN if int(n) else SSM for n in kv_heads),
                head_width=int(h("attention.key_length", 0) or 0),
                ssm_inner_norms=True, **_ssm_sizes(h, arch))


def _phi4flash_fields(h, n_heads: int, n_kv: int, window: int) -> dict:
    """The ``phi4flash`` keys (gguf/constants.py) as ``ModelConfig`` fields;
    a ValueError naming what the block here cannot compute."""
    arch = "phi4flash"
    n_layers, dim = int(h("block_count")), int(h("embedding_length"))
    listed = str(h("mixer_types", "")).split(",")
    if len(listed) != n_layers or any(
            m not in (SSM, WINDOW, FULL, GMU, CROSS) for m in listed):
        raise ValueError(
            f"{arch}: mixer_types {listed!r} must name one of ssm, window, "
            f"full, gmu, cross for each of the {n_layers} layers")
    # the stack this block walks: (ssm, window) pairs, ONE (ssm, full)
    # pair, (gmu, cross) pairs
    n_low = listed.index(FULL) - 1 if FULL in listed else -1
    if n_low < 0 or n_low % 2 or (n_layers - n_low) % 2 or tuple(listed) != (
            (SSM, WINDOW) * (n_low // 2) + (SSM, FULL)
            + (GMU, CROSS) * ((n_layers - n_low - 2) // 2)):
        raise ValueError(
            f"{arch}: mixer_types {listed!r}: the block here is (ssm, "
            "window) pairs, one (ssm, full) pair, then (gmu, cross) pairs")
    sizes = _ssm_sizes(h, arch)
    d_k = int(h("attention.key_length", 0) or 0) or dim // n_heads
    if n_heads % 2 or n_kv % 2 or (n_heads // 2) % (n_kv // 2) \
            or 2 * d_k != 128:
        raise ValueError(
            f"{arch}: {n_heads} heads on {n_kv} KV heads of {d_k}: "
            "differential attention pairs even and odd heads, and the block "
            "here lays a pair's two 64-wide keys side by side in one row")
    if window < 1:
        raise ValueError(f"{arch}: attention.sliding_window {window}")
    return dict(mixers=tuple(listed), head_width=d_k, **sizes)


def _exaone_moe_fields(h, n_heads: int, window: int) -> dict:
    """The ``exaone-moe`` keys (gguf/constants.py) as ``ModelConfig``
    fields; a ValueError naming what the block here cannot compute."""
    period = int(h("attention.sliding_window_pattern", 0) or 0)
    n_layers = int(h("block_count"))
    if period < 2 or window < 1:
        raise ValueError(
            f"exaone-moe: attention.sliding_window_pattern {period} and "
            f"attention.sliding_window {window}: the block here is window "
            "layers with every pattern-th layer global, window >= 1")
    d_k = int(h("attention.key_length", 0) or 0)
    if d_k != int(h("attention.value_length", d_k) or d_k):
        raise ValueError(
            "exaone-moe: attention.key_length and value_length differ")
    n_kv = int(h("attention.head_count_kv", n_heads))
    if n_heads % n_kv:
        raise ValueError(
            f"exaone-moe: {n_heads} heads on {n_kv} KV heads")
    if str(h("rope.scaling.type", "none")) not in ("none", "linear") \
            or float(h("rope.scaling.factor", 1.0) or 1.0) != 1.0:
        raise ValueError(
            "exaone-moe: rope.scaling is not served (rope scaling per "
            "layer kind: ROADMAP B-I 1)")
    # llama.cpp's ``set_swa_pattern(n)``: layer i is a window layer unless
    # (i + 1) % n == 0; the family's hybrid rule rotates the window layers'
    # Q and K and leaves the global layers' unrotated
    kinds = tuple(GLOBAL if (i + 1) % period == 0 else WINDOW
                  for i in range(n_layers))
    return dict(attn_kinds=kinds, rope_kinds=(WINDOW,), head_width=d_k,
                **_routed_fields(h, "exaone-moe"))


# Canonical full-size configs (for synthesis / benches; no network egress, so
# bench models are built from these shapes with random weights).
LLAMA3_8B = ModelConfig(
    vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    ffn_dim=14336, n_ctx=1024, rope_theta=500000.0, rms_eps=1e-5,
)
MISTRAL_7B = ModelConfig(
    vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    ffn_dim=14336, n_ctx=1024, rope_theta=1000000.0, rms_eps=1e-5,
)
