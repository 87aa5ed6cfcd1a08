"""Model configurations for the flagship decoder family.

Sizes mirror the models the reference benchmarks with
(GPT-2 1.5B for flash-checkpoint, Llama2-7B for ATorch throughput —
BASELINE.md #3-#11), plus small configs for tests and CI.
"""

import math
import re
from dataclasses import dataclass, field, replace
from typing import Callable, Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny"
    vocab_size: int = 50304          # padded to a multiple of 128 for the MXU
    n_layer: int = 2
    n_head: int = 4
    n_kv_head: Optional[int] = None  # GQA; None = n_head
    d_model: int = 128
    d_ff: int = 512
    max_seq: int = 256
    # architecture family
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    # swiglu | gelu | relu2 (two matrices around relu(.)², no gate: the
    # experts and the shared expert of a ``layer_pattern`` model)
    act: str = "swiglu"
    pos: str = "rope"                # rope | learned | none
    # False = bidirectional attention (BERT-family encoders; the TP/SP
    # machinery is identical — same weights, different mask)
    causal: bool = True
    # GLM-family prefix-LM (reference: atorch's TP GLM blocks,
    # distributed_modules/transformer.py:270): bidirectional attention
    # over a per-sequence prefix, causal over the tail. The prefix
    # lengths arrive at runtime as batch["prefix_len"] ([B] int32).
    prefix_lm: bool = False
    # GPTNeoX/GPT-J-style parallel residual (reference: atorch's TP
    # GPTNeoX blocks, transformer.py:838): attention and MLP both read
    # the same layer input, x = x + attn(ln1 x) + mlp(ln2 x) — shortens
    # the critical path and lets XLA overlap the two matmul chains
    parallel_residual: bool = False
    # Mistral-style sliding-window attention (0 = unlimited): each query
    # attends to the last attn_window positions. Causal only; mutually
    # exclusive with prefix_lm. The flash kernel skips (and never DMAs)
    # blocks outside the window, so attention cost is O(S·window). None
    # reads as 0 everywhere (a published ``sliding_window: null``).
    attn_window: Optional[int] = 0
    # flash-kernel tile sizes (128-multiples; tunable by strategy search).
    # 1024 measured +12% step throughput over 512 on v5e at s=1024
    # (less grid overhead); _fit_block caps them to the actual sequence.
    attn_block_q: int = 1024
    attn_block_k: int = 1024
    rope_theta: float = 10000.0
    # a SCALED rope (YaRN, the ``transformers`` reading of a published
    # ``rope_type: yarn``; 0 = none): a pair whose wavelength fits
    # ``rope_beta_fast`` times and more into ``rope_original_max``
    # positions keeps its frequency, one that fits ``rope_beta_slow``
    # times or fewer turns ``rope_factor`` times slower, those between
    # are blended linearly by pair index, and cos and sin are multiplied
    # by ``rope_attn_factor`` (0 = 0.1 ln(rope_factor) + 1), so scores
    # carry its square. Which layers it turns ``ATTN_KINDS`` says: every
    # layer of a model without ``layer_types``. Training path only
    rope_factor: float = 0.0
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attn_factor: float = 0.0
    # RMS/LayerNorm (cfg.norm) over the WHOLE q and k projections
    # (n_head·head_dim / kv_heads·head_dim wide, one scale each), before
    # the head split and rope — OLMoE's q_norm/k_norm
    qk_norm: bool = False
    # RMSNorm of q and k PER HEAD (one scale of head_dim each, shared by
    # the heads), after the head split and before rope — Qwen3's and
    # Keye's q_norm/k_norm. Not ``qk_norm``, whose statistic spans the
    # whole projection
    qk_head_norm: bool = False
    # channels of one head where they are not d_model // n_head (0 =
    # that): wq and wo are n_head · head_dim wide, which need not be
    # d_model (Keye: 32 x 128 over 2048)
    d_head: int = 0
    # an attention KIND per layer (a published ``layer_types``; "" =
    # every layer the model's one kind): one letter a layer of the
    # trunk, the dense prefix first, each a row of ``ATTN_KINDS`` (its
    # window, its rope). All kinds have the same parameters, so a stack
    # stays one stack and is scanned a period at a time. Training path
    # only
    layer_types: str = ""
    # a sigmoid gate on the attention's output, per channel, from the
    # layer's normed input: o <- o * sigmoid(h W_g) before W_o
    attn_gate: bool = False
    # a norm on each part's OUTPUT before the residual add, beside the
    # one on its input: x <- x + norm(part(norm(x))), four norms a layer
    post_norm: bool = False
    # token embeddings times sqrt(d_model) (afmoe's ``mup_enabled``)
    scale_embedding: bool = False
    # the norms' epsilon (None = 1e-6 RMSNorm, 1e-5 LayerNorm)
    norm_eps: Optional[float] = None
    tie_embeddings: bool = True
    # standard deviation the token embeddings are drawn with
    # (``decoder.init``). Every matrix is drawn at 1 / sqrt(fan-in), so
    # a block's output is near 1 a channel; at 0.02 a fresh residual
    # stream is what the blocks added and not the token's, and a fresh
    # router sends whole stretches of a sequence to the same experts
    embed_init_std: float = 0.02
    # numerics
    dtype: str = "bfloat16"          # activation/compute dtype
    param_dtype: str = "float32"
    # rematerialisation policy: none | full. none keeps every
    # intermediate of the forward; full recomputes the layer in the
    # backward but what is quadratic to remake and linear to hold: a
    # selecting model's selection and alignment derivative, and — where
    # the attention runs the flash kernels and their forward executes
    # 2,048 keys a query or more (the kernels' own count of whole tiles,
    # pallas_attention.forward_keys; decoder.keeps_attention_output) —
    # the kernel's output and row statistics, 2·D + 4 bytes a (query,
    # head). What is kept is read from the shape; there is no tier to
    # name
    remat: str = "none"
    # MoE (0 = dense)
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    moe_gating: str = "topk"         # topk | switch (top-1 w/ jitter)
    moe_jitter: float = 0.0          # switch-gating router noise (train only)
    moe_aux_coef: float = 0.0        # load-balancing loss coefficient
    moe_z_coef: float = 0.0          # router z-loss coefficient
    moe_alltoall: bool = False       # explicit shard_map all-to-all dispatch
    moe_impl: str = "dense"          # dense (capacity) | ragged (dropless)
    # ragged+ep: per-destination all-to-all buffer bound, as a multiple
    # of the balanced share (t·k/ep). Memory/wire bound ONLY — compute
    # stays ragged; tokens past the bound drop. ep (the worst case)
    # guarantees droplessness at ep× wire cost.
    moe_a2a_bound: float = 2.0
    # top-k combine weights divided by their sum (Mixtral) or the raw
    # softmax probabilities at the chosen experts (OLMoE's
    # norm_topk_prob=false). Switch gating is always raw.
    moe_renorm_topk: bool = True
    # what the top-k is taken over and the combine weights are made of:
    # softmax over the router's logits, or each logit's sigmoid
    # (DeepSeek-V3's ``noaux_tc`` family; its selection bias is a buffer
    # without gradient, zero at initialisation, and is not modelled)
    moe_score: str = "softmax"       # softmax | sigmoid
    # multiplies the combine weights after renormalisation
    routed_scaling_factor: float = 1.0
    # width of one expert's SwiGLU (0 = d_ff). With n_dense_layer > 0
    # d_ff is the dense prefix's width and this the experts'
    d_expert: int = 0
    # experts every token meets beside the routed ones, as ONE SwiGLU of
    # width n_shared_experts · d_expert (0 = none)
    n_shared_experts: int = 0
    # expert parallelism's share on this device: the router stays
    # n_experts wide and a token's k choices and weights are over all of
    # them, but only experts [expert_offset, expert_offset +
    # n_experts_held) live here and add to the layer's output
    # (0 = every expert is held). Ragged lowering only.
    n_experts_held: int = 0
    expert_offset: int = 0
    # leading layers with a dense MLP of d_ff in a routed model
    # (``first_k_dense_replace``); the other n_layer - n_dense_layer are
    # routed. params holds each kind stacked by itself
    n_dense_layer: int = 0
    # latent attention (MLA; kv_lora_rank 0 = plain q/k/v projections):
    # q through rank q_lora_rank (0 = ONE matrix at full rank, a
    # published ``q_lora_rank: null``) to n_head × (qk_nope + qk_rope)
    # channels, k and v through rank kv_lora_rank (+ qk_rope channels all
    # heads share: rope's where ``pos`` is rope, and as they are where a
    # layer_pattern model has ``pos: none``, a published ``mla_use_nope``)
    # to n_head × (qk_nope ‖ v_head_dim). Training runs the expanded
    # form: MHA with scores over qk_nope + qk_rope channels and values
    # of v_head_dim, which may be narrower (192 against 128)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # multi-token prediction (DeepSeek-V3 §2.2): one extra routed block
    # on [norm(emb(t_{i+1})) ‖ norm(h_i)] predicting t_{i+2} through the
    # shared embedding and head; its cross-entropy enters the objective
    # times mtp_loss_coef. Training path only
    n_mtp_module: int = 0
    mtp_loss_coef: float = 0.3
    # a learned selection of keys (DeepSeek-Sparse-Attention; 0 = every
    # visible key): in every layer an indexer of ``index_n_heads`` query
    # heads x ``index_head_dim`` channels against ONE key head scores
    # each visible key, I_ts = sum_j w_tj relu(qI_tj . kI_s), and the
    # attention runs over the ``index_topk`` best (all of them where a
    # query sees no more). The indexer reads the layer's input detached
    # and is trained by its own term alone: ``indexer_loss_coef`` x the
    # KL from the attention's head-mean probabilities on the selection
    # (detached) to softmax of I there, mean over queries, summed over
    # layers. Training path only
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    indexer_loss_coef: float = 1.0
    # queries the indexer scores at a time (``sa_config``'s chunk): no
    # float [B, S, S] of index scores is ever whole
    index_chunk: int = 512
    # a trunk made of PARTS, x <- x + part(norm(x)) each
    # (NemotronH's ``hybrid_override_pattern``; "" = every layer an
    # attention and an MLP): one letter a part, the kinds and what each
    # asks in ``PART_RULES`` below (``S`` runs without rope). A ``-``
    # that follows another part is the second part of that part's LAYER
    # (a mixer + MLP layer, pre-norm twice: ``m-``, ``*-``), and so is an
    # ``e``, the ROUTED experts as a layer's second part (``Ge``, ``*e``;
    # a leading dense-MLP layer beside routed ones is spelled ``K-Ke``,
    # not ``n_dense_layer``); every other letter is a layer by itself,
    # and ``n_layer`` counts layers. Parameters are stacked kind by kind
    # and visited in this order. ``mtp_pattern`` is the prediction
    # module's layers, likewise. A ``C`` is a GATED SHORT CONVOLUTION
    # (LFM2's conv mixer), a mixer like ``m`` or ``K`` with no state but
    # the last ``conv_kernel`` - 1 tokens: [B | C | x] = u W_in (d -> 3d),
    # y = (C * conv(B * x)) W_out, the conv depthwise, causal, of
    # ``conv_kernel`` taps, without bias or activation, over d_model
    # channels; it has no field of its own beside ``conv_kernel``. A
    # leading dense layer whose mixer is a conv is spelled like any
    # other, by its two parts: ``C-C-*eCeCeCe`` is two conv + dense-MLP
    # layers, then an attention + routed layer and three conv + routed
    # ones (``n_dense_layer`` cannot say which mixer a dense layer has;
    # the pattern can). Training path only
    layer_pattern: str = ""
    mtp_pattern: str = ""
    # the Mamba-2 mixer (``M``): ``mamba_num_heads`` heads of
    # ``mamba_head_dim`` channels (d_inner their product), a state of
    # ``ssm_state_size`` a channel, B and C shared by the heads of each
    # of ``n_groups`` groups, a causal depthwise conv of ``conv_kernel``
    # taps over [x | B | C], the scan in chunks of ``ssm_chunk`` tokens
    # (ops/ssd.py), ``ssm_head_block`` heads at a time (0 = all at
    # once; a multiple or a divisor of a group's heads), a gated RMSNorm over each group at ``ssm_norm_eps``. The
    # time steps are drawn log-uniform in [time_step_min, time_step_max]
    # and floored, A uniform in [1, 16], D = 1
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    n_groups: int = 1
    conv_kernel: int = 4
    ssm_chunk: int = 128
    ssm_head_block: int = 0
    ssm_norm_eps: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # the Mamba-1 mixer (``m``): ``mamba_expand`` x d_model channels
    # with a state of ``ssm_state_size`` EACH and a decay a channel and
    # state (ops/selective_scan.py), the time step through a low rank
    # of ``mamba_dt_rank``, RMSNorms on that rank's input and on B and
    # C, a conv of ``conv_kernel`` taps over the channels alone. The
    # time step's bias is drawn as the Mamba-2 mixer's, A = 1..state in
    # every channel, D = 1
    mamba_expand: int = 0
    mamba_dt_rank: int = 0
    # the gated-delta-rule mixer (``G``; Gated DeltaNet,
    # ops/gated_delta.py): ``gdn_key_heads`` heads of ``gdn_key_dim``
    # channels for q and k, each shared by ``gdn_value_heads`` /
    # ``gdn_key_heads`` NEIGHBOURING value heads (repeat-interleave) of
    # ``gdn_value_dim`` channels; one write strength and one decay a
    # value head and token; a causal depthwise conv of ``conv_kernel``
    # taps over [q | k | v], no bias; the rule in chunks the op chooses
    # (``ops/gated_delta.py``: no size of the model); an RMSNorm over
    # each value head's read-out with ONE scale of ``gdn_value_dim``,
    # THEN the gate silu(z). The decay's A is
    # drawn uniform in [1, 16] and the time step's bias as the Mamba
    # mixers' (``time_step_*``)
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    # the delta-rule mixer with a decay a key channel (``K``; Kimi Delta
    # Attention, arXiv:2510.26692; ops/gated_delta.py with g a vector):
    # ``kda_heads`` heads of ``kda_head_dim`` key and as many value
    # channels, q, k and v each their own, no sharing; a causal depthwise
    # conv of ``conv_kernel`` taps over [q | k | v], no bias; the decay
    # g = -exp(A_log_h) softplus((x W_fa) W_fb + dt_bias), one a head
    # and KEY CHANNEL, through a low rank of ``kda_gate_rank``; one write
    # strength a head; an RMSNorm over each head's read-out with ONE
    # scale of ``kda_head_dim``, THEN a SIGMOID gate through the same
    # low rank, (x W_ga) W_gb. A and the time step's bias are drawn as
    # the ``G`` part's
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_gate_rank: int = 0
    # the share of a head's channels that rope turns, the FIRST ones
    # (rotate-half inside them; the rest pass untouched): a published
    # ``partial_rotary_factor``
    partial_rotary_factor: float = 1.0
    # every RMSNorm of the trunk (a part's input norm, the final norm,
    # the per-head q and k norms) multiplies by 1 + w and w starts at 0
    # (Qwen3-Next's and Gemma's form): w is what is stored, so weight
    # decay pulls the multiplier to 1. A layer_pattern model's field
    norm_zero_centered: bool = False
    # the shared expert's own gate: sigmoid(x w) of ONE d_model x 1
    # matrix times its output
    shared_expert_gate: bool = False
    # a selection of BLOCKS of keys with no parameters of its own
    # (InfLLM-v2, MiniCPM4's ``sparse_config``; the ``S`` part of a
    # ``layer_pattern``; 0 = none): keys mean-pooled over windows of
    # ``pool_window`` every ``pool_stride`` are scored by every query
    # head (softmax over the pooled keys whose window has ended), the
    # probabilities summed over the query heads of a KV head, a block of
    # ``sparse_block`` keys scored by the max over the pooled keys that
    # overlap it; the first ``select_init_blocks`` blocks and those of
    # the last ``select_local`` keys are taken whatever their score and
    # the best of the rest fill up to ``index_topk`` blocks a query and
    # KV head, ties to the lower block. Not differentiated. A sequence
    # of at most ``select_dense_len`` tokens runs plain causal
    # attention. ``index_chunk`` queries are scored at a time
    sparse_block: int = 0
    pool_window: int = 0
    pool_stride: int = 0
    select_init_blocks: int = 0
    select_local: int = 0
    select_dense_len: int = 0
    # a ``layer_pattern`` model's three multipliers (MiniCPM's
    # ``scale_emb``; ``scale_depth`` / sqrt(the PUBLISHED depth) on
    # every part's output; ``dim_model_base`` / d_model on the last
    # hidden state before the head)
    scale_emb: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # a latent around the routed experts (LatentMoE; 0 = none): the
    # router and the shared expert read the d_model-wide input, the
    # experts a projection of it to this width, and one projection back
    # follows the combine. Ragged lowering only
    moe_latent_size: int = 0
    # width of the shared expert where it is not n_shared_experts ·
    # d_expert (0 = that)
    d_shared_expert: int = 0
    # pipeline microbatches when the mesh has pp > 1 (0 → one per stage)
    pp_microbatches: int = 0
    # interleaved (circular) pipeline: v layer chunks per stage cut the
    # bubble to (P−1)/(M·v+P−1). The chunk→stage assignment permutes the
    # semantic layer order, so v>1 requires pp_stages to pin the stage
    # count the layout was built for (checkpoints stay well-defined on
    # other meshes via parallel.pipeline.semantic_layer_perm).
    pp_interleave: int = 1
    pp_stages: int = 0
    # stage-hop dtype override; None rides hops at the compute dtype
    # (bf16 models → half the ICI bytes, numerically free — see
    # parallel/pipeline.py module doc). Set "float32" to force wide hops.
    pp_boundary_dtype: Optional[str] = None
    # muP (train/mup.py): width of the base model hyperparams were tuned
    # at; None = standard parametrization. When set, attention uses 1/d
    # scaling and tied logits get the 1/width_mult MuReadout multiplier.
    mup_base_width: Optional[int] = None
    # fused lm-head cross-entropy (ops/fused_ce.py): chunk the vocab
    # axis with online logsumexp so the [B*S, vocab] f32 logits tensor
    # (~1 GiB at b8*s1024*v32k) never materializes. loss_fn falls back
    # to the unfused path automatically when the vocab axis is
    # tp-sharded (Megatron-style vocab parallelism splits the head
    # weight across chips; the chunk scan would force a gather).
    fused_ce: bool = True
    ce_block_v: int = 4096           # vocab chunk width (128-multiple)
    # fp8 GEMMs with delayed scaling in the MLP projections
    # (ops/fp8.py): forward operands e4m3, gradients e5m2, per-tensor
    # scales from rolling amax histories threaded through the train
    # state (state["fp8"], updated via the state-on-cotangent
    # convention). Numerics are identical on every backend (pre-fp8
    # chips upcast the already-quantized values to bf16); the
    # accelerate strategy enables it by default only where the MXU
    # consumes fp8 natively (v6e+, device_context.fp8_supported).
    fp8: bool = False
    # fused norm/residual kernels (ops/pallas_norm.py): rmsnorm /
    # layernorm with f32 statistics in one VMEM visit, and the
    # pre-norm residual add folded into the same kernel so
    # `x + attn_out -> norm(...)` is one HBM round-trip instead of
    # three. None = auto (on when the Pallas TPU path is available,
    # jnp fallback elsewhere — CPU/GPU programs are byte-identical to
    # the unfused build); True/False force it either way.
    fused_norm: Optional[bool] = None

    def __post_init__(self):
        if not self.embed_init_std > 0:
            raise ValueError(
                f"embed_init_std is a standard deviation, got "
                f"{self.embed_init_std!r}"
            )
        if self.moe_impl not in ("dense", "ragged"):
            raise ValueError(
                f"moe_impl must be 'dense' or 'ragged', got "
                f"{self.moe_impl!r}"
            )
        if self.moe_gating not in ("topk", "switch"):
            raise ValueError(
                f"moe_gating must be 'topk' or 'switch', got "
                f"{self.moe_gating!r}"
            )
        if self.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_score must be 'softmax' or 'sigmoid', got "
                f"{self.moe_score!r}"
            )
        if self.act not in ("swiglu", "gelu", "relu2"):
            raise ValueError(f"unknown act {self.act!r}")
        if self.pos not in ("rope", "learned", "none"):
            raise ValueError(f"unknown pos {self.pos!r}")
        if self.layer_pattern:
            self._check_pattern()
        elif (
            self.mtp_pattern or self.act == "relu2" or self.moe_latent_size
            or self.pos == "none"
        ):
            raise ValueError(
                "mtp_pattern, act='relu2', moe_latent_size and pos='none' "
                "belong to a layer_pattern model"
            )
        if self.n_experts_held:
            if self.moe_impl != "ragged":
                raise ValueError(
                    "n_experts_held needs moe_impl='ragged': the capacity "
                    "lowerings hold every expert"
                )
            if not (
                0 <= self.expert_offset
                and self.expert_offset + self.n_experts_held
                <= self.n_experts
            ):
                raise ValueError(
                    f"experts [{self.expert_offset}, {self.expert_offset} "
                    f"+ {self.n_experts_held}) are not among "
                    f"{self.n_experts}"
                )
        if self.n_dense_layer and not (
            self.n_experts and self.n_dense_layer < self.n_layer
        ):
            raise ValueError(
                "n_dense_layer is the dense prefix of a routed model: it "
                "needs n_experts > 0 and a routed layer after it"
            )
        if self.latent_attention:
            ranks = (
                self.kv_lora_rank, self.qk_nope_head_dim,
                self.qk_rope_head_dim,
            )
            if (
                not all(r > 0 for r in ranks) or self.qk_rope_head_dim % 2
                or self.q_lora_rank < 0
            ):
                raise ValueError(
                    "latent attention needs kv_lora_rank, qk_nope_head_dim, "
                    "an even qk_rope_head_dim and q_lora_rank >= 0 (0: q "
                    "at full rank)"
                )
            if not 0 < self.v_head_dim <= self.head_dim:
                raise ValueError(
                    "latent attention runs expanded, as MHA whose values "
                    "are no wider than its scores: v_head_dim must lie in "
                    f"(0, qk_nope_head_dim + qk_rope_head_dim], got "
                    f"{self.v_head_dim} against {self.qk_nope_head_dim} + "
                    f"{self.qk_rope_head_dim}"
                )
            if (
                self.pos == "learned"
                or self.qk_norm
                or self.kv_heads != self.n_head
            ):
                raise ValueError(
                    "latent attention is rope on its own channels (or, in "
                    "a layer_pattern model with pos 'none', no rotation "
                    "at all), MHA, no qk_norm"
                )
        if self.partial_rotary_factor != 1.0 and (
            not 0.0 < self.partial_rotary_factor < 1.0
            or (self.head_dim * self.partial_rotary_factor) % 2
        ):
            raise ValueError(
                "partial_rotary_factor leaves rope an even number of a "
                f"head's channels, in (0, 1]; got {self.partial_rotary_factor}"
            )
        if (
            self.partial_rotary_factor != 1.0 or self.norm_zero_centered
        ) and not self.layer_pattern:
            raise ValueError(
                "partial_rotary_factor and norm_zero_centered are a "
                "layer_pattern model's"
            )
        if self.shared_expert_gate and not self.n_shared_experts:
            raise ValueError("shared_expert_gate gates a shared expert")
        if self.sparse_block and "S" not in self.layer_pattern:
            raise ValueError(
                "sparse_block is the S part of a layer_pattern model"
            )
        if (self.scale_emb, self.residual_scale, self.logit_scale) != (
            1.0, 1.0, 1.0
        ) and not self.layer_pattern:
            raise ValueError(
                "scale_emb, residual_scale and logit_scale are a "
                "layer_pattern model's multipliers"
            )
        if self.selects_keys:
            if not (self.index_n_heads > 0 and self.index_head_dim > 0
                    and self.index_head_dim % 2 == 0):
                raise ValueError(
                    "a selection of keys needs an indexer: index_n_heads "
                    "and an even index_head_dim"
                )
            if (
                not self.causal or self.prefix_lm or self.attn_window
                or self.latent_attention or self.pos != "rope"
            ):
                raise ValueError(
                    "the selection is built for causal rope attention "
                    "with plain q/k/v projections and no window"
                )
        if self.qk_head_norm and (self.qk_norm or self.latent_attention):
            raise ValueError(
                "qk_head_norm norms each head of plain q and k "
                "projections; qk_norm norms them whole: one or the other"
            )
        if self.rope_factor and (
            self.rope_factor <= 1.0 or self.rope_original_max <= 0
            or not 0 < self.rope_beta_slow < self.rope_beta_fast
            or self.rope_attn_factor < 0 or self.pos != "rope"
            or self.latent_attention or self.selects_keys
            or self.layer_pattern or self.n_mtp_module
        ):
            raise ValueError(
                "a scaled rope needs rope_factor > 1, rope_original_max > 0 "
                "and 0 < rope_beta_slow < rope_beta_fast, on the plain "
                "attention of a rope model: latent attention, a key "
                "selection, layer_pattern's parts and a prediction module "
                "build tables of their own"
            )
        if self.layer_types:
            odd = set(self.layer_types) - set(ATTN_KINDS)
            if odd or len(self.layer_types) != self.n_layer:
                raise ValueError(
                    "layer_types names each of the n_layer layers "
                    f"{_kinds_said()}; got {self.layer_types!r} for "
                    f"{self.n_layer} layers"
                )
            rules = [ATTN_KINDS[kind] for kind in set(self.layer_types)]
            if (
                self.pos != "rope" or not self.causal or self.prefix_lm
                or self.latent_attention or self.selects_keys
                or self.layer_pattern or self.n_mtp_module or self.fp8
                or (any(r.window for r in rules) and not self.attn_window)
                or (
                    any(r.rope == "scaled" for r in rules)
                    and not self.rope_factor
                )
            ):
                raise ValueError(
                    "layer_types is for causal plain-attention layers of "
                    "a rope model, with attn_window set where a kind has "
                    "a window and rope_factor where one is turned by the "
                    "scaled table: no prefix-LM, latent attention, key "
                    "selection, layer_pattern, prediction module or fp8"
                )
        if (self.attn_gate or self.post_norm) and (
            self.latent_attention or self.selects_keys
            or (self.layer_pattern and self.post_norm)
            or self.parallel_residual or self.fp8
        ):
            raise ValueError(
                "attn_gate and post_norm are built into the plain "
                "attention layer: no latent attention, key selection, "
                "parallel residual or fp8, and no post_norm in a "
                "layer_pattern model"
            )
        if self.n_mtp_module not in (0, 1):
            raise ValueError(
                "one multi-token-prediction module is built; "
                f"n_mtp_module={self.n_mtp_module}"
            )
        if self.remat not in ("none", "full"):
            # a typo'd policy would silently train with NO remat and
            # OOM configs that only fit WITH one — fail at build time
            raise ValueError(
                f"unknown remat policy {self.remat!r}: remat is 'none' "
                "or 'full' (the graded and offloaded tiers — "
                "dots_saveable, save_attn, save_qkv, save_qkv_gate, "
                "save_dots, offload_attn, save_qkv_offload — went in "
                "PR 51; 'full' keeps by shape what they kept by name)"
            )
        for name in ("attn_block_q", "attn_block_k"):
            b = getattr(self, name)
            if b <= 0 or b % 128:
                raise ValueError(
                    f"{name} must be a positive multiple of 128, got {b}"
                )
        if self.attn_window:
            if self.attn_window < 0:
                raise ValueError(
                    f"attn_window must be >= 0, got {self.attn_window}"
                )
            if not self.causal:
                raise ValueError("attn_window requires causal=True")
            if self.prefix_lm:
                raise ValueError(
                    "attn_window and prefix_lm are mutually exclusive"
                )

    def _check_pattern(self):
        """What each of a ``layer_pattern`` model's kinds needs
        (``PART_RULES``), then what ties two kinds or the whole model."""
        for name in ("layer_pattern", "mtp_pattern"):
            odd = set(getattr(self, name)) - set(PART_RULES)
            if odd:
                kinds = [f"{c} ({r.what})" for c, r in PART_RULES.items()]
                raise ValueError(
                    f"{name} is made of {', '.join(kinds[:-1])} and "
                    f"{kinds[-1]}; got {sorted(odd)}"
                )
        if pattern_layers(self.layer_pattern) != self.n_layer:
            raise ValueError(
                f"layer_pattern names {pattern_layers(self.layer_pattern)} "
                f"layers, n_layer is {self.n_layer}"
            )
        if bool(self.mtp_pattern) != bool(self.n_mtp_module):
            raise ValueError(
                "a layer_pattern model's prediction module runs "
                "mtp_pattern: both or neither"
            )
        letters = self.layer_pattern + self.mtp_pattern
        for letter, rule in PART_RULES.items():
            if rule.trunk_only and letter in self.mtp_pattern:
                raise ValueError(
                    f"{letter} parts are the trunk's: a prediction module's "
                    "selection or read-out is handed over by no one"
                )
            if letter not in letters:
                continue
            sized = all(getattr(self, name) > 0 for name in rule.needs)
            if not sized or (rule.unless and rule.unless(self)):
                raise ValueError(rule.refusal)
            for wrong, refusal in rule.also:
                if wrong(self):
                    raise ValueError(refusal)
        if "S" not in letters and (self.index_topk or self.sparse_block):
            raise ValueError(
                "index_topk and sparse_block in a layer_pattern model are "
                "its S parts'"
            )
        if "E" in letters and "e" in letters:
            raise ValueError(
                "a pattern routes by E (a layer by itself) or by e (a "
                "layer's second part): the two share one stack"
            )
        if self.latent_attention and (
            "S" in letters or self.attn_gate or self.mtp_pattern
        ):
            raise ValueError(
                "latent attention as a part is the * of a layer_pattern "
                "model's trunk: no S part, output gate or prediction module"
            )
        if (
            self.n_dense_layer or self.selects_keys
            or self.parallel_residual or self.prefix_lm or self.fp8
            or self.norm != "rmsnorm" or self.pos == "learned"
            or self.qk_norm
        ):
            raise ValueError(
                "a layer_pattern model is RMSNorm parts, x + part(norm(x)) "
                "each: no dense prefix (a leading mixer + dense-MLP layer "
                "is spelled K-, *-, m-), learned key selection, parallel "
                "residual, prefix-LM, fp8, position table or "
                "whole-projection q/k norm; latent attention may be a "
                "part (*)"
            )

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head or self.n_head

    @property
    def d_inner(self) -> int:
        """Channels of a Mamba-2 mixer."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def d_inner1(self) -> int:
        """Channels of a Mamba-1 mixer."""
        return self.mamba_expand * self.d_model

    @property
    def conv_dim(self) -> int:
        """Channels the mixer's conv runs over: [x | B | C]."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def shared_expert_width(self) -> int:
        return self.d_shared_expert or (
            self.n_shared_experts * self.expert_width
        )

    @property
    def expert_in(self) -> int:
        """Width of the rows the routed experts read and write."""
        return self.moe_latent_size or self.d_model

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def head_dim(self) -> int:
        if self.latent_attention:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.d_head or self.d_model // self.n_head

    @property
    def selects_keys(self) -> bool:
        """A LEARNED selection of keys (the indexer); a selection of
        blocks by the keys themselves is ``selects_blocks``."""
        return self.index_topk > 0 and not self.sparse_block

    @property
    def selects_blocks(self) -> bool:
        return self.sparse_block > 0

    @property
    def select_block(self) -> int:
        """Keys a unit of the selection, and ``select_groups``, the
        selections a selecting layer makes (one a KV head), under the
        names the benchmark's ``selected`` comparison states them by
        (``benchmarks/lib/selected.py``). ONLY a model that selects
        blocks has them: the comparison reads their absence as "keys,
        one selection a layer", so elsewhere they are no attribute."""
        if not self.sparse_block:
            raise AttributeError("select_block: this model selects no blocks")
        return self.sparse_block

    @property
    def select_groups(self) -> int:
        if not self.sparse_block:
            raise AttributeError("select_groups: this model selects no blocks")
        return self.kv_heads

    def selects_at(self, seq_len: int) -> bool:
        """Whether an ``S`` part's queries choose at ``seq_len``: a
        sequence of at most ``select_dense_len`` runs plain causal
        attention."""
        return self.selects_blocks and seq_len > self.select_dense_len

    @property
    def lightning_params(self) -> int:
        """One ``L`` part's matrices: q, k, v, the output gate and o,
        ``n_head`` heads of ``head_dim`` each."""
        return 5 * self.d_model * self.n_head * self.head_dim

    @property
    def gdn_conv_dim(self) -> int:
        """Channels a ``G`` part's conv runs over: [q | k | v]."""
        return (
            2 * self.gdn_key_heads * self.gdn_key_dim
            + self.gdn_value_heads * self.gdn_value_dim
        )

    @property
    def gdn_params(self) -> int:
        """One ``G`` part's matrices: [q | k | v | z], [b | a] and the
        output's."""
        inner = self.gdn_value_heads * self.gdn_value_dim
        return self.d_model * (
            self.gdn_conv_dim + inner + 2 * self.gdn_value_heads + inner
        )

    @property
    def kda_params(self) -> int:
        """One ``K`` part's matrices: [q | k | v], the low-rank pairs of
        the decay and of the output gate, the write strength's and the
        output's."""
        inner = self.kda_heads * self.kda_head_dim
        return (
            self.d_model * (3 * inner + 2 * self.kda_gate_rank
                            + self.kda_heads)
            + 2 * self.kda_gate_rank * inner + inner * self.d_model
        )

    @property
    def value_dim(self) -> int:
        """Channels of a head's values: ``v_head_dim`` under latent
        attention, else the head's."""
        return self.v_head_dim if self.latent_attention else self.head_dim

    @property
    def latent_params(self) -> int:
        """One latent attention's matrices: q (through its rank, or one
        matrix where ``q_lora_rank`` is 0), the joint down-projection
        [c ‖ k_r], the up-projection [k_nope ‖ v] and o."""
        d, d_q = self.d_model, self.n_head * self.head_dim
        q = (
            d * self.q_lora_rank + self.q_lora_rank * d_q
            if self.q_lora_rank else d * d_q
        )
        return (
            q + d * (self.kv_lora_rank + self.qk_rope_head_dim)
            + self.kv_lora_rank * self.n_head
            * (self.qk_nope_head_dim + self.v_head_dim)
            + self.n_head * self.v_head_dim * d
        )

    def kind_window(self, kind: str = "") -> int:
        """Keys a query of a layer of ``kind`` may see (0 = every
        earlier one; None reads as 0): ``attn_window`` where the kind
        has a window (``ATTN_KINDS``; "" = the model's one kind: as it
        is)."""
        if kind and not ATTN_KINDS[kind].window:
            return 0
        return self.attn_window

    def kind_rope(self, kind: str = "") -> str:
        """The rope that turns q and k in a layer of ``kind``: "" none,
        "plain" or "scaled" (``rope_factor`` and its fields); the
        model's one kind is under the scaled table where it has one."""
        if self.pos != "rope":
            return ""
        if kind:
            return ATTN_KINDS[kind].rope
        return "scaled" if self.rope_factor else "plain"

    @property
    def rope_kinds(self) -> Tuple[str, ...]:
        """The rope tables a forward builds: one a rope ("plain",
        "scaled") that some layer of the trunk is turned by."""
        return tuple(sorted(
            {self.kind_rope(kind) for kind in self.layer_types or ("",)}
            - {""}
        ))

    @property
    def rope_scaling(self):
        """(factor, original length, beta_fast, beta_slow, amplitude) of
        the scaled table; None without one."""
        if not self.rope_factor:
            return None
        return (
            self.rope_factor, self.rope_original_max, self.rope_beta_fast,
            self.rope_beta_slow,
            self.rope_attn_factor or 0.1 * math.log(self.rope_factor) + 1.0,
        )

    @property
    def attn_params(self) -> int:
        """One plain-attention layer's matrices: q, k, v, o and the
        output gate's where the model has one."""
        d_attn = self.n_head * self.head_dim
        return (
            (2 + self.attn_gate) * self.d_model * d_attn
            + 2 * self.d_model * self.kv_heads * self.head_dim
        )

    @property
    def index_params(self) -> int:
        """One layer's indexer matrices: the query heads, the one key
        head and the head weights (0 without a selection)."""
        if not self.selects_keys:
            return 0
        return self.d_model * (
            self.index_n_heads * self.index_head_dim
            + self.index_head_dim + self.index_n_heads
        )

    @property
    def rope_dim(self) -> int:
        """Channels of a head that rope turns."""
        if self.latent_attention:
            return self.qk_rope_head_dim
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def expert_width(self) -> int:
        return self.d_expert or self.d_ff

    @property
    def experts_here(self) -> int:
        """Routed experts whose weights this device holds."""
        return self.n_experts_held or self.n_experts

    @property
    def n_routed_layer(self) -> int:
        """Routed layers of the trunk (the prediction module's block is
        not among them)."""
        if self.layer_pattern:
            return sum(self.layer_pattern.count(c) for c in "Ee")
        return self.n_layer - self.n_dense_layer if self.n_experts else 0

    @property
    def train_only(self) -> str:
        """Why the cache, paged, pipeline and generate paths cannot run
        this model ("" where they can): they are written for one stack
        of plain-attention layers."""
        letters = self.layer_pattern + self.mtp_pattern
        for letter, rule in PART_RULES.items():
            if rule.train_only and letter in letters:
                return rule.train_only
        if self.layer_pattern:
            return "a trunk whose layers differ"
        if self.latent_attention:
            return "latent attention (no latent cache is built)"
        if self.n_dense_layer or self.layer_types:
            return "a trunk whose layers differ"
        if self.rope_factor:
            return (
                "a scaled rope (prefill and decode build the plain table)"
            )
        if self.attn_gate or self.post_norm or self.scale_embedding:
            return "a gated, twice-normed layer the cache paths do not build"
        if self.n_mtp_module:
            return "a prediction module"
        if self.selects_keys:
            return "a learned selection of keys has no cache path"
        return ""

    @property
    def routed_top_k(self) -> int:
        """Experts a token is sent to: switch gating is top-1."""
        return 1 if self.moe_gating == "switch" else self.expert_top_k

    def _part_counts(self):
        """letter -> (parameters held here, parameters a token is
        multiplied by) of one layer of a ``layer_pattern`` model. The
        scan's state update and read-out (2 · heads · head_dim · state
        multiply-adds a token) are entered among the multiplied, as the
        benchmark's reference counts them; the conv's taps are not."""
        d = self.d_model
        inner, heads = self.d_inner, self.mamba_num_heads
        attn, attn_scales = self.attn_params, 0
        if self.latent_attention:
            # the ``*`` of this model; the latents' norm scales beside it
            attn = self.latent_params
            attn_scales = self.q_lora_rank + self.kv_lora_rank
        w_in = d * (inner + self.conv_dim + heads)
        mamba = w_in + inner * d
        mats = 2 if self.act == "relu2" else 3  # matrices of an expert
        outside = (
            d * self.n_experts + 2 * d * self.moe_latent_size
            + mats * d * self.shared_expert_width
            * bool(self.n_shared_experts)
            + d * self.shared_expert_gate
        )
        expert = mats * self.expert_in * self.expert_width
        inner1, rank = self.d_inner1, self.mamba_dt_rank
        low = rank + 2 * self.ssm_state_size       # [Δ's rank | B | C]
        mamba1 = 2 * d * inner1 + inner1 * low + rank * inner1 + inner1 * d
        mlp = (3 if self.act == "swiglu" else 2) * d * self.d_ff
        d_attn = self.n_head * self.head_dim
        qk_scales = 2 * self.head_dim * self.qk_head_norm
        routed = (
            outside + self.experts_here * expert + d,
            outside + (
                self.routed_top_k * self.experts_here
                / max(self.n_experts, 1)
            ) * expert,
        )
        return {
            "m": (
                mamba1 + inner1 * (self.conv_kernel + 1)   # conv, its bias
                + inner1 * (self.ssm_state_size + 2)       # A, D, Δ's bias
                + low + d,
                mamba1 + 2 * inner1 * self.ssm_state_size,
            ),
            "-": (mlp + d, mlp),
            # [B | C | x] and the output's matrices, the taps, the norm
            "C": (4 * d * d + self.conv_kernel * d + d, 4 * d * d),
            "M": (
                mamba + self.conv_dim * (self.conv_kernel + 1)
                + 3 * heads + inner + d,
                mamba + 2 * inner * self.ssm_state_size,
            ),
            "*": (attn + d + qk_scales + attn_scales, attn),
            "S": (attn + d + qk_scales, attn),
            # the recurrence's update and read-out: 2 x inner x state
            "L": (
                self.lightning_params + d_attn + d + qk_scales,
                self.lightning_params + 2 * d_attn * self.head_dim,
            ),
            # the rule's own work a token and value head: the decay of
            # the state (half a multiply-add a cell), Sᵀk, the rank-one
            # write and the read-out Sᵀq: 3.5 x key x value channels
            "G": (
                self.gdn_params + self.gdn_conv_dim * self.conv_kernel
                + 2 * self.gdn_value_heads + self.gdn_value_dim + d,
                self.gdn_params + int(
                    3.5 * self.gdn_value_heads * self.gdn_key_dim
                    * self.gdn_value_dim
                ),
            ),
            # as G's: conv taps, A, the time step's bias a channel, the
            # output norm's scale and the part's norm beside the
            # matrices; the rule's own 3.5 x key x value channels a head
            "K": (
                self.kda_params
                + 3 * self.kda_heads * self.kda_head_dim * self.conv_kernel
                + self.kda_heads + self.kda_heads * self.kda_head_dim
                + self.kda_head_dim + d,
                self.kda_params + int(
                    3.5 * self.kda_heads * self.kda_head_dim ** 2
                ),
            ),
            "E": routed,
            "e": routed,
        }

    def num_params(self) -> int:
        """Parameter count. A capacity-routed (``moe_impl: dense``)
        layer of a model of one kind is counted as one MLP of ``d_ff``
        (the dense part); a dropless routed model counts what this
        device holds: a dense prefix at ``d_ff``, each routed block's
        held and shared experts and router, and the prediction
        module."""
        d, f, v, L = self.d_model, self.d_ff, self.vocab_size, self.n_layer
        if self.layer_pattern:
            held = {k: n for k, (n, _) in self._part_counts().items()}
            mtp = self.n_mtp_module * (
                2 * d * d + 3 * d + sum(held[c] for c in self.mtp_pattern)
            )
            return (
                sum(held[c] for c in self.layer_pattern) + mtp
                + v * d * (1 if self.tie_embeddings else 2) + d
            )
        if self.latent_attention:
            attn = self.latent_params + self.q_lora_rank + self.kv_lora_rank
        else:
            attn = self.attn_params
        # the indexer; and the two norms on the parts' outputs
        attn += self.index_params + 2 * d * self.post_norm
        attn += 2 * self.head_dim * self.qk_head_norm  # the per-head scales
        gated = 3 if self.act == "swiglu" else 2
        mlp = gated * d * f
        embed = v * d * (1 if self.tie_embeddings else 2)
        pos = self.max_seq * d if self.pos == "learned" else 0
        if self.n_experts and self.moe_impl == "ragged" and (
            not self.n_dense_layer
        ):
            # this device's share of a routed model of one kind
            mlp = d * self.n_experts + (
                self.experts_here * gated * d * self.expert_width
            )
        if not self.n_dense_layer:
            return L * (attn + mlp + 2 * d) + embed + pos + d
        routed = attn + 2 * d + d * self.n_experts + (
            self.experts_here + self.n_shared_experts
        ) * gated * d * self.expert_width
        mtp = self.n_mtp_module * (2 * d * d + routed + 3 * d)
        return (
            self.n_dense_layer * (attn + mlp + 2 * d)
            + self.n_routed_layer * routed + mtp + embed + pos + d
        )

    @property
    def n_attention_layers(self) -> int:
        """Layers with an attention, the prediction module's included."""
        if self.layer_pattern:
            letters = self.layer_pattern + self.mtp_pattern
            return letters.count("*") + letters.count("S")
        return self.n_layer + self.n_mtp_module

    def executed_span(self, seq_len: int, kind: str = "") -> float:
        """The mean number of keys a query ATTENDS to at ``seq_len`` in
        a layer of ``kind`` (``layer_types``' letter; "" = the model's
        one kind): the causal span under that kind's window, every key
        without the mask — the pairs ``flops_per_token`` requires. A
        model that selects its keys is given the whole causal span here
        (``flops_per_token`` puts ``index_topk`` in its place). What
        the kernels execute — whole tiles, more than this — is the
        kernels' to say (``pallas_attention.forward_keys``), and is what
        ``remat: full`` decides by."""
        if not self.causal:
            return float(seq_len)
        return mean_span(seq_len, self.kind_window(kind))

    def flops_per_token(self, seq_len: int) -> float:
        """FLOPs a training step requires per token, forward and
        backward, counted as the benchmark counts them
        (``benchmarks/lib/flops.py``): 6 × the parameters that are
        multiplied, plus 12 · L · (heads × head_dim) × the mean number
        of keys a query sees.

        Multiplied: every projection and MLP matrix of every layer and
        the output head once, tied or not; not the embedding gather, the
        learned positions or the norm scales. A routed layer counts the
        ``routed_top_k`` experts a token meets and the router, not the
        experts it never visits. Under the causal mask query i sees
        min(i + 1, window or seq_len) keys; without it, all of them.
        A device that holds h of E experts counts k · h / E of them, a
        shared expert whole, a leading dense layer at ``d_ff``, latent
        attention's five projections, and a prediction module's
        projection, block and the head once more. A layer that selects
        ``index_topk`` keys counts min(i + 1, k) keys a query, and its
        indexer's projections and heads x channels / 2 over every
        visible key. Recomputation does not count. A ``layer_pattern``
        model counts each layer's one part (``_part_counts``) and the
        pairs of its attention layers alone."""
        d = self.d_model
        # a pair's two products: one over the score channels, one over
        # the value channels (the same number but under latent attention
        # with narrower values)
        d_attn = self.n_head * (self.head_dim + self.value_dim) / 2
        if self.layer_pattern:
            met = {k: n for k, (_, n) in self._part_counts().items()}
            head = d * self.vocab_size
            multiplied = (
                sum(met[c] for c in self.layer_pattern) + head
                + self.n_mtp_module * (
                    2 * d * d + head + sum(met[c] for c in self.mtp_pattern)
                )
            )
            span = self.executed_span(seq_len)
            pairs = d_attn * span
            if self.selects_at(seq_len):
                # an S part counts the keys of the blocks a query chose
                # and, at half a pair-channel, the pooled keys it scored
                pairs = d_attn * selected_span(
                    seq_len, self.index_topk, self.sparse_block
                ) + d_attn / 2 * span / self.pool_stride
            stars = (self.layer_pattern + self.mtp_pattern).count("*")
            return 6.0 * multiplied + 12.0 * (
                stars * d_attn * span
                + self.layer_pattern.count("S") * pairs
            )
        attn = (
            self.latent_params if self.latent_attention else self.attn_params
        )
        # a score-only indexer: its projections among the multiplied
        attn += self.index_params
        gated = 3 if self.act == "swiglu" else 2
        head = d * self.vocab_size
        routed = 0
        if self.n_experts:
            # the experts a token meets HERE: its k choices' balanced
            # share of the experts held, and the shared ones whole
            met = (
                self.routed_top_k * self.experts_here / self.n_experts
                + self.n_shared_experts
            )
            routed = attn + met * gated * d * self.expert_width + (
                d * self.n_experts
            )
        dense = attn + gated * d * self.d_ff
        multiplied = (
            (self.n_layer - self.n_routed_layer) * dense
            + self.n_routed_layer * routed
            + self.n_mtp_module * (2 * d * d + routed + head)
            + head
        )
        span = self.executed_span(seq_len)
        pairs = d_attn * span
        if self.selects_keys:
            # the attention counts the keys it selects, min(i + 1, k) a
            # query; the indexer half a pair-channel (a score product
            # and no value product) over every key it scores
            pairs = d_attn * mean_span(seq_len, self.index_topk) + (
                self.index_n_heads * self.index_head_dim / 2 * span
            )
        if self.layer_types:
            # each layer the span of its own kind
            return 6.0 * multiplied + 12.0 * d_attn * sum(
                self.executed_span(seq_len, kind) for kind in self.layer_types
            )
        return 6.0 * multiplied + 12.0 * self.n_attention_layers * pairs


@dataclass(frozen=True)
class AttnKind:
    """One kind of plain-attention layer a ``layer_types`` letter names:
    what ``kind_window``, ``kind_rope``, the validation, the counts and
    ``models/decoder.py`` (its scope, its table) read. A kind added
    later is one row."""

    what: str  # the kind in a few words, as errors and refusals list it
    window: bool  # the last ``attn_window`` keys (else every earlier one)
    rope: str  # "" no positional term | "plain" | "scaled" (rope_factor)
    scope: str  # the tracing scope of its whole attention part


ATTN_KINDS = {
    "S": AttnKind("sliding window, plain rope", True, "plain", "attn.window"),
    "F": AttnKind("full, no positions", False, "", "attn.full"),
    "Y": AttnKind("full, scaled rope", False, "scaled", "attn.full"),
}


def _kinds_said() -> str:
    """The kinds as a refusal lists them: "S (sliding ...), F (...) or
    Y (...)"."""
    said = [f"{k} ({rule.what})" for k, rule in ATTN_KINDS.items()]
    return ", ".join(said[:-1]) + " or " + said[-1]


@dataclass(frozen=True)
class PartRule:
    """What ``ModelConfig`` asks of one kind of ``layer_pattern`` part
    (what the trunk does with it: ``models/decoder.py::PARTS``; what it
    counts: ``ModelConfig._part_counts``)."""

    what: str  # the kind in a few words, as the refusal of a letter lists it
    # fields that must be positive and what must NOT hold beside them
    # (asked only where they are), else ``refusal``
    needs: Tuple[str, ...] = ()
    unless: Optional[Callable] = None
    refusal: str = ""
    also: tuple = ()  # ((what must not hold, its refusal), ...) behind those
    trunk_only: bool = False  # not a part of a prediction module
    # why the cache paths refuse a model with such a part ("": as a trunk
    # whose layers differ); the first kind of the table a model has speaks
    train_only: str = ""


_STATE_SPACE = "state-space layers: no recurrent state beside the cache"
_ROUTED = dict(
    unless=lambda c: not (c.n_experts and c.moe_impl == "ragged"),
    refusal="an E layer is the ragged (dropless) routed block: "
    "n_experts > 0 and moe_impl='ragged'",
)
# a ``layer_pattern`` letter -> its rule, in the order the checks and
# ``train_only`` go by
PART_RULES = {
    "M": PartRule(
        "Mamba-2", train_only=_STATE_SPACE,
        needs=("mamba_num_heads", "mamba_head_dim", "ssm_state_size",
               "n_groups", "conv_kernel", "ssm_chunk"),
        unless=lambda c: c.mamba_num_heads % c.n_groups,
        refusal="a Mamba-2 layer needs mamba_num_heads (a multiple of "
        "n_groups), mamba_head_dim, ssm_state_size, conv_kernel and "
        "ssm_chunk",
    ),
    "m": PartRule(
        "Mamba-1", train_only=_STATE_SPACE,
        needs=("mamba_expand", "mamba_dt_rank", "ssm_state_size",
               "conv_kernel"),
        refusal="a Mamba-1 part needs mamba_expand, mamba_dt_rank, "
        "ssm_state_size and conv_kernel",
    ),
    "C": PartRule(
        "gated short convolution", needs=("conv_kernel",),
        unless=lambda c: c.conv_kernel < 2,
        refusal="a C part needs conv_kernel >= 2: a conv of one tap is a "
        "gate and no conv",
        train_only="gated-short-convolution (C) layers: a conv state of "
        "conv_kernel - 1 rows has no place beside the cache",
    ),
    "*": PartRule("attention; latent attention where kv_lora_rank is set"),
    "L": PartRule(
        "lightning attention", trunk_only=True,
        unless=lambda c: c.head_dim % 2,
        refusal="an L part turns q and k by rope: an even head",
        train_only="lightning (L) layers: no recurrent state beside the "
        "cache",
    ),
    "G": PartRule(
        "gated delta rule", trunk_only=True,
        needs=("gdn_key_heads", "gdn_value_heads", "gdn_key_dim",
               "gdn_value_dim", "conv_kernel"),
        unless=lambda c: c.gdn_value_heads % c.gdn_key_heads,
        refusal="a G part needs gdn_key_heads, gdn_value_heads (a multiple "
        "of them), gdn_key_dim, gdn_value_dim, and conv_kernel",
        train_only="gated-delta-rule (G) layers: no recurrent state beside "
        "the cache",
    ),
    "K": PartRule(
        "delta rule with a decay a key channel, KDA", trunk_only=True,
        needs=("kda_heads", "kda_head_dim", "kda_gate_rank", "conv_kernel"),
        refusal="a K part needs kda_heads, kda_head_dim, kda_gate_rank and "
        "conv_kernel",
        train_only="delta-rule layers with a decay a key channel (K): no "
        "recurrent state beside the cache",
    ),
    "S": PartRule(
        "block-sparse attention", trunk_only=True,
        needs=("sparse_block", "pool_window", "pool_stride", "index_topk",
               "select_local"),
        unless=lambda c: c.select_init_blocks < 0,
        refusal="an S part needs sparse_block, pool_window, pool_stride, "
        "index_topk and select_local",
        also=(
            (lambda c: (
                c.sparse_block % c.pool_stride
                or c.pool_window % c.pool_stride
                or c.select_local % c.sparse_block
                or c.index_topk
                < c.select_init_blocks + c.select_local // c.sparse_block
            ), "pool_stride divides pool_window and sparse_block, "
             "sparse_block divides select_local, and the forced blocks "
             "(select_init_blocks and the local window's) fit in index_topk"),
            (lambda c: not c.causal or c.attn_window,
             "a selection of blocks runs under the plain causal mask"),
        ),
        train_only="block-sparse (S) layers: a selection has no cache path",
    ),
    "E": PartRule("routed experts", **_ROUTED),
    "e": PartRule("routed experts, a layer's second part", **_ROUTED),
    "-": PartRule(
        "dense MLP", unless=lambda c: c.act not in ("swiglu", "gelu"),
        refusal="a - part is the dense MLP of d_ff: act 'swiglu' or 'gelu'",
    ),
}


def pattern_parts(pattern: str):
    """A ``layer_pattern`` cut into its layers, each a string of parts:
    a ``-`` (the dense MLP) or an ``e`` (the routed experts) that
    follows another part is that part's layer's second part, every
    other letter a layer by itself."""
    return re.findall(r"[^-e][-e]?|[-e]", pattern)


def pattern_layers(pattern: str) -> int:
    """Layers a ``layer_pattern`` names."""
    return len(pattern_parts(pattern))


def mean_span(seq_len: int, window: int = 0) -> float:
    """Keys a query sees under the causal mask, averaged over a sequence
    of ``seq_len``: query i sees min(i + 1, ``window`` or seq_len)."""
    w = min(window or seq_len, seq_len)
    return (w * (w + 1) / 2 + (seq_len - w) * w) / seq_len


def selected_span(seq_len: int, topk: int, block: int) -> float:
    """Keys a query attends to where it selects at most ``topk`` BLOCKS
    of ``block`` keys, its own among them, averaged over a sequence of
    whole blocks: min(i // block + 1, topk) - 1 whole blocks and
    i mod block + 1 keys of its own."""
    units = seq_len // block
    k = min(topk, units)
    whole = k * (k - 1) // 2 + (units - k) * (k - 1)
    return (
        whole * block * block + units * block * (block + 1) / 2
    ) / seq_len


def lightning_log_decay(n_head: int):
    """float [n_head]: log λ_h = -2^(-8 h / n_head), h = 1 .. n_head —
    Lightning Attention's fixed slope a head, a constant and no
    parameter, the same in every ``L`` part."""
    return [-(2.0 ** (-8.0 * h / n_head)) for h in range(1, n_head + 1)]


def mup_base_config(cfg: "ModelConfig") -> "ModelConfig":
    """The base-width twin of ``cfg`` for muP infshape computation.

    Width dims (d_model, d_ff, heads) shrink to ``mup_base_width``
    proportionally with head_dim held constant — depth, vocab and seq are
    muP-invariant and stay put.
    """
    if not cfg.mup_base_width:
        raise ValueError("cfg.mup_base_width is not set")
    ratio = cfg.mup_base_width / cfg.d_model
    return replace(
        cfg,
        d_model=cfg.mup_base_width,
        d_ff=max(int(cfg.d_ff * ratio), 1),
        n_head=max(int(cfg.n_head * ratio), 1),
        n_kv_head=(
            max(int(cfg.kv_heads * ratio), 1)
            if cfg.n_kv_head is not None
            else None
        ),
    )


def _gpt2(name, n_layer, n_head, d_model, max_seq=1024):
    return ModelConfig(
        name=name,
        vocab_size=50304,
        n_layer=n_layer,
        n_head=n_head,
        d_model=d_model,
        d_ff=4 * d_model,
        max_seq=max_seq,
        norm="layernorm",
        act="gelu",
        pos="learned",
        tie_embeddings=True,
    )


def _llama(name, n_layer, n_head, d_model, d_ff, max_seq=4096, n_kv_head=None):
    return ModelConfig(
        name=name,
        vocab_size=32000,
        n_layer=n_layer,
        n_head=n_head,
        n_kv_head=n_kv_head,
        d_model=d_model,
        d_ff=d_ff,
        max_seq=max_seq,
        norm="rmsnorm",
        act="swiglu",
        pos="rope",
        tie_embeddings=False,
    )


def _bert(name, n_layer, n_head, d_model, max_seq=512):
    """BERT-family encoder (reference: atorch's TP BERT blocks,
    distributed_modules/transformer.py:45): bidirectional attention,
    learned positions, layernorm+gelu, tied MLM head."""
    return ModelConfig(
        name=name,
        vocab_size=30592,            # 30522 padded to a 128 multiple
        n_layer=n_layer,
        n_head=n_head,
        d_model=d_model,
        d_ff=4 * d_model,
        max_seq=max_seq,
        causal=False,
        pos="learned",
        norm="layernorm",
        act="gelu",
        tie_embeddings=True,
    )


def _gptneox(name, n_layer, n_head, d_model, max_seq=2048):
    return ModelConfig(
        name=name,
        vocab_size=50432,
        n_layer=n_layer,
        n_head=n_head,
        d_model=d_model,
        d_ff=4 * d_model,
        max_seq=max_seq,
        norm="layernorm",
        act="gelu",
        pos="rope",
        parallel_residual=True,
        tie_embeddings=False,
    )


def _glm(name, n_layer, n_head, d_model, max_seq=2048):
    """GLM-family prefix-LM decoder (bidirectional prefix + causal tail).
    Design divergence from the reference's GLM blocks: rope instead of
    GLM's 2D block positions — the infilling capability lives in the
    prefix mask, and rope needs no learned table."""
    return ModelConfig(
        name=name,
        vocab_size=50304,
        n_layer=n_layer,
        n_head=n_head,
        d_model=d_model,
        d_ff=4 * d_model,
        max_seq=max_seq,
        norm="layernorm",
        act="gelu",
        pos="rope",
        prefix_lm=True,
        tie_embeddings=True,
    )


CONFIGS = {
    "tiny": ModelConfig(),
    "tiny-moe": replace(ModelConfig(name="tiny-moe"), n_experts=4),
    "tiny-neox": replace(
        ModelConfig(name="tiny-neox"),
        parallel_residual=True,
        norm="layernorm",
        act="gelu",
    ),
    "tiny-glm": replace(ModelConfig(name="tiny-glm"), prefix_lm=True),
    "tiny-bert": replace(
        ModelConfig(name="tiny-bert"),
        causal=False,
        pos="learned",
        norm="layernorm",
        act="gelu",
    ),
    "bert-base": _bert("bert-base", 12, 12, 768),
    "bert-large": _bert("bert-large", 24, 16, 1024),
    "gpt2-124m": _gpt2("gpt2-124m", 12, 12, 768),
    "gpt2-355m": _gpt2("gpt2-355m", 24, 16, 1024),
    "gpt2-1.5b": _gpt2("gpt2-1.5b", 48, 25, 1600),
    # single-chip flagship: llama proportions sized for one v5e, with
    # every hot dim a 128-multiple (d=16·128, head_dim=128, ff=44·128) —
    # measured ~10pt better raw matmul efficiency than gpt2-1.5b's
    # d=1600/head_dim=64 shapes on the v5e MXU
    "llama-1.4b": _llama("llama-1.4b", 24, 16, 2048, 5632),
    "llama-1.7b": _llama("llama-1.7b", 24, 18, 2304, 6144),
    "llama2-7b": _llama("llama2-7b", 32, 32, 4096, 11008),
    "llama2-13b": _llama("llama2-13b", 40, 40, 5120, 13824),
    "llama3-8b": _llama(
        "llama3-8b", 32, 32, 4096, 14336, max_seq=8192, n_kv_head=8
    ),
    "gptneox-20b": _gptneox("gptneox-20b", 44, 64, 6144),
    "glm-10b": _glm("glm-10b", 48, 64, 4096),
    # sliding-window flagship: Mistral-style decoder (GQA + 4k window;
    # attention cost O(S·window) — the kernel never touches blocks
    # outside the window)
    "mistral-7b": replace(
        _llama(
            "mistral-7b", 32, 32, 4096, 14336,
            max_seq=8192, n_kv_head=8,
        ),
        attn_window=4096,
    ),
    # sparse flagship: Mixtral-style MoE decoder (GQA + top-2 routing);
    # the ep mesh axis + explicit all-to-all dispatch carry it
    "mixtral-8x7b": replace(
        _llama(
            "mixtral-8x7b", 32, 32, 4096, 14336,
            max_seq=8192, n_kv_head=8,
        ),
        n_experts=8,
        expert_top_k=2,
        moe_aux_coef=0.01,
        moe_z_coef=0.001,
        moe_alltoall=True,  # ep>1 meshes must not replicate expert acts
    ),
    # many narrow experts: OLMoE-1B-7B (arXiv 2409.02060) — 64 SwiGLU
    # experts of width 1024, top-8 with raw softmax weights, dropless,
    # data parallel with every expert on every device; MHA with
    # whole-projection QK-norm
    "olmoe-1b-7b": replace(
        _llama(
            "olmoe-1b-7b", 16, 16, 2048, 1024, max_seq=4096, n_kv_head=16
        ),
        vocab_size=50304,
        qk_norm=True,
        n_experts=64,
        expert_top_k=8,
        moe_impl="ragged",
        moe_renorm_topk=False,
        moe_aux_coef=0.01,
        moe_z_coef=0.001,
    ),
    # latent attention, a dense prefix, sigmoid-scored experts beside a
    # shared one, one prediction module: GLM-4.7-Flash (``glm4_moe_lite``,
    # huggingface.co/zai-org/GLM-4.7-Flash config.json) — 20 MLA heads
    # (ranks 768 / 512, 192 + 64 score and 256 value channels), one
    # SwiGLU layer of 10240 then 46 of 64 experts of width 1536, top-4
    # renormalised × 1.8, no router loss term
    "glm-4.7-flash": replace(
        _llama(
            "glm-4.7-flash", 47, 20, 2048, 10240, max_seq=202752,
            n_kv_head=20,
        ),
        vocab_size=154880,
        rope_theta=1e6,
        q_lora_rank=768,
        kv_lora_rank=512,
        qk_nope_head_dim=192,
        qk_rope_head_dim=64,
        v_head_dim=256,
        n_dense_layer=1,
        n_experts=64,
        expert_top_k=4,
        d_expert=1536,
        n_shared_experts=1,
        moe_impl="ragged",
        moe_score="sigmoid",
        routed_scaling_factor=1.8,
        n_mtp_module=1,
    ),
    # a learned selection of keys in every layer, a head wider than
    # d_model / n_head, 128 narrow experts: Keye-VL-2.0-30B-A3B's
    # language tower (huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B
    # config.json, ``text_config`` and ``sa_config``; the vision tower is
    # not built) — 48 layers of GQA 32 / 4 heads of 128 over d 2048 with
    # per-head RMSNorm on q and k, rope theta 1e7, a DeepSeek-Sparse-
    # Attention indexer (16 heads x 64 channels, one key head, top-2048),
    # 128 SwiGLU experts of width 768, softmax top-8 renormalised, no
    # shared expert; router loss 0.001 and indexer_loss_coef 1.0 are
    # assumed. Training path only
    "keye-vl-2.0": replace(
        # intermediate_size 6144 is published and unused: every layer is
        # routed (mlp_only_layers empty)
        _llama(
            "keye-vl-2.0", 48, 32, 2048, 6144, max_seq=262144, n_kv_head=4
        ),
        vocab_size=151936,
        attn_window=None,  # sliding_window: null, as published
        d_head=128,
        qk_head_norm=True,
        rope_theta=1e7,
        index_n_heads=16,
        index_head_dim=64,
        index_topk=2048,
        indexer_loss_coef=1.0,
        n_experts=128,
        expert_top_k=8,
        d_expert=768,
        moe_impl="ragged",
        moe_renorm_topk=True,
        moe_aux_coef=0.001,
    ),
    # state-space layers, one attention layer in eleven and experts in
    # a latent: NVIDIA-Nemotron-3-Super-120B-A12B (``nemotron_h``,
    # huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
    # config.json) — 88 layers of ONE part each by
    # ``hybrid_override_pattern``: 40 Mamba-2 mixers (128 heads x 64,
    # 8 groups, state 128, conv 4, chunks of 128), 8 attentions (GQA
    # 32 / 2 heads of 128, no rope), 40 LatentMoE blocks (sigmoid top-22
    # of 512 relu² experts of width 2688 in a 1024-wide latent,
    # renormalised x 5, beside a shared relu² expert of 5376 on the full
    # width); one prediction module of an attention and a routed layer.
    # Training path only
    "nemotron-3-super": ModelConfig(
        name="nemotron-3-super",
        vocab_size=131072,
        n_layer=88,
        layer_pattern=(
            "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
        ),
        mtp_pattern="*E",
        n_head=32,
        n_kv_head=2,
        d_head=128,
        d_model=4096,
        d_ff=2688,  # intermediate_size: published, and no layer uses it
        max_seq=262144,
        act="relu2",
        pos="none",
        attn_window=None,  # sliding_window: null
        tie_embeddings=False,
        mamba_num_heads=128,
        mamba_head_dim=64,
        ssm_state_size=128,
        n_groups=8,
        conv_kernel=4,
        ssm_chunk=128,
        ssm_head_block=16,
        n_experts=512,
        expert_top_k=22,
        d_expert=2688,
        moe_latent_size=1024,
        n_shared_experts=1,
        d_shared_expert=5376,
        moe_impl="ragged",
        moe_score="sigmoid",
        moe_renorm_topk=True,
        routed_scaling_factor=5.0,
        n_mtp_module=1,
    ),
    # Mamba-1 mixers and one attention in fourteen, every layer a mixer
    # and a dense MLP: AI21-Jamba2-3B (``jamba``;
    # huggingface.co/ai21labs/AI21-Jamba2-3B config.json) — 28 layers
    # over d 2560, each x + mixer(norm(x)) then x + MLP(norm(x)), so two
    # parts a layer; the mixer is an attention (20 query heads of 128 on
    # ONE key/value head, no rope) where i mod 14 = 7 and a Mamba-1
    # mixer elsewhere (5120 channels of 16 states, Δ through a rank of
    # 160, conv 4); one SwiGLU of 8192 a layer (num_experts 1); vocabulary
    # 65,536, head tied. Training path only
    "jamba2-3b": ModelConfig(
        name="jamba2-3b",
        vocab_size=65536,
        n_layer=28,
        layer_pattern=("m-" * 7 + "*-" + "m-" * 6) * 2,
        n_head=20,
        n_kv_head=1,
        d_head=128,
        d_model=2560,
        d_ff=8192,
        max_seq=262144,
        act="swiglu",
        pos="none",
        attn_window=None,  # sliding_window: null
        tie_embeddings=True,
        mamba_expand=2,
        mamba_dt_rank=160,
        ssm_state_size=16,
        conv_kernel=4,
    ),
    # block-sparse attention in one layer of four and lightning linear
    # attention in the rest, every layer a mixer and a dense MLP:
    # MiniCPM-SALA (``minicpm_sala``, 9B;
    # huggingface.co/openbmb/MiniCPM-SALA config.json) — 32 layers over
    # d 4096 in ``mixer_types``' order, each x + s mixer(norm(x)) then
    # x + s MLP(norm(x)) with s = scale_depth 1.4 / sqrt(32); 8
    # ``minicpm4`` mixers (``S``: GQA 32 / 2 heads of 128, per-head
    # RMSNorm on q and k, no rope, a sigmoid output gate, InfLLM-v2's
    # selection of 64 blocks of 64 keys a KV head from keys pooled 32
    # every 16 — MiniCPM4's ``sparse_config``, assumed) among 24
    # ``lightning-attn`` mixers (``L``: 32 heads of 128 with their own
    # k and v, per-head RMSNorm and rope on q and k, a fixed decay a
    # head, an output norm and gate); SwiGLU 16,384; embeddings x 12,
    # the last hidden state x 256 / 4096; vocabulary 73,448 untied.
    # Training path only
    "minicpm-sala": ModelConfig(
        name="minicpm-sala",
        vocab_size=73448,
        n_layer=32,
        layer_pattern="".join(
            c + "-" for c in "SLLLLLLLLSLLLLLLSSLLLLSLLLLLLSSS"
        ),
        n_head=32,
        n_kv_head=2,
        d_head=128,
        d_model=4096,
        d_ff=16384,
        max_seq=524288,
        act="swiglu",
        pos="rope",  # the L parts' (lightning_use_rope); the S parts none
        rope_theta=10000.0,
        attn_window=None,
        tie_embeddings=False,
        qk_head_norm=True,
        attn_gate=True,
        norm_eps=1e-6,
        index_topk=64,
        sparse_block=64,
        pool_window=32,
        pool_stride=16,
        select_init_blocks=1,
        select_local=2048,
        select_dense_len=8192,
        scale_emb=12.0,
        residual_scale=1.4 / 32 ** 0.5,
        logit_scale=256 / 4096,
    ),
    # a gated delta rule in three layers of four and a gated attention
    # in the fourth, every layer's second part routed:
    # Qwen3-Next-80B-A3B-Instruct (``qwen3_next``; huggingface.co/Qwen/
    # Qwen3-Next-80B-A3B-Instruct config.json) — 48 layers over d 2048,
    # each x + mixer(norm(x)) then x + routed(norm(x)), every norm but
    # the mixer's output norm zero-centred (1 + w); layer i a
    # gated-delta-rule mixer (``G``: 16 key heads of 128 shared two to
    # one by 32 value heads of 128, conv 4 over [q | k | v], a decay and
    # a write strength a value head, RMSNorm a head THEN silu(z)) where
    # (i + 1) mod 4 is not 0, else a gated attention (``*``: GQA 16 / 2
    # heads of 256, per-head zero-centred RMSNorm on q and k, rope theta
    # 1e7 on the first 64 channels of a head, a sigmoid gate a channel);
    # 512 SwiGLU experts of width 512, softmax top-10 renormalised,
    # beside a shared expert of 512 under its own sigmoid gate; no
    # router loss (config.json carries no coefficient); vocabulary
    # 151,936 untied. The module for multi-token prediction is not
    # built (config.json has no key for it). Training path only
    "qwen3-next": ModelConfig(
        name="qwen3-next",
        vocab_size=151936,
        n_layer=48,
        layer_pattern="GeGeGe*e" * 12,
        n_head=16,
        n_kv_head=2,
        d_head=256,
        d_model=2048,
        d_ff=5120,  # intermediate_size: published, and no layer uses it
        max_seq=262144,
        act="swiglu",
        pos="rope",
        rope_theta=1e7,
        partial_rotary_factor=0.25,
        attn_window=None,  # use_sliding_window: false
        tie_embeddings=False,
        qk_head_norm=True,
        attn_gate=True,
        norm_eps=1e-6,
        norm_zero_centered=True,
        gdn_key_heads=16,
        gdn_value_heads=32,
        gdn_key_dim=128,
        gdn_value_dim=128,
        conv_kernel=4,
        n_experts=512,
        expert_top_k=10,
        d_expert=512,
        n_shared_experts=1,
        d_shared_expert=512,
        shared_expert_gate=True,
        moe_impl="ragged",
        moe_score="softmax",
        moe_renorm_topk=True,
    ),
    # a delta rule whose decay is a vector over the key channels in
    # three layers of four and latent attention without positions in
    # the fourth: Kimi-Linear-48B-A3B-Instruct (``kimi_linear``;
    # huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct config.json;
    # arXiv:2510.26692) — 27 layers over d 2304, each x + mixer(norm(x))
    # then x + mlp(norm(x)), RMSNorm eps 1e-5; published layer i (from 1)
    # a KDA mixer (``K``: 32 heads of 128 key and 128 value channels,
    # conv 4 over [q | k | v], a decay a head and KEY CHANNEL and a
    # sigmoid output gate, both through a rank of 128 — the model
    # code's, no key in config.json —, RMSNorm a head THEN the gate)
    # where i is in ``kda_layers``, else latent attention (``*``: 32
    # heads, q at full rank (``q_lora_rank: null``), k and v through a
    # latent of 512, scores over 128 + 64 channels and values of 128, the
    # 64 shared channels NOT rotated, ``mla_use_nope``); the first
    # layer's second part a dense SwiGLU of 9216, every other layer's 256
    # SwiGLU experts of width 1024, sigmoid top-8 of ONE group
    # renormalised x 2.446, beside a shared expert of 1024; no router
    # loss (config.json carries no coefficient), the selection bias held
    # at zero; no prediction module; vocabulary 163,840 untied. Training
    # path only
    "kimi-linear": ModelConfig(
        name="kimi-linear",
        vocab_size=163840,
        n_layer=27,
        layer_pattern="".join(
            ("*" if i % 4 == 0 or i == 27 else "K") + ("-" if i == 1 else "e")
            for i in range(1, 28)
        ),
        n_head=32,
        n_kv_head=32,
        d_model=2304,
        d_ff=9216,
        max_seq=1048576,
        act="swiglu",
        pos="none",  # mla_use_nope; rope_theta is published and unused
        attn_window=None,
        tie_embeddings=False,
        norm_eps=1e-5,
        q_lora_rank=0,  # q_lora_rank: null
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        kda_heads=32,
        kda_head_dim=128,
        kda_gate_rank=128,
        conv_kernel=4,
        n_experts=256,
        expert_top_k=8,
        d_expert=1024,
        n_shared_experts=1,
        moe_impl="ragged",
        moe_score="sigmoid",
        moe_renorm_topk=True,
        routed_scaling_factor=2.446,
    ),
    # an attention kind per layer, a gate on the attention's output,
    # four norms a layer: Trinity-Mini (``afmoe``, 26B-A3B;
    # huggingface.co/arcee-ai/Trinity-Mini config.json) — 32 layers of
    # GQA 32 / 4 heads of 128 over d 2048 with per-head RMSNorm on q
    # and k, ``layer_types`` three sliding-window layers (2,048 keys,
    # rope theta 1e4) then one full layer without positions, eight
    # times; two dense SwiGLU layers of 6144, then 30 of 128 experts of
    # width 1024 (sigmoid top-8 renormalised x 2.826) beside a shared
    # one; embeddings x sqrt(d) (``mup_enabled``), rms_norm_eps 1e-5,
    # load balancing 0.001. The gate, the per-head norm, the full
    # layers' missing rope and the two output norms are the ``afmoe``
    # model code's (no key in config.json). Training path only
    "trinity-mini": replace(
        _llama(
            "trinity-mini", 32, 32, 2048, 6144, max_seq=131072, n_kv_head=4
        ),
        vocab_size=200192,
        d_head=128,
        qk_head_norm=True,
        attn_window=2048,
        layer_types="SSSF" * 8,
        attn_gate=True,
        post_norm=True,
        scale_embedding=True,
        norm_eps=1e-5,
        n_dense_layer=2,
        n_experts=128,
        expert_top_k=8,
        d_expert=1024,
        n_shared_experts=1,
        moe_impl="ragged",
        moe_score="sigmoid",
        moe_renorm_topk=True,
        routed_scaling_factor=2.826,
        moe_aux_coef=0.001,
    ),
    # a rope per layer kind: Mellum2-12B-A2.5B-Instruct (``mellum``;
    # huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct config.json) —
    # 28 layers of GQA 32 / 4 heads of 128 over d 2304, ``layer_types``
    # three sliding-window layers (1,024 keys, plain rope at theta 5e5)
    # then one full layer under YaRN x 16 over an original 8,192
    # (beta 32 / 1, amplitude 1.2772588722239782), seven times; every
    # layer 64 experts of width 896, softmax top-8 renormalised, no
    # shared expert, no dense layer; rms_norm_eps 1e-6, untied head. The
    # per-head norm of q and k and the router's balance term (0.001,
    # OLMoE's form) have no key in config.json: the Qwen3-MoE family's,
    # whose key set this is. Training path only
    "mellum2": replace(
        _llama("mellum2", 28, 32, 2304, 7168, max_seq=131072, n_kv_head=4),
        vocab_size=98304,
        d_head=128,
        qk_head_norm=True,
        attn_window=1024,
        layer_types="SSSY" * 7,
        rope_theta=5e5,
        rope_factor=16.0,
        rope_original_max=8192,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_attn_factor=1.2772588722239782,
        norm_eps=1e-6,
        n_experts=64,
        expert_top_k=8,
        d_expert=896,
        moe_impl="ragged",
        moe_renorm_topk=True,
        moe_aux_coef=0.001,
    ),
    # a gated short convolution in three layers of four and a
    # grouped-query attention in the fourth: LFM2-8B-A1B (``lfm2_moe``,
    # 8.3B-A1.5B; huggingface.co/LiquidAI/LFM2-8B-A1B config.json) — 24
    # layers over d 2048, each x + mixer(norm(x)) then x + ffn(norm(x)),
    # RMSNorm eps 1e-5; ``layer_types`` a conv mixer (``C``: d -> 3d,
    # [B | C | x], a depthwise causal conv of ``conv_L_cache`` 3 taps
    # over B * x, no bias, no activation, gated by C, d -> d) but for
    # layers 2, 6, 10, 14, 18 and 21, an attention (``*``: GQA 32 / 8
    # heads of 64, per-head RMSNorm on q and k, rope theta 1e6 over all
    # 64 channels); the first two layers' second part a dense SwiGLU of
    # 7168 (``num_dense_layers`` 2: ``C-C-``), every other layer's 32
    # SwiGLU experts of width 1792, sigmoid top-4 renormalised x 1, no
    # shared expert, the selection bias (``use_expert_bias``) held at
    # zero; vocabulary 65,536, head tied (the family's default).
    # Training path only
    "lfm2-8b-a1b": ModelConfig(
        name="lfm2-8b-a1b",
        vocab_size=65536,
        n_layer=24,
        layer_pattern="".join(
            ("*" if i in (2, 6, 10, 14, 18, 21) else "C")
            + ("-" if i < 2 else "e")
            for i in range(24)
        ),
        n_head=32,
        n_kv_head=8,
        d_model=2048,
        d_ff=7168,
        max_seq=128000,
        act="swiglu",
        pos="rope",
        rope_theta=1e6,
        attn_window=None,
        tie_embeddings=True,
        qk_head_norm=True,
        norm_eps=1e-5,
        conv_kernel=3,
        n_experts=32,
        expert_top_k=4,
        d_expert=1792,
        moe_impl="ragged",
        moe_score="sigmoid",
        moe_renorm_topk=True,
    ),
}


def get_config(name: str, **overrides) -> ModelConfig:
    cfg = CONFIGS[name]
    return replace(cfg, **overrides) if overrides else cfg
