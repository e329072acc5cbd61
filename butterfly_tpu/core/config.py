"""Configuration dataclasses for models, meshes, and the runtime.

The reference scaffold prescribes a config/flag system only by implication
(/root/reference/CLAUDE.md:25-27 — "To be added once build system is
established"); we use plain frozen dataclasses: hashable (usable as jit
static args), serializable, no global state.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np


#: the recurrent layer kinds `layer_types` may name: Mamba-2, Gated
#: DeltaNet, Mamba-1 (a model has one of them)
RECURRENT_KINDS = ("mamba", "linear_attention", "mamba1")


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for a transformer LM.

    One config class covers the model families (GPT-2, Llama-3,
    Mixtral, SmallThinker, Keye, Granite-4.0-H, JoyAI-LLM-Flash,
    Xing4.0, GLM-5, Olmo-Hybrid, Jamba, Trinity) — the family is selected by `arch`,
    the MoE fields, the per-layer attention pattern, the sparse-attention
    indexer, the per-layer KIND (`layer_types`: Mamba-2, Gated DeltaNet or
    Mamba-1 mixers beside attention layers), the latent-attention fields
    (`kv_lora_rank` and the split
    head dims: one cached latent a token in place of heads of keys and
    values) and the residual path (`hc_mult`: n streams mixed by
    hyper-connections in place of one).
    """

    arch: str = "llama"  # "gpt2" | "llama" | "mixtral" | "smallthinker"
                         # | "keye" | "granite_hybrid" | "joyai" | "xing"
                         # | "glm5" | "olmo_hybrid" | "jamba" | "trinity"
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32  # < num_heads => grouped-query attention
    head_dim: int = 128
    intermediate_size: int = 11008
    max_seq_len: int = 8192

    # normalization / activations
    norm_eps: float = 1e-5
    use_bias: bool = False            # gpt2: True
    tie_embeddings: bool = False      # gpt2: True
    act: str = "silu"                 # gpt2: "gelu_new"; llama/mixtral: "silu"

    # positional encoding
    pos_embedding: str = "rope"       # "rope" | "learned" | "none" (no
                                      # positional encoding in any layer)
    rope_theta: float = 500000.0
    # a scaled rotation, as published (a dict; held as its sorted items,
    # which hash). Only `type` "yarn" is known: factor, beta_fast,
    # beta_slow, original_max_position_embeddings, mscale, mscale_all_dim
    # in the DeepSeek-V2/V3 convention (yarn_inv_freq, attn_scale_mult).
    # () = plain rotation at rope_theta
    rope_scaling: Tuple[Tuple[str, object], ...] = ()

    # the residual path: hc_mult n > 0 = n STREAMS a token in place of
    # one, mixed by manifold-constrained hyper-connections
    # (arXiv:2512.24880): a sublayer reads a learned mix of the streams,
    # writes its output onto all of them, and the streams mix among
    # themselves through an n x n matrix made doubly stochastic by
    # hc_sinkhorn_iters rounds of column and row normalisation of
    # exp(logits clipped to [hc_clamp_min, hc_clamp_max]); hc_eps is in
    # the mixing's norm and in every Sinkhorn denominator
    # (models/common.py stream_read / stream_write). 0 = one stream,
    # x + F(norm(x))
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0

    # MoE (mixtral)
    num_experts: int = 0              # 0 => dense FFN; 1 => the ONE
                                      # expert IS the feed-forward: a
                                      # dense FFN, no router (`routed`)
    num_experts_per_tok: int = 2
    moe_impl: str = "dense"           # "dense" | "ep" (GShard dispatch)
    moe_capacity_factor: float = 2.0  # per-expert slots multiplier (ep)
    router_input: str = "ffn"         # what the router reads: "ffn" = the
                                      # feed-forward's normed input (Mixtral);
                                      # "attn" = the ATTENTION's normed input,
                                      # the router placed before attention
                                      # (SmallThinker)

    shared_intermediate_size: int = 0  # width of ONE shared expert every
                                      # token passes beside its routed
                                      # experts, added unweighted; 0 = none
    moe_intermediate_size: int = 0    # width of one routed expert where
                                      # it differs from the dense
                                      # feed-forward's (intermediate_size);
                                      # 0 = the same
    first_k_dense: int = 0            # leading layers whose feed-forward
                                      # is DENSE (intermediate_size) in a
                                      # model of experts. The two kinds
                                      # have unlike parameter shapes:
                                      # params["dense"] and
                                      # params["sparse"] stack each apart,
                                      # and the layers run as scans over
                                      # runs (models/common.py layer_runs)
    router_score: str = "softmax"     # "softmax": a softmax over the
                                      # chosen k's logits (Mixtral);
                                      # "sigmoid": s = sigmoid(logits),
                                      # the chosen k's s divided by their
                                      # sum (DeepSeek-V3's noaux_tc)
    router_bias: bool = False         # a stored bias an expert that is
                                      # added to the scores for the
                                      # CHOICE of the k and never to
                                      # their weights
    routed_scaling_factor: float = 0.0  # on the routed experts' weights;
                                      # 0 = the family has none
    # ONE chip's share of a deployment that splits each layer's experts
    # over chips: experts_held of the num_experts, from experts_first on,
    # have weights here (the expert leaves are [experts_held, ..]). The
    # router still ranges over all num_experts and a token's gates are
    # normalised over its chosen k wherever they live; the layer
    # computes the part of its result that the held experts give, plus
    # the shared expert, and what the absent experts would add is left
    # out (no code stands in for the other chips or their exchange).
    # 0 = every expert is held: the whole layer
    experts_held: int = 0
    experts_first: int = 0

    # latent attention (MLA): the query through a latent of q_lora_rank,
    # keys and values through ONE joint latent
    # of kv_lora_rank a token beside ONE rotary key of qk_rope_head_dim
    # shared by all heads. A head's query and key are qk_nope_head_dim
    # + qk_rope_head_dim wide (the second part rotates), its value
    # v_head_dim; the score scale is (nope + rope) ** -0.5. What a
    # token caches is the normed latent and the rotated key: one row of
    # kv_lora_rank + qk_rope_head_dim, no heads and no values
    # (cache/paged.py pool_row); head_dim and num_kv_heads are not read.
    # All five are 0 for a model without.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False     # the rotation pairs dims (2i, 2i+1)
                                      # of a head, not (i, i + half)

    # per-layer KIND: "mamba" (a Mamba-2 mixer), "linear_attention" (a
    # Gated DeltaNet mixer), "mamba1" (a Mamba-1 mixer), each with a
    # fixed-size recurrent state a stream (cache/ssm_state.py), or
    # "attention" (keys and values in the paged pool), one entry a layer
    # (a longer list is read up to num_layers); () = every layer is
    # attention. A model has ONE kind of recurrent layer
    # (recurrent_kind). Kinds have unlike parameter SHAPES, so each
    # kind's mixer weights are stacked apart (params["mamba"],
    # params["gdn"] or params["mamba1"], params["attn"]) and the layers
    # run as scans over runs of one kind (models/common.py layer_runs);
    # the feed-forward of all layers is one stack (params["layers"]).
    layer_types: Tuple[str, ...] = ()
    ssm_heads: int = 0                # Mamba-2 heads
    ssm_head_dim: int = 0             # values a head
    ssm_state: int = 0                # d_state: the state of a head is
                                      # [ssm_head_dim, ssm_state]
    ssm_groups: int = 1               # groups that share B and C
    ssm_conv: int = 4                 # taps of the causal depthwise conv
    # a Gated DeltaNet mixer (arXiv:2412.06464): gdn_heads heads whose
    # state is a matrix [gdn_value_dim, gdn_key_dim] updated by the
    # delta rule; q, k and v pass ONE causal depthwise conv of gdn_conv
    # taps (no bias) over 2 x heads x key_dim + heads x value_dim
    # channels; gdn_neg_eigval: beta = 2 sigmoid(b) in (0, 2), so the
    # transition I - beta k k^T has an eigenvalue in (-1, 1) (else beta
    # in (0, 1))
    gdn_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv: int = 4
    gdn_neg_eigval: bool = False
    # a Mamba-1 mixer (arXiv:2312.00752, as the `jamba` family has it):
    # mamba1_inner channels, each with a state of mamba1_state numbers
    # decayed by exp(dt[c] A[c, n]): a step size a CHANNEL (dt reaches
    # the channels through a bottleneck of mamba1_dt_rank) and a rate a
    # channel and state index, where a Mamba-2 head has one scalar; dt,
    # B and C are projected from the conv's OUTPUT (the conv of
    # mamba1_conv taps, with a bias, runs over the inner stream alone);
    # mamba1_norms: an RMSNorm with a learned weight on each of dt's
    # bottleneck, B and C (the family's; the published layer has none)
    mamba1_inner: int = 0
    mamba1_state: int = 0
    mamba1_dt_rank: int = 0
    mamba1_conv: int = 4
    mamba1_norms: bool = False
    # what a slot keeps between steps (state, conv tail), any kind, is
    # in `dtype`; a step's arithmetic is float32

    # Granite's four multipliers; 0 = the family has none (the term is
    # left out of the program, not multiplied by one)
    embedding_multiplier: float = 0.0  # on the token embedding
    residual_multiplier: float = 0.0   # on each sublayer's output
    attention_multiplier: float = 0.0  # the score scale, in place of
                                       # head_dim ** -0.5
    logits_scaling: float = 0.0        # logits are DIVIDED by it

    # per-layer attention pattern: layers of one model that differ in
    # mask and rotation. A layout has one 0/1 entry per layer (a longer
    # one is read up to num_layers: a config cut in depth); () = every
    # layer alike. The pattern rides the
    # layer scan as data (models/common.py layer_stack), so unlike
    # layers share one compiled body.
    sliding_window: int = 0           # p attends j only if p - j < this;
                                      # 0 = no layer slides
    sliding_window_layout: Tuple[int, ...] = ()  # 1 = the layer slides;
                                      # given whenever sliding_window > 0
    rope_layout: Tuple[int, ...] = ()  # 1 = the layer rotates q and k; 0 =
                                      # no positional encoding in the layer;
                                      # () = every layer, as pos_embedding says

    # attention variants
    qk_norm: bool = False             # an RMSNorm with a learned weight over
                                      # each head's queries and keys, before
                                      # the rotation
    qk_norm_wide: bool = False        # an RMSNorm with a learned weight over
                                      # the WHOLE query and the whole key
                                      # projection (all heads at once),
                                      # before the heads are split (OLMo 2)
    post_norm: bool = False           # the norm of a sublayer sits on its
                                      # OUTPUT, x + norm(F(x)), and none on
                                      # its input (OLMo 2); False = x +
                                      # F(norm(x)). models/common.py
                                      # stream_read / stream_write
    sandwich_norm: bool = False       # a norm on BOTH sides of a sublayer,
                                      # x + norm_post(F(norm_in(x))): four
                                      # learned weights a layer (ln1,
                                      # ln1_post, ln2, ln2_post); the same
                                      # pair carries it
    attn_gate: bool = False           # an output gate on attention: g = h
                                      # W_g [D, Nq x H] of the sublayer's
                                      # normed input, and the heads' output
                                      # times sigmoid(g), elementwise,
                                      # BEFORE the output projection
                                      # (models/common.py attn_gate)
    mup_embed: bool = False           # the token embedding times
                                      # sqrt(hidden_size), in float32
                                      # before it is rounded to `dtype`
    # learned sparse attention (the lightning indexer of DeepSeek Sparse
    # Attention): index_heads queries of index_head_dim and ONE index key
    # a token score every cached position, and a query attends only the
    # index_topk positions that score highest (all of them while there
    # are no more). The index keys are a cached row of their own
    # (cache/paged.py). All three are 0 for a model without an indexer.
    # Beside latent attention (DeepSeek-V3.2's own form, GLM-5) the
    # index queries read the QUERY LATENT c_q, the index key and the
    # weights the layer's normed input, and only the first
    # qk_rope_head_dim dims of an index head rotate (index_rope_dim)
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0

    # numerics
    dtype: str = "bfloat16"           # activation/weight compute dtype
    param_dtype: str = "float32"      # master param dtype

    # attention implementation: "dense" = XLA einsum attend over the cache;
    # "flash" = Pallas blockwise kernel — fresh prefills attend the
    # freshly-projected K/V, warm multi-token steps (chunk continuations,
    # prefix-cache resumes) fold the cached context in as a count-masked
    # prefix segment (ops/flash_attention.py warm-prefix prefill); the
    # engines swap it in for exactly those steps.
    attn_impl: str = "dense"

    def __post_init__(self):
        # a layout read from JSON is a list; the dataclass is a static
        # jit argument and must hash
        for name in ("sliding_window_layout", "rope_layout"):
            layout = tuple(int(v) for v in getattr(self, name))
            if layout and len(layout) < self.num_layers:
                raise ValueError(f"{name} has {len(layout)} entries for "
                                 f"{self.num_layers} layers")
            object.__setattr__(self, name, layout)
        kinds = tuple(str(k) for k in self.layer_types)[:self.num_layers]
        if kinds:
            if len(kinds) < self.num_layers \
                    or set(kinds) - {*RECURRENT_KINDS, "attention"}:
                raise ValueError(
                    f"layer_types names {len(kinds)} layers of "
                    f"{self.num_layers}, each 'mamba', 'linear_attention', "
                    f"'mamba1' or 'attention': {kinds}")
            both = [k for k in RECURRENT_KINDS if k in kinds]
            if len(both) > 1:
                raise ValueError(
                    "layer_types names " + " and ".join(map(repr, both))
                    + " layers: a model has one kind of recurrent layer")
            if "mamba" in kinds and not (self.ssm_heads and self.ssm_head_dim
                                         and self.ssm_state):
                raise ValueError("a 'mamba' layer needs ssm_heads, "
                                 "ssm_head_dim and ssm_state")
            if "linear_attention" in kinds and not (
                    self.gdn_heads and self.gdn_key_dim
                    and self.gdn_value_dim and self.gdn_conv > 1):
                raise ValueError(
                    "a 'linear_attention' layer needs gdn_heads, "
                    "gdn_key_dim, gdn_value_dim and a conv of two taps "
                    "or more (gdn_conv)")
            if "mamba1" in kinds and not (
                    self.mamba1_inner and self.mamba1_state
                    and self.mamba1_dt_rank and self.mamba1_conv > 1):
                raise ValueError(
                    "a 'mamba1' layer needs mamba1_inner, mamba1_state, "
                    "mamba1_dt_rank and a conv of two taps or more "
                    "(mamba1_conv)")
            if self.layer_pattern() is not None or self.has_indexer:
                raise ValueError(
                    "layer_types beside a per-layer attention pattern or "
                    "a sparse-attention indexer is not supported")
        object.__setattr__(self, "layer_types", kinds)
        self._check_norms()
        if self.router_input not in ("ffn", "attn"):
            raise ValueError(f"unknown router_input {self.router_input!r}")
        self._check_latent()
        self._check_router()
        self._check_rope_scaling()
        self._check_streams()
        if bool(self.sliding_window_layout) != (self.sliding_window > 0):
            raise ValueError("sliding_window and sliding_window_layout "
                             "come together: which layers slide is stated")
        if len({self.index_heads > 0, self.index_head_dim > 0,
                self.index_topk > 0}) > 1:
            raise ValueError("index_heads, index_head_dim and index_topk "
                             "come together: an indexer has all three")
        if self.index_topk and self.layer_pattern() is not None:
            raise ValueError("a sparse-attention indexer beside a per-layer "
                             "attention pattern is not supported")
        if self.router_input == "attn" and self.moe_impl == "ep":
            raise ValueError("expert parallelism (moe_impl 'ep') does not "
                             "carry router logits taken before attention")

    def _check_norms(self):
        """post_norm and qk_norm_wide beside what carries them: the
        paths of a model with a recurrent layer kind norm through
        stream_read / stream_write alone; others have bodies that norm
        a sublayer's input themselves."""
        if self.qk_norm and self.qk_norm_wide:
            raise ValueError("qk_norm (a head at a time) and qk_norm_wide "
                             "(the whole projection): one or the other")
        if self.sandwich_norm:
            for name, on in (("post_norm", self.post_norm),
                             ("hc_mult", bool(self.hc_mult)),
                             ("arch 'gpt2'", self.arch == "gpt2")):
                if on:
                    raise ValueError(
                        f"sandwich_norm beside {name} is not supported")
        if self.attn_gate and (self.is_latent or self.use_bias):
            raise ValueError("attn_gate beside kv_lora_rank (latent "
                             "attention) or use_bias is not supported")
        if not self.post_norm:
            return
        for name, on in (("no recurrent layer kind (layer_types)",
                          not self.has_ssm),
                         ("hc_mult", bool(self.hc_mult)),
                         ("router_input 'attn'",
                          self.routed and self.router_input == "attn"),
                         ("arch 'gpt2'", self.arch == "gpt2")):
            if on:
                raise ValueError(f"post_norm beside {name} is not supported")

    def _check_latent(self):
        """The latent-attention fields come together, and beside
        nothing that has no path for a cached latent yet."""
        split = ("q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                 "v_head_dim")
        missing = [n for n in split if getattr(self, n) <= 0]
        if not self.kv_lora_rank:
            given = [n for n in split if getattr(self, n)]
            if given or self.rope_interleave:
                raise ValueError(
                    f"{given or ['rope_interleave']} without kv_lora_rank: "
                    "the split head dims and the query's rank describe "
                    "latent attention, which has a latent of kv_lora_rank")
            return
        if missing:
            raise ValueError(
                f"kv_lora_rank {self.kv_lora_rank} without {missing}: "
                "latent attention has a query latent (q_lora_rank) and a "
                "head's qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim rotates in pairs: it is even")
        if self.pos_embedding != "rope":
            raise ValueError("latent attention rotates its shared key: "
                             f"pos_embedding is {self.pos_embedding!r}")
        if self.has_indexer and self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError(
                f"index_head_dim {self.index_head_dim} under "
                f"qk_rope_head_dim {self.qk_rope_head_dim}: beside latent "
                "attention an index head's first qk_rope_head_dim dims rotate")
        for name, on in (("layer_types", bool(self.layer_types)),
                         ("sliding_window", self.layer_pattern() is not None),
                         ("qk_norm", self.qk_norm or self.qk_norm_wide),
                         ("use_bias", self.use_bias),
                         ("attention_multiplier",
                          bool(self.attention_multiplier))):
            if on:
                raise ValueError(f"{name} beside kv_lora_rank (latent "
                                 "attention) is not supported")

    def _check_router(self):
        """The router's and the experts' fields describe a model of
        experts; leading dense layers are fewer than the layers."""
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router_score {self.router_score!r}")
        for name, on in (("router_score", self.router_score != "softmax"),
                         ("router_bias", self.router_bias),
                         ("routed_scaling_factor",
                          bool(self.routed_scaling_factor)),
                         ("moe_intermediate_size",
                          bool(self.moe_intermediate_size)),
                         ("first_k_dense", bool(self.first_k_dense))):
            if not on:
                continue
            if not self.routed:
                raise ValueError(f"{name} without num_experts: it "
                                 "describes a model of routed experts")
            if self.moe_impl == "ep" and name != "moe_intermediate_size":
                raise ValueError(f"expert parallelism (moe_impl 'ep') does "
                                 f"not carry {name}")
        if not 0 <= self.first_k_dense < max(self.num_layers, 1):
            raise ValueError(
                f"first_k_dense {self.first_k_dense} of {self.num_layers} "
                "layers: the leading dense layers are fewer than the "
                "layers, and the rest have experts")
        if self.first_k_dense and (self.has_ssm or self.has_indexer) \
                and not self.is_latent:
            raise ValueError(
                "first_k_dense beside layer_types or an indexer over keys "
                "and values: feed-forwards of two shapes run as layer runs, "
                "which the latent-attention family's forward and the plain "
                "grouped-query one (models/common.py _runs_forward) carry")
        if not self.experts_held:
            if self.experts_first:
                raise ValueError("experts_first without experts_held: the "
                                 "share's first expert comes with its count")
            return
        if not 0 <= self.experts_first \
                <= self.num_experts - self.experts_held \
                or self.experts_held < 0:
            raise ValueError(
                f"experts_held {self.experts_held} from expert "
                f"{self.experts_first} on, of num_experts "
                f"{self.num_experts}: the share lies inside the experts")
        if self.moe_impl == "ep":
            raise ValueError(
                "expert parallelism (moe_impl 'ep') does not carry "
                "experts_held: it splits ALL the experts over a mesh and "
                "runs their exchange, a share holds some and runs none")

    def _check_rope_scaling(self):
        """A published `rope_scaling` group, held as sorted items: of
        type "yarn" with every key yarn_inv_freq and attn_scale_mult
        read, on a model that rotates."""
        rs = dict(self.rope_scaling or ())
        object.__setattr__(self, "rope_scaling", tuple(sorted(rs.items())))
        if not rs:
            return
        if rs.get("type") != "yarn":
            raise ValueError(f"rope_scaling of type {rs.get('type')!r}: the "
                             "program knows plain rotation and 'yarn'")
        missing = [k for k in ("factor", "original_max_position_embeddings")
                   if not rs.get(k)]
        if missing:
            raise ValueError(f"rope_scaling 'yarn' without {missing}")
        if self.pos_embedding != "rope" or self.layer_pattern() is not None:
            raise ValueError(
                "rope_scaling on a model that does not rotate every layer "
                f"(pos_embedding {self.pos_embedding!r}, or a per-layer "
                "pattern) is not supported")
        if rs.get("mscale_all_dim") and not self.is_latent:
            raise ValueError(
                "rope_scaling.mscale_all_dim scales the softmax of latent "
                "attention (kv_lora_rank); no other attention here takes it")
        if rs.get("mscale_all_dim") \
                and rs.get("mscale", 1) != rs["mscale_all_dim"]:
            raise ValueError(
                f"rope_scaling mscale {rs.get('mscale')} beside "
                f"mscale_all_dim {rs['mscale_all_dim']}: cos and sin would "
                "carry their ratio, which the program does not apply")

    def _check_streams(self):
        """hc_mult streams come with at least one Sinkhorn round, an
        ordered clamp, and beside nothing that reads a layer's input
        around the sublayer's own read of the streams."""
        if self.hc_mult < 0 or self.hc_mult == 1:
            raise ValueError(f"hc_mult {self.hc_mult}: 0 (one stream, "
                             "x + F(norm(x))) or two streams and more")
        if not self.hc_mult:
            return
        if self.hc_sinkhorn_iters < 1 or self.hc_eps <= 0 \
                or not self.hc_clamp_min < self.hc_clamp_max:
            raise ValueError(
                f"hc_mult {self.hc_mult} with hc_sinkhorn_iters "
                f"{self.hc_sinkhorn_iters}, hc_eps {self.hc_eps}, clamp "
                f"[{self.hc_clamp_min}, {self.hc_clamp_max}]: one round "
                "or more, a positive eps, an ordered clamp")
        for name, on in (("layer_types", bool(self.layer_types)),
                         ("index_topk", self.has_indexer),
                         ("router_input 'attn'",
                          self.routed and self.router_input == "attn"),
                         ("residual_multiplier",
                          bool(self.residual_multiplier)),
                         ("arch 'gpt2'", self.arch == "gpt2")):
            if on:
                raise ValueError(f"{name} beside hc_mult (n residual "
                                 "streams) is not supported")

    def yarn_inv_freq(self) -> np.ndarray:
        """The rotation rate of each pair of rope_dim under rope_scaling
        "yarn", float32 [rope_dim / 2]: pair i's plain rate f_i =
        theta^(-2i/rope_dim) where it turns more than beta_fast times
        over the original context, f_i / factor where it turns fewer
        than beta_slow times, and a linear ramp between the two pairs
        those counts fall on (the correction range: floor and ceil,
        clipped to [0, rope_dim - 1], as the DeepSeek-V2/V3 code the
        keys come from has it)."""
        rs = dict(self.rope_scaling)
        half = self.rope_dim // 2
        freq = self.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
        orig = rs["original_max_position_embeddings"]

        def pair_of(turns):    # the pair that turns `turns` times in orig
            return half * np.log(orig / (turns * 2 * np.pi)) \
                / np.log(self.rope_theta)

        lo = max(np.floor(pair_of(rs.get("beta_fast", 32))), 0)
        hi = min(np.ceil(pair_of(rs.get("beta_slow", 1))), 2 * half - 1)
        ramp = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0, 1)
        return (freq / rs["factor"] * ramp
                + freq * (1 - ramp)).astype(np.float32)

    @property
    def attn_scale_mult(self) -> float:
        """What rope_scaling "yarn" puts on the softmax scale of latent
        attention: m^2, m = 0.1 x mscale_all_dim x ln(factor) + 1 (1.0
        without). Cos and sin stay unscaled: their factor is m(mscale)
        / m(mscale_all_dim), 1 where the two are equal
        (_check_rope_scaling refuses a file in which they differ)."""
        rs = dict(self.rope_scaling)
        if not rs or not rs.get("mscale_all_dim") or rs["factor"] <= 1:
            return 1.0
        m = 0.1 * rs["mscale_all_dim"] * np.log(rs["factor"]) + 1.0
        return float(m * m)

    @property
    def attn_scale(self) -> float:
        """The softmax scale of latent attention: (nope + rope) ** -0.5
        times attn_scale_mult."""
        return self.qk_head_dim ** -0.5 * self.attn_scale_mult

    @property
    def is_moe(self) -> bool:
        """The configuration states experts (num_experts >= 1): what a
        benchmark file's fields are held to. The program asks `routed`
        wherever it builds, shards or checks anything of a router."""
        return self.num_experts > 0

    @property
    def routed(self) -> bool:
        """A router chooses among experts. With ONE expert there is
        nothing to choose: the layer is that expert, a dense
        feed-forward of intermediate_size under params["layers"]["mlp"],
        and no router is built (the `jamba` family publishes
        `num_experts` 1 for exactly that and builds its plain
        feed-forward there)."""
        return self.num_experts > 1

    @property
    def is_latent(self) -> bool:
        """Latent attention: a token caches one latent row, not heads."""
        return self.kv_lora_rank > 0

    @property
    def latent_row(self) -> int:
        """Values of a token's cached row: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        """Width of a head's query and key (a latent model's two parts)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim \
            if self.is_latent else self.head_dim

    @property
    def rope_dim(self) -> int:
        """Dims of a head that rotate: all of them, or a latent model's
        rotary part."""
        return self.qk_rope_head_dim if self.is_latent else self.head_dim

    @property
    def expert_width(self) -> int:
        """Width of one routed expert."""
        return self.moe_intermediate_size or self.intermediate_size

    def layer_pattern(self):
        """{"sliding_window": int32 [L] (0 = the layer is full),
        "rope": int32 [L]} where some layer slides or some layer does
        not rotate, else None: the model's layers are all alike and no
        program carries a pattern."""
        L = self.num_layers
        if not (self.sliding_window > 0 or 0 in self.rope_layout[:L]):
            return None
        slides = self.sliding_window_layout[:L] or (0,) * L
        rope = self.rope_layout[:L] or (1,) * L
        return {"sliding_window": np.asarray(slides, np.int32)
                * np.int32(self.sliding_window),
                "rope": np.asarray(rope, np.int32)}

    @property
    def slides(self) -> Tuple[int, ...]:
        """One entry a layer, 1 where it slides; () for a model none of
        whose layers does. What the cache reads to keep a sliding
        layer's rows apart (cache/paged.py ring_pages)."""
        if self.sliding_window <= 0:
            return ()
        kinds = self.sliding_window_layout[:self.num_layers]
        return kinds if any(kinds) else ()

    @property
    def has_indexer(self) -> bool:
        return self.index_topk > 0

    @property
    def index_rope_dim(self) -> int:
        """Dims of an index head that rotate, its first: beside latent
        attention the rotary part's width (the rest pass unrotated, as a
        head's q_nope does), else all of them."""
        return self.qk_rope_head_dim if self.is_latent \
            else self.index_head_dim

    @property
    def local_experts(self) -> int:
        """Experts whose weights are here: the share, or all of them."""
        return self.experts_held or self.num_experts

    @property
    def recurrent_kind(self) -> str:
        """The model's ONE kind of recurrent layer (of
        RECURRENT_KINDS), "" for a model without."""
        return next((k for k in RECURRENT_KINDS if k in self.layer_types),
                    "")

    @property
    def has_ssm(self) -> bool:
        """Some layer is a recurrent mixer (Mamba-2, Gated DeltaNet or
        Mamba-1): a stream holds a fixed-size state beside (or in place
        of) its pages."""
        return bool(self.recurrent_kind)

    @property
    def num_ssm_layers(self) -> int:
        """Layers of the recurrent kind."""
        return self.layer_types.count(self.recurrent_kind) \
            if self.has_ssm else 0

    @property
    def num_attn_layers(self) -> int:
        """Layers that own rows of the paged pool."""
        return self.layer_types.count("attention") if self.layer_types \
            else self.num_layers

    @property
    def ssm_inner(self) -> int:
        """Width of the mixer's inner stream: heads x head_dim."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels through the conv: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def gdn_key_width(self) -> int:
        """All heads' keys (and queries) of a Gated DeltaNet mixer."""
        return self.gdn_heads * self.gdn_key_dim

    @property
    def gdn_value_width(self) -> int:
        """All heads' values (and the output gate)."""
        return self.gdn_heads * self.gdn_value_dim

    @property
    def gdn_conv_dim(self) -> int:
        """Channels through the conv: q, k and v."""
        return 2 * self.gdn_key_width + self.gdn_value_width

    @property
    def gdn_head_group(self) -> int:
        """Heads whose values share one row of lanes in the stored
        state (cache/ssm_state.py): the fewest that make a whole number
        of 128 lanes of gdn_value_dim-wide values, if they divide the
        heads; else 1 (a head's values alone, padded to whole lanes by
        the device)."""
        g = 128 // np.gcd(self.gdn_value_dim, 128)
        return int(g) if self.gdn_heads % g == 0 else 1

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Presets (BASELINE.json configs[0..3] model families)
# ---------------------------------------------------------------------------

def gpt2_124m() -> ModelConfig:
    return ModelConfig(
        arch="gpt2", vocab_size=50257, hidden_size=768, num_layers=12,
        num_heads=12, num_kv_heads=12, head_dim=64, intermediate_size=3072,
        max_seq_len=1024, norm_eps=1e-5, use_bias=True, tie_embeddings=True,
        act="gelu_new", pos_embedding="learned",
    )


def llama3_8b() -> ModelConfig:
    return ModelConfig(
        arch="llama", vocab_size=128256, hidden_size=4096, num_layers=32,
        num_heads=32, num_kv_heads=8, head_dim=128, intermediate_size=14336,
        max_seq_len=8192, rope_theta=500000.0,
    )


def llama3_70b() -> ModelConfig:
    return ModelConfig(
        arch="llama", vocab_size=128256, hidden_size=8192, num_layers=80,
        num_heads=64, num_kv_heads=8, head_dim=128, intermediate_size=28672,
        max_seq_len=8192, rope_theta=500000.0,
    )


def mixtral_8x7b() -> ModelConfig:
    return ModelConfig(
        arch="mixtral", vocab_size=32000, hidden_size=4096, num_layers=32,
        num_heads=32, num_kv_heads=8, head_dim=128, intermediate_size=14336,
        max_seq_len=32768, rope_theta=1000000.0,
        num_experts=8, num_experts_per_tok=2,
    )


def smallthinker_21b_a3b() -> ModelConfig:
    """SmallThinker-21BA3B-Instruct (huggingface.co/PowerInfer): 64 ReGLU
    experts of width 768, 6 a token, routed on the attention's normed
    input; of every four layers the first is full attention without
    positional encoding, the other three slide over 4,096 tokens and
    rotate."""
    L = 52
    return ModelConfig(
        arch="smallthinker", vocab_size=151936, hidden_size=2560,
        num_layers=L, num_heads=28, num_kv_heads=4, head_dim=128,
        intermediate_size=768, max_seq_len=16384, norm_eps=1e-6,
        rope_theta=1500000.0, act="relu",
        num_experts=64, num_experts_per_tok=6, router_input="attn",
        sliding_window=4096, sliding_window_layout=(0, 1, 1, 1) * (L // 4),
        rope_layout=(0, 1, 1, 1) * (L // 4),
    )


def keye_vl2_30b_a3b() -> ModelConfig:
    """The language model of Keye-VL-2.0-30B-A3B (huggingface.co/Kwai-Keye):
    grouped-query attention, 32 queries over 4 KV heads of 128, a norm on
    each head's queries and keys, and a sparse-attention indexer (16 index
    heads of 64, one index key a token) that picks the 2,048 cached
    positions a query attends; 128 experts of 768, 8 a token, every layer
    sparse. Text only: the vision tower is not here, and a text sequence
    gives the three position ids of `mrope_section` one value, plain RoPE."""
    return ModelConfig(
        arch="keye", vocab_size=151936, hidden_size=2048, num_layers=48,
        num_heads=32, num_kv_heads=4, head_dim=128, intermediate_size=768,
        max_seq_len=262144, norm_eps=1e-6, rope_theta=1e7,
        num_experts=128, num_experts_per_tok=8, qk_norm=True,
        index_heads=16, index_head_dim=64, index_topk=2048,
    )


#: one period of granite-4.0-h-small's published pattern (layers 0-9)
_GRANITE_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


def granite_4_h_small() -> ModelConfig:
    """granite-4.0-h-small (huggingface.co/ibm-granite, `granitemoehybrid`,
    32B-A9B): 40 layers of which 36 are Mamba-2 mixers (128 heads of 64,
    d_state 128, one group, conv of 4) and 4 are grouped-query attention
    without positional encoding; every layer is followed by 72 SiLU
    experts of 768, 10 a token, and one shared expert of 1,536; Granite's
    four multipliers; tied embeddings."""
    # attention at layers 5, 15, 25, 35: 9 Mamba layers to 1
    kinds = _GRANITE_PERIOD * 4
    return ModelConfig(
        arch="granite_hybrid", vocab_size=100352, hidden_size=4096,
        num_layers=40, num_heads=32, num_kv_heads=8, head_dim=128,
        intermediate_size=768, max_seq_len=131072, norm_eps=1e-5,
        pos_embedding="none", tie_embeddings=True,
        num_experts=72, num_experts_per_tok=10,
        shared_intermediate_size=1536, layer_types=kinds,
        ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
        ssm_conv=4, embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.0078125, logits_scaling=16.0,
    )


def joyai_llm_flash() -> ModelConfig:
    """JoyAI-LLM-Flash (huggingface.co/jdopensource, `joyai_llm_flash`,
    48B-A2.7B): 40 layers of latent attention (32 heads of 128 + 64
    rotary against ONE cached row of 512 + 64 a token, values of 128,
    the query through a latent of 1,536); layer 0's feed-forward is
    dense (7,168), every other layer has 256 sigmoid-routed experts of
    768, 8 a token chosen with a selection bias and weighted by their
    normalised scores times 2.5, plus one shared expert; untied head.
    The published prediction layer (`num_nextn_predict_layers` 1) takes
    no part in the next-token distribution and is not held."""
    return ModelConfig(
        arch="joyai", vocab_size=129280, hidden_size=2048, num_layers=40,
        num_heads=32, num_kv_heads=32, head_dim=64, intermediate_size=7168,
        max_seq_len=131072, norm_eps=1e-6, rope_theta=32e6,
        num_experts=256, num_experts_per_tok=8, moe_intermediate_size=768,
        shared_intermediate_size=768, first_k_dense=1,
        router_score="sigmoid", router_bias=True, routed_scaling_factor=2.5,
        kv_lora_rank=512, q_lora_rank=1536, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_interleave=True,
    )


def xing4_29b_a4b() -> ModelConfig:
    """Xing4.0-29B-A4B (huggingface.co/XingChen-AGI, `xing4_0`): 40
    layers whose residual path is FOUR streams mixed by
    manifold-constrained hyper-connections (hc_mult 4, 20 Sinkhorn
    rounds) around latent attention (32 heads of 128 + 64 rotary against
    ONE cached row of 512 + 64 a token, values of 128, the query through
    a latent of 768; YaRN, factor 64 over 4,096) and a feed-forward
    that is dense (9,216) in layers 0-1 and 64 sigmoid-routed experts
    of 1,024, 4 a token with a selection bias and weights times 2, plus
    one shared expert, behind them; untied head. The published
    prediction layer (`num_nextn_predict_layers` 1) takes no part in
    the next-token distribution and is not held."""
    return ModelConfig(
        arch="xing", vocab_size=131072, hidden_size=3584, num_layers=40,
        num_heads=32, num_kv_heads=32, intermediate_size=9216,
        max_seq_len=262144, norm_eps=1e-6, rope_theta=10000.0,
        rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096},
        num_experts=64, num_experts_per_tok=4, moe_intermediate_size=1024,
        shared_intermediate_size=1024, first_k_dense=2,
        router_score="sigmoid", router_bias=True, routed_scaling_factor=2.0,
        kv_lora_rank=512, q_lora_rank=768, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_interleave=True,
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_clamp_min=-30.0, hc_clamp_max=30.0,
    )


def glm5() -> ModelConfig:
    """GLM-5 (huggingface.co/zai-org, `glm_moe_dsa`, 744B-A40B): 78
    layers of latent attention (64 heads of 192 + 64 rotary against ONE
    cached row of 512 + 64 a token, values of 256, the query through a
    latent of 2,048) whose rows a lightning indexer SELECTS (DeepSeek
    Sparse Attention: 32 index heads of 128 fed from the query latent,
    one index key a token, the 2,048 positions that score highest);
    layers 0-2 have a dense feed-forward (12,288), every other layer 256
    sigmoid-routed experts of 2,048, 8 a token chosen with a selection
    bias and weighted by their normalised scores times 2.5, plus one
    shared expert; untied head. No chip holds one expert layer (9.66 G
    parameters): a deployment splits the experts (experts_held). The
    published prediction layer (`num_nextn_predict_layers` 1) takes no
    part in the next-token distribution and is not held."""
    return ModelConfig(
        arch="glm5", vocab_size=154880, hidden_size=6144, num_layers=78,
        num_heads=64, num_kv_heads=64, head_dim=64, intermediate_size=12288,
        max_seq_len=202752, norm_eps=1e-5, rope_theta=1e6,
        num_experts=256, num_experts_per_tok=8, moe_intermediate_size=2048,
        shared_intermediate_size=2048, first_k_dense=3,
        router_score="sigmoid", router_bias=True, routed_scaling_factor=2.5,
        kv_lora_rank=512, q_lora_rank=2048, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, rope_interleave=True,
        index_heads=32, index_head_dim=128, index_topk=2048,
    )


def olmo_hybrid_7b() -> ModelConfig:
    """Olmo-Hybrid-7B (huggingface.co/allenai, `olmo_hybrid`): 32 layers,
    of every four three Gated DeltaNet mixers (30 heads whose state is
    192 x 96, updated by the delta rule with beta in (0, 2); q, k and v
    through one causal conv of 4 taps; a gated norm a head) and one full
    attention layer of 30 heads over 30 KV heads of 128 with a norm over
    the whole query and key projections and NO rotation; a dense SwiGLU
    of 11,008 in every layer; the norm of each sublayer on its OUTPUT;
    untied head."""
    return ModelConfig(
        arch="olmo_hybrid", vocab_size=100352, hidden_size=3840,
        num_layers=32, num_heads=30, num_kv_heads=30, head_dim=128,
        intermediate_size=11008, max_seq_len=65536, norm_eps=1e-6,
        pos_embedding="none", post_norm=True, qk_norm_wide=True,
        layer_types=(("linear_attention",) * 3 + ("attention",)) * 8,
        gdn_heads=30, gdn_key_dim=96, gdn_value_dim=192, gdn_conv=4,
        gdn_neg_eigval=True,
    )


def jamba2_3b() -> ModelConfig:
    """AI21-Jamba2-3B (huggingface.co/ai21labs, `jamba`): 28 layers of
    which 26 are Mamba-1 mixers (5,120 channels with a state of 16 each,
    decayed a channel and a state index at a time; dt through a rank of
    160; a conv of 4 taps with a bias over the inner stream; an RMSNorm
    on each of dt, B and C) and layers 7 and 21 attention of 20 heads
    over ONE key-value head of 128 without rotation; a dense SwiGLU of
    8,192 in every layer (`num_experts` 1: no router); tied embeddings."""
    return ModelConfig(
        arch="jamba", vocab_size=65536, hidden_size=2560, num_layers=28,
        num_heads=20, num_kv_heads=1, head_dim=128, intermediate_size=8192,
        max_seq_len=262144, norm_eps=1e-6, pos_embedding="none",
        tie_embeddings=True, num_experts=1, num_experts_per_tok=1,
        layer_types=tuple("attention" if l % 14 == 7 else "mamba1"
                          for l in range(28)),
        mamba1_inner=5120, mamba1_state=16, mamba1_dt_rank=160,
        mamba1_conv=4, mamba1_norms=True,
    )


def trinity_large(layers: int = 60) -> ModelConfig:
    """Trinity-Large-Preview (huggingface.co/arcee-ai, `afmoe`,
    400B-A13B): 60 layers of grouped-query attention, 48 queries over 8
    key-value heads of 128 with a norm on each head's queries and keys
    and an OUTPUT GATE (sigmoid of a projection of the layer's normed
    input, before the output projection); of every four layers three
    slide over 4,096 tokens and rotate, the fourth is full and does not;
    a norm on both sides of each sublayer; the embedding times
    sqrt(3,072); layers 0-5 a dense SwiGLU of 12,288, every other layer
    256 sigmoid-routed experts of 3,072, 4 a token chosen with a
    selection bias and weighted by their normalised scores times 2.448,
    plus one shared expert; untied head. `layers`: the first so many of
    the published sixty."""
    return ModelConfig(
        arch="trinity", vocab_size=200192, hidden_size=3072,
        num_layers=layers, num_heads=48, num_kv_heads=8, head_dim=128,
        intermediate_size=12288, max_seq_len=262144, norm_eps=1e-5,
        rope_theta=10000.0, num_experts=256, num_experts_per_tok=4,
        moe_intermediate_size=3072, shared_intermediate_size=3072,
        first_k_dense=6, router_score="sigmoid", router_bias=True,
        routed_scaling_factor=2.448, qk_norm=True, sliding_window=4096,
        sliding_window_layout=(1, 1, 1, 0) * 15,
        rope_layout=(1, 1, 1, 0) * 15,
        sandwich_norm=True, attn_gate=True, mup_embed=True,
    )


def trinity_large_ep8() -> ModelConfig:
    """ONE chip of an 8-way expert-parallel stage of
    Trinity-Large-Preview, as servebench/configs/trinity-large-ep8.json
    cuts it: published layers 5-12 (the last leading dense layer, then
    seven expert layers: S | S F S S S F S), experts 0-31 of each
    layer's 256, an eighth of the vocabulary."""
    kinds = (1, 1, 0, 1, 1, 1, 0, 1)
    return trinity_large(8).replace(
        first_k_dense=1, experts_held=32, experts_first=0, vocab_size=25024,
        sliding_window_layout=kinds, rope_layout=kinds)


def tiny(arch: str = "llama", **kw) -> ModelConfig:
    """Small config for tests: runs in <1s on CPU, exercises every code path."""
    base = dict(
        # 258 = ByteTokenizer vocab (bytes + BOS/EOS) so the CLI demo works.
        vocab_size=258, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, intermediate_size=128, max_seq_len=128,
    )
    if arch == "gpt2":
        base.update(num_kv_heads=4, use_bias=True, tie_embeddings=True,
                    act="gelu_new", pos_embedding="learned")
    if arch == "mixtral":
        base.update(num_experts=4, num_experts_per_tok=2)
    if arch == "smallthinker":
        # the shape of the real one: 7 queries a KV head, ReGLU experts,
        # the pattern [0, 1, 1, 1], a window short enough to bind
        base.update(num_layers=4, num_heads=14, num_kv_heads=2,
                    intermediate_size=48, act="relu",
                    num_experts=8, num_experts_per_tok=3,
                    router_input="attn", sliding_window=8,
                    sliding_window_layout=(0, 1, 1, 1),
                    rope_layout=(0, 1, 1, 1))
    if arch == "keye":
        # the shape of the real one: norms on heads, experts in every
        # layer, an indexer whose top-k binds inside the toy's contexts
        base.update(intermediate_size=32, num_experts=8,
                    num_experts_per_tok=2, qk_norm=True, index_heads=2,
                    index_head_dim=16, index_topk=8)
    if arch == "granite_hybrid":
        # every mechanism of the real one: both layer kinds, conv of 4,
        # heads x head_dim x state, experts with a shared one, the four
        # multipliers, a tied head, no positional encoding
        base.update(num_layers=4, intermediate_size=32, num_experts=8,
                    num_experts_per_tok=3, shared_intermediate_size=48,
                    layer_types=("mamba", "mamba", "attention", "mamba"),
                    ssm_heads=8, ssm_head_dim=16, ssm_state=16,
                    pos_embedding="none", tie_embeddings=True,
                    embedding_multiplier=12.0, residual_multiplier=0.22,
                    attention_multiplier=0.0625, logits_scaling=16.0)
    if arch == "olmo_hybrid":
        # every mechanism of the real one: two Gated DeltaNet layers
        # and a full one, values twice as wide as keys, FOUR heads'
        # values in one row of 128 lanes (the real one: two in 384),
        # beta to 2, one query a KV head, the projection-wide norms, no
        # rotation, the norm on each sublayer's output, an untied head
        base.update(num_layers=3, num_kv_heads=4,
                    layer_types=("linear_attention", "linear_attention",
                                 "attention"),
                    gdn_heads=4, gdn_key_dim=16, gdn_value_dim=32,
                    gdn_neg_eigval=True, pos_embedding="none",
                    post_norm=True, qk_norm_wide=True, norm_eps=1e-6)
    if arch == "jamba":
        # every mechanism of the real one: two Mamba-1 layers and an
        # attention layer of 4 heads over ONE key-value head, a state of
        # 16 a channel, dt through a bottleneck, the three inner norms,
        # no rotation, ONE "expert" (a dense feed-forward), a tied head
        base.update(num_layers=3, num_kv_heads=1, num_experts=1,
                    num_experts_per_tok=1,
                    layer_types=("mamba1", "attention", "mamba1"),
                    mamba1_inner=128, mamba1_state=16, mamba1_dt_rank=8,
                    mamba1_norms=True, pos_embedding="none",
                    tie_embeddings=True, norm_eps=1e-6)
    if arch == "joyai":
        # every mechanism of the real one: the query's latent, one cached
        # row of latent + rotary key, value heads narrower than the
        # query's, interleaved rotation, a leading dense layer of its
        # own width, sigmoid routing with a bias and a scale, a shared
        # expert
        base.update(num_layers=3, intermediate_size=96,
                    moe_intermediate_size=32, shared_intermediate_size=32,
                    num_experts=8, num_experts_per_tok=3, first_k_dense=1,
                    router_score="sigmoid", router_bias=True,
                    routed_scaling_factor=2.5, kv_lora_rank=32,
                    q_lora_rank=48, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True)
    if arch == "xing":
        # every mechanism of the real one: four residual streams mixed
        # with 20 Sinkhorn rounds, TWO leading dense layers before the
        # experts, latent attention under a YaRN rotation whose ramp
        # falls inside the toy's 4 pairs, sigmoid routing with a bias
        # and a scale, a shared expert
        base.update(num_layers=4, intermediate_size=96,
                    moe_intermediate_size=32, shared_intermediate_size=32,
                    num_experts=8, num_experts_per_tok=3, first_k_dense=2,
                    router_score="sigmoid", router_bias=True,
                    routed_scaling_factor=2.0, kv_lora_rank=32,
                    q_lora_rank=48, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True,
                    rope_theta=10000.0,
                    rope_scaling={"type": "yarn", "factor": 64,
                                  "beta_fast": 32, "beta_slow": 1,
                                  "mscale": 1, "mscale_all_dim": 1,
                                  "original_max_position_embeddings": 64},
                    hc_mult=4)
    if arch == "glm5":
        # every mechanism of the real one: JoyAI's toy with a key part
        # wider than the rotary one and values wider still, an indexer
        # fed from the query latent whose heads are TWICE the rotary
        # width (half of a head rotates, half passes) and whose top-k
        # binds inside the toy's contexts; all experts held (a test
        # gives it a share)
        base.update(num_layers=3, intermediate_size=96,
                    moe_intermediate_size=32, shared_intermediate_size=32,
                    num_experts=8, num_experts_per_tok=3, first_k_dense=1,
                    router_score="sigmoid", router_bias=True,
                    routed_scaling_factor=2.5, kv_lora_rank=32,
                    q_lora_rank=48, qk_nope_head_dim=24,
                    qk_rope_head_dim=8, v_head_dim=40, rope_interleave=True,
                    rope_theta=1e6, index_heads=2, index_head_dim=16,
                    index_topk=8)
    if arch == "trinity":
        # every mechanism of the real one: six queries a KV head, norms
        # on heads, the output gate, a norm on both sides of a sublayer,
        # the scaled embedding, sliding layers that rotate beside full
        # ones that do not (the published S S S F), a window short
        # enough to bind, a leading dense layer of its own width,
        # sigmoid routing with a bias and a scale, a shared expert; all
        # experts held (a test gives it a share)
        base.update(num_layers=5, num_heads=12, num_kv_heads=2,
                    intermediate_size=96, moe_intermediate_size=32,
                    shared_intermediate_size=32, num_experts=8,
                    num_experts_per_tok=2, first_k_dense=1,
                    router_score="sigmoid", router_bias=True,
                    routed_scaling_factor=2.448, qk_norm=True,
                    rope_theta=10000.0, sliding_window=8,
                    sliding_window_layout=(1, 1, 1, 0, 1),
                    rope_layout=(1, 1, 1, 0, 1), sandwich_norm=True,
                    attn_gate=True, mup_embed=True)
    base.update(kw)
    return ModelConfig(arch=arch, **base)


PRESETS = {
    "gpt2-124m": gpt2_124m,
    "llama3-8b": llama3_8b,
    "llama3-70b": llama3_70b,
    "mixtral-8x7b": mixtral_8x7b,
    "smallthinker-21b-a3b": smallthinker_21b_a3b,
    "keye-vl2-30b-a3b": keye_vl2_30b_a3b,
    "granite-4.0-h-small": granite_4_h_small,
    "joyai-llm-flash": joyai_llm_flash,
    "xing4.0-29b-a4b": xing4_29b_a4b,
    "glm-5": glm5,
    "olmo-hybrid-7b": olmo_hybrid_7b,
    "jamba2-3b": jamba2_3b,
    "trinity-large": trinity_large,
    "trinity-large-ep8": trinity_large_ep8,
}


# ---------------------------------------------------------------------------
# Mesh / parallelism config
# ---------------------------------------------------------------------------

#: Canonical mesh axis names, outermost-first. Collectives over `tensor`
#: (innermost) ride the fastest ICI links; `data` (outermost) may span DCN.
MESH_AXES: Tuple[str, ...] = ("data", "stage", "expert", "seq", "tensor")


@dataclass(frozen=True)
class MeshConfig:
    """Sizes of the parallelism axes; the product must equal device count.

    data   : data parallel (replicated params, sharded batch)
    stage  : pipeline parallel (layer groups, ppermute handoff)
    expert : MoE expert parallel (all_to_all token routing)
    seq    : sequence/context parallel (ring attention / Ulysses)
    tensor : tensor parallel (Megatron row/column sharding, psum)
    """

    data: int = 1
    stage: int = 1
    expert: int = 1
    seq: int = 1
    tensor: int = 1

    @property
    def axis_sizes(self) -> Tuple[int, ...]:
        return (self.data, self.stage, self.expert, self.seq, self.tensor)

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.axis_sizes:
            n *= s
        return n

    def replace(self, **kw) -> "MeshConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class RuntimeConfig:
    """Serving/engine runtime knobs (BASELINE.json configs[4] surface)."""

    max_batch_size: int = 8
    max_seq_len: int = 2048
    prefill_chunk: int = 512          # upper bound on the width C of a
                                      # prompt chunk in a mixed block's
                                      # step (C is the smaller of this
                                      # and prefill_inline_budget), and
                                      # each shard's share of a
                                      # seq-parallel lane dispatch; long
                                      # prompts continue across steps
                                      # and ticks
    prefill_max_batch: int = 8        # how many one-token requests the
                                      # server's warm-up submits as a
                                      # burst before it listens
                                      # (serve/server.py run_server):
                                      # nothing else reads it since the
                                      # batched prefill dispatch it
                                      # capped went (ROADMAP C5)
    prefill_inline_budget: int = 32   # max prefill
                                      # tokens chewed per scan STEP
                                      # across all prefilling slots —
                                      # the ITL-tail knob. Each
                                      # prefilling slot consumes a
                                      # C-token chunk per step; this
                                      # bounds how many slots may be in
                                      # prefill phase concurrently
                                      # (budget // C), trading admission
                                      # throughput against decode-slot
                                      # step latency
    seq_parallel_threshold: int = 0   # long-prompt admission lane: a
                                      # waiting prompt LONGER than this
                                      # routes its prefill through
                                      # chunked seq-parallel dispatches
                                      # (ring attention over the mesh's
                                      # seq axis, engine.sp_prefill_chunk)
                                      # whose K/V lands in the ordinary
                                      # page pool — prefix-registry-
                                      # visible, evictable, exportable —
                                      # then decodes as a normal paged
                                      # slot. 0 = off (every prompt
                                      # takes the single-device chunk
                                      # path). Needs a mesh with seq > 1
                                      # and stage == 1; ignored (with a
                                      # warning) otherwise
    seq_parallel_chunk: int = 0       # tokens per seq-parallel prefill
                                      # dispatch (rounded up to a
                                      # multiple of the seq degree N).
                                      # 0 = auto: N * prefill_chunk —
                                      # each shard chews a prefill_chunk
                                      # worth of work per dispatch
    page_size: int = 16               # paged-KV tokens per block
    num_pages: int = 0                # 0 => derive from max_batch/max_seq
    max_queue: int = 256
    decode_steps_per_tick: int = 1    # fused decode block width: the
                                      # scheduler runs this many decode
                                      # iterations per tick() inside ONE
                                      # jitted scan (one dispatch + one
                                      # stacked drain per tick)
    inflight_blocks: int = 2          # decode blocks kept IN FLIGHT on
                                      # the device: block t+1 chains on
                                      # block t's device-resident carry
                                      # before t is drained, so host
                                      # scheduling overlaps device
                                      # compute (dispatch-ahead). 1 =
                                      # the synchronous drain-every-tick
                                      # loop; membership changes force a
                                      # drain barrier regardless
    prefix_caching: bool = False      # content-hash KV page reuse across
                                      # requests (cache/prefix.py): shared
                                      # prompt prefixes skip prefill entirely
    kv_quant: str = "none"            # "int8" stores the contiguous KV
                                      # cache as int8 codes + per-vector
                                      # scales: half the HBM bytes in the
                                      # bandwidth-bound decode loop
    kv_write_combine: bool = True     # serving-path write-combined KV
                                      # decode window: fused decode/spec
                                      # blocks stage fresh K/V in a small
                                      # per-slot window riding the scan
                                      # carry (the page pool is READ-ONLY
                                      # inside the block) and the window
                                      # flushes with ONE pool scatter per
                                      # drain instead of one per token —
                                      # the serving twin of decode_window
                                      # below. Greedy outputs are
                                      # byte-identical either way (the
                                      # window stores the pool's exact
                                      # representation); False = the
                                      # per-token write_paged_layer path.
                                      # Ignored (per-token writes) under
                                      # pipeline (stage>1) serving
    host_kv_tier_mb: float = 0.0      # host-RAM KV tier capacity in MB
                                      # (cache/hosttier.py): > 0 turns
                                      # prefix-cache eviction into
                                      # evict-to-host — recycled pages
                                      # park their bytes in host DRAM
                                      # keyed by chain digest and revive
                                      # on the next prefix hit instead
                                      # of re-prefilling. Requires
                                      # prefix_caching; 0 = off (drop
                                      # on evict, the pre-tier behavior)
    host_kv_tier_dir: Optional[str] = None
                                      # optional disk-spill directory
                                      # for the host tier: pages LRU'd
                                      # out of the RAM budget demote to
                                      # one .npz each instead of being
                                      # dropped, and promote back on
                                      # access. None = RAM only
    decode_window: int = 0            # fused-generate write combining:
                                      # decode this many tokens into a
                                      # small window, flush to the cache
                                      # in one write. 1 = per-step
                                      # writes; 0 = auto (16 with an
                                      # int8 cache — measured best on
                                      # v5e — else 1)
    speculative_gamma: int = 0        # serving-path speculative
                                      # decoding: draft this many
                                      # tokens per slot per round by
                                      # prompt lookup over the
                                      # device-side token history and
                                      # verify ALL slots in one batched
                                      # (gamma+1)-token forward, with
                                      # accept/rollback computed on
                                      # device inside the speculative
                                      # mixed block
                                      # (engine._mixed_spec_scan).
                                      # Sampling-safe: temperature /
                                      # top-k / top-p requests get the
                                      # exact rejection-sampling
                                      # correction. 0 = off
    speculative_ngram: int = 2        # lookup ngram for the drafts
    top_k: int = 0                    # serving-wide sampling filters
    top_p: float = 1.0
    port: int = 8000

    def replace(self, **kw) -> "RuntimeConfig":
        return dataclasses.replace(self, **kw)
