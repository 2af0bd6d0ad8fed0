"""Architecture registry: one ModelConfig dataclass covers every decoder-only
family the reference sweeps (reference: analysis/compare_base_vs_instruct.py:136-180,
analysis/compare_instruct_models.py:145-166) plus the T5 encoder-decoder branch
(routing rule "t5|t0|tk-instruct -> Seq2Seq", compare_instruct_models.py:471-475).

Instead of one torch class per HF repo (the reference relies on
``AutoModelForCausalLM`` + ``trust_remote_code``), we describe each family by a
small set of orthogonal architectural knobs and run them all through a single
functional JAX forward (models/decoder.py). trust_remote_code families (Qwen,
Baichuan) are re-implemented via these knobs, not remote code.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Unified decoder-only transformer description.

    Defaults are Llama-style; presets below override per family.
    """

    name: str = "unnamed"
    vocab_size: int = 32000
    hidden_size: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: Optional[int] = None      # None -> MHA (= n_heads); 1 -> MQA (falcon)
    head_dim: Optional[int] = None        # None -> hidden_size // n_heads
    intermediate_size: int = 11008
    max_seq_len: int = 2048

    # Position encoding
    pos_embedding: str = "rotary"         # "rotary" | "learned" | "alibi"
    rotary_pct: float = 1.0               # gpt-neox/pythia: 0.25
    rope_theta: float = 10000.0
    learned_pos_offset: int = 0           # OPT: positions start at 2

    # Normalization
    norm: str = "rmsnorm"                 # "rmsnorm" | "layernorm"
    norm_eps: float = 1e-5
    embedding_norm: bool = False          # bloom: LayerNorm right after embedding
    final_norm: bool = True

    # Block structure
    parallel_block: bool = False          # gpt-neox/falcon: h = x + attn(ln1 x) + mlp(ln2 x)
    shared_block_ln: bool = False         # falcon-7b: one LN feeds both attn and mlp

    # MLP
    activation: str = "silu"              # "silu" | "gelu" | "gelu_new" | "relu"
    gated_mlp: bool = True                # llama/mistral/qwen: silu(gate) * up

    # Biases
    qkv_bias: bool = False
    attn_out_bias: bool = False
    mlp_bias: bool = False

    # Output head
    tie_embeddings: bool = False
    logit_softcap: Optional[float] = None

    # Attention backend: route full-sequence self-attention through the
    # Pallas flash kernel (O(S*hd) memory) instead of the dense score
    # matrix. ALiBi (bloom) rides the kernel via per-head slopes; decode
    # steps and non-block-divisible sequences fall back dense.
    use_flash_attention: bool = False

    # Decode attention backend: route single-query KV-cached decode steps
    # through the fused Pallas flash-decode kernel (ops/flash_decode.py —
    # K-split online softmax + log-sum-exp combine, scores never leave
    # VMEM) instead of the dense score-row lowering. Default ON; engages
    # only where Pallas lowers (TPU; CPU keeps the dense path unless the
    # interpreter test hook is set) and only for the non-int8 cache (the
    # int8 cache has its own fused s8-dot path). RuntimeConfig.
    # fused_decode / --no-fused-decode opt out, restoring the dense
    # decode path exactly.
    fused_decode: bool = True

    # Cascade decode (ops/flash_decode.flash_decode_trunk): shared-trunk
    # dispatches read the trunk splits' K/V from the first batch block
    # for ALL blocks' queries (the trunk K/V tiles stream from HBM once
    # per step instead of once per batch block); tail splits read each
    # block's own rows, merged by ops/lse — the flat kernel with another
    # index map, so the flat kernel's result by construction. Static so the decode
    # executables specialize on it; mirrored from RuntimeConfig.
    # cascade_decode / --no-cascade-decode, which restores the flat
    # kernel exactly (the trunk extent is then pinned to 0).
    cascade_decode: bool = True

    # Fused cascade-prefill suffix leg (ops/cascade_prefill): trunk and
    # own keys under one softmax in ONE Pallas launch, no HBM
    # round-trip for partial (o, m, l) triples; the two-leg path to
    # float32 rounding; RuntimeConfig.cascade_fused_suffix /
    # --no-cascade-fused-suffix restores the two-leg lowering exactly.
    cascade_fused_suffix: bool = True

    # KV-cache storage: int8 with per-(head, position, row) scales halves
    # cache HBM (the single-chip long-context limiter: when SCALE.md was
    # measured a 7B's bf16 cache plus the second copy XLA kept of it
    # through the decode loop OOMed a v5e at seq 1024; since PR 27 the
    # decode and layer loops carry ONE buffer per side and update it
    # where it lies, tests/test_tpu_compile.py, so what is left of that
    # limit is the cache itself, not re-measured) and
    # halves decode-phase cache reads. Decode attention then runs s8 x s8
    # dots with dynamic query/probability quantization, mirroring the
    # dynamic int8 weight mode. Prefill attention is unaffected (it reads
    # the pre-quantization k/v). Opt-in; measured accuracy in tests.
    kv_cache_int8: bool = False

    # Mamba-2 mixer beside attention in every block (Falcon-H1):
    # ``ssm_heads`` > 0 adds, on the same normed input as attention, a
    # state-space mixer of ``ssm_heads`` heads of ``ssm_head_dim`` with a
    # per-head (head_dim, ssm_state) float32 state, ``ssm_groups`` B/C
    # groups, a depthwise causal conv of ``ssm_conv`` taps (with bias)
    # over the x|B|C channels and a gated group-RMSNorm before the output
    # projection (models/decoder._mixer; the scan is ops/ssd_scan at
    # ``ssm_chunk`` tokens a chunk). Such a model carries a SECOND kind
    # of per-sequence state beside K/V — the SSM state and the conv's
    # last ``ssm_conv - 1`` inputs — which no mask can rewind
    # (decoder.init_cache, decoder.rewind). 0 = no mixer: every model
    # before this one, bit for bit.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # muP multipliers (Falcon-H1 publishes them in config.json); a
    # multiplier of exactly 1.0 is not applied at all.
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: tuple = (1.0, 1.0)          # gate pre-activation, output
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)   # z, x, B, C, dt

    # Layers that differ in kind (MiniCPM-SALA): ``layer_kinds`` names
    # every layer's mixer in order, ``"sparse"`` (softmax attention with
    # InfLLM-v2 block selection past ``sparse_dense_len`` tokens of
    # context; the ``n_heads`` / ``n_kv_heads`` / ``head_dim`` above) or
    # ``"lightning"`` (scalar-decay linear attention, ``lightning_heads``
    # heads of ``lightning_head_dim``, a float32 (head_dim, head_dim)
    # state a head, decay ``exp(-2^(-8(h+1)/H))``). Each layer has ONE
    # mixer, then the MLP. ``params["layers"]`` is then a dict of groups,
    # one a kind, each stacked over its own layers; the layer loop runs
    # :attr:`layer_runs`; the cache holds K/V for the sparse layers only
    # and a state for the lightning layers only (models/mixed.py). Empty:
    # every layer alike, every model before this one bit for bit.
    layer_kinds: tuple = ()
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    lightning_chunk: int = 128
    qk_norm: bool = False             # per-head RMSNorm on q and k
    attn_rope: bool = True            # False: the softmax layers rotate nothing
    lightning_rope: bool = True
    output_gate: bool = False         # y = (o * sigmoid(h Wg)) Wo, both kinds
    residual_scale: float = 1.0       # x + residual_scale * branch(norm(x))
    # InfLLM-v2 selection (ops/sparse_attention.py): keys pooled over
    # ``sparse_kernel`` tokens at ``sparse_stride``, scored, max-pooled to
    # blocks of ``sparse_block``; a query past ``sparse_dense_len`` tokens
    # of context keeps ``sparse_init_blocks`` first blocks, the blocks
    # reaching into its last ``sparse_window`` positions and the
    # ``sparse_topk`` best of the rest.
    sparse_block: int = 64
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048
    sparse_dense_len: int = 8192

    # Layers that attend a window (K-EXAONE): ``layer_windowed`` marks, per
    # layer, the layers whose query at position p attends keys at
    # positions (p - sliding_window, p]; the others attend every earlier
    # key and rotate nothing (the family's global NoPE). Empty: no window
    # anywhere, every model before this one bit for bit.
    layer_windowed: tuple = ()
    sliding_window: int = 0
    # Norms on each branch's OUTPUT (``x + norm(attn)``, ``x + norm(mlp)``)
    # and none on its input: EXAONE 4's QK-Reorder-Norm.
    post_norm: bool = False
    # Routed experts (models/moe.py): after ``first_dense_layers`` layers
    # with the dense MLP, every layer routes each token to ``expert_top_k``
    # of ``num_experts`` gated SiLU experts of width ``expert_size`` by a
    # sigmoid router with a selection bias, the chosen scores normalized
    # and scaled by ``router_scale``, beside ``shared_experts`` shared
    # ones. The program holds the ``experts_held`` (first, count) slice of
    # the experts and computes only their terms. 0 experts: no expert
    # layer, every model before this one bit for bit.
    num_experts: int = 0
    experts_held: tuple = (0, 0)
    expert_top_k: int = 0
    expert_size: int = 0
    shared_experts: int = 0
    router_scale: float = 1.0
    first_dense_layers: int = 0
    # Latent attention (MLA: DeepSeek-V3, Kimi-K2): ``kv_lora_rank`` > 0
    # makes every layer's attention project the normed input down to a
    # query latent of ``q_lora_rank`` (RMSNorm) and a key-value latent of
    # ``kv_lora_rank`` (RMSNorm) beside ONE rotated key part of
    # ``qk_rope_dim`` that every head shares; each of the ``n_heads``
    # heads expands its query (``qk_nope_dim`` + ``qk_rope_dim``), key
    # (``qk_nope_dim``, then the shared rotated part) and value
    # (``v_head_dim``) from them. The cache holds the latent and the
    # rotated key part alone (models/mla.py). ``rope_scaling`` is YaRN's
    # ``(factor, original_max_position_embeddings, beta_fast, beta_slow,
    # mscale, mscale_all_dim)`` or empty (plain rotation). 0: no latent
    # attention, every model before this one bit for bit.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: tuple = ()

    def __post_init__(self) -> None:
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.hidden_size // self.n_heads)
        if self.n_kv_heads is None:
            object.__setattr__(self, "n_kv_heads", self.n_heads)
        assert self.pos_embedding in ("rotary", "learned", "alibi"), self.pos_embedding
        assert self.norm in ("rmsnorm", "layernorm"), self.norm
        assert self.activation in ("silu", "gelu", "gelu_new", "relu"), self.activation
        # shared_block_ln reuses the attention LN for the MLP, which only
        # exists in falcon-style PARALLEL blocks; a sequential block with
        # it set would KeyError('ln2') deep inside the first forward trace.
        assert not (self.shared_block_ln and not self.parallel_block), (
            "shared_block_ln=True requires parallel_block=True "
            f"({self.name})")
        object.__setattr__(self, "mlp_multipliers",
                           tuple(self.mlp_multipliers))
        object.__setattr__(self, "ssm_multipliers",
                           tuple(self.ssm_multipliers))
        object.__setattr__(self, "layer_kinds", tuple(self.layer_kinds))
        if self.layer_kinds:
            assert len(self.layer_kinds) == self.n_layers, self.name
            assert set(self.layer_kinds) <= {"sparse", "lightning"}, self.name
            if self.has_mixer or self.kv_cache_int8 or self.parallel_block:
                raise ValueError(
                    f"{self.name}: layers that differ in kind carry one "
                    "mixer a layer in a sequential block with a float cache")
        if self.has_mixer and self.kv_cache_int8:
            raise ValueError(
                f"{self.name}: kv_cache_int8 has no recurrent-state side; "
                "a model with a state-space mixer keeps a float cache")
        object.__setattr__(self, "layer_windowed",
                           tuple(bool(w) for w in self.layer_windowed))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        if self.layer_windowed:
            assert len(self.layer_windowed) == self.n_layers, self.name
            assert self.sliding_window > 0, self.name
        if self.num_experts or self.layer_windowed or self.post_norm:
            if (self.layer_kinds or self.has_mixer or self.parallel_block
                    or self.kv_cache_int8
                    or self.pos_embedding != "rotary"):
                raise ValueError(
                    f"{self.name}: windows, experts and branch-output norms "
                    "run in a sequential rotary block with a float cache")
        if self.num_experts:
            first, count = self.experts_held
            assert 0 <= first and 0 < count and first + count <= \
                self.num_experts, self.name
            assert 0 < self.expert_top_k <= self.num_experts, self.name
            assert 0 <= self.first_dense_layers < self.n_layers, self.name
        object.__setattr__(self, "rope_scaling", tuple(self.rope_scaling))
        if self.kv_lora_rank:
            if (self.layer_kinds or self.has_mixer or self.parallel_block
                    or self.kv_cache_int8 or self.layer_windowed
                    or self.post_norm or self.pos_embedding != "rotary"):
                raise ValueError(
                    f"{self.name}: latent attention runs in a sequential "
                    "pre-norm rotary block with a float latent cache")
            assert self.head_dim == self.qk_nope_dim + self.qk_rope_dim, \
                self.name
            assert self.rope_scaling == () or len(self.rope_scaling) == 6, \
                self.name

    @property
    def latent(self) -> bool:
        """Latent attention (models/mla.py)."""
        return self.kv_lora_rank > 0

    @property
    def trunk_apart(self) -> bool:
        """The cache holds a dispatch's shared trunk ONCE, apart from the
        rows' own slots, and is not laid out by row: layers that differ
        in kind (models/mixed.py) and latent attention (models/mla.py).
        A grouped batch cannot gather such a cache's rows, and one trunk
        length is one cache shape."""
        return bool(self.layer_kinds) or self.latent

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.rotary_pct)

    @property
    def has_mixer(self) -> bool:
        return self.ssm_heads > 0

    @property
    def carries_state(self) -> bool:
        """A recurrent state rides the cache beside (or instead of) K/V:
        what no mask can rewind (decoder.rewind, decoder.refuse_recurrent)."""
        return self.has_mixer or "lightning" in self.layer_kinds

    @property
    def expert_layers(self) -> int:
        """Layers that route to experts (0 without experts)."""
        return (self.n_layers - self.first_dense_layers
                if self.num_experts else 0)

    def kind_layers(self, kind: str) -> int:
        return sum(k == kind for k in self.layer_kinds)

    @property
    def layer_runs(self) -> tuple:
        """The published order as runs of one kind: ``(kind, first index
        within the kind's own stack, layers)``."""
        runs, seen = [], {}
        for kind in self.layer_kinds:
            n = seen.get(kind, 0)
            if runs and runs[-1][0] == kind:
                runs[-1] = (kind, runs[-1][1], runs[-1][2] + 1)
            else:
                runs.append((kind, n, 1))
            seen[kind] = n + 1
        return tuple(runs)

    @property
    def ssm_inner(self) -> int:
        """Width of the mixer's x (and z, and output) stream."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the depthwise conv runs over: x | B | C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_in_width(self) -> int:
        """Columns of the mixer's input projection: z | x | B | C | dt."""
        return self.ssm_inner + self.ssm_conv_dim + self.ssm_heads


@dataclasses.dataclass(frozen=True)
class T5Config:
    """Encoder-decoder (T5 v1.1 / flan-t5 / T0 / tk-instruct) description."""

    name: str = "t5"
    vocab_size: int = 32128
    hidden_size: int = 512                # d_model
    n_layers: int = 8                     # per stack
    n_heads: int = 6
    head_dim: int = 64                    # d_kv (NOT hidden/heads for t5 v1.1)
    intermediate_size: int = 1024         # d_ff
    norm_eps: float = 1e-6
    relative_attention_num_buckets: int = 32
    relative_attention_max_distance: int = 128
    gated_mlp: bool = True                # v1.1: gelu-gated; v1.0: relu non-gated
    activation: str = "gelu_new"
    tie_embeddings: bool = False          # v1.1: untied lm_head
    decoder_start_token_id: int = 0


# ---------------------------------------------------------------------------
# Family presets — shapes are the real HF configs for the reference model zoo.
# ---------------------------------------------------------------------------

def gpt2(size: str = "small") -> ModelConfig:
    dims = {"small": (768, 12, 12), "medium": (1024, 24, 16), "large": (1280, 36, 20),
            "xl": (1600, 48, 25)}[size]
    d, l, h = dims
    return ModelConfig(
        name=f"gpt2-{size}", vocab_size=50257, hidden_size=d, n_layers=l, n_heads=h,
        intermediate_size=4 * d, max_seq_len=1024, pos_embedding="learned",
        norm="layernorm", activation="gelu_new", gated_mlp=False,
        qkv_bias=True, attn_out_bias=True, mlp_bias=True, tie_embeddings=True,
    )


def gptneox(name: str = "pythia-6.9b", *, hidden: int = 4096, layers: int = 32,
            heads: int = 32, vocab: int = 50432, rotary_pct: float = 0.25,
            inter: Optional[int] = None, max_seq: int = 2048) -> ModelConfig:
    """Pythia / dolly-v2 / stablelm-alpha / RedPajama-INCITE / h2ogpt family."""
    return ModelConfig(
        name=name, vocab_size=vocab, hidden_size=hidden, n_layers=layers, n_heads=heads,
        intermediate_size=inter if inter is not None else 4 * hidden, max_seq_len=max_seq,
        pos_embedding="rotary", rotary_pct=rotary_pct, norm="layernorm",
        activation="gelu", gated_mlp=False, parallel_block=True,
        qkv_bias=True, attn_out_bias=True, mlp_bias=True,
    )


# 7B-class presets run DENSE prefill attention by default: measured on a
# v5e chip (SCALE.md "flash vs dense"), dense beats the Pallas flash
# kernel by ~8% at every batch/seq that fits a single chip (S<=512 —
# XLA's fused softmax never materializes the full (B, H, S, S) f32
# tensor), and past that the KV-cache while-loop layout copies OOM first
# either way. Flip use_flash_attention=True for long-S workloads on
# larger-HBM chips; ALiBi (bloom) is supported in-kernel.

def llama2_7b() -> ModelConfig:
    return ModelConfig(name="llama-2-7b", vocab_size=32000, hidden_size=4096,
                       n_layers=32, n_heads=32, intermediate_size=11008,
                       max_seq_len=4096, use_flash_attention=False)


def mistral_7b() -> ModelConfig:
    return ModelConfig(name="mistral-7b", vocab_size=32000, hidden_size=4096,
                       n_layers=32, n_heads=32, n_kv_heads=8, intermediate_size=14336,
                       max_seq_len=4096, use_flash_attention=False)


def qwen_7b() -> ModelConfig:
    # Qwen-7B (v1): llama-like but qkv bias and vocab 151936 (trust_remote_code
    # upstream; re-implemented here).
    return ModelConfig(name="qwen-7b", vocab_size=151936, hidden_size=4096,
                       n_layers=32, n_heads=32, intermediate_size=11008,
                       max_seq_len=2048, qkv_bias=True, norm_eps=1e-6,
                       use_flash_attention=False)


def pythia_69b() -> ModelConfig:
    """EleutherAI/pythia-6.9b at real size (gptneox: partial rotary 0.25,
    parallel block, LayerNorm) — the base half of the dolly-v2 pair
    (compare_base_vs_instruct.py:136-180)."""
    return gptneox(name="pythia-6.9b")


def h2ogpt_12b() -> ModelConfig:
    """h2oai/h2ogpt-oasst1-512-12b — the reference zoo's largest model
    (compare_instruct_models.py:145-166). Pythia-12b architecture:
    gptneox with hidden 5120 / 36 layers / 40 heads / vocab 50688."""
    return gptneox(name="h2ogpt-oasst1-512-12b", hidden=5120, layers=36,
                   heads=40, vocab=50688)


def baichuan2_7b() -> ModelConfig:
    return ModelConfig(name="baichuan2-7b", vocab_size=125696, hidden_size=4096,
                       n_layers=32, n_heads=32, intermediate_size=11008,
                       max_seq_len=4096, use_flash_attention=False)


def falcon_7b() -> ModelConfig:
    return ModelConfig(
        name="falcon-7b", vocab_size=65024, hidden_size=4544, n_layers=32,
        n_heads=71, n_kv_heads=1, intermediate_size=4 * 4544, max_seq_len=2048,
        pos_embedding="rotary", norm="layernorm", activation="gelu", gated_mlp=False,
        parallel_block=True, shared_block_ln=True, tie_embeddings=True,
        use_flash_attention=False,
    )


def falcon_h1_34b(n_layers: int = 72) -> ModelConfig:
    """tiiuae/Falcon-H1-34B-Instruct (config.json): in every block a
    Mamba-2 mixer (32 heads of 128, state 256, 2 groups, conv 4) beside
    GQA attention (20 query / 4 key-value heads of 128) on one RMSNorm,
    then a gated SiLU MLP of 21504; muP multipliers on every branch;
    untied 261120-row head. ``n_layers`` is the only size a cut may
    change (benchmarks/configs/falcon-h1-34b.json runs 8 of the 72)."""
    return ModelConfig(
        name="falcon-h1-34b", vocab_size=261120, hidden_size=5120,
        n_layers=n_layers, n_heads=20, n_kv_heads=4, head_dim=128,
        intermediate_size=21504, max_seq_len=262144, rope_theta=1e11,
        norm_eps=1e-5, use_flash_attention=False,
        ssm_heads=32, ssm_head_dim=128, ssm_state=256, ssm_groups=2,
        ssm_conv=4, ssm_chunk=128,
        embedding_multiplier=5.656854249492381,
        lm_head_multiplier=0.0078125,
        attention_in_multiplier=1.0,
        attention_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804,
        ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369,
                         0.5, 0.3535533905932738))


def falcon_h1_34b_l8() -> ModelConfig:
    """The published model cut in depth alone, to what one 16 GB chip
    holds with the whole vocabulary at batch 40 (PERF.md §4)."""
    return falcon_h1_34b(n_layers=8)


SALA_MIXERS = tuple(
    "sparse" if i in (0, 9, 16, 17, 22, 29, 30, 31) else "lightning"
    for i in range(32))


def minicpm_sala() -> ModelConfig:
    """openbmb/MiniCPM-SALA (config.json, ``model_type`` minicpm_sala):
    32 layers in the published ``mixer_types`` order, 8 of softmax
    attention (32 query / 2 key-value heads of 128, no rotation,
    InfLLM-v2 block selection past 8192 tokens) among 24 of lightning
    linear attention (32 heads of 128, rotated); per-head RMSNorm on q and
    k and a sigmoid output gate in both; gated SiLU MLP of 16384; the
    MiniCPM scales (embedding x 12, residual x 1.4 / sqrt(32), logits /
    (4096 / 256)); untied 73448-row head. The selection's sizes are
    MiniCPM4's ``sparse_config`` (the published file has no key:
    benchmarks/configs/minicpm-sala.json ``assumed``)."""
    return ModelConfig(
        name="minicpm-sala", vocab_size=73448, hidden_size=4096, n_layers=32,
        n_heads=32, n_kv_heads=2, head_dim=128, intermediate_size=16384,
        max_seq_len=524288, rope_theta=10000.0, norm_eps=1e-6,
        use_flash_attention=False, layer_kinds=SALA_MIXERS,
        lightning_heads=32, lightning_head_dim=128, lightning_chunk=128,
        qk_norm=True, attn_rope=False, lightning_rope=True, output_gate=True,
        residual_scale=1.4 / 32 ** 0.5, embedding_multiplier=12.0,
        lm_head_multiplier=256 / 4096,
        sparse_block=64, sparse_kernel=32, sparse_stride=16, sparse_topk=64,
        sparse_init_blocks=1, sparse_window=2048, sparse_dense_len=8192)


def k_exaone_236b(n_layers: int = 48, vocab_size: int = 153600,
                  experts_held: tuple = (0, 128)) -> ModelConfig:
    """LGAI-EXAONE/K-EXAONE-236B-A23B (config.json, ``model_type``
    exaone_moe): d 6144, GQA 64 query / 8 key-value heads of 128 with a
    per-head RMSNorm on q and k; layers in the pattern ``LLLG``, the
    ``L`` layers attending a 128-token window and rotated (RoPE theta
    1e6), the ``G`` layers attending every key unrotated; a norm on each
    branch's output and none on its input; layer 0 a gated SiLU MLP of
    18432, every later layer a sigmoid router over 128 experts of 2048
    (top 8 by score + selection bias, the chosen scores normalized and
    scaled by 2.5) beside one shared expert of 2048; untied head.
    ``n_layers``, ``vocab_size`` and ``experts_held`` are what a cut may
    change (benchmarks/configs/k-exaone-236b.json)."""
    return ModelConfig(
        name="k-exaone-236b", vocab_size=vocab_size, hidden_size=6144,
        n_layers=n_layers, n_heads=64, n_kv_heads=8, head_dim=128,
        intermediate_size=18432, max_seq_len=262144, rope_theta=1e6,
        norm_eps=1e-5, use_flash_attention=False, qk_norm=True,
        layer_windowed=tuple(i % 4 != 3 for i in range(n_layers)),
        sliding_window=128, post_norm=True,
        num_experts=128, experts_held=experts_held, expert_top_k=8,
        expert_size=2048, shared_experts=1, router_scale=2.5,
        first_dense_layers=1)


def k_exaone_236b_l12() -> ModelConfig:
    """One chip of the 32-chip deployment PERF.md §4 sets out: the first
    pipeline stage's 12 layers (three whole ``LLLG`` periods: the dense
    layer and 11 expert layers), experts 0-15 of every expert layer, and
    a 19,200-row slice of the embedding and the head."""
    return k_exaone_236b(n_layers=12, vocab_size=19200, experts_held=(0, 16))


def kimi_k2_6(n_layers: int = 61, vocab_size: int = 163840,
              experts_held: tuple = (0, 384)) -> ModelConfig:
    """moonshotai/Kimi-K2.6 (config.json, ``model_type`` kimi_k2, the
    DeepSeek-V3 layout; its MoonViT vision tower is not here): d 7168,
    64 heads of latent attention (query latent 1536, key-value latent
    512, 128 unrotated + 64 rotated query-key channels a head, values of
    128), YaRN rotation (factor 64 over 4096 positions, beta 32 / 1,
    theta 50000); layer 0 a gated SiLU MLP of 18432, every later layer a
    sigmoid router over 384 experts of 2048 (top 8 by score + selection
    bias, normalized, scaled by 2.827) beside one shared expert of 2048;
    untied head. ``n_layers``, ``vocab_size`` and ``experts_held`` are
    what a cut may change (benchmarks/configs/kimi-k2-6.json)."""
    return ModelConfig(
        name="kimi-k2-6", vocab_size=vocab_size, hidden_size=7168,
        n_layers=n_layers, n_heads=64, n_kv_heads=1, head_dim=192,
        intermediate_size=18432, max_seq_len=262144, rope_theta=50000.0,
        norm_eps=1e-5, use_flash_attention=False,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128,
        rope_scaling=(64.0, 4096, 32.0, 1.0, 1.0, 1.0),
        num_experts=384, experts_held=experts_held, expert_top_k=8,
        expert_size=2048, shared_experts=1, router_scale=2.827,
        first_dense_layers=1)


def kimi_k2_6_l13() -> ModelConfig:
    """One chip of the 160-chip deployment PERF.md §4 sets out: the
    first pipeline stage's 13 layers (the dense layer and 12 expert
    layers), experts 0-11 of every expert layer (32-way expert
    parallel) and a 20,480-row slice of the embedding and the head."""
    return kimi_k2_6(n_layers=13, vocab_size=20480, experts_held=(0, 12))


def bloom_7b1() -> ModelConfig:
    return ModelConfig(
        name="bloom-7b1", vocab_size=250880, hidden_size=4096, n_layers=30,
        n_heads=32, intermediate_size=4 * 4096, max_seq_len=2048,
        pos_embedding="alibi", norm="layernorm", activation="gelu_new", gated_mlp=False,
        embedding_norm=True, qkv_bias=True, attn_out_bias=True, mlp_bias=True,
        tie_embeddings=True, use_flash_attention=False,
    )


def opt(name: str = "opt-iml-1.3b") -> ModelConfig:
    return ModelConfig(
        name=name, vocab_size=50272, hidden_size=2048, n_layers=24, n_heads=32,
        intermediate_size=8192, max_seq_len=2048, pos_embedding="learned",
        learned_pos_offset=2, norm="layernorm", activation="relu", gated_mlp=False,
        qkv_bias=True, attn_out_bias=True, mlp_bias=True, tie_embeddings=True,
    )


def t5_v1_1(size: str = "base") -> T5Config:
    dims = {"small": (512, 8, 6, 1024), "base": (768, 12, 12, 2048),
            "large": (1024, 24, 16, 2816), "xl": (2048, 24, 32, 5120)}[size]
    d, l, h, ff = dims
    return T5Config(name=f"t5-v1_1-{size}", hidden_size=d, n_layers=l, n_heads=h,
                    intermediate_size=ff)


def flan_t5(size: str = "base") -> T5Config:
    cfg = t5_v1_1(size)
    return dataclasses.replace(cfg, name=f"flan-t5-{size}")


def t0_3b() -> T5Config:
    return T5Config(name="T0_3B", hidden_size=2048, n_layers=24, n_heads=32,
                    intermediate_size=5120)


# Tiny configs for tests (parity vs transformers CPU on random weights).
def tiny(family: str) -> ModelConfig:
    base = dict(vocab_size=256, hidden_size=64, n_layers=2, n_heads=4,
                intermediate_size=128, max_seq_len=128)
    if family == "gpt2":
        return ModelConfig(name="tiny-gpt2", pos_embedding="learned", norm="layernorm",
                           activation="gelu_new", gated_mlp=False, qkv_bias=True,
                           attn_out_bias=True, mlp_bias=True, tie_embeddings=True, **base)
    if family == "gptneox":
        return ModelConfig(name="tiny-gptneox", pos_embedding="rotary", rotary_pct=0.25,
                           norm="layernorm", activation="gelu", gated_mlp=False,
                           parallel_block=True, qkv_bias=True, attn_out_bias=True,
                           mlp_bias=True, **base)
    if family == "llama":
        return ModelConfig(name="tiny-llama", **base)
    if family == "mistral":
        return ModelConfig(name="tiny-mistral", n_kv_heads=2, **base)
    if family == "falcon":
        return ModelConfig(name="tiny-falcon", pos_embedding="rotary", norm="layernorm",
                           activation="gelu", gated_mlp=False, parallel_block=True,
                           shared_block_ln=True, n_kv_heads=1, tie_embeddings=True, **base)
    if family == "falcon-h1":
        return ModelConfig(
            name="tiny-falcon-h1", n_kv_heads=2, ssm_heads=4,
            ssm_head_dim=16, ssm_state=16, ssm_groups=2, ssm_conv=4,
            ssm_chunk=16, embedding_multiplier=2.0,
            lm_head_multiplier=0.5, attention_out_multiplier=0.75,
            key_multiplier=0.5, ssm_in_multiplier=0.5,
            ssm_out_multiplier=0.75, mlp_multipliers=(0.5, 0.75),
            ssm_multipliers=(0.5, 0.75, 1.25, 1.5, 0.5), **base)
    if family == "sala":
        # One layer of each kind twice over, a selection small enough to
        # be live on rows of ~100 tokens.
        return ModelConfig(
            name="tiny-sala", n_kv_heads=2, head_dim=16,
            layer_kinds=("sparse", "lightning", "lightning", "sparse"),
            lightning_heads=4, lightning_head_dim=16, lightning_chunk=16,
            qk_norm=True, attn_rope=False, output_gate=True,
            residual_scale=0.7, embedding_multiplier=2.0,
            lm_head_multiplier=0.5, sparse_block=8, sparse_kernel=4,
            sparse_stride=2, sparse_topk=2, sparse_init_blocks=1,
            sparse_window=32, sparse_dense_len=32,
            **{**base, "n_layers": 4, "max_seq_len": 256})
    if family == "exaone-moe":
        # Two LLLG periods; a window shorter than the tests' trunks; 16
        # experts of which the program holds 4.
        return ModelConfig(
            name="tiny-exaone-moe", n_kv_heads=2, head_dim=16,
            qk_norm=True, post_norm=True, rope_theta=1e6,
            layer_windowed=tuple(i % 4 != 3 for i in range(8)),
            sliding_window=8, num_experts=16,
            experts_held=(4, 4), expert_top_k=4, expert_size=32,
            shared_experts=1, router_scale=2.5, first_dense_layers=1,
            **{**base, "n_layers": 8})
    if family == "bloom":
        return ModelConfig(name="tiny-bloom", pos_embedding="alibi", norm="layernorm",
                           activation="gelu_new", gated_mlp=False, embedding_norm=True,
                           qkv_bias=True, attn_out_bias=True, mlp_bias=True,
                           tie_embeddings=True, **base)
    if family == "opt":
        return ModelConfig(name="tiny-opt", pos_embedding="learned", learned_pos_offset=2,
                           norm="layernorm", activation="relu", gated_mlp=False,
                           qkv_bias=True, attn_out_bias=True, mlp_bias=True,
                           tie_embeddings=True, **base)
    raise KeyError(family)


REGISTRY = {
    "gpt2": gpt2, "gptneox": gptneox, "llama2-7b": llama2_7b,
    "mistral-7b": mistral_7b, "qwen-7b": qwen_7b, "baichuan2-7b": baichuan2_7b,
    "falcon-7b": falcon_7b, "falcon-h1-34b": falcon_h1_34b,
    "falcon-h1-34b-l8": falcon_h1_34b_l8, "minicpm-sala": minicpm_sala,
    "k-exaone-236b": k_exaone_236b, "k-exaone-236b-l12": k_exaone_236b_l12,
    "kimi-k2-6": kimi_k2_6, "kimi-k2-6-l13": kimi_k2_6_l13,
    "bloom-7b1": bloom_7b1, "opt": opt,
    "t5-v1_1": t5_v1_1, "flan-t5": flan_t5, "t0-3b": t0_3b,
}
