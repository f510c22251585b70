"""Config dataclasses for the repro framework.

Every assigned architecture is described by a :class:`ModelConfig`; input-shape
cells by :class:`ShapeConfig`.  Configs are plain frozen dataclasses so they are
hashable (usable as jit static args) and trivially serializable for checkpoint
manifests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Layer-stack patterns
# ---------------------------------------------------------------------------
# A homogeneous decoder stack is described by ``pattern=("attn",)``.
# Hybrid stacks repeat a group, e.g. gemma3 = ("local","local","local","local",
# "local","global") and recurrentgemma = ("rec","rec","attn").  The stack is
# ``pattern * (n_layers // len(pattern))`` followed by
# ``pattern[:n_layers % len(pattern)]``.

BLOCK_ATTN = "attn"          # full causal attention
BLOCK_LOCAL = "local"        # sliding-window attention
BLOCK_GLOBAL = "global"      # full attention inside a hybrid stack
BLOCK_REC = "rec"            # RG-LRU recurrent block (Griffin)
BLOCK_RWKV = "rwkv"          # RWKV6 time-mix block


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description.  One instance per assigned architecture."""

    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm | rnnt
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    ffn_type: str = "swiglu"         # swiglu | geglu | gelu | sq_relu
    pattern: Tuple[str, ...] = (BLOCK_ATTN,)
    window: int = 0                  # sliding-window size for local blocks (0 = none)
    rope_theta: float = 10000.0
    qk_norm: bool = False
    tie_embeddings: bool = False
    embed_scale: bool = False        # gemma-style sqrt(d_model) embedding scaling
    norm_eps: float = 1e-6
    moe: Optional[MoEConfig] = None
    # --- hybrid (RG-LRU) extras ---
    lru_width: int = 0
    conv_width: int = 4
    # --- rwkv extras ---
    rwkv_head_dim: int = 64
    # --- encoder-decoder extras ---
    n_enc_layers: int = 0
    # --- modality frontend stubs (audio / vlm) ---
    frontend: str = "none"           # none | audio_frames | image_patches
    n_prefix: int = 0                # number of frontend positions (e.g. patches)
    # --- rnnt extras (paper's own arch) ---
    rnnt: Optional["RNNTConfig"] = None
    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    # ------------------------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def layer_kinds(self) -> Tuple[str, ...]:
        reps = self.n_layers // len(self.pattern)
        rem = self.n_layers % len(self.pattern)
        return tuple(self.pattern) * reps + tuple(self.pattern[:rem])

    def is_subquadratic(self) -> bool:
        """True when the arch can serve 500k-token contexts without an
        unbounded full-attention KV cache in every layer (see DESIGN.md §4)."""
        kinds = set(self.layer_kinds())
        if kinds <= {BLOCK_REC, BLOCK_RWKV, BLOCK_LOCAL}:
            return True
        # hybrid local:global (gemma3): bounded local caches + few seq-sharded
        # global layers -> runnable
        if BLOCK_GLOBAL in kinds and BLOCK_LOCAL in kinds:
            return True
        if kinds & {BLOCK_REC, BLOCK_RWKV}:
            return True
        return False

    def n_params(self) -> int:
        """Analytic parameter count (embedding + stack + head)."""
        if self.rnnt is not None:
            return self.rnnt.n_params()
        d, ff, V = self.d_model, self.d_ff, self.vocab_size
        embed = V * d * (1 if self.tie_embeddings else 2)
        n = embed
        for kind in self.layer_kinds():
            if kind in (BLOCK_ATTN, BLOCK_LOCAL, BLOCK_GLOBAL):
                attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
                n += attn
            elif kind == BLOCK_REC:
                w = self.lru_width or d
                # rg-lru block: in/out proj + conv + gates
                n += 2 * d * w + self.conv_width * w + 3 * w
            elif kind == BLOCK_RWKV:
                # r,k,v,g,o projections + decay lora + token-shift mus
                n += 5 * d * d + 2 * d * 96 + 6 * d
            # ffn (moe or dense) attaches to attn/local/global/rwkv blocks;
            # rec blocks in griffin also carry an MLP
            if self.moe is not None and kind != BLOCK_REC:
                e = self.moe
                n += e.n_experts * 3 * d * e.d_ff_expert + d * e.n_experts
            else:
                mult = 3 if self.ffn_type in ("swiglu", "geglu") else 2
                n += mult * d * ff
        if self.n_enc_layers:
            for _ in range(self.n_enc_layers):
                attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
                mult = 3 if self.ffn_type in ("swiglu", "geglu") else 2
                n += attn + mult * d * ff
                # cross attention in decoder accounted approximately here
                n += attn
        return n

    def n_active_params(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if self.moe is None:
            return self.n_params()
        e = self.moe
        total = self.n_params()
        expert_params = (
            len(self.layer_kinds()) * e.n_experts * 3 * self.d_model * e.d_ff_expert
        )
        active = (
            len(self.layer_kinds()) * e.top_k * 3 * self.d_model * e.d_ff_expert
        )
        return total - expert_params + active


@dataclass(frozen=True)
class RNNTConfig:
    """RNN-Transducer architectures: the paper's CRDNN and the Conformer.

    ``encoder="crdnn"`` (the defaults): SpeechBrain's Librispeech
    transducer recipe — 2 CNN blocks -> 4 bi-LSTM layers -> 2 DNN layers,
    prediction net embedding + 1-layer GRU, joint a single linear
    projecting the 1024-d fused representation to 1000 BPE units.
    ``encoder="conformer"`` (``models/conformer.py``): convolutional
    subsampling by 4 to ``conformer_dim``, then ``conformer_blocks``
    Conformer blocks; its batch norms keep running statistics as
    non-trained state.  ``predictor``
    is ``"gru"`` or ``"lstm"``.  The fields are flat (no nested groups)
    so that a configuration file's keys map onto them one to one.
    """

    n_feats: int = 80
    cnn_channels: Tuple[int, int] = (64, 128)
    lstm_layers: int = 4
    lstm_hidden: int = 512           # per direction
    dnn_dim: int = 1024
    pred_embed: int = 256
    pred_hidden: int = 512
    joint_dim: int = 1024
    vocab_size: int = 1000           # BPE units + blank
    time_reduction: int = 4          # cnn striding
    # transducer-loss path (DESIGN.md §2): "fused" = custom_vjp
    # alpha/beta lattice with a vocab-streamed joint (never materializes
    # the (B,T,U+1,V) tensor); "dense" = the autodiff parity oracle
    loss_impl: str = "fused"
    # vocab-chunk size for the fused loss's streamed logsumexp/backward
    # (<= 0: one chunk of the full vocab — right for smoke vocabs; set
    # to e.g. 512 when V is large enough that a (B,U+1,V) row dominates)
    loss_vocab_chunk: int = 0
    encoder: str = "crdnn"           # crdnn | conformer
    predictor: str = "gru"           # gru | lstm
    # --- conformer encoder (encoder="conformer") ---
    conformer_blocks: int = 17
    conformer_dim: int = 512
    conformer_heads: int = 8
    conformer_ffn_dim: int = 2048
    conformer_kernel: int = 32       # depthwise conv kernel
    subsample_channels: int = 512

    @property
    def enc_dim(self) -> int:
        """Width of the encoder's output frames."""
        return self.conformer_dim if self.encoder == "conformer" \
            else self.dnn_dim

    @property
    def pred_state(self) -> int:
        """Width of the prediction network's recurrent state: the GRU's
        hidden state, or the LSTM's hidden and cell states."""
        return (2 if self.predictor == "lstm" else 1) * self.pred_hidden

    def n_params(self) -> int:
        """Trained parameters (batch norm's running statistics are not)."""
        if self.encoder == "conformer":
            d, f, c = self.conformer_dim, self.conformer_ffn_dim, \
                self.subsample_channels
            n = 9 * c + c + 9 * c * c + c + c * (self.n_feats // 4) * d + d
            ffn = 2 * d + d * f + f + f * d + d
            mhsa = 2 * d + 4 * (d * d + d) + d * d + 2 * d
            conv = (2 * d + d * 2 * d + 2 * d + self.conformer_kernel * d
                    + d + 2 * d + d * d + d)
            n += self.conformer_blocks * (2 * ffn + mhsa + conv + 2 * d)
        else:
            n = 0
            c_in = 1
            for c in self.cnn_channels:
                n += c_in * c * 9 + c
                c_in = c
            feat = self.cnn_channels[-1] * (self.n_feats // 4)
            d_in = feat
            for _ in range(self.lstm_layers):
                n += 2 * 4 * (d_in * self.lstm_hidden
                              + self.lstm_hidden ** 2 + self.lstm_hidden)
                d_in = 2 * self.lstm_hidden
            n += d_in * self.dnn_dim + self.dnn_dim * self.dnn_dim
        n += self.vocab_size * self.pred_embed
        if self.predictor == "lstm":
            n += 4 * (self.pred_embed * self.pred_hidden
                      + self.pred_hidden ** 2 + self.pred_hidden)
        else:
            n += 3 * (self.pred_embed * self.pred_hidden
                      + self.pred_hidden ** 2)
        n += (self.enc_dim + self.pred_hidden) * self.joint_dim
        n += self.joint_dim * self.vocab_size
        return n


# ---------------------------------------------------------------------------
# Shapes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


@dataclass(frozen=True)
class PGMConfig:
    """Paper hyper-parameters (§5): selection interval R, partitions D,
    warm-start epochs, subset fraction, OMP regularization/tolerance."""

    subset_fraction: float = 0.3
    n_partitions: int = 8            # D; paper: 7 (100H) / 50 (960H)
    select_every: int = 5            # R
    warm_start_epochs: int = 2
    val_matching: bool = False       # 'Val' flag (noisy/robust mode)
    lam: float = 0.5                 # l2 reg on weights (lambda)
    eps: float = 1e-10               # OMP stopping tolerance
    sketch_dim_h: int = 64           # tensor-JL sketch dims (beyond-paper)
    sketch_dim_v: int = 64
    use_sketch: bool = True          # False -> paper-faithful exact gradients
    nonneg_weights: bool = True      # clip OMP weights at 0 (GradMatch impl.)
    # sparse-expert (MoE) selection gradients (DESIGN.md §8): append the
    # per-unit router-weight gradient (task + load-balance aux) to the
    # last-layer head representation.  Opt-in — it costs one autodiff
    # backward per unit vs the closed-form head path; default False is
    # the paper-faithful last-layer-only definition.  Ignored for
    # non-MoE families.
    moe_router_term: bool = False
    # selection-round kernel backend (kernels/backend.py): "auto" uses
    # the fused Pallas grad-sketch + Gram kernels on TPU and the XLA
    # streamed paths elsewhere; "pallas"/"xla" force one side ("pallas"
    # off-TPU runs the interpreter — parity/debug only, it is slow).
    kernel_impl: str = "auto"
    # a failing resident selection round (DESIGN.md §10): "raise" fails
    # fast; "soft_random" opts into the Pallas -> XLA -> soft-random
    # degradation ladder
    on_failure: str = "raise"


@dataclass(frozen=True)
class TrainConfig:
    batch_size: int = 8              # global batch for SGD
    lr: float = 1.0
    optimizer: str = "sgd"           # sgd | adamw
    momentum: float = 0.0
    weight_decay: float = 0.0
    grad_clip: float = 5.0
    epochs: int = 30
    # newbob (paper's scheduler): anneal lr by `anneal_factor` when relative
    # validation-loss improvement < `improvement_threshold`
    anneal_factor: float = 0.8
    improvement_threshold: float = 0.0025
    seed: int = 0
    # cross-pod gradient compression (DESIGN.md §5): when the training
    # mesh carries a `pod_axis` axis, the scanned engine computes per-pod
    # gradients and runs an explicit `train/compress.py:compressed_psum`
    # over it inside the epoch scan — "none" keeps that collective dense
    # fp32, "bf16" halves its wire width, "topk" sends the k largest
    # entries per leaf with error feedback carried in the scan state
    compress_mode: str = "none"      # none | bf16 | topk
    compress_k_frac: float = 0.05    # top-k fraction per gradient leaf
    pod_axis: str = "pod"            # mesh axis name of the slow pod axis
    # fault tolerance (DESIGN.md §10): with `nonfinite_guard` the step
    # checks loss/grads for NaN/Inf *inside* the jitted epoch scan and
    # gates a non-finite step into a bit-exact no-op (optim.gate_step,
    # the same select that implements weight-0 padding rows) — no host
    # sync, no retrace; skipped-step counts ride the donated carry.
    # `max_skipped_steps` arms the host-side divergence watchdog: K
    # consecutive skipped steps (or a non-finite train/val loss) roll
    # the run back to the newest intact checkpoint with re-keyed batch
    # plans.  0 disables the consecutive-skip trigger.
    nonfinite_guard: bool = False
    max_skipped_steps: int = 0
    pgm: PGMConfig = field(default_factory=PGMConfig)


def reduce_for_smoke(cfg: ModelConfig) -> ModelConfig:
    """Tiny same-family variant used by CPU smoke tests: few layers, small
    widths/vocab/experts so one forward+train step runs in seconds."""
    kw = dict(
        n_layers=min(cfg.n_layers, 2 * max(1, len(cfg.pattern))),
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 4) if cfg.n_kv_heads > 1 else 1,
        head_dim=16,
        d_ff=128,
        vocab_size=277,
        window=min(cfg.window, 16) if cfg.window else 0,
        lru_width=64 if cfg.lru_width else 0,
        n_enc_layers=2 if cfg.n_enc_layers else 0,
        n_prefix=8 if cfg.n_prefix else 0,
        compute_dtype="float32",
    )
    if cfg.moe is not None:
        kw["moe"] = MoEConfig(
            n_experts=4, top_k=min(cfg.moe.top_k, 2), d_ff_expert=32
        )
    if cfg.rnnt is not None and cfg.rnnt.encoder == "conformer":
        kw["rnnt"] = dataclasses.replace(
            cfg.rnnt, n_feats=8, conformer_blocks=2, conformer_dim=32,
            conformer_heads=4, conformer_ffn_dim=64, conformer_kernel=4,
            subsample_channels=8, pred_embed=16, pred_hidden=16,
            joint_dim=32, vocab_size=37)
    elif cfg.rnnt is not None:
        kw["rnnt"] = RNNTConfig(
            n_feats=8, cnn_channels=(4, 8), lstm_layers=1, lstm_hidden=16,
            dnn_dim=32, pred_embed=16, pred_hidden=16, joint_dim=32,
            vocab_size=37,
        )
    return dataclasses.replace(cfg, name=cfg.name + "-smoke", **kw)
