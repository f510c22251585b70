"""Unified model API: every architecture exposes the same bundle of pure
functions (init / loss / per-example loss / PGM last-layer hooks / prefill /
decode / input specs).  This is the surface the trainer, server, PGM core,
and the multi-pod dry-run all build on.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.rnnt_loss import rnnt_loss_from_logits, rnnt_loss_fused
from repro.models import encdec as encdec_mod
from repro.models import rnnt as rnnt_mod
from repro.models import transformer as tfm
from repro.models.attention import prefix_lm_mask
from repro.models.common import IDENTITY_SHARDER, STATE_KEY, Sharder

Batch = Dict[str, jax.Array]


def softmax_xent(logits, targets, mask):
    """Per-example mean cross-entropy.  logits (B,S,V); targets (B,S);
    mask (B,S).  Computed in fp32.  The gold logit is extracted with a
    one-hot contraction (not take_along_axis) so a vocab-sharded logits
    tensor reduces to partial sums + a tiny all-reduce instead of a full
    logits all-gather (DESIGN.md §5)."""
    lv = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lv, axis=-1)
    onehot = jax.nn.one_hot(targets, lv.shape[-1], dtype=lv.dtype)
    gold = jnp.einsum("bsv,bsv->bs", lv, onehot)
    nll = (logz - gold) * mask
    denom = jnp.maximum(mask.sum(axis=-1), 1.0)
    return nll.sum(axis=-1) / denom                     # (B,)


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ModelConfig
    init_params: Callable[..., Any]
    per_example_loss: Callable[..., jax.Array]          # (params, batch) -> (B,)
    loss_fn: Callable[..., Tuple[jax.Array, Dict]]      # weighted scalar + metrics
    final_hidden: Callable[..., Tuple]                  # PGM last-layer hook
    head_weight: Callable[[Any], jax.Array]             # (d, V) last-layer W
    prefill: Callable[..., Tuple[jax.Array, Any]]
    decode: Callable[..., Tuple[jax.Array, Any]]
    init_cache: Callable[..., Any]
    input_specs: Callable[[ShapeConfig], Dict[str, jax.ShapeDtypeStruct]]
    make_batch: Callable[..., Batch]


def _weights_of(batch: Batch, B: int):
    w = batch.get("weights")
    return jnp.ones((B,), jnp.float32) if w is None else w.astype(jnp.float32)


def _weighted(per_ex: jax.Array, batch: Batch, aux) -> Tuple[jax.Array, Dict]:
    w = _weights_of(batch, per_ex.shape[0])
    loss = jnp.sum(per_ex * w) / jnp.maximum(jnp.sum(w), 1e-9)
    total = loss + aux
    return total, {"loss": loss, "aux_loss": aux, "total_loss": total}


# ===========================================================================
# Decoder-only LMs (dense / moe / ssm / hybrid) and VLM
# ===========================================================================

def _build_lm(cfg: ModelConfig) -> ModelBundle:
    is_vlm = cfg.family == "vlm"
    P = cfg.n_prefix if is_vlm else 0
    mask_fn = prefix_lm_mask(P) if is_vlm else None

    def assemble(params, batch):
        """-> (x_embedded (B,S,d), targets, loss_mask, text_offset)."""
        tokens = batch["tokens"]
        x = tfm.embed_tokens(params, cfg, tokens)
        if is_vlm:
            patches = batch["patches"].astype(x.dtype)
            x = jnp.concatenate([patches, x], axis=1)
        # position i predicts token i+1 of the text stream
        targets = tokens[:, 1:]
        mask = batch.get("loss_mask")
        mask = (jnp.ones_like(targets, jnp.float32) if mask is None
                else mask[:, 1:].astype(jnp.float32))
        return x, targets, mask

    def hidden(params, batch, shard=IDENTITY_SHARDER, remat=True):
        x, targets, mask = assemble(params, batch)
        h, aux, _ = tfm.forward_hidden(params, cfg, x, mask_fn=mask_fn,
                                       shard=shard, remat=remat)
        # text hidden states aligned with next-token targets
        S_txt = batch["tokens"].shape[1]
        h_txt = h[:, P : P + S_txt - 1] if is_vlm else h[:, :-1]
        return h_txt, targets, mask, aux

    def per_example_loss(params, batch, shard=IDENTITY_SHARDER, remat=True):
        h, targets, mask, aux = hidden(params, batch, shard, remat)
        logits = tfm.unembed(params, cfg, h)
        return softmax_xent(logits, targets, mask)

    def loss_fn(params, batch, shard=IDENTITY_SHARDER, remat=True):
        h, targets, mask, aux = hidden(params, batch, shard, remat)
        logits = tfm.unembed(params, cfg, h)
        per_ex = softmax_xent(logits, targets, mask)
        return _weighted(per_ex, batch, aux)

    def head_weight(params):
        return (params["embed"]["w"].T if cfg.tie_embeddings
                else params["lm_head"]["w"])

    def prefill(params, batch, shard=IDENTITY_SHARDER, cache_len=None,
                prompt_lens=None):
        """Prefill the decode cache.  With ``prompt_lens`` (B,) the prompt
        is treated as right-padded to its bucket length: pad positions get
        position id -1, which every attention mask rule treats as invalid
        (``attention._valid``), so the returned last-token logits and the
        cache contents are bit-identical to an unpadded prefill of the
        live prefix — the retrace-free bucketed-prompt contract of the
        serving engine (DESIGN.md §4)."""
        x, _, _ = assemble(params, batch)
        S_total = x.shape[1]
        cache_len = cache_len or S_total
        if prompt_lens is None:
            h, _, cache = tfm.forward_hidden(
                params, cfg, x, mask_fn=mask_fn, shard=shard, remat=False,
                collect_cache=True, cache_len=cache_len)
            logits = tfm.unembed(params, cfg, h[:, -1:])
            return logits[:, 0], cache
        if is_vlm:
            raise NotImplementedError(
                "bucketed (prompt_lens) prefill is text-LM only; VLM "
                "prompts carry a fixed patch prefix")
        B = x.shape[0]
        pos = jnp.broadcast_to(jnp.arange(S_total), (B, S_total))
        pos = jnp.where(pos < prompt_lens[:, None], pos, -1)
        h, _, cache = tfm.forward_hidden(
            params, cfg, x, positions=pos, mask_fn=mask_fn, shard=shard,
            remat=False, collect_cache=True, cache_len=cache_len)
        last = jnp.clip(prompt_lens - 1, 0, S_total - 1)
        h_last = jnp.take_along_axis(
            h, last[:, None, None].astype(jnp.int32), axis=1)
        logits = tfm.unembed(params, cfg, h_last)
        return logits[:, 0], cache

    def decode(params, cache, tokens, shard=IDENTITY_SHARDER):
        """tokens: (B,) next input token ids."""
        x_t = tfm.embed_tokens(params, cfg, tokens[:, None])
        h, cache = tfm.decode_step(params, cfg, x_t, cache, shard=shard,
                                   mask_fn=mask_fn)
        logits = tfm.unembed(params, cfg, h)
        return logits[:, 0], cache

    def init_cache(batch_size: int, cache_len: int, dtype=None):
        return tfm.init_cache(cfg, batch_size, cache_len, dtype=dtype)

    def input_specs(shape: ShapeConfig):
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "train":
            specs = {
                "tokens": jax.ShapeDtypeStruct((B, S - P), i32),
                "loss_mask": jax.ShapeDtypeStruct((B, S - P), jnp.float32),
                "weights": jax.ShapeDtypeStruct((B,), jnp.float32),
            }
        elif shape.kind == "prefill":
            specs = {"tokens": jax.ShapeDtypeStruct((B, S - P), i32)}
        else:  # decode
            specs = {"tokens": jax.ShapeDtypeStruct((B,), i32)}
        if is_vlm and shape.kind != "decode":
            specs["patches"] = jax.ShapeDtypeStruct(
                (B, P, cfg.d_model), jnp.float32)
        return specs

    def make_batch(key, B: int, S: int) -> Batch:
        ks = jax.random.split(key, 3)
        batch = {
            "tokens": jax.random.randint(ks[0], (B, S - P), 0, cfg.vocab_size),
            "loss_mask": jnp.ones((B, S - P), jnp.float32),
            "weights": jnp.ones((B,), jnp.float32),
        }
        if is_vlm:
            batch["patches"] = jax.random.normal(
                ks[1], (B, P, cfg.d_model), jnp.float32)
        return batch

    return ModelBundle(
        cfg=cfg,
        init_params=lambda key: tfm.init_params(cfg, key),
        per_example_loss=per_example_loss,
        loss_fn=loss_fn,
        final_hidden=hidden,
        head_weight=head_weight,
        prefill=prefill,
        decode=decode,
        init_cache=init_cache,
        input_specs=input_specs,
        make_batch=make_batch,
    )


# ===========================================================================
# Encoder-decoder (seamless-m4t)
# ===========================================================================

def _build_encdec(cfg: ModelConfig) -> ModelBundle:

    def hidden(params, batch, shard=IDENTITY_SHARDER, remat=True):
        enc = encdec_mod.encode(params, cfg, batch["frames"], shard=shard,
                                remat=remat)
        dec_in = batch["tokens"][:, :-1]
        targets = batch["tokens"][:, 1:]
        mask = batch.get("loss_mask")
        mask = (jnp.ones_like(targets, jnp.float32) if mask is None
                else mask[:, 1:].astype(jnp.float32))
        h, _ = encdec_mod.decode_train(params, cfg, dec_in, enc, shard=shard,
                                       remat=remat)
        return h, targets, mask, jnp.zeros((), jnp.float32)

    def per_example_loss(params, batch, shard=IDENTITY_SHARDER, remat=True):
        h, targets, mask, _ = hidden(params, batch, shard, remat)
        logits = tfm.unembed(params, cfg, h)
        return softmax_xent(logits, targets, mask)

    def loss_fn(params, batch, shard=IDENTITY_SHARDER, remat=True):
        per_ex = per_example_loss(params, batch, shard, remat)
        return _weighted(per_ex, batch, jnp.zeros((), jnp.float32))

    def head_weight(params):
        return (params["embed"]["w"].T if cfg.tie_embeddings
                else params["lm_head"]["w"])

    def prefill(params, batch, shard=IDENTITY_SHARDER, cache_len=None):
        enc = encdec_mod.encode(params, cfg, batch["frames"], shard=shard,
                                remat=False)
        dec_in = batch["tokens"]
        cache_len = cache_len or dec_in.shape[1]
        h, cache = encdec_mod.decode_train(
            params, cfg, dec_in, enc, shard=shard, remat=False,
            collect_cache=True, cache_len=cache_len)
        logits = tfm.unembed(params, cfg, h[:, -1:])
        return logits[:, 0], cache

    def decode(params, cache, tokens, shard=IDENTITY_SHARDER):
        x_t = tfm.embed_tokens(params, cfg, tokens[:, None])
        h, cache = encdec_mod.decode_step(params, cfg, x_t, cache, shard=shard)
        logits = tfm.unembed(params, cfg, h)
        return logits[:, 0], cache

    def init_cache(batch_size: int, cache_len: int, dtype=None,
                   src_len: Optional[int] = None):
        dtype = jnp.dtype(cfg.compute_dtype) if dtype is None else dtype
        from repro.models.attention import init_kv_cache
        L = cfg.n_layers
        src_len = src_len or cache_len
        one = init_kv_cache(cfg, batch_size, cache_len, window=False,
                            dtype=dtype)
        stack = lambda t: jax.tree.map(
            lambda l: jnp.broadcast_to(l, (L,) + l.shape).copy(), t)
        return {
            "self": stack(one),
            "ck": jnp.zeros((L, batch_size, src_len, cfg.n_kv_heads,
                             cfg.head_dim), dtype),
            "cv": jnp.zeros((L, batch_size, src_len, cfg.n_kv_heads,
                             cfg.head_dim), dtype),
        }

    def input_specs(shape: ShapeConfig):
        B, S = shape.global_batch, shape.seq_len
        T_src, U = S // 2, S // 2
        i32 = jnp.int32
        if shape.kind == "train":
            return {
                "frames": jax.ShapeDtypeStruct((B, T_src, cfg.d_model),
                                               jnp.float32),
                "tokens": jax.ShapeDtypeStruct((B, U), i32),
                "loss_mask": jax.ShapeDtypeStruct((B, U), jnp.float32),
                "weights": jax.ShapeDtypeStruct((B,), jnp.float32),
            }
        if shape.kind == "prefill":
            return {
                "frames": jax.ShapeDtypeStruct((B, T_src, cfg.d_model),
                                               jnp.float32),
                "tokens": jax.ShapeDtypeStruct((B, U), i32),
            }
        return {"tokens": jax.ShapeDtypeStruct((B,), i32)}

    def make_batch(key, B: int, S: int) -> Batch:
        ks = jax.random.split(key, 2)
        T_src, U = max(S // 2, 4), max(S // 2, 4)
        return {
            "frames": jax.random.normal(ks[0], (B, T_src, cfg.d_model)),
            "tokens": jax.random.randint(ks[1], (B, U), 0, cfg.vocab_size),
            "loss_mask": jnp.ones((B, U), jnp.float32),
            "weights": jnp.ones((B,), jnp.float32),
        }

    return ModelBundle(
        cfg=cfg,
        init_params=lambda key: encdec_mod.init_params(cfg, key),
        per_example_loss=per_example_loss,
        loss_fn=loss_fn,
        final_hidden=hidden,
        head_weight=head_weight,
        prefill=prefill,
        decode=decode,
        init_cache=init_cache,
        input_specs=input_specs,
        make_batch=make_batch,
    )


# ===========================================================================
# RNN-T (the paper's architecture)
# ===========================================================================

def _build_rnnt(cfg: ModelConfig) -> ModelBundle:
    r = cfg.rnnt
    if r.loss_impl not in ("fused", "dense"):
        raise ValueError(f"rnnt.loss_impl must be 'fused' or 'dense', "
                         f"got {r.loss_impl!r}")

    def _t_lens(batch):
        return rnnt_mod.encoder_lengths(cfg, batch["feat_lens"])

    def per_example_nll(params, batch, shard=IDENTITY_SHARDER, train=False,
                        remat=True):
        """Per-example transducer NLL, path keyed by ``r.loss_impl``
        (DESIGN.md §2): ``fused`` streams the joint inside a custom_vjp
        (no (B,T,U+1,V) tensor, analytic gradients); ``dense`` is the
        materialized autodiff parity oracle.  The joint factors are
        pinned with ``shard(..., "act_bsd")`` (batch over data,
        replicated elsewhere) — on a mesh this anchors GSPMD's
        propagation at the custom_vjp boundary, which XLA:CPU SPMD
        otherwise mispartitions through the CRDNN encoder (wrong
        *values*, not just reordering; see tests/test_sharded_engine.py).

        -> ``(nll (B,), state)``: with ``train`` the encoder's batch norms
        (if any) take the batch's statistics and ``state`` is the running
        statistics after it; otherwise the running statistics normalize
        and ``state`` is None."""
        if r.loss_impl == "fused":
            if train:
                ze, zp, state = rnnt_mod.joint_factors_train(
                    params, cfg, batch["feats"], batch["tokens"],
                    batch["feat_lens"], remat=remat)
            else:
                ze, zp = rnnt_mod.joint_factors(
                    params, cfg, batch["feats"], batch["tokens"],
                    batch["feat_lens"])
                state = None
            ze = shard(ze, "act_bsd")
            zp = shard(zp, "act_bsd")
            return rnnt_loss_fused(
                ze, zp, params["joint"]["w_out"], batch["tokens"],
                _t_lens(batch), batch["token_lens"],
                vocab_chunk=r.loss_vocab_chunk,
                lattice_part=shard.batch_partition(ze.shape[0])), state
        if train and r.encoder == "conformer":
            raise NotImplementedError(
                "the dense (oracle) loss trains the CRDNN only; the "
                "Conformer trains on the fused loss")
        logits = rnnt_mod.forward(params, cfg, batch["feats"], batch["tokens"],
                                  batch["feat_lens"])
        return rnnt_loss_from_logits(logits, batch["tokens"], _t_lens(batch),
                                     batch["token_lens"]), None

    def _per_token(nll, batch):
        return nll / jnp.maximum(batch["token_lens"].astype(jnp.float32), 1.0)

    def per_example_loss(params, batch, shard=IDENTITY_SHARDER, remat=True):
        """Evaluation: the running statistics normalize (validation,
        selection)."""
        nll, _ = per_example_nll(params, batch, shard)
        return _per_token(nll, batch)

    def loss_fn(params, batch, shard=IDENTITY_SHARDER, remat=True):
        """The training objective.  An encoder with batch norm normalizes
        with the batch's statistics and reports the running statistics
        after this batch as ``metrics[STATE_KEY]``, for the training step
        to put in place of the params' (``make_step_core``)."""
        nll, state = per_example_nll(params, batch, shard, train=True,
                                     remat=remat)
        total, metrics = _weighted(_per_token(nll, batch), batch,
                                   jnp.zeros((), jnp.float32))
        if state is not None:
            metrics[STATE_KEY] = state
        return total, metrics

    def hidden(params, batch, shard=IDENTITY_SHARDER, remat=True):
        """PGM hook: joint pre-vocab activations + what's needed for the
        loss-to-logits error signal."""
        enc = rnnt_mod.encode(params, cfg, batch["feats"], batch["feat_lens"])
        pred = rnnt_mod.predict(params, cfg, batch["tokens"])
        z = rnnt_mod.joint_hidden(params, enc, pred)
        return z, batch["tokens"], None, jnp.zeros((), jnp.float32)

    def head_weight(params):
        return params["joint"]["w_out"]

    def input_specs(shape: ShapeConfig):
        B = shape.global_batch
        T = shape.seq_len // 8          # audio frames per "token budget"
        U = shape.seq_len // 32
        return {
            "feats": jax.ShapeDtypeStruct((B, T, r.n_feats), jnp.float32),
            "feat_lens": jax.ShapeDtypeStruct((B,), jnp.int32),
            "tokens": jax.ShapeDtypeStruct((B, U), jnp.int32),
            "token_lens": jax.ShapeDtypeStruct((B,), jnp.int32),
            "weights": jax.ShapeDtypeStruct((B,), jnp.float32),
        }

    def make_batch(key, B: int, S: int, T: Optional[int] = None,
                   U: Optional[int] = None) -> Batch:
        ks = jax.random.split(key, 2)
        T = T or max(S // 2, 16)
        U = U or max(S // 8, 4)
        return {
            "feats": jax.random.normal(ks[0], (B, T, r.n_feats)),
            "feat_lens": jnp.full((B,), T, jnp.int32),
            "tokens": jax.random.randint(ks[1], (B, U), 1, r.vocab_size),
            "token_lens": jnp.full((B,), U, jnp.int32),
            "weights": jnp.ones((B,), jnp.float32),
        }

    # -- streaming greedy transducer serve hooks (DESIGN.md §4) --------
    # The LM serve contract maps onto the transducer search: "prefill"
    # runs the encoder once and seeds the blank-start prediction
    # state; "decode" is one *joint step* — it consumes the previously
    # sampled symbol (blank advances the frame cursor, a label advances
    # the prediction GRU) and returns the next joint logits.  The cache
    # is the per-utterance decode state: the encoder output buffer, the
    # frame cursor/limit, the prediction-net state and the
    # symbols-emitted-this-frame counter (the per-frame emission cap is
    # enforced by forcing blank logits once the cap is hit, which is
    # exactly where the non-streaming reference breaks its inner loop).

    def rnnt_prefill(params, batch, shard=IDENTITY_SHARDER, cache_len=None,
                     max_symbols: int = 8):
        feats = batch["feats"]
        enc = rnnt_mod.encode(params, cfg, feats, batch["feat_lens"])
        B, T_enc, _ = enc.shape
        t_len = jnp.minimum(_t_lens(batch), T_enc).astype(jnp.int32)
        g, h = rnnt_mod.pred_start(params, cfg, B, dtype=enc.dtype)
        logits = rnnt_mod.joint_step(params, enc[:, 0], g)
        cache = {
            "enc": enc,
            "t": jnp.zeros((B,), jnp.int32),
            "t_len": t_len,
            "g": g,
            "h": h,
            "syms": jnp.zeros((B,), jnp.int32),
            "max_syms": jnp.full((B,), max_symbols, jnp.int32),
        }
        return logits, cache

    def rnnt_decode(params, cache, tokens, shard=IDENTITY_SHARDER):
        """tokens: (B,) the symbol sampled from the previous logits."""
        blank = tokens == rnnt_mod.BLANK_ID
        g_new, h_new = rnnt_mod.pred_step(params, cfg, tokens, cache["h"])
        g = jnp.where(blank[:, None], cache["g"], g_new)
        h = jnp.where(blank[:, None], cache["h"], h_new)
        t = cache["t"] + blank.astype(jnp.int32)
        syms = jnp.where(blank, 0, cache["syms"] + 1)
        T_enc = cache["enc"].shape[1]
        t_idx = jnp.clip(t, 0, T_enc - 1)
        enc_t = jnp.take_along_axis(
            cache["enc"], t_idx[:, None, None], axis=1)[:, 0]
        logits = rnnt_mod.joint_step(params, enc_t, g)
        # per-frame emission cap: force blank so greedy search advances —
        # the same place the reference inner loop stops (DESIGN.md §4)
        forced = jnp.full_like(logits, -1e30)
        forced = forced.at[:, rnnt_mod.BLANK_ID].set(0.0)
        logits = jnp.where((syms >= cache["max_syms"])[:, None],
                           forced, logits)
        cache = dict(cache, t=t, g=g, h=h, syms=syms)
        return logits, cache

    def rnnt_init_cache(batch_size: int, cache_len: int, dtype=None,
                        max_symbols: int = 8):
        """Zero decode state; ``cache_len`` is the *encoder-frame*
        capacity (audio frames // time_reduction)."""
        dtype = jnp.float32 if dtype is None else dtype
        return {
            "enc": jnp.zeros((batch_size, cache_len, r.enc_dim), dtype),
            "t": jnp.zeros((batch_size,), jnp.int32),
            "t_len": jnp.zeros((batch_size,), jnp.int32),
            "g": jnp.zeros((batch_size, r.pred_hidden), dtype),
            "h": jnp.zeros((batch_size, r.pred_state), dtype),
            "syms": jnp.zeros((batch_size,), jnp.int32),
            "max_syms": jnp.full((batch_size,), max_symbols, jnp.int32),
        }

    return ModelBundle(
        cfg=cfg,
        init_params=lambda key: rnnt_mod.init_params(cfg, key),
        per_example_loss=per_example_loss,
        loss_fn=loss_fn,
        final_hidden=hidden,
        head_weight=head_weight,
        prefill=rnnt_prefill,
        decode=rnnt_decode,
        init_cache=rnnt_init_cache,
        input_specs=input_specs,
        make_batch=make_batch,
    )


# ===========================================================================

def build_model(cfg: ModelConfig) -> ModelBundle:
    if cfg.family == "rnnt":
        return _build_rnnt(cfg)
    if cfg.family == "encdec":
        return _build_encdec(cfg)
    return _build_lm(cfg)
