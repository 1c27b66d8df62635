"""BERT model family (reference capability: BERT-base fused-attention config in
BASELINE.json; fused stack ≙ operators/fused/fused_attention_op.cu +
fused_feedforward_op.cu).

Same TPU-first skeleton as models/gpt.py: all encoder layers stacked in one
pytree consumed by ``lax.scan`` (O(1) compile in depth), flash attention from
paddle_tpu.ops, bf16 compute / fp32 params, TP via dims_mapping annotations.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Parameter, Tensor
from ..nn.layer.base import Layer
from ..ops.attention import dense_attention, flash_attention


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768, num_hidden_layers=12,
                 num_attention_heads=12, intermediate_size=None,
                 max_position_embeddings=512, type_vocab_size=2,
                 hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
                 initializer_range=0.02, layer_norm_eps=1e-12,
                 compute_dtype="bfloat16", use_flash_attention=True,
                 scan_unroll=1, hidden_act="gelu"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position_embeddings = max_position_embeddings
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.initializer_range = initializer_range
        self.layer_norm_eps = layer_norm_eps
        self.compute_dtype = compute_dtype
        self.use_flash_attention = use_flash_attention
        # "gelu" = exact erf form (paddle F.gelu / HF BERT default);
        # "gelu_approx" = tanh form.  Round-2 shipped the tanh approx
        # unconditionally — a measurable deviation from the reference.
        if hidden_act not in ("gelu", "gelu_approx"):
            raise ValueError(f"hidden_act must be 'gelu' or 'gelu_approx', "
                             f"got {hidden_act!r}")
        self.hidden_act = hidden_act
        self.scan_unroll = scan_unroll


BERT_CONFIGS = {
    "bert-base": dict(hidden_size=768, num_hidden_layers=12, num_attention_heads=12),
    "bert-large": dict(hidden_size=1024, num_hidden_layers=24, num_attention_heads=16),
}


def bert_preset(name: str, **overrides) -> BertConfig:
    cfg = dict(BERT_CONFIGS[name])
    cfg.update(overrides)
    return BertConfig(**cfg)


class BertModel(Layer):
    """Bidirectional encoder with stacked block parameters."""

    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = c = config
        L, H, V = c.num_hidden_layers, c.hidden_size, c.vocab_size
        I = c.intermediate_size
        std = c.initializer_range

        def normal(shape, s=std):
            from ..nn.initializer import Normal
            return Normal(0.0, s)(shape, "float32")

        def param(name, data, mapping=None):
            p = Parameter(data, name=name)
            if mapping:
                p._dims_mapping = mapping
            self.add_parameter(name.replace(".", "_"), p)
            return p

        zeros, ones = (lambda s: jnp.zeros(s, jnp.float32)), (lambda s: jnp.ones(s, jnp.float32))
        self.word_emb = param("word_emb", normal([V, H]), {0: "model"})
        self.pos_emb = param("pos_emb", normal([c.max_position_embeddings, H]))
        self.type_emb = param("type_emb", normal([c.type_vocab_size, H]))
        self.emb_ln_w = param("emb_ln_w", ones([H]))
        self.emb_ln_b = param("emb_ln_b", zeros([H]))
        # stacked encoder blocks — post-LN (original BERT residual order)
        self.blocks_qkv_w = param("blocks.qkv_w", normal([L, H, 3 * H]), {2: "model"})
        self.blocks_qkv_b = param("blocks.qkv_b", zeros([L, 3 * H]), {1: "model"})
        self.blocks_proj_w = param("blocks.proj_w",
                                   normal([L, H, H], std / math.sqrt(2 * L)),
                                   {1: "model"})
        self.blocks_proj_b = param("blocks.proj_b", zeros([L, H]))
        self.blocks_ln1_w = param("blocks.ln1_w", ones([L, H]))
        self.blocks_ln1_b = param("blocks.ln1_b", zeros([L, H]))
        self.blocks_fc1_w = param("blocks.fc1_w", normal([L, H, I]), {2: "model"})
        self.blocks_fc1_b = param("blocks.fc1_b", zeros([L, I]), {1: "model"})
        self.blocks_fc2_w = param("blocks.fc2_w",
                                  normal([L, I, H], std / math.sqrt(2 * L)),
                                  {1: "model"})
        self.blocks_fc2_b = param("blocks.fc2_b", zeros([L, H]))
        self.blocks_ln2_w = param("blocks.ln2_w", ones([L, H]))
        self.blocks_ln2_b = param("blocks.ln2_b", zeros([L, H]))
        # pooler + heads
        self.pooler_w = param("pooler_w", normal([H, H]))
        self.pooler_b = param("pooler_b", zeros([H]))
        self.mlm_dense_w = param("mlm_dense_w", normal([H, H]))
        self.mlm_dense_b = param("mlm_dense_b", zeros([H]))
        self.mlm_ln_w = param("mlm_ln_w", ones([H]))
        self.mlm_ln_b = param("mlm_ln_b", zeros([H]))
        self.mlm_bias = param("mlm_bias", zeros([V]), {0: "model"})
        self.nsp_w = param("nsp_w", normal([H, 2]))
        self.nsp_b = param("nsp_b", zeros([2]))

    @staticmethod
    def stacked_param_names():
        return [f"blocks_{n}" for n in ("qkv_w", "qkv_b", "proj_w", "proj_b",
                                        "ln1_w", "ln1_b", "fc1_w", "fc1_b",
                                        "fc2_w", "fc2_b", "ln2_w", "ln2_b")]

    # -------------------------------------------------------- pure functions
    def _ln(self, x, w, b):
        eps = self.config.layer_norm_eps
        x32 = x.astype(jnp.float32)
        m = x32.mean(-1, keepdims=True)
        v = x32.var(-1, keepdims=True)
        return (x32 - m) * jax.lax.rsqrt(v + eps) * w + b

    def embed_fn(self, params, input_ids, token_type_ids=None):
        c = self.config
        dt = jnp.dtype(c.compute_dtype)
        pos = jnp.arange(input_ids.shape[-1])
        h = jnp.take(params["word_emb"], input_ids, axis=0) + params["pos_emb"][pos]
        if token_type_ids is None:
            h = h + params["type_emb"][0]
        else:
            h = h + jnp.take(params["type_emb"], token_type_ids, axis=0)
        return self._ln(h, params["emb_ln_w"], params["emb_ln_b"]).astype(dt)

    def block_fn(self, sl: Dict[str, Any], h, attn_mask=None, mesh=None):
        c = self.config
        dt = h.dtype
        B, Lq, H = h.shape
        nh = c.num_attention_heads
        hd = H // nh
        qkv = h @ sl["blocks_qkv_w"].astype(dt) + sl["blocks_qkv_b"].astype(dt)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (t.reshape(B, Lq, nh, hd) for t in (q, k, v))
        if c.use_flash_attention:
            # the (B,1,1,L) padding mask rides inside the Pallas kernel as a
            # key mask — no dense fallback (ops/attention.py)
            att = flash_attention(q, k, v, causal=False, key_mask=attn_mask,
                                  mesh=mesh)
        else:
            att = dense_attention(q, k, v, mask=attn_mask, causal=False)
        att = att.reshape(B, Lq, H)

        def epilogue(x, residual, ln_w, ln_b, bias):
            """LN(residual + x + bias), the post-norm epilogue of both
            halves of the block."""
            return self._ln(residual + x + bias.astype(dt), ln_w, ln_b).astype(dt)

        h = epilogue(att @ sl["blocks_proj_w"].astype(dt), h,
                     sl["blocks_ln1_w"], sl["blocks_ln1_b"],
                     sl["blocks_proj_b"])
        ff = jax.nn.gelu(h @ sl["blocks_fc1_w"].astype(dt)
                         + sl["blocks_fc1_b"].astype(dt),
                         approximate=c.hidden_act == "gelu_approx")
        return epilogue(ff @ sl["blocks_fc2_w"].astype(dt), h,
                        sl["blocks_ln2_w"], sl["blocks_ln2_b"],
                        sl["blocks_fc2_b"])

    def scan_blocks(self, params, h, attn_mask=None, remat=True, mesh=None):
        from ..distributed.sharding_rules import constrain_activation
        h = constrain_activation(h, mesh)   # the carry: rows on the batch axes
        stacked = {k: params[k] for k in self.stacked_param_names()}

        def fn(sl, hh):
            return self.block_fn(sl, hh, attn_mask, mesh=mesh)

        if remat:
            fn = jax.checkpoint(fn)
        from ._scan import resolve_scan_unroll
        out, _ = jax.lax.scan(lambda carry, sl: (fn(sl, carry), None), h, stacked,
                              unroll=resolve_scan_unroll(self.config))
        return out

    def encode(self, params, input_ids, token_type_ids=None, attn_mask=None,
               remat=False, mesh=None):
        h = self.embed_fn(params, input_ids, token_type_ids)
        return self.scan_blocks(params, h, attn_mask, remat=remat, mesh=mesh)

    def pool_fn(self, params, h):
        dt = h.dtype
        return jnp.tanh(h[:, 0] @ params["pooler_w"].astype(dt)
                        + params["pooler_b"].astype(dt))

    def _mlm_logits(self, params, h):
        dt = h.dtype
        x = jax.nn.gelu(h @ params["mlm_dense_w"].astype(dt)
                        + params["mlm_dense_b"].astype(dt),
                        approximate=self.config.hidden_act == "gelu_approx")
        x = self._ln(x, params["mlm_ln_w"], params["mlm_ln_b"]).astype(dt)
        # stays in the compute dtype: the fused CE (ops/loss.py) reduces in
        # fp32 internally, so fp32 logits would only add HBM traffic
        return x @ params["word_emb"].astype(dt).T + params["mlm_bias"].astype(dt)

    def mlm_logits(self, params, h):
        """fp32 MLM head for external use (eval perplexity, logit inspection),
        mirroring GPT's head_fn/_head_logits split; the loss path uses the
        compute-dtype variant since fused CE reduces in fp32 anyway."""
        return self._mlm_logits(params, h).astype(jnp.float32)

    @staticmethod
    def _additive_mask(attention_mask):
        """(B, L) 1=keep/0=pad → additive (B, 1, 1, L) mask, or None."""
        if attention_mask is None:
            return None
        return (1.0 - attention_mask.astype(jnp.float32))[:, None, None, :] * -1e30

    def pretrain_loss_fn(self, params, input_ids, mlm_labels, nsp_labels=None,
                         token_type_ids=None, attention_mask=None, remat=False,
                         mesh=None):
        """MLM (ignore label -100) + optional NSP loss.  ``mesh``: the mesh
        the step is partitioned over, for the flash kernel."""
        h = self.encode(params, input_ids, token_type_ids,
                        attn_mask=self._additive_mask(attention_mask),
                        remat=remat, mesh=mesh)
        logits = self._mlm_logits(params, h)
        valid = mlm_labels >= 0
        safe = jnp.where(valid, mlm_labels, 0)
        # fused masked CE — no fp32 (B, L, V) log-prob tensor (ops/loss.py)
        from ..ops.loss import softmax_cross_entropy_weighted_mean
        mlm_loss = softmax_cross_entropy_weighted_mean(logits, safe, valid)
        if nsp_labels is None:
            return mlm_loss
        pooled = self.pool_fn(params, h).astype(jnp.float32)
        nsp_logits = pooled @ params["nsp_w"] + params["nsp_b"]
        nsp_logp = jax.nn.log_softmax(nsp_logits, axis=-1)
        nsp_loss = -jnp.take_along_axis(nsp_logp, nsp_labels[:, None],
                                        axis=-1).mean()
        return mlm_loss + nsp_loss

    # ------------------------------------------------------------- nn.Layer
    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        raw = getattr(input_ids, "_data", input_ids)
        tok = getattr(token_type_ids, "_data", token_type_ids)
        am = getattr(attention_mask, "_data", attention_mask)
        params = {n: p._data for n, p in self.named_parameters()}
        h = self.encode(params, raw, tok, attn_mask=self._additive_mask(am))
        pooled = self.pool_fn(params, h)
        if isinstance(input_ids, Tensor):
            return Tensor(h), Tensor(pooled)
        return h, pooled


def make_bert_train_step(model: BertModel, optimizer, hcg, remat: bool = True,
                         donate: bool = True):
    """Data/tensor-parallel MLM+NSP pretraining step over the hybrid mesh."""
    from ..distributed.spmd import make_gspmd_step_from_loss

    params0 = {n: p._data for n, p in model.named_parameters()}

    def loss_of(params, input_ids, mlm_labels, nsp_labels):
        return model.pretrain_loss_fn(params, input_ids, mlm_labels,
                                      nsp_labels, remat=remat, mesh=hcg.mesh)

    return make_gspmd_step_from_loss(loss_of, params0, optimizer, hcg.mesh,
                                     layer=model, donate=donate)


def make_sharded_bert_train_step(cfg: BertConfig, optimizer, hcg,
                                 zero_stage: int = 0, seed: int = 0,
                                 remat: bool = True, donate: bool = True):
    """BERT pretraining step with mesh-direct sharded init (see
    models/gpt.py make_sharded_gpt_train_step — same contract: sharding
    SPECS only; contractual-ZeRO extras ride make_bert_train_step)."""
    from ..core import rng as _rng
    from ..distributed.spmd import make_gspmd_sharded_init_step

    holder = {}

    def build(key):
        with _rng.rng_scope(key):
            m = BertModel(cfg)
        holder.setdefault("model", m)
        return {n: p._data for n, p in m.named_parameters()}

    jax.eval_shape(build, jax.random.key(seed))
    meta = holder["model"]

    def loss_of(params, input_ids, mlm_labels, nsp_labels):
        return meta.pretrain_loss_fn(params, input_ids, mlm_labels,
                                     nsp_labels, remat=remat, mesh=hcg.mesh)

    return make_gspmd_sharded_init_step(loss_of, build, optimizer, hcg.mesh,
                                        meta, zero_stage=zero_stage,
                                        donate=donate, seed=seed)
