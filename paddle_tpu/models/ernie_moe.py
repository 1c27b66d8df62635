"""ERNIE-style MoE transformer (reference capability: "ERNIE MoE alltoall"
config in BASELINE.json; EP transport ≙ global_scatter/global_gather,
distributed/utils.py:57,179).

Decoder-only transformer where every block's FFN is a top-k routed mixture of
experts.  TPU-first: blocks stacked for ``lax.scan`` (expert weights get an
extra leading layer dim: (L, E, H, I)); expert parallelism is a sharding
constraint on the dispatched (E, C, H) tensor — GSPMD emits the token
all_to_all over the expert mesh axis.  Aux (load-balance) losses are summed
over layers via the scan carry.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..core.tensor import Parameter, Tensor
from ..nn.layer.base import Layer
from ._decode import CausalDecoderMixin
from ..ops.attention import flash_attention
from ..ops.moe import moe_ffn, moe_ffn_gather, moe_ffn_indices


class ErnieMoeConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_attention_heads=12, num_experts=8, top_k=2,
                 expert_hidden_size=None, capacity_factor=1.25,
                 max_position_embeddings=1024, initializer_range=0.02,
                 layer_norm_epsilon=1e-5, compute_dtype="bfloat16",
                 aux_loss_weight=0.01, expert_axis="data", scan_unroll=1,
                 index_dispatch=True):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_attention_heads = num_attention_heads
        self.num_experts = num_experts
        self.top_k = top_k
        self.expert_hidden_size = expert_hidden_size or 4 * hidden_size
        self.capacity_factor = capacity_factor
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.layer_norm_epsilon = layer_norm_epsilon
        self.compute_dtype = compute_dtype
        self.aux_loss_weight = aux_loss_weight
        self.expert_axis = expert_axis
        self.scan_unroll = scan_unroll
        self.index_dispatch = index_dispatch


class ErnieMoeModel(CausalDecoderMixin, Layer):
    """Causal LM with MoE FFNs in every block."""

    def __init__(self, config: ErnieMoeConfig):
        super().__init__()
        self.config = c = config
        L, H, V, E = c.num_layers, c.hidden_size, c.vocab_size, c.num_experts
        I = c.expert_hidden_size
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

        zeros = lambda s: jnp.zeros(s, jnp.float32)
        ones = lambda s: jnp.ones(s, jnp.float32)
        self.wte = param("wte", normal([V, H]), {0: "model"})
        self.wpe = param("wpe", normal([c.max_position_embeddings, H]))
        self.blocks_ln1_w = param("blocks.ln1_w", ones([L, H]))
        self.blocks_ln1_b = param("blocks.ln1_b", zeros([L, H]))
        self.blocks_qkv_w = param("blocks.qkv_w", normal([L, H, 3 * H]), {2: "model"})
        self.blocks_qkv_b = param("blocks.qkv_b", zeros([L, 3 * H]), {1: "model"})
        self.blocks_proj_w = param("blocks.proj_w",
                                   normal([L, H, H], std / math.sqrt(2 * L)),
                                   {1: "model"})
        self.blocks_proj_b = param("blocks.proj_b", zeros([L, H]))
        self.blocks_ln2_w = param("blocks.ln2_w", ones([L, H]))
        self.blocks_ln2_b = param("blocks.ln2_b", zeros([L, H]))
        # MoE FFN: gate + stacked experts, leading (L, E) dims
        self.blocks_gate_w = param("blocks.gate_w", normal([L, H, E]))
        self.blocks_expert_w1 = param("blocks.expert_w1", normal([L, E, H, I]),
                                      {1: c.expert_axis})
        self.blocks_expert_b1 = param("blocks.expert_b1", zeros([L, E, I]),
                                      {1: c.expert_axis})
        self.blocks_expert_w2 = param("blocks.expert_w2",
                                      normal([L, E, I, H], std / math.sqrt(2 * L)),
                                      {1: c.expert_axis})
        self.blocks_expert_b2 = param("blocks.expert_b2", zeros([L, E, H]),
                                      {1: c.expert_axis})
        self.lnf_w = param("lnf_w", ones([H]))
        self.lnf_b = param("lnf_b", zeros([H]))

    @staticmethod
    def stacked_param_names():
        return [f"blocks_{n}" for n in
                ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
                 "ln2_w", "ln2_b", "gate_w", "expert_w1", "expert_b1",
                 "expert_w2", "expert_b2")]

    # -------------------------------------------------------- pure functions
    def embed_fn(self, params, input_ids, key=None):
        c = self.config
        pos = jnp.arange(input_ids.shape[-1])
        h = jnp.take(params["wte"], input_ids, axis=0) + params["wpe"][pos]
        return h.astype(jnp.dtype(c.compute_dtype))

    def _block_ln(self, x, w, b, dt):
        x32 = x.astype(jnp.float32)
        m = x32.mean(-1, keepdims=True)
        v = x32.var(-1, keepdims=True)
        return ((x32 - m) * jax.lax.rsqrt(v + self.config.layer_norm_epsilon)
                * w + b).astype(dt)

    def _block_qkv(self, sl, h):
        """pre-LN + fused QKV; returns q, k, v as (B, L, nh, hd)."""
        c = self.config
        dt = h.dtype
        B, Lq, H = h.shape
        nh = c.num_attention_heads
        hd = H // nh
        a_in = self._block_ln(h, sl["blocks_ln1_w"], sl["blocks_ln1_b"], dt)
        qkv = a_in @ sl["blocks_qkv_w"].astype(dt) + sl["blocks_qkv_b"].astype(dt)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        return (q.reshape(B, Lq, nh, hd), k.reshape(B, Lq, nh, hd),
                v.reshape(B, Lq, nh, hd))

    def _attn_residual(self, sl, h, att):
        dt = h.dtype
        B, Lq, H = h.shape
        att = att.reshape(B, Lq, H)
        return h + att @ sl["blocks_proj_w"].astype(dt) \
            + sl["blocks_proj_b"].astype(dt)

    def _moe_residual(self, sl, h, mesh=None, capacity_factor=None):
        """ln2 + routed FFN + residual.  capacity_factor=None → training
        config; a float overrides (generation passes the no-drop value)."""
        c = self.config
        dt = h.dtype
        B, Lq, H = h.shape
        m_in = self._block_ln(h, sl["blocks_ln2_w"], sl["blocks_ln2_b"], dt)
        tokens = m_in.reshape(B * Lq, H)
        # index (gather/scatter) dispatch by default — the einsum dispatch's
        # (T, E, C) masks cost ~2x the expert FLOPs at bench shapes
        ffn = moe_ffn_indices if getattr(c, "index_dispatch", True) else moe_ffn
        out, aux = ffn(tokens, sl["blocks_gate_w"], sl["blocks_expert_w1"],
                       sl["blocks_expert_b1"], sl["blocks_expert_w2"],
                       sl["blocks_expert_b2"], k=c.top_k,
                       capacity_factor=(c.capacity_factor
                                        if capacity_factor is None
                                        else capacity_factor),
                       mesh=mesh, expert_axis=c.expert_axis)
        return h + out.reshape(B, Lq, H), aux

    def block_fn(self, sl: Dict[str, Any], h, mesh=None):
        """One block; returns (h, aux_loss)."""
        q, k, v = self._block_qkv(sl, h)
        att = flash_attention(q, k, v, causal=True, mesh=mesh)
        h = self._attn_residual(sl, h, att)
        return self._moe_residual(sl, h, mesh=mesh)

    def scan_blocks(self, params, h, mesh=None, remat=True):
        from ..distributed.sharding_rules import constrain_activation
        h = constrain_activation(h, mesh)   # the carry: rows on the batch axes
        stacked = {k: params[k] for k in self.stacked_param_names()}
        fn = self.block_fn
        if remat:
            fn = jax.checkpoint(lambda sl, hh: self.block_fn(sl, hh, mesh))
        else:
            fn = lambda sl, hh: self.block_fn(sl, hh, mesh)

        def body(carry, sl):
            hh, aux_sum = carry
            hh, aux = fn(sl, hh)
            return (hh, aux_sum + aux), None

        from ._scan import resolve_scan_unroll
        (out, aux_sum), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                                         stacked,
                                         unroll=resolve_scan_unroll(self.config))
        return out, aux_sum

    def _head_logits(self, params, h, dtype=None):
        c = self.config
        x32 = h.astype(jnp.float32)
        m = x32.mean(-1, keepdims=True)
        v = x32.var(-1, keepdims=True)
        hn = (x32 - m) * jax.lax.rsqrt(v + c.layer_norm_epsilon) * params["lnf_w"] \
            + params["lnf_b"]
        dt = jnp.dtype(c.compute_dtype) if dtype is None else dtype
        return hn.astype(dt) @ params["wte"].astype(dt).T

    def head_loss_fn(self, params, h, labels, aux_sum=0.0):
        # fused CE — no fp32 (B, L, V) log-prob tensor (ops/loss.py)
        from ..ops.loss import softmax_cross_entropy_mean
        nll = softmax_cross_entropy_mean(self._head_logits(params, h), labels)
        return nll + self.config.aux_loss_weight * aux_sum

    # ------------------------------------------------------------- nn.Layer
    def forward(self, input_ids, labels=None):
        raw = getattr(input_ids, "_data", input_ids)
        params = {n: p._data for n, p in self.named_parameters()}
        h = self.embed_fn(params, raw)
        h, aux = self.scan_blocks(params, h, remat=False)
        if labels is None:
            logits = self._head_logits(params, h, dtype=jnp.float32)
            return Tensor(logits) if isinstance(input_ids, Tensor) else logits
        raw_labels = getattr(labels, "_data", labels)
        loss = self.head_loss_fn(params, h, raw_labels, aux)
        return Tensor(loss) if isinstance(input_ids, Tensor) else loss

    # ------------------------------------------------- KV-cache generation
    # Same static-cache single-scan design as models/gpt.py, with one MoE
    # twist: capacity-based token dropping is CONTEXT-dependent, so an
    # incremental decode only reproduces the full forward if nothing drops.
    # Generation therefore routes with a no-drop capacity (cf = E/k ⇒
    # C >= T always) in both prefill and decode — which is also the right
    # serving behavior (dropping a live request's FFN output is not an
    # option at inference).

    def _nodrop_cf(self) -> float:
        c = self.config
        return float(c.num_experts) / float(c.top_k)

    def _moe_residual_gather(self, sl, h):
        """ln2 + capacity-free gather-dispatch FFN + residual — the decode
        hot path: O(k·T) expert FLOPs, no (E, C, H) buffer (ops/moe.py:
        moe_ffn_gather; equal to the no-drop indices path by test)."""
        c = self.config
        dt = h.dtype
        B, Lq, H = h.shape
        m_in = self._block_ln(h, sl["blocks_ln2_w"], sl["blocks_ln2_b"], dt)
        out = moe_ffn_gather(m_in.reshape(B * Lq, H), sl["blocks_gate_w"],
                             sl["blocks_expert_w1"], sl["blocks_expert_b1"],
                             sl["blocks_expert_w2"], sl["blocks_expert_b2"],
                             k=c.top_k)
        return h + out.reshape(B, Lq, H)

    def _block_decode(self, sl, h, ck, cv, t, pad_lens=None):
        """One block for one new token at position t (h (B,1,H); ck/cv
        (B, max_len, nh, hd))."""
        from ._decode import cached_attention, dequantize_cache, write_cache
        q, k, v = self._block_qkv(sl, h)
        ck = write_cache(ck, k, t)
        cv = write_cache(cv, v, t)
        att = cached_attention(q, dequantize_cache(ck, q.dtype),
                               dequantize_cache(cv, q.dtype), t,
                               pad_lens=pad_lens)
        h = self._attn_residual(sl, h, att)
        return self._moe_residual_gather(sl, h), ck, cv

    def prefill(self, params, input_ids, max_len: int, pad_lens=None,
                mesh=None):
        """Prompt pass with no-drop routing; returns (h, (ck, cv)) with
        caches filled at [0, P).  Uses the buffered no-drop indices dispatch
        (cf = E/k): at prefill T = B·P is large, so gathering (T, k, H, I)
        weight slices would cost more than the padded buffer does.  With
        ``pad_lens`` (left-padded prompts), pad keys get a finite -1e30 mask
        and positions shift per row; ``mesh`` is the serving mesh, for the
        flash kernel (see GPT.prefill)."""
        c = self.config
        B, P = input_ids.shape
        if pad_lens is None:
            h, key_mask = self.embed_fn(params, input_ids), None
        else:
            h = self._prefill_embed(params, input_ids, pad_lens)
            key_mask = self._prefill_key_mask(P, pad_lens)
        stacked = {k: params[k] for k in self.stacked_param_names()}

        def body(carry, sl):
            q, k, v = self._block_qkv(sl, carry)
            att = flash_attention(q, k, v, causal=True, key_mask=key_mask,
                                  mesh=mesh)
            hh = self._attn_residual(sl, carry, att)
            hh, _ = self._moe_residual(sl, hh,
                                       capacity_factor=self._nodrop_cf())
            return hh, (k, v)

        h, (ks, vs) = jax.lax.scan(body, h, stacked)
        pad = [(0, 0), (0, 0), (0, max_len - P), (0, 0), (0, 0)]
        cdt = jnp.dtype(c.compute_dtype)
        return h, (jnp.pad(ks.astype(cdt), pad), jnp.pad(vs.astype(cdt), pad))

    def _block_decode_ragged(self, sl, h, pck, pcv, table, row_seq,
                             row_pos, pad_lens, layer=None):
        """One block for a flattened ragged pack (the mixed serving step;
        see GPTModel._block_decode_ragged): scatter each row's k/v to its
        table-mapped pool position (in layer ``layer`` of the stack's
        pools) BEFORE attention, then the gather-dispatch MoE FFN — the
        no-drop decode hot path.  CausalDecoderMixin.decode_ragged scans
        it over the layers, so MoE targets ride the ragged engine
        (speculative verification included) as GPT does."""
        from ._decode import ragged_attention, ragged_write
        q, k, v = self._block_qkv(sl, h)               # (1, T, nh, hd)
        pck = ragged_write(pck, k[0], table, row_seq, row_pos, layer)
        pcv = ragged_write(pcv, v[0], table, row_seq, row_pos, layer)
        att = ragged_attention(q[0], pck, pcv, table, row_seq, row_pos,
                               pad_lens, layer)
        h = self._attn_residual(sl, h, att[None])
        return self._moe_residual_gather(sl, h), pck, pcv

    def decode_step(self, params, h, caches, t, pad_lens=None):
        stacked = {k: params[k] for k in self.stacked_param_names()}

        def body(carry, xs):
            sl, ck, cv = xs
            out, ck, cv = self._block_decode(sl, carry, ck, cv, t,
                                             pad_lens=pad_lens)
            return out, (ck, cv)

        h, (cks, cvs) = jax.lax.scan(body, h, (stacked, caches[0], caches[1]))
        return h, (cks, cvs)

    def decode_logits(self, params, h):
        """fp32 logits for the shared decode loops (CausalDecoderMixin)."""
        return self._head_logits(params, h, dtype=jnp.float32)


def make_ernie_moe_train_step(model: ErnieMoeModel, optimizer, hcg,
                              remat: bool = True, donate: bool = True):
    """Expert-parallel (+dp/mp) train step over the hybrid mesh."""
    from ..distributed.spmd import make_gspmd_step_from_loss

    mesh = hcg.mesh
    params0 = {n: p._data for n, p in model.named_parameters()}

    def loss_of(params, input_ids, labels):
        h = model.embed_fn(params, input_ids)
        h, aux = model.scan_blocks(params, h, mesh=mesh, remat=remat)
        return model.head_loss_fn(params, h, labels, aux)

    return make_gspmd_step_from_loss(loss_of, params0, optimizer, mesh,
                                     layer=model, donate=donate)


def make_sharded_ernie_moe_train_step(cfg: ErnieMoeConfig, optimizer, hcg,
                                      zero_stage: int = 0, seed: int = 0,
                                      remat: bool = True, donate: bool = True):
    """ERNIE-MoE step with mesh-direct sharded init (see models/gpt.py
    make_sharded_gpt_train_step — sharding SPECS only)."""
    from ..core import rng as _rng
    from ..distributed.spmd import make_gspmd_sharded_init_step

    mesh = hcg.mesh
    holder = {}

    def build(key):
        with _rng.rng_scope(key):
            m = ErnieMoeModel(cfg)
        holder.setdefault("model", m)
        return {n: p._data for n, p in m.named_parameters()}

    jax.eval_shape(build, jax.random.key(seed))
    meta = holder["model"]

    def loss_of(params, input_ids, labels):
        h = meta.embed_fn(params, input_ids)
        h, aux = meta.scan_blocks(params, h, mesh=mesh, remat=remat)
        return meta.head_loss_fn(params, h, labels, aux)

    return make_gspmd_sharded_init_step(loss_of, build, optimizer, mesh,
                                        meta, zero_stage=zero_stage,
                                        donate=donate, seed=seed)
