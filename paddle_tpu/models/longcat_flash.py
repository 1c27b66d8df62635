"""LongCat-Flash (``model_type: longcat_flash``; technical report
arXiv:2509.01322): a layer of TWO multi-head-latent-attention sublayers,
each followed by a dense gated-SiLU MLP, and a shortcut-connected expert
branch (ScMoE) whose input is taken after the first attention and whose
output lands after the second MLP — for SERVING through the ragged paged
engine.

    layer (x in R^H; j = 0, 1 the layer's sublayers):
      x  = x + MLA_0(N_in0(x));   m0 = N_post0(x)
      s  = MoE(m0)                        # the shortcut branch
      x  = x + FFN_0(m0)
      x  = x + MLA_1(N_in1(x));   m1 = N_post1(x)
      x  = x + FFN_1(m1) + s

    MLA_j: ``models/_mla.py``, with the query times ``sqrt(H /
           q_lora_rank)`` and the normed latent times ``sqrt(H /
           kv_lora_rank)`` (``mla_scale_q_lora`` / ``mla_scale_kv_lora``);
           ``k_r`` is not scaled.  Rotary without scaling.
    MoE:   p = softmax_float32(m W_r) over ``n_routed_experts`` real and
           then ``zero_expert_num`` zero-compute outputs; the ``moe_topk``
           largest of ``p + e_score_correction_bias``; ``w_e =
           routed_scaling_factor * p_e``, not normalised;
           s = sum_{e chosen, real} w_e E_e(m) + (sum_{e chosen, zero}
           w_e) m                              (``zero_expert_type``
           "identity": a zero-compute expert returns its input).

No shared expert, RMSNorm, an untied head, a final norm.  The model is
ONE stack, scanned over its layers; a parameter of a sublayer has a second
leading axis of two (``layers_q_a_w`` is ``(num_layers, 2, H,
q_lora_rank)``).  What is cached is one latent row per token per SUBLAYER,
``[N(c_kv) * kv scale ; rope(k_r) ; zeros]``: ``cache_spec()`` states one
leaf of ``2 * num_layers`` rows of the stack's pool, sublayer ``j`` of
layer ``l`` at row ``2 l + j`` (docs/CACHE_SPEC.md), each written and
attended in place through ``layer=``.

The share, as ``models/pangu_moe.py`` has it: ``experts_held`` (a
``range`` inside the real experts) beside the router's full width.  The
branch routes over every output, top-k, and computes what its own experts
contribute and — for every row this process serves — the zero-compute
experts' term, which has no weights and which the chip that owns the token
applies.  Nothing stands in for the absent chips: the partial sum is the
branch's output here.

A round of decode rows only runs the engine's narrow program
(``ragged_narrow_rounds``): this tick has no branch on the pack.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Parameter
from ..nn.layer.base import Layer
from ..ops.moe import (gated_mlp, held_experts_ffn, identity_experts,
                       route_softmax_topk)
from ._decode import (CacheLeaf, CacheSpec, CausalDecoderMixin, build_pools,
                      ragged_latent_attention, ragged_write, rms_norm)
from ._mla import (MlaGeometry, mla_attend_dense, mla_in, mla_kv_b, mla_out,
                   mla_softmax_scale)

# a sublayer's own parameters (a second leading axis of two), under the
# names models/_mla.py reads them by; ``ln1_w`` is the norm on the
# attention's input, ``ln3_w`` the one on the MLP's
_SUBLAYER = ("ln1_w", "q_a_w", "q_a_norm_w", "q_b_w", "kv_a_w",
             "kv_a_norm_w", "kv_b_w", "o_w", "ln3_w", "gate_w", "up_w",
             "down_w")
_ROUTER = ("router_w", "router_bias")
# never sliced by the layer scan: the grouped products read a layer's
# experts in place in the whole stack (ops/moe.py held_experts_ffn)
_EXPERTS = ("e_gate_w", "e_up_w", "e_down_w")
TICK_STATS = ("expert_rows", "expert_rows_max", "expert_pairs", "zero_pairs")


class LongcatFlashConfig(MlaGeometry):
    """The published keys of ``config.json`` (defaults: LongCat-Flash-Chat)
    and, beside them, ``experts_held``: the real experts this process
    holds.  ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` are the published
    switches; the factors they turn on are ``sqrt(hidden_size / rank)``.
    ``lora_norm_eps`` is no published key: the epsilon of the two norms on
    the low-rank latents, which the public implementation leaves at its
    norm's default instead of ``rms_norm_eps``."""

    def __init__(self, vocab_size=131072, hidden_size=6144, num_layers=28,
                 num_attention_heads=64, ffn_hidden_size=12288,
                 q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128,
                 mla_scale_q_lora=True, mla_scale_kv_lora=True,
                 expert_ffn_hidden_size=2048, moe_topk=12,
                 n_routed_experts=512, zero_expert_num=256,
                 zero_expert_type="identity", routed_scaling_factor=6.0,
                 rms_norm_eps=1e-5, lora_norm_eps=1e-6,
                 rope_theta=10000000.0, max_position_embeddings=131072,
                 initializer_range=0.02, compute_dtype="bfloat16",
                 experts_held: Optional[range] = None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_attention_heads = num_attention_heads
        self.ffn_hidden_size = ffn_hidden_size
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.mla_scale_q_lora = bool(mla_scale_q_lora)
        self.mla_scale_kv_lora = bool(mla_scale_kv_lora)
        self.expert_ffn_hidden_size = expert_ffn_hidden_size
        self.moe_topk = moe_topk
        self.n_routed_experts = n_routed_experts
        self.zero_expert_num = int(zero_expert_num or 0)
        if self.zero_expert_num and zero_expert_type != "identity":
            raise ValueError(f"zero_expert_type {zero_expert_type!r}: only "
                             f"identity is written")
        self.zero_expert_type = zero_expert_type
        self.routed_scaling_factor = routed_scaling_factor
        self.rms_norm_eps = rms_norm_eps
        self.lora_norm_eps = lora_norm_eps
        self.rope_theta = rope_theta
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.compute_dtype = compute_dtype
        held = range(n_routed_experts) if experts_held is None \
            else experts_held
        if held.step != 1 or not len(held) or held.start < 0 \
                or held.stop > n_routed_experts:
            raise ValueError(
                f"experts_held must be a non-empty contiguous range inside "
                f"the real experts [0, {n_routed_experts}), got "
                f"{experts_held!r}")
        self.experts_held = held
        if moe_topk > self.router_width:
            raise ValueError("moe_topk exceeds the router's outputs")

    @property
    def router_width(self):
        """The router's outputs: the real experts, then the zero ones."""
        return self.n_routed_experts + self.zero_expert_num

    @property
    def q_scale(self):
        return (self.hidden_size / self.q_lora_rank) ** 0.5 \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self):
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 \
            if self.mla_scale_kv_lora else 1.0


class LongcatFlashModel(CausalDecoderMixin, Layer):
    """One stack of two-sublayer blocks; parameters stacked over the
    layers (``layers_*``), a sublayer's own over (layers, 2)."""

    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        self.config = config
        from ..nn.initializer import Normal
        for name, (shape, init) in self.param_table(config).items():
            data = jnp.full(shape, float(init == "ones"), jnp.float32) \
                if isinstance(init, str) \
                else Normal(0.0, init)(list(shape), "float32")
            self.add_parameter(name, Parameter(data, name=name))

    @staticmethod
    def param_table(c: LongcatFlashConfig):
        """name -> (shape, standard deviation | "ones" | "zeros"): the
        program's parameter dictionary (``initializer_range`` normal
        weights, norm scales at one, the router's selection bias — a
        trained buffer a checkpoint brings — at zero)."""
        H, nh, L = c.hidden_size, c.num_attention_heads, c.num_layers
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        I, F, Eh = c.ffn_hidden_size, c.expert_ffn_hidden_size, \
            len(c.experts_held)
        std = c.initializer_range
        sub = {
            "ln1_w": ((H,), "ones"), "ln3_w": ((H,), "ones"),
            "q_a_w": ((H, c.q_lora_rank), std),
            "q_a_norm_w": ((c.q_lora_rank,), "ones"),
            "q_b_w": ((c.q_lora_rank, nh * qk), std),
            "kv_a_w": ((H, c.latent_width), std),
            "kv_a_norm_w": ((c.kv_lora_rank,), "ones"),
            "kv_b_w": ((c.kv_lora_rank,
                        nh * (c.qk_nope_head_dim + c.v_head_dim)), std),
            "o_w": ((nh * c.v_head_dim, H), std),
            "gate_w": ((H, I), std), "up_w": ((H, I), std),
            "down_w": ((I, H), std),
        }
        branch = {
            "router_w": ((H, c.router_width), std),
            "router_bias": ((c.router_width,), "zeros"),
            "e_gate_w": ((Eh, H, F), std), "e_up_w": ((Eh, H, F), std),
            "e_down_w": ((Eh, F, H), std),
        }
        table = {"wte": ((c.vocab_size, H), std),
                 "lm_head": ((H, c.vocab_size), std),
                 "norm_f_w": ((H,), "ones")}
        for name in _SUBLAYER:
            shape, init = sub[name]
            table[f"layers_{name}"] = ((L, 2) + shape, init)
        for name in _ROUTER + _EXPERTS:
            shape, init = branch[name]
            table[f"layers_{name}"] = ((L,) + shape, init)
        return table

    def cache_spec(self) -> CacheSpec:
        """One leaf: a latent row per token per SUBLAYER, the two of layer
        ``l`` at rows ``2 l`` and ``2 l + 1`` of the stack's pool."""
        c = self.config
        return CacheSpec(
            pools=(CacheLeaf(2 * c.num_layers, (c.latent_row,),
                             str(jnp.dtype(c.compute_dtype))),),
            layout="latent", tick_stats=TICK_STATS)

    # ------------------------------------------------------ pure functions

    def _stack(self, params):
        """(the router's two, which a layer scan slices a layer at a time;
        the sublayers' own stacks with their two leading axes made one,
        sublayer ``j`` of layer ``l`` at ``2 l + j``; the experts' three
        stacks) — the last two whole: a layer indexes them itself.  Sliced
        by the scan a sublayer's weights would arrive as a pair, and a
        pair that two products each read half of is a buffer: 1.27 GB
        copied out of the stacks a layer a round (PERF.md section 6,
        PR 44)."""
        flat = lambda w: w.reshape((-1,) + w.shape[2:])
        return ({n: params[f"layers_{n}"] for n in _ROUTER},
                {n: flat(params[f"layers_{n}"]) for n in _SUBLAYER},
                tuple(params[f"layers_{n}"] for n in _EXPERTS))

    def _mla_in(self, sub, x, pos):
        """(q_nope, q_r, the row to cache) of ``mla_in`` under this
        model's two scale factors."""
        c = self.config
        return mla_in(c, sub, x, pos, c.q_scale, c.kv_scale,
                      c.lora_norm_eps)[:3]

    def _branch(self, sl, experts, layer, m, valid=None):
        """The shortcut expert branch of layer ``layer`` on m (T, H): (s
        (T, H) float32 — the held experts' partial sum and the
        zero-compute experts' term —, (rows a held expert computed (Eh,),
        pairs routed to real experts, pairs routed to zero-compute ones),
        the latter two over the rows ``valid`` keeps).  ``experts``: the
        three expert stacks, whole."""
        c = self.config
        with jax.named_scope("router"):
            idx, w = route_softmax_topk(
                m, sl["router_w"], c.moe_topk, c.routed_scaling_factor,
                sl["router_bias"])
            real = idx < c.n_routed_experts
            if valid is not None:
                real = real & valid[:, None]
            real_pairs = jnp.sum(real, dtype=jnp.int32)
        routed, rows = held_experts_ffn(
            m, idx, w, *experts, c.experts_held.start, valid,
            n_real=c.n_routed_experts, layer=layer)
        zero_pairs = jnp.int32(0)
        if c.zero_expert_num:
            ident, zero_pairs = identity_experts(
                m, idx, w, c.n_routed_experts, valid)
            routed = routed + ident
        return routed, (rows, real_pairs, zero_pairs)

    def _layer(self, sl, subs, experts, layer, x, cache, attend, valid=None):
        """Layer ``layer`` on a flat x (T, H); ``sl``, ``subs``,
        ``experts`` as ``_stack`` gives them.  ``attend(j, sub, x, cache)
        -> (x, cache)`` is sublayer ``j``'s attention with its residual,
        over whatever cache the caller keeps; the rest is here: N_post and
        the dense MLP of each sublayer, the branch leaving on sublayer 0's
        normed input and landing with sublayer 1's MLP.  Returns (x,
        cache, the branch's counts)."""
        s = counts = None
        for j in (0, 1):
            sub = {n: jax.lax.dynamic_index_in_dim(
                w, 2 * layer + j, 0, keepdims=False) for n, w in subs.items()}
            with jax.named_scope("attn"):
                x, cache = attend(j, sub, x, cache)
            with jax.named_scope("mlp"):
                m = rms_norm(x, sub["ln3_w"], self.config.rms_norm_eps)
                if j == 0:
                    s, counts = self._branch(sl, experts, layer, m, valid)
                with jax.named_scope("dense_ffn"):
                    f = gated_mlp(m, sub["gate_w"], sub["up_w"],
                                  sub["down_w"])
                x = x + f if j == 0 else (
                    x.astype(jnp.float32) + f.astype(jnp.float32)
                    + s).astype(x.dtype)
        return x, cache, counts

    def decode_logits(self, params, h):
        """Final norm and the untied head: float32 logits."""
        with jax.named_scope("head"):
            dt = jnp.dtype(self.config.compute_dtype)
            h = rms_norm(h.astype(dt), params["norm_f_w"],
                         self.config.rms_norm_eps)
            return (h @ params["lm_head"].astype(dt)).astype(jnp.float32)

    # ------------------------------------------------------- ragged serving

    def _embed_ragged(self, params, toks, row_seq, row_pos, pad_lens):
        """A plain lookup (positions enter in the attention): (1, T, H)."""
        with jax.named_scope("embed"):
            return jnp.take(params["wte"], toks, axis=0)[None].astype(
                jnp.dtype(self.config.compute_dtype))

    ragged_narrow_rounds = True

    def _layer_ragged(self, sl, subs, experts, x, pool, layer, table,
                      row_seq, row_pos, pad_lens):
        """One layer for a flattened pack x (T, H) over the stack's latent
        pool (2 L, NB+1, bs, W), carried whole: each sublayer writes its
        rows' latents at pool row ``2 * layer + j`` and attends them
        (absorbed) in place.  Returns (x, pool, the branch's counts)."""
        c = self.config
        seq = jnp.clip(row_seq, 0, pad_lens.shape[0] - 1)
        pos = jnp.maximum(row_pos - pad_lens[seq], 0)

        def attend(j, sub, x, pool):
            at = 2 * layer + j
            w_k, w_v = mla_kv_b(c, sub, x.dtype)
            q_nope, q_r, latent = self._mla_in(sub, x, pos)
            q_abs = jnp.einsum("thd,rhd->thr", q_nope, w_k)
            pool = ragged_write(pool, latent, table, row_seq, row_pos,
                                layer=at)
            o_lat = ragged_latent_attention(
                q_abs, q_r, pool, table, row_seq, row_pos, pad_lens,
                scale=mla_softmax_scale(c), layer=at)
            return mla_out(c, sub, x, jnp.einsum(
                "thr,rhd->thd", o_lat, w_v)), pool

        return self._layer(sl, subs, experts, layer, x, pool, attend,
                           valid=row_pos >= 0)

    def decode_ragged(self, params, h, pools, table, row_seq, row_pos,
                      pad_lens):
        """The stack for one mixed ragged tick: h (1, T, H); ``pools`` the
        one entry of ``cache_spec()``.  Returns (h, pools, stats):
        ``stats`` int32 in the order of the spec's ``tick_stats`` — pairs
        the held experts computed (summed over the layers), the fullest
        single expert of any layer, the pairs routed to REAL experts (held
        here or not) and those routed to zero-compute experts, both over
        the pack's real rows and the layers: they add to real rows x
        ``moe_topk`` x layers."""
        (pool,) = pools
        stacked, subs, experts = self._stack(params)

        def body(carry, xs):
            sl, i = xs
            x, pool, counts = self._layer_ragged(
                sl, subs, experts, *carry, i, table, row_seq, row_pos,
                pad_lens)
            return (x, pool), counts

        with jax.named_scope("layers"):
            (x, pool), (rows, real, zero) = jax.lax.scan(
                body, (h[0], pool),
                (stacked, jnp.arange(self.config.num_layers)))
        stats = jnp.stack([jnp.sum(rows), jnp.max(rows, initial=0),
                           jnp.sum(real), jnp.sum(zero)]).astype(jnp.int32)
        return x[None], (pool,), stats

    # ------------------------------------- dense cache: prefill / generate
    # (the mixin's generate(): a plain contiguous cache, for tests and
    # small runs; the serving engines use the ragged path above)

    def init_cache(self, batch_size: int, max_len: int):
        return build_pools(self.cache_spec(), (batch_size, max_len))

    def _prefill_embed(self, params, input_ids, pad_lens):
        return self._embed_ragged(params, input_ids, None, None, None)[0]

    def _embed_one(self, params, tok, t, pad_lens=None):
        return self._embed_ragged(params, tok[:, None], None, None, None)[0]

    def _run_dense(self, params, x, caches, t0, pad_lens):
        """The stack over x (B, k, H) written at cache slots
        [t0, t0 + k): the body of ``prefill`` and ``decode_step``."""
        c = self.config
        B, k, H = x.shape
        if pad_lens is None:
            pad_lens = jnp.zeros((B,), jnp.int32)
        pos = jnp.maximum(t0 + jnp.arange(k)[None, :] - pad_lens[:, None], 0)
        (cache,) = caches                           # (2 L, B, Lmax, W)
        stacked, subs, experts = self._stack(params)

        def attend(j, sub, x, pair):
            x = x.reshape(B, k, H)
            q_nope, q_r, latent = self._mla_in(sub, x, pos)
            lat = jax.lax.dynamic_update_slice_in_dim(
                pair[j], latent.astype(pair.dtype), t0, axis=1)
            x = mla_out(c, sub, x, mla_attend_dense(
                c, sub, x, lat, q_nope, q_r, t0, pad_lens))
            return x.reshape(B * k, H), pair.at[j].set(lat)

        def body(x, xs):
            sl, i, pair = xs                        # (2, B, Lmax, W)
            x, pair, _ = self._layer(sl, subs, experts, i, x, pair, attend)
            return x, pair

        with jax.named_scope("layers"):
            x, cache = jax.lax.scan(
                body, x.reshape(B * k, H),
                (stacked, jnp.arange(c.num_layers),
                 cache.reshape((c.num_layers, 2) + cache.shape[1:])))
        return x.reshape(B, k, H), (cache.reshape((-1,) + cache.shape[2:]),)

    def prefill(self, params, input_ids, max_len: int, pad_lens=None,
                mesh=None):
        """The prompt through the stack: (h (B, P, H), caches filled at
        [0, P)).  Left-padded prompts shift the rotary positions and mask
        the pad keys."""
        B, P = input_ids.shape
        x = self._prefill_embed(params, input_ids, pad_lens)
        return self._run_dense(params, x, self.init_cache(B, max_len), 0,
                               pad_lens)

    def decode_step(self, params, h, caches, t, pad_lens=None):
        """One token per row at cache slot ``t`` (a scalar)."""
        return self._run_dense(params, h, caches, t, pad_lens)

    def forward(self, input_ids):
        """float32 logits (B, L, V) of a full causal pass."""
        raw = getattr(input_ids, "_data", input_ids)
        params = {n: p._data for n, p in self.named_parameters()}
        h, _ = self.prefill(params, raw, raw.shape[1])
        return self.decode_logits(params, h)
