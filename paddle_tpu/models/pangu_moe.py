"""openPangu-Ultra-MoE family (``model_type: pangu_ultra_moe``): multi-head
latent attention, sandwich RMSNorm, rotary positions, gated-SiLU MLPs, a
sigmoid router over many experts of which this process holds a range, an
untied head — for SERVING through the ragged paged engine.

Layer (Pangu Ultra MoE config.json; MLA as DeepSeek-V2 §2.1, whose key
names the config uses; sandwich norm as Pangu Ultra, arXiv:2504.07866):

    x = x + N2(MLA(N1(x)));  x = x + N4(F(N3(x)))

``F`` is a dense gated MLP in the ``first_k_dense_replace`` leading layers
and the expert layer after them, so the model is TWO stacks, each scanned
(``dense_*`` then ``moe_*`` parameters).  What is cached per token per
layer is one latent row ``[N(c_kv) ; rope(k_r)]`` of ``kv_lora_rank +
qk_rope_head_dim`` numbers, stored in a row of the next multiple of 128
columns (``cache_spec()``: one entry per stack, layout "latent"); every row that reads the cache — prefill chunk or decode —
attends in the absorbed form (ops/ragged_latent_attention.py).

The share.  ``experts_held`` (a ``range``) beside ``n_routed_experts``
(the router's width): the expert layer routes over all experts, top-k,
and computes the part of the sum that its own experts contribute, plus
the shared expert.  On one chip nothing is exchanged and nothing stands
in for the absent chips: the partial sum is the layer's output here.
With ``experts_held = range(n_routed_experts)`` it is the whole layer.

The multi-token-prediction module of the published model is not built: it
does not enter the main model's logits.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Parameter
from ..nn.layer.base import Layer
from ..ops.moe import gated_mlp, held_experts_ffn, route_sigmoid_topk
from ._decode import (CacheLeaf, CacheSpec, CausalDecoderMixin, build_pools,
                      ragged_latent_attention, ragged_write)

_MLA = ("ln1_w", "q_a_w", "q_a_norm_w", "q_b_w", "kv_a_w", "kv_a_norm_w",
        "kv_b_w", "o_w", "ln2_w", "ln3_w", "ln4_w")
_STACKS = {
    "dense": _MLA + ("gate_w", "up_w", "down_w"),
    "moe": _MLA + ("router_w", "e_gate_w", "e_up_w", "e_down_w",
                   "s_gate_w", "s_up_w", "s_down_w"),
}
TICK_STATS = ("expert_rows", "expert_rows_max", "expert_pairs")


class PanguMoeConfig:
    def __init__(self, vocab_size=153600, hidden_size=7680,
                 num_hidden_layers=61, first_k_dense_replace=3,
                 num_attention_heads=128, q_lora_rank=1536,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 n_routed_experts=256, n_shared_experts=1,
                 num_experts_per_tok=8, routed_scaling_factor=2.5,
                 norm_topk_prob=True, rms_norm_eps=1e-5,
                 rope_theta=25600000.0, max_position_embeddings=131072,
                 initializer_range=0.02, compute_dtype="bfloat16",
                 experts_held: Optional[range] = None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
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
                f"[0, {n_routed_experts}), got {experts_held!r}")
        if not 0 <= first_k_dense_replace <= num_hidden_layers:
            raise ValueError("first_k_dense_replace outside the stack")
        self.experts_held = held

    @property
    def num_layers(self):
        return self.num_hidden_layers

    @property
    def num_expert_layers(self):
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self):
        """Columns of a cached row: ``latent_width`` and zeros up to the
        next multiple of 128.  A tiled device layout pads a row to whole
        128-lane tiles whatever its logical width; stating the padded
        width keeps the pool's default layout row-major, which is the
        layout the kernel's block DMAs need (at the logical 576 the
        compiler stores the pool block-minor and transposes the whole of
        it in and out of every kernel call)."""
        return -(-self.latent_width // 128) * 128


class PanguMoeModel(CausalDecoderMixin, Layer):
    """Two stacks of sandwich-norm MLA blocks; parameters stacked over the
    layers of their stack (``dense_*`` / ``moe_*``)."""

    def __init__(self, config: PanguMoeConfig):
        super().__init__()
        self.config = c = config
        from ..nn.initializer import Normal
        for name, (shape, init) in self.param_table(c).items():
            data = jnp.ones(shape, jnp.float32) if init == "ones" \
                else Normal(0.0, init)(list(shape), "float32")
            self.add_parameter(name, Parameter(data, name=name))

    @staticmethod
    def param_table(c: PanguMoeConfig):
        """name -> (shape, standard deviation | "ones"): the program's
        parameter dictionary (``initializer_range`` normal weights, norms
        at one; no bias anywhere)."""
        H, nh = c.hidden_size, c.num_attention_heads
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        std = c.initializer_range
        mla = {
            "ln1_w": ((H,), "ones"), "ln2_w": ((H,), "ones"),
            "ln3_w": ((H,), "ones"), "ln4_w": ((H,), "ones"),
            "q_a_w": ((H, c.q_lora_rank), std),
            "q_a_norm_w": ((c.q_lora_rank,), "ones"),
            "q_b_w": ((c.q_lora_rank, nh * qk), std),
            "kv_a_w": ((H, c.latent_width), std),
            "kv_a_norm_w": ((c.kv_lora_rank,), "ones"),
            "kv_b_w": ((c.kv_lora_rank,
                        nh * (c.qk_nope_head_dim + c.v_head_dim)), std),
            "o_w": ((nh * c.v_head_dim, H), std),
        }
        I, F, Eh = c.intermediate_size, c.moe_intermediate_size, \
            len(c.experts_held)
        Fs = F * c.n_shared_experts
        own = {
            "dense": {"gate_w": ((H, I), std), "up_w": ((H, I), std),
                      "down_w": ((I, H), std)},
            "moe": {"router_w": ((H, c.n_routed_experts), std),
                    "e_gate_w": ((Eh, H, F), std),
                    "e_up_w": ((Eh, H, F), std),
                    "e_down_w": ((Eh, F, H), std),
                    "s_gate_w": ((H, Fs), std), "s_up_w": ((H, Fs), std),
                    "s_down_w": ((Fs, H), std)},
        }
        layers = {"dense": c.first_k_dense_replace,
                  "moe": c.num_expert_layers}
        table = {"wte": ((c.vocab_size, H), std),
                 "lm_head": ((H, c.vocab_size), std),
                 "norm_f_w": ((H,), "ones")}
        for stack, n in layers.items():
            for name, (shape, init) in {**mla, **own[stack]}.items():
                table[f"{stack}_{name}"] = ((n,) + shape, init)
        return table

    @staticmethod
    def stacked_param_names(stack: Optional[str] = None):
        """Parameters with a leading layer axis: of one stack ("dense",
        "moe"), or of both."""
        stacks = _STACKS if stack is None else {stack: _STACKS[stack]}
        return [f"{s}_{n}" for s, names in stacks.items() for n in names]

    def cache_spec(self) -> CacheSpec:
        c = self.config
        dt = str(jnp.dtype(c.compute_dtype))
        return CacheSpec(
            pools=(CacheLeaf(c.first_k_dense_replace, (c.latent_row,), dt),
                   CacheLeaf(c.num_expert_layers, (c.latent_row,), dt)),
            layout="latent", tick_stats=TICK_STATS)

    # ------------------------------------------------------ pure functions

    def _rms(self, x, w):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, -1, keepdims=True) + self.config.rms_norm_eps)
        return (y * w.astype(jnp.float32)).astype(x.dtype)

    def _rope(self, x, pos):
        """Rotate-half rotary positions over the last axis of x (..., D)
        at positions ``pos`` (broadcast against x's leading axes but the
        last two: x is (..., heads, D) and pos (...,))."""
        D = x.shape[-1]
        inv = self.config.rope_theta ** (
            -jnp.arange(0, D, 2, dtype=jnp.float32) / D)
        ang = pos.astype(jnp.float32)[..., None, None] * inv   # (..,1,D/2)
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x32 = x.astype(jnp.float32)
        x1, x2 = x32[..., :D // 2], x32[..., D // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1).astype(x.dtype)

    def _stack(self, params, stack):
        return {n: params[f"{stack}_{n}"] for n in _STACKS[stack]}

    def _mla_in(self, sl, x, pos):
        """N1 and the MLA projections of x (..., H) at logical positions
        ``pos`` (...,): q_nope (..., nh, nope), q_r (..., nh, rope) after
        rotation, and the row to cache (..., latent_row): c_kv, k_r, zeros."""
        c = self.config
        dt = x.dtype
        nh, R = c.num_attention_heads, c.kv_lora_rank
        a = self._rms(x, sl["ln1_w"])
        c_q = self._rms(a @ sl["q_a_w"].astype(dt), sl["q_a_norm_w"])
        q = (c_q @ sl["q_b_w"].astype(dt)).reshape(
            x.shape[:-1] + (nh, c.qk_nope_head_dim + c.qk_rope_head_dim))
        q_nope, q_r = q[..., :c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:]
        kv = a @ sl["kv_a_w"].astype(dt)
        c_kv = self._rms(kv[..., :R], sl["kv_a_norm_w"])
        k_r = self._rope(kv[..., None, R:], pos)[..., 0, :]
        pad = jnp.zeros(x.shape[:-1] + (c.latent_row - c.latent_width,), dt)
        return q_nope, self._rope(q_r, pos), \
            jnp.concatenate([c_kv, k_r, pad], -1)

    def _kv_b(self, sl, dt):
        """W_kvb as (R, nh, nope) for keys and (R, nh, v) for values."""
        c = self.config
        w = sl["kv_b_w"].astype(dt).reshape(
            c.kv_lora_rank, c.num_attention_heads,
            c.qk_nope_head_dim + c.v_head_dim)
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    @property
    def _scale(self):
        c = self.config
        return float(c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5

    def _mla_out(self, sl, x, o):
        """Heads concatenated, W_o, N2, residual: o (..., nh, v)."""
        o = o.reshape(o.shape[:-2] + (-1,))
        return x + self._rms(o @ sl["o_w"].astype(x.dtype), sl["ln2_w"])

    def _ffn(self, sl, x, expert: bool, valid=None):
        """N3, F, N4, residual on x (T, H); (x, rows a held expert
        computed (Eh,) or None)."""
        c = self.config
        with jax.named_scope("mlp"):
            m = self._rms(x, sl["ln3_w"])
            if not expert:
                return x + self._rms(gated_mlp(
                    m, sl["gate_w"], sl["up_w"], sl["down_w"]),
                    sl["ln4_w"]), None
            with jax.named_scope("router"):
                idx, w = route_sigmoid_topk(
                    m, sl["router_w"], c.num_experts_per_tok,
                    c.routed_scaling_factor, c.norm_topk_prob)
            routed, rows = held_experts_ffn(
                m, idx, w, sl["e_gate_w"], sl["e_up_w"], sl["e_down_w"],
                c.experts_held.start, valid)
            with jax.named_scope("shared_expert"):
                shared = gated_mlp(m, sl["s_gate_w"], sl["s_up_w"],
                                   sl["s_down_w"])
            f = (routed + shared.astype(jnp.float32)).astype(x.dtype)
            return x + self._rms(f, sl["ln4_w"]), rows

    def decode_logits(self, params, h):
        """Final norm and the untied head: float32 logits."""
        with jax.named_scope("head"):
            dt = jnp.dtype(self.config.compute_dtype)
            h = self._rms(h.astype(dt), params["norm_f_w"])
            return (h @ params["lm_head"].astype(dt)).astype(jnp.float32)

    # ------------------------------------------------------- ragged serving

    def _embed_ragged(self, params, toks, row_seq, row_pos, pad_lens):
        """A plain lookup (positions enter in the attention): (1, T, H)."""
        with jax.named_scope("embed"):
            return jnp.take(params["wte"], toks, axis=0)[None].astype(
                jnp.dtype(self.config.compute_dtype))

    @staticmethod
    def _rowwise(few, fn, *rows):
        """``fn(*rows) -> (row-wise outputs, anything else)`` over arrays
        whose leading axis is the pack's rows.  ``few`` is None (never),
        or ``(n, flag)``: where the traced bool ``flag`` says that every
        real row lies in the first ``n``, ``fn`` runs over those rows
        alone and its row-wise outputs are padded back with zeros.  The
        program's row count is the token budget, and at a budget of 2,048
        a round of 16 decode rows would pay a whole chunk's products.  The
        pools never pass through the ``cond`` (it would copy them): writes
        and the kernel take all the rows and skip the padding themselves."""
        if few is None:
            return fn(*rows)
        n, flag = few
        T = rows[0].shape[0]

        def first(*rows):
            out, rest = fn(*(r[:n] for r in rows))
            return jax.tree.map(
                lambda o: jnp.pad(o, ((0, T - n),)
                                  + ((0, 0),) * (o.ndim - 1)), out), rest

        return jax.lax.cond(flag, first, fn, *rows)

    def _block_ragged(self, sl, x, pool, layer, table, row_seq, row_pos,
                      pad_lens, expert, few=None):
        """One block for a flattened pack x (T, H) over layer ``layer`` of
        its stack's latent pools (L, NB+1, bs, W): write each row's
        latent, then attend (absorbed) — both in place in the stack, which
        the scan carries whole (sliced per layer, the scan would hold the
        pools twice and copy a layer in and out every iteration)."""
        seq = jnp.clip(row_seq, 0, pad_lens.shape[0] - 1)
        pos = jnp.maximum(row_pos - pad_lens[seq], 0)
        w_k, w_v = self._kv_b(sl, x.dtype)

        def project(x, pos):
            q_nope, q_r, latent = self._mla_in(sl, x, pos)
            return (jnp.einsum("thd,rhd->thr", q_nope, w_k), q_r,
                    latent), ()

        def finish(x, o_lat, valid):
            with jax.named_scope("attn"):
                x = self._mla_out(sl, x,
                                  jnp.einsum("thr,rhd->thd", o_lat, w_v))
            x, rows = self._ffn(sl, x, expert, valid=valid)
            return (x,), rows

        with jax.named_scope("attn"):
            (q_abs, q_r, latent), _ = self._rowwise(few, project, x, pos)
            pool = ragged_write(pool, latent, table, row_seq, row_pos,
                                layer=layer)
            o_lat = ragged_latent_attention(
                q_abs, q_r, pool, table, row_seq, row_pos, pad_lens,
                scale=self._scale, layer=layer)
        (x,), rows = self._rowwise(few, finish, x, o_lat, row_pos >= 0)
        return x, pool, rows

    def decode_ragged(self, params, h, pools, table, row_seq, row_pos,
                      pad_lens):
        """Both stacks for one mixed ragged tick: h (1, T, H); ``pools``
        the two latent pools of ``cache_spec()``, stacked over their
        stack's layers.  Returns (h, pools, stats): ``stats`` int32 (3,)
        in the order of ``TICK_STATS`` — pairs the held experts computed
        (summed over the expert layers), the fullest single expert of any
        layer, and the pairs routed in all (real rows x top-k x expert
        layers)."""
        c = self.config
        x = h[0]
        # a round of decode rows only has at most one real row a slot
        # (``pad_lens`` has a row a slot), and the engine packs real rows
        # first: where the program is over twice that wide, see
        # ``_rowwise``.  What is observed is the pack, so a round with
        # real rows further back takes the whole-width branch
        slots = pad_lens.shape[0]
        few = (slots, jnp.all(row_pos[slots:] < 0)) \
            if x.shape[0] > 2 * slots else None
        out_pools, rows = [], None
        with jax.named_scope("layers"):
            for stack, pool in zip(("dense", "moe"), pools):
                def body(carry, xs, expert=stack == "moe"):
                    sl, i = xs
                    y, p, r = self._block_ragged(
                        sl, carry[0], carry[1], i, table, row_seq, row_pos,
                        pad_lens, expert, few)
                    return (y, p), r
                (x, pool), r = jax.lax.scan(
                    body, (x, pool), (self._stack(params, stack),
                                      jnp.arange(pool.shape[0])))
                out_pools.append(pool)
                rows = r if stack == "moe" else rows        # (Le, Eh)
        pairs = jnp.sum(row_pos >= 0) * (c.num_experts_per_tok
                                         * c.num_expert_layers)
        stats = jnp.stack([jnp.sum(rows), jnp.max(rows, initial=0),
                           pairs]).astype(jnp.int32)
        return x[None], tuple(out_pools), stats

    # ------------------------------------- dense cache: prefill / generate
    # (the mixin's generate(): a plain contiguous cache, for tests and
    # small runs; the serving engines use the ragged path above)

    def init_cache(self, batch_size: int, max_len: int):
        return build_pools(self.cache_spec(), (batch_size, max_len))

    def _prefill_embed(self, params, input_ids, pad_lens):
        return self._embed_ragged(params, input_ids, None, None, None)[0]

    def _embed_one(self, params, tok, t, pad_lens=None):
        return self._embed_ragged(params, tok[:, None], None, None, None)[0]

    def _attend_dense(self, sl, x, cache, q_nope, q_r, t0, pad_lens):
        """Absorbed attention of x's rows (B, k, ...) at cache slots
        [t0, t0 + k) over a dense latent cache (B, Lmax, R + rope)."""
        c = self.config
        R, W = c.kv_lora_rank, c.latent_width
        w_k, w_v = self._kv_b(sl, x.dtype)
        q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_k)
        sc = jnp.einsum("bqhr,bkr->bhqk", q_abs, cache[..., :R],
                        preferred_element_type=jnp.float32) \
            + jnp.einsum("bqhd,bkd->bhqk", q_r, cache[..., R:W],
                         preferred_element_type=jnp.float32)
        k = jnp.arange(cache.shape[1])
        mask = k[None, None, :] <= (t0 + jnp.arange(x.shape[1]))[None, :, None]
        mask = mask & (k[None, None, :] >= pad_lens[:, None, None])
        sc = jnp.where(mask[:, None], sc * self._scale, -1e30)
        p = jax.nn.softmax(sc, -1).astype(x.dtype)
        o_lat = jnp.einsum("bhqk,bkr->bqhr", p, cache[..., :R])
        return jnp.einsum("bqhr,rhd->bqhd", o_lat, w_v)

    def _run_dense(self, params, x, caches, t0, pad_lens):
        """Both stacks over x (B, k, H) written at cache slots
        [t0, t0 + k): the body of ``prefill`` and ``decode_step``."""
        B, k, H = x.shape
        if pad_lens is None:
            pad_lens = jnp.zeros((B,), jnp.int32)
        pos = jnp.maximum(t0 + jnp.arange(k)[None, :] - pad_lens[:, None], 0)
        out = []
        with jax.named_scope("layers"):
            for stack, cache in zip(("dense", "moe"), caches):
                def body(carry, xs, expert=stack == "moe"):
                    sl, ch = xs
                    with jax.named_scope("attn"):
                        q_nope, q_r, latent = self._mla_in(sl, carry, pos)
                        ch = jax.lax.dynamic_update_slice_in_dim(
                            ch, latent.astype(ch.dtype), t0, axis=1)
                        y = self._mla_out(sl, carry, self._attend_dense(
                            sl, carry, ch, q_nope, q_r, t0, pad_lens))
                    y, _ = self._ffn(sl, y.reshape(B * k, H), expert)
                    return y.reshape(B, k, H), ch
                x, cache = jax.lax.scan(
                    body, x, (self._stack(params, stack), cache))
                out.append(cache)
        return x, tuple(out)

    def prefill(self, params, input_ids, max_len: int, pad_lens=None,
                mesh=None):
        """The prompt through both stacks: (h (B, P, H), caches filled at
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
