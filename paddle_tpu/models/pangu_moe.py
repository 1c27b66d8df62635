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

DeepSeek-V3.2-Exp (``model_type: deepseek_v32``) is the same class under
its own published keys, each a switch the Pangu file lacks:
``sandwich_norm`` false (no norm on a sublayer's output: two norms a
layer); ``rope_scaling`` of type "yarn" (the rotary frequencies blended
per frequency, and the softmax scale times ``m**2``); ``n_group`` /
``topk_group`` / ``topk_method`` "noaux_tc" (group-limited routing with a
selection bias, ``ops/moe.route_sigmoid_topk``); ``index_topk`` /
``index_n_heads`` / ``index_head_dim`` (a lightning indexer in every
layer: its own projections of the MLA's ``c_q`` and normed input, a
LayerNorm on its one shared key, rotary on the first
``qk_rope_head_dim`` of its columns, signed head weights; DeepSeek-V3.2-Exp
report, DSA).  With an indexer a stack caches TWO leaves on one table —
the latent row and the indexer's key, ``index_head_dim`` numbers: one
128-lane tile — and a row attends its ``min(index_topk, context)``
highest-scored positions, exactly (``_decode.ragged_index_select``,
``ragged_sparse_latent_attention``); a program whose table holds no more
than ``index_topk`` positions attends everything through the accepted
kernel and only writes the indexer's keys.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Parameter
from ..nn.layer.base import Layer
from ..ops.moe import gated_mlp, held_experts_ffn, route_sigmoid_topk
from ._decode import (CacheLeaf, CacheSpec, CausalDecoderMixin, build_pools,
                      ragged_index_select, ragged_latent_attention,
                      ragged_sparse_latent_attention, ragged_write, rms_norm)
from ._mla import (MlaGeometry, mla_attend_dense, mla_in, mla_kv_b, mla_out,
                   mla_rope, mla_softmax_scale)
from ._mla import yarn_inv_freq, yarn_mscale  # noqa: F401  (their old home)

_MLA = ("ln1_w", "q_a_w", "q_a_norm_w", "q_b_w", "kv_a_w", "kv_a_norm_w",
        "kv_b_w", "o_w", "ln2_w", "ln3_w", "ln4_w")
_STACKS = {
    "dense": _MLA + ("gate_w", "up_w", "down_w"),
    "moe": _MLA + ("router_w", "e_gate_w", "e_up_w", "e_down_w",
                   "s_gate_w", "s_up_w", "s_down_w"),
}
# never sliced by a layer scan: the grouped products read a layer's experts
# in place in the whole stack (ops/moe.py held_experts_ffn)
_EXPERTS = ("e_gate_w", "e_up_w", "e_down_w")
_SANDWICH = ("ln2_w", "ln4_w")      # only under ``sandwich_norm``
_INDEXER = ("idx_q_b_w", "idx_k_w", "idx_k_norm_w", "idx_k_norm_b",
            "idx_w_w")              # only with ``index_topk``
_ROUTER_BIAS = "router_bias"        # only with ``topk_method: noaux_tc``
TICK_STATS = ("expert_rows", "expert_rows_max", "expert_pairs")
INDEX_STATS = ("index_candidates", "index_selected")


class PanguMoeConfig(MlaGeometry):
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
                 experts_held: Optional[range] = None,
                 sandwich_norm=True, rope_scaling: Optional[dict] = None,
                 n_group=1, topk_group=1, topk_method=None,
                 index_topk: Optional[int] = None, index_n_heads=64,
                 index_head_dim=128):
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
        self.sandwich_norm = bool(sandwich_norm)
        if rope_scaling is not None and \
                rope_scaling.get("type", "yarn") != "yarn":
            raise ValueError(f"rope_scaling {rope_scaling.get('type')!r}: "
                             f"only yarn is written")
        self.rope_scaling = rope_scaling
        if n_routed_experts % n_group or not 1 <= topk_group <= n_group:
            raise ValueError("n_group must divide n_routed_experts and "
                             "topk_group lie in [1, n_group]")
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        if topk_method not in (None, "noaux_tc"):
            raise ValueError(f"topk_method {topk_method!r}: only noaux_tc "
                             f"(a selection bias) is written")
        self.topk_method = topk_method
        self.index_topk = None if index_topk is None else int(index_topk)
        self.index_n_heads = int(index_n_heads)
        self.index_head_dim = int(index_head_dim)
        if self.index_topk is not None \
                and not qk_rope_head_dim <= index_head_dim:
            raise ValueError("the indexer's rotary columns exceed its head")

    @property
    def num_layers(self):
        return self.num_hidden_layers

    @property
    def num_expert_layers(self):
        return self.num_hidden_layers - self.first_k_dense_replace

    def stack_names(self, stack):
        """The parameter names of one stack ("dense", "moe") under this
        configuration's switches, without the stack's prefix."""
        names = _STACKS[stack]
        if not self.sandwich_norm:
            names = tuple(n for n in names if n not in _SANDWICH)
        if self.index_topk is not None:
            names += _INDEXER
        if stack == "moe" and self.topk_method == "noaux_tc":
            names += (_ROUTER_BIAS,)
        return names


class PanguMoeModel(CausalDecoderMixin, Layer):
    """Two stacks of MLA blocks; parameters stacked over the layers of
    their stack (``dense_*`` / ``moe_*``)."""

    def __init__(self, config: PanguMoeConfig):
        super().__init__()
        self.config = c = config
        from ..nn.initializer import Normal
        for name, (shape, init) in self.param_table(c).items():
            data = jnp.full(shape, float(init == "ones"), jnp.float32) \
                if isinstance(init, str) \
                else Normal(0.0, init)(list(shape), "float32")
            self.add_parameter(name, Parameter(data, name=name))

    @staticmethod
    def param_table(c: PanguMoeConfig):
        """name -> (shape, standard deviation | "ones" | "zeros"): the
        program's parameter dictionary (``initializer_range`` normal
        weights, norm scales at one, the indexer's LayerNorm bias and the
        router's selection bias at zero)."""
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
        Di, nhi = c.index_head_dim, c.index_n_heads
        mla.update({        # the indexer; its LayerNorm has scale and bias
            "idx_q_b_w": ((c.q_lora_rank, nhi * Di), std),
            "idx_k_w": ((H, Di), std), "idx_k_norm_w": ((Di,), "ones"),
            "idx_k_norm_b": ((Di,), "zeros"), "idx_w_w": ((H, nhi), std)})
        # the selection bias is a trained buffer: a checkpoint brings it
        own["moe"][_ROUTER_BIAS] = ((c.n_routed_experts,), "zeros")
        layers = {"dense": c.first_k_dense_replace,
                  "moe": c.num_expert_layers}
        table = {"wte": ((c.vocab_size, H), std),
                 "lm_head": ((H, c.vocab_size), std),
                 "norm_f_w": ((H,), "ones")}
        for stack, n in layers.items():
            every = {**mla, **own[stack]}
            for name in c.stack_names(stack):
                shape, init = every[name]
                table[f"{stack}_{name}"] = ((n,) + shape, init)
        return table

    def stacked_param_names(self, stack: Optional[str] = None):
        """Parameters with a leading layer axis: of one stack ("dense",
        "moe"), or of both."""
        return [f"{s}_{n}" for s in ((stack,) if stack else _STACKS)
                for n in self.config.stack_names(s)]

    def cache_spec(self) -> CacheSpec:
        c = self.config
        dt = str(jnp.dtype(c.compute_dtype))
        stacks = (c.first_k_dense_replace, c.num_expert_layers)
        if c.index_topk is None:
            return CacheSpec(
                pools=tuple(CacheLeaf(n, (c.latent_row,), dt)
                            for n in stacks),
                layout="latent", tick_stats=TICK_STATS)
        # a stack's entry is a pair: the latent row and the indexer's key
        return CacheSpec(
            pools=tuple((CacheLeaf(n, (c.latent_row,), dt),
                         CacheLeaf(n, (c.index_head_dim,), dt))
                        for n in stacks),
            layout="latent", tick_stats=TICK_STATS + INDEX_STATS)

    # ------------------------------------------------------ pure functions

    def _rms(self, x, w):
        return rms_norm(x, w, self.config.rms_norm_eps)

    def _stack(self, params, stack):
        """(the stack's parameters that a layer scan slices a layer at a
        time, the expert stacks whole — () for the dense stack): a layer
        indexes its experts itself.  Sliced by the scan they are copied
        out of their stacks a layer a round, 1.4-1.5 GB at the latent
        cells' widths (PERF.md section 6, PR 46)."""
        names = self.config.stack_names(stack)
        return ({n: params[f"{stack}_{n}"] for n in names
                 if n not in _EXPERTS},
                tuple(params[f"{stack}_{n}"] for n in _EXPERTS
                      if n in names))

    def _mla_in(self, sl, x, pos):
        """``mla_in`` (models/_mla.py) of x (..., H) at logical positions
        ``pos`` (...,): q_nope, q_r after rotation, the row to cache.
        With an indexer, what it projects follows: (q_idx, w_idx, k_idx)
        of ``_index_in``."""
        *out, a, c_q = mla_in(self.config, sl, x, pos)
        if self.config.index_topk is not None:
            with jax.named_scope("indexer"):
                out += self._index_in(sl, a, c_q, pos)
        return tuple(out)

    def _index_in(self, sl, a, c_q, pos):
        """The lightning indexer's side of a row: q_idx (..., nhi, Di)
        from the MLA's ``c_q``; w_idx (..., nhi) float32, the signed head
        weights ``a W_w / sqrt(nhi * Di)``; k_idx (..., Di), ``LayerNorm(a
        W_k)``, the row of the indexer's cache.  Rotary on the first
        ``qk_rope_head_dim`` columns of q_idx and k_idx."""
        c = self.config
        dt = a.dtype
        nhi, Di, Dr = c.index_n_heads, c.index_head_dim, c.qk_rope_head_dim
        q = (c_q @ sl["idx_q_b_w"].astype(dt)).reshape(
            a.shape[:-1] + (nhi, Di))
        q = jnp.concatenate([mla_rope(c, q[..., :Dr], pos), q[..., Dr:]], -1)
        k32 = (a @ sl["idx_k_w"].astype(dt)).astype(jnp.float32)
        mu = jnp.mean(k32, -1, keepdims=True)
        k32 = (k32 - mu) * jax.lax.rsqrt(
            jnp.mean((k32 - mu) ** 2, -1, keepdims=True) + 1e-6)
        k = (k32 * sl["idx_k_norm_w"].astype(jnp.float32)
             + sl["idx_k_norm_b"].astype(jnp.float32)).astype(dt)
        k = jnp.concatenate(
            [mla_rope(c, k[..., None, :Dr], pos)[..., 0, :], k[..., Dr:]], -1)
        w = (a @ sl["idx_w_w"].astype(dt)).astype(jnp.float32) \
            * float(nhi * Di) ** -0.5
        return q, w, k

    @property
    def _scale(self):
        return mla_softmax_scale(self.config)

    def _ffn(self, sl, experts, layer, x, valid=None):
        """N3, F, N4, residual on x (T, H) in layer ``layer`` of its
        stack; ``experts`` the stack's three expert stacks, whole, or ()
        where F is the dense MLP.  Returns (x, rows a held expert computed
        (Eh,) or None)."""
        c = self.config
        n4 = (lambda f: self._rms(f, sl["ln4_w"])) if c.sandwich_norm \
            else (lambda f: f)
        with jax.named_scope("mlp"):
            m = self._rms(x, sl["ln3_w"])
            if not experts:
                return x + n4(gated_mlp(
                    m, sl["gate_w"], sl["up_w"], sl["down_w"])), None
            with jax.named_scope("router"):
                grouped = {} if c.n_group == 1 and c.topk_method is None \
                    else dict(bias=sl.get(_ROUTER_BIAS), n_group=c.n_group,
                              topk_group=c.topk_group)
                idx, w = route_sigmoid_topk(
                    m, sl["router_w"], c.num_experts_per_tok,
                    c.routed_scaling_factor, c.norm_topk_prob, **grouped)
            routed, rows = held_experts_ffn(
                m, idx, w, *experts, c.experts_held.start, valid,
                layer=layer)
            with jax.named_scope("shared_expert"):
                shared = gated_mlp(m, sl["s_gate_w"], sl["s_up_w"],
                                   sl["s_down_w"])
            f = (routed + shared.astype(jnp.float32)).astype(x.dtype)
            return x + n4(f), rows

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

    def _block_ragged(self, sl, experts, x, pool, layer, table, row_seq,
                      row_pos, pad_lens):
        """One block for a flattened pack x (T, H) over layer ``layer`` of
        its stack's latent pools (L, NB+1, bs, W): write each row's
        latent, then attend (absorbed) — both in place in the stack, which
        the scan carries whole (sliced per layer, the scan would hold the
        pools twice and copy a layer in and out every iteration).  With an
        indexer ``pool`` is the stack's pair (latent rows, indexer keys):
        both are written, and where the table holds more positions than
        ``index_topk`` a row attends the positions the indexer selects.
        ``experts`` as ``_stack`` gives them.  No branch on the pack: a
        round of decode rows only runs the engine's narrow program
        (``ragged_narrow_rounds``).  Returns (x, pool, rows a held expert
        computed, the selection ``(scores, thr)`` or None)."""
        c = self.config
        seq = jnp.clip(row_seq, 0, pad_lens.shape[0] - 1)
        pos = jnp.maximum(row_pos - pad_lens[seq], 0)
        w_k, w_v = mla_kv_b(c, sl, x.dtype)
        chosen = None
        with jax.named_scope("attn"):
            q_nope, q_r, latent, *index = self._mla_in(sl, x, pos)
            q_abs = jnp.einsum("thd,rhd->thr", q_nope, w_k)
            if not index:
                pool = ragged_write(pool, latent, table, row_seq, row_pos,
                                    layer=layer)
                lat_pool = pool
            else:
                q_idx, w_idx, k_idx = index
                lat_pool, idx_pool = pool
                lat_pool = ragged_write(lat_pool, latent, table, row_seq,
                                        row_pos, layer=layer)
                idx_pool = ragged_write(idx_pool, k_idx, table, row_seq,
                                        row_pos, layer=layer)
                pool = (lat_pool, idx_pool)
                if table.shape[1] * idx_pool.shape[2] > c.index_topk:
                    chosen = ragged_index_select(
                        q_idx, w_idx, idx_pool, table, row_seq, row_pos,
                        pad_lens, k=c.index_topk, layer=layer)
            if chosen is None:
                o_lat = ragged_latent_attention(
                    q_abs, q_r, lat_pool, table, row_seq, row_pos, pad_lens,
                    scale=self._scale, layer=layer)
            else:
                o_lat = ragged_sparse_latent_attention(
                    q_abs, q_r, lat_pool, *chosen, table, row_seq, row_pos,
                    pad_lens, scale=self._scale, layer=layer)
            x = mla_out(c, sl, x, jnp.einsum("thr,rhd->thd", o_lat, w_v))
        x, rows = self._ffn(sl, experts, layer, x, valid=row_pos >= 0)
        return x, pool, rows, chosen

    @staticmethod
    def _selected(chosen, table, pool, lo, hi, at):
        """The mask a block's attention applied to pack rows ``at``: the
        indexer's choice, or every position in [lo, hi] where the program
        is too narrow for a choice."""
        from ..ops.index_select import selected
        if chosen is not None:
            return selected(chosen[0][at], chosen[1][at], lo, hi)
        width = table.shape[1] * jax.tree.leaves(pool)[0].shape[2]
        col = jnp.arange(width)[None, :]
        return (col >= lo[:, None]) & (col <= hi[:, None])

    def decode_ragged(self, params, h, pools, table, row_seq, row_pos,
                      pad_lens, selection_of=None):
        """Both stacks for one mixed ragged tick: h (1, T, H); ``pools``
        the entries of ``cache_spec()``, one a stack, stacked over its
        layers.  Returns (h, pools, stats): ``stats`` int32 in the order
        of the spec's ``tick_stats`` — pairs the held experts computed
        (summed over the expert layers), the fullest single expert of any
        layer, and the pairs routed in all (real rows x top-k x expert
        layers); with an indexer also the kv positions its rows scored
        (``index_candidates``: context summed over real rows and layers)
        and those they attended (``index_selected``: ``min(index_topk,
        context)`` likewise).

        ``selection_of=(first, n)`` (static) makes the indexer's choice
        for pack rows [first, first + n) a fourth output: bool (layers,
        n, C * bs), the mask the attention applied, dense stack first —
        what a comparison with a reference reads; the tick itself never
        asks."""
        c = self.config
        x = h[0]
        seq = jnp.clip(row_seq, 0, pad_lens.shape[0] - 1)
        out_pools, rows, masks = [], None, []
        with jax.named_scope("layers"):
            for stack, pool in zip(("dense", "moe"), pools):
                stacked, experts = self._stack(params, stack)

                def body(carry, xs, experts=experts):
                    sl, i = xs
                    y, p, r, chosen = self._block_ragged(
                        sl, experts, carry[0], carry[1], i, table, row_seq,
                        row_pos, pad_lens)
                    if selection_of is not None:
                        at = slice(selection_of[0], sum(selection_of))
                        r = (r, self._selected(
                            chosen, table, p, pad_lens[seq][at],
                            row_pos[at], at))
                    return (y, p), r
                (x, pool), r = jax.lax.scan(
                    body, (x, pool), (stacked,
                                      jnp.arange(jax.tree.leaves(pool)[0]
                                                 .shape[0])))
                if selection_of is not None:
                    r, mask = r
                    masks.append(mask)
                out_pools.append(pool)
                rows = r if stack == "moe" else rows        # (Le, Eh)
        real = row_pos >= 0
        pairs = jnp.sum(real) * (c.num_experts_per_tok * c.num_expert_layers)
        stats = [jnp.sum(rows), jnp.max(rows, initial=0), pairs]
        if c.index_topk is not None:
            ctx = jnp.where(real, row_pos - pad_lens[seq] + 1, 0)
            stats += [jnp.sum(ctx) * c.num_hidden_layers,
                      jnp.sum(jnp.minimum(ctx, c.index_topk))
                      * c.num_hidden_layers]
        stats = jnp.stack(stats).astype(jnp.int32)
        if selection_of is not None:
            return x[None], tuple(out_pools), stats, jnp.concatenate(masks)
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

    def _select_dense(self, q_idx, w_idx, cache, t0, pad_lens):
        """The indexer's choice over a dense cache of its keys (B, Lmax,
        Di) for rows (B, k) at slots [t0, t0 + k): bool (B, k, Lmax)."""
        from ..ops.index_select import select_threshold_ref, selected
        B, k = q_idx.shape[:2]
        with jax.named_scope("indexer"):
            sc = jnp.einsum("bqhd,bkd->bqhk", q_idx, cache,
                            preferred_element_type=jnp.float32)
            sc = jnp.sum(jnp.maximum(sc, 0.0) * w_idx[..., None], axis=2)
        sc = sc.reshape(B * k, -1)
        seq = jnp.repeat(jnp.arange(B), k)
        hi = jnp.tile(t0 + jnp.arange(k), B)
        thr = select_threshold_ref(sc, seq, hi, pad_lens,
                                   k=self.config.index_topk)
        return selected(sc, thr, pad_lens[seq], hi).reshape(B, k, -1)

    def _run_dense(self, params, x, caches, t0, pad_lens):
        """Both stacks over x (B, k, H) written at cache slots
        [t0, t0 + k): the body of ``prefill`` and ``decode_step``."""
        c = self.config
        B, k, H = x.shape
        if pad_lens is None:
            pad_lens = jnp.zeros((B,), jnp.int32)
        pos = jnp.maximum(t0 + jnp.arange(k)[None, :] - pad_lens[:, None], 0)
        out = []
        with jax.named_scope("layers"):
            for stack, cache in zip(("dense", "moe"), caches):
                stacked, experts = self._stack(params, stack)

                def body(carry, xs, experts=experts):
                    sl, i, ch = xs
                    with jax.named_scope("attn"):
                        q_nope, q_r, latent, *index = self._mla_in(
                            sl, carry, pos)
                        put = lambda buf, v: \
                            jax.lax.dynamic_update_slice_in_dim(
                                buf, v.astype(buf.dtype), t0, axis=1)
                        if not index:
                            lat = ch = put(ch, latent)
                            chosen = None
                        else:
                            q_idx, w_idx, k_idx = index
                            lat, keys = put(ch[0], latent), put(ch[1], k_idx)
                            ch = (lat, keys)
                            chosen = self._select_dense(
                                q_idx, w_idx, keys, t0, pad_lens)
                        y = mla_out(c, sl, carry, mla_attend_dense(
                            c, sl, carry, lat, q_nope, q_r, t0, pad_lens,
                            chosen))
                    y, _ = self._ffn(sl, experts, i, y.reshape(B * k, H))
                    return y.reshape(B, k, H), ch
                x, cache = jax.lax.scan(
                    body, x, (stacked, jnp.arange(
                        jax.tree.leaves(cache)[0].shape[0]), cache))
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
