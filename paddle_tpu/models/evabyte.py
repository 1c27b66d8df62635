"""EvaByte family (``model_type: evabyte``, ``attention_class: eva``): a
byte-level decoder whose attention reads one exact window and one summary
per chunk of everything before it — for SERVING through the ragged paged
engine.

Layer (EvaByte config.json; EVA attention as Zheng et al., ICLR 2023,
arXiv:2302.04542, in the deterministic form the release runs: the
proposal's sample replaced by a learned vector per head).  The residual
stream is float32 (``fp32_skip_add``):

    x = x + W_o EVA(N1(x));  x = x + W_down(silu(W_gate b) * W_up b), b = N2(x)

``N(x) = x / sqrt(mean(x^2) + eps) * (1 + g)`` (``norm_add_unit_offset``).
A row at position ``p`` (window ``w = p // window_size``, chunk ``c = p //
chunk_size``) attends, under ONE softmax in float32:

- ``E(p)``: the rotated keys of its own window at positions <= p, exactly;
- ``R(p)``: one summary ``(k~_c, v~_c)`` per chunk of every EARLIER
  window, ``k~_c = sum_j a_j k_j + mu_h``, ``v~_c = sum_j a_j v_j``,
  ``a = softmax_j(scale * phi_h . k_j)`` over the chunk's keys, with
  ``phi_h`` / ``mu_h`` the layer's learned ``adaptive_phi`` /
  ``adaptive_mu_k``.

So what is cached is two kinds of state (``cache_spec()``, layout "eva",
docs/CACHE_SPEC.md): a WINDOW leaf that does not page — K and V of one
window per slot, overwritten when the next window starts — and a SUMMARY
leaf that pages by chunk, one row per ``chunk_size`` positions.  A pack's
rows of one sequence never cross a window (``row_boundary``): the window
leaf has one window's room.

The head is ``num_pred_heads`` x ``vocab_size`` wide and untied; head 0
predicts the next byte and is what ``decode_logits`` returns.  Heads 1..
(bytes further ahead) are rows of the same product and are NOT used to
draft: the release's multibyte self-speculation needs a draft that shares
the target's trunk and cache (ROADMAP R2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Parameter
from ..nn.layer.base import Layer
from ..ops.moe import gated_mlp
from ._decode import (CacheLeaf, CacheSpec, CausalDecoderMixin, build_pools,
                      eva_summarize, ragged_eva_attention, ragged_write,
                      rms_norm, rope_rotate_half, slot_write)

_BLOCK = ("ln1_w", "qkv_w", "o_w", "adaptive_phi", "adaptive_mu_k",
          "ln2_w", "gate_w", "up_w", "down_w")
TICK_STATS = ("eva_window_keys", "eva_summary_keys", "eva_chunks_closed")


class EvaByteConfig:
    def __init__(self, vocab_size=320, hidden_size=4096,
                 num_hidden_layers=32, num_attention_heads=32,
                 intermediate_size=11008, num_pred_heads=8, chunk_size=16,
                 window_size=2048, max_position_embeddings=32768,
                 rope_theta=100000.0, rms_norm_eps=1e-5,
                 norm_add_unit_offset=True, init_std=0.01275,
                 compute_dtype="bfloat16"):
        if hidden_size % num_attention_heads:
            raise ValueError("num_attention_heads must divide hidden_size")
        if window_size % chunk_size:
            raise ValueError(f"chunk_size ({chunk_size}) must divide "
                             f"window_size ({window_size}): no chunk "
                             f"straddles a window")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size
        self.num_pred_heads = num_pred_heads
        self.chunk_size = chunk_size
        self.window_size = window_size
        self.max_position_embeddings = max_position_embeddings
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.norm_add_unit_offset = bool(norm_add_unit_offset)
        self.init_std = init_std
        self.compute_dtype = compute_dtype

    @property
    def num_layers(self):
        return self.num_hidden_layers

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


class EvaByteModel(CausalDecoderMixin, Layer):
    """One stack of EVA blocks; parameters stacked over the layers
    (``blocks_*``)."""

    def __init__(self, config: EvaByteConfig):
        super().__init__()
        self.config = config
        from ..nn.initializer import Normal
        key = jax.random.key(0)
        for i, (name, (shape, init)) in enumerate(
                self.param_table(config).items()):
            if isinstance(init, str) and init != "head_vector":
                data = jnp.full(shape, float(init == "ones"), jnp.float32)
            elif init == "head_vector":
                data = self.head_vectors(jax.random.fold_in(key, i), shape)
            else:
                data = Normal(0.0, init)(list(shape), "float32")
            self.add_parameter(name, Parameter(data, name=name))

    @staticmethod
    def head_vectors(key, shape):
        """``adaptive_phi`` / ``adaptive_mu_k`` at initialisation: normal
        x ``head_dim ** -0.5``, clipped to +-1."""
        return jnp.clip(jax.random.normal(key, shape, jnp.float32)
                        * shape[-1] ** -0.5, -1.0, 1.0)

    @staticmethod
    def param_table(c: EvaByteConfig):
        """name -> (shape, standard deviation | "zeros" | "ones" |
        "head_vector"):
        the program's parameter dictionary.  Norm scales are stored as
        their distance from one (``norm_add_unit_offset``), so they start
        at zero; no bias anywhere."""
        H, I, L = c.hidden_size, c.intermediate_size, c.num_hidden_layers
        nh, hd, std = c.num_attention_heads, c.head_dim, c.init_std
        norm = "zeros" if c.norm_add_unit_offset else "ones"
        block = {
            "ln1_w": ((H,), norm), "qkv_w": ((H, 3 * H), std),
            "o_w": ((H, H), std),
            "adaptive_phi": ((nh, hd), "head_vector"),
            "adaptive_mu_k": ((nh, hd), "head_vector"),
            "ln2_w": ((H,), norm), "gate_w": ((H, I), std),
            "up_w": ((H, I), std), "down_w": ((I, H), std),
        }
        table = {"wte": ((c.vocab_size, H), std),
                 "lm_head": ((H, c.num_pred_heads * c.vocab_size), std),
                 "norm_f_w": ((H,), norm)}
        for name in _BLOCK:
            shape, init = block[name]
            table[f"blocks_{name}"] = ((L,) + shape, init)
        return table

    @staticmethod
    def stacked_param_names():
        return [f"blocks_{n}" for n in _BLOCK]

    def cache_spec(self) -> CacheSpec:
        """Two entries, each a (K, V) pair: the window leaf — one window's
        rows per slot, no table — and the summary leaf, one row per
        ``chunk_size`` positions on the block table."""
        c = self.config
        dt = str(jnp.dtype(c.compute_dtype))
        tail = (c.num_attention_heads, c.head_dim)
        window = CacheLeaf(c.num_layers, tail, dt, slot_rows=c.window_size)
        summary = CacheLeaf(c.num_layers, tail, dt,
                            tokens_per_row=c.chunk_size)
        return CacheSpec(pools=((window, window), (summary, summary)),
                         layout="eva", tick_stats=TICK_STATS,
                         row_boundary=c.window_size)

    # ------------------------------------------------------ pure functions

    def _rms(self, x, w):
        c = self.config
        return rms_norm(x, w, c.rms_norm_eps, c.norm_add_unit_offset)

    def _rope(self, x, pos):
        D = x.shape[-1]
        inv = self.config.rope_theta ** (
            -jnp.arange(0, D, 2, dtype=jnp.float32) / D)
        return rope_rotate_half(x, pos, inv)

    @property
    def _scale(self):
        return float(self.config.head_dim) ** -0.5

    def decode_logits(self, params, h):
        """Final norm and the untied head in float32 (``fp32_logits``):
        all ``num_pred_heads`` heads are one product, head 0 — the next
        byte — is returned."""
        with jax.named_scope("head"):
            c = self.config
            dt = jnp.dtype(c.compute_dtype)
            a = self._rms(h.astype(jnp.float32), params["norm_f_w"])
            logits = jnp.matmul(a.astype(dt), params["lm_head"].astype(dt),
                                preferred_element_type=jnp.float32)
            return logits[..., :c.vocab_size]

    # ------------------------------------------------------- ragged serving

    def _embed_ragged(self, params, toks, row_seq, row_pos, pad_lens):
        """A plain lookup into the float32 residual stream (positions
        enter in the attention): (1, T, H)."""
        with jax.named_scope("embed"):
            return jnp.take(params["wte"], toks, axis=0)[None].astype(
                jnp.float32)

    def _block_ragged(self, sl, x, pools, layer, table, seq, pos, closed):
        """One block for a flattened pack x (T, H) float32 over layer
        ``layer`` of both leaves, in place: write the rows' rotated K and
        V into the window leaf, close every chunk whose last row is here
        (read back from the window leaf, written to the summary leaf),
        attend.  ``pos`` (T,) counts from the sequence's first real
        position, -1 for rows that are not real; ``closed`` = (slot, chunk)
        of every chunk to close, the chunk -1 where the entry names none."""
        c = self.config
        dt = jnp.dtype(c.compute_dtype)
        nh, hd = c.num_attention_heads, c.head_dim
        window, sums = pools
        c_seq, c_at = closed

        with jax.named_scope("attn"):
            a = self._rms(x, sl["ln1_w"]).astype(dt)
            # cut into thirds BEFORE the heads are split off: reshaped
            # first, the compiler moves the reshape onto the weight and
            # copies the layer's ``qkv_w`` out of its stack, transposed,
            # every round (tests/test_aot_tpu_compile.py holds it)
            qkv = a @ sl["qkv_w"].astype(dt)
            q, k, v = (qkv[:, i * nh * hd:(i + 1) * nh * hd]
                       .reshape(-1, nh, hd) for i in range(3))
            at = jnp.maximum(pos, 0)
            q, k = self._rope(q, at), self._rope(k, at)
            window = tuple(slot_write(w, r, seq, pos, layer)
                           for w, r in zip(window, (k, v)))
            in_window = jnp.maximum(c_at, 0) % (c.window_size // c.chunk_size)
            summaries = eva_summarize(
                *window, sl["adaptive_phi"], sl["adaptive_mu_k"], c_seq,
                in_window, chunk=c.chunk_size, scale=self._scale,
                layer=layer)
            sums = tuple(ragged_write(p, r, table, c_seq, c_at, layer=layer)
                         for p, r in zip(sums, summaries))
            o = ragged_eva_attention(
                q, window, sums, table, seq, pos, chunk=c.chunk_size,
                scale=self._scale, layer=layer)
            x = x + (o.reshape(-1, nh * hd)
                     @ sl["o_w"].astype(dt)).astype(jnp.float32)
        with jax.named_scope("mlp"):
            b = self._rms(x, sl["ln2_w"]).astype(dt)
            x = x + gated_mlp(b, sl["gate_w"], sl["up_w"],
                              sl["down_w"]).astype(jnp.float32)
        return x, (window, sums)

    def decode_ragged(self, params, h, pools, table, row_seq, row_pos,
                      pad_lens):
        """All blocks for one mixed ragged tick: h (1, T, H) float32;
        ``pools`` the entries of ``cache_spec()`` — ((window K, window V),
        (summary K, summary V)), each stacked over the layers and carried
        whole through the scan; ``table`` (S, C) names the summary leaf's
        blocks.  Positions count from a sequence's first real row (the
        bucket's left-pad rows are not real rows: nothing is written or
        attended for them).  Returns (h, pools, stats): ``stats`` int32 in
        the order of ``tick_stats`` — window keys and summary keys the
        pack's real rows attended, summed over the layers, and the chunks
        the pack closed."""
        c = self.config
        x = h[0]
        T, S = x.shape[0], pad_lens.shape[0]
        seq = jnp.clip(row_seq, 0, S - 1)
        pos = jnp.where(row_pos >= 0,
                        jnp.maximum(row_pos - pad_lens[seq], -1), -1)
        real = pos >= 0
        # the chunks whose last row is in the pack: at most one a chunk's
        # worth of rows and one a sequence's lone row
        closes = real & (pos % c.chunk_size == c.chunk_size - 1)
        (rows,) = jnp.nonzero(closes, size=min(T, T // c.chunk_size + S),
                              fill_value=T)
        named = rows < T
        at = jnp.minimum(rows, T - 1)
        closed = (seq[at], jnp.where(named, pos[at] // c.chunk_size, -1))
        stacked = {n: params[f"blocks_{n}"] for n in _BLOCK}

        def body(carry, xs):
            sl, i = xs
            return self._block_ragged(sl, *carry, i, table, seq, pos,
                                      closed), None

        with jax.named_scope("layers"):
            (x, pools), _ = jax.lax.scan(
                body, (x, tuple(pools)),
                (stacked, jnp.arange(c.num_layers)))
        per_window = c.window_size // c.chunk_size
        stats = jnp.stack([
            jnp.sum(jnp.where(real, pos % c.window_size + 1, 0))
            * c.num_layers,
            jnp.sum(jnp.where(real, pos // c.window_size * per_window, 0))
            * c.num_layers,
            jnp.sum(closes)]).astype(jnp.int32)
        return x[None], pools, stats

    # ------------------------------------------------------ whole sequences

    def forward(self, input_ids):
        """float32 logits (B, L, vocab_size) of head 0 over a full causal
        pass: the ragged tick over a fresh cache, one window of every
        sequence a pack."""
        c = self.config
        raw = jnp.asarray(getattr(input_ids, "_data", input_ids), jnp.int32)
        params = {n: p._data for n, p in self.named_parameters()}
        B, L = raw.shape
        W, per_block = c.window_size, 16
        C = -(-L // (per_block * c.chunk_size))
        pools = build_pools(self.cache_spec(), (B * C + 1, per_block),
                            slots=B)
        table = 1 + jnp.arange(B * C, dtype=jnp.int32).reshape(B, C)
        no_pad = jnp.zeros((B,), jnp.int32)
        out = []
        for start in range(0, L, W):
            n = min(W, L - start)
            seq = jnp.repeat(jnp.arange(B, dtype=jnp.int32), n)
            pos = jnp.tile(start + jnp.arange(n, dtype=jnp.int32), B)
            toks = raw[:, start:start + n].reshape(-1)
            h = self._embed_ragged(params, toks, seq, pos, no_pad)
            h, pools, _ = self.decode_ragged(params, h, pools, table, seq,
                                             pos, no_pad)
            out.append(self.decode_logits(params, h[0]).reshape(B, n, -1))
        return jnp.concatenate(out, axis=1)

    def prefill(self, params, input_ids, max_len, pad_lens=None, mesh=None):
        raise NotImplementedError(
            "EvaByteModel caches 'eva' leaves (a window that does not page "
            "and chunk summaries): it is served by "
            "RaggedPagedContinuousBatchingEngine; the dense-cache generate() "
            "paths are not written for it (docs/CACHE_SPEC.md)")

    decode_step = prefill
