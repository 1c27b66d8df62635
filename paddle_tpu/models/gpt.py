"""GPT model family — the flagship for hybrid-parallel training.

Reference capability: the fleet hybrid-parallel GPT tests
(hybrid_parallel_pp_transformer.py, GPT-3 configs in BASELINE.json).

TPU-first design decisions:
- **Stacked blocks**: all L transformer blocks live in ONE pytree with a
  leading layer dim, consumed by ``lax.scan`` — one compiled block program
  regardless of depth (compile time O(1) in L), and the leading dim is the
  natural pipeline-stage shard ("pipe") for the shard_map pipeline engine.
- **TP via dims_mapping**: qkv/fc1 are column-parallel (out dim on "model"),
  proj/fc2 row-parallel (in dim on "model") — GSPMD inserts the allreduces
  the reference's ColumnParallelLinear/RowParallelLinear issue explicitly.
- **Sequence parallel**: activations constrained to P("data", "sep", None)
  between blocks when a "sep" axis exists.
- **bf16 compute, fp32 params** by default; flash attention from
  paddle_tpu.ops (Pallas on TPU).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import rng
from ..core.tensor import Parameter, Tensor, apply
from ._decode import (CausalDecoderMixin, cached_attention,  # noqa: F401
                      dequantize_cache, make_token_sampler, quantize_kv,
                      ragged_attention, ragged_write,
                      validate_sampler_args, write_cache)
from ..nn.layer.base import Layer
from ..ops.attention import flash_attention


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_attention_heads=12, intermediate_size=None,
                 max_position_embeddings=1024, hidden_dropout_prob=0.0,
                 attention_probs_dropout_prob=0.0, initializer_range=0.02,
                 layer_norm_epsilon=1e-5, compute_dtype="bfloat16",
                 use_flash_attention=True, tie_word_embeddings=True,
                 sequence_parallel=None, scan_unroll=1,
                 hidden_act="gelu_approx", kv_cache_dtype=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position_embeddings = max_position_embeddings
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.initializer_range = initializer_range
        self.layer_norm_epsilon = layer_norm_epsilon
        self.compute_dtype = compute_dtype
        self.use_flash_attention = use_flash_attention
        self.tie_word_embeddings = tie_word_embeddings
        self.scan_unroll = scan_unroll  # layers per scan step (see scan_blocks)
        # GPT-2's canonical activation is the tanh approximation ("gelu_new")
        # — hence the approx default; "gelu" selects the exact erf form
        if hidden_act not in ("gelu", "gelu_approx"):
            raise ValueError(f"hidden_act must be 'gelu' or 'gelu_approx', "
                             f"got {hidden_act!r}")
        self.hidden_act = hidden_act
        # None → KV cache stored in compute_dtype; "int8" → per-(position,
        # head) symmetric-quantized cache (half the decode HBM traffic of
        # bf16; serving accuracy tradeoff, see models/_decode.py)
        if kv_cache_dtype not in (None, "int8"):
            raise ValueError(f"kv_cache_dtype must be None or 'int8', "
                             f"got {kv_cache_dtype!r}")
        self.kv_cache_dtype = kv_cache_dtype
        # None → GSPMD decides (sequence gathered for attention);
        # "ring"/"ulysses" → explicit context parallelism over the "sep" axis
        if sequence_parallel not in (None, "ring", "ulysses"):
            raise ValueError(f"sequence_parallel must be None, 'ring' or "
                             f"'ulysses', got {sequence_parallel!r}")
        self.sequence_parallel = sequence_parallel


# canonical sizes (GPT-3 paper / fleet configs)
GPT_CONFIGS = {
    "gpt2-small": dict(hidden_size=768, num_layers=12, num_attention_heads=12),
    "gpt2-medium": dict(hidden_size=1024, num_layers=24, num_attention_heads=16),
    "gpt2-large": dict(hidden_size=1280, num_layers=36, num_attention_heads=20),
    "gpt3-1.3B": dict(hidden_size=2048, num_layers=24, num_attention_heads=16),
    "gpt3-2.7B": dict(hidden_size=2560, num_layers=32, num_attention_heads=32),
    "gpt3-6.7B": dict(hidden_size=4096, num_layers=32, num_attention_heads=32),
    "gpt3-13B": dict(hidden_size=5120, num_layers=40, num_attention_heads=40),
}


class GPTModel(CausalDecoderMixin, Layer):
    """Decoder-only transformer with stacked block parameters."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = c = config
        L, H, V = c.num_layers, c.hidden_size, c.vocab_size
        I = c.intermediate_size
        std = c.initializer_range

        def normal(shape, s=std):
            from ..nn.initializer import Normal
            return Normal(0.0, s)(shape, "float32")

        def zeros(shape):
            return jnp.zeros(shape, jnp.float32)

        def ones(shape):
            return jnp.ones(shape, jnp.float32)

        def param(name, data, mapping=None):
            p = Parameter(data, name=name)
            if mapping:
                p._dims_mapping = mapping
            self.add_parameter(name.replace(".", "_"), p)
            return p

        # embeddings (vocab-parallel like VocabParallelEmbedding)
        self.wte = param("wte", normal([V, H]), {0: "model"})
        self.wpe = param("wpe", normal([c.max_position_embeddings, H]))
        # stacked blocks — column-parallel qkv/fc1, row-parallel proj/fc2
        # (reference: fused_attention_op.cu QKV fused gemm; fleet mp_layers)
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
        self.blocks_fc1_w = param("blocks.fc1_w", normal([L, H, I]), {2: "model"})
        self.blocks_fc1_b = param("blocks.fc1_b", zeros([L, I]), {1: "model"})
        self.blocks_fc2_w = param("blocks.fc2_w",
                                  normal([L, I, H], std / math.sqrt(2 * L)),
                                  {1: "model"})
        self.blocks_fc2_b = param("blocks.fc2_b", zeros([L, H]))
        self.lnf_w = param("lnf_w", ones([H]))
        self.lnf_b = param("lnf_b", zeros([H]))
        if not c.tie_word_embeddings:
            self.lm_head = param("lm_head", normal([H, V]), {1: "model"})

    # -------------------------------------------------------- pure functions
    @staticmethod
    def stacked_param_names():
        return [f"blocks_{n}" for n in ("ln1_w", "ln1_b", "qkv_w", "qkv_b",
                                        "proj_w", "proj_b", "ln2_w", "ln2_b",
                                        "fc1_w", "fc1_b", "fc2_w", "fc2_b")]

    # Named regions (``jax.named_scope``): embed / layers {attn {kv_write,
    # <kernel>}, mlp} / head are the one vocabulary the training step and
    # the serving programs share.  They are metadata on the operations (the
    # ``op_name`` of the HLO, the ``tf_op`` of a profiler trace): a device
    # operation is attributed to the innermost region on its path.

    def embed_fn(self, params: Dict[str, Any], input_ids, key=None):
        c = self.config
        dt = jnp.dtype(c.compute_dtype)
        with jax.named_scope("embed"):
            pos = jnp.arange(input_ids.shape[-1])
            h = jnp.take(params["wte"], input_ids, axis=0) \
                + params["wpe"][pos]
            return h.astype(dt)

    def _block_ln(self, x, w, b, dt):
        x32 = x.astype(jnp.float32)
        m = x32.mean(-1, keepdims=True)
        v = x32.var(-1, keepdims=True)
        return ((x32 - m) * jax.lax.rsqrt(v + self.config.layer_norm_epsilon)
                * w + b).astype(dt)

    def _block_qkv(self, sl, h):
        """pre-LN + QKV projection; returns q, k, v as (B, L, nh, hd)."""
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

    def _block_post_attn(self, sl, h, att):
        """attention output projection + residual (the end of the block's
        ``attn`` region), then the MLP half of the block (``mlp``)."""
        dt = h.dtype
        B, Lq, H = h.shape
        with jax.named_scope("attn"):
            att = att.reshape(B, Lq, H)
            h = h + att @ sl["blocks_proj_w"].astype(dt) \
                + sl["blocks_proj_b"].astype(dt)
        with jax.named_scope("mlp"):
            m_in = self._block_ln(h, sl["blocks_ln2_w"], sl["blocks_ln2_b"],
                                  dt)
            ff = jax.nn.gelu(
                m_in @ sl["blocks_fc1_w"].astype(dt)
                + sl["blocks_fc1_b"].astype(dt),
                approximate=self.config.hidden_act == "gelu_approx")
            return h + ff @ sl["blocks_fc2_w"].astype(dt) \
                + sl["blocks_fc2_b"].astype(dt)

    def block_fn(self, sl: Dict[str, Any], h, key=None, mesh=None):
        """One transformer block given this layer's parameter slice.

        ``mesh``: the mesh the step is partitioned over (set by the GSPMD
        and ZeRO builders).  The flash kernel then runs on each device's
        own rows, and with ``sequence_parallel`` on a mesh with sep>1
        attention runs as explicit ring/Ulysses context parallelism over
        the "sep" axis instead of letting GSPMD gather the sequence."""
        with jax.named_scope("attn"):
            att = self._block_attention(sl, h, mesh)
        return self._block_post_attn(sl, h, att)

    def _block_attention(self, sl, h, mesh):
        """pre-LN + QKV + attention of ``block_fn``: (B, L, nh, hd)."""
        c = self.config
        B, Lq, H = h.shape
        q, k, v = self._block_qkv(sl, h)
        sp_mode = getattr(c, "sequence_parallel", None)
        if sp_mode and mesh is not None and mesh.shape.get("sep", 1) > 1:
            if Lq % mesh.shape["sep"] != 0:
                # never fall back silently — gathered attention is exactly the
                # O(L) per-device memory blowup the user opted out of
                raise ValueError(
                    f"sequence_parallel={sp_mode!r} needs seq_len ({Lq}) "
                    f"divisible by the sep degree ({mesh.shape['sep']}); pad "
                    f"the sequence or change sep_degree")
            # context parallelism: activations stay sequence-sharded on "sep";
            # ring/Ulysses attention inside a partial-manual shard_map region
            # (only "sep" is manual — dp/mp stay under GSPMD)
            from ..distributed.sharding_rules import sep_activation_spec
            from ..distributed.spmd import shard_map
            from ..ops.ring_attention import sequence_parallel_attention
            att = shard_map(
                functools.partial(sequence_parallel_attention, axis_name="sep",
                                  causal=True, mode=sp_mode),
                mesh=mesh, in_specs=sep_activation_spec(),
                out_specs=sep_activation_spec(), axis_names={"sep"},
            )(q, k, v)
        else:
            att = flash_attention(q, k, v, causal=True, mesh=mesh)
        return att

    def _head_logits(self, params: Dict[str, Any], h):
        c = self.config
        with jax.named_scope("head"):
            x32 = h.astype(jnp.float32)
            m = x32.mean(-1, keepdims=True)
            v = x32.var(-1, keepdims=True)
            h = (x32 - m) * jax.lax.rsqrt(v + c.layer_norm_epsilon) \
                * params["lnf_w"] + params["lnf_b"]
            w = params.get("lm_head")
            if w is None:
                w = params["wte"].T
            dt = jnp.dtype(c.compute_dtype)
            return h.astype(dt) @ w.astype(dt)

    def head_fn(self, params: Dict[str, Any], h):
        return self._head_logits(params, h).astype(jnp.float32)

    def head_loss_fn(self, params: Dict[str, Any], h, labels):
        # fused CE on compute-dtype logits: never materializes the fp32
        # (B, L, V) log-prob tensor (ops/loss.py — ≙ the reference's fused
        # softmax_with_cross_entropy, operators/math/cross_entropy.cu)
        from ..ops.loss import softmax_cross_entropy_mean
        with jax.named_scope("head"):
            return softmax_cross_entropy_mean(self._head_logits(params, h),
                                              labels)

    def scan_blocks(self, params, h, key=None, remat=True, mesh=None):
        """``remat``: False = save all activations; True = full per-block
        recompute (≙ RecomputeOptimizer, fluid/optimizer.py:5930); "dots" =
        selective policy that saves MXU (matmul) outputs and recomputes only
        elementwise interiors — near-full-speed backward at a fraction of the
        activation memory (the TPU-idiomatic default for large batches).
        Under a ``mesh`` the carry is pinned to the batch axes (and "sep"):
        every block runs on its own rows, and a weight split inside the
        layer is gathered, one layer an iteration, where it is used."""
        from ..distributed.sharding_rules import constrain_activation
        h = constrain_activation(h, mesh)
        stacked = {k: params[k] for k in self.stacked_param_names()}
        if remat:
            policy = None
            if remat == "dots":
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            fn = jax.checkpoint(
                lambda sl, hh: self.block_fn(sl, hh, key, mesh=mesh),
                policy=policy)

            def body(carry, sl):
                return fn(sl, carry), None
        else:
            def body(carry, sl):
                return self.block_fn(sl, carry, key, mesh=mesh), None
        from ._scan import resolve_scan_unroll
        with jax.named_scope("layers"):
            out, _ = jax.lax.scan(body, h, stacked,
                                  unroll=resolve_scan_unroll(self.config))
        return out

    # ------------------------------------------------------------- nn.Layer
    def forward(self, input_ids, position_ids=None, attention_mask=None,
                use_cache=False, cache=None):
        raw = getattr(input_ids, "_data", input_ids)
        params = {n: p._data for n, p in self.named_parameters()}
        h = self.embed_fn(params, raw)
        h = self.scan_blocks(params, h, remat=False)
        logits = self.head_fn(params, h)
        return Tensor(logits) if isinstance(input_ids, Tensor) else logits

    # ------------------------------------------------- KV-cache generation
    # machinery shared via CausalDecoderMixin (models/_decode.py);
    # GPT provides the model-specific pieces: prefill, decode_step,
    # decode_logits.

    def decode_logits(self, params, h):
        """fp32 logits for the decode loops (mixin contract)."""
        return self.head_fn(params, h)

    def _block_decode(self, sl, h, ck, cv, t, pad_lens=None):
        """One block for ONE new token at position ``t``.

        h (B, 1, H); ck/cv (B, max_len, nh, hd) are this layer's caches.
        Returns (h_out, ck, cv) with the new k/v written at index t and
        attention taken over cache positions ≤ t (later slots hold zeros or
        stale values — and left-pad slots, when pad_lens is set — masked)."""
        with jax.named_scope("attn"):
            q, k, v = self._block_qkv(sl, h)
            ck = write_cache(ck, k, t)
            cv = write_cache(cv, v, t)
            # int8 caches dequantize here; XLA fuses the convert*scale into
            # the attention einsum's operand read (no fp cache copy
            # materializes)
            dt = q.dtype
            att = cached_attention(q, dequantize_cache(ck, dt),
                                   dequantize_cache(cv, dt), t,
                                   pad_lens=pad_lens)
        return self._block_post_attn(sl, h, att), ck, cv

    def prefill(self, params, input_ids, max_len: int, pad_lens=None,
                mesh=None):
        """Run the prompt through all blocks, returning the final hidden
        states (B, P, H) and caches filled at positions [0, P).  With
        ``pad_lens`` (left-padded prompts), embedding positions shift and
        pad keys are masked (mixin helpers — one canonical convention).
        ``mesh``: the tensor-parallel serving mesh, for the flash kernel
        (see block_fn)."""
        c = self.config
        B, P = input_ids.shape
        if pad_lens is None:
            h, key_mask = self.embed_fn(params, input_ids), None
        else:
            h = self._prefill_embed(params, input_ids, pad_lens)
            key_mask = self._prefill_key_mask(P, pad_lens)
        stacked = {k: params[k] for k in self.stacked_param_names()}

        def body(carry, sl):
            with jax.named_scope("attn"):
                q, k, v = self._block_qkv(sl, carry)
                att = flash_attention(q, k, v, causal=True,
                                      key_mask=key_mask, mesh=mesh)
            return self._block_post_attn(sl, carry, att), (k, v)

        with jax.named_scope("layers"):
            h, (ks, vs) = jax.lax.scan(body, h, stacked)
        if getattr(c, "kv_cache_dtype", None) == "int8":
            def padq(x):
                q, s = quantize_kv(x)
                pad5 = [(0, 0), (0, 0), (0, max_len - P), (0, 0), (0, 0)]
                return (jnp.pad(q, pad5), jnp.pad(s, pad5[:-1]))
            return h, (padq(ks), padq(vs))
        pad = [(0, 0), (0, 0), (0, max_len - P), (0, 0), (0, 0)]
        dt = jnp.dtype(c.compute_dtype)
        return h, (jnp.pad(ks.astype(dt), pad), jnp.pad(vs.astype(dt), pad))

    def _block_decode_ragged(self, sl, h, pck, pcv, table, row_seq,
                             row_pos, pad_lens, layer=None):
        """One block for a flattened ragged pack: h (1, T, H); pck/pcv are
        the whole stack's block pools (L, NB+1, bs, nh, hd) of which this
        block is layer ``layer`` (CausalDecoderMixin.decode_ragged), or
        one layer's own without it.  Each row's k/v is
        scattered to its table-mapped pool position BEFORE attention, so
        intra-pack causal attention (a prefill chunk's rows attending each
        other) reads the freshly written keys — the _block_decode
        write-then-attend order over the ragged layout."""
        with jax.named_scope("attn"):
            q, k, v = self._block_qkv(sl, h)           # (1, T, nh, hd)
            pck = ragged_write(pck, k[0], table, row_seq, row_pos, layer)
            pcv = ragged_write(pcv, v[0], table, row_seq, row_pos, layer)
            att = ragged_attention(q[0], pck, pcv, table, row_seq, row_pos,
                                   pad_lens, layer)
        return self._block_post_attn(sl, h, att[None]), pck, pcv

    def decode_step(self, params, h, caches, t, pad_lens=None):
        """All blocks for one token: h (B,1,H), caches = (ck, cv) stacked
        over layers.  Returns (h_out, caches)."""
        stacked = {k: params[k] for k in self.stacked_param_names()}

        def body(carry, xs):
            sl, ck, cv = xs
            out, ck, cv = self._block_decode(sl, carry, ck, cv, t,
                                             pad_lens=pad_lens)
            return out, (ck, cv)

        with jax.named_scope("layers"):
            h, (cks, cvs) = jax.lax.scan(body, h,
                                         (stacked, caches[0], caches[1]))
        return h, (cks, cvs)


class GPTForPretraining(GPTModel):
    """LM-head + loss (reference: GPTForPretraining in the fleet tests)."""

    def forward(self, input_ids, labels=None, **kw):
        logits = super().forward(input_ids, **kw)
        if labels is None:
            return logits
        raw_logits = getattr(logits, "_data", logits)
        raw_labels = getattr(labels, "_data", labels)
        logp = jax.nn.log_softmax(raw_logits, axis=-1)
        loss = -jnp.take_along_axis(logp, raw_labels[..., None], axis=-1).mean()
        return Tensor(loss) if isinstance(input_ids, Tensor) else loss


def gpt_preset(name: str, **overrides) -> GPTConfig:
    cfg = dict(GPT_CONFIGS[name])
    cfg.update(overrides)
    return GPTConfig(**cfg)


def make_gpt_train_step(model: GPTModel, optimizer, hcg, n_microbatches: int = 1,
                        remat: bool = True, donate: bool = True,
                        zero_stage: int = 0, dynamic_loss_scale: bool = False,
                        virtual_pp_degree: Optional[int] = None,
                        monitor=None, grad_comm=None,
                        update_sharding: bool = False):
    """Build the full hybrid train step for GPT over the mesh.

    dp/mp/sharding/sep via GSPMD; pp via the stacked shard_map pipeline when
    the mesh has pipe>1.  step(state, key, lr, input_ids, labels) -> (state, loss).
    zero_stage>0 routes through the contractual ZeRO step (distributed/zero.py:
    grad reduce-scatter at stage 2, sharded params at stage 3, fp32 masters +
    found_inf + dynamic loss scaling — ≙ sharding_optimizer.py:45 semantics).
    ``monitor``: optional ``telemetry.TrainMonitor``, forwarded to the
    underlying builder (pipeline/zero) or wrapped around the GSPMD step —
    pure host-side timing, compiled programs identical either way.
    ``grad_comm``: gradient-communication policy ("fp32"/"bf16"/"int8_ef"
    or a ``distributed.grad_comm.GradCommPolicy``), forwarded to the zero
    or GSPMD builder; not wired for pp_degree>1 (the pipeline step owns
    its own exchange schedule).
    ``update_sharding``: on a plain data-parallel mesh, shard the weight
    update over the replicas (arXiv:2004.13336 via
    ``distributed.update_sharding``): optimizer-state HBM and update
    FLOPs per replica drop ~dp_degree×, token/loss-parity with the
    replicated update.  Mutually exclusive with zero_stage>0, pp>1, and
    sequence_parallel (those regimes own their own state layouts).
    """
    from ..distributed.grad_comm import comm_info, resolve_policy
    from ..distributed.pipeline_engine import make_stacked_pipeline_step
    from ..distributed.spmd import make_gspmd_step_from_loss

    policy = resolve_policy(grad_comm)
    mesh = hcg.mesh
    params0 = {n: p._data for n, p in model.named_parameters()}
    S = mesh.shape.get("pipe", 1)
    sp_mode = getattr(model.config, "sequence_parallel", None)
    sp_mesh = mesh if (sp_mode and mesh.shape.get("sep", 1) > 1) else None

    if S > 1:
        if policy.name != "fp32":
            raise NotImplementedError(
                "grad_comm with pp_degree>1 is not wired yet: the stacked "
                "pipeline step owns its own exchange schedule; use "
                "pp_degree=1 for compressed gradient collectives")
        if zero_stage > 0 or dynamic_loss_scale:
            raise NotImplementedError(
                "zero_stage/dynamic_loss_scale with pp_degree>1 is not wired "
                "yet: the stacked pipeline step manages its own state layout. "
                "Use pp_degree=1 for ZeRO, or sharding via the pipeline's own "
                "slot sharding (build_state_shardings).")
        if sp_mesh is not None:
            raise ValueError(
                "sequence_parallel with pp_degree>1 is not supported yet: the "
                "pipeline engine's shard_map over 'pipe' cannot nest the "
                "'sep' shard_map region; set sep_degree=1 or pp_degree=1")
        if virtual_pp_degree is None:  # strategy pp_configs default
            getter = getattr(hcg, "get_virtual_pipeline_degree", None)
            virtual_pp_degree = getter() if getter else 1
        return make_stacked_pipeline_step(
            model.embed_fn, model.block_fn, model.head_loss_fn, params0,
            optimizer, hcg, model.config.num_layers,
            max(n_microbatches, S), model.stacked_param_names(), layer=model,
            donate=donate, remat=remat, virtual_pp_degree=virtual_pp_degree,
            monitor=monitor)

    def loss_of(params, key, x, labels):
        h = model.embed_fn(params, x, key)
        h = model.scan_blocks(params, h, key, remat=remat, mesh=mesh)
        return model.head_loss_fn(params, h, labels)

    raw_step = None
    if zero_stage > 0:
        if update_sharding:
            raise ValueError(
                "update_sharding composes the plain-DP regime; zero_stage>0 "
                "already shards the optimizer state over 'sharding' — pick "
                "one")
        from ..distributed.zero import make_zero_train_step
        inner_step, state0 = make_zero_train_step(
            loss_of, params0, optimizer, mesh, layer=model,
            zero_stage=zero_stage, dynamic_loss_scale=dynamic_loss_scale,
            donate=donate, monitor=monitor, grad_comm=policy)
    elif update_sharding:
        if sp_mesh is not None:
            raise NotImplementedError(
                "update_sharding with sequence_parallel is not wired: the "
                "dp shard_map cannot nest the 'sep' shard_map region")
        from ..distributed.update_sharding import \
            make_dp_update_sharded_train_step

        # inside the dp shard_map the batch is already local — no mesh, so
        # scan_blocks threads no GSPMD activation constraint
        def loss_of_local(params, key, x, labels):
            h = model.embed_fn(params, x, key)
            h = model.scan_blocks(params, h, key, remat=remat)
            return model.head_loss_fn(params, h, labels)

        # batch layout: (key, x, labels) — the key rides replicated
        inner_step, state0 = make_dp_update_sharded_train_step(
            loss_of_local, params0, optimizer, mesh, donate=donate,
            monitor=monitor, grad_comm=policy, replicated_args=(0,))
    else:
        from ..telemetry import instrument_train_step
        raw_step, state0 = make_gspmd_step_from_loss(
            loss_of, params0, optimizer, mesh, layer=model, donate=donate,
            grad_comm=policy)
        inner_step = instrument_train_step(raw_step, monitor, "gpt",
                                           comm=comm_info(params0, policy))

    def step(state, key, lr, x, labels):
        return inner_step(state, lr, key, x, labels)

    if raw_step is not None:
        # AOT seam (jit.functional.warm_train_step): an outer-order alias
        # of the same program — jit-of-jit inlines at trace time, so the
        # lowered/compiled executable is callable with step's PUBLIC
        # signature (the bare pre-instrument step is traced: the monitor
        # wrapper's host timing must never run under tracing)
        step.lower = jax.jit(
            lambda state, key, lr, x, labels: raw_step(
                state, lr, key, x, labels),
            donate_argnums=(0,) if donate else ()).lower
    else:
        # the zero step's bare program is not reachable from here, and
        # compile_aot's jax.jit fallback would trace the monitor wrapper
        # (corrupting its first-call compile accounting) — refuse loudly
        def _no_lower(*args, **kwargs):
            raise NotImplementedError(
                "AOT lowering for zero_stage>0 / update_sharding gpt steps "
                "is not wired (those builders own their state layouts); "
                "warm the plain GSPMD path, or rely on jit.aot."
                "enable_persistent_compilation_cache for cross-process "
                "reuse")
        step.lower = _no_lower

    return step, state0


def make_sharded_gpt_train_step(cfg: GPTConfig, optimizer, hcg,
                                zero_stage: int = 0, seed: int = 0,
                                remat=True, donate: bool = True,
                                monitor=None, grad_comm=None):
    """GPT train step whose parameters are initialized DIRECTLY sharded on
    the mesh — no host-side full-size materialization (GPT-3 6.7B fp32
    params are ~27GB on host with eager init).  Non-pipeline meshes only;
    use make_gpt_train_step for pp_degree > 1.

    ``zero_stage`` here means sharding SPECS only (params/slots partitioned
    over the "sharding" axis); the contractual ZeRO extras — fp32 masters,
    found_inf, dynamic loss scaling — live in make_gpt_train_step's
    make_zero_train_step route and are NOT applied on this path.

    ``grad_comm``: gradient-communication policy (``"fp32"`` / ``"bf16"``
    / ``"int8_ef"``), applied at the post-backward seam of the GSPMD step
    (LOCAL mode — see distributed/grad_comm.py); stateful policies add a
    flat ``"comm_e"`` residual leaf to the sharded TrainState.

    Returns ``(step, state0)`` with ``step(state, lr, key, x, labels)``.
    """
    from ..core import rng as _rng
    from ..distributed.grad_comm import comm_info, resolve_policy
    from ..distributed.spmd import make_gspmd_sharded_init_step

    mesh = hcg.mesh
    if mesh.shape.get("pipe", 1) > 1:
        raise NotImplementedError("sharded init with pp_degree>1: use "
                                  "make_gpt_train_step")
    if cfg.sequence_parallel is not None:
        raise NotImplementedError(
            "sharded init does not wire sequence_parallel yet — ring/Ulysses "
            "attention would silently fall back to gathered sequences; use "
            "make_gpt_train_step for sep meshes")
    holder = {}

    def build(key):
        with _rng.rng_scope(key):
            m = GPTModel(cfg)
        holder.setdefault("model", m)
        return {n: p._data for n, p in m.named_parameters()}

    jax.eval_shape(build, jax.random.key(seed))  # captures metadata model
    meta_model = holder["model"]  # params hold dead tracers; metadata + pure fns only

    def loss_of(params, key, x, labels):
        h = meta_model.embed_fn(params, x, key)
        h = meta_model.scan_blocks(params, h, key, remat=remat, mesh=mesh)
        return meta_model.head_loss_fn(params, h, labels)

    from ..telemetry import instrument_train_step
    policy = resolve_policy(grad_comm)
    step, state0 = make_gspmd_sharded_init_step(
        loss_of, build, optimizer, mesh, meta_model, zero_stage=zero_stage,
        donate=donate, seed=seed, grad_comm=policy)
    return instrument_train_step(
        step, monitor, "gpt_sharded",
        comm=comm_info(state0["params"], policy)), state0
