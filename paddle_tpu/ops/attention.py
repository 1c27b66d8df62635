"""Attention kernels.

TPU-native replacement for the reference's fused attention stack
(operators/fused/fused_attention_op.cu, fmha_ref.h:57): a Pallas
flash-attention kernel (online-softmax, O(L) memory) with an XLA einsum
fallback.  Layout convention: (batch, seq, heads, head_dim) — BLHD, matching
paddle's MultiHeadAttention internals.

Forward supports causal masking, an additive key-padding mask (the BERT
(B, 1, 1, L) shape — reference fused_attention_op.cu consumes the same
broadcast mask), and in-kernel attention-probability dropout driven by a
position-based counter RNG (same bits in forward and backward by
construction, like the reference's seeded dropout in
fused_dropout_helper.h).  The backward recomputes probabilities blockwise
from the saved logsumexp — no pass materializes the (L, L) score matrix —
in ONE Pallas kernel of five products (scores, dP, dV, dK, dQ) where a
head's float32 dQ fits VMEM, else in a pair (dQ; dK/dV).  The products take
their operands in the arrays' own dtype (bfloat16 to the MXU) and
accumulate in float32; max, sum, logsumexp and every accumulator are
float32.  ``flash_plan`` chooses the form and the blocks from the shape.

Caveat (standard for flash attention): every query row must have at least
one unmasked key, else its logsumexp is -inf and gradients NaN.  Causal +
key-padding masks used by the model zoo satisfy this (CLS is never padded).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.flags import flag
from ..core.tensor import Tensor, apply

_NEG_INF = -1e30


def _use_pallas() -> bool:
    return flag("FLAGS_use_pallas_kernels") and jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Dense XLA path (also the reference implementation for tests)
# ---------------------------------------------------------------------------

def dense_attention(q, k, v, mask=None, causal=False, scale=None, dropout_p=0.0,
                    dropout_key=None):
    """q,k,v: (B, L, H, D) raw arrays. mask: additive, broadcastable to (B,H,Lq,Lk)."""
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    scores = jnp.einsum("blhd,bmhd->bhlm", q, k) * jnp.asarray(scale, q.dtype)
    if causal:
        Lq, Lk = scores.shape[-2], scores.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (Lq, Lk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (Lq, Lk), 1)
        cmask = (col <= row + (Lk - Lq))
        scores = jnp.where(cmask, scores, jnp.asarray(_NEG_INF, scores.dtype))
    if mask is not None:
        scores = scores + mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhlm,bmhd->blhd", probs, v)


# ---------------------------------------------------------------------------
# Portable in-kernel dropout RNG: murmur3-finalizer hash of (seed, bh, row,
# col).  Position-based, so the forward and every backward kernel reproduce
# the exact same keep-mask whatever their block decomposition or the
# orientation of their score tiles, and it lowers on both Mosaic (TPU) and
# the interpret path (CPU tests) — pltpu.prng_* has no CPU lowering.
# ---------------------------------------------------------------------------

def position_hash_keep(mixed_seed, row0, col0, shape, dropout_p,
                       transposed=False):
    """Shared keep-mask core: murmur3-finalize hash((row, col) ⊕ mixed_seed)
    ≥ p·2³².  ``mixed_seed`` is a uint32 scalar the caller pre-mixes with any
    extra coordinates (head index etc.).  ``transposed``: the tile holds
    columns along its first dimension and rows along its second (the
    kernels' key-major score tiles)."""
    r_ax, c_ax = (1, 0) if transposed else (0, 1)
    rows = jnp.uint32(row0) + lax.broadcasted_iota(jnp.uint32, shape, r_ax)
    cols = jnp.uint32(col0) + lax.broadcasted_iota(jnp.uint32, shape, c_ax)
    x = (rows * jnp.uint32(0x9E3779B1)) ^ (cols * jnp.uint32(0x85EBCA77))
    x = x ^ mixed_seed
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    thresh = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return x >= thresh


def _dropout_keep(seed, bh, q0, k0, shape, dropout_p):
    """The keep mask of a key-major tile (keys down, queries across) whose
    first query is ``q0`` and first key ``k0``, as every kernel holds it."""
    mixed = seed.astype(jnp.uint32) + jnp.uint32(bh) * jnp.uint32(0xC2B2AE3D)
    return position_hash_keep(mixed, q0, k0, shape, dropout_p,
                              transposed=True)


# ---------------------------------------------------------------------------
# The plan: what the kernels do at one shape.  Static — a pure function of
# (L, D, causal, dtype) — so the same shape always runs the same program.
# ---------------------------------------------------------------------------

class FlashPlan(NamedTuple):
    """``form``: "fused" — the backward is ONE kernel of five products
    (scores, dP, dV, dK, dQ), dQ accumulated for the whole head in a
    float32 VMEM scratch of (L, D); "split" — dQ and dK/dV in a kernel each
    (seven products), where that scratch does not fit ``DQ_VMEM_BUDGET``.
    A ``block_q`` x ``block_k`` block is one grid step (what is fetched);
    inside it the kernels walk groups of ``sub`` query rows, and in a block
    on the causal diagonal a group stops at its own edge: the keys left of
    its ``sub`` x ``sub`` square in one unmasked piece, the square masked,
    nothing to the right of it."""
    form: str
    block_q: int
    block_k: int
    sub: int

    def walk(self, diag):
        """[(q0, [(k0, keys, masked), ...])]: the groups of one block and
        the pieces of the kv block each of them meets."""
        if not diag:
            return [(q0, [(0, self.block_k, False)])
                    for q0 in range(0, self.block_q, self.sub)]
        return [(q0, ([(0, q0, False)] if q0 else []) + [(q0, self.sub, True)])
                for q0 in range(0, self.block_q, self.sub)]

    def runs(self, qi, ki, causal):
        """Whether block (qi, ki) is computed (and fetched), and whether it
        lies on the diagonal.  Python ints or traced ones."""
        return (ki <= qi, ki == qi) if causal else (True, False)

    def tiles(self, L, causal):
        """(computed, square): ``sub`` x ``sub`` tiles of score the kernels
        compute for one head against the L x L square."""
        done = 0
        for qi in range(L // self.block_q):
            for ki in range(L // self.block_k):
                run, diag = self.runs(qi, ki, causal)
                if run:
                    done += sum(keys for _, pieces in self.walk(diag)
                                for _, keys, _ in pieces) // self.sub
        return done, (L // self.sub) ** 2


# VMEM the plan may spend, in bytes as a buffer lies there (its last
# dimension padded to the 128 lanes): the fused backward's float32 dQ
# accumulator, (L, D) a head; one operand block, (block, D)
DQ_VMEM_BUDGET = 2 << 20
BLOCK_VMEM_BUDGET = 512 << 10

# tools/flash_sweep.py puts its candidates here; nothing else does
_plan_override: Optional[FlashPlan] = None


def flash_plan(L, D, causal, dtype) -> Optional[FlashPlan]:
    """The plan for (L, D) sequences of ``dtype``, or None where no block
    tiles L (the caller then takes the dense path).  From the sweep on the
    chip (tools/flash_sweep.py; PERF.md section 6, PR 37): the largest
    block that tiles L and fits — at the training cells' 1,024 x 64 and
    2,048 x 128 the whole head, one grid step and no rescaling of the
    accumulator — walked in groups of 256 queries (a block, forward +
    backward, is 18% and 9% faster than with 512-blocks there, and groups
    of 128 or 512 are 2-12% slower than 256).  ``causal`` moves nothing
    yet: the diagonal costs a non-causal call nothing."""
    if _plan_override is not None:
        return _plan_override
    lanes = max(D, 128)
    block = next((b for b in (2048, 1024, 512, 256, 128) if L % b == 0
                  and b * lanes * jnp.dtype(dtype).itemsize
                  <= BLOCK_VMEM_BUDGET), None)
    if block is None:
        return None
    form = "fused" if L * lanes * 4 <= DQ_VMEM_BUDGET else "split"
    return FlashPlan(form, block, block, min(block, 256))


def _note_plan(plan, L, causal):
    """The plan's gauges (static, so set when a program is traced)."""
    from ..utils.stats import stat_registry
    done, square = plan.tiles(L, causal)
    reg = stat_registry()
    reg.set("flash_blocks_computed", done)
    reg.set("flash_blocks_square", square)
    reg.set("flash_backward_fused", int(plan.form == "fused"))


def _dots(dtype):
    """The three products of the kernels, (a b^T, a b, a^T b), on operands
    of ``dtype`` with a float32 result.  bfloat16 operands multiply exactly
    in one MXU pass; a global "highest" default would ask Mosaic for a
    float32 product of bfloat16 vectors, which it refuses."""
    dot = functools.partial(
        lax.dot_general, preferred_element_type=jnp.float32,
        precision=(lax.Precision.DEFAULT if dtype == jnp.bfloat16
                   else lax.Precision.HIGHEST))
    return (lambda a, b: dot(a, b, (((1,), (1,)), ((), ()))),
            lambda a, b: dot(a, b, (((1,), (0,)), ((), ()))),
            lambda a, b: dot(a, b, (((0,), (0,)), ((), ()))))


def _score_tile(nt, k, q, scale, km_ref, rows, masked):
    """One key-major tile of scaled scores, (keys, queries) float32:
    ``k`` the piece's keys (``rows`` of the kv block), the additive key
    mask where there is one, and under ``masked`` (the square a group of
    queries shares with its own keys) the causal mask."""
    s = nt(k, q) * scale
    if km_ref is not None:
        s = s + km_ref[0, 0, rows][:, None]
    if masked:
        s = jnp.where(lax.broadcasted_iota(jnp.int32, s.shape, 0)
                      <= lax.broadcasted_iota(jnp.int32, s.shape, 1),
                      s, _NEG_INF)
    return s


def _on_blocks(plan, causal, qi, ki, walk):
    """Run ``walk(diag)`` for block (qi, ki) as the plan says: blocks under
    the causal diagonal with no mask arithmetic, blocks on it masked, blocks
    above it not at all (their index maps name the block already resident,
    so nothing is fetched for them either)."""
    from jax.experimental import pallas as pl
    if not causal:
        walk(False)
        return
    run, diag = plan.runs(qi, ki, True)
    pl.when(diag)(lambda: walk(True))
    pl.when(run & jnp.logical_not(diag))(lambda: walk(False))


# ---------------------------------------------------------------------------
# Pallas flash attention: forward
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, *rest, causal, scale,
                      dropout_p, has_km, plan, n_k):
    """Grid (head, q block, kv block).  The score tiles are key-major as
    the backward's (S^T = K Q^T: keys down, queries across): the running
    max and sum of a group of queries are then ONE lane vector (a column
    of them is a vreg for every eight queries, and its arithmetic costs as
    much as a score tile's), a group's q tile stays in the MXU while all
    its keys stream through, and only the rescaling of the accumulator,
    once a group and kv block, needs them as a column."""
    from jax.experimental import pallas as pl

    km_ref = rest[0] if has_km else None
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest[has_km:]
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    sub = plan.sub
    nt, _, tn = _dots(q_ref.dtype)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def walk(diag):
        for q0, pieces in plan.walk(diag):
            cols = slice(q0, q0 + sub)
            q = q_ref[0, cols]
            scores = [                                       # (keys, sub)
                _score_tile(nt, k_ref[0, k0:k0 + keys], q, scale, km_ref,
                            slice(k0, k0 + keys), masked)
                for k0, keys, masked in pieces]
            m_prev = m_ref[:, cols]                                 # (1, sub)
            m_new = functools.reduce(
                jnp.maximum,
                [jnp.max(s, axis=0, keepdims=True) for s in scores], m_prev)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_ref[:, cols]
            pv = None
            for (k0, keys, _), s in zip(pieces, scores):
                p = jnp.exp(s - m_new)
                l_new = l_new + jnp.sum(p, axis=0, keepdims=True)
                if dropout_p > 0.0:
                    keep = _dropout_keep(
                        seed_ref[0], bh, qi * plan.block_q + q0,
                        ki * plan.block_k + k0, p.shape, dropout_p)
                    p = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
                part = tn(p.astype(v_ref.dtype), v_ref[0, k0:k0 + keys])
                pv = part if pv is None else pv + part             # (sub, D)
            acc_ref[cols] = acc_ref[cols] * alpha[0][:, None] + pv
            m_ref[:, cols] = m_new
            l_ref[:, cols] = l_new

    _on_blocks(plan, causal, qi, ki, walk)

    # a q block's last contributing kv block: its diagonal one when causal
    @pl.when(ki == (qi if causal else n_k - 1))
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)                            # (1, bq)
        o_ref[0] = (acc_ref[:] / l[0][:, None]).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l)


def _flash_fwd_pallas(q, k, v, kmask, seed, causal, scale, dropout_p, plan,
                      n_heads, interpret):
    """q,k,v: (BH, L, D); kmask: (B, L) additive or None.  Returns
    (out, lse)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, L, D = q.shape
    H, bq, bk = n_heads, plan.block_q, plan.block_k
    has_km = kmask is not None
    # a skipped step (above the diagonal) names the block already resident
    kv = (lambda i, j: jnp.minimum(i, j)) if causal else (lambda i, j: j)
    # Row-stat operands (kmask, lse) ride a unit sublane dim: Mosaic requires
    # the last-two block dims be (mult-of-8, mult-of-128) or equal the array
    # dims, so (B, L) with block (1, block) is illegal while (B, 1, L) with
    # block (1, 1, block) is fine.
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # seed (1,)
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (b, kv(i, j), 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (b, kv(i, j), 0)),
    ]
    args = [seed, q, k, v]
    if has_km:
        in_specs.append(
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b // H, 0, kv(i, j))))
        args.append(kmask.reshape(kmask.shape[0], 1, L))
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, causal=causal, scale=scale,
            dropout_p=dropout_p, has_km=has_km, plan=plan, n_k=L // bk),
        name="flash_attention_fwd",
        grid=(BH, L // bq, L // bk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, L), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((1, bq), jnp.float32),
            pltpu.VMEM((1, bq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out, lse.reshape(BH, L)


# ---------------------------------------------------------------------------
# Pallas flash attention: backward (blockwise recompute from saved lse)
#
# P  = exp(S - lse)            (true softmax probs, recomputed per tile)
# Pd = keep ∘ P / (1-p)        (dropout-applied probs)
# dV = Pd^T dO
# dPd = dO V^T ;  dS = Pd ∘ dPd - P ∘ delta,   delta = rowsum(dO ∘ O)
# dQ = scale · dS K ;  dK = scale · dS^T Q
#
# The tiles are key-major (S^T = K Q^T: keys down, queries across), so lse
# and delta, stored with L on lanes, broadcast down the sublanes as they
# are, dV and dK are plain products of P^T and dS^T, and only dQ contracts
# over the tile's first dimension.
# ---------------------------------------------------------------------------

def _bwd_walk(plan, diag, refs, seed_bh, q0, k0, scale, dropout_p,
              dq_out, dkv_out):
    """The backward of one block: for each group of ``sub`` queries and each
    piece of keys it meets, the scores and dP once, then whichever of the
    three gradient products the caller takes — ``dkv_out(keys, dV, dK)``
    per piece, ``dq_out(q_offset, dQ)`` per group (None: not computed)."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, km_ref = refs
    dt = q_ref.dtype
    nt, nn, tn = _dots(dt)
    for c0, pieces in plan.walk(diag):
        cols = slice(c0, c0 + plan.sub)
        q, do = q_ref[0, cols], do_ref[0, cols]
        lse, delta = lse_ref[0, :, cols], delta_ref[0, :, cols]   # (1, sub)
        dq = None
        for r0, keys, masked in pieces:
            rows = slice(r0, r0 + keys)
            k = k_ref[0, rows]
            s = _score_tile(nt, k, q, scale, km_ref, rows, masked)
            p = jnp.exp(s - lse)                           # (keys, sub) f32
            dp = nt(v_ref[0, rows], do)
            if dropout_p > 0.0:
                keep = _dropout_keep(*seed_bh, q0 + c0, k0 + r0, p.shape,
                                     dropout_p)
                pd = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
                ds = pd * dp - p * delta
            else:
                pd = p
                ds = p * (dp - delta)
            ds = ds.astype(dt)
            if dkv_out is not None:
                dkv_out(rows, nn(pd.astype(dt), do), nn(ds, q))
            if dq_out is not None:
                part = tn(ds, k)                           # (sub, D)
                dq = part if dq is None else dq + part
        if dq_out is not None:
            dq_out(c0, dq)


def _flash_bwd_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, *rest, causal, scale, dropout_p, has_km,
                      plan, n_q, n_k, with_dq):
    """Grid (head, kv block, q block).  dK and dV accumulate over the q
    blocks of one kv block.  ``with_dq`` (the fused form): dQ accumulates
    for the whole head in ``dq_acc`` (L, D) and is written at the head's
    last step; without it this is the split form's dK/dV kernel."""
    from jax.experimental import pallas as pl

    km_ref = rest[0] if has_km else None
    outs = rest[has_km:]
    if with_dq:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = outs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = outs
    bh, ki, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bq, bk, sub = plan.block_q, plan.block_k, plan.sub

    # a kv block's first contributing q block: its diagonal one when causal
    @pl.when(qi == (ki if causal else 0))
    def _init_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def dkv_out(rows, dv, dk):
        dv_acc[rows] += dv
        dk_acc[rows] += dk

    dq_out = None
    if with_dq:
        @pl.when((ki == 0) & (qi == 0))
        def _init_dq():
            dq_acc[:] = jnp.zeros_like(dq_acc)

        def dq_out(c0, dq):
            at = pl.ds(pl.multiple_of(qi * bq + c0, sub), sub)
            dq_acc[at, :] += dq

    refs = (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, km_ref)
    _on_blocks(plan, causal, qi, ki, lambda diag: _bwd_walk(
        plan, diag, refs, (seed_ref[0], bh), qi * bq, ki * bk, scale,
        dropout_p, dq_out, dkv_out))

    @pl.when(qi == n_q - 1)
    def _write_dkv():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    if with_dq:
        @pl.when((ki == n_k - 1) & (qi == n_q - 1))
        def _write_dq():
            dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, *rest, causal, scale, dropout_p, has_km,
                         plan, n_k):
    """Split form, dQ: grid (head, q block, kv block)."""
    from jax.experimental import pallas as pl

    km_ref = rest[0] if has_km else None
    dq_ref, acc_ref = rest[has_km:]
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def dq_out(c0, dq):
        acc_ref[c0:c0 + plan.sub] += dq

    refs = (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, km_ref)
    _on_blocks(plan, causal, qi, ki, lambda diag: _bwd_walk(
        plan, diag, refs, (seed_ref[0], bh), qi * plan.block_q,
        ki * plan.block_k, scale, dropout_p, dq_out, None))

    @pl.when(ki == (qi if causal else n_k - 1))
    def _fin():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, kmask, seed, do, lse, delta, causal, scale,
                      dropout_p, plan, n_heads, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, L, D = q.shape
    H, bq, bk = n_heads, plan.block_q, plan.block_k
    n_q, n_k = L // bq, L // bk
    has_km = kmask is not None
    common = dict(causal=causal, scale=scale, dropout_p=dropout_p,
                  has_km=has_km, plan=plan)
    # unit sublane dim for row stats — see _flash_fwd_pallas
    args = [seed, q, k, v, do, lse.reshape(BH, 1, L), delta.reshape(BH, 1, L)]
    if has_km:
        args.append(kmask.reshape(kmask.shape[0], 1, L))

    def in_specs(qmap, kmap):
        """Operand specs from the grid's second and third index to the q
        block and to the kv block."""
        qspec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, qmap(i, j), 0))
        kspec = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, kmap(i, j), 0))
        stat = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, qmap(i, j)))
        specs = [pl.BlockSpec(memory_space=pltpu.SMEM),      # seed
                 qspec, kspec, kspec, qspec, stat, stat]
        if has_km:
            specs.append(pl.BlockSpec(
                (1, 1, bk), lambda b, i, j: (b // H, 0, kmap(i, j))))
        return specs

    def params(*semantics):
        return pltpu.CompilerParams(dimension_semantics=semantics)

    # a skipped step (above the diagonal) names the block already resident:
    # kv-major grids clamp the q block up, q-major ones the kv block down
    kv_major = ((lambda j, i: jnp.maximum(i, j)) if causal
                else (lambda j, i: i)), (lambda j, i: j)
    q_major = (lambda i, j: i), ((lambda i, j: jnp.minimum(i, j)) if causal
                                 else (lambda i, j: j))
    dkv_shapes = [jax.ShapeDtypeStruct((BH, L, D), k.dtype),
                  jax.ShapeDtypeStruct((BH, L, D), v.dtype)]
    dkv_specs = [pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
                 pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))]
    dkv_scratch = [pltpu.VMEM((bk, D), jnp.float32),
                   pltpu.VMEM((bk, D), jnp.float32)]

    if plan.form == "fused":
        dq, dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_kernel, n_q=n_q, n_k=n_k,
                              with_dq=True, **common),
            name="flash_attention_bwd",
            grid=(BH, n_k, n_q),
            in_specs=in_specs(*kv_major),
            out_specs=[pl.BlockSpec((1, L, D), lambda b, j, i: (b, 0, 0))]
            + dkv_specs,
            out_shape=[jax.ShapeDtypeStruct((BH, L, D), q.dtype)]
            + dkv_shapes,
            scratch_shapes=[pltpu.VMEM((L, D), jnp.float32)] + dkv_scratch,
            # each gradient takes its operand's buffer: a block of q, k or
            # v is fetched for the last time before the step that writes
            # the same rows of dq, dk or dv, so the three gradients cost no
            # memory beside the operands
            input_output_aliases={1: 0, 2: 1, 3: 2},
            compiler_params=params("parallel", "arbitrary", "arbitrary"),
            interpret=interpret,
        )(*args)
        return dq, dk, dv

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, n_k=n_k, **common),
        name="flash_attention_dq",
        grid=(BH, n_q, n_k),
        in_specs=in_specs(*q_major),
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(*args)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, n_q=n_q, n_k=n_k, with_dq=False,
                          **common),
        name="flash_attention_dkv",
        grid=(BH, n_k, n_q),
        in_specs=in_specs(*kv_major),
        out_specs=dkv_specs,
        out_shape=dkv_shapes,
        scratch_shapes=dkv_scratch,
        compiler_params=params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(*args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing (BLHD public layout)
# ---------------------------------------------------------------------------

def _to_bh(x):
    B, L, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, L, D)


def _from_bh(x, B, H):
    BH, L, D = x.shape
    return x.reshape(B, H, L, D).transpose(0, 2, 1, 3)


def _per_shard(local, mesh, q_shape, in_kinds, out_kinds):
    """``local(sharded_axes, *arrays)`` calls Pallas on per-shard arrays;
    this returns it as a function of the whole arrays.  GSPMD cannot
    partition a Pallas call, so under a mesh the kernels sit in a
    ``shard_map`` with the batch split over the data-parallel axes and the
    heads over "model" (sharding_rules.attention_specs);
    ``in_kinds``/``out_kinds`` name each operand's layout there.  With no
    mesh (or one device) ``local`` runs on the arrays as they are."""
    if mesh is None or mesh.size == 1:
        return functools.partial(local, ())
    from ..distributed.sharding_rules import attention_specs
    from ..distributed.spmd import shard_map
    specs, axes = attention_specs(mesh, q_shape[0], q_shape[2])
    return shard_map(functools.partial(local, axes), mesh=mesh,
                     in_specs=tuple(specs[kd] for kd in in_kinds),
                     out_specs=tuple(specs[kd] for kd in out_kinds),
                     check_vma=False)


def _shard_seed(seed, axes):
    """Fold this shard's mesh coordinates into the dropout seed: the
    in-kernel keep-mask hashes LOCAL (batch·head, row, col) positions, so
    without this every shard would replay shard 0's masks.  Forward and
    backward fold identically (same mesh, same axes)."""
    for ax in axes:
        seed = seed * jnp.uint32(0x9E3779B1) \
            + lax.axis_index(ax).astype(jnp.uint32) + jnp.uint32(1)
    return seed


def _masked(kinds, kmask):
    """The operands' layouts with the key mask's left out when there is
    none: no key mask is a static fact, and the kernels then neither take
    the operand nor add it."""
    return tuple(kd for kd in kinds if kd != "kmask" or kmask is not None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_attention(q, k, v, kmask, seed, causal, scale, dropout_p, plan,
                     mesh=None):
    out, _ = _flash_fwd(q, k, v, kmask, seed, causal, scale, dropout_p, plan,
                        mesh)
    return out


def _flash_fwd(q, k, v, kmask, seed, causal, scale, dropout_p, plan, mesh):
    """Returns (out (B,L,H,D), lse (B,H,L)).  ``kmask``: (B, L) or None."""
    interpret = jax.default_backend() != "tpu"

    def local(axes, q, k, v, seed, kmask=None):
        B, L, H, D = q.shape
        qkv = _to_bh(q), _to_bh(k), _to_bh(v)
        # the region and the kernels' names say what they are, wherever
        # this file moves: a trace finds the kernels by them
        with jax.named_scope("flash_attention"):
            out, lse = _flash_fwd_pallas(
                *qkv, kmask, _shard_seed(seed, axes),
                causal, scale, dropout_p, plan, H, interpret)
        return _from_bh(out, B, H), lse.reshape(B, H, L)

    args = (q, k, v, seed) + (() if kmask is None else (kmask,))
    return _per_shard(local, mesh, q.shape,
                      _masked(("qkv", "qkv", "qkv", "rep", "kmask"), kmask),
                      ("qkv", "stat"))(*args)


def _flash_fwd_rule(q, k, v, kmask, seed, causal, scale, dropout_p, plan,
                    mesh):
    out, lse = _flash_fwd(q, k, v, kmask, seed, causal, scale, dropout_p,
                          plan, mesh)
    return out, (q, k, v, kmask, seed, out, lse)


def _flash_bwd_rule(causal, scale, dropout_p, plan, mesh, res, g):
    q, k, v, kmask, seed, out, lse = res
    interpret = jax.default_backend() != "tpu"

    def local(axes, q, k, v, seed, out, lse, g, kmask=None):
        B, L, H, D = q.shape
        do = _to_bh(g)
        o = _to_bh(out)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)
        qkv = _to_bh(q), _to_bh(k), _to_bh(v)
        with jax.named_scope("flash_attention"):
            dq, dk, dv = _flash_bwd_pallas(
                *qkv, kmask, _shard_seed(seed, axes),
                do, lse.reshape(B * H, L), delta, causal, scale, dropout_p,
                plan, H, interpret)
        return (_from_bh(dq, B, H).astype(q.dtype),
                _from_bh(dk, B, H).astype(k.dtype),
                _from_bh(dv, B, H).astype(v.dtype))

    args = (q, k, v, seed, out, lse, g) + (() if kmask is None else (kmask,))
    dq, dk, dv = _per_shard(
        local, mesh, q.shape,
        _masked(("qkv", "qkv", "qkv", "rep", "qkv", "stat", "qkv", "kmask"),
                kmask),
        ("qkv", "qkv", "qkv"))(*args)
    return (dq, dk, dv, None if kmask is None else jnp.zeros_like(kmask),
            None)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal=False, scale=None, key_mask=None,
                    dropout_p=0.0, dropout_seed=None, mesh=None):
    """Public flash attention on raw arrays, (B,L,H,D).

    mesh: the ``jax.sharding.Mesh`` the caller's program is partitioned
    over, when it is — the Pallas kernels then run on each device's own
    batch rows and heads (see _per_shard); the dense path needs no mesh.

    key_mask: optional additive mask over keys, shape (B, Lk) (or any shape
    reshapeable to it, e.g. the BERT (B,1,1,Lk) padding mask).  dropout_p
    applies to attention probabilities; dropout_seed (uint32 scalar) selects
    the deterministic in-kernel keep-mask.

    The form of the backward and the block sizes come from ``flash_plan``,
    a pure function of the shape; a shape it cannot serve (no block tiles
    L, ``q.shape != k.shape``) takes the dense path.

    Limitation: key_mask is treated as a constant — its cotangent on the
    Pallas path is zero.  Do not feed a *learned* additive bias through
    key_mask; use dense_attention(mask=...) for differentiable biases.
    """
    B, L, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if dropout_p > 0.0 and dropout_seed is None:
        # a silent default seed would replay one fixed keep-mask every step
        # (and the dense fallback would apply no dropout at all)
        raise ValueError("dropout_p > 0 requires dropout_seed (vary it per "
                         "step, e.g. jax.random.bits(key, (), jnp.uint32))")
    plan = flash_plan(L, D, causal, q.dtype) \
        if _use_pallas() and q.shape == k.shape else None
    if plan is not None:
        _note_plan(plan, L, causal)
        kmask = None if key_mask is None \
            else key_mask.reshape(B, L).astype(jnp.float32)
        seed = (jnp.zeros((1,), jnp.uint32) if dropout_seed is None
                else jnp.asarray(dropout_seed, jnp.uint32).reshape(1))
        return _flash_attention(q, k, v, kmask, seed, causal, scale,
                                float(dropout_p), plan, mesh)
    mask4 = None if key_mask is None else \
        key_mask.reshape(B, 1, 1, k.shape[1]).astype(jnp.float32)
    dkey = None
    if dropout_p > 0.0 and dropout_seed is not None:
        dkey = jax.random.PRNGKey(jnp.asarray(dropout_seed, jnp.uint32).reshape(()))
    return dense_attention(q, k, v, mask=mask4, causal=causal, scale=scale,
                           dropout_p=dropout_p, dropout_key=dkey)


def _is_key_padding_mask(m, B, Lk) -> bool:
    """True for masks that broadcast over heads and query rows: (B,1,1,Lk),
    (1,1,1,Lk) or (B,1,Lk).  A 2-D (B,Lk) mask is deliberately NOT accepted:
    it is ambiguous with a (Lq,Lk) positional mask when B == Lq, which dense
    attention broadcasts over batch — different semantics."""
    if m is None:
        return False
    shape = tuple(m.shape)
    return shape in ((B, 1, 1, Lk), (1, 1, 1, Lk), (B, 1, Lk))


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None):
    """Tensor-level entry (BLHD), used by nn.MultiHeadAttention / F.sdpa."""
    from ..core import rng
    B, Lk = key.shape[0], key.shape[1]
    raw_mask = getattr(attn_mask, "_data", attn_mask)
    dropout_key = None
    p = dropout_p if training else 0.0
    if p > 0.0:
        dropout_key = rng.next_key()

    if raw_mask is None or _is_key_padding_mask(raw_mask, B, Lk):
        def f(q, k, v, m, dk):
            seed = None if dk is None else \
                jax.random.bits(dk, (), jnp.uint32)
            km = None if m is None else jnp.broadcast_to(
                m.astype(jnp.float32).reshape(m.shape[0], Lk), (B, Lk))
            return flash_attention(q, k, v, causal=is_causal, key_mask=km,
                                   dropout_p=p, dropout_seed=seed)
        return apply(f, query, key, value, attn_mask,
                     None if dropout_key is None else Tensor(dropout_key))

    def f(q, k, v, m, dk):
        return dense_attention(q, k, v, mask=m, causal=is_causal,
                               dropout_p=p if dk is not None else 0.0,
                               dropout_key=dk)
    return apply(f, query, key, value, attn_mask,
                 None if dropout_key is None else Tensor(dropout_key))
