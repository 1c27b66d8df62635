"""Continuous-batching serving engine for the causal decoder stack.

No reference counterpart at this granularity — the reference snapshot's
decode machinery is MultiHeadAttention.Cache incremental k/v
(python/paddle/nn/layer/transformer.py:151) driven whole-batch by
BeamSearchDecoder/dynamic_decode (python/paddle/nn/decode.py): batches are
admitted and retired together.  (The later-Paddle ecosystem adds
fused_multi_transformer CacheKV serving — not in this snapshot.)  This engine
is the TPU-native upgrade: requests join and leave a running decode batch at
any step (the JetStream/Orca "continuous batching" discipline), while every
device program stays STATIC-shape so XLA compiles each signature exactly
once:

- one global KV cache of ``max_slots`` rows (a slot = one in-flight request,
  layout (num_layers, S, max_len, nh, hd) — slot is the batch index);
- admission runs a per-bucket prefill program that writes ONE slot's cache
  region (prompts are left-padded to the bucket length; the mixin's
  ``pad_lens`` machinery masks pad keys and shifts positions);
- every decode tick is ONE compiled step over all S slots with per-row cache
  clocks (``write_cache``/``cached_attention`` per-row ``t`` — the same
  scatter form batched speculative decoding uses); inactive slots are
  carried inert: their clock is frozen and their stale writes land at
  positions a future occupant overwrites before it can ever read them
  (decode at position u writes u before attending ≤ u).

Typical use::

    eng = ContinuousBatchingEngine(model, params, max_slots=8, max_len=256)
    rid = eng.add_request([12, 71, 9], max_new_tokens=32)
    while eng.pending():          # interleaves admission + batched decode
        eng.step()
    out = eng.pop_finished()[rid]

Greedy by default; temperature/top-k/top-p sampling share the engine key.
"""

from __future__ import annotations

import contextlib
import itertools
import logging
import time
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .jit.bucketing import select_bucket
from .telemetry import (PART_CALL, PART_KEY, PART_OPERANDS, PHASE_ADMIT,
                        PHASE_DISPATCH, PHASE_PACK, PHASE_SYNC, PHASE_UNPACK,
                        program_label)
from .utils.stats import StatRegistry, stat_add
from .utils.stats import prometheus_text as _prometheus_text
from .models._decode import (apply_repetition_penalty, make_row_sampler,
                             make_token_sampler, seed_presence,
                             suppress_eos, suppress_eos_rows,
                             validate_sampler_args)

__all__ = ["ContinuousBatchingEngine", "Request"]


def _no_part(name):
    pass


# what _step_impl enters for each phase of a round when no tracer is
# attached: one shared object that does nothing, and hands out a part()
# that does nothing
_NO_PHASE = contextlib.nullcontext(_no_part)


def _no_phase(name):
    return _NO_PHASE


def _timed_first_dispatch(run, cb):
    """Wrap a freshly built program so its FIRST invocation — the one that
    pays trace + XLA compile — is timed end-to-end (block_until_ready) and
    reported through ``cb(seconds, args, kwargs)`` (the call operands ride
    along so the callback can re-lower for cost attribution).  Only
    installed when a tracer is attached at build time; later invocations
    are one bool check."""
    state = [False]

    def wrapped(*a, **kw):
        if state[0]:
            return run(*a, **kw)
        t0 = time.perf_counter()
        out = run(*a, **kw)
        jax.block_until_ready(out)
        state[0] = True
        cb(time.perf_counter() - t0, a, kw)
        return out

    return wrapped


def _program_cost(run, a, kw):
    """Best-effort XLA cost analysis for a jitted program at its observed
    call signature: re-lower (cheap) and consult the process-wide
    digest-keyed cost cache (hapi/dynamic_flops — ONE compile per
    distinct program per process).  None on any failure; never raises —
    MFU attribution must not break serving."""
    try:
        from .hapi.dynamic_flops import cost_of_lowered
        return cost_of_lowered(run.lower(*a, **kw))
    except Exception:  # noqa: BLE001 — best-effort telemetry only
        logging.getLogger(__name__).debug(
            "serving cost attribution failed", exc_info=True)
        return None


def _slot_write(slot):
    """Tree-mapper writing one slot's region of a global cache leaf
    (rank-generic: int8 caches pair a 5D value plane with a 4D scale
    plane; slot is the batch dim at axis 1)."""
    def put(big, new):
        return jax.lax.dynamic_update_slice(
            big, new.astype(big.dtype), (0, slot) + (0,) * (big.ndim - 2))
    return put


class Request:
    """One in-flight generation request (host-side bookkeeping)."""

    def __init__(self, rid: int, prompt: List[int], max_new_tokens: int,
                 due_at: Optional[float] = None):
        self.id = rid
        self.prompt = list(prompt)
        self.max_new_tokens = int(max_new_tokens)
        self.generated: List[int] = []
        self.done = False
        self.enqueued_at = time.monotonic()
        # where TTFT and latency count from: the time the caller says the
        # request was due, else the time it was queued
        self.due_at = self.enqueued_at if due_at is None else float(due_at)
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.on_token = None          # optional streaming callback

    def __repr__(self):
        return (f"Request(id={self.id}, prompt_len={len(self.prompt)}, "
                f"generated={len(self.generated)}, done={self.done})")


class ContinuousBatchingEngine:
    """Slot-scheduled continuous batching over a CausalDecoderMixin model.

    ``max_slots`` bounds concurrent requests; ``max_len`` bounds
    prompt+generation length per request (one request's logical positions
    must also fit max_position_embeddings).  ``prompt_buckets`` quantizes
    admission prefills so the number of compiled prefill programs is
    len(buckets), not len(distinct prompt lengths).
    """

    def __init__(self, model, params, max_slots: int, max_len: int,
                 prompt_buckets=None, temperature: float = 1.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 greedy: bool = True, eos_token_id: Optional[int] = None,
                 key=None, ticks_per_sync: int = 1, mesh=None,
                 repetition_penalty: float = 1.0, min_new_tokens: int = 0,
                 prefill_chunk: Optional[int] = None,
                 per_request_sampling: bool = False, tracer=None):
        """``ticks_per_sync``: decode ticks fused into one device program
        between host synchronizations.  1 = retire/admit after every token
        (lowest latency); k > 1 amortizes the host round-trip over k tokens
        — tokens a request emits past its EOS/budget inside a chunk are
        discarded host-side (wasted compute < k per request), and a slot
        retires when it lacks room for a FULL chunk, stranding at most k-1
        cache positions.  Greedy outputs are identical for any k.

        ``mesh``: optional ``jax.sharding.Mesh`` with a "model" axis for
        tensor-parallel serving — params are placed by their
        ``_dims_mapping`` specs (the same metadata the training path uses)
        and the KV cache shards over the heads dim; GSPMD inserts the TP
        collectives in the prefill/decode programs exactly as it does for
        training.

        ``repetition_penalty`` / ``min_new_tokens``: the generate()
        processors, engine-wide — a per-slot (S, V) presence plane rides
        next to the KV cache (reset and seeded by admission prefill), and
        EOS windows are per-row (each request's own emission count).

        ``prefill_chunk``: admission prefills at most this many prompt
        positions per scheduler round (must divide every bucket), so one
        long prompt cannot stall every running request's decode for a full
        prefill — the head-of-line latency fix.  None = whole-bucket
        prefill in one round.

        ``tracer``: optional ``paddle_tpu.telemetry.Tracer``; when set the
        engine emits per-tick, per-compile, and per-request structured
        events (host-side only — compiled programs are identical with or
        without it).  None (default) keeps the scheduler hot path at a
        single attribute check: no event allocation, no tracer lock."""
        c = model.config
        if max_len > c.max_position_embeddings:
            raise ValueError(f"max_len {max_len} exceeds "
                             f"max_position_embeddings "
                             f"({c.max_position_embeddings})")
        self._key = key if key is not None else jax.random.key(0)
        validate_sampler_args(c.vocab_size, top_k, top_p, greedy,
                              None if greedy else self._key)
        self.model = model
        self.params = params
        self.S = int(max_slots)
        self.max_len = int(max_len)
        if prompt_buckets is None:
            prompt_buckets = [b for b in (16, 32, 64, 128, 256, 512, 1024)
                              if b <= max_len] or [int(max_len)]
        self.buckets = sorted(set(int(b) for b in prompt_buckets))
        self.eos_token_id = eos_token_id
        self.ticks_per_sync = int(ticks_per_sync)
        if self.ticks_per_sync < 1:
            raise ValueError("ticks_per_sync must be >= 1")
        self.prefill_chunk = None if prefill_chunk is None \
            else int(prefill_chunk)
        if self.prefill_chunk is not None:
            if self.prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1")
            # only buckets that actually chunk (b > chunk) need to divide;
            # smaller buckets take the whole-bucket path untouched
            chunked = [b for b in self.buckets if b > self.prefill_chunk]
            bad = [b for b in chunked if b % self.prefill_chunk]
            if bad:
                raise ValueError(
                    f"prefill_chunk ({self.prefill_chunk}) must divide "
                    f"every prompt bucket it chunks; doesn't divide {bad}")
            if chunked and max(chunked) + self.ticks_per_sync > self.max_len:
                # a filling slot's stale decode writes park in the strip
                # [max_len - ticks_per_sync, max_len); it must sit ABOVE
                # the largest chunked bucket or parking would clobber the
                # prompt region being filled (see _admit)
                raise ValueError(
                    f"chunked prefill needs max_len >= largest chunked "
                    f"bucket ({max(chunked)}) + ticks_per_sync "
                    f"({self.ticks_per_sync}) as a stale-write parking "
                    f"strip; max_len is {self.max_len}")
        self.repetition_penalty = float(repetition_penalty)
        self.min_new_tokens = int(min_new_tokens)
        if self.repetition_penalty <= 0:
            raise ValueError("repetition_penalty must be > 0")
        if self.min_new_tokens > 0 and eos_token_id is None:
            raise ValueError("min_new_tokens needs eos_token_id")
        if eos_token_id is not None and \
                not 0 <= eos_token_id < c.vocab_size:
            raise ValueError(f"eos_token_id {eos_token_id} outside vocab "
                             f"(size {c.vocab_size})")
        self._track = self.repetition_penalty != 1.0
        self._sample_sig = (float(temperature),
                            None if top_k is None else int(top_k),
                            None if top_p is None else float(top_p), greedy,
                            self.repetition_penalty, self.min_new_tokens,
                            eos_token_id if self.min_new_tokens > 0 else None)
        self._sample = make_token_sampler(*self._sample_sig[:4])
        self.per_request = bool(per_request_sampling)
        # classic mode: the ctor knobs ARE the engine-wide sampler, and
        # greedy=True argmax ignores them — same silent mis-serve the
        # add_request guard closes (ADVICE r5).  NEUTRAL values pass
        # (temperature=1.0, top_p=1.0 — clients forwarding their defaults
        # are not asking for sampling).  Per-request mode is exempt: there
        # the knobs are request DEFAULTS a greedy=False request may
        # legitimately inherit.
        if not self.per_request and greedy and (
                top_k is not None
                or (top_p is not None and float(top_p) != 1.0)
                or float(temperature) != 1.0):
            raise ValueError(
                "temperature/top_k/top_p have no effect under greedy "
                "decoding (the engine default) — pass greedy=False to "
                "sample, or drop the knobs")
        if self.per_request:
            # sampler config becomes per-slot DATA (S-row planes, traced
            # operands): the ctor args are the defaults a request may
            # override per call — matching generate()'s per-call contract —
            # and the compiled program count stays mode-wide, not
            # config-wide.  Presence tracking is always on (any request
            # may carry a penalty).
            self._track = True
            self._row_sample = make_row_sampler()
            self._plane_defaults = (
                float(temperature),
                0 if top_k is None else int(top_k),
                2.0 if top_p is None else float(top_p),
                bool(greedy), self.repetition_penalty,
                self.min_new_tokens,
                -1 if eos_token_id is None else int(eos_token_id))
            self._r_temp = np.ones(self.S, np.float32)
            self._r_topk = np.zeros(self.S, np.int32)
            self._r_topp = np.full(self.S, 2.0, np.float32)
            self._r_greedy = np.ones(self.S, bool)
            self._r_rp = np.ones(self.S, np.float32)
            self._r_minnew = np.zeros(self.S, np.int32)
            self._r_eos = np.full(self.S, -1, np.int32)
        self._presence = (jnp.zeros((self.S, c.vocab_size), bool)
                          if self._track else None)

        self.mesh = mesh
        if mesh is None:
            self.caches = self._alloc_caches()
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from .distributed.spmd import build_param_specs
            specs = build_param_specs(params, mesh, layer=model)
            self.params = {name: jax.device_put(
                v, NamedSharding(mesh, specs[name]))
                for name, v in params.items()}
            nh = c.num_attention_heads
            mp = mesh.shape.get("model", 1)
            shard_heads = mp > 1 and nh % mp == 0
            if mp > 1 and not shard_heads:
                import warnings
                warnings.warn(
                    f"num_attention_heads ({nh}) is not divisible by the "
                    f"model axis ({mp}): the KV cache falls back to full "
                    f"replication — per-device memory is {mp}x the "
                    f"sharded size", UserWarning)

            def leaf_spec(leaf):
                # heads is dim 3 of both the (L,S,T,nh,hd) value plane and
                # the (L,S,T,nh) int8 scale plane
                if not shard_heads:
                    return NamedSharding(mesh, P())
                entries = [None] * leaf.ndim
                entries[3] = "model"
                return NamedSharding(mesh, P(*entries))

            # allocate the cache SHARDED from the start — a transient
            # replicated (L, S, max_len, nh, hd) buffer on one device is
            # exactly the allocation TP serving exists to avoid
            shapes = jax.eval_shape(
                lambda: model.init_cache(self.S, self.max_len))
            # tpulint: disable=jit-in-hot-loop(one-shot sharded alloc at engine construction, never on the request path)
            self.caches = jax.jit(
                lambda: model.init_cache(self.S, self.max_len),
                out_shardings=jax.tree.map(leaf_spec, shapes))()
        # per-slot host state
        self._slot_req: List[Optional[Request]] = [None] * self.S
        self._t = np.zeros(self.S, np.int32)         # next physical slot
        self._pad = np.zeros(self.S, np.int32)       # left-pad length
        self._tok = np.zeros(self.S, np.int32)       # last sampled token
        self._active = np.zeros(self.S, bool)
        self._filling: Dict[int, dict] = {}          # slot -> chunked state

        self._queue: List[Request] = []
        self._finished: Dict[int, List[int]] = {}
        self._ids = itertools.count()
        # observability: a PRIVATE registry per engine (concurrent engines
        # must not alias counters) feeding metrics()/prometheus_text();
        # plain ints for the compile counters (they sit on the program-fetch
        # path and need no lock under the GIL)
        self.tracer = tracer
        self._stats = StatRegistry()
        self._started = time.monotonic()
        self._compile_hits = 0
        self._compile_misses = 0
        self._tick_note: Dict[str, object] = {}
        self._memory = None          # telemetry_memory.MemoryLedger

    def _alloc_caches(self):
        """Cache storage seam: the contiguous engine allocates one
        (L, S, max_len, nh, hd) row per slot; the paged subclass replaces
        this with a block pool + tables (serving_paged.py)."""
        return self.model.init_cache(self.S, self.max_len)

    # ---------------------------------------------------------- programs --

    @property
    def _sig(self):
        """Program-cache signature: engines with identical shapes and
        sampler config share compiled programs via the MODEL (the
        _gen_program pattern) — constructing a fresh engine per request
        wave must not recompile.  In per-request mode the sampler config is
        DATA (planes), so the signature carries only the mode marker —
        engines with different defaults share programs."""
        samp = ("perreq",) if self.per_request else self._sample_sig
        return (self.S, self.max_len, self.ticks_per_sync, samp)

    def _plane_operands(self):
        """The per-slot sampling planes as one traced operand (empty tuple
        in classic mode — a pytree with no leaves, so program signatures
        stay uniform across modes)."""
        if not self.per_request:
            return ()
        return (jnp.asarray(self._r_temp), jnp.asarray(self._r_topk),
                jnp.asarray(self._r_topp), jnp.asarray(self._r_greedy),
                jnp.asarray(self._r_rp), jnp.asarray(self._r_minnew),
                jnp.asarray(self._r_eos))

    def _cached_prog(self, cache_key, build):
        """Model-level compiled-program cache (see _sig), instrumented:
        every fetch counts a hit or miss, and with a tracer attached a
        miss's first dispatch is wall-timed — recompile storms become
        visible, warnable events instead of silent bench sinkholes."""
        progs = self.model.__dict__.setdefault("_serving_programs", {})
        if cache_key in progs:
            return self._note_prog(cache_key, True, progs[cache_key])
        run = build()
        # the BARE program goes in the model-lifetime cache; only the
        # engine-local return is timing-wrapped — a wrapper in the cache
        # would pin this engine's tracer for the model's lifetime and
        # misroute a later engine's first dispatch to it
        progs[cache_key] = run
        return self._note_prog(cache_key, False, run)

    def _note_prog(self, key, hit: bool, run=None):
        """Compile-cache accounting: bump the engine counters (always —
        two lock-free int adds), and with a tracer attached emit a compile
        event; a miss returns ``run`` wrapped so its first dispatch
        reports the compile wall time.  With ``tracer.attribute_cost``
        the first dispatch additionally records the program's XLA
        cost-analysis FLOPs/bytes (digest-cached process-wide) — on
        misses AND on model-cache hits whose label has no cost yet (a
        fresh engine over a warm model still gets MFU attribution)."""
        if hit:
            self._compile_hits += 1
        else:
            self._compile_misses += 1
        tr = self.tracer
        if tr is None:
            return run
        label = program_label(key)
        self._tick_note.setdefault("programs", []).append(label)
        name = type(self).__name__
        if hit:
            tr.compile_event(name, key, True)
            if tr.attribute_cost and not tr.has_cost(label):
                # a zero sentinel on probe failure stops re-probing the
                # same label on every later fetch
                return _timed_first_dispatch(
                    run, lambda dt, a, kw: tr.record_cost(
                        label, _program_cost(run, a, kw)
                        or {"flops": 0.0, "bytes": 0.0}))
            return run

        def report(dt, a, kw):
            cost = (_program_cost(run, a, kw)
                    if tr.attribute_cost else None)
            tr.compile_event(name, key, False, dt, cost=cost)

        return _timed_first_dispatch(run, report)

    def attach_ledger(self, ledger):
        """Route this engine's wall-clock into a ``telemetry_ledger
        .RunLedger``: scheduler-tick walls feed the ``compute`` bucket and
        compile-miss walls feed ``compile``, through the attached tracer's
        event stream (``Tracer.set_ledger``) — the goodput accounting for
        a serving process.  Requires a ``tracer=``; the ledger consumes
        tracer events rather than adding a second instrumentation layer."""
        if self.tracer is None:
            raise ValueError(
                "attach_ledger needs a tracer: construct the engine with "
                "tracer=Tracer() — the ledger consumes its event stream")
        self.tracer.set_ledger(ledger)
        return ledger

    def attach_memory(self, ledger):
        """Register this engine's device arrays with a
        ``telemetry_memory.MemoryLedger``: params → the ``params`` pool,
        the KV caches → ``kv_pages`` (the hbm tier of the census).
        ``metrics()`` then carries ``memory_device_bytes`` /
        ``memory_host_bytes``.  Tick programs rebuild the caches
        functionally, so their registration goes stale between ticks —
        call :meth:`refresh_memory` before a census (the bench/ops
        pattern); steady-state ticks stay untouched."""
        self._memory = ledger
        if self.tracer is not None and getattr(ledger, "_tracer", None) \
                is None:
            ledger.set_tracer(self.tracer)
        self.refresh_memory()
        return ledger

    def refresh_memory(self):
        """Re-register params + current KV caches with the attached
        memory ledger (no-op without one — one attribute check)."""
        ml = self._memory
        if ml is None:
            return
        ml.register_tree("params", self.params,
                         name=f"engine{id(self)}.params")
        caches = getattr(self, "caches", None)
        if caches is not None:
            ml.register_tree("kv_pages", caches,
                             name=f"engine{id(self)}.kv")

    def _note(self, key: str, value=1):
        """Accumulate one per-tick telemetry field (no-op when tracing is
        off — a single attribute check)."""
        if self.tracer is None:
            return
        self._tick_note[key] = self._tick_note.get(key, 0) + value

    def _phases(self):
        """``tracer.phase`` with a tracer attached, else the shared no-op:
        what ``_step_impl`` brackets each phase of the round with."""
        tr = self.tracer
        return _no_phase if tr is None else tr.phase

    def _first_token_tail(self):
        """The first-token sampling sequence (penalty → EOS window → draw →
        presence update) shared by whole-bucket prefill and the last
        prefill segment — ONE copy, so the two admission paths cannot
        drift (test_chunked_prefill_matches_whole_prefill pins it)."""
        sample = self._sample
        track = self._track
        rp, min_new, eos = self._sample_sig[4:]
        model = self.model
        if self.per_request:
            row_sample = self._row_sample

            def tail(params, h_last, presence, slot, key, planes=()):
                temp, topk, topp, greedy, rpv, mnv, eosv = planes
                l2 = model.decode_logits(params, h_last)[:, -1]
                l2 = apply_repetition_penalty(l2, presence[slot][None],
                                              rpv[slot][None])
                # first token: emitted count is 0, window open iff mn > 0
                l2 = suppress_eos_rows(l2, eosv[slot][None],
                                       (mnv[slot] > 0)[None])
                tok = row_sample(l2[:, None, :], key, temp[slot][None],
                                 topk[slot][None], topp[slot][None],
                                 greedy[slot][None])[0]
                presence = presence.at[slot, tok].set(True)
                return tok, presence
            return tail

        def tail(params, h_last, presence, slot, key, planes=()):
            l2 = model.decode_logits(params, h_last)[:, -1]
            if track:
                l2 = apply_repetition_penalty(l2, presence[slot][None], rp)
            if min_new > 0:
                l2 = suppress_eos(l2, eos, jnp.bool_(True))  # emitted 0
            tok = sample(l2[:, None, :], key)[0]
            if track:
                presence = presence.at[slot, tok].set(True)
            return tok, presence
        return tail

    def _prefill_prog(self, P: int):
        """Prefill ONE request (left-padded to bucket length P) directly
        into slot ``slot`` of the global cache; returns the first token."""
        return self._cached_prog(("prefill", P, self._sig),
                                 lambda: self._build_prefill(P))

    def _build_prefill(self, P: int):
        model = self.model
        track = self._track
        V = model.config.vocab_size
        tail = self._first_token_tail()

        @partial(jax.jit, donate_argnums=(1, 2, 7))
        def run(params, big_ck, big_cv, ids, pad_len, slot, key, presence,
                planes):
            h, (ck, cv) = model.prefill(params, ids, P,
                                        pad_lens=pad_len[None],
                                        mesh=self.mesh)

            put = _slot_write(slot)
            big_ck = jax.tree.map(put, big_ck, ck)
            big_cv = jax.tree.map(put, big_cv, cv)
            if track:
                # reset + seed the slot's presence row from the prompt
                row = seed_presence(ids, V, pad_len[None])
                presence = jax.lax.dynamic_update_slice(
                    presence, row, (slot, 0))
            tok, presence = tail(params, h[:, -1:], presence, slot, key,
                                 planes)
            return big_ck, big_cv, tok, presence

        return run

    def _seg_prog(self, seg: int, first: bool, last: bool):
        """One prefill SEGMENT for one slot: embed ``seg`` prompt tokens at
        [t0, t0+seg), write the slot's cache region via the chunk decode
        path (cached_attention's k-query form — the same machinery as
        speculative verification), and on the last segment sample the first
        token.  Only the slot's cache row is computed on (sliced out and
        written back), so a segment costs B=1 work, not B=S."""
        return self._cached_prog(
            ("seg", seg, first, last, self._sig),
            lambda: self._build_seg(seg, first, last))

    def _build_seg(self, seg: int, first: bool, last: bool):
        model = self.model
        track = self._track
        V = model.config.vocab_size
        tail = self._first_token_tail()

        @partial(jax.jit, donate_argnums=(1, 2, 7))
        def run(params, big_ck, big_cv, toks, t0, pad, slot, presence, key,
                planes):
            take = lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1)
            ck_s = jax.tree.map(take, big_ck)
            cv_s = jax.tree.map(take, big_cv)
            h = model._embed_chunk(params, toks[0], t0, pad_lens=pad[None])
            h, (ck_s, cv_s) = model.decode_step(params, h, (ck_s, cv_s), t0,
                                                pad_lens=pad[None])

            put = _slot_write(slot)
            big_ck = jax.tree.map(put, big_ck, ck_s)
            big_cv = jax.tree.map(put, big_cv, cv_s)
            if track:
                if first:
                    presence = jax.lax.dynamic_update_slice(
                        presence, jnp.zeros((1, V), bool), (slot, 0))
                valid = t0 + jnp.arange(seg) >= pad     # pads: segment 0
                row = presence[slot].at[toks[0]].max(valid)
                presence = jax.lax.dynamic_update_slice(
                    presence, row[None], (slot, 0))
            tok = jnp.int32(0)
            if last:
                tok, presence = tail(params, h[:, -1:], presence, slot, key,
                                     planes)
            return big_ck, big_cv, tok, presence

        return run

    def _decode_prog_all(self):
        """``ticks_per_sync`` decode ticks over all S slots (per-row cache
        clocks), one host sync: returns the (k, S) token block."""
        return self._cached_prog(("decode", self._sig), self._build_decode)

    def _make_decode_tick(self):
        """One decode tick over all S slots (embed → decode_step → process →
        sample → presence), shared by the contiguous and paged decode
        programs so the scheduling semantics cannot drift between cache
        layouts.  ``caches`` inside the tick is whatever layout the calling
        program scans over (the paged program passes the gathered logical
        view)."""
        model = self.model
        sample = self._sample
        track = self._track
        rp, min_new, eos = self._sample_sig[4:]
        S = self.S
        per_request = self.per_request
        row_sample = self._row_sample if per_request else None

        def tick(carry, i, params, ts, pads, active, emitted0, planes=()):
            big_ck, big_cv, tok, key, presence = carry
            h = model._embed_one(params, tok, ts + i, pad_lens=pads)
            h, (big_ck, big_cv) = model.decode_step(
                params, h, (big_ck, big_cv), ts + i, pad_lens=pads)
            key, sub = jax.random.split(key)
            l2 = model.decode_logits(params, h)[:, -1]
            if per_request:
                temp, topk, topp, greedy, rpv, mnv, eosv = planes
                l2 = apply_repetition_penalty(l2, presence, rpv)
                l2 = suppress_eos_rows(l2, eosv, emitted0 + i < mnv)
                ntok = row_sample(l2[:, None, :], sub, temp, topk, topp,
                                  greedy)
            else:
                if track:
                    l2 = apply_repetition_penalty(l2, presence, rp)
                if min_new > 0:
                    # per-row window: each request's own emission count
                    l2 = suppress_eos(l2, eos, emitted0 + i < min_new)
                ntok = sample(l2[:, None, :], sub)
            # inactive slots carry their token unchanged (their stale
            # cache writes are never read — see module docstring)
            ntok = jnp.where(active, ntok, tok)
            if track:
                # bool max == set-only-where-active: an INACTIVE slot's
                # ntok is a stale carried token (previous occupant, or a
                # chunk-filling request's segment-0-reset row) — marking
                # it would poison the next occupant's penalty plane
                presence = presence.at[jnp.arange(S), ntok].max(active)
            return (big_ck, big_cv, ntok, key, presence), ntok

        return tick

    def _build_decode(self):
        k_ticks = self.ticks_per_sync
        tick = self._make_decode_tick()

        @partial(jax.jit, donate_argnums=(1, 2, 8))
        def run(params, big_ck, big_cv, toks, ts, pads, active, key,
                presence, emitted0, planes):
            (big_ck, big_cv, _, _, presence), toks_out = jax.lax.scan(
                lambda c, i: tick(c, i, params, ts, pads, active, emitted0,
                                  planes),
                (big_ck, big_cv, toks, key, presence),
                jnp.arange(k_ticks))
            return big_ck, big_cv, toks_out, presence      # toks (k, S)

        return run

    # ------------------------------------------------------------- warmup --

    def compile_grid(self) -> List[str]:
        """Labels of every program family this engine can dispatch — the
        declared compile grid the AOT warmup planner precompiles
        (jit/aot.py; docs/COMPILATION.md)."""
        return [t.label for t in self._warmup_tasks()]

    def warmup(self, cache_dir=None, max_workers: int = 1,
               block: bool = True):
        """Precompile the engine's full program grid BEFORE traffic, so no
        request ever pays an XLA compile stall on the serving path.

        ``cache_dir``: also wires jax's persistent compilation cache there,
        making the compiles durable across processes — a later engine (or
        restart) warming against the same directory re-traces but skips
        XLA, and its compile events carry ``provenance: disk``.
        ``block=False`` runs on a background thread and returns the report
        Future (``jit.aot.warmup_async``); requests admitted mid-warmup
        simply compile what they need first.

        Each task dispatches against freshly allocated scratch caches
        (donated and freed immediately), a constant key, and zeroed
        metadata: live engine state, the sampling key stream, and request
        outputs are untouched — a warmed engine serves token-for-token
        what an unwarmed one would.  Transient memory: each IN-FLIGHT
        task holds one scratch cache allocation, so peak extra HBM is
        ``max_workers`` cache copies on top of the live cache — keep the
        default ``max_workers=1`` on memory-tight configs.  With a tracer
        attached the run sits in an ``expected_compiles`` window (compile
        events tagged, storm warning ignores them)."""
        if self.mesh is not None:
            # scratch caches come from _alloc_caches (host layout); the TP
            # engine's live caches are mesh-sharded, so a scratch dispatch
            # would compile a DIFFERENT program than serving uses — worse
            # than no warmup (it hides the stall behind a false green)
            raise NotImplementedError(
                "warmup v1 is single-mesh; TP serving engines compile on "
                "first dispatch (persistent-cache reuse still applies via "
                "jit.aot.enable_persistent_compilation_cache)")
        from .jit.aot import run_warmup, warmup_async
        tasks = self._warmup_tasks()
        kw = dict(tracer=self.tracer, cache_dir=cache_dir,
                  max_workers=max_workers)
        if block:
            return run_warmup(tasks, **kw)
        return warmup_async(tasks, **kw)

    def _prefill_seg_tasks(self):
        """Prefill-bucket + chunked-seg warmup tasks — ONE enumeration
        shared by the contiguous and paged grids (the paged engine
        overrides only the dispatch helpers and its decode family), so
        the two engines' seg-variant sets cannot drift."""
        from .jit.aot import WarmupTask
        tasks = []
        chunk = self.prefill_chunk
        for P in self.buckets:
            if chunk is not None and P > chunk:
                continue                  # chunked buckets use seg programs
            tasks.append(WarmupTask(f"prefill:{P}",
                                    partial(self._warmup_prefill, P)))
        if chunk is not None:
            combos = sorted({(i == 0, i == P // chunk - 1)
                             for P in self.buckets if P > chunk
                             for i in range(P // chunk)})
            for first, last in combos:
                tasks.append(WarmupTask(
                    f"seg:{chunk}:{int(first)}{int(last)}",
                    partial(self._warmup_seg, first, last)))
        return tasks

    def _warmup_tasks(self):
        from .jit.aot import WarmupTask
        tasks = self._prefill_seg_tasks()
        tasks.append(WarmupTask("decode", self._warmup_decode))
        return tasks

    def _scratch_presence(self):
        return None if self._presence is None \
            else jnp.zeros_like(self._presence)

    @staticmethod
    def _warmup_key():
        # constant: warmup must not advance the engine's sampling stream
        # (a warmed sampled engine draws the same tokens as an unwarmed one)
        return jax.random.key(0)

    def _warmup_prefill(self, P: int):
        run = self._prefill_prog(P)
        ck, cv = self._alloc_caches()
        jax.block_until_ready(run(
            self.params, ck, cv, jnp.zeros((1, P), jnp.int32),
            jnp.int32(0), jnp.int32(0), self._warmup_key(),
            self._scratch_presence(), self._plane_operands()))

    def _warmup_seg(self, first: bool, last: bool):
        seg = self.prefill_chunk
        run = self._seg_prog(seg, first, last)
        ck, cv = self._alloc_caches()
        jax.block_until_ready(run(
            self.params, ck, cv, jnp.zeros((1, seg), jnp.int32),
            jnp.int32(0), jnp.int32(0), jnp.int32(0),
            self._scratch_presence(), self._warmup_key(),
            self._plane_operands()))

    def _warmup_decode(self):
        run = self._decode_prog_all()
        ck, cv = self._alloc_caches()
        z = jnp.zeros(self.S, jnp.int32)
        jax.block_until_ready(run(
            self.params, ck, cv, z, z, z, jnp.zeros(self.S, bool),
            self._warmup_key(), self._scratch_presence(), z,
            self._plane_operands()))

    # --------------------------------------------------------- scheduling --

    def add_request(self, prompt, max_new_tokens: int,
                    on_token=None, trace_ctx=None, due_at=None,
                    **sampling) -> int:
        """Queue a prompt; returns the request id.  Admission happens inside
        ``step()`` whenever a slot is free.

        ``due_at``: optional time, on ``time.monotonic``, at which the
        request was DUE.  A load generator that injects between ticks runs
        late by up to a tick; with ``due_at`` the ``queued`` event carries
        it and ``RequestTimeline.ttft_s`` / ``metrics()["mean_ttft_s"]``
        (and ``mean_latency_s``) count from it instead of from the call.

        ``trace_ctx``: optional ``telemetry.TraceContext`` propagated by a
        caller that minted the request's end-to-end trace (the gateway's
        dispatch path).  Host-side metadata only — it binds the engine
        rid to the trace in the attached tracer so every request-timeline
        event carries the shared trace_id; compiled programs and their
        cache keys are identical with or without one.

        ``on_token(request_id, token, done)``: optional streaming callback,
        invoked on the host as each token is accepted (chunked/speculative
        modes deliver a burst per sync — ordering within a request is
        guaranteed, across requests it follows slot order).  A
        ``cancel(request_id)`` ends the stream with ONE terminal
        ``on_token(request_id, None, True)`` call — ``token is None`` with
        ``done=True`` is the documented clean end-of-stream (the paged
        engines' preemption replay signal is the ``done=False`` variant).

        With ``per_request_sampling=True`` the engine accepts the
        generate()-style per-call knobs here — ``temperature``, ``top_k``,
        ``top_p``, ``greedy``, ``repetition_penalty``, ``min_new_tokens``,
        ``eos_token_id`` — each defaulting to the engine's constructor
        value.  The configs ride per-slot data planes: any mixture shares
        ONE compiled decode program."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if int(max_new_tokens) <= 0:
            # generate() returns an empty array here; a scheduler admitting
            # the request would still emit the prefill token, silently
            # over-generating — refuse instead
            raise ValueError("max_new_tokens must be >= 1")
        P = select_bucket(len(prompt), self.buckets)
        need = self._positions_needed(P, int(max_new_tokens))
        if need > self.max_len:
            raise ValueError(
                f"bucketed prompt ({len(prompt)} -> bucket {P}) needs "
                f"{need} cache positions for max_new_tokens="
                f"{max_new_tokens}; exceeds max_len ({self.max_len})")
        req = Request(next(self._ids), prompt, max_new_tokens, due_at)
        req.sampling = self._resolve_sampling(sampling)
        req.on_token = on_token
        self._queue.append(req)
        tr = self.tracer
        if tr is not None:
            if trace_ctx is not None:
                tr.bind_trace(req.id, trace_ctx)
            # due_at on the tracer's clock, like every timeline stamp
            due = {} if due_at is None else {"due_at": req.due_at - tr.t0}
            tr.request_event(req.id, "queued", prompt_len=len(prompt), **due)
        return req.id

    _SAMPLING_KEYS = ("temperature", "top_k", "top_p", "greedy",
                      "repetition_penalty", "min_new_tokens",
                      "eos_token_id")

    def _resolve_sampling(self, overrides):
        """Merge per-request overrides onto the engine defaults and
        validate; returns the plane-encoded tuple (or None in classic
        mode, where any override is an error)."""
        unknown = set(overrides) - set(self._SAMPLING_KEYS)
        if unknown:
            raise TypeError(f"unknown add_request kwargs: {sorted(unknown)}")
        given = {k: v for k, v in overrides.items() if v is not None}
        if not self.per_request:
            if given:
                raise ValueError(
                    f"per-request sampling params {sorted(given)} need "
                    f"per_request_sampling=True")
            return None
        V = self.model.config.vocab_size
        t, k, p, g, rp, mn, eos = self._plane_defaults
        if "temperature" in given:
            t = float(given["temperature"])
            if t <= 0:
                raise ValueError("temperature must be > 0 (use greedy=True "
                                 "for deterministic decoding)")
        if "top_k" in given:
            k = int(given["top_k"])
            validate_sampler_args(V, k, None, True, None)
        if "top_p" in given:
            p = float(given["top_p"])
            validate_sampler_args(V, None, p, True, None)
        if "greedy" in given:
            g = bool(given["greedy"])
        if "repetition_penalty" in given:
            rp = float(given["repetition_penalty"])
            if rp <= 0:
                raise ValueError("repetition_penalty must be > 0")
        if "min_new_tokens" in given:
            mn = int(given["min_new_tokens"])
            if mn < 0:
                raise ValueError("min_new_tokens must be >= 0")
        if "eos_token_id" in given:
            eos = int(given["eos_token_id"])
            if not 0 <= eos < V:
                raise ValueError(f"eos_token_id {eos} outside vocab "
                                 f"(size {V})")
        if mn > 0 and eos < 0:
            raise ValueError("min_new_tokens needs an eos_token_id "
                             "(engine default or per-request)")
        # sampling-only knobs are argmax-inert while the effective greedy
        # flag is True — add_request(p, n, temperature=0.8) would silently
        # decode greedy (ADVICE r5); fail loudly instead of mis-serving.
        # NEUTRAL values pass (temperature=1.0, top_p=1.0): clients that
        # always forward their defaults are not asking for sampling (the
        # ctor guard draws the same line)
        if g and (("temperature" in given and t != 1.0)
                  or "top_k" in given
                  or ("top_p" in given and p != 1.0)):
            raise ValueError(
                "temperature/top_k/top_p have no effect under greedy "
                "decoding — pass greedy=False with them (or construct the "
                "engine with greedy=False)")
        return (t, k, p, g, rp, mn, eos)

    def _positions_needed(self, P: int, mnt: int) -> int:
        """Worst-case cache positions a request occupies — the bucket plus
        CHUNK-ROUNDED decode: the first token comes from prefill (no decode
        position), the remaining budget-1 tokens consume ceil((budget-1)/k)
        * k positions (decode advances k ticks per sync; pad slots occupy
        physical positions).  The ragged engine overrides this with its
        speculative over-proposal arithmetic."""
        k = self.ticks_per_sync
        return P + -(-(mnt - 1) // k) * k

    def pending(self) -> bool:
        return bool(self._queue) or bool(self._active.any()) \
            or bool(self._filling)

    # ------------------------------------------------------ prefix index --

    #: engines without a prefix cache answer the routing plane honestly
    prefix_caching = False

    def prefix_index(self) -> Dict[str, str]:
        """PUBLIC prefix-cache view: ``{chain_hex: tier}`` for every
        resident prefix page (``"hbm"`` here; the paged engines merge
        their attached :class:`~paddle_tpu.kv_store.TieredKVStore`'s
        ``"dram"``/``"disk"`` tiers under it).  The gateway's
        fleet-wide ``prefix_index()`` and the ops ``/kvstore`` view read
        this instead of reaching into engine internals.  Empty for
        engines without prefix caching."""
        return {}

    def prefix_match(self, prompt) -> Dict[str, Any]:
        """PUBLIC tier-aware prefix-affinity read for one prompt:
        ``{"hbm": leading blocks resident in HBM, "total": leading
        blocks resident in ANY tier, "tiers": per-block tier labels}``.
        A pure read — no LRU touch, no pinning, no restore (admission
        does those).  The gateway's router scores replicas with this:
        a deep lower-tier hit (restorable, no recompute) outranks a
        shallow HBM hit."""
        return {"hbm": 0, "total": 0, "tiers": []}

    def pop_finished(self) -> Dict[int, List[int]]:
        out, self._finished = self._finished, {}
        return out

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _free_slots(self):
        return [s for s in range(self.S)
                if not self._active[s] and s not in self._filling]

    def _admit(self):
        free = self._free_slots()
        while self._queue and free:
            slot = free.pop(0)
            req = self._queue.pop(0)
            P = select_bucket(len(req.prompt), self.buckets)
            pad = P - len(req.prompt)
            ids = [0] * pad + req.prompt
            if self.prefill_chunk is not None and P > self.prefill_chunk:
                # chunked admission: segments run one per scheduler round,
                # interleaved with everyone else's decode.  PARK the slot's
                # decode clock in the strip above every chunked bucket:
                # the batched decode program stale-writes EVERY row at its
                # clock each tick (inactive ones included), and unlike
                # whole-bucket prefill — which overwrites [0, P) after any
                # stale write — segments land progressively, so a stale
                # write at the old clock (0 for a fresh slot) would corrupt
                # already-filled prompt positions.  The parking strip is
                # overwritten by the occupant's own decode before it can
                # ever be read (write-before-read induction).
                self._set_planes(slot, req)
                self._t[slot] = self.max_len - self.ticks_per_sync
                self._filling[slot] = {"req": req, "ids": ids, "pad": pad,
                                       "P": P, "seg": 0,
                                       "nseg": P // self.prefill_chunk}
                continue
            self._set_planes(slot, req)
            run = self._prefill_prog(P)
            ck, cv, tok0, self._presence = run(
                self.params, self.caches[0], self.caches[1],
                jnp.asarray([ids], jnp.int32), jnp.int32(pad),
                jnp.int32(slot), self._next_key(), self._presence,
                self._plane_operands())
            self.caches = (ck, cv)
            self._note("prefill_tokens", P)
            self._activate(slot, req, P, pad, int(tok0))

    def _set_planes(self, slot, req):
        """Write the request's effective sampler config into the slot's
        row of the per-request planes (no-op in classic mode).  Must run
        BEFORE the admission prefill — the first token samples through the
        planes.  Doubles as the single admission choke point every engine
        passes through, so it also emits the ``admitted`` telemetry
        transition."""
        if self.tracer is not None:
            self.tracer.request_event(req.id, "admitted", slot=int(slot))
        if not self.per_request:
            return
        t, k, p, g, rp, mn, eos = req.sampling
        self._r_temp[slot] = t
        self._r_topk[slot] = k
        self._r_topp[slot] = p
        self._r_greedy[slot] = g
        self._r_rp[slot] = rp
        self._r_minnew[slot] = mn
        self._r_eos[slot] = eos

    def _activate(self, slot, req, P, pad, tok0):
        req.first_token_at = time.monotonic()   # tok0 exists: TTFT point
        if self.tracer is not None:
            self.tracer.request_event(req.id, "first_token",
                                      slot=int(slot))
        self._slot_req[slot] = req
        self._t[slot] = P
        self._pad[slot] = pad
        self._tok[slot] = tok0
        self._active[slot] = True
        self._record(slot, tok0)

    def _fill_segments(self):
        """Run ONE prefill segment for every filling slot (round-robin
        progress: a long prompt advances without stalling decode)."""
        seg = self.prefill_chunk
        for slot, st in list(self._filling.items()):
            i, first = st["seg"], st["seg"] == 0
            last = i == st["nseg"] - 1
            toks = jnp.asarray([st["ids"][i * seg:(i + 1) * seg]], jnp.int32)
            run = self._seg_prog(seg, first, last)
            ck, cv, tok0, self._presence = run(
                self.params, self.caches[0], self.caches[1], toks,
                jnp.int32(i * seg), jnp.int32(st["pad"]), jnp.int32(slot),
                self._presence, self._next_key(), self._plane_operands())
            self.caches = (ck, cv)
            self._note("prefill_tokens", seg)
            if last:
                del self._filling[slot]
                self._activate(slot, st["req"], st["P"], st["pad"],
                               int(tok0))
            else:
                st["seg"] += 1

    def _record(self, slot: int, tok: int):
        """Append a token to the slot's request; retire on EOS/budget."""
        req = self._slot_req[slot]
        req.generated.append(tok)
        if self.tracer is not None:
            self.tracer.request_event(req.id, "token", token=int(tok))
        eos = (req.sampling[6] if self.per_request else self.eos_token_id)
        hit_eos = (eos is not None and eos >= 0 and tok == eos)
        done = len(req.generated) >= req.max_new_tokens or hit_eos
        if req.on_token is not None:
            try:
                req.on_token(req.id, tok, done)
            except Exception:  # noqa: BLE001 — a user callback must not
                # desync host state mid-block (tokens for later slots in
                # this sync would be silently dropped); log and continue
                logging.getLogger(__name__).exception(
                    "on_token callback failed for request %d", req.id)
        # the callback may have cancel()ed this very request (reentrant
        # consumer): the slot is already released — nothing left to retire
        if done and self._slot_req[slot] is not None:
            self._retire(slot)

    def _retire(self, slot: int):
        req = self._slot_req[slot]
        req.done = True
        req.finished_at = time.monotonic()
        self._finished[req.id] = list(req.generated)
        self._slot_req[slot] = None
        self._active[slot] = False
        n = len(req.generated)
        stat_add("serving_requests_finished")
        stat_add("serving_tokens_emitted", n)
        s = self._stats
        s.add("requests_finished")
        s.add("tokens_emitted", n)
        s.add("ttft_seconds_sum", req.first_token_at - req.due_at)
        s.add("latency_seconds_sum", req.finished_at - req.due_at)
        if self.tracer is not None:
            self.tracer.request_event(req.id, "retired", tokens=n)

    def cancel(self, rid: int) -> bool:
        """Cancel one in-flight request and release every resource it holds.

        Works at ANY lifecycle stage — still queued, mid-(chunked-)prefill,
        or actively decoding — and is pure host bookkeeping (no device
        program runs): the slot frees for the next admission, the paged
        engines additionally release the slot's KV blocks and prefix-cache
        pins (``_release_cancelled_slot``), and per-request sampling rows
        reset to the engine defaults.  Cancelled requests never appear in
        ``pop_finished()``; a streaming consumer gets ONE terminal
        ``on_token(rid, None, True)`` call — the documented clean
        end-of-stream (``done=True``, vs the preemption replay signal's
        ``done=False``).  Returns True iff the request was found in flight;
        False means an unknown rid or an already-finished request (the
        caller raced retirement — its tokens are in ``pop_finished()``).

        The slot's stale cache/presence contents need no device work: the
        next occupant's admission prefill rewrites both before anything
        reads them (the same write-before-read induction inactive slots
        rely on — module docstring)."""
        for i, req in enumerate(self._queue):
            if req.id == rid:
                del self._queue[i]
                self._finalize_cancel(req)
                return True
        for slot, st in list(self._filling.items()):
            if st["req"].id == rid:
                del self._filling[slot]
                self._release_cancelled_slot(slot)
                self._finalize_cancel(st["req"])
                return True
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.id == rid:
                self._slot_req[slot] = None
                self._active[slot] = False
                self._release_cancelled_slot(slot)
                self._finalize_cancel(req)
                return True
        return False

    def _release_cancelled_slot(self, slot: int):
        """Free the per-slot resources a cancelled occupant held (seam:
        the paged engines add block + prefix-pin release)."""
        if self.per_request:
            t, k, p, g, rp, mn, eos = self._plane_defaults
            self._r_temp[slot] = t
            self._r_topk[slot] = k
            self._r_topp[slot] = p
            self._r_greedy[slot] = g
            self._r_rp[slot] = rp
            self._r_minnew[slot] = mn
            self._r_eos[slot] = eos

    def _finalize_cancel(self, req: Request):
        """Terminal bookkeeping shared by every cancel path: counters, the
        ``cancelled`` telemetry transition, and the clean end-of-stream
        signal."""
        req.done = True
        req.finished_at = time.monotonic()
        self._stats.add("requests_cancelled")
        stat_add("serving_requests_cancelled")
        if self.tracer is not None:
            self.tracer.request_event(req.id, "cancelled",
                                      tokens=len(req.generated))
        if req.on_token is not None:
            try:
                req.on_token(req.id, None, True)   # terminal end-of-stream
            except Exception:  # noqa: BLE001 — same contract as _record:
                # a user callback must not desync the scheduler
                logging.getLogger(__name__).exception(
                    "on_token cancel signal failed for request %d", req.id)

    _TICK_COUNTERS = ("tokens_emitted", "requests_finished")

    def step(self):
        """One scheduler round (each engine's ``_step_impl`` documents its
        semantics).  With a tracer attached the round is bracketed by tick
        telemetry — its number, host wall time and the seconds of each
        phase, queue depth, counter deltas, packed rows, program labels —
        and by the ``engine.tick`` span; with ``tracer=None`` (default)
        this wrapper is ONE attribute check and a tail call: no event
        allocation, no tracer lock, no span, no extra operands anywhere
        near a compiled program.

        An exception escaping ``_step_impl`` is SURFACED before it
        propagates — the ``step_errors`` counter ticks and (with a
        tracer) an ``engine_error`` event lands in the ring — so a
        replica that dies mid-tick leaves evidence in the observability
        plane even when its caller (the gateway's step isolation, a bare
        serving loop) swallows or crashes on the re-raise."""
        tr = self.tracer
        if tr is None:
            try:
                return self._step_impl()
            except Exception:
                self._stats.add("step_errors")
                raise
        t0 = time.perf_counter()
        self._tick_note = tr.open_tick()
        s = self._stats
        base = {k: s.value(k) for k in self._TICK_COUNTERS}
        try:
            return self._step_impl()
        except Exception as e:
            self._stats.add("step_errors")
            tr.emit("engine_error", what="step_error",
                    engine=type(self).__name__, error=repr(e))
            raise
        finally:
            fields = {k: s.value(k) - base[k] for k in self._TICK_COUNTERS}
            fields.update(self._tick_gauges())
            fields.update(self._tick_note)
            self._tick_note = {}
            tr.tick(type(self).__name__, time.perf_counter() - t0,
                    queue_depth=len(self._queue),
                    active=int(self._active.sum()),
                    filling=len(self._filling), **fields)

    def _tick_gauges(self) -> Dict[str, float]:
        """Instantaneous per-tick gauges (subclass hook; only consulted
        when tracing is on)."""
        return {}

    def _step_impl(self):
        """One scheduler round: admit waiting requests into free slots, then
        run ``ticks_per_sync`` batched decode ticks and retire finished
        requests from the returned token block."""
        phase = self._phases()
        with phase(PHASE_ADMIT):    # the bucketed engines prefill in here
            self._admit()
            if self._filling:
                self._fill_segments()
        if not self._active.any():
            return
        res = self._run_decode(phase)
        if res is None:
            return
        active_before, blk = res                   # blk (k, S)
        with phase(PHASE_UNPACK):
            for slot in np.flatnonzero(active_before):
                for j in range(self.ticks_per_sync):
                    if not self._active[slot]:
                        break  # retired mid-chunk: discard the chunk's tail
                    self._t[slot] += 1
                    self._tok[slot] = blk[j, slot]
                    self._record(int(slot), int(blk[j, slot]))
                # room is a CHUNK-boundary concern: a surviving slot must
                # fit a whole next chunk.  Admission-validated budgets
                # always do; this is the safety net against inconsistent
                # slot state, truncating rather than writing past the cache.
                if self._active[slot] and int(self._t[slot]) \
                        + self.ticks_per_sync > self.max_len:
                    self._retire(int(slot))

    def _prepare_decode(self) -> bool:
        """Pre-sync hook: the paged subclass grows block tables here
        (preempting when the pool is dry).  False = nothing left to
        decode."""
        return True

    def _decode_extra_operands(self):
        """Extra traced operands the decode program takes after the caches
        (the paged subclass passes its block table)."""
        return ()

    def _run_decode(self, phase=_no_phase):
        """One ``ticks_per_sync`` decode sync over the engine's cache
        storage; returns (active_before, (k, S) token block) or None if no
        slot could decode."""
        with phase(PHASE_PACK):
            if not self._prepare_decode():
                return None
            active_before = self._active.copy()
            self._note("decode_rows", int(active_before.sum()))
            emitted0 = np.asarray(
                [len(r.generated) if r is not None else 0
                 for r in self._slot_req], np.int32)
        with phase(PHASE_DISPATCH) as part:     # three parts partition it
            part(PART_OPERANDS)
            operands = (
                *self._decode_extra_operands(),
                jnp.asarray(self._tok), jnp.asarray(self._t),
                jnp.asarray(self._pad), jnp.asarray(active_before))
            emitted0 = jnp.asarray(emitted0)
            planes = self._plane_operands()
            part(PART_KEY)
            key = self._next_key()
            part(PART_CALL)
            run = self._decode_prog_all()
            ck, cv, blk, self._presence = run(
                self.params, self.caches[0], self.caches[1], *operands,
                key, self._presence, emitted0, planes)
            # freed here, as the call's own temporaries were: the phase
            # keeps its extent
            del operands, key, emitted0, planes
            self.caches = (ck, cv)
        with phase(PHASE_SYNC):
            blk = np.asarray(blk)
        return active_before, blk

    # metrics() contract: {key: (kind, pytype)}; kind "counter" = monotonic
    # over the engine's lifetime, "gauge" = instantaneous/derived.  Keys
    # never change meaning; subclasses extend (docs/OBSERVABILITY.md).
    METRICS_SCHEMA = {
        "requests_finished": ("counter", int),
        "requests_cancelled": ("counter", int),
        "tokens_emitted": ("counter", int),
        "mean_ttft_s": ("gauge", float),
        "mean_latency_s": ("gauge", float),
        "tokens_per_sec": ("gauge", float),
        "compile_hits": ("counter", int),
        "compile_misses": ("counter", int),
        "step_errors": ("counter", int),
        # present only with attach_memory(MemoryLedger):
        "memory_device_bytes": ("gauge", float),
        "memory_host_bytes": ("gauge", float),
    }

    @classmethod
    def metrics_schema(cls) -> Dict[str, tuple]:
        """The stable ``metrics()`` schema for this engine class, merged
        over the MRO.  Every key metrics() returns appears here with its
        kind and type; conditional keys (prefix caching off) may be absent
        from a given metrics() dict but never change meaning."""
        out: Dict[str, tuple] = {}
        for klass in reversed(cls.__mro__):
            out.update(klass.__dict__.get("METRICS_SCHEMA", {}))
        return out

    def metrics(self) -> Dict[str, float]:
        """Serving observability, registry-backed (one private
        ``utils.stats.StatRegistry`` per engine — the same mechanism the
        rest of the framework counts through, exported whole by
        ``prometheus_text()``): finished-request counts, mean
        time-to-first-token (queue wait + prefill), mean request latency,
        lifetime throughput, and compile-cache hit/miss counts.  Schema:
        ``metrics_schema()``."""
        s = self._stats
        nreq = int(s.value("requests_finished"))
        n = max(nreq, 1)
        toks = int(s.value("tokens_emitted"))
        dt = max(time.monotonic() - self._started, 1e-9)
        out = {"requests_finished": nreq,
               "requests_cancelled": int(s.value("requests_cancelled")),
               "tokens_emitted": toks,
               "mean_ttft_s": float(s.value("ttft_seconds_sum")) / n,
               "mean_latency_s": float(s.value("latency_seconds_sum")) / n,
               "tokens_per_sec": toks / dt,
               "compile_hits": self._compile_hits,
               "compile_misses": self._compile_misses,
               "step_errors": int(s.value("step_errors"))}
        if self._memory is not None:
            totals = self._memory.memory_snapshot()["totals"]
            out["memory_device_bytes"] = float(totals["device_bytes"])
            out["memory_host_bytes"] = float(totals["host_bytes"])
        return out

    def prometheus_text(self, namespace: str = "paddle_tpu_serving") -> str:
        """Prometheus text exposition of this engine's registry plus the
        derived ``metrics()`` values not stored as raw registry stats,
        each typed per ``metrics_schema()`` (compile counts stay
        counters, means/throughput are gauges)."""
        raw = set(self._stats.snapshot())
        schema = self.metrics_schema()
        gauges, counters = {}, {}
        for k, v in self.metrics().items():
            if k in raw:
                continue
            (counters if schema[k][0] == "counter" else gauges)[k] = v
        return _prometheus_text(self._stats, namespace=namespace,
                                extra_gauges=gauges,
                                extra_counters=counters)

    def run_to_completion(self, max_ticks: Optional[int] = None
                          ) -> Dict[int, List[int]]:
        """Drive step() until every queued request finishes; returns
        {request_id: generated tokens}."""
        ticks = 0
        while self.pending():
            self.step()
            ticks += 1
            if max_ticks is not None and ticks > max_ticks:
                raise RuntimeError(f"not done after {max_ticks} ticks")
        return self.pop_finished()


# Every paged (block-table) variant is defined in serving_paged.py and
# re-exported here LAZILY (PEP 562) so `paddle_tpu.serving` stays the
# single public serving namespace without a circular import
# (serving_paged imports this module at its top).  Speculation runs
# inside `RaggedPagedContinuousBatchingEngine` as part of the one-
# program-per-tick ragged pack (draft_model=/draft_k= constructor args).
_PAGED_NAMES = ("PagedContinuousBatchingEngine",
                "RaggedPagedContinuousBatchingEngine")
__all__ += [n for n in _PAGED_NAMES if n not in __all__]


def __getattr__(name):
    if name in _PAGED_NAMES:
        from . import serving_paged
        return getattr(serving_paged, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
