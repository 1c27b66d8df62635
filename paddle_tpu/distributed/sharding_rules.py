"""Unified sharding-rules layer — THE place array-layout decisions live.

Before this module, zero.py, localsgd.py, dgc.py, spmd.py,
pipeline_engine.py and the GPT builders each hand-threaded their own
``PartitionSpec`` literals (65 sites across 12 files), and nothing tied a
layout decision to the executables compiled under it.  This module owns
all of it:

1. **Spec constructors** (:func:`make_spec`, :func:`replicated_spec`,
   :func:`replica_stacked_spec`, :func:`batch_spec`, ...): the ONLY
   sanctioned ``PartitionSpec`` construction sites.  The tpulint rule
   ``raw-partition-spec`` machine-enforces that no other module builds a
   literal spec, so a layout change is a one-file diff here.

2. **Metadata-driven inference** (:func:`build_param_specs`,
   :func:`build_state_shardings`): the TP/PP/ZeRO spec inference that
   previously lived in ``spmd.py`` — params carry ``_dims_mapping`` /
   ``_pipe_stacked`` annotations, optimizer slots follow their params and
   pick up the "sharding" axis for ZeRO stages.  Moved verbatim so every
   trainer lowers identically to before the move (parity pinned by
   tests/test_sharding_rules.py).

3. **Rules-based resolver** (:class:`ShardingRules`): ordered
   ``(regex, PartitionSpec)`` rules matched against ``/``-joined tree
   paths (the ``match_partition_rules`` shape proven by the JAX LLM
   training community) — scalar/size-1 leaves are exempt (always
   replicated), unmatched paths follow an explicit policy (``"raise"`` |
   ``"replicate"``), axes that do not divide a dimension follow an
   explicit ``indivisible`` policy with byte-accounted fallback.  Covers
   params, optimizer-state trees (:meth:`ShardingRules.resolve_state`)
   and KV-cache pools (plain trees — :meth:`ShardingRules.resolve`).

4. **Stable digests** (:meth:`ShardingRules.digest`,
   :func:`spec_tree_digest`, :func:`sharding_rules_digest`): content
   digests of rule sets and resolved spec trees.  ``jit/aot.py`` folds
   :func:`sharding_rules_digest` into its environment fingerprint and
   validates it per cache entry, so editing a rule here can never revive
   a stale-spec executable from disk.

5. **Replication-fallback accounting** (:func:`replication_fallback`,
   :func:`resolve_flat_shard_spec`): any spot that quietly falls back to
   full replication (a non-divisible flat residual, an unmatched path
   under ``unmatched="replicate"``) now warns AND bumps
   ``sharding_replicated_fallback_bytes`` /
   ``sharding_replicated_fallback_leaves`` so the replicated bytes are
   visible in the stats registry, never silent.

The automatic cross-replica weight-update sharding for plain
data-parallel training (arXiv:2004.13336) that consumes this resolver
lives in :mod:`paddle_tpu.distributed.update_sharding`.
"""

from __future__ import annotations

import hashlib
import re
import warnings
from typing import Any, Dict, Iterable, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "CATALOG_VERSION", "ShardingRules", "activation_batch_spec",
    "attention_specs", "batch_spec", "build_param_specs", "build_state_shardings",
    "constrain_activation", "constrain_batch", "data_parallel_axes",
    "loop_collectives", "make_spec", "match_partition_rules",
    "override_leading_axis",
    "register_rules", "replica_stacked_spec", "replicated_spec",
    "replication_fallback", "resolve_flat_shard_spec",
    "sep_activation_spec", "sharding_rules_digest", "spec_tree_digest",
    "unregister_rules", "zero3_layout_comm",
]

#: Bump when the SEMANTICS of the built-in inference below change without
#: the code path changing shape — the catalog digest folds it in, so every
#: AOT-cached executable compiled under the old semantics is invalidated.
CATALOG_VERSION = 2

#: The built-in rule catalog: one row per layout decision this module
#: makes.  ``sharding_rules_digest()`` digests these rows, so editing a
#: rule (or its semantics, via CATALOG_VERSION) changes the digest that
#: jit/aot.py bakes into cache-entry environments.
_RULE_CATALOG: Tuple[Tuple[str, str], ...] = (
    ("tp", "params with _dims_mapping={dim: axis} shard that dim on the "
           "axis when the axis exists, has size>1, and divides the dim"),
    ("pp", "_pipe_stacked params shard dim 0 over 'pipe' when divisible"),
    ("zero3", "zero_stage>=3 shards one free divisible param dim over "
              "'sharding': the largest INSIDE the layer for a parameter "
              "the layer scan stacks (never dim 0, which the scan slices), "
              "the first for any other"),
    ("slots", "optimizer slots follow their param's spec; zero_stage>=1 "
              "adds 'sharding' on the first free divisible dim"),
    ("scalars", "scalar/size-1 leaves are always replicated"),
    ("dp_update", "plain-DP weight-update sharding: flat optimizer shards "
                  "carry a leading replica dim over the dp axis "
                  "(update_sharding.py)"),
    ("batch", "the batch dim lives on the data-parallel axes: 'data', "
              "then 'sharding', each where its size > 1 and divides the "
              "batch (data_parallel_axes; batch_spec, "
              "activation_batch_spec and attention_specs read it)"),
    ("attention", "Pallas attention kernels run per shard: batch over the "
                  "data-parallel axes, heads over 'model' where it "
                  "divides (attention_specs)"),
    ("flat_residual", "flat comm residuals ride an axis only when the "
                      "length divides; otherwise replicate WITH byte "
                      "accounting (resolve_flat_shard_spec)"),
)

#: Explicitly registered custom rule sets (name -> digest); folded into
#: ``sharding_rules_digest()``.  Registration is process-global state —
#: register only rule sets that genuinely govern AOT-compiled programs in
#: this process, and keep the set identical across processes sharing an
#: executable cache (docs/SHARDING.md).
_REGISTERED: Dict[str, str] = {}


# --------------------------------------------------------------------------
# spec constructors — the only sanctioned PartitionSpec literals
# --------------------------------------------------------------------------

def make_spec(*entries) -> PartitionSpec:
    """``PartitionSpec(*entries)`` — the constructor every other module
    uses instead of a raw literal (enforced by tpulint raw-partition-spec)."""
    return PartitionSpec(*entries)


def replicated_spec() -> PartitionSpec:
    """Fully replicated layout (``PartitionSpec()``)."""
    return PartitionSpec()


def replica_stacked_spec(leaf, axis: str) -> PartitionSpec:
    """Leading-dim-over-``axis`` layout for per-replica stacked state
    (localsgd params/opt, dgc residuals): ``P(axis, None, ..., None)``
    padded to the leaf's rank."""
    return PartitionSpec(axis, *([None] * (np.ndim(leaf) - 1)))


def data_parallel_axes(mesh: Mesh, batch: Optional[int] = None
                        ) -> Tuple[str, ...]:
    """The mesh axes a batch is split over, in order: "data", then
    "sharding" (ZeRO is data parallelism with the state split as well),
    each only where its size is > 1 and, when ``batch`` is given, where it
    still divides what the axes before it left of the batch.  The ONE
    place that names them: :func:`batch_spec`,
    :func:`activation_batch_spec` and :func:`attention_specs` read it."""
    axes, split = [], 1
    for ax in ("data", "sharding"):
        n = mesh.shape.get(ax, 1)
        if n > 1 and (batch is None or batch % (split * n) == 0):
            axes.append(ax)
            split *= n
    return tuple(axes)


def batch_spec(mesh: Mesh, batch: Optional[int] = None) -> PartitionSpec:
    """Batch-dim layout: dim 0 over :func:`data_parallel_axes`, replicated
    when there is none (single replica, or no axis divides ``batch``)."""
    axes = data_parallel_axes(mesh, batch)
    return PartitionSpec(axes) if axes else PartitionSpec()


def activation_batch_spec(mesh: Mesh, batch: Optional[int] = None
                          ) -> Optional[PartitionSpec]:
    """(B, L, H) activation layout for the GPT builders: batch over
    :func:`data_parallel_axes`, sequence on "sep" when sequence
    parallelism is on; None when the mesh gives no reason to constrain
    (one replica, no sep)."""
    axes = data_parallel_axes(mesh, batch)
    sep = "sep" if mesh.shape.get("sep", 1) > 1 else None
    if not axes and sep is None:
        return None
    return PartitionSpec(axes or None, sep, None)


def _constrained(x, mesh: Mesh, spec: Optional[PartitionSpec]):
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_batch(x, mesh: Optional[Mesh]):
    """A step's batch operand with dim 0 pinned to the data-parallel axes
    inside the jitted program; ``x`` itself where no axis splits it (and
    for a scalar, such as the step's key).  A layout hint only: the
    values are the same either way."""
    if mesh is None or np.ndim(x) == 0:
        return x
    axes = data_parallel_axes(mesh, x.shape[0])
    return _constrained(x, mesh, PartitionSpec(axes) if axes else None)


def constrain_activation(h, mesh: Optional[Mesh]):
    """A (B, L, H) activation pinned to :func:`activation_batch_spec` —
    what the layer scan carries, so every block runs on its own rows."""
    if mesh is None:
        return h
    return _constrained(h, mesh, activation_batch_spec(mesh, h.shape[0]))


def attention_specs(mesh: Mesh, batch: int, heads: int):
    """shard_map layouts for a Pallas attention kernel under ``mesh``
    (ops/attention.py): the batch dim over :func:`data_parallel_axes` and
    the head dim over "model", each only where the axis has size > 1 and
    divides the dim — an axis that does not divide is left out, so its
    devices each compute the whole dim (correct, just not split).
    Returns ``(specs, sharded_axes)`` with ``specs`` keyed "qkv" for
    (B, L, H, D), "kmask" for (B, L), "stat" for (B, H, L) and "rep" for
    replicated operands."""
    b_axes = data_parallel_axes(mesh, batch)
    b = b_axes or None
    mp = mesh.shape.get("model", 1)
    h = "model" if mp > 1 and heads % mp == 0 else None
    specs = {"qkv": PartitionSpec(b, None, h, None),
             "kmask": PartitionSpec(b, None),
             "stat": PartitionSpec(b, h, None),
             "rep": PartitionSpec()}
    return specs, b_axes + ((h,) if h else ())


def sep_activation_spec(ndim: int = 4, axis: str = "sep",
                        seq_dim: int = 1) -> PartitionSpec:
    """Sequence-parallel shard_map operand layout: ``axis`` on the
    sequence dim, everything else replicated (the ring/Ulysses attention
    in/out spec: ``P(None, "sep", None, None)`` at the default rank)."""
    entries: list = [None] * ndim
    entries[seq_dim] = axis
    return PartitionSpec(*entries)


def override_leading_axis(spec: PartitionSpec, ndim: int,
                          axis: str) -> PartitionSpec:
    """``spec`` widened to ``ndim`` entries with dim 0 forced onto
    ``axis`` — the pipeline engine's stacked-parameter layout (leading
    layer dim over "pipe")."""
    entries = [None] * ndim
    for i, a in enumerate(spec):
        entries[i] = a
    entries[0] = axis
    return PartitionSpec(*entries)


# --------------------------------------------------------------------------
# replication-fallback accounting
# --------------------------------------------------------------------------

def replication_fallback(kind: str, name: str, nbytes: int, *,
                         axis: Optional[str] = None,
                         degree: Optional[int] = None,
                         tracer=None) -> None:
    """Record one quietly-replicated tensor: warn, bump the stats
    registry, and (when a telemetry tracer is supplied) emit a structured
    ``sharding_fallback`` event.  Every path that degrades a sharded
    layout to full replication routes through here so the replicated
    bytes are observable (OBSERVABILITY.md)."""
    from ..utils.stats import stat_add
    stat_add("sharding_replicated_fallback_bytes", int(nbytes))
    stat_add("sharding_replicated_fallback_leaves", 1)
    detail = f" over axis {axis!r} (degree {degree})" if axis else ""
    warnings.warn(
        f"sharding: {kind} {name!r} stays fully replicated{detail} — "
        f"{nbytes / 1e6:.2f} MB per device that a divisible layout would "
        f"shard (stat: sharding_replicated_fallback_bytes)")
    if tracer is not None:
        tracer.emit("sharding_fallback", kind=kind, name=name,
                    bytes=int(nbytes), axis=axis, degree=degree)


def resolve_flat_shard_spec(name: str, length: int, mesh: Mesh, axis: str,
                            *, itemsize: int = 4,
                            tracer=None) -> PartitionSpec:
    """Layout for a flat fp32 buffer (grad-comm residuals, fused shards):
    ``P(axis)`` when ``length`` divides over the axis, else replicated
    WITH fallback accounting — the fix for the silent ``P()`` fallback
    that zero.py's comm residual used to take."""
    deg = mesh.shape.get(axis, 1)
    if deg > 1 and length % deg == 0:
        return PartitionSpec(axis)
    if deg > 1:
        replication_fallback("flat-residual", name, length * itemsize,
                             axis=axis, degree=deg, tracer=tracer)
    return PartitionSpec()


# --------------------------------------------------------------------------
# metadata-driven inference (moved verbatim from spmd.py — trainers lower
# identically; spmd.py re-exports these names for compatibility)
# --------------------------------------------------------------------------

def _spec_for_param(name: str, p, mesh: Mesh, named_params: Dict,
                    zero_stage: int, stacked_pipe: bool,
                    scan_stacked: bool = False) -> PartitionSpec:
    """``scan_stacked``: dim 0 of ``p`` is the layer-stack axis that the
    model's layer scan slices (the model says so: ``stacked_param_names``).
    ZeRO-3 never shards that axis — a scan over a sharded stack makes the
    partitioner gather the WHOLE stack in every iteration — and takes the
    largest free divisible dim inside the layer, so one iteration gathers
    one layer."""
    ndim = len(p.shape)
    entries = [None] * ndim
    meta = getattr(named_params.get(name), "_dims_mapping", None) \
        if named_params else None
    if meta is None:
        meta = getattr(p, "_dims_mapping", None) or {}
    for dim, axis in meta.items():
        if axis in mesh.axis_names and mesh.shape[axis] > 1 and \
                p.shape[int(dim)] % mesh.shape[axis] == 0:
            entries[int(dim)] = axis
    if stacked_pipe and "pipe" in mesh.axis_names and mesh.shape["pipe"] > 1 \
            and ndim >= 1 and entries[0] is None and \
            p.shape[0] % mesh.shape["pipe"] == 0 and \
            getattr(named_params.get(name), "_pipe_stacked", False):
        entries[0] = "pipe"
    if zero_stage >= 3 and "sharding" in mesh.axis_names and \
            mesh.shape["sharding"] > 1:
        free = [d for d in range(1 if scan_stacked else 0, ndim)
                if entries[d] is None
                and p.shape[d] % mesh.shape["sharding"] == 0]
        if free:
            d = max(free, key=lambda d: p.shape[d]) if scan_stacked \
                else free[0]
            entries[d] = "sharding"
    return PartitionSpec(*entries)


def build_param_specs(params: Dict[str, Any], mesh: Mesh, layer=None,
                      zero_stage: int = 0) -> Dict[str, PartitionSpec]:
    named = dict(layer.named_parameters()) if layer is not None else {}
    # whose dim 0 the layer scan slices: the model's own list, the scan's
    # source of truth; with no layer the rule knows of no scan
    stacked = frozenset(getattr(layer, "stacked_param_names", tuple)())
    specs = {name: _spec_for_param(name, p, mesh, named, zero_stage, True,
                                   name in stacked)
             for name, p in params.items()}
    if zero_stage >= 3 and mesh.shape.get("sharding", 1) > 1:
        zero3_layout_comm(params, specs, mesh, stacked)
    return specs


def zero3_layout_comm(params: Dict[str, Any],
                      specs: Dict[str, PartitionSpec], mesh: Mesh,
                      stacked: Iterable[str] = ()) -> Dict[str, int]:
    """What one step of a ZeRO-3 layout must move over "sharding", per
    device, from the shapes alone (no compile): ``gather_bytes`` — every
    leaf split over the axis is gathered where it is used, once forward
    and once backward, and a leaf split on the axis the layer scan slices
    is gathered WHOLE in every one of its iterations; ``reduce_bytes`` —
    gradients land on the parameter's layout, a reduce-scatter for a
    split leaf, an all-reduce for a replicated one; ``scan_axis_leaves`` —
    leaves whose spec names the scanned axis (0 under the built-in rule).
    Bytes are at the leaves' stored dtype; a model that casts before the
    gather moves that fraction of ``gather_bytes``.  Also the gauges
    ``sharding_zero3_*`` of the stats registry."""
    from ..utils.stats import stat_registry
    n = mesh.shape.get("sharding", 1)
    stacked = frozenset(stacked)
    out = {"gather_bytes": 0, "reduce_bytes": 0, "scan_axis_leaves": 0}

    def names(entry):
        return entry if isinstance(entry, tuple) else (entry,)

    for name, p in params.items():
        spec = tuple(specs[name])
        moved = _leaf_nbytes(p) * (n - 1) // n
        if not any("sharding" in names(e) for e in spec):
            out["reduce_bytes"] += 2 * moved
            continue
        passes = 2
        if name in stacked and "sharding" in names(spec[0]):
            out["scan_axis_leaves"] += 1
            passes *= p.shape[0]
        out["gather_bytes"] += passes * moved
        out["reduce_bytes"] += moved
    for key, val in out.items():
        stat_registry().set(f"sharding_zero3_{key}", val)
    return out


def _slot_spec(param_spec: PartitionSpec, p, mesh: Mesh,
               zero_stage: int) -> PartitionSpec:
    """Optimizer slots follow param sharding; ZeRO-1/2 additionally shards
    them over "sharding" (reference DygraphShardingOptimizer /
    ShardingOptimizerStage2 semantics, without the manual bucketing)."""
    entries = list(param_spec) + [None] * (len(p.shape) - len(param_spec))
    if zero_stage >= 1 and "sharding" in mesh.axis_names and \
            mesh.shape["sharding"] > 1 and "sharding" not in entries:
        for d in range(len(p.shape)):
            if entries[d] is None and p.shape[d] % mesh.shape["sharding"] == 0:
                entries[d] = "sharding"
                break
    return PartitionSpec(*entries)


def build_state_shardings(state, params_specs: Dict[str, PartitionSpec],
                          mesh: Mesh, zero_stage: int, params):
    """Shardings for the full TrainState pytree {params, opt, buffers}."""
    def param_sh(name):
        return NamedSharding(mesh, params_specs[name])

    p_sh = {k: param_sh(k) for k in state["params"]}
    rep = NamedSharding(mesh, replicated_spec())

    def slot_sh(path_name, slots):
        out = {}
        for sname, val in slots.items():
            if hasattr(val, "shape") and len(val.shape) > 0:
                out[sname] = NamedSharding(
                    mesh, _slot_spec(params_specs[path_name],
                                     params[path_name], mesh, zero_stage))
            else:
                out[sname] = rep
        return out

    opt_sh = {"step": rep,
              "slots": {k: slot_sh(k, v)
                        for k, v in state["opt"]["slots"].items()}}
    buf_sh = {k: rep for k in state["buffers"]}
    return {"params": p_sh, "opt": opt_sh, "buffers": buf_sh}


# --------------------------------------------------------------------------
# what a compiled program moves inside its loops
# --------------------------------------------------------------------------

# "%x = <type> <op>(": the type may hold "=" (/*index=5*/) and parentheses
# (tiled layouts), but never another operation's " name("
_COLLECTIVE_OP = re.compile(
    r" = (?P<type>(?:(?! [a-z][\w\-]*\().)*?) (?P<op>all-gather|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute)(?:-start)?\(")
_COMPUTATION_HEAD = re.compile(r"^(?:ENTRY )?%?(?P<name>[\w.\-]+) \(.*\{$")
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations|"
    r"called_computations)=\{?(?P<names>[%\w.\-, ]+)")


def loop_collectives(hlo_text: str) -> list:
    """The collectives of a compiled program (``compiled.as_text()``) that
    run inside a ``while`` body, directly or through the fusions and
    calls the body makes: ``[{"op", "dims", "computation"}]`` with
    ``dims`` the dimension tuples of the result.  The check a layout
    wants before a chip run: under ZeRO-3 no ``all-gather`` in the layer
    scan may have the layer count among its dims (one layer an iteration,
    not the stack).  The TPU compiler writes a reduce-scatter as a fusion
    named ``all-reduce-scatter`` around an ``all-reduce``; the CPU's as an
    ``all-reduce`` and a slice."""
    lines_of: Dict[str, list] = {}
    current = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION_HEAD.match(line)
        if head:
            current = lines_of.setdefault(head["name"], [])
        elif current is not None:
            current.append(line)

    def called(name):
        for line in lines_of.get(name, ()):
            for m in _CALLED.finditer(line):
                yield from (n.strip(" %") for n in m["names"].split(","))

    todo = [m.strip(" %") for m in re.findall(r"body=(%?[\w.\-]+)", hlo_text)]
    inside = set()
    while todo:
        name = todo.pop()
        if name in lines_of and name not in inside:
            inside.add(name)
            todo.extend(called(name))
    out = []
    for name in sorted(inside):
        for line in lines_of[name]:
            m = _COLLECTIVE_OP.search(line)
            if m:
                dims = [tuple(int(d) for d in g.split(",") if d)
                        for g in re.findall(r"\w+\[([\d,]*)\]", m["type"])]
                out.append({"op": m["op"], "dims": dims, "computation": name})
    return out


# --------------------------------------------------------------------------
# path utilities + digests
# --------------------------------------------------------------------------

def _path_str(path) -> str:
    """'/'-joined string for a jax key path (DictKey/SequenceKey/...)."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _leaf_size(leaf) -> int:
    shape = tuple(getattr(leaf, "shape", ()) or ())
    return int(np.prod(shape)) if shape else 1


def _leaf_nbytes(leaf) -> int:
    dt = getattr(leaf, "dtype", None)
    itemsize = np.dtype(dt).itemsize if dt is not None else 4
    return _leaf_size(leaf) * itemsize


def _canon_spec(spec) -> Tuple:
    """Canonical hashable form of one spec entry tree leaf."""
    if spec is None:
        return ("<none>",)
    return tuple(tuple(e) if isinstance(e, (tuple, list)) else e
                 for e in spec)


def _is_spec_leaf(x) -> bool:
    return x is None or isinstance(x, PartitionSpec)


def spec_tree_digest(spec_tree) -> str:
    """Stable hex digest of a resolved spec tree: sorted (path, entries)
    pairs under blake2b.  Pass the output of :meth:`ShardingRules.resolve`
    or :func:`build_param_specs`; fold into AOT cache keys when a layout
    decision should invalidate a cached executable."""
    flat = jax.tree_util.tree_flatten_with_path(
        spec_tree, is_leaf=_is_spec_leaf)[0]
    rows = sorted((_path_str(path), _canon_spec(spec)) for path, spec in flat)
    h = hashlib.blake2b(digest_size=16)
    for path, entries in rows:
        h.update(path.encode())
        h.update(repr(entries).encode())
        h.update(b"\x00")
    return h.hexdigest()


def sharding_rules_digest() -> str:
    """Digest of the ACTIVE sharding rules in this process: the built-in
    catalog (CATALOG_VERSION + _RULE_CATALOG) plus every explicitly
    registered :class:`ShardingRules` set.  jit/aot.py folds this into
    ``fingerprint()`` environments and validates it per executable-cache
    entry, so an edit to any rule refuses stale disk executables."""
    h = hashlib.blake2b(digest_size=16)
    h.update(repr((CATALOG_VERSION,) + _RULE_CATALOG).encode())
    for name in sorted(_REGISTERED):
        h.update(name.encode())
        h.update(_REGISTERED[name].encode())
        h.update(b"\x00")
    return h.hexdigest()


def register_rules(rules: "ShardingRules") -> None:
    """Enroll a custom rule set in the process-global active digest (see
    :func:`sharding_rules_digest`).  Call this for rule sets that govern
    programs going through the AOT executable cache; keep the registered
    set identical across processes that share a cache directory."""
    _REGISTERED[rules.name] = rules.digest()


def unregister_rules(name: str) -> None:
    _REGISTERED.pop(name, None)


# --------------------------------------------------------------------------
# the rules-based resolver
# --------------------------------------------------------------------------

class ShardingRules:
    """Ordered (regex, PartitionSpec) sharding rules over tree paths.

    ``rules``: sequence of ``(pattern, spec)`` where ``pattern`` is a
    regex matched with ``re.search`` against the ``/``-joined path of
    each leaf (first match wins — order the specific before the general)
    and ``spec`` is a ``PartitionSpec``, a tuple of entries, or ``None``
    (replicated).

    ``unmatched``: ``"raise"`` (default — an unmatched non-scalar leaf is
    a configuration error) or ``"replicate"`` (fall back to ``P()`` WITH
    replication-fallback accounting).

    ``indivisible``: when a ``mesh`` is bound and a matched axis does not
    divide the leaf's dimension: ``"replicate"`` (default — drop the
    entry, account the bytes) or ``"raise"``.

    Scalar and size-1 leaves are always replicated, whatever the rules
    say — a scalar cannot be usefully sharded and exempting it keeps rule
    tables free of step-counter noise.
    """

    def __init__(self, rules: Sequence[Tuple[str, Any]], *,
                 unmatched: str = "raise", indivisible: str = "replicate",
                 mesh: Optional[Mesh] = None, name: str = "custom",
                 tracer=None):
        if unmatched not in ("raise", "replicate"):
            raise ValueError(
                f"unmatched must be 'raise' or 'replicate', got {unmatched!r}")
        if indivisible not in ("raise", "replicate"):
            raise ValueError(f"indivisible must be 'raise' or 'replicate', "
                             f"got {indivisible!r}")
        self.rules: Tuple[Tuple[str, PartitionSpec], ...] = tuple(
            (str(pat), self._as_spec(spec)) for pat, spec in rules)
        self._compiled = tuple((re.compile(pat), spec)
                               for pat, spec in self.rules)
        self.unmatched = unmatched
        self.indivisible = indivisible
        self.mesh = mesh
        self.name = str(name)
        self.tracer = tracer

    @staticmethod
    def _as_spec(spec) -> PartitionSpec:
        if spec is None:
            return PartitionSpec()
        if isinstance(spec, PartitionSpec):
            return spec
        if isinstance(spec, (tuple, list)):
            return PartitionSpec(*spec)
        raise TypeError(f"rule spec must be a PartitionSpec, entry tuple, "
                        f"or None; got {type(spec).__name__}")

    # ------------------------------------------------------------ resolve --

    def spec_for(self, path: str, leaf=None) -> PartitionSpec:
        """The spec for one '/'-joined path (scalar exemption + first-match
        + divisibility applied when ``leaf`` is given)."""
        if leaf is not None and _leaf_size(leaf) <= 1:
            return PartitionSpec()
        for rx, spec in self._compiled:
            if rx.search(path):
                return self._fit(path, leaf, spec)
        if self.unmatched == "raise":
            raise ValueError(
                f"sharding rules {self.name!r}: no rule matches path "
                f"{path!r} — add a rule or construct with "
                f"unmatched='replicate'")
        if leaf is not None:
            replication_fallback("unmatched-path", path, _leaf_nbytes(leaf),
                                 tracer=self.tracer)
        return PartitionSpec()

    def _fit(self, path: str, leaf, spec: PartitionSpec) -> PartitionSpec:
        """Trim/pad ``spec`` to the leaf's rank and enforce divisibility
        against the bound mesh (per the ``indivisible`` policy)."""
        if leaf is None:
            return spec
        shape = tuple(getattr(leaf, "shape", ()) or ())
        entries = list(spec)[:len(shape)] + \
            [None] * max(0, len(shape) - len(spec))
        if self.mesh is None:
            return self._squeeze(entries)
        for d, entry in enumerate(entries):
            axes = entry if isinstance(entry, (tuple, list)) else \
                ((entry,) if entry is not None else ())
            deg = 1
            for a in axes:
                deg *= self.mesh.shape.get(a, 1)
            if deg > 1 and shape[d] % deg != 0:
                if self.indivisible == "raise":
                    raise ValueError(
                        f"sharding rules {self.name!r}: axis {entry!r} "
                        f"(degree {deg}) does not divide dim {d} "
                        f"(size {shape[d]}) of {path!r}")
                replication_fallback(
                    "indivisible-dim", f"{path}[{d}]",
                    _leaf_nbytes(leaf), axis=str(entry), degree=deg,
                    tracer=self.tracer)
                entries[d] = None
        return self._squeeze(entries)

    @staticmethod
    def _squeeze(entries) -> PartitionSpec:
        """Drop trailing Nones so rank-fitting never changes spec equality
        (``P(None, None)`` and ``P()`` lower identically; keeping the short
        canonical form makes parity pins and digests rank-independent)."""
        while entries and entries[-1] is None:
            entries = entries[:-1]
        return PartitionSpec(*entries)

    def resolve(self, tree) -> Any:
        """Spec tree (same structure as ``tree``) for any pytree — params,
        KV-cache pools, whole train states.  Paths are '/'-joined."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs = [self.spec_for(_path_str(path), leaf) for path, leaf in flat]
        return jax.tree_util.tree_unflatten(treedef, specs)

    def resolve_state(self, state) -> Any:
        """Specs for an optimizer-carrying train state ``{"params": ...,
        "opt": {"step", "slots": {param: {slot: leaf}}}, ...}``: params
        resolve under their own path; each optimizer slot resolves under
        its PARAM's path (slots inherit their param's layout; scalar
        exemption still applies), so one rule table covers both."""
        out = {}
        for key, sub in state.items():
            if key == "opt" and isinstance(sub, dict) and "slots" in sub:
                slot_specs = {}
                for pname, slots in sub["slots"].items():
                    slot_specs[pname] = {
                        sname: self.spec_for(f"params/{pname}", leaf=sval)
                        for sname, sval in slots.items()}
                out["opt"] = {"step": PartitionSpec(), "slots": slot_specs}
                if "step" not in sub:
                    del out["opt"]["step"]
            else:
                prefixed = jax.tree_util.tree_flatten_with_path(sub)
                flat, treedef = prefixed
                specs = [self.spec_for(f"{key}/{_path_str(p)}" if p else key,
                                       leaf) for p, leaf in flat]
                out[key] = jax.tree_util.tree_unflatten(treedef, specs)
        return out

    def shardings(self, tree, mesh: Optional[Mesh] = None) -> Any:
        """``NamedSharding`` tree over ``mesh`` (or the bound mesh)."""
        m = mesh if mesh is not None else self.mesh
        if m is None:
            raise ValueError("shardings() needs a mesh (bind one at "
                             "construction or pass mesh=)")
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(m, s), self.resolve(tree),
            is_leaf=_is_spec_leaf)

    # ------------------------------------------------------------- digest --

    def digest(self) -> str:
        """Stable digest of the rule CONTENT (patterns, specs, policies —
        not the name): two rule sets that resolve identically digest
        identically across processes."""
        h = hashlib.blake2b(digest_size=16)
        h.update(repr((self.unmatched, self.indivisible)).encode())
        for pat, spec in self.rules:
            h.update(pat.encode())
            h.update(repr(_canon_spec(spec)).encode())
            h.update(b"\x00")
        return h.hexdigest()

    def __repr__(self):
        return (f"ShardingRules({self.name!r}, {len(self.rules)} rules, "
                f"unmatched={self.unmatched!r}, digest={self.digest()[:8]})")


def match_partition_rules(rules: Sequence[Tuple[str, Any]], tree,
                          unmatched: str = "raise",
                          mesh: Optional[Mesh] = None) -> Any:
    """Functional shorthand: resolve ``tree`` under ``rules`` in one call
    (the community ``match_partition_rules`` signature)."""
    return ShardingRules(rules, unmatched=unmatched, mesh=mesh,
                         name="match_partition_rules").resolve(tree)
