"""Parameter layout + sharding spec machinery.

Every model declares its parameters as `WeightSpec`s: shape, the TP
(tensor-parallel, `model` axis) placement, the ZDP axis (which dim the
OSDP plan may shard over `data`/`pod`), and the OSDP operator name the
weight belongs to. `materialize` turns specs + an OSDP plan into:

  * the param pytree (weights split into per-mode segments along the
    ZDP axis when the plan mixes modes — paper §3.3 per-slice plans),
  * a matching pytree of `NamedSharding`s,
  * per-op segment metadata the model fwd uses (`SegLayout`).

TP conventions (see DESIGN.md §6):
  * column-parallel: output dim sharded over `model` (w_q, w13, embed^T)
  * row-parallel: input dim sharded over `model`, output psum (w_o, w2)
  * experts: expert axis over `model` (expert parallelism)
  * small tensors (norms, biases, kv for replicated-kv GQA): no TP
ZDP overlays `data` (ZDP mode) or nothing (DP) on `zdp_axis`; in the
multi-pod mesh, ZDP uses ('pod','data') and ZDP_POD only 'data'.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.cluster.topology import parse_level_mode
from repro.core.cost_model import DP, ZDP, ZDP_POD, Decision


@dataclass(frozen=True)
class WeightSpec:
    """Declaration of one parameter tensor."""

    path: str                       # pytree path, e.g. "layers/ffn/w13"
    shape: Tuple[int, ...]
    op: str                         # OSDP operator this weight belongs to
    tp_axis: Optional[int] = None   # dim sharded over 'model' (None = no TP)
    zdp_axis: Optional[int] = None  # dim OSDP may shard (None = always DP)
    init: str = "normal"            # "normal" | "zeros" | "ones" | "ssm_a"
    init_scale: float = 0.02
    dtype: jnp.dtype = jnp.bfloat16
    stacked: bool = False           # leading dim is the layer axis


@dataclass
class Segment:
    """One contiguous slice of a weight along its ZDP axis."""

    mode: str
    start: int
    size: int
    key: str          # leaf name suffix ("" if single segment)
    # per-segment remat resolved from the plan's Decision.remat bits:
    # True = recompute this segment's activations, False = keep them,
    # None = inherit the run's global checkpointing default.  Segments
    # merge by sharding mode (storage), so a segment spanning slices
    # with mixed remat bits resolves to True (recompute — the
    # memory-safe direction).
    remat: Optional[bool] = None


@dataclass
class SegLayout:
    """Per-weight segmentation derived from the plan."""

    spec: WeightSpec
    segments: List[Segment]

    @property
    def is_split(self) -> bool:
        return len(self.segments) > 1


def _merge_modes(modes: Sequence[str], dim: int
                 ) -> List[Tuple[str, int, int, Tuple[int, ...]]]:
    """Merge adjacent equal-mode slices
    -> [(mode, start, size, contributing_slice_indices)].

    The slice boundaries quantize `dim` into len(modes) near-equal
    chunks, rounded to multiples of 128 where possible (MXU alignment).
    The index tuple records which plan slices actually contribute bytes
    to the merged segment (zero-width slices are excluded, so their
    remat bits cannot contaminate per-slice remat resolution).
    """
    g = len(modes)
    bounds = [0]
    for j in range(1, g):
        b = round(dim * j / g)
        if dim % 128 == 0 and dim // g >= 128:
            b = round(b / 128) * 128
        bounds.append(min(max(b, bounds[-1]), dim))
    bounds.append(dim)
    out: List[Tuple[str, int, int, Tuple[int, ...]]] = []
    for j, (m, s, e) in enumerate(zip(modes, bounds[:-1], bounds[1:])):
        if e <= s:
            continue
        if out and out[-1][0] == m:
            pm, ps, psz, pidx = out[-1]
            out[-1] = (pm, ps, psz + (e - s), pidx + (j,))
        else:
            out.append((m, s, e - s, (j,)))
    return out or [(modes[0], 0, dim, tuple(range(g)))]


def _segment_remat(decision: Optional[Decision],
                   idxs: Sequence[int]) -> Optional[bool]:
    """Resolve one merged segment's remat bit from the plan slices that
    contribute bytes to it: uniform -> that bit, mixed -> True
    (recompute is the memory-safe approximation), no explicit bits ->
    None (inherit)."""
    if decision is None or decision.remat is None:
        return None
    bits = set(decision.remat[j] for j in idxs)
    if bits == {True}:
        return True
    if bits == {False}:
        return False
    if bits == {None}:
        return None
    return True


def layout_for(spec: WeightSpec,
               decision: Optional[Decision]) -> SegLayout:
    modes = decision.modes if decision is not None else (DP,)
    if spec.zdp_axis is None or len(modes) == 1:
        mode = modes[0] if spec.zdp_axis is not None else DP
        return SegLayout(spec, [Segment(
            mode, 0, spec.shape[spec.zdp_axis]
            if spec.zdp_axis is not None else 0, "",
            _segment_remat(decision, range(len(modes))))])
    dim = spec.shape[spec.zdp_axis]
    merged = _merge_modes(list(modes), dim)
    if len(merged) == 1:
        m, _, _, idxs = merged[0]
        return SegLayout(spec, [Segment(m, 0, dim, "",
                                        _segment_remat(decision, idxs))])
    return SegLayout(spec, [Segment(m, s, z, f"@{i}",
                                    _segment_remat(decision, idxs))
                            for i, (m, s, z, idxs) in enumerate(merged)])


def data_axis_names(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes carrying the data-parallel extent, outermost first
    (every axis that is not model/pipe — covers cluster-derived meshes
    whose axes are hierarchy level names).  The single definition of
    this rule; `core.plan.batch_axes` delegates here."""
    return tuple(a for a in mesh.axis_names if a not in ("model", "pipe"))


def _zdp_axes_names(mode: str, mesh: Mesh) -> Optional[Tuple[str, ...]]:
    """Mesh axes a sharding mode spreads the weight over.  ZDP takes
    the whole data extent; level-k modes (`ZDP@k` / the depth-2 alias
    ZDP_POD) take the k innermost (trailing) data axes."""
    if mode == DP:
        return None
    data_axes = data_axis_names(mesh)
    if mode == ZDP:
        return data_axes
    if mode == ZDP_POD:
        return data_axes[-1:]
    k = parse_level_mode(mode)
    if k is not None:
        return data_axes[-k:]
    raise ValueError(mode)


def segment_sharding(spec: WeightSpec, seg: Segment, seg_shape: Tuple[int, ...],
                     mesh: Mesh) -> NamedSharding:
    parts: List[Optional[object]] = [None] * len(seg_shape)
    if spec.tp_axis is not None:
        parts[spec.tp_axis] = "model"
    names = _zdp_axes_names(seg.mode, mesh)
    if names is not None and spec.zdp_axis is not None:
        n = math.prod(mesh.shape[a] for a in names)
        if seg_shape[spec.zdp_axis] % n == 0:
            parts[spec.zdp_axis] = names if len(names) > 1 else names[0]
        elif (len(names) > 1
              and seg_shape[spec.zdp_axis] % mesh.shape[names[-1]] == 0):
            # fall back to the innermost data axis (in-pod sharding)
            parts[spec.zdp_axis] = names[-1]
        # else: leave replicated (divisibility guard; cost model's saving
        # for this segment is then optimistic — flagged by tests)
    return NamedSharding(mesh, P(*parts))


def _init_array(key: jax.Array, spec: WeightSpec,
                shape: Tuple[int, ...]) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(shape, spec.dtype)
    if spec.init == "ssm_a":
        # Mamba2 A init: -exp(U[log 1, log 16]) stored as log(-A)
        u = jax.random.uniform(key, shape, jnp.float32,
                               minval=math.log(1.0), maxval=math.log(16.0))
        return u.astype(spec.dtype)
    scale = spec.init_scale
    if spec.init == "fan_in":
        fan = shape[-2] if len(shape) >= 2 else shape[-1]
        scale = 1.0 / math.sqrt(fan)
    return (jax.random.normal(key, shape, jnp.float32) * scale
            ).astype(spec.dtype)


@dataclass(frozen=True)
class OverlapConfig:
    """Runtime comm/compute overlap knobs — the executed counterpart of
    `ClusterLevel.overlap` in the cost model.

    `prefetch` is how many segment weights ahead `seg_matmul` forces
    XLA to gather: slice k+prefetch's all-gather is barrier-ordered
    before slice k's contraction, so the gather streams behind the
    matmul instead of serializing with it (0 disables).  `bucket_bytes`
    groups gradient leaves into independently-schedulable all-reduce
    buckets overlapping the remaining backward walk; smaller buckets
    start reducing earlier but pay more per-collective latency (the
    alpha term), larger ones amortize latency but expose more tail —
    the trade-off `docs/cost_model.md` §9 quantifies.  Both transforms
    are identity on values: the overlapped step computes bit-identical
    results (asserted by tests/test_overlap.py)."""

    prefetch: int = 1
    bucket_bytes: int = 4 << 20

    def __post_init__(self):
        if self.prefetch < 0:
            raise ValueError("prefetch must be >= 0")
        if self.bucket_bytes < 0:
            raise ValueError("bucket_bytes must be >= 0")


@dataclass
class ParamSet:
    """Materialized parameters + shardings + segmentation metadata."""

    params: Dict[str, jax.Array]              # flat path -> array
    shardings: Dict[str, NamedSharding]
    layouts: Dict[str, SegLayout]              # weight path -> layout
    overlap: Optional[OverlapConfig] = None    # runtime overlap knobs

    def tree(self) -> Dict[str, jax.Array]:
        return self.params

    def sharding_tree(self) -> Dict[str, NamedSharding]:
        return self.shardings

    def segments(self, path: str) -> List[Tuple[str, Segment]]:
        """[(leaf_key, segment)] for a declared weight path."""
        lay = self.layouts[path]
        return [(path + s.key, s) for s in lay.segments]

    def n_params(self) -> int:
        return sum(int(np.prod(a.shape)) for a in self.params.values())


def seg_shape(spec: WeightSpec, seg: Segment) -> Tuple[int, ...]:
    if spec.zdp_axis is None:
        return spec.shape
    shp = list(spec.shape)
    shp[spec.zdp_axis] = seg.size
    return tuple(shp)


def build_param_set(specs: Sequence[WeightSpec],
                    decisions: Optional[Dict[str, Decision]],
                    mesh: Optional[Mesh],
                    key: jax.Array,
                    abstract: bool = False,
                    overlap: Optional[OverlapConfig] = None) -> ParamSet:
    """Create params (or ShapeDtypeStructs if abstract) + shardings."""
    params: Dict[str, jax.Array] = {}
    shardings: Dict[str, NamedSharding] = {}
    layouts: Dict[str, SegLayout] = {}
    keys = jax.random.split(key, max(1, len(specs)))
    for k, spec in zip(keys, specs):
        dec = decisions.get(spec.op) if decisions else None
        lay = layout_for(spec, dec)
        layouts[spec.path] = lay
        for seg in lay.segments:
            shp = seg_shape(spec, seg)
            leaf = spec.path + seg.key
            if mesh is not None:
                shardings[leaf] = segment_sharding(spec, seg, shp, mesh)
            if abstract:
                params[leaf] = jax.ShapeDtypeStruct(shp, spec.dtype)
            else:
                params[leaf] = _init_array(k, spec, shp)
    return ParamSet(params, shardings, layouts, overlap)


# --- hybrid 3D meshes (data x model x pipe) ----------------------------------
#
# A HybridPlan executes on a 3-axis mesh: `data` carries the DP/ZDP
# decisions exactly as above, `model` carries TP, and `pipe` carries
# the GPipe stages. The pipe axis never appears in a weight's
# PartitionSpec — each stage materializes only its own layer slice
# (below), so `segment_sharding` applies unchanged on the hybrid mesh
# (ZDP resolves to ('data',) since hybrid meshes have no 'pod' axis).

def hybrid_mesh_spec(dp: int, tp: int, pp: int):
    """(shape, axes) of the 3-axis hybrid mesh for jax.make_mesh."""
    from repro.core.hybrid import Factorization
    cfg = Factorization(dp, tp, pp).mesh_config()
    return cfg.shape, cfg.axes


def stage_of_layer(layer: int, bounds: Sequence[int]) -> int:
    """Pipeline stage owning `layer` under HybridPlan.stage_bounds."""
    for s in range(len(bounds) - 1):
        if bounds[s] <= layer < bounds[s + 1]:
            return s
    raise ValueError(f"layer {layer} outside stage bounds {bounds}")


def stage_weight_specs(specs: Sequence[WeightSpec],
                       bounds: Sequence[int],
                       stage: int) -> List[WeightSpec]:
    """The per-stage view of a weight list for pipeline execution.

    Stacked weights (leading layer axis) shrink to the stage's layer
    range; unstacked weights follow the usual GPipe placement —
    embeddings on the first stage, head/final-norm on the last. When
    embeddings are tied (no separate head weight in the list), the
    embedding is also placed on the last stage so it can project
    logits there.
    """
    n_stages = len(bounds) - 1
    last = n_stages - 1
    lo, hi = bounds[stage], bounds[stage + 1]
    tied = not any(s.path.startswith("head") for s in specs
                   if not s.stacked)
    out: List[WeightSpec] = []
    for spec in specs:
        if spec.stacked:
            n = hi - lo
            if n <= 0:
                continue
            shp = (n,) + tuple(spec.shape[1:])
            out.append(dataclasses.replace(spec, shape=shp))
        elif spec.path.startswith("embed"):
            if stage == 0 or (tied and stage == last):
                out.append(spec)
        elif stage == last:
            out.append(spec)
    return out


# --- selective-remat checkpoint policy ---------------------------------------

def saved_activation_names(layouts: Dict[str, SegLayout],
                           default_remat: bool
                           ) -> Tuple[Tuple[str, ...], bool]:
    """(names whose activations the jax.checkpoint policy should save,
    whether anything remats at all) for a materialized plan.

    `seg_matmul` tags each segment's output with `checkpoint_name`:
    per-leaf names for output-dim (concat) segments, the bare weight
    path for the combined output (single-segment and input-dim-sum
    cases — where per-slice saving isn't representable, the whole
    output is saved only if every slice keeps its activations).
    Unresolved (inherit) segments follow `default_remat`.
    """
    saved: List[str] = []
    any_remat = False
    for path, lay in layouts.items():
        kept = []
        for seg in lay.segments:
            r = bool(default_remat) if seg.remat is None else seg.remat
            if r:
                any_remat = True
                kept.append(False)
            else:
                saved.append(path + seg.key)
                kept.append(True)
        if len(lay.segments) > 1 and all(kept):
            saved.append(path)    # sum-variant tag on the whole output
    return tuple(sorted(set(saved))), any_remat


# --- helpers used by model forward passes -----------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _zdp_gather(w: jax.Array, whole: NamedSharding,
                split: NamedSharding) -> jax.Array:
    with jax.named_scope("zdp/gather"):
        return jax.lax.with_sharding_constraint(w, whole)


def _zdp_gather_fwd(w, whole, split):
    return _zdp_gather(w, whole, split), None


def _zdp_gather_bwd(whole, split, _, g):
    # the gradient, summed over the data axes, lands split as the
    # weight is: a reduce-scatter, not an all-reduce of the whole
    return (jax.lax.with_sharding_constraint(g, split),)


_zdp_gather.defvjp(_zdp_gather_fwd, _zdp_gather_bwd)


def _gathered(w: jax.Array, pset: ParamSet, path: str,
              leaf: str) -> jax.Array:
    """A segment's weight as its operator uses it: a ZDP segment is
    gathered whole on every device of its data axes (`zdp/gather`), and
    its gradient reduce-scattered back; a DP segment, or one of a build
    without a mesh, passes as it is."""
    sh = pset.shardings.get(leaf)
    spec = pset.layouts[path].spec
    if sh is None or spec.zdp_axis is None:
        return w
    parts = list(sh.spec) + [None] * (len(spec.shape) - len(sh.spec))
    if parts[spec.zdp_axis] is None:
        return w
    if spec.stacked and w.ndim == len(spec.shape) - 1:
        parts = parts[1:]            # the layer scan's slice of the stack
    whole = list(parts)
    whole[spec.zdp_axis - (len(spec.shape) - w.ndim)] = None
    return _zdp_gather(w, NamedSharding(sh.mesh, P(*whole)),
                       NamedSharding(sh.mesh, P(*parts)))


def gather_weight(params: Dict[str, jax.Array], pset: ParamSet,
                  path: str) -> jax.Array:
    """Concatenate a weight's segments back (for ops that don't exploit
    sequential slice processing). Axis accounts for the layer axis being
    consumed when called inside the scan-over-layers body."""
    segs = pset.segments(path)
    ws = [_gathered(params[k], pset, path, k) for k, _ in segs]
    if len(segs) == 1:
        return ws[0]
    spec = pset.layouts[path].spec
    axis = spec.zdp_axis
    if spec.stacked and ws[0].ndim == len(spec.shape) - 1:
        axis -= 1
    return jnp.concatenate(ws, axis=axis)


def _prefetch_weights(ws: List[jax.Array], ahead: int) -> List[jax.Array]:
    """One-slice-ahead (or `ahead`-ahead) weight prefetch.

    Barrier-ties each segment's weight to its successor `ahead` slices
    later: `optimization_barrier` is identity on values but tells XLA
    that slice k's contraction cannot be scheduled before slice
    k+ahead's weight (i.e. its ZDP all-gather) has been issued, so the
    gather of the next slice streams behind the current matmul instead
    of serializing after it.  Numerics are untouched.
    """
    out = list(ws)
    ahead = max(1, ahead)
    for k in range(len(out) - 1):
        j = min(k + ahead, len(out) - 1)
        out[k], out[j] = jax.lax.optimization_barrier((out[k], out[j]))
    return out


def seg_matmul(x: jax.Array, params: Dict[str, jax.Array], pset: ParamSet,
               path: str, in_axis_in_weight: int) -> jax.Array:
    """Operator splitting (§3.3) over per-mode segments.

    If the split axis is the weight's *input* (contraction) dim — the
    paper's Figure 4 case — segments are processed sequentially and
    summed: y = sum_j x[..., slice_j] @ W_j. If it is the *output* dim
    (row-parallel weights, whose input dim is TP-owned), segment outputs
    are computed sequentially and concatenated. Either way only one
    gathered slice is live at a time. `in_axis_in_weight` counts within
    the per-layer weight (excluding a stacked layer axis).

    With `pset.overlap.prefetch > 0` segment weights are chained through
    `_prefetch_weights` so slice k+1's all-gather overlaps slice k's
    contraction (value-identical; scheduling only).
    """
    segs = pset.segments(path)
    spec = pset.layouts[path].spec
    # outputs are tagged with checkpoint_name so a selective-remat plan
    # compiles to a save_only_these_names policy (identity otherwise)
    ws = [_gathered(params[leaf], pset, path, leaf) for leaf, _ in segs]
    if len(segs) == 1:
        return checkpoint_name(_contract(x, ws[0], in_axis_in_weight), path)
    if pset.overlap is not None and pset.overlap.prefetch > 0:
        ws = _prefetch_weights(ws, pset.overlap.prefetch)
    zdp_local = spec.zdp_axis - (1 if spec.stacked else 0)
    if zdp_local == in_axis_in_weight:
        # sum variant (input-dim split, Figure 4): partial sums are
        # full-size, so only the combined output carries a name
        y = None
        off = 0
        for w, (leaf, seg) in zip(ws, segs):
            xs = jax.lax.dynamic_slice_in_dim(x, off, seg.size, axis=-1)
            part = _contract(xs, w, in_axis_in_weight)
            y = part if y is None else y + part
            off += seg.size
        return checkpoint_name(y, path)
    # concat variant (output-dim split): per-segment names, so remat
    # stays a per-slice choice in the executed program
    parts = [checkpoint_name(_contract(x, w, in_axis_in_weight), leaf)
             for w, (leaf, _) in zip(ws, segs)]
    return jnp.concatenate(parts, axis=-1)


def _contract(x: jax.Array, w: jax.Array, in_axis: int) -> jax.Array:
    if w.ndim == 2 and in_axis == 0:
        return x @ w
    return jnp.tensordot(x, w, axes=((x.ndim - 1,), (in_axis,)))
