"""Sharding-rule engine: one regex table, pytree path -> logical spec.

Before ISSUE 12, the placement policy lived as code — `parallel/
sharding.py::_spec_for_leaf` walked each leaf's path objects and
hand-tested names ("w", "proj", "head") and ranks. That worked for one
model family and died the moment specs had to become DATA: a checkpoint
that wants to restore onto a different topology must carry its placement
policy as inspectable metadata, and a new model family must extend a
table, not a function. This module is the SNIPPETS [3]
`match_partition_rules` idiom applied to this repo's whole train state:

- `PARTITION_RULES` is an ordered table of (regex, logical spec) rows.
  A leaf's coordinate is its "/"-joined tree path ("params/gen/proj/w",
  "opt/disc/1/0/mu/head/w", "ema_gen/deconv1/w", ...), so the SAME rows
  cover params, both Adam states (mu/nu mirror the param tree), and the
  EMA copy for all three model families (dcgan / resnet / stylegan,
  attention + spectral-norm + conditional variants included).
- A logical spec is a tuple of mesh-AXIS NAMES (or None) per dim — never
  device counts. Resolution against a concrete Mesh happens separately
  (`resolve_spec`), which is what makes specs portable across
  topologies: the same logical row yields a valid PartitionSpec on a
  v5e-32 and on the v5e-16 it restores onto ("Scalable Training of LMs
  using pjit"'s mesh-axis discipline).
- Matching is EXACT-ONE by construction: a leaf matching zero rules
  raises (a new layer must be classified, not silently replicated — the
  SNIPPETS [3] contract), and the DCG011 analyzer audits the whole
  table offline for unmatched AND multiply-matched paths over every
  model family's full train state.

Resolution policies (`resolve_spec`) reproduce the previous derivation
bit-for-bit — the semantic-tier program fingerprints must not move:

- divisibility guard: a dim keeps its axis only when the mesh axis size
  divides it (the c_dim-output deconv stays replicated under model > 1);
- `spatial=True` replicates ALL weights (the "model" axis then carries
  activation height via `batch_sharding`, and sharding kernels over the
  same axis would force all-gathers around every conv);
- `shard_opt=True` (ZeRO-1) additionally inserts the "data" axis on the
  first unsharded dim it divides, for optimizer-state paths only — the
  cross-replica weight-update sharding of arXiv:2004.13336;
- `zero_stage >= 2` (ISSUE 13, DESIGN §6i) applies the same insertion —
  via ONE shared `zero_insert` policy, with a co-sharding second pass for
  dims already carrying mesh axes — to the optimizer state (stage 2) and
  to params + the EMA mirror (stage 3), and derives the matching GRADIENT
  specs (`grad_shardings`) and the shard_map backend's explicit
  psum_scatter/all_gather dims (`zero_scatter_dims`) from the same table,
  so the four layouts can never disagree.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from dcgan_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

Pytree = Any

#: the any-rank "fully replicated" logical spec (rank-specific tuples of
#: None would need one row per rank for no information)
REPLICATED = "replicated"

LogicalSpec = Any  # REPLICATED or Tuple[Optional[str], ...]

#: The rule table. Ordered for readability only — the engine enforces
#: that every leaf matches EXACTLY one row (DCG011), so order never
#: decides a placement. Patterns are re.search'd against the "/"-joined
#: path; every row's tail is anchored with `$` and the leading `(^|/)`
#: keeps a component match from binding mid-name (plain `conv1/w` would
#: also hit `b0_conv1/w`, which has its own row).
PARTITION_RULES: Tuple[Tuple[str, LogicalSpec], ...] = (
    # -- tensor-parallel weights (the widest matmuls) --------------------
    # generator projection [z_dim, top_ch*S*S]: shard the huge output dim
    (r"(^|/)proj/w$", (None, MODEL_AXIS)),
    # discriminator head [flat, 1]: shard the huge input dim
    (r"(^|/)head/w$", (MODEL_AXIS, None)),
    # conv / deconv kernels [kh, kw, in, out] — every 4-d kernel in the
    # three families — shard output channels
    (r"(^|/)(deconv\d+|conv\d+|out_conv|b\d+_conv\d+|b\d+_skip|b\d+_trgb)"
     r"/w$", (None, None, None, MODEL_AXIS)),

    # -- replicated by policy --------------------------------------------
    # attention projections and the stylegan mapping/style/rgb-style
    # linears: small [c, c]-ish matmuls, not worth a collective per block
    (r"(^|/)(query|key|value|out|map\d+|b\d+_style\d+|b\d+_rgb_style)/w$",
     REPLICATED),
    # biases of every layer kind
    (r"(^|/)b$", REPLICATED),
    # BatchNorm scale/bias (params) and mean/var (running stats)
    (r"(^|/)(bn\d+|bn_out|b\d+_bn\d+)/(scale|bias|mean|var)$", REPLICATED),
    # spectral-norm power-iteration vectors (state-side sn_<layer> leaves)
    (r"(^|/)sn_[A-Za-z0-9_]+$", REPLICATED),
    # attention output gate (scalar)
    (r"(^|/)attn/gamma$", REPLICATED),
    # stylegan learned constant input [S, S, C]
    (r"(^|/)const$", REPLICATED),
    # -- the token family (models/mla_moe.py): every leaf replicated; the
    # mesh has no expert axis, so a chip's state IS its share of the experts
    (r"(^|/)embed/table$", REPLICATED),
    (r"(^|/)(q_a|q_b|kv_a|kv_b|o_proj|gate|up|down|eh_proj|lm_head|router)"
     r"/w$", REPLICATED),
    (r"(^|/)experts/(gate|up|down)$", REPLICATED),
    (r"(^|/)(attn_norm|ffn_norm|q_norm|kv_norm|final_norm|enorm|hnorm)"
     r"/scale$", REPLICATED),
    (r"^moe_(bias|counts)/\w+$", REPLICATED),
    # -- the looped token family (models/loop_lm.py): the leaves the rows
    # above do not name; the per-exit mass the step accumulates
    (r"(^|/)(q_proj|k_proj|v_proj|exit_gate)/w$", REPLICATED),
    (r"(^|/)(attn_out_norm|ffn_out_norm)/scale$", REPLICATED),
    (r"^exit_mass$", REPLICATED),
    # -- the decoder-hybrid-decoder token family (models/sambay.py): the
    # leaves the rows above do not name (every `b` is the bias row's):
    # LayerNorm's leaves, the scan's, the lambda vectors; the memory's
    # per-channel mean
    (r"(^|/)(in_proj|x_proj|dt_proj|out_proj|qkv_proj|conv)/w$", REPLICATED),
    (r"(^|/)(norm1|norm2)/(scale|bias)$", REPLICATED),
    (r"(^|/)final_norm/bias$", REPLICATED),
    (r"(^|/)mixer/(A_log|D|lambda_[qk][12])$", REPLICATED),
    (r"(^|/)subln/scale$", REPLICATED),
    (r"^mem_abs$", REPLICATED),
    # Adam step counts (optax ScaleByAdamState / schedule counts)
    (r"(^|/)count$", REPLICATED),
    # the trainer's global step
    (r"^step$", REPLICATED),
)


def count_master_f32_leaves(state: Pytree) -> int:
    """Census of the reduced-precision ladder's f32 MASTER leaves: Adam
    first-moment (`.../mu/...`) leaves stored as float32 while their
    mirrored param leaf is sub-f32 (precision='bf16' sets
    optax.adam(mu_dtype=f32) — train/steps.py::make_optimizer).

    Master-weight LAYOUT note for the rule table above: mu/nu mirror the
    param tree by PATH ("opt/<net>/1/0/mu/<leaf>"), and every row keys on
    the path TAIL — so an f32 master mu shards exactly like its bf16
    param twin without any precision-specific row. dtype is storage, not
    placement; the ladder must never add rules here. This count feeds the
    `perf/precision/master_f32_leaves` metric + CounterSnapshot so a
    restore/config drift that silently drops the master copy (e.g. a
    rebuilt optimizer without mu_dtype) is visible in telemetry and
    pinned by tests.
    """
    import jax
    import jax.numpy as jnp

    params = state.get("params", {})
    param_dtypes = {
        path_str(p): jnp.dtype(leaf.dtype)
        for p, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    }
    n = 0
    for p, leaf in jax.tree_util.tree_flatten_with_path(
            state.get("opt", {}))[0]:
        path = path_str(p)
        if "/mu/" not in path:
            continue
        net, tail = path.split("/", 1)[0], path.split("/mu/", 1)[1]
        twin = param_dtypes.get(f"{net}/{tail}")
        if twin is not None and twin.itemsize < 4 \
                and jnp.dtype(leaf.dtype) == jnp.float32:
            n += 1
    return n


def path_str(path: Sequence[Any]) -> str:
    """The "/"-joined coordinate of one tree_flatten_with_path entry —
    DictKey.key / SequenceKey.idx / GetAttrKey.name, in tree order. This
    is the string the rule regexes and the checkpoint sidecar key on."""
    parts: List[str] = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:  # future jax: unknown key kind — still deterministic
            parts.append(str(k))
    return "/".join(parts)


def matching_rules(path: str, ndim: int,
                   rules: Optional[Sequence[Tuple[str, LogicalSpec]]] = None
                   ) -> List[int]:
    """Indices of every rule that applies to (path, rank). A sharded row
    applies only at its own rank (its spec names one axis per dim);
    REPLICATED rows are rank-free. DCG011 runs this over every leaf of
    every family and flags len != 1. `rules` defaults to the module's
    PARTITION_RULES at CALL time (so table fixtures can patch it)."""
    if rules is None:
        rules = PARTITION_RULES
    out: List[int] = []
    for i, (pat, spec) in enumerate(rules):
        if re.search(pat, path) is None:
            continue
        if spec is not REPLICATED and len(spec) != ndim:
            continue
        out.append(i)
    return out


def logical_spec(path: str, ndim: int,
                 rules: Optional[Sequence[Tuple[str, LogicalSpec]]] = None
                 ) -> LogicalSpec:
    """The single rule row for one leaf; raises on an unclassified path
    (a new layer name must be added to the table — the loud-failure
    contract of SNIPPETS [3] match_partition_rules)."""
    if rules is None:
        rules = PARTITION_RULES
    hits = matching_rules(path, ndim, rules)
    if not hits:
        raise ValueError(
            f"no sharding rule matches state leaf {path!r} (rank {ndim}) — "
            "add a row to dcgan_tpu/elastic/rules.PARTITION_RULES "
            "(`python -m dcgan_tpu.analysis --semantic --checks DCG011` "
            "audits coverage over every model family)")
    return rules[hits[0]][1]


def zero_insert(parts: Sequence[Optional[str]], shape: Sequence[int],
                mesh_shape, *, co_shard: bool = False
                ) -> Tuple[Optional[int], Tuple[Any, ...]]:
    """The data-axis insertion policy shared by every ZeRO stage: pad
    `parts` to the leaf's rank and place DATA_AXIS on the first unsharded
    dim with `size >= data_size` that it divides. Returns (dim, spec) —
    dim None (and `parts` unpadded, matching the pre-engine derivation
    bit-for-bit) when no dim is eligible. ONE definition serves the
    optimizer-state shardings, the ZeRO-2 gradient specs, the ZeRO-3
    param/EMA residency, and the shard_map backend's explicit
    psum_scatter/all_gather dims, so the four can never disagree on where
    a leaf splits.

    co_shard=True (the ZeRO-2/3 form; ZeRO-1 keeps the historical
    first-pass-only behavior so shard_opt placements never move) adds a
    SECOND pass when no free dim divides: a dim already carrying mesh
    axes takes DATA_AXIS as a trailing co-axis — `("model", "data")` on a
    conv kernel's out-channels is the classic TP x ZeRO layout — when the
    dim divides the combined axis product. Without this, any leaf whose
    only large dim is model-annotated (e.g. the first conv's
    [5, 5, c_dim, out] kernel) would silently stay replicated."""
    if DATA_AXIS not in mesh_shape:
        return None, tuple(parts)
    data_size = int(mesh_shape[DATA_AXIS])
    padded: List[Any] = \
        list(parts) + [None] * (len(shape) - len(parts))
    for d, (axis, size) in enumerate(zip(padded, shape)):
        if axis is None and int(size) >= data_size \
                and int(size) % data_size == 0:
            padded[d] = DATA_AXIS
            return d, tuple(padded)
    if co_shard:
        for d, (axis, size) in enumerate(zip(padded, shape)):
            if axis is None:
                continue
            axes = (axis,) if isinstance(axis, str) else tuple(axis)
            combined = data_size
            for a in axes:
                combined *= int(mesh_shape.get(a, 1))
            if int(size) >= combined and int(size) % combined == 0:
                padded[d] = axes + (DATA_AXIS,)
                return d, tuple(padded)
    return None, tuple(parts)


def resolve_spec(spec: LogicalSpec, shape: Sequence[int], mesh_shape,
                 *, spatial: bool = False, shard_opt: bool = False,
                 is_opt: bool = False,
                 zero: bool = False) -> Tuple[Optional[str], ...]:
    """One leaf's logical spec -> the concrete PartitionSpec entries
    (`P(*result)`) for the mesh at hand (`mesh_shape`: {axis: size}).

    Policies, in order, each reproducing the pre-engine derivation
    BIT-FOR-BIT (the committed semantic-tier program fingerprints ride on
    the spec objects, not just the placements):

    - scalars, spatial-mode leaves, and REPLICATED rows resolve to `()`;
    - a sharded row survives only when every named axis exists on the
      current mesh and divides its dim — otherwise the WHOLE spec
      collapses to `()` (the old single `ok(dim)` gate; a size-1 axis
      divides everything, so `model=1` meshes keep the axis name in the
      spec exactly as before);
    - ZeRO-1 (`shard_opt`, optimizer-state leaves only) pads the spec to
      the leaf's rank and inserts the data axis on the first unsharded
      dim with `size >= data_size` that it divides; no eligible dim
      leaves the spec untouched (arXiv:2004.13336 as annotations);
    - `zero=True` applies the same insertion unconditionally — the
      ZeRO-2/3 form, where the caller (state_shardings/grad_shardings)
      decides which leaves the stage shards (opt at stage 2, plus
      params/EMA at stage 3, gradients in both)."""
    shape = tuple(int(d) for d in shape)
    if spec is REPLICATED or len(shape) == 0 or spatial:
        parts: Tuple[Optional[str], ...] = ()
    else:
        keep = True
        for d, axis in enumerate(spec):
            if axis is None:
                continue
            size = mesh_shape.get(axis)
            if size is None or shape[d] % int(size) != 0:
                keep = False
                break
        parts = tuple(spec) if keep else ()
    if zero or (shard_opt and is_opt):
        d, padded = zero_insert(parts, shape, mesh_shape, co_shard=zero)
        if d is not None:
            return padded
    return parts


def zero_targets_leaf(path: str, zero_stage: int) -> bool:
    """Whether the ZeRO stage shards this STATE leaf over the data axis:
    stage >= 2 takes the optimizer state (the ZeRO-2 shard-local update),
    stage 3 additionally keeps params and the EMA mirror resident sharded
    between steps. BN statistics and the step counter never shard — they
    are updated inside the forward, not by the weight-update computation
    this stage partitions (arXiv:2004.13336's scope), and they are a
    rounding error of the state footprint."""
    if zero_stage >= 2 and path.startswith("opt/"):
        return True
    return zero_stage >= 3 and (path.startswith("params/")
                                or path.startswith("ema_gen"))


def state_partition_specs(state_shapes: Pytree, mesh_shape, *,
                          spatial: bool = False,
                          shard_opt: bool = False,
                          zero_stage: int = 1) -> Dict[str, Tuple]:
    """{path: resolved per-dim axis tuple} over a ShapeDtypeStruct tree —
    the flat, serializable form (the checkpoint sidecar stores exactly
    this). `mesh_shape` is {axis name: size}."""
    import jax

    out: Dict[str, Tuple] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state_shapes)[0]:
        p = path_str(path)
        shape = tuple(getattr(leaf, "shape", ()))
        out[p] = resolve_spec(
            logical_spec(p, len(shape)), shape, mesh_shape,
            spatial=spatial, shard_opt=shard_opt,
            is_opt=p.startswith("opt/"),
            zero=zero_targets_leaf(p, zero_stage))
    return out


def state_shardings(state_shapes: Pytree, mesh, *, spatial: bool = False,
                    shard_opt: bool = False,
                    zero_stage: int = 1) -> Pytree:
    """ShapeDtypeStruct tree -> matching NamedSharding tree, via the rule
    table resolved against `mesh`. The engine form of the derivation
    `parallel/sharding.state_shardings` wraps (both backends and the
    serve sources stay callers of that name)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh_shape = dict(mesh.shape)

    def to_sharding(path, leaf):
        p = path_str(path)
        shape = tuple(getattr(leaf, "shape", ()))
        parts = resolve_spec(
            logical_spec(p, len(shape)), shape, mesh_shape,
            spatial=spatial, shard_opt=shard_opt,
            is_opt=p.startswith("opt/"),
            zero=zero_targets_leaf(p, zero_stage))
        return NamedSharding(mesh, P(*parts))
    return jax.tree_util.tree_map_with_path(to_sharding, state_shapes)


def grad_shardings(param_shapes: Pytree, mesh) -> Pytree:
    """NamedSharding tree for one net's GRADIENT tree under ZeRO >= 2
    (the gspmd backend's reduce-scatter constraint targets): the same
    rule rows as the params with the `zero_insert` data-axis policy
    applied — a gradient leaf shards exactly like its mu/nu mirrors (the
    tail of "opt/<net>/.../mu/<leaf>" matches the same row as "<leaf>",
    audited by DCG011's grad-spec-derivation check), which is what makes
    the reduce-scattered gradient the shard-local Adam update's input
    with zero re-layout."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh_shape = dict(mesh.shape)

    def to_sharding(path, leaf):
        p = path_str(path)
        shape = tuple(getattr(leaf, "shape", ()))
        parts = resolve_spec(logical_spec(p, len(shape)), shape, mesh_shape,
                             zero=True)
        return NamedSharding(mesh, P(*parts))
    return jax.tree_util.tree_map_with_path(to_sharding, param_shapes)


def zero_scatter_dims(param_shapes: Pytree, mesh_shape) -> Pytree:
    """int tree over one net's params: the dim `zero_insert` places the
    data axis on, -1 when the leaf stays replicated (-1, not None — None
    is an empty pytree subtree and would break mapping this tree against
    a gradient tree). The shard_map backend's explicit collectives read
    this — psum_scatter's scatter_dimension and all_gather's axis must be
    THE dim the NamedSharding derivation chose, or the stored shards and
    the wire layout disagree."""
    import jax

    def to_dim(path, leaf):
        p = path_str(path)
        shape = tuple(int(d) for d in getattr(leaf, "shape", ()))
        base = resolve_spec(logical_spec(p, len(shape)), shape, mesh_shape)
        d, _ = zero_insert(base, shape, mesh_shape, co_shard=True)
        return -1 if d is None else d
    return jax.tree_util.tree_map_with_path(to_dim, param_shapes)


def validate_zero_state(state_shapes: Pytree, mesh_shape, *,
                        zero_stage: int) -> None:
    """The mesh-concrete half of the zero_stage validation (the config
    dataclass cannot see the device count). Raises when:

    - stage >= 2 runs over a data axis of size 1 (every reduce-scatter
      would be elided and the 'sharded' state would silently be the
      replicated state — the knob must fail loudly, not no-op);
    - a leaf the stage targets has >= 2x the data axis's elements yet NO
      dim the axis divides — the stage's memory model silently degrades
      for that leaf, so the error names it (leaves smaller than 2x the
      axis replicate for free and are exempt)."""
    import jax

    data_size = int(mesh_shape.get(DATA_AXIS, 1))
    if zero_stage >= 2 and data_size < 2:
        raise ValueError(
            f"zero_stage={zero_stage} shards state over the data axis, "
            f"which needs size > 1 (got data={data_size}); use "
            "zero_stage=1 on single-replica meshes")
    if zero_stage < 2:
        return
    for path, leaf in jax.tree_util.tree_flatten_with_path(state_shapes)[0]:
        p = path_str(path)
        if not zero_targets_leaf(p, zero_stage):
            continue
        shape = tuple(int(d) for d in getattr(leaf, "shape", ()))
        size = 1
        for d in shape:
            size *= d
        if size < 2 * data_size:
            continue
        base = resolve_spec(logical_spec(p, len(shape)), shape, mesh_shape)
        d, _ = zero_insert(base, shape, mesh_shape, co_shard=True)
        if d is None:
            raise ValueError(
                f"zero_stage={zero_stage} cannot shard state leaf {p!r} "
                f"(shape {shape}) over the {data_size}-way data axis: no "
                f"dim is divisible by {data_size}. Pad the offending dim, "
                "shrink the data axis, or drop to zero_stage=1")


def zero_bucket_plan(param_shapes: Pytree, mesh_shape, *,
                     bucket_mb: int = 4) -> Tuple[Tuple[int, ...], ...]:
    """Bucket plan for the collective overlap plane (ISSUE 20, DESIGN
    §6n): group one net's scatter-targeted leaves (`zero_scatter_dims`
    dim >= 0; replicated leaves stay outside every bucket) by dtype —
    packing mixed dtypes would force a cast and break the bit-exactness
    contract — and greedily cap each bucket at `bucket_mb` MiB of
    full-leaf bytes. A single leaf larger than the cap gets a bucket of
    its own. Deriving the plan HERE, from the same rule table that
    placed the shards, is what keeps the wire layout and the stored
    layout from ever disagreeing (the zero_scatter_dims contract).

    Returns a tuple of buckets, each a tuple of indices into the
    tree_leaves order of `param_shapes` — deterministic for a given
    (tree, mesh, cap), so the lowered program is cache-stable."""
    import math

    import jax
    import numpy as np

    dims_tree = zero_scatter_dims(param_shapes, mesh_shape)
    leaves = jax.tree_util.tree_leaves(param_shapes)
    dleaves = jax.tree_util.tree_leaves(dims_tree)
    cap = int(bucket_mb) * (1 << 20)
    if cap <= 0:
        raise ValueError(f"bucket_mb must be > 0, got {bucket_mb!r}")
    plan: List[Tuple[int, ...]] = []
    open_buckets: Dict[str, Tuple[List[int], int]] = {}
    for i, (leaf, d) in enumerate(zip(leaves, dleaves)):
        if d < 0:
            continue
        shape = tuple(int(s) for s in getattr(leaf, "shape", ()))
        nbytes = math.prod(shape) * np.dtype(leaf.dtype).itemsize
        dt = str(np.dtype(leaf.dtype))
        idxs, used = open_buckets.get(dt, ([], 0))
        if idxs and used + nbytes > cap:
            plan.append(tuple(idxs))
            idxs, used = [], 0
        idxs.append(i)
        used += nbytes
        if used >= cap:
            plan.append(tuple(idxs))
            idxs, used = [], 0
        open_buckets[dt] = (idxs, used)
    for dt in sorted(open_buckets):
        idxs, _ = open_buckets[dt]
        if idxs:
            plan.append(tuple(idxs))
    return tuple(plan)
