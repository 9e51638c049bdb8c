"""Parameter / activation / cache sharding rules (GSPMD partition specs).

Scheme (MaxText-style):
  * TP  — attention heads, FFN columns, vocab on the `model` axis.
  * FSDP — the other big weight dim additionally sharded on `data` (and `pod`
    when present), so 340B-class params fit 16 GB HBM chips. XLA inserts the
    per-layer all-gathers; scan-over-layers keeps them inside the loop body.
  * EP  — MoE expert dim on `model` (dispatch becomes an all-to-all).
  * Activations — batch on (pod, data); saved-for-backward residuals are
    additionally sequence-sharded on `model` (sequence parallelism).

Rules dispatch on the parameter's path (nested dict keys) + ndim, so one rule
set covers all 10 architectures. Fallback: replicate.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, tree_map_with_path

from repro.parallel.mesh import BATCH_AXES

# Leaf-key parents whose "w" is sharded on its LAST dim (TP columns):
_COL_PARALLEL = {
    "wq", "wk", "wv", "wg", "wu", "wq_a", "wq_b", "wkv_b", "ck", "cr",
    "exp", "pw", "c1",
}
# Parents whose "w" is sharded on its SECOND-TO-LAST dim (TP rows):
_ROW_PARALLEL = {"wo", "wd", "cv"}
# Parents replicated on model (small / awkward dims):
_REPLICATED = {
    "router", "wkv_a", "kv_a_norm", "q_a_norm", "in_proj", "out_proj",
    "conv", "dw", "fc",
}

# FSDP axis: shard the OTHER big dim of every matrix on the data axes too.
# Enabled per-call; the dry-run enables it for every arch (nothing fits
# otherwise at 340B), tests on 1 device disable it implicitly (axes absent).


def _fsdp_axes(mesh_axes: tuple[str, ...]) -> tuple[str, ...]:
    return tuple(a for a in BATCH_AXES if a in mesh_axes)


def _path_keys(path) -> list[str]:
    keys = []
    for p in path:
        if isinstance(p, DictKey):
            keys.append(str(p.key))
        elif hasattr(p, "name"):      # GetAttrKey (KVSegment fields)
            keys.append(str(p.name))
        elif hasattr(p, "idx"):       # SequenceKey (cache.segments index)
            keys.append(str(p.idx))
        else:
            keys.append(str(p))
    return keys


def _spec_for_leaf(keys: list[str], leaf, mesh_axes: tuple[str, ...], fsdp: bool) -> P:
    ndim = np.ndim(leaf)
    model = "model" if "model" in mesh_axes else None
    fsdp_ax = _fsdp_axes(mesh_axes) if fsdp else ()
    fsdp_ax = fsdp_ax if fsdp_ax else None

    name = keys[-1]
    parent = keys[-2] if len(keys) >= 2 else ""

    def spec(*entries):
        """Pad leading None for stacked layer axes."""
        pad = ndim - len(entries)
        return P(*((None,) * pad + tuple(entries)))

    # ---- embeddings / unembeddings ------------------------------------
    if name == "embed":
        # (V, D): vocab on model (keeps logits V-sharded), D on fsdp.
        return spec(model, fsdp_ax)
    if name in ("lm_head",):
        # (D, V)
        return spec(fsdp_ax, model)
    if name in ("pos_embed_dec",):
        return spec(None, None)

    # ---- MoE expert stacks: raw arrays named wg/wu/wd with an E dim ----
    if name in ("wg", "wu", "wd") and ndim >= 3 and parent == "moe" or (
        name in ("wg", "wu", "wd") and ndim >= 3 and "moe" in keys
    ):
        # (..., E, d_in, d_out): experts on model (EP), d_in on fsdp.
        return spec(model, fsdp_ax, None)

    # ---- dense matrices {parent: {"w": ...}} ---------------------------
    if name == "w":
        if parent in _COL_PARALLEL:
            return spec(fsdp_ax, model)
        if parent in _ROW_PARALLEL:
            return spec(model, fsdp_ax)
        if parent in _REPLICATED:
            # still FSDP-shard the biggest dim so huge replicated mats fit
            if ndim >= 2:
                return spec(fsdp_ax, None)
            return spec()
        if ndim >= 2:
            return spec(fsdp_ax, None)
        return spec()
    if name == "b":
        if parent in _COL_PARALLEL:
            return spec(model)
        return spec()

    # ---- rwkv raw mats (wr/wk/wv/wg live as {"w"} too -> handled above)
    if name in ("wA", "wB", "u", "w0"):
        return spec(*([None] * ndim))

    # ---- mamba conv / scalars ------------------------------------------
    if name in ("conv_w", "conv_b", "A_log", "dt_bias", "D"):
        return spec(*([None] * ndim))

    # ---- norms / small vectors ------------------------------------------
    return spec(*([None] * ndim))


def fit_spec(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Drop / shrink spec entries whose axis sizes don't divide the dim.

    Tuple entries degrade to their longest dividing prefix (e.g. batch 1 on
    ("pod","data") -> replicated; batch 64 on ("pod","data")=32 stays). GSPMD
    CAN pad uneven shardings, but padded params corrupt optimizer norms and
    padded activations waste flops — we never want them implicitly.
    """
    out = []
    for i, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        while names:
            prod = 1
            for n in names:
                prod *= mesh.shape[n]
            if shape[i] % prod == 0 and shape[i] >= prod:
                break
            names = names[:-1]
        if not names:
            out.append(None)
        elif len(names) == 1:
            out.append(names[0])
        else:
            out.append(tuple(names))
    return P(*out)


def param_specs(params: Any, mesh: Mesh, *, fsdp: bool = True):
    """PartitionSpec pytree matching `params` (nested dicts of arrays)."""
    axes = tuple(mesh.axis_names)

    def rule(path, leaf):
        spec = _spec_for_leaf(_path_keys(path), leaf, axes, fsdp)
        return fit_spec(spec, tuple(leaf.shape), mesh)

    return tree_map_with_path(rule, params)


def param_shardings(params: Any, mesh: Mesh, *, fsdp: bool = True):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs(params, mesh, fsdp=fsdp)
    )


# ---------------------------------------------------------------------------
# Activation / batch / cache specs
# ---------------------------------------------------------------------------

def data_batch_spec(mesh_axes: tuple[str, ...], ndim: int,
                    dim0: int | None = None, mesh: Mesh | None = None) -> P:
    """(B, ...) arrays: batch on all DP axes (longest dividing prefix when
    dim0/mesh are given — a batch of 1 replicates)."""
    axes = tuple(a for a in BATCH_AXES if a in mesh_axes)
    lead = axes if axes else None
    spec = P(*((lead,) + (None,) * (ndim - 1)))
    if dim0 is not None and mesh is not None:
        spec = fit_spec(spec, (dim0,) + (1,) * (ndim - 1), mesh)
    return spec


def step_vec_sharding(mesh: Mesh, batch: int):
    """NamedSharding for the serve loop's device-resident (B,) per-slot
    vectors — the fused decode step's token/position state and its sampled
    token output.  Slots ride the data axes exactly like the pool's batch
    dim, so the step's scatter/gather stays shard-local; a batch the data
    axes don't divide replicates (fit_spec)."""
    from jax.sharding import NamedSharding

    spec = data_batch_spec(tuple(mesh.axis_names), 1, dim0=batch, mesh=mesh)
    return NamedSharding(mesh, spec)


def activation_spec(mesh_axes: tuple[str, ...], *, seq_sharded: bool = False) -> P:
    """(B, S, D) activations: batch on DP; optionally S on model (seq-par)."""
    axes = tuple(a for a in BATCH_AXES if a in mesh_axes)
    lead = axes if axes else None
    model = "model" if (seq_sharded and "model" in mesh_axes) else None
    return P(lead, model, None)


def kv_cache_spec(mesh_axes: tuple[str, ...], n_kv_heads: int, model_size: int,
                  *, stacked: bool = True) -> P:
    """(L, B, S, Hkv, hd) cache: B on data axes; heads on model if divisible,
    else the sequence dim (long caches shard fine over S)."""
    axes = tuple(a for a in BATCH_AXES if a in mesh_axes)
    lead = axes if axes else None
    has_model = "model" in mesh_axes
    head_ok = has_model and n_kv_heads % model_size == 0 and n_kv_heads >= model_size
    if head_ok:
        body = (None, "model", None)
    else:
        body = ("model" if has_model else None, None, None)  # shard S
    entries = (lead,) + body
    if stacked:
        return P(*((None,) + entries))
    return P(*entries)


def latent_cache_spec(mesh_axes: tuple[str, ...], *, stacked: bool = True) -> P:
    """MLA (L, B, S, r) latent cache: B on data, S on model."""
    axes = tuple(a for a in BATCH_AXES if a in mesh_axes)
    lead = axes if axes else None
    model = "model" if "model" in mesh_axes else None
    entries = (lead, model, None)
    if stacked:
        return P(*((None,) + entries))
    return P(*entries)


def ambient_mesh():
    """The mesh installed with `jax.set_mesh` — the one whose axes sharding
    hints may name — or None when no mesh is set."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def _free_axes(mesh) -> tuple[str, ...]:
    """Mesh axes not already manual (shard_map) at this point of the trace."""
    manual = set(mesh.manual_axes)
    return tuple(a for a in mesh.axis_names if a not in manual)


def kernel_rows_axes(n_rows: int) -> tuple[str, ...] | None:
    """Mesh axes a kernel call may split `n_rows` independent rows over:
    every free ambient axis when their product divides `n_rows`, else None
    (the call runs replicated)."""
    mesh = ambient_mesh()
    if mesh is None:
        return None
    axes = _free_axes(mesh)
    size = int(np.prod([mesh.shape[a] for a in axes]))
    return axes if axes and n_rows % size == 0 else None


def kernel_map(fn, in_specs, out_specs):
    """`fn` (a Pallas kernel call) made runnable under the ambient mesh.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so under a mesh the call runs per device
    through `jax.shard_map` over every free axis, with the given specs;
    operands arrive resharded to them.  Without a mesh `fn` is returned
    unchanged."""
    mesh = ambient_mesh()
    if mesh is None or not _free_axes(mesh):
        return fn
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(_free_axes(mesh)), check_vma=False)


def attn_hint(x: jax.Array, *, s_axis: int = 1, h_axis: int = 2) -> jax.Array:
    """(B, S, H, hd) attention-tensor constraint: heads on `model` when
    divisible (Megatron TP), else SEQUENCE on `model` (context parallelism —
    works for any head count, e.g. qwen2's 14 or whisper's 8 heads; K/V get
    all-gathered per block, which is cheap next to score-sized partial-sum
    all-reduces GSPMD otherwise invents)."""
    mesh = ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return x
    msize = mesh.shape["model"]
    entries = ["batch"] + [None] * (x.ndim - 1)
    if x.shape[h_axis] % msize == 0 and x.shape[h_axis] >= msize:
        entries[h_axis] = "model"
    elif x.shape[s_axis] % msize == 0 and x.shape[s_axis] >= msize:
        entries[s_axis] = "model"
    return logical(x, *entries)


def _plane_block_ndims() -> dict:
    """Block rank per codec-family plane base name (lazy: parallel must not
    import codec at module scope — codec.api pulls jax program-building
    machinery this leaf module stays independent of)."""
    from repro.codec import families

    return families.plane_block_ndims()


def cache_specs(cache_shapes: Any, cfg, mesh: Mesh):
    """PartitionSpec pytree for a decode cache (raw, latent, recurrent, or
    DCT-compressed). Dispatch on leaf key + rank.

    Accepts plain dicts of planes AND the serve engine's `CompressedKVCache`
    (a tuple of `KVSegment`s — registered with key paths, so each segment's
    packed/scale/tail planes dispatch by name exactly like the dict form).
    Batch slots land on the data axes, kv heads on `model` when divisible —
    the mesh-wide analogue of the paper's banked feature-map buffer: every
    bank (device) owns a fixed slice of the slot pool and of the head planes,
    and decode-step traffic for a slot never leaves its bank."""
    axes = tuple(mesh.axis_names)
    dp = tuple(a for a in BATCH_AXES if a in axes) or None
    has_model = "model" in axes
    msize = mesh.shape["model"] if has_model else 1

    def head_axis_ok(n_heads):
        return has_model and n_heads >= msize and n_heads % msize == 0

    def rule(path, leaf):
        keys = _path_keys(path)
        name = keys[-1]
        nd = len(leaf.shape)
        if name in ("k", "v"):                      # (L|G, B, S, Hkv, hd)
            return kv_cache_spec(axes, cfg.n_kv_heads, msize, stacked=True)
        if name in ("c_kv", "k_rope"):              # (L, B, S, r)
            return latent_cache_spec(axes, stacked=True)
        if name in ("tail_k", "tail_v"):            # (L, B, 8, Hkv, hd)
            h = "model" if head_axis_ok(cfg.n_kv_heads) else None
            return P(None, dp, None, h, None)
        if name.startswith("rows_"):                # (L, rows, 1, 128) page rows
            # pages ride the data axes; a row holds every KV head of a
            # page, so heads cannot ride `model`: replicated over it
            return P(None, dp, None, None)
        base = name[:-2] if name.endswith(("_k", "_v")) else None
        block_nd = _plane_block_ndims().get(base)
        if block_nd is not None:
            # codec-family block plane (families.plane_block_ndims declares
            # the per-block rank n; dct packed n=3, scale n=1, ...):
            #   dense      : (L, B, S/8, Hkv) + block_shape  -> rank 4 + n
            h = "model" if head_axis_ok(cfg.n_kv_heads) else None
            assert nd == 4 + block_nd, (name, nd, block_nd)
            return P(None, dp, None if h else ("model" if has_model else None),
                     h, *([None] * block_nd))      # dense
        if name == "block_table":                   # (B, S/8) page ids
            return P(dp, None)
        if name == "ssm":                           # (G, A, B, H, P, N)
            nh = leaf.shape[3]
            h = "model" if (has_model and nh % msize == 0 and nh >= msize) else None
            return P(None, None, dp, h, None, None)
        if name == "conv":                          # (G, A, B, K-1, conv_dim)
            return P(None, None, dp, None, None)
        if name == "S":                             # rwkv (L, B, H, N, N)
            nh = leaf.shape[2]
            h = "model" if (has_model and nh % msize == 0 and nh >= msize) else None
            return P(None, dp, h, None, None)
        if name in ("x_tm", "x_cm"):                # (L, B, D)
            return P(None, dp, None)
        return P(*([None] * nd))

    return tree_map_with_path(
        lambda path, leaf: fit_spec(rule(path, leaf), tuple(leaf.shape), mesh),
        cache_shapes,
    )


def cache_shardings(cache_shapes: Any, cfg, mesh: Mesh):
    """NamedSharding pytree matching `cache_shapes` (see cache_specs)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), cache_specs(cache_shapes, cfg, mesh)
    )


def kv_pool_specs(cfg, plan, mesh: Mesh, *, batch: int, max_seq: int,
                  dtype=None, n_pages: int | None = None):
    """Cache specs for the compressed KV slot pool straight from the plan.

    Builds the `CompressedKVCache` shape tree (one `KVSegment` per contiguous
    equal-policy layer run) without allocating, then applies the cache rules:
    int8 DCT blocks, scales and raw tails sharded on the data axes (batch
    slots) with kv heads on `model` — the same placement `param_specs` gives
    the attention weights, so decode never reshards between them.

    With `n_pages` the tree is the PAGED pool instead: page rows and block
    tables shard on the data axes (each device/bank owns a slice of the
    page pool), replicated over `model` (a row holds every KV head of its
    page), tails per slot as before.
    """
    from repro.core import kv_cache as kvc  # lazy: core imports stay one-way

    kw = {} if dtype is None else {"dtype": dtype}
    if n_pages is None:
        shapes = jax.eval_shape(
            lambda: kvc.init_compressed_cache(cfg, batch, max_seq, plan=plan,
                                              **kw))
    else:
        shapes = jax.eval_shape(
            lambda: kvc.init_paged_cache(cfg, batch, max_seq, n_pages,
                                         plan=plan, **kw))
    return cache_specs(shapes, cfg, mesh)


def host_transfer_shardings(tree_shapes: Any, mesh: Mesh):
    """Replicated NamedShardings for host-origin tensors entering the mesh.

    The tiered page pool's host backing store lives OUTSIDE the mesh (plain
    numpy on the serve host); when a parked slot's pages stream back, the
    restore jit takes the numpy update tree as input and scatters it into
    the sharded pool. Pinning the update's in_shardings to replicated makes
    that boundary explicit and deterministic — every device receives the
    handful of restored blocks, and the jit's `out_shardings` (the pool's
    own NamedShardings) re-places the result on the pool's banks, so the
    hot decode path never sees a differently-placed cache. Works for any
    pytree: spill/restore update trees, page-id vectors, table rows.
    """
    rep = NamedSharding(mesh, P())
    return jax.tree.map(lambda _: rep, tree_shapes)


def per_device_bytes(shapes: Any, specs: Any, mesh: Mesh) -> float:
    """Bytes each device holds of a pytree sharded per `specs` on `mesh`."""
    leaves = jax.tree.leaves(shapes)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    total = 0.0
    for leaf, spec in zip(leaves, spec_leaves):
        factor = 1
        for entry in spec:
            if entry is None:
                continue
            for name in (entry if isinstance(entry, tuple) else (entry,)):
                factor *= mesh.shape[name]
        itemsize = np.dtype(leaf.dtype).itemsize
        total += int(np.prod(leaf.shape)) * itemsize / factor
    return total


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint that no-ops when no mesh context is set
    (keeps single-device unit tests independent of distribution)."""
    if ambient_mesh() is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def logical(x: jax.Array, *entries) -> jax.Array:
    """Activation sharding constraint with axis filtering + divisibility.

    `entries` name one spec entry per dim: "batch" (-> all DP axes present),
    "model", or None. Axes absent from the active mesh are dropped; a "model"
    entry whose dim is not divisible by the model-axis size is dropped too
    (GSPMD padding on activations is never worth it). No mesh context => noop.

    This is the single hook every model layer uses — the hillclimb loop
    changes WHERE these are placed, not the models themselves.
    """
    mesh = ambient_mesh()
    if mesh is None:
        return x
    # inside a partial-manual shard_map (GradCompress pod exchange) the
    # manual axes must not appear in constraints — they're implicit
    names = set(mesh.axis_names) - set(mesh.manual_axes)
    shape = x.shape
    out = []
    for i, e in enumerate(entries):
        if e == "batch":
            dp = tuple(a for a in BATCH_AXES if a in names)
            dpn = 1
            for a in dp:
                dpn *= mesh.shape[a]
            out.append(dp if dp and shape[i] % max(dpn, 1) == 0 else None)
        elif e == "model":
            ok = "model" in names and shape[i] % mesh.shape["model"] == 0 \
                and shape[i] >= mesh.shape["model"]
            out.append("model" if ok else None)
        else:
            out.append(None)
    return jax.lax.with_sharding_constraint(x, P(*out))


def table_slice_hint(table: jax.Array) -> jax.Array:
    """Placement constraint for a decode-ladder block-table slice.

    A bucket slice `table_view(bt, attend_blocks)` must keep the FULL
    table's placement (`cache_specs`'s block_table rule: slots on the data
    axes, table entries replicated) — otherwise the static slice inside the
    decode step would resolve to a fresh GSPMD decision per bucket and the
    per-bucket jits could disagree on where the gather runs.  One rule,
    applied to every sliced view, keeps all ladder buckets layout-identical
    to the unsliced step."""
    return logical(table, "batch", None)
