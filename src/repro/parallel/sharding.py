"""Sharding rules: FSDP (ZeRO-3) x TP (Megatron) x EP x decode-KV context
parallelism, expressed as PartitionSpecs over the production mesh.

Mesh axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod.
  * FSDP: every weight's non-TP giant dim is sharded over ("pod","data");
    GSPMD inserts the use-site all-gather and grad reduce-scatter.
  * TP: attention Q/O over heads (when divisible and cfg.attn_tp), FFN
    hidden over `model`, vocab/logits over `model`; GQA KV projections are
    small and stay replicated over `model`.
  * EP: MoE expert dim over `model`.
  * Decode caches: sequence/time dim over `model` (context parallelism) —
    the softmax/LSE merge across shards is derived by the partitioner from
    the reduction structure of decode_attention.
Dims that do not divide the axis size stay unsharded (exception: the vocab
dim may shard unevenly; XLA pads).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

STACK_KEYS = ("blocks", "enc_blocks", "dec_blocks")


def fsdp_axes(mesh: Mesh):
    """FSDP sharding entry: ('pod', 'data') multi-pod, bare 'data' otherwise
    (a singleton tuple and the bare name shard identically; the bare name
    keeps PartitionSpecs canonical for comparison/printing)."""
    return (("pod", "data") if "pod" in mesh.axis_names else "data")


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    s = 1
    for a in axes:
        s *= mesh.shape[a]
    return s


def _div(dim: int, mesh: Mesh, axes) -> Optional[Any]:
    """Return axes if dim divides the axes size, else None (no sharding)."""
    return axes if dim % axis_size(mesh, axes) == 0 else None


def _param_spec(name: str, shape: Tuple[int, ...], cfg: ModelConfig,
                mesh: Mesh, stacked: bool) -> P:
    """Sharding rule for one parameter by name/rank."""
    F = fsdp_axes(mesh)
    M = "model"
    body = shape[1:] if stacked else shape

    def spec(*parts):
        parts = tuple(_div(body[i], mesh, parts[i]) for i in range(len(parts)))
        return P(*((None,) + parts if stacked else parts))

    r = len(body)
    attn_tp = cfg.attn_tp
    if name in ("embed",):
        # (V, d): vocab over model (when divisible), d over FSDP
        return P(_div(body[0], mesh, M), _div(body[1], mesh, F))
    if name in ("lm_head",):
        return P(_div(body[0], mesh, F), _div(body[1], mesh, M))
    if name in ("wq",) and r == 3:          # (d, H, hd)
        return spec(F, M if attn_tp else None, None)
    if name in ("wk", "wv") and r == 3:     # (d, K, hd): KV replicated on M
        return spec(F, None, None)
    if name == "wo" and r == 3:             # (H, hd, d)
        return spec(M if attn_tp else None, None, F)
    if name == "bq":
        return spec(M if attn_tp else None, None)
    if name in ("bk", "bv"):
        return spec(None, None)
    if name in ("w_gate", "w_up", "w_in") and r == 2:    # (d, f)
        return spec(F, M)
    if name in ("w_down", "w_out") and r == 2:           # (f, d)
        return spec(M, F)
    if name in ("w_gate", "w_up", "w_in") and r == 3:    # MoE (E, d, f)
        return spec(M, F, None)
    if name in ("w_down", "w_out") and r == 3:           # MoE (E, f, d)
        return spec(M, None, F)
    if name == "router":
        return spec(F, None)
    # SSM branch
    if name in ("w_in_ssm", "w_z"):
        return spec(F, M)
    if name == "w_bc" or name == "w_dt":
        return spec(M, None)
    if name == "a_log":
        return spec(M, None)
    if name == "d_skip":
        return spec(M)
    # RWKV
    if name in ("wr", "wk2", "wv2", "wd", "cr"):
        return spec(F, M)
    if name == "ck":
        return spec(F, M)
    if name == "cv":
        return spec(M, F)
    # Norms, mixes, small vectors: replicated.
    return P(*((None,) * len(shape)))


# Names that collide between modules get disambiguated by their parent key.
_RENAME_BY_PARENT = {
    ("ssm", "w_in"): "w_in_ssm",
    ("ssm", "w_out"): "w_out_ssm",
}
_RWKV_RENAME = {"wk": "wk2", "wv": "wv2", "wo": "wo2"}


def _leaf_name(path) -> Tuple[str, Tuple[str, ...]]:
    keys = [k.key for k in path if hasattr(k, "key")]
    return keys[-1], tuple(keys)


def param_pspecs(cfg: ModelConfig, abstract_params: Dict, mesh: Mesh) -> Dict:
    """PartitionSpec pytree matching the params pytree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    specs = []
    for path, leaf in flat:
        name, keys = _leaf_name(path)
        stacked = any(k in STACK_KEYS for k in keys)
        parent = keys[-2] if len(keys) >= 2 else ""
        if (parent, name) in _RENAME_BY_PARENT:
            name = _RENAME_BY_PARENT[(parent, name)]
        if cfg.block_type == "rwkv" and name in _RWKV_RENAME:
            name = _RWKV_RENAME[name]
        # rwkv wo2 (d,d): shard (M, F) like an output proj
        if name == "wo2":
            body = leaf.shape[1:] if stacked else leaf.shape
            s = (("model" if body[0] % axis_size(mesh, "model") == 0
                  else None),
                 (fsdp_axes(mesh) if body[1] % axis_size(
                     mesh, fsdp_axes(mesh)) == 0 else None))
            specs.append(P(*((None,) + s if stacked else s)))
            continue
        if name == "w_out_ssm":
            body = leaf.shape[1:] if stacked else leaf.shape
            s = (("model" if body[0] % axis_size(mesh, "model") == 0
                  else None),
                 (fsdp_axes(mesh) if body[1] % axis_size(
                     mesh, fsdp_axes(mesh)) == 0 else None))
            specs.append(P(*((None,) + s if stacked else s)))
            continue
        specs.append(_param_spec(name, leaf.shape, cfg, mesh, stacked))
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_pspecs(cfg: ModelConfig, spec_tree: Dict, mesh: Mesh) -> Dict:
    """Input batch sharding: global batch over FSDP axes (when divisible —
    long_500k has global_batch=1, which stays replicated)."""
    F = fsdp_axes(mesh)

    def one(path, leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        return P(*((_div(leaf.shape[0], mesh, F),) + (None,) * (nd - 1)))

    return jax.tree_util.tree_map_with_path(one, spec_tree)


def cache_pspecs(cfg: ModelConfig, abstract_caches, mesh: Mesh) -> Dict:
    """Decode caches: batch over FSDP, sequence/state dim over `model`.

    Layouts (stacked leading L):
      kv k/v   (L, B, T, K, hd)   -> (None, F, M, None, None)
      ssm      (L, B, di, n)      -> (None, F, M, None)
      rwkv wkv (L, B, H, D, D)    -> (None, F, M, None, None)
      shifts   (L, B, d)          -> (None, F, M-if-divisible)
      cross xk (L, B, S, K, hd)   -> (None, F, M, None, None)
    """
    F = fsdp_axes(mesh)
    M = "model"

    def one(path, leaf):
        name, _ = _leaf_name(path)
        shp = leaf.shape
        nd = len(shp)
        if nd == 5:                      # (L,B,T,K,hd) or (L,B,H,D,D)
            return P(None, _div(shp[1], mesh, F), _div(shp[2], mesh, M),
                     None, None)
        if nd == 4:                      # ssm (L,B,di,n)
            return P(None, _div(shp[1], mesh, F), _div(shp[2], mesh, M),
                     None)
        if nd == 3:                      # shift (L,B,d)
            return P(None, _div(shp[1], mesh, F), _div(shp[2], mesh, M))
        return P(*(None,) * nd)

    return jax.tree_util.tree_map_with_path(one, abstract_caches)


def logical_out_pspec(mesh: Mesh) -> P:
    return P(fsdp_axes(mesh), "model")        # logits (B, V)


def _strip_fsdp(spec: P, drop_leading: bool) -> P:
    """Remove FSDP ('pod'/'data') axes from a spec; optionally drop the
    leading (layer-stack) entry — the use-site spec for one scanned layer."""
    entries = tuple(spec)
    if drop_leading and entries:
        entries = entries[1:]

    def strip(a):
        if a is None:
            return None
        axes = (a,) if isinstance(a, str) else tuple(a)
        kept = tuple(x for x in axes if x not in ("pod", "data"))
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]

    return P(*(strip(a) for a in entries))


def use_pspecs(cfg: ModelConfig, abstract_params: Dict, mesh: Mesh) -> Dict:
    """Use-site sharding for parameters: ZeRO-3 semantics.

    Parameters are *stored* FSDP-sharded (param_pspecs) but must be
    *consumed* gathered over the FSDP axes (TP sharding kept).  Without
    these hints GSPMD may instead partially contract against the FSDP-
    sharded weight and all-reduce the activations over `data` every layer
    (observed: 39 GiB/layer on nemotron train_4k — see EXPERIMENTS §Perf).
    Leaves keep the layer-stack dim dropped: hints apply inside the scan.
    """
    pflat = jax.tree_util.tree_flatten_with_path(abstract_params)[0]
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_pspecs(cfg, abstract_params, mesh))
    out = []
    for (path, spec), (_, leaf) in zip(flat, pflat):
        keys = [k.key for k in path if hasattr(k, "key")]
        stacked = any(k in STACK_KEYS for k in keys)
        name = keys[-1] if keys else ""
        rank = len(leaf.shape) - (1 if stacked else 0)
        if cfg.num_experts and rank == 3 and name in (
                "w_gate", "w_up", "w_in", "w_down", "w_out"):
            # MoE expert tensors: a gather hint here gets hoisted out of
            # the layer scan by XLA and materializes the WHOLE gathered
            # expert stack (arctic prefill: +106 GiB/chip — §Perf P3).
            # Leave experts to GSPMD's partial-contraction strategy.
            # ("skip" sentinel: None would vanish as an empty pytree.)
            out.append("skip")
            continue
        out.append(_strip_fsdp(spec, drop_leading=stacked))
    return jax.tree_util.tree_unflatten(treedef, out)


def named(mesh: Mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree)


def constrain_activations(x: jax.Array, mesh: Mesh,
                          seq_parallel: bool = False) -> jax.Array:
    """Sharding hint for (B, S, d) activations inside the step function."""
    F = fsdp_axes(mesh)
    if seq_parallel:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(F, "model", None)))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(F, None, None)))


# ---------------------------------------------------------------------------
# Collision service: shard the canonical flat pair pool (DESIGN.md §6)
# ---------------------------------------------------------------------------

#: The collision mesh's single axis: the flat query pool is split over it,
#: the scene octree is replicated on every device.
COLLISION_AXIS = "shard"


def make_collision_mesh(shards: int) -> Mesh:
    """1-D mesh of ``shards`` devices for sharded collision traversal."""
    devs = jax.devices()
    if not 1 <= shards <= len(devs):
        raise ValueError(
            f"collision mesh wants {shards} device(s) but the backend "
            f"exposes {len(devs)}")
    return Mesh(devs[:shards], (COLLISION_AXIS,))


def shard_collision_traversal(fn, mesh: Mesh):
    """shard_map a single-scene traversal over the collision mesh.

    ``fn(num_valid, c, h, r, dev) -> (verdict, stats)`` is the per-device
    traversal body; the wrapper maps it over :data:`COLLISION_AXIS` with
    the (padded) query pool split into equal contiguous blocks and the
    scene tables replicated, then reduces the stats dict so the caller
    sees the same values a single-device run would produce:

      * every work counter is summed over shards (traversal of each query
        is independent, so partitioning the pool partitions the sums —
        bitwise equality, CI-enforced);
      * ``overflow`` takes the **global max** over per-shard overflow
        flags — the executor's escalation loop replays ALL shards at 4x
        capacity as soon as any one of them spilled, keeping the replay
        ladder (and therefore the traced capacities) globally coordinated.

    The wrapped callable takes ``(counts (shards,) int32, c, h, r, dev)``
    and returns the still-sharded verdict plus the reduced stats with a
    leading shard axis of identical rows (the traversal's ``while_loop``
    has no shard_map replication rule, so the wrapper runs with
    ``check_vma=False`` and cannot declare replicated ``P()`` outputs —
    callers read row 0).
    """
    axis = COLLISION_AXIS

    def local(counts, c, h, r, dev):
        verdict, st = fn(counts[0], c, h, r, dev)
        red = {k: (jax.lax.pmax(v, axis) if k == "overflow"
                   else jax.lax.psum(v, axis))[None]
               for k, v in st.items()}
        return verdict, red

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(axis), P(axis), P(axis), P(axis), P()),
                         out_specs=(P(axis), P(axis)), check_vma=False)
