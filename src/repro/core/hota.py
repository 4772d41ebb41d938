"""HOTA-FedGradNorm, distributed (the production integration — DESIGN.md §3.1).

The paper's two-level aggregation is attached to the FSDP parameter gather
as a ``jax.custom_vjp``:

    forward : shard --all-gather over ("cluster","client")--> full param
              (= PS -> IS -> client broadcast, Alg. 1 lines 3-6)
    backward: per-client full grad
              --weighted psum over "client"-->        x^(l) at the IS (eq. 3)
              --masked psum over ("pod","cluster")--> MAC superposition (eq. 8)
              + AWGN, / (|M|·N)                       PS estimate     (eq. 10)
              --slice own shard-->                    FSDP reduce-scatter

so autodiff of any scan-stacked backbone routes every parameter gradient
through the paper's aggregation, one layer at a time (no full per-client
gradient is ever materialized). The shard_map is *manual* over every mesh
axis — Mosaic kernels cannot be auto-partitioned — and no spec names a
non-FL axis ("model"), so devices along one hold replicas.

Channel keys: fold(step_key, class_salt, *layer_tags, leaf_idx) then, in
the backward, fold(cluster) — one i.i.d. gain per parameter entry per
cluster per iteration (paper Sec. III-A), reproducible across the FGN
phase (mask in eq. 5) and the transmission (eq. 8).

Model code cooperates through an optional ``param_hook(subtree, klass,
*tags)`` called right before each layer's parameters are used; without a
hook the models behave as plain (non-FL) networks.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import FLConfig, ModelConfig, TrainConfig
from repro.core.ota import HOTA_MASK_SALT
from repro.common.flatpack import check_tree_matches_packer, packer_for
from repro.core.channel import ChannelParams
from repro.kernels.ota_channel.ops import _ota_channel_impl
from repro.kernels.slab import flat_to_slab, on_tpu
from repro.models.model import Model, lm_loss
from repro.models.params import logical_axes
from repro.optim.adam import adam_init, adam_update

CLIENT_AXIS = "client"

KLASS_SALT = {
    "embed": 1, "layers": 2, "final": 3, "mamba": 4,
    "shared_attn": 5, "shared_mlp": 6, "mlstm": 7, "slstm": 8,
}


def _is_axes(x):
    return isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)


def _strip_layer(axes: tuple) -> tuple:
    return tuple(a for a in axes if a != "layer")


def _fsdp_axis(axes: tuple) -> int:
    stripped = _strip_layer(axes)
    return stripped.index("embed") if "embed" in stripped else -1


def _zero_cot(x):
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.zeros_like(x)
    return np.zeros(x.shape, jax.dtypes.float0)


class OTACtx(NamedTuple):
    """Traced context for the OTA backward. Passed as explicit custom_vjp
    arguments (closures over tracers break under scan)."""
    p_weight: jax.Array      # this client's FedGradNorm weight p_k^(l,i)
    key: jax.Array           # folded key for this leaf
    sigma2: jax.Array        # this cluster's channel variance σ_l²
    h_th: jax.Array          # threshold H_th
    noise_std: jax.Array     # AWGN std
    ota_on: jax.Array        # 1.0 = fading MAC; 0.0 = error-free baseline
    # Partial participation (DESIGN.md §3.14). None = full participation
    # (an empty pytree node — the custom_vjp residual tree stays legal).
    live: Optional[jax.Array] = None    # (C,) cluster participation flags
    n_eff: Optional[jax.Array] = None   # () traced effective N of eq. 10


def fold_tags(key: jax.Array, klass: str, tags, leaf_idx: int) -> jax.Array:
    k = jax.random.fold_in(key, KLASS_SALT[klass])
    for t in tags:
        k = jax.random.fold_in(k, t)
    return jax.random.fold_in(k, leaf_idx)


def cluster_index(cluster_axes: Tuple[str, ...]) -> jax.Array:
    cidx = jax.lax.axis_index(cluster_axes[0])
    for a in cluster_axes[1:]:
        cidx = cidx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return cidx


def channel_mask_for(key: jax.Array, shape, sigma2, h_th, ota_on,
                     cluster_axes) -> jax.Array:
    """The mask M_k^(l) this device's cluster sees for one leaf (eq. 7)."""
    ckey = jax.random.fold_in(key, cluster_index(cluster_axes))
    h = jax.random.normal(ckey, shape, jnp.float32) * jnp.sqrt(sigma2)
    return jnp.logical_or((h * h) >= h_th, ota_on < 0.5)


REGION_SALT = 0xC0


def region_mask_key(leaf_key: jax.Array, region) -> jax.Array:
    """Key for one scatter region's channel draw (scatter mode). Region
    indices partition the FSDP axis client-major; the full-tensor mask is
    the concatenation of region masks (see full_transmission_mask)."""
    return jax.random.fold_in(jax.random.fold_in(leaf_key, REGION_SALT),
                              region)


def full_transmission_mask(leaf_key, shape, axis, n_regions, sigma2, h_th,
                           ota_on, cluster_axes, scatter_mode: bool):
    """The full-tensor mask M_k^(l) exactly as the transmission draws it —
    used by the FGN phase (eq. 5) so F_grad sees the channel the MAC will
    apply. In scatter mode, sharded leaves draw per-region; replicated
    leaves (and all leaves in naive mode) draw whole-tensor."""
    if not scatter_mode or axis < 0:
        return channel_mask_for(leaf_key, shape, sigma2, h_th, ota_on,
                                cluster_axes)
    sub = list(shape)
    assert sub[axis] % n_regions == 0, (shape, axis, n_regions)
    sub[axis] //= n_regions
    pieces = [
        channel_mask_for(region_mask_key(leaf_key, r), tuple(sub), sigma2,
                         h_th, ota_on, cluster_axes)
        for r in range(n_regions)
    ]
    return jnp.concatenate(pieces, axis=axis)


def make_ota_gather(data_axes: Tuple[str, ...],
                    cluster_axes: Tuple[str, ...],
                    n_clients: int, n_shards: int, compute_dtype,
                    mode: str = "scatter"):
    """Build the custom-vjp FSDP gather for one mesh topology.

    ``data_axes`` MUST be ("client", "cluster") — client-major piece order
    is what makes the scatter pipeline's regions align with FSDP pieces.

    axis >= 0 leaves are FSDP-sharded on that dim; axis == -1 leaves are
    replicated over the data axes (identity fwd, full-size OTA bwd).

    Backward = Algorithm 1's aggregation, two implementations:

    * mode="naive"   (paper-literal): weighted psum over "client" (LAN,
      eq. 3) at FULL tensor size, masked psum over clusters (MAC, eq. 8)
      at full size, estimate (eq. 10), slice own shard. 2 full-size
      all-reduces + a full-size count per parameter per round.
    * mode="scatter" (optimized, identical math): psum_scatter the
      weighted gradients over "client" — the LAN sum arrives pre-split
      into per-client regions (1/N size); per-region channel masks; the
      MAC psum over clusters runs on regions; slice my cluster's sub-piece.
      ~3x fewer collective bytes, no full-size intermediate.

    Round semantics under gradient accumulation: channel keys fold only
    (step, layer, leaf) — masks and AWGN are IDENTICAL across microbatches,
    so averaging microbatch estimates equals one MAC transmission of the
    round-averaged x^(l) (eq. 8 applied once per iteration k).
    """
    assert data_axes[0] == CLIENT_AXIS, data_axes

    @partial(jax.custom_vjp, nondiff_argnums=(0,))
    def ota_gather(axis: int, shard, ctx: OTACtx):
        if axis >= 0:
            full = jax.lax.all_gather(shard, data_axes, axis=axis, tiled=True)
        else:
            full = shard
        return full.astype(compute_dtype)

    def _fwd(axis, shard, ctx):
        return ota_gather(axis, shard, ctx), (ctx,)

    def _estimate(y, cnt, z, n):
        return jnp.where(cnt > 0, (y + z) / (jnp.maximum(cnt, 1.0) * n), 0.0)

    def _bwd(axis, res, g):
        (ctx,) = res
        g = g.astype(jnp.float32)

        if mode == "scatter" and axis >= 0:
            # LAN via reduce-scatter: region i of x^(l) lands on client i
            x_reg = jax.lax.psum_scatter(ctx.p_weight * g, CLIENT_AXIS,
                                         scatter_dimension=axis, tiled=True)
            my_region = jax.lax.axis_index(CLIENT_AXIS)
            mkey = region_mask_key(ctx.key, my_region)
            mask = channel_mask_for(mkey, x_reg.shape, ctx.sigma2, ctx.h_th,
                                    ctx.ota_on, cluster_axes)
            cnt = jax.lax.psum(mask.astype(jnp.float32), cluster_axes)
            y = jax.lax.psum(jnp.where(mask, x_reg, 0.0), cluster_axes)
            z = (jax.random.normal(
                jax.random.fold_in(mkey, HOTA_MASK_SALT), x_reg.shape,
                jnp.float32)
                * ctx.noise_std * ctx.ota_on)
            ghat_reg = _estimate(y, cnt, z, n_clients)
            # my FSDP piece = my cluster's sub-slice of my region
            cidx = jax.lax.axis_index(data_axes[1])
            for a in data_axes[2:]:
                cidx = cidx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
            n_sub = n_shards // n_clients   # CLIENT_AXIS size by construction
            sz = ghat_reg.shape[axis] // n_sub
            my = jax.lax.dynamic_slice_in_dim(ghat_reg, cidx * sz, sz, axis)
            return (my, jax.tree.map(_zero_cot, ctx))

        # naive / replicated-leaf path: full-size psums
        x = jax.lax.psum(ctx.p_weight * g, CLIENT_AXIS)
        mask = channel_mask_for(ctx.key, g.shape, ctx.sigma2, ctx.h_th,
                                ctx.ota_on, cluster_axes)
        cnt = jax.lax.psum(mask.astype(jnp.float32), cluster_axes)
        y = jax.lax.psum(jnp.where(mask, x, 0.0), cluster_axes)
        z = (jax.random.normal(jax.random.fold_in(ctx.key, HOTA_MASK_SALT),
                               g.shape, jnp.float32)
             * ctx.noise_std * ctx.ota_on)
        ghat = _estimate(y, cnt, z, n_clients)
        if axis >= 0:
            me = jax.lax.axis_index(data_axes[0])
            for a in data_axes[1:]:
                me = me * jax.lax.axis_size(a) + jax.lax.axis_index(a)
            sz = g.shape[axis] // n_shards
            ghat = jax.lax.dynamic_slice_in_dim(ghat, me * sz, sz, axis)
        return (ghat, jax.tree.map(_zero_cot, ctx))

    ota_gather.defvjp(_fwd, _bwd)
    return ota_gather


# --------------------------------------------------------------------------
# flat-packed final-subtree gather (ω̃ as ONE slab through the OTA MAC)
# --------------------------------------------------------------------------
# The last shared layer is where FedGradNorm reads its masked norms (eq. 5)
# and where the per-leaf machinery costs the most bookkeeping: every leaf
# used to pay its own mask draw + 3 collectives in the backward. Packing
# ω̃'s full-size gradients into one lane-aligned slab runs the whole
# subtree through ONE fused Pallas mask+apply kernel and ONE set of psums,
# and gives the FGN phase bit-identical masks from the same flat draw.

PACKED_FINAL_FOLD = 0x7FFF00F1   # reserved fold — disjoint from leaf indices


def packed_final_key(base_key: jax.Array) -> jax.Array:
    """The single channel key of the packed ω̃ slab (replaces per-leaf
    fold_tags(base_key, "final", (), i))."""
    return jax.random.fold_in(
        jax.random.fold_in(base_key, KLASS_SALT["final"]), PACKED_FINAL_FOLD)


def _packed_mask_apply(x_slab: jax.Array, key: jax.Array, sigma2, h_th,
                       ota_on, cluster_axes):
    """This cluster's fused bits→gaussian→threshold→apply on a (P,) slab.

    Returns (masked_x, mask) as (P,) f32 — the Pallas ota_channel kernel
    on the packed layout. Both the gather backward and the FGN norm call
    this with the same key, so eq. 5 sees exactly the transmission masks.
    """
    ckey = jax.random.fold_in(key, cluster_index(cluster_axes))
    bits = jax.random.bits(ckey, x_slab.shape, jnp.uint32)
    out, mask = _ota_channel_impl(
        flat_to_slab(x_slab), flat_to_slab(bits), sigma2, h_th, ota_on,
        interpret=not on_tpu())
    p = x_slab.shape[-1]
    return out.reshape(p), mask.reshape(p)


def make_packed_final_gather(data_axes: Tuple[str, ...],
                             cluster_axes: Tuple[str, ...],
                             n_clients: int, n_shards: int, compute_dtype,
                             axes_list: List[tuple], template=None):
    """Custom-vjp gather for the WHOLE final subtree.

    forward : per-leaf all-gather of the FSDP shards (as before)
    backward: pack full-size cotangents -> (P,) slab; weighted psum over
              "client" (LAN, eq. 3); fused Pallas mask+apply; masked psum
              over clusters (MAC, eq. 8) + AWGN; guarded |M|·N estimate
              (eq. 10); unpack; slice each leaf's own FSDP shard.

    3 collectives + 1 kernel for the subtree instead of 3·L psums and L
    mask draws. Masks are whole-tensor draws (the scatter-mode per-region
    scheme does not apply to the packed slab); ω̃ is small, so the full-
    size psums cost less than the per-leaf dispatch they replace.

    ``template`` (optional, full-size ω̃ shapes — e.g.
    ``abstract_params(model.final_specs())``) turns a mismatched
    gradient pytree into a readable error naming the leaf path and its
    expected section, instead of an opaque downstream shape error.
    """
    tpl_packer = (packer_for(jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(tuple(l.shape), jnp.float32),
        template), tail=None) if template is not None else None)

    def _check(tree, what):
        if tpl_packer is not None:
            check_tree_matches_packer(tpl_packer, tree, what)
        elif len(jax.tree.leaves(tree)) != len(axes_list):
            raise ValueError(
                f"{what}: got {len(jax.tree.leaves(tree))} leaves but this "
                f"gather was built over {len(axes_list)} ω̃ leaves (the "
                f"tail section 'final') — the pytree must mirror "
                f"model.final_specs() exactly.")

    @jax.custom_vjp
    def gather_final(shard_tree, ctx: OTACtx):
        if tpl_packer is not None:   # structure only — shards are smaller
            check_tree_matches_packer(tpl_packer, shard_tree,
                                      "parameter pytree (packed final "
                                      "gather)", check_shapes=False)
        leaves, treedef = jax.tree.flatten(shard_tree)
        out = []
        for leaf, axes in zip(leaves, axes_list):
            ax = _fsdp_axis(axes)
            if ax >= 0:
                leaf = jax.lax.all_gather(leaf, data_axes, axis=ax,
                                          tiled=True)
            out.append(leaf.astype(compute_dtype))
        return jax.tree.unflatten(treedef, out)

    def _fwd(shard_tree, ctx):
        return gather_final(shard_tree, ctx), (ctx,)

    def _bwd(res, g_tree):
        (ctx,) = res
        _check(g_tree, "gradient pytree (packed final gather)")
        g_tree = jax.tree.map(lambda g: g.astype(jnp.float32), g_tree)
        packer = packer_for(g_tree, tail=None)
        g_slab = packer.pack(g_tree)                       # (P,) full-size
        x = jax.lax.psum(ctx.p_weight * g_slab, CLIENT_AXIS)
        xm, mask = _packed_mask_apply(x, ctx.key, ctx.sigma2, ctx.h_th,
                                      ctx.ota_on, cluster_axes)
        y = jax.lax.psum(xm, cluster_axes)
        cnt = jax.lax.psum(mask, cluster_axes)
        z = (jax.random.normal(jax.random.fold_in(ctx.key, HOTA_MASK_SALT),
                               g_slab.shape, jnp.float32)
             * ctx.noise_std * ctx.ota_on)
        ghat = jnp.where(cnt > 0,
                         (y + z) / (jnp.maximum(cnt, 1.0) * n_clients), 0.0)
        gh_tree = packer.unpack(ghat)
        me = jax.lax.axis_index(data_axes[0])
        for a in data_axes[1:]:
            me = me * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        leaves = jax.tree.leaves(gh_tree)
        out = []
        for leaf, axes in zip(leaves, axes_list):
            ax = _fsdp_axis(axes)
            if ax >= 0:
                sz = leaf.shape[ax] // n_shards
                leaf = jax.lax.dynamic_slice_in_dim(leaf, me * sz, sz, ax)
            out.append(leaf)
        grads = jax.tree.unflatten(jax.tree.structure(gh_tree), out)
        return (grads, jax.tree.map(_zero_cot, ctx))

    gather_final.defvjp(_fwd, _bwd)
    return gather_final


def packed_final_norm(g_final, base_key: jax.Array, chan_c: ChannelParams,
                      cluster_axes) -> jax.Array:
    """n_i = ‖M ∘ ∇_{ω̃}F_i‖ (eq. 6) on the packed slab — the SAME flat
    mask draw the packed gather backward applies (one fused kernel, no
    per-leaf loop)."""
    g32 = jax.tree.map(lambda g: g.astype(jnp.float32), g_final)
    packer = packer_for(g32, tail=None)
    g_slab = packer.pack(g32)
    masked, _ = _packed_mask_apply(
        g_slab, packed_final_key(base_key), chan_c.sigma2, chan_c.h_threshold,
        chan_c.ota_on, cluster_axes)
    return jnp.sqrt(jnp.sum(jnp.square(masked)))


# --------------------------------------------------------------------------
# axes registry + param hook
# --------------------------------------------------------------------------

def build_axes_registry(model: Model) -> Dict[str, List[tuple]]:
    """klass -> list of per-leaf logical-axes tuples ('layer' dims stripped),
    in the flatten order the hook will see."""
    cfg = model.cfg
    ax = logical_axes(model.trunk_specs())
    reg: Dict[str, List[tuple]] = {}

    def leaves_of(subtree):
        return [t for t in jax.tree.leaves(subtree, is_leaf=_is_axes)]

    if cfg.family == "mlp":
        reg["layers"] = []      # mlp trunk hooked per-fc via "embed"? no:
        # the MLP trunk is hooked as one flat subtree under "embed" klass?
        # Simpler: treat the whole mlp trunk as klass "layers" (single call).
        reg["layers"] = leaves_of(ax)
    elif cfg.family in ("dense", "moe"):
        reg["embed"] = [ax["embed"]]
        key = "layers" if "layers" in ax else "global"
        reg["layers"] = leaves_of(ax[key] if "layers" in ax else ax["global"])
    elif cfg.family == "hybrid":
        reg["embed"] = [ax["embed"]]
        reg["mamba"] = leaves_of(ax["mamba"])
        reg["shared_attn"] = leaves_of(ax["shared_attn"])
        reg["shared_mlp"] = leaves_of(ax["shared_mlp"])
    elif cfg.family == "xlstm":
        reg["embed"] = [ax["embed"]]
        reg["mlstm"] = leaves_of(ax["mlstm"])
        reg["slstm"] = leaves_of(ax["slstm"])
    elif cfg.family == "ssm":
        reg["embed"] = [ax["embed"]]
        reg["layers"] = leaves_of(ax["layers"])
    reg["final"] = leaves_of(logical_axes(model.final_specs()))
    return reg


def make_param_hook(gather, registry: Dict[str, List[tuple]],
                    base_key: jax.Array, p_weight, chan: ChannelParams,
                    final_packed_gather=None):
    """hook(subtree, klass, *tags) -> gathered/OTA-wrapped subtree.

    ``chan`` is this cluster's traced channel view (scalar σ² — see
    ``repro.core.channel.cluster_channel``); its knobs become the OTACtx
    consts, so sweeping scenarios never re-traces the gather.

    When ``final_packed_gather`` is set (see make_packed_final_gather),
    the "final" klass routes the WHOLE ω̃ subtree through one packed
    gather under one channel key instead of per-leaf calls."""
    consts = dict(
        p_weight=jnp.asarray(p_weight, jnp.float32),
        sigma2=jnp.asarray(chan.sigma2, jnp.float32),
        h_th=jnp.asarray(chan.h_threshold, jnp.float32),
        noise_std=jnp.asarray(chan.noise_std, jnp.float32),
        ota_on=jnp.asarray(chan.ota_on, jnp.float32),
    )

    def hook(lp, klass, *tags):
        if klass == "final" and final_packed_gather is not None:
            ctx = OTACtx(key=packed_final_key(base_key), **consts)
            return final_packed_gather(lp, ctx)
        leaves, treedef = jax.tree.flatten(lp)
        axes = registry[klass]
        assert len(leaves) == len(axes), (klass, len(leaves), len(axes))
        out = []
        for i, leaf in enumerate(leaves):
            ctx = OTACtx(key=fold_tags(base_key, klass, tags, i), **consts)
            out.append(gather(_fsdp_axis(axes[i]), leaf, ctx))
        return jax.tree.unflatten(treedef, out)
    return hook


def identity_hook(lp, klass, *tags):
    return lp


def shard_specs_for(model: Model, mesh) -> Any:
    """Manual PartitionSpecs (FL axes only) for the trunk+final shards."""
    from jax.sharding import PartitionSpec as P
    data_axes = _mesh_data_axes(mesh)

    def spec(axes):
        # position of embed in the FULL (unstripped) axes tuple
        if "embed" in axes:
            full_i = axes.index("embed")
            parts = [None] * len(axes)
            parts[full_i] = data_axes if len(data_axes) > 1 else data_axes[0]
            return P(*parts)
        return P()

    ax = {"trunk": logical_axes(model.trunk_specs()),
          "final": logical_axes(model.final_specs())}
    return jax.tree.map(spec, ax, is_leaf=_is_axes)


def _mesh_data_axes(mesh) -> Tuple[str, ...]:
    """FSDP axes in CLIENT-major order (scatter-region alignment)."""
    assert "client" in mesh.axis_names and "cluster" in mesh.axis_names
    return ("client", "cluster")


def _mesh_cluster_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "cluster"))


def _mesh_client_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "cluster", "client"))

