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
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ota import HOTA_MASK_SALT
from repro.core.channel import ChannelParams
from repro.models.model import Model
from repro.models.params import logical_axes

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


# The distributed packed-ω̃ gather's key fold. That gather is retired (the
# slab-native engine, repro.core.hota_slab, draws ω̃ as its tail section);
# the value stays reserved in the DESIGN.md §4 registry so no new stream
# takes it.
PACKED_FINAL_FOLD = 0x7FFF00F1


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
                    base_key: jax.Array, p_weight, chan: ChannelParams):
    """hook(subtree, klass, *tags) -> gathered/OTA-wrapped subtree.

    ``chan`` is this cluster's traced channel view (scalar σ² — see
    ``repro.core.channel.cluster_channel``); its knobs become the OTACtx
    consts, so sweeping scenarios never re-traces the gather."""
    consts = dict(
        p_weight=jnp.asarray(p_weight, jnp.float32),
        sigma2=jnp.asarray(chan.sigma2, jnp.float32),
        h_th=jnp.asarray(chan.h_threshold, jnp.float32),
        noise_std=jnp.asarray(chan.noise_std, jnp.float32),
        ota_on=jnp.asarray(chan.ota_on, jnp.float32),
    )

    def hook(lp, klass, *tags):
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

