"""Plain reference of the over-the-air channel, written from its stream
specification (the repo's DESIGN.md §4) and the paper's eqs. 3, 7-10.

Nothing here imports the program. The reference draws the same threefry
streams the program is specified to draw, so a sound program and this
reference see identical masks and noise, and their estimates differ only
by rounding:

* the round's channel key is ``fold_in(round_key, SIM_CHAN_FOLD)``;
* the shared tree is laid out in sections: one per depth-2 path prefix
  ("trunk/fc0", ...) in flatten order, the ``tail`` subtree last; inside
  a section every leaf starts on a multiple of ``ROW_QUANTUM``;
* trunk section ``s`` draws under ``SECTION_FOLD_BASE + s``, the tail
  under ``TAIL_FOLD``; cluster ``c``'s gain stream is keyed
  ``fold_in(fold_in(chan_key, fold), c)``, the section's noise stream
  ``fold_in(fold_in(chan_key, NOISE_FOLD), fold)``;
* a stream is drawn in chunks of ``CHUNK`` words, chunk ``j`` being
  ``bits(fold_in(stream_key, j), (CHUNK,))``;
* mask: ``u < erfc(sqrt(H_th / 2 sigma2))`` with ``u`` the word's top 24
  bits over 2**24; noise: Box-Muller on the word's two 16-bit halves.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NOISE_FOLD = 0x7FFFFFFF
TAIL_FOLD = 0x7FFF0002
SIM_CHAN_FOLD = 0x7FFF0003
SECTION_FOLD_BASE = 0x7FFF0100
ROW_QUANTUM = 1024
CHUNK = 1024 * 128
TWO_PI = 6.283185307179586


class LeafRun(NamedTuple):
    path: str          # "trunk/fc0/w"
    fold: int          # the section's stream fold
    offset: int        # first stream position of the leaf in its section
    size: int


def _key_name(step) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(step, attr):
            return str(getattr(step, attr))
    raise TypeError(step)


def path_str(path) -> str:
    return "/".join(_key_name(s) for s in path)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def layout(tree, tail: str = "final") -> List[LeafRun]:
    """Leaf runs of the multi-section layout, in flatten order."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    names: List[str] = []
    groups: Dict[str, List[Tuple[str, int]]] = {}
    for path, leaf in leaves:
        keys = [_key_name(s) for s in path]
        name = tail if keys[0] == tail else "/".join(keys[:2])
        if name not in groups:
            groups[name] = []
            names.append(name)
        groups[name].append(("/".join(keys), int(np.prod(leaf.shape))))
    if tail in names:
        names.remove(tail)
        names.append(tail)
    runs = []
    for index, name in enumerate(names):
        fold = TAIL_FOLD if name == tail else SECTION_FOLD_BASE + index
        off = 0
        for path, size in groups[name]:
            off = _round_up(off, ROW_QUANTUM)
            runs.append(LeafRun(path, fold, off, size))
            off += size
    order = {path_str(p): i for i, (p, _) in enumerate(leaves)}
    return sorted(runs, key=lambda r: order[r.path])


def stream_bits(key, start: int, length: int) -> jax.Array:
    """Words [start, start + length) of ``key``'s chunked stream."""
    j0, j1 = start // CHUNK, (start + length - 1) // CHUNK
    chunks = [jax.random.bits(jax.random.fold_in(key, j), (CHUNK,),
                              jnp.uint32) for j in range(j0, j1 + 1)]
    words = jnp.concatenate(chunks) if len(chunks) > 1 else chunks[0]
    a = start - j0 * CHUNK
    return words[a:a + length]


def gain_bits(chan_key, run: LeafRun, n_clusters: int) -> jax.Array:
    """(C, size) gain words of one leaf."""
    skey = jax.random.fold_in(chan_key, run.fold)
    return jnp.stack([stream_bits(jax.random.fold_in(skey, c), run.offset,
                                  run.size) for c in range(n_clusters)])


def noise_bits(chan_key, run: LeafRun) -> jax.Array:
    nkey = jax.random.fold_in(jax.random.fold_in(chan_key, NOISE_FOLD),
                              run.fold)
    return stream_bits(nkey, run.offset, run.size)


def pass_probability(sigma2, h_th) -> jax.Array:
    sig2 = jnp.maximum(jnp.asarray(sigma2, jnp.float32), 1e-30)
    return jax.lax.erfc(jnp.sqrt(jnp.float32(h_th) / (2.0 * sig2)))


def mask_from_bits(bits, p_pass) -> jax.Array:
    u = (bits >> 8).astype(jnp.int32).astype(jnp.float32) * jnp.float32(
        2.0 ** -24)
    return u < p_pass


def gaussian_from_bits(bits) -> jax.Array:
    hi = (bits >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    u1 = (hi + 1.0) / 65536.0
    u2 = lo / 65536.0
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(TWO_PI * u2)


def aggregate_leaf(g, p, gbits, nbits, p_pass, noise_std, n_clients: int,
                   dtype=jnp.float32):
    """Eqs. 3 + 8-10 for one leaf. g: (C, N, *shape) raw client gradients;
    p: (C, N) loss weights; gbits: (C, size); nbits: (size,);
    p_pass: (C,). Channel inversion cancels the gain on passing entries,
    so the received sum is the masked sum of the weighted gradients.
    Returns the estimate and its noise term, the AWGN over the same
    divisor, which the estimate holds exactly once."""
    c = g.shape[0]
    shape = g.shape[2:]
    wg = jnp.einsum("cn,cn...->c...", p.astype(dtype), g.astype(dtype),
                    precision=jax.lax.Precision.HIGHEST)
    wg = wg.reshape(c, -1)
    masks = mask_from_bits(gbits, p_pass.reshape(c, 1))
    y = jnp.sum(jnp.where(masks, wg, jnp.zeros((), dtype)), axis=0)
    z = (gaussian_from_bits(nbits) * noise_std).astype(dtype)
    y = y + z
    cnt = jnp.sum(masks.astype(dtype), axis=0)
    div = jnp.maximum(cnt, 1) * n_clients
    zero = jnp.zeros((), dtype)
    est = jnp.where(cnt > 0, y / div, zero)
    noise = jnp.where(cnt > 0, z / div, zero)
    return est.reshape(shape), noise.reshape(shape)


def tail_masks(chan_key, runs: List[LeafRun], tail_paths, n_clusters: int,
               p_pass) -> Dict[str, jax.Array]:
    """(C, size) eq.-7 masks of the tail leaves, from the tail stream."""
    out = {}
    for run in runs:
        if run.path in tail_paths:
            out[run.path] = mask_from_bits(
                gain_bits(chan_key, run, n_clusters),
                p_pass.reshape(n_clusters, 1))
    return out
