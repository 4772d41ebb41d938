"""Flat-packing of parameter pytrees for the fused OTA aggregation engine.

The paper's channel model is per-entry: every parameter entry j gets its
own gain draw, threshold test and superposition (eqs. 7-10) — nothing in
the math cares about the pytree structure. The per-leaf implementation in
``repro.core.ota`` therefore pays pure overhead: dozens of small
memory-bound kernels (one gain/mask/noise draw per leaf per cluster) per
round, multiplied by the scenario count under ``ScenarioBank``.

``TreePacker`` lays the shared-model pytree ONCE out as a lane-aligned
``(P,)`` slab with static per-leaf offsets, so the whole round's channel
is one set of section streams that the client-fold kernels
(``repro.kernels.ota_channel``) read leaf by leaf at static positions.

Layout contract (relied on by ``repro.core.ota.final_layer_masks_packed``):

* leaves are packed in flatten order, **except** the leaves of the
  ``tail`` subtree (the last-shared-layer params ω̃), which are packed
  last, forming one contiguous tail slice of the slab;
* the head and tail sections are each zero-padded up to a multiple of
  ``ROW_QUANTUM`` (= 8·128), so every section — and the whole slab —
  reshapes exactly to the kernels' (rows, 128) view and each section can
  be drawn from its own counter-based bit stream (section folds and the
  chunk-quantized draw are specified in DESIGN.md §4);
* FedGradNorm's sparsified F_grad (eqs. 5-7) needs exactly the masks of
  ω̃: with this layout they are the tail slice of the same flat channel
  draw the transmission uses — no second per-leaf mask loop.

Multi-section layouts (``sections="toplevel"`` — DESIGN.md §3.10): every
depth-≤2 path prefix of the template becomes its own ROW_QUANTUM-aligned
section (so a {"final", "trunk"} omega template splits into one section
per trunk layer stack — "trunk/embed", "trunk/layers", ... — each with
its own bit stream), with the ``tail`` key's section always last. Within
a section every leaf additionally starts
ROW_QUANTUM-aligned, so a leaf's slice of the section's bit stream is
computable from static offsets alone — the zero-copy contract: the
slab-native distributed step (``repro.core.hota_slab``) never
materializes the (P,) slab, it walks ``leaf_runs()`` and consumes each
leaf's storage in place against the stream positions this layout pins.
The zero-copy consumers also accept leaves carrying identical LEADING
batch axes over the template shapes — the simulator's client-folded
channel (DESIGN.md §3.12) reads raw (C, N, *shape) gradient leaves
against (*shape,) slots (``check_tree_matches_packer(batch_ndim=2)``);
the maps themselves are batch-free element ranges.

Chunk coalescing (``min_section_rows`` — DESIGN.md §3.13): the stream
spec draws bits in 1024-row chunks (§4), so a template with many tiny
top-level groups pays a full chunk draw per sub-chunk section — the
adversarial-layout loss the benchmarks pin. With a nonzero threshold,
adjacent trunk groups below ``min_section_rows`` rows merge into one
ROW_QUANTUM-aligned section. Leaf slab offsets are IDENTICAL at every
threshold (every leaf and every group start is already
ROW_QUANTUM-aligned, so merging only re-groups — it never moves data);
what changes is the Section partition and therefore the per-section
stream folds. ``min_section_rows=0`` is bit-identical to the uncoalesced
layout (stream-pinned in tests), and the ω̃ tail always stays its own
last section so eq.-5 consumers keep ``PACKED_TAIL_FOLD``.

Section splitting (``max_section_rows`` — DESIGN.md §4): the
section-streaming engine (§3.16) holds ONE section's streams live at a
time, so its peak memory is the largest section — useless if one giant
layer stack is most of the model. With a nonzero cap, any trunk section
longer than ``max_section_rows`` LANE-wide rows is split at leaf
boundaries into consecutive sections of at most the cap (a single leaf
larger than the cap stays one section — leaf runs never straddle
sections, so the reachable bound is
``max(max_section_rows, ceil(largest_leaf / LANE))`` rows,
``peak_section_rows()``). Exactly like coalescing, the split moves no
data: leaf offsets are identical at every cap (every leaf start is
already ROW_QUANTUM-aligned); only the Section partition — and so the
per-section stream folds — changes. The ω̃ tail is never split.

Packers are cached on (treedef, shapes, dtypes, tail, sections,
min_section_rows, max_section_rows), so tracing a step re-uses the
offsets computed at the first call.
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from repro.kernels.slab import LANE, ROW_QUANTUM, round_up


class LeafSlot(NamedTuple):
    offset: int                # start index into the (P,) slab
    size: int                  # element count
    shape: Tuple[int, ...]
    dtype: Any


class Section(NamedTuple):
    """One ROW_QUANTUM-aligned region of the slab (DESIGN.md §4 streams)."""
    name: str                  # top-level template key ("" = head catch-all)
    index: int                 # section position — selects the stream fold
    start: int                 # slab offset (ROW_QUANTUM-aligned)
    length: int                # padded length (ROW_QUANTUM multiple)
    leaf_indices: Tuple[int, ...]   # flatten-order leaf ids, pack order


class LeafRun(NamedTuple):
    """Zero-copy map entry: where one leaf's data sits inside a section's
    bit stream. The slab-native executor reads the leaf array in place
    and draws stream elements [offset, offset+size) of the section."""
    leaf: int                  # flatten-order leaf index
    section: int               # section index
    offset: int                # start within the section (elements)
    size: int


def _path_key(step):
    """One path step's key: dict key / attr name / sequence index (list
    and tuple containers carry SequenceKey with .idx, not .key/.name)."""
    key = getattr(step, "key", getattr(step, "name", None))
    return getattr(step, "idx", None) if key is None else key


def _top_key(path):
    return _path_key(path[0]) if path else None


def _in_tail(path, tail: Optional[str]) -> bool:
    return tail is not None and _top_key(path) == tail


def _section_key(path, tail: Optional[str]) -> Optional[str]:
    """Section of a leaf in the multi-section layout: the tail key, or
    the depth-≤2 path prefix — one section PER LAYER STACK ("trunk/
    layers", "trunk/embed", ...), not per top-level container, so a
    {"final", "trunk"} omega template still splits its trunk stacks
    into separate stream sections."""
    if _in_tail(path, tail):
        return tail
    if not path:
        return None
    return "/".join(str(_path_key(s)) for s in path[:2])


class TreePacker:
    """Static pack/unpack between a pytree and a lane-aligned (P,) slab.

    ``tail`` names a top-level key of ``template`` (usually ``"final"``)
    whose leaves are laid out as the contiguous tail of the slab; pass
    ``None`` to pack everything as one head section.

    ``sections`` selects the layout:

    * ``"tail"`` (default, the PR-2 layout): two sections — head leaves
      butt-packed in flatten order, tail leaves butt-packed last, each
      section ROW_QUANTUM-padded. Streams and values are bit-identical
      to the original two-section packer.
    * ``"toplevel"``: one section per depth-≤2 path prefix of
      ``template`` (the per-layer-stack trunk sections — "trunk/embed",
      "trunk/layers", ...), tail key last, and EVERY leaf starts
      ROW_QUANTUM-aligned inside its section — the zero-copy layout:
      ``leaf_runs()`` / ``chunk_leaf_map()`` give static maps from leaf
      storage to stream positions, so the slab-native executor
      (repro.core.hota_slab) never materializes the slab, and a
      full-section stream draw is bounded by ONE layer stack.

    ``min_section_rows`` (``sections="toplevel"`` only) coalesces
    adjacent trunk groups shorter than that many LANE-wide rows into one
    section, closing each merged section once it reaches the threshold;
    a trailing under-threshold remainder folds into the previous trunk
    section, and the ``tail`` group is never merged — it stays its own
    last section. Leaf offsets are identical at every threshold; only
    the Section partition (and so the stream folds) changes. ``0``
    (the default) reproduces the uncoalesced layout bit-exactly.

    ``max_section_rows`` (``sections="toplevel"`` only) splits, AFTER
    coalescing, any trunk section longer than that many rows at leaf
    boundaries into consecutive sections of at most the cap — the
    memory-budget knob of the section-streaming engine (DESIGN.md
    §3.16): peak live streams are one section, so the cap bounds them.
    A leaf larger than the cap stays one oversized section (runs never
    straddle sections); the tail is never split. Like coalescing this
    never moves data — only the partition and stream folds change —
    and ``0`` (the default) performs no split.

    The template must carry ONE uniform leaf dtype: the slab is a single
    flat buffer and the zero-copy maps alias leaf storage in place, so a
    mixed-dtype tree has no representable layout — cast it first.
    """

    def __init__(self, template, tail: Optional[str] = "final",
                 sections: str = "tail", min_section_rows: int = 0,
                 max_section_rows: int = 0):
        if sections not in ("tail", "toplevel"):
            raise ValueError(
                f"sections must be 'tail' or 'toplevel', got {sections!r}")
        min_section_rows = int(min_section_rows)
        max_section_rows = int(max_section_rows)
        if min_section_rows < 0:
            raise ValueError(
                f"min_section_rows must be >= 0, got {min_section_rows}")
        if max_section_rows < 0:
            raise ValueError(
                f"max_section_rows must be >= 0, got {max_section_rows}")
        if sections == "tail" and min_section_rows:
            raise ValueError(
                "min_section_rows requires sections='toplevel': the legacy "
                "two-section layout has no trunk groups to coalesce "
                f"(got min_section_rows={min_section_rows})")
        if sections == "tail" and max_section_rows:
            raise ValueError(
                "max_section_rows requires sections='toplevel': the legacy "
                "two-section layout has no trunk sections to split "
                f"(got max_section_rows={max_section_rows})")
        if max_section_rows and max_section_rows < min_section_rows:
            raise ValueError(
                f"max_section_rows ({max_section_rows}) < min_section_rows "
                f"({min_section_rows}): the coalescer would merge sections "
                f"the splitter immediately re-cuts — contradictory layout")
        self.min_section_rows = min_section_rows
        self.max_section_rows = max_section_rows
        paths_leaves, treedef = jtu.tree_flatten_with_path(template)
        self.treedef = treedef
        self.tail_name = tail
        self.layout = sections

        dtypes = sorted({jnp.dtype(l.dtype).name for _, l in paths_leaves})
        if len(dtypes) > 1:
            detail = ", ".join(f"{jtu.keystr(p)}={jnp.dtype(l.dtype).name}"
                               for p, l in paths_leaves)
            raise ValueError(
                f"TreePacker requires one uniform leaf dtype (the slab is "
                f"one flat buffer and the zero-copy maps read leaf storage "
                f"in place) but the template mixes {dtypes}; cast the tree "
                f"to a single dtype first. Leaves: {detail}")

        head_idx = [i for i, (p, _) in enumerate(paths_leaves)
                    if not _in_tail(p, tail)]
        tail_idx = [i for i, (p, _) in enumerate(paths_leaves)
                    if _in_tail(p, tail)]
        # pack order: head leaves in flatten order, tail leaves last
        self.order: List[int] = head_idx + tail_idx
        self.tail_indices = tail_idx

        self.slots: Dict[int, LeafSlot] = {}
        self.sections: List[Section] = []

        def _slot(i, off):
            leaf = paths_leaves[i][1]
            self.slots[i] = LeafSlot(off, int(leaf.size), tuple(leaf.shape),
                                     jnp.dtype(leaf.dtype))
            return int(leaf.size)

        if sections == "tail":
            off = 0
            for i in head_idx:
                off += _slot(i, off)
            self.head_len = round_up(off, ROW_QUANTUM)  # section boundary
            off = self.head_len
            for i in tail_idx:
                off += _slot(i, off)
            self.tail_len = round_up(off - self.head_len, ROW_QUANTUM)
            if head_idx:
                self.sections.append(Section("", 0, 0, self.head_len,
                                             tuple(head_idx)))
            if tail_idx:
                self.sections.append(
                    Section(tail, len(self.sections), self.head_len,
                            self.tail_len, tuple(tail_idx)))
        else:
            names: List[Optional[str]] = []
            groups: Dict[Optional[str], List[int]] = {}
            for i in head_idx + tail_idx:
                name = _section_key(paths_leaves[i][0], tail)
                if name not in groups:
                    groups[name] = []
                    names.append(name)
                groups[name].append(i)
            if tail is not None and tail in names:   # tail always last
                names.remove(tail)
                names.append(tail)
            # Phase 1: lay out every top-level group exactly as the
            # uncoalesced layout does. Leaf offsets are therefore
            # invariant under min_section_rows — every leaf and every
            # group start is ROW_QUANTUM-aligned, so re-grouping below
            # never moves data.
            off = 0
            atoms = []   # (name, start, length, leaf_indices, is_tail)
            for name in names:
                start = off
                for i in groups[name]:
                    # every leaf ROW_QUANTUM-aligned: its stream slice is
                    # a static, lane-aligned range of the section stream
                    off = start + round_up(off - start, ROW_QUANTUM)
                    off += _slot(i, off)
                length = round_up(off - start, ROW_QUANTUM)
                off = start + length
                atoms.append(("" if name is None else name, start, length,
                              tuple(groups[name]),
                              tail is not None and name == tail))
            # Phase 2: greedily merge adjacent sub-threshold trunk
            # groups; a trailing remainder folds into the previous trunk
            # section; the tail group is never merged (eq.-5 consumers
            # rely on it keeping its own fold in every layout).
            threshold = min_section_rows * LANE
            merged: List[List[Any]] = []   # [names, start, length, leaves]
            open_grp: Optional[List[Any]] = None
            for name, start, length, leaf_idx, is_tail in atoms:
                if is_tail:
                    continue
                if open_grp is None:
                    open_grp = [[name], start, length, list(leaf_idx)]
                else:
                    open_grp[0].append(name)
                    open_grp[2] += length
                    open_grp[3].extend(leaf_idx)
                if open_grp[2] >= threshold:
                    merged.append(open_grp)
                    open_grp = None
            if open_grp is not None:
                if merged:
                    merged[-1][0].extend(open_grp[0])
                    merged[-1][2] += open_grp[2]
                    merged[-1][3].extend(open_grp[3])
                else:
                    merged.append(open_grp)
            # Phase 2b: split over-cap trunk sections at leaf boundaries
            # (every leaf start is ROW_QUANTUM-aligned, so every piece
            # is too — no data moves, only the partition/folds change).
            # A single leaf longer than the cap stays one section: leaf
            # runs never straddle sections.
            if max_section_rows:
                cap = max_section_rows * LANE
                split: List[List[Any]] = []
                for sec_names, start, length, leaf_list in merged:
                    if length <= cap:
                        split.append([sec_names, start, length, leaf_list])
                        continue
                    base = "+".join(sec_names)
                    end = start + length
                    pieces: List[Tuple[int, List[int]]] = []
                    p_start, p_leaves = start, []
                    for i in leaf_list:
                        slot = self.slots[i]
                        if p_leaves and round_up(
                                slot.offset + slot.size - p_start,
                                ROW_QUANTUM) > cap:
                            pieces.append((p_start, p_leaves))
                            p_start, p_leaves = slot.offset, []
                        p_leaves.append(i)
                    pieces.append((p_start, p_leaves))
                    for k, (ps, pl) in enumerate(pieces):
                        pe = pieces[k + 1][0] if k + 1 < len(pieces) else end
                        split.append([[f"{base}[{k}]"], ps, pe - ps, pl])
                merged = split
            merged.extend([[a[0]], a[1], a[2], list(a[3])]
                          for a in atoms if a[4])
            self.order = []
            for sec_names, start, length, leaf_list in merged:
                self.sections.append(
                    Section("+".join(sec_names), len(self.sections),
                            start, length, tuple(leaf_list)))
                self.order.extend(leaf_list)
            self.tail_len = (self.sections[-1].length
                             if tail is not None and tail in names else 0)
            self.head_len = off - self.tail_len

        self.size = self.head_len + self.tail_len       # P, lane-aligned
        if self.size == 0:
            raise ValueError("cannot pack an empty pytree")
        self.n_rows = self.size // LANE

    # ------------------------------------------------------------------
    def leaf_runs(self) -> List[LeafRun]:
        """Static zero-copy map: one entry per leaf in pack order, giving
        the (section, offset, size) stream slice its storage occupies."""
        runs = []
        for sec in self.sections:
            for i in sec.leaf_indices:
                slot = self.slots[i]
                runs.append(LeafRun(i, sec.index, slot.offset - sec.start,
                                    slot.size))
        return runs

    def peak_section_rows(self) -> int:
        """Largest section in LANE-wide rows — the peak live stream
        footprint of the section-streaming engine (DESIGN.md §3.16).
        With ``max_section_rows`` set this is at most
        ``max(max_section_rows, ceil(largest_leaf / LANE))``; computable
        from the template alone (no weights materialized)."""
        return max(sec.length for sec in self.sections) // LANE

    def chunk_leaf_map(
            self, chunk: int,
    ) -> Dict[int, List[Tuple[int, List[LeafRun]]]]:
        """section index -> {chunk j: leaf runs intersecting
        [j·chunk, (j+1)·chunk)} — the inverse view of ``leaf_runs`` a
        chunk-driven kernel would walk. Purely static."""
        out: Dict[int, Dict[int, List[LeafRun]]] = {}
        for run in self.leaf_runs():
            per = out.setdefault(run.section, {})
            j0 = run.offset // chunk
            # a zero-size run still belongs to the chunk at its offset;
            # (offset + size - 1) // chunk would underflow past j0 and
            # silently drop the leaf from the chunk-driven view
            j1 = (run.offset + run.size - 1) // chunk if run.size else j0
            for j in range(j0, j1 + 1):
                per.setdefault(j, []).append(run)
        return {s: sorted(d.items()) for s, d in out.items()}

    # ------------------------------------------------------------------
    def pack(self, tree) -> jax.Array:
        """Pytree -> (..., P) f32 slab (section padding stays zero).

        Leaves may carry identical leading batch dims (e.g. the (C,)
        cluster axis — compare against ``slots[i].shape``); the batch
        axes are preserved: output is (*batch, P).

        Implementation note: a chain of static dynamic_update_slices into
        a zeros slab, NOT one big concatenate — XLA updates the buffer in
        place, while a wide concatenate of odd-sized segments falls off
        the vectorized copy path (~10x slower at 16M params on CPU).
        """
        leaves = self.treedef.flatten_up_to(tree)
        i0 = self.order[0]
        nb = leaves[i0].ndim - len(self.slots[i0].shape)
        batch = tuple(leaves[i0].shape[:nb])
        slab = jnp.zeros(batch + (self.size,), jnp.float32)
        for i in self.order:
            piece = leaves[i].astype(jnp.float32).reshape(batch + (-1,))
            slab = jax.lax.dynamic_update_slice(
                slab, piece, (0,) * nb + (self.slots[i].offset,))
        return slab

    # ------------------------------------------------------------------
    def unpack(self, slab: jax.Array):
        """(..., P) slab -> pytree with leaves (..., *shape)."""
        batch = slab.shape[:-1]
        leaves = [None] * len(self.slots)
        for i, slot in self.slots.items():
            piece = jax.lax.slice_in_dim(slab, slot.offset,
                                         slot.offset + slot.size, axis=-1)
            leaves[i] = piece.reshape(batch + slot.shape).astype(slot.dtype)
        return self.treedef.unflatten(leaves)

    # ------------------------------------------------------------------
    def tail_slice(self, slab: jax.Array) -> jax.Array:
        """The contiguous last-shared-layer tail of a (..., P) slab."""
        return jax.lax.slice_in_dim(slab, self.head_len, self.size, axis=-1)

    def unpack_tail(self, tail_slab: jax.Array):
        """(..., tail_len) tail slice -> the ``tail`` subtree's pytree,
        leaves (..., *shape) — dtype is NOT cast (masks stay bool etc.)."""
        if self.tail_name is None:
            raise ValueError("this packer was built with tail=None — it has "
                             "no tail section to unpack")
        batch = tail_slab.shape[:-1]
        sub_leaves = []
        for i in self.tail_indices:
            slot = self.slots[i]
            off = slot.offset - self.head_len
            piece = jax.lax.slice_in_dim(tail_slab, off, off + slot.size,
                                         axis=-1)
            sub_leaves.append(piece.reshape(batch + slot.shape))
        full = self.treedef.unflatten(list(range(len(self.slots))))
        _, tail_def = jtu.tree_flatten(full[self.tail_name])
        return jtu.tree_unflatten(tail_def, sub_leaves)


# ---------------------------------------------------------------------------
# template validation — readable mismatch errors for the gather paths
# ---------------------------------------------------------------------------

def check_tree_matches_packer(packer: TreePacker, tree, what: str,
                              check_shapes: bool = True,
                              batch_ndim: int = 0) -> None:
    """Raise a readable error when ``tree`` does not match the packer
    template: names the first offending leaf path and the section it was
    expected in, instead of letting a zip mispair leaves and die in an
    opaque downstream shape error (used by the packed gather in
    repro.core.hota_slab and the client-folded sim path in
    repro.core.ota).

    ``batch_ndim`` allows every leaf to carry that many IDENTICAL
    leading batch axes on top of its template shape — the zero-copy
    consumers read e.g. (C, N, *shape) gradient leaves against a
    template of (*shape,) slots (the (cluster, client) axes of the
    simulator)."""
    leaves, treedef = jax.tree.flatten(tree)

    def _leaf_ok(i, l):
        shape = tuple(l.shape)
        if len(shape) < batch_ndim:
            return False
        if batch_ndim and shape[:batch_ndim] != tuple(
                leaves[0].shape[:batch_ndim]):
            return False
        return shape[batch_ndim:] == packer.slots[i].shape

    if treedef == packer.treedef:
        if not check_shapes or all(
                _leaf_ok(i, l) for i, l in enumerate(leaves)):
            return
    by_leaf = {i: sec for sec in packer.sections for i in sec.leaf_indices}
    n = len(packer.slots)
    tpl = packer.treedef.unflatten(list(range(n)))
    exp_paths = [None] * n
    for p, i in jtu.tree_flatten_with_path(tpl)[0]:
        exp_paths[i] = jtu.keystr(p)
    got_paths = [jtu.keystr(p)
                 for p, _ in jtu.tree_flatten_with_path(tree)[0]]
    for i in range(max(n, len(got_paths))):
        exp = exp_paths[i] if i < n else "<nothing — extra leaf>"
        got = got_paths[i] if i < len(got_paths) else "<missing leaf>"
        shape_ok = (not check_shapes) or (
            i < n and i < len(leaves) and _leaf_ok(i, leaves[i]))
        if exp != got or not shape_ok:
            sec = by_leaf.get(i)
            where = (f"section {sec.index} ({sec.name or 'head'!r}, slab "
                     f"[{sec.start}:{sec.start + sec.length}))"
                     if sec is not None else "beyond the template")
            exp_shape = packer.slots[i].shape if i < n else "-"
            got_shape = tuple(leaves[i].shape) if i < len(leaves) else "-"
            raise ValueError(
                f"{what} does not match the packer template at leaf {i}: "
                f"expected {exp} with shape {exp_shape} in {where}, got "
                f"{got} with shape {got_shape}. The packer was built from "
                f"the model's parameter template — pass a pytree of that "
                f"exact structure (same treedef, same leaf shapes).")
    raise ValueError(
        f"{what} does not match the packer template: treedefs differ "
        f"({treedef} vs {packer.treedef}) though every leaf path agrees — "
        f"check container types (dict vs namedtuple) at the root.")


# ---------------------------------------------------------------------------
# packer cache — keyed on static structure, reused across traces
# ---------------------------------------------------------------------------

_PACKER_CACHE: Dict[Any, TreePacker] = {}


def packer_for(tree, tail: Optional[str] = "final",
               sections: str = "tail",
               min_section_rows: int = 0,
               max_section_rows: int = 0) -> TreePacker:
    """Cached TreePacker for ``tree``'s (treedef, shapes, dtypes, tail,
    sections, min_section_rows, max_section_rows).

    ``tree`` may hold arrays, tracers or ShapeDtypeStructs — only the
    static structure is read.
    """
    leaves, treedef = jax.tree.flatten(tree)
    key = (treedef, tuple((tuple(l.shape), jnp.dtype(l.dtype).name)
                          for l in leaves), tail, sections,
           int(min_section_rows), int(max_section_rows))
    packer = _PACKER_CACHE.get(key)
    if packer is None:
        packer = TreePacker(
            treedef.unflatten([jax.ShapeDtypeStruct(tuple(l.shape), l.dtype)
                               for l in leaves]), tail, sections=sections,
            min_section_rows=min_section_rows,
            max_section_rows=max_section_rows)
        _PACKER_CACHE[key] = packer
    return packer
