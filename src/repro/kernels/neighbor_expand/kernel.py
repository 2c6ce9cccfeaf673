"""Pallas TPU kernel: fused 2-hop neighbor expansion.

The per-hop candidate generation of ACORN's predicate-subgraph traversal
(Figure 4b/4c): from the 1-hop neighbor row of the node being expanded,
take the 2-hop rows, drop predicate-failing / visited / duplicate ids,
and pack the first M survivors in candidate order.

The jnp path dedups the flattened ~(cap - m_beta) x (cap + 1) candidate
array with a (B, n) scatter-min tile or a stable argsort (``ref.py``).
This kernel fuses filter, dedup and pack into one sequential
first-occurrence scan per lane: a candidate packs iff it is valid, passes
the predicate and is not yet *seen* — where the seen set is the lane's
visited bitmap plus every id packed so far (packing an id sets its bit),
so the dedup costs one bit test and nothing is sorted.  The scan stops as
soon as M ids are packed: later candidates could not change the output.

Layout (what Mosaic accepts on a v5e, at any corpus size):

  * every per-lane block carries a squeezed leading lane axis, so its last
    two dims equal the array's (the (8, 128) block rule holds for any
    width);
  * the candidate ids — the 1-hop head, the tails to expand and their
    2-hop rows — sit in SMEM, where the scalar scan reads them.  The 2-hop
    rows are gathered by XLA before the call (one (B, t, cap) gather, the
    same one the jnp path makes): a single row of a tiled (n_l, cap) table
    cannot be DMA'd on its own unless cap is one 128-lane tile;
  * the predicate and visited bitmaps are packed 32 ids per int32 word
    into (n / 4096, 128) VMEM tiles per lane (:func:`pack_bitmap`) — n / 8
    bytes, not the n bytes (padded to 32 sublanes) of a bool row — and a
    bit test loads the word's 128-lane row and selects its lane.

:func:`neighbor_expand_packed` takes both bitmaps already packed and
returns the visited bitmap with the packed ids set, written in place over
its input: the level-0 beam search packs its predicate once per batch and
carries visited packed, so no hop packs a (B, n) mask.

Grid: one step per query lane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import gather_rows

INVALID = -1
LANES = 128
WORD_BITS = 32


def bitmap_words(n: int) -> int:
    """Words per lane of a packed n-id bitmap: a whole number of 128-lane
    rows holding 32 ids each."""
    return -(-n // (WORD_BITS * LANES)) * LANES


def pack_bitmap(mask):
    """(B, n) bool -> (B, W / 128, 128) int32 with W = bitmap_words(n).

    Id ``i`` is bit ``i // W`` of word ``i % W`` (zero-padded past n), so
    each bit plane is a contiguous slice of the mask and packing is one
    elementwise fusion, with no (B, n) word array in between."""
    b, n = mask.shape
    w = bitmap_words(n)
    padded = jnp.pad(mask, ((0, 0), (0, WORD_BITS * w - n)))
    planes = padded.reshape(b, WORD_BITS, w)
    packed = functools.reduce(jnp.bitwise_or, (
        planes[:, k].astype(jnp.uint32) << k for k in range(WORD_BITS)))
    return jax.lax.bitcast_convert_type(packed, jnp.int32).reshape(
        b, w // LANES, LANES)


def _neighbor_expand_kernel(*refs, strategy: str, m: int, n: int, cap: int,
                            t: int, has_mask: bool, has_vis: bool):
    """One query lane.  Ref layout (built by the wrapper, in order):

    head_ref (1, H) SMEM       candidates scanned first
    exp_ids_ref (1, t) SMEM    tail ids to 2-hop expand   [compress/two_hop]
    hop2_ref (t, cap) SMEM     their rows (-1 if absent)  [compress/two_hop]
    pm_ref (R, 128) VMEM       packed predicate bitmap    [has_mask]
    vis_ref (R, 128) VMEM      packed visited bitmap      [has_vis]
    o_ref (1, m) SMEM          packed output ids
    vis_out_ref (R, 128) VMEM  visited | packed ids       [has_vis]
    cnt_ref (1,) SMEM scratch  number packed so far
    seen_ref (R, 128) VMEM     visited | packed bitmap    [compress/two_hop,
                               scratch unless has_vis: then vis_out_ref]
    """
    refs = list(refs)
    head_ref = refs.pop(0)
    has_exp = strategy != "filter"
    exp_ids_ref = refs.pop(0) if has_exp else None
    hop2_ref = refs.pop(0) if has_exp else None
    pm_ref = refs.pop(0) if has_mask else None
    vis_ref = refs.pop(0) if has_vis else None
    o_ref = refs.pop(0)
    vis_out_ref = refs.pop(0) if has_vis else None
    cnt_ref = refs.pop(0)
    seen_ref = None
    if has_exp:
        seen_ref = vis_out_ref if has_vis else refs.pop(0)
    # where a packed id is recorded: the seen set, or for 'filter' the
    # visited output alone (its checks read the input visited bitmap)
    record_ref = seen_ref if has_exp else vis_out_ref

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    words = bitmap_words(n)

    def locate(cid):
        """(row, lane, bit) of id ``cid`` in a packed bitmap."""
        w = cid % words
        return w // LANES, w % LANES, cid // words

    def bit_set(ref, cid):
        r, col, bit = locate(cid)
        row = ref[pl.ds(r, 1), :]
        word = jnp.sum(jnp.where(lane == col, row, 0))
        return (jax.lax.shift_right_logical(word, bit) & 1) == 1

    def mark(ref, cid):
        r, col, bit = locate(cid)
        ref[pl.ds(r, 1), :] = ref[pl.ds(r, 1), :] | jnp.where(
            lane == col, jax.lax.shift_left(jnp.int32(1), bit), 0)

    for j in range(m):
        o_ref[0, j] = INVALID
    cnt_ref[0] = 0
    if has_vis:
        vis_out_ref[...] = vis_ref[...]
    elif has_exp:
        seen_ref[...] = jnp.zeros(seen_ref.shape, jnp.int32)

    def try_pack(cid):
        """First-occurrence pack: a packed id joins the seen set."""
        cnt = cnt_ref[0]
        safe = jnp.clip(cid, 0, n - 1)
        ok = cid >= 0
        if has_mask:
            ok &= bit_set(pm_ref, safe)
        if has_exp:
            ok &= jnp.logical_not(bit_set(seen_ref, safe))
        elif has_vis:  # 'filter' scans a duplicate-free stored row
            ok &= jnp.logical_not(bit_set(vis_ref, safe))

        @pl.when(ok)
        def _():
            o_ref[0, cnt] = cid
            cnt_ref[0] = cnt + 1
            if record_ref is not None:
                mark(record_ref, safe)

    def scan(total, candidate):
        """try_pack over candidate(0..total-1), until m ids are packed."""
        def cond(carry):
            s, cnt = carry
            return (s < total) & (cnt < m)

        def body(carry):
            s, _ = carry
            try_pack(candidate(s))
            return s + 1, cnt_ref[0]

        jax.lax.while_loop(cond, body, (jnp.int32(0), cnt_ref[0]))

    # ---- phase 1: head candidates in stored order ----
    scan(head_ref.shape[1], lambda j: head_ref[0, j])

    # ---- phase 2: the 2-hop stream, in the strategy's scan order ----
    if not has_exp:
        return
    if strategy == "compress":
        # per tail: the tail id itself, then its row left-to-right
        def candidate(s):
            tt = s // (cap + 1)
            r = s % (cap + 1)
            hid = hop2_ref[tt, jnp.maximum(r - 1, 0)]
            return jnp.where(r == 0, exp_ids_ref[0, tt], hid)

        scan(t * (cap + 1), candidate)
    else:  # two_hop: j-th neighbor of every 1-hop node before the (j+1)-th
        scan(t * cap, lambda s: hop2_ref[s % t, s // t])


@functools.partial(jax.jit,
                   static_argnames=("strategy", "m", "m_beta", "interpret"))
def neighbor_expand_pallas(row, nbr_table, pos, pass_mask=None, visited=None,
                           *, strategy: str, m: int, m_beta: int = 0,
                           interpret: bool = False):
    """row (B, cap), nbr_table (n_l, cap), pos (n,) -> (B, m) int32 ids.

    pass_mask / visited are (B, n) bool, their :func:`pack_bitmap` words,
    or None.  Bit-identical to
    :func:`repro.kernels.neighbor_expand.ref.neighbor_expand_ref`
    (enforced by tests/test_neighbor_expand.py).
    """
    def pack(a):
        return a if a is None or a.dtype == jnp.int32 else pack_bitmap(a)

    ids, _ = neighbor_expand_packed(
        row, nbr_table, pos, pack(pass_mask), pack(visited),
        strategy=strategy, m=m, m_beta=m_beta, interpret=interpret)
    return ids


@functools.partial(jax.jit,
                   static_argnames=("strategy", "m", "m_beta", "interpret"))
def neighbor_expand_packed(row, nbr_table, pos, pass_words=None,
                           visited_words=None, *, strategy: str, m: int,
                           m_beta: int = 0, interpret: bool = False):
    """:func:`neighbor_expand_pallas` over bitmaps already packed by
    :func:`pack_bitmap` ((B, n / 4096, 128) int32, or None).

    Returns ``(ids, visited_words | ids)``: the visited bitmap with every
    returned id set, written over ``visited_words`` (None when it is
    None) — the level-0 beam's visited update, with no (B, n) pass.
    """
    b, cap = row.shape
    n = pos.shape[0]
    if strategy == "filter":
        head, exp = row, None
    elif strategy == "compress":
        head, exp = row[:, :m_beta], row[:, m_beta:]
    elif strategy == "two_hop":
        head, exp = row, row
    else:
        raise ValueError(strategy)
    if head.shape[1] == 0:   # zero-width SMEM blocks are illegal; a single
        head = jnp.full((b, 1), INVALID, jnp.int32)   # -1 never packs
    has_exp = exp is not None
    has_mask = pass_words is not None
    has_vis = visited_words is not None

    def lane_block(a, memory_space=None):
        """Per-lane block whose last two dims are the whole array's."""
        kw = {} if memory_space is None else dict(memory_space=memory_space)
        return pl.BlockSpec((None,) + a.shape[1:],
                            lambda i: (i,) + (0,) * (a.ndim - 1), **kw)

    inputs = [head[:, None, :]]
    t = 1
    tbl_cap = nbr_table.shape[1]
    if has_exp:
        if exp.shape[1] == 0:   # m_beta == cap: dummy -1 tail, never packs
            exp = jnp.full((b, 1), INVALID, jnp.int32)
        t = exp.shape[1]
        inputs += [exp[:, None, :], gather_rows(nbr_table, pos, exp)]
    smem_inputs = len(inputs)
    if has_mask:
        inputs.append(pass_words)
    if has_vis:
        inputs.append(visited_words)
    in_specs = [lane_block(a, pltpu.SMEM) for a in inputs[:smem_inputs]]
    in_specs += [lane_block(a) for a in inputs[smem_inputs:]]
    ids_shape = jax.ShapeDtypeStruct((b, 1, m), jnp.int32)
    out_shape = [ids_shape]
    out_specs = [pl.BlockSpec((None, 1, m), lambda i: (i, 0, 0),
                              memory_space=pltpu.SMEM)]
    aliases = {}
    if has_vis:
        out_shape.append(jax.ShapeDtypeStruct(visited_words.shape,
                                              jnp.int32))
        out_specs.append(lane_block(visited_words))
        aliases = {len(inputs) - 1: 1}
    scratch = [pltpu.SMEM((1,), jnp.int32)]
    if has_exp and not has_vis:
        scratch.append(pltpu.VMEM((bitmap_words(n) // LANES, LANES),
                                  jnp.int32))

    kern = functools.partial(
        _neighbor_expand_kernel, strategy=strategy, m=m, n=n, cap=tbl_cap,
        t=t, has_mask=has_mask, has_vis=has_vis)
    outs = pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*inputs)
    return outs[0][:, 0, :], (outs[1] if has_vis else None)
