"""Sequence/context parallelism: ring attention over a mesh axis.

Long-context support is first-class in this framework even though the
reference has none (SURVEY.md §2.3 lists sequence parallelism as absent;
its graph-partition parallelism — vertex-sharded activations + per-layer
halo exchange — is the structural analogue and lives in
:mod:`dgraph_tpu.comm.collectives`). This module supplies the sequence
side of that story for transformer-style attention over sequences too
long for one device:

- **Ring attention** (blockwise attention + online softmax): Q stays
  resident; K/V blocks stream around the ring via ``lax.ppermute``, one
  neighbor hop per step, so each device holds O(T/W) of the sequence and
  the ICI traffic per step is exactly one K/V block. The online-softmax
  recurrence makes the result numerically identical to dense attention
  (it is the flash-attention accumulation, distributed).
- The all-to-all (DeepSpeed-Ulysses-style) head-scatter variant trades
  one big collective for per-step neighbor hops; on TPU the ring maps
  straight onto ICI neighbor links, so the ring is the default here.

Differentiable end to end: the backward of ``ppermute`` is the reverse
``ppermute`` and the scan transposes into the standard two-pass
flash-attention backward schedule, so ``jax.grad`` through
:func:`ring_attention` emits ring communication in the backward too —
no hand-written transpose needed (pinned in tests/test_sequence.py).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import ClassVar, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

NEG_BIG = -0.7 * float(jnp.finfo(jnp.float32).max)  # finite -inf stand-in:
# keeps the online-softmax recurrence NaN-free for fully-masked blocks
# (exp(NEG_BIG - NEG_BIG) would be exp(0); masked probabilities are
# re-zeroed explicitly, see below)


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMask:
    """The training mask of block diffusion in its vectorised form (one pass
    over the noised and the clean copy of a sequence).

    The ``2 * seq_len`` rows are ``[xt ; x0]``: row ``i < seq_len`` is token
    ``i`` of the noised copy, row ``seq_len + i`` token ``i`` of the clean
    one; tokens lie in blocks of ``block``. Row q may attend row k iff

    - both are noised and in the same block (a block denoises jointly), or
    - q is noised, k is clean and k's block is EARLIER than q's, or
    - both are clean and k's block is not later than q's (block-causal);

    a clean row never attends a noised one. ``seq_len * (seq_len + block)``
    of the ``4 seq_len^2`` pairs are allowed, and every row has at least its
    own block. Static and hashable: a structured mask is a build-time
    constant of a program, beside ``causal``.
    """

    seq_len: int
    block: int
    name: ClassVar[str] = "block_diffusion"

    def __post_init__(self):
        if self.block < 1 or self.seq_len % self.block:
            raise ValueError(
                f"block {self.block} does not divide seq_len {self.seq_len}")

    @property
    def rows(self) -> int:
        return 2 * self.seq_len

    def allowed(self, q_ids, k_ids):
        """May row ``q_ids`` attend row ``k_ids``? Broadcasts; numpy or jax
        integer arrays alike (the splash kernel calls it on tiles of ids)."""
        L, B = self.seq_len, self.block
        q_clean, k_clean = q_ids >= L, k_ids >= L
        if B & (B - 1):
            blk = lambda i: i // B
        else:  # a shift: cheaper than a division inside a kernel
            blk = lambda i: i >> (B.bit_length() - 1)
        qb = blk(q_ids - L * q_clean)
        kb = blk(k_ids - L * k_clean)
        # a clean key of an earlier block, for either kind of query; or the
        # query's own block in the query's own copy
        return (k_clean & (kb < qb)) | ((q_clean == k_clean) & (kb == qb))

    def pairs(self) -> int:
        return self.seq_len * (self.seq_len + self.block)

    def dense(self) -> np.ndarray:
        """The ``[rows, rows]`` boolean matrix, written out (small sizes)."""
        ids = np.arange(self.rows)
        return np.asarray(self.allowed(ids[:, None], ids[None, :]))

    def tile_map(self, tile: int) -> np.ndarray:
        """``[rows / tile, rows / tile]`` bool: which ``tile x tile`` tiles of
        the square hold an allowed pair (what a tile-skipping kernel visits).
        The mask is constant within a pair of blocks, so one row a block is
        looked at."""
        if tile % self.block or self.rows % tile:
            raise ValueError(f"tile {tile} against block {self.block}, "
                             f"rows {self.rows}")
        ids = np.arange(0, self.rows, self.block)
        per, n = tile // self.block, self.rows // tile
        m = np.asarray(self.allowed(ids[:, None], ids[None, :]))
        return m.reshape(n, per, n, per).any(axis=(1, 3))

    def tile_pairs(self, tile: int) -> int:
        return int(self.tile_map(tile).sum()) * tile * tile

    def over(self, rows: int) -> "BlockDiffusionMask":
        """A mask of the same kind over ``rows`` rows (the self-check's)."""
        return dataclasses.replace(self, seq_len=rows // 2)


@dataclasses.dataclass(frozen=True)
class CausalMask:
    """``causal`` as a mask object, for the kernels that take one: row q may
    attend row k iff ``k <= q``. The splash kernels skip the tiles above the
    diagonal and read grouped KV heads where they lie, which is how a plain
    causal call runs (:func:`_causal_splash`: at a head of 128 where the one
    backward kernel takes the shape, and at every head that is no multiple of
    the 128 lanes: 64, or 192 on values of 128)."""

    seq_len: int
    name: ClassVar[str] = "causal"

    @property
    def rows(self) -> int:
        return self.seq_len

    def allowed(self, q_ids, k_ids):
        return k_ids <= q_ids

    def pairs(self) -> int:
        return self.seq_len * (self.seq_len + 1) // 2

    def tile_pairs(self, tile: int) -> int:
        return _band_tile_pairs(self, tile)

    def over(self, rows: int) -> "CausalMask":
        return CausalMask(rows)


@dataclasses.dataclass(frozen=True)
class WindowMask:
    """Causal attention under a sliding window: row q may attend row k iff
    ``q - window < k <= q`` (itself and the ``window - 1`` rows before it).
    The splash kernels skip the tiles above the diagonal and those wholly
    below the band."""

    seq_len: int
    window: int
    name: ClassVar[str] = "window"

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"a window of {self.window} rows")

    @property
    def rows(self) -> int:
        return self.seq_len

    def allowed(self, q_ids, k_ids):
        return (k_ids <= q_ids) & (q_ids - k_ids < self.window)

    def pairs(self) -> int:
        w = min(self.window, self.seq_len)
        return w * (w + 1) // 2 + (self.seq_len - w) * w

    def tile_pairs(self, tile: int) -> int:
        return _band_tile_pairs(self, tile)

    def over(self, rows: int) -> "WindowMask":
        """The self-check's: a window of an eighth of its rows at most, so
        that whole, partial and skipped tiles all occur."""
        return WindowMask(rows, min(self.window, max(1, rows // 8)))


def _band_tile_pairs(mask, tile: int) -> int:
    """Pairs in the ``tile x tile`` tiles of a causal or windowed mask that
    hold an allowed pair: tile (i, j) does iff its closest corner pair, row
    ``i tile`` (or the diagonal's) against key ``j tile + tile - 1``, is
    allowed."""
    if mask.rows % tile:
        raise ValueError(f"tile {tile} against {mask.rows} rows")
    i = np.arange(mask.rows // tile)[:, None]
    j = np.arange(mask.rows // tile)[None, :]
    q = i * tile + (tile - 1) * (i == j)
    k = np.minimum(j * tile + tile - 1, q)
    return int(((j <= i) & mask.allowed(q, k)).sum()) * tile * tile


def _block_attend(q, k, v, m, l, o, allowed, scale):
    """One online-softmax accumulation step against a K/V block.

    q: [T, H, D]; k/v: [S, H, D]; m/l: [T, H] running max / normalizer;
    o: [T, H, D] running (unnormalized) output; allowed: [T, S] bool.
    Returns updated (m, l, o). All math in f32 for stability.
    """
    logits = jnp.einsum(
        "thd,shd->ths", q, k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale  # [T, H, S]
    ok = allowed[:, None, :]  # [T, 1, S]
    logits = jnp.where(ok, logits, NEG_BIG)
    m_new = jnp.maximum(m, logits.max(axis=-1))  # [T, H]
    # alpha rescales the running state; exp() of (NEG_BIG - NEG_BIG) = 1 is
    # fine for alpha (state is all zeros then)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new[..., None]) * ok  # masked entries -> exactly 0
    l = l * alpha + p.sum(axis=-1)
    o = o * alpha[..., None] + jnp.einsum(
        "ths,shd->thd", p, v.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    return m_new, l, o


def ring_attention(
    q: jax.Array,  # [T_loc, H, D] this shard's queries
    k: jax.Array,  # [T_loc, H, D] this shard's keys
    v: jax.Array,  # [T_loc, H, D] this shard's values
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,  # [T_loc] 1.0 = real position
) -> jax.Array:
    """Exact attention over the full (sharded) sequence, computed blockwise
    with K/V rotating around the ring. Call inside ``shard_map`` with the
    sequence dimension sharded over ``axis_name``.

    Global position of local row i on rank r is ``r * T_loc + i`` (the
    natural contiguous-block sharding); ``causal=True`` masks with those
    global positions, so the result equals dense causal attention on the
    gathered sequence. Padded tail positions (ragged sequences) are
    excluded via ``kv_mask``.

    Returns [T_loc, H, D] in q's dtype.
    """
    T, H, D = q.shape
    W = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    qf = q.astype(jnp.float32)
    # constants must be marked device-varying over the ring axis or the
    # scan carry types mismatch (shard_map varying-axis tracking)
    vary = lambda t: lax.pcast(t, axis_name, to="varying")
    m0 = vary(jnp.full((T, H), NEG_BIG, jnp.float32))
    l0 = vary(jnp.zeros((T, H), jnp.float32))
    o0 = vary(jnp.zeros((T, H, D), jnp.float32))
    had_mask = kv_mask is not None
    if kv_mask is None:
        kv_mask = vary(jnp.ones((T,), jnp.float32))

    q_pos = me * T + jnp.arange(T)  # [T] global query positions

    # jax.checkpoint: AD through the scan would otherwise SAVE every
    # step's [T, H, S] probability block (O(W * T^2 * H / W) — the exact
    # memory wall ring attention exists to avoid); rematerializing the
    # block math in the backward keeps saved state at the O(T/W) carries.
    @jax.checkpoint
    def attend(m, l, o, k_blk, v_blk, mask_blk, s):
        # the block we hold at step s originated on rank (me - s) mod W
        src = (me - s) % W
        k_pos = src * T + jnp.arange(T)  # [S] global key positions
        allowed = mask_blk[None, :] > 0
        if causal:
            allowed = allowed & (k_pos[None, :] <= q_pos[:, None])
        return _block_attend(qf, k_blk, v_blk, m, l, o, allowed, scale)

    def step(carry, s):
        m, l, o, k_blk, v_blk, mask_blk = carry
        if causal:
            # a block strictly in the query shard's future contributes
            # nothing; skip its FLOPs entirely (on W ranks, (W-1)/2W of
            # all ring-step blocks — the causal load-imbalance half)
            src = (me - s) % W
            m, l, o = lax.cond(
                src > me,
                lambda *a: a[:3],
                attend,
                m, l, o, k_blk, v_blk, mask_blk, s,
            )
        else:
            m, l, o = attend(m, l, o, k_blk, v_blk, mask_blk, s)
        # rotate K/V/mask to the next rank (one ICI neighbor hop)
        perm = [(i, (i + 1) % W) for i in range(W)]
        k_blk, v_blk, mask_blk = (
            lax.ppermute(t, axis_name, perm) for t in (k_blk, v_blk, mask_blk)
        )
        return (m, l, o, k_blk, v_blk, mask_blk), None

    # K/V rotate in their INPUT dtype (bf16 halves the per-hop ICI bytes and
    # the scan-carry memory); _block_attend upcasts per block, so numerics
    # are unchanged
    (m, l, o, _, _, _), _ = lax.scan(
        step, (m0, l0, o0, k, v, kv_mask), jnp.arange(W)
    )
    # fully-masked rows (all-padding shard under kv_mask) have l == 0
    out = o / jnp.maximum(l, 1e-30)[..., None]
    if had_mask:
        # kv_mask here is the un-rotated local shard mask = this shard's
        # query-row mask
        out = _zero_padded_rows(out, kv_mask)
    return out.astype(q.dtype)


def _zero_padded_rows(out: jax.Array, kv_mask: jax.Array) -> jax.Array:
    """The contract every attention impl shares (dense/ring/ulysses/flash):
    PADDED QUERY ROWS ARE ZERO, so full-tensor outputs agree across
    implementations instead of diverging on don't-care rows (ADVICE r3 #2).
    ``out`` is [T, H, D]; ``kv_mask`` is the [T] query-position mask."""
    return out * (kv_mask > 0).astype(out.dtype)[:, None, None]


def repeat_kv(q: jax.Array, k: jax.Array, v: jax.Array):
    """Grouped-query heads for an implementation that knows one head count:
    KV head ``j // (H / Hkv)`` serves query head ``j``, so each KV head is
    repeated ``H / Hkv`` times along the head axis. (The splash path reads
    the KV heads where they lie and never calls this.)"""
    H, Hkv = q.shape[1], k.shape[1]
    if H == Hkv:
        return k, v
    if H % Hkv:
        raise ValueError(f"heads {H} not divisible by kv heads {Hkv}")
    return tuple(jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))


def dense_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = False, scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None, mask=None,
) -> jax.Array:
    """Single-device oracle: softmax(q k^T) v over the FULL sequence
    ([T, H, D] queries; keys and values may have fewer, grouped-query,
    heads, and the values a head size of their own). ``mask``: a structured mask object (``BlockDiffusionMask``),
    honoured exactly, in ``causal``'s place. The equivalence target for
    :func:`ring_attention` and the kernels (tests/test_sequence.py) and the
    small-sequence fallback."""
    T, H, D = q.shape
    k, v = repeat_kv(q, k, v)
    if mask is not None and (causal or mask.rows != T):
        raise ValueError(
            f"{mask.name} mask over {mask.rows} rows against causal={causal}, "
            f"T={T}: a structured mask stands in causal's place, on its rows")
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum(
        "thd,shd->ths", q.astype(jnp.float32), k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale
    allowed = jnp.ones((T, T), bool) if kv_mask is None else (kv_mask[None, :] > 0)
    if causal:
        allowed = allowed & (
            jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        )
    if mask is not None:
        allowed = allowed & mask.allowed(
            jnp.arange(T)[:, None], jnp.arange(T)[None, :])
    logits = jnp.where(allowed[:, None, :], logits, NEG_BIG)  # bcast to heads
    p = jax.nn.softmax(logits, axis=-1)
    p = p * allowed[:, None, :]
    out = jnp.einsum("ths,shd->thd", p, v.astype(jnp.float32))
    if kv_mask is not None:
        out = _zero_padded_rows(out, kv_mask)
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,  # [T_loc, H, D]
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,  # [T_loc]
) -> jax.Array:
    """All-to-all sequence parallelism (the DeepSpeed-Ulysses layout swap):
    one ``all_to_all`` re-shards [T/W, H, D] -> [T, H/W, D] (full sequence,
    subset of heads), dense attention runs per head with NO inner-loop
    communication, and a second ``all_to_all`` restores sequence sharding.

    vs the ring: 2 big collectives + O(T) memory/device instead of W
    neighbor hops + O(T/W) memory. The ring wins at long context (memory)
    and maps onto ICI neighbor links; Ulysses wins when heads are plentiful
    and T fits — both are exact. Requires H divisible by the axis size.
    Same contract as :func:`ring_attention` (call inside shard_map,
    contiguous-block sequence sharding).
    """
    H = q.shape[1]
    W = lax.psum(1, axis_name)  # static (mesh axis size)
    if H % W:
        raise ValueError(
            f"ulysses_attention needs heads ({H}) divisible by the "
            f"{axis_name!r} axis size ({W}); use ring_attention otherwise"
        )

    def seq_to_head(x):  # [T_loc, H, D] -> [W*T_loc, H/W, D]
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=0, tiled=True
        )

    def head_to_seq(x):  # [W*T_loc, H/W, D] -> [T_loc, H, D]
        return lax.all_to_all(
            x, axis_name, split_axis=0, concat_axis=1, tiled=True
        )

    qh, kh, vh = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    if kv_mask is None:
        mask_full = None
    else:
        # every device needs the FULL-sequence mask once heads are sharded
        mask_full = lax.all_gather(kv_mask, axis_name, tiled=True)
    if _flash_applicable(qh, group=qh.shape[1] // kh.shape[1], causal=causal,
                         kv_mask=mask_full):
        out = _flash_dense(qh, kh, vh, causal=causal, scale=scale,
                           kv_mask=mask_full)
        return head_to_seq(out)
    out = dense_attention(
        qh, kh, vh, causal=causal, scale=scale, kv_mask=mask_full
    )
    return head_to_seq(out)


def _head_key(head_dim: int, v_head_dim=None):
    """How a head size is latched: the q.k size, or ``(q.k, v)`` where the
    values' differs."""
    return head_dim if v_head_dim in (None, head_dim) \
        else (head_dim, v_head_dim)


def _flash_applicable(qh: jax.Array, *, require_pinned: bool = False,
                      mask=None, group: int = 1, v_head_dim=None,
                      causal: bool = False, kv_mask=None) -> bool:
    """Run a full-sequence dense attention site through the Mosaic kernels?
    Which kernels follows from the call itself. Under a structured ``mask``
    they are the splash ones; a plain causal call (``causal``, no
    ``kv_mask``) takes the splash ones too, under :class:`CausalMask`, where
    :func:`_causal_splash` says so; every other call at a head of whole lanes
    takes the library's flash kernels. Each family engages only after ITS
    self-check passed in this process (the splash kernels' for this kind of
    mask, this many query heads a KV head, ``group``, and these head sizes;
    the flash kernels' ``_flash_verified``): no flag stands in for a check,
    and neither check for the other.

    Trace-time decision: config tri-state (``DGRAPH_TPU_FLASH_ATTN``) +
    shape constraints of the TPU kernels (T a multiple of their 128 query
    block). ``require_pinned=True`` (the single-comm ORACLE site) engages
    only on an explicit config True — never on auto — so a Mosaic kernel
    can't silently replace the dense reference that parity harnesses compare
    against.
    """
    from dgraph_tpu import config as _cfg

    if jax.default_backend() != "tpu":
        return False  # the kernel is Mosaic-only; a pinned flag on CPU
        # must not trace it (every other Pallas gate has this check)
    pinned = _cfg.use_flash_attention is True
    if (require_pinned or not _cfg.flash_attention_enabled()) and not pinned:
        return False
    T, _, D = qh.shape
    if T % 128:
        return False
    Dv = v_head_dim or D
    if mask is not None:
        return (mask.name, group, _head_key(D, Dv)) in _splash_verified
    if causal and kv_mask is None and _causal_splash(T, D, Dv, group,
                                                     qh.dtype):
        return True
    return D % 128 == 0 and Dv == D and _flash_verified


def _causal_splash(T: int, D: int, Dv: int, group: int, dtype) -> bool:
    """Does a plain causal call (no ``kv_mask``) of ``T`` rows at ``group``
    query heads a KV head and heads of ``D | Dv`` run through the splash
    kernels under :class:`CausalMask`? Where the shape is theirs
    (:func:`_causal_splash_shape`) and their self-check latched this grouping
    and these head sizes. What the call shows decides: no flag."""
    return _causal_splash_shape(T, D, Dv, dtype) and (
        CausalMask.name, group, _head_key(D, Dv)) in _splash_verified


def _causal_splash_shape(T: Optional[int], D: int, Dv: int, dtype) -> bool:
    """The shapes of plain causal calls that are the splash kernels' (``T``
    None: no such call is stated). A head off the lanes always: the flash
    kernels take none but repeated and zero-padded (at D = 64, 32 heads on 8,
    T = 16384: 20.2 ms forward / 100.7 forward + backward against the splash
    kernels' 17.7 / 83.7; PERF.md section 6, PR 34). A head on them where the
    backward is the one kernel (:func:`_one_kernel_backward`: 8.5 us a
    visited 1024 x 1024 tile where the library's flash pair takes 13.1, with
    K and V read where they lie; PERF.md section 6, PR 52)."""
    return D % 128 != 0 or (
        T is not None and _one_kernel_backward(T, D, Dv, dtype))


def _flash_dense(qh, kh, vh, *, causal, scale, kv_mask):
    """[T, H_loc, D] full-sequence attention through the Mosaic kernels a
    call's own arguments choose (the site passed :func:`_flash_applicable`):
    a plain causal call the splash kernels under :class:`CausalMask` where
    :func:`_causal_splash` holds (grouped K and V as they are), any other the
    library's flash kernels (:func:`_flash_kernels`)."""
    T, H, D = qh.shape
    plain = causal and kv_mask is None
    if D % 128 and not plain:
        raise NotImplementedError(
            f"head_dim {D}: the kernel path of a head off the lanes is "
            f"causal and takes no kv_mask")
    if plain and _causal_splash(T, D, vh.shape[-1], H // kh.shape[1],
                                qh.dtype):
        return _splash_dense(qh, kh, vh, mask=CausalMask(T), scale=scale)
    return _flash_kernels(qh, kh, vh, causal=causal, scale=scale,
                          kv_mask=kv_mask)


def _flash_kernels(qh, kh, vh, *, causal, scale, kv_mask):
    """``jax.experimental.pallas.ops.tpu.flash_attention`` (forward AND
    backward are Mosaic kernels with their own custom VJP — memory stays
    O(T * block) instead of the [T, H, T] logits tensor). Padded tail
    positions are excluded by giving them a second segment id; grouped K and
    V are repeated to the query heads."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    T, _, D = qh.shape
    kh, vh = repeat_kv(qh, kh, vh)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    # kernel layout: [batch, heads, T, D]
    to_k = lambda x: x.transpose(1, 0, 2)[None]
    seg = None
    if kv_mask is not None:
        ids = (kv_mask <= 0).astype(jnp.int32)[None]  # padding -> segment 1
        seg = fa.SegmentIds(q=ids, kv=ids)
    out = fa.flash_attention(
        to_k(qh), to_k(kh), to_k(vh), segment_ids=seg, causal=causal,
        sm_scale=float(scale), block_sizes=_flash_block_sizes(fa, T),
    )
    res = out[0].transpose(1, 0, 2)
    if kv_mask is not None:
        # without this the flash path's padded rows attend the padding
        # SEGMENT while the dense oracle's attend real keys
        res = _zero_padded_rows(res, kv_mask)
    return res.astype(qh.dtype)


# Largest tile edge of the flash kernels (forward, dq and dkv alike). The
# library's default of 128 everywhere leaves the MXU waiting on grid steps
# at long T; FLASH_BLOCK is what the chip measured fastest at T = 8192,
# H = 16, D = 128 (PERF.md, section 6, PR 28).
FLASH_BLOCK = 1024
# Columns of a kv tile the splash kernels take through the softmax at a time:
# what the chip measured fastest under the block-diffusion mask at 16384 rows,
# 32 heads on 4 (forward 19.5 ms at 256, 23.8 at 512, 22.1 at 128; PERF.md,
# section 6, PR 32), and for a q.k head of 192 on values of 128 (32 heads on
# 32, causal, 16384 rows; forward / forward + backward 25.98 / 102.12 ms at
# 256, 27.04 / 103.10 at 512, 30.77 / 107.03 at 128; q and k zero-padded to
# 256, which is exact, are SLOWER at each: 26.72 / 103.01, 27.92 / 104.08,
# 31.57 / 107.91, so such a head runs as it is; scripts/splash_head_sweep.py,
# PERF.md, section 6, PR 49).
SPLASH_KV_COMPUTE = 256
# ... and for a head of 64 under the causal mask at 16384 rows, 32 heads on 8
# (forward / forward + backward 17.68 / 83.70 ms at 512, 18.07 / 83.88 at 256,
# 19.58 / 87.49 at 1024; PERF.md, section 6, PR 34).
SPLASH_KV_COMPUTE_NARROW = 512


def flash_tile(T: int) -> int:
    """The tile edge of every block of the kernels: the largest power of two
    <= FLASH_BLOCK that divides T (T is a multiple of 128 here)."""
    b = FLASH_BLOCK
    while T % b:
        b //= 2
    return b


@functools.lru_cache(maxsize=None)
def _splash_mask(mask, heads: int):
    """``mask`` as the splash kernels take one: ``heads`` copies of a mask
    they compute tile by tile from the row ids (nothing of size T x T is
    ever stored; a tile with no allowed pair is skipped, one with all of
    them is not masked)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm)

    class Computed(sm._ComputableMask):
        def __init__(self):
            super().__init__(shape=(mask.rows, mask.rows),
                             mask_function=mask.allowed)

        def __eq__(self, other):
            return type(other) is type(self)

        def __hash__(self):
            return hash((type(self), mask))

    return sm.MultiHeadMask([Computed()] * heads)


def _splash_dense(qh, kh, vh, *, mask, scale, interpret: bool = False):
    """[T, H, D] queries on [T, Hkv, D] keys and values under a structured
    mask, via ``jax.experimental.pallas.ops.tpu.splash_attention``: tiles of
    ``flash_tile(T)`` rows, skipped where the mask allows no pair. Grouped-query
    heads are native: one multi-query kernel over the ``H / Hkv`` query heads
    of a KV head, mapped over the KV heads; K and V are read where they lie.
    The values' head size may differ from D (the result has theirs).

    The forward is the library's Mosaic kernel. The backward is ONE kernel of
    the repo's own (:mod:`dgraph_tpu.ops.pallas_attention`: scores and
    probabilities once a visited tile, ``dq``, ``dk``, ``dv`` summed in
    float32 on chip) where :func:`_one_kernel_backward` says a KV head's
    blocks fit, and the library's two (dkv, then dq) everywhere else. Counted
    a traced backward: ``attn.bwd_calls``, ``attn.bwd_one_kernel``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)

    T, H, D = qh.shape
    Hkv, Dv = kh.shape[1], vh.shape[-1]
    if H % Hkv or mask.rows != T:
        raise ValueError(f"heads {H} on {Hkv} kv heads, T={T} under a mask "
                         f"over {mask.rows} rows")
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    one = _one_kernel_backward(T, D, Dv, qh.dtype, interpret=interpret)
    blocks = _splash_block_sizes(sk, T, D, backward=not one)
    heads_mask = _splash_mask(mask, H // Hkv)
    kernel = sk.make_splash_mqa_single_device(
        heads_mask, interpret=interpret, block_sizes=blocks)
    # kernel layout: [kv heads, query heads of one kv head, T, D]; the kernel
    # has no scale of its own
    operands = ((qh * jnp.asarray(scale, qh.dtype)).transpose(1, 0, 2).reshape(
        Hkv, H // Hkv, T, D), kh.transpose(1, 0, 2), vh.transpose(1, 0, 2))
    if one:
        out = _one_kernel_attend(kernel, heads_mask, mask.allowed)(*operands)
    else:
        out = _counted_backward(jax.vmap(kernel)(*operands))
    return out.reshape(H, T, Dv).transpose(1, 0, 2).astype(qh.dtype)


@jax.custom_vjp
def _counted_backward(out):
    """The identity, whose pull-back counts a traced backward of a splash
    call that kept the library's two kernels (``attn.bwd_calls``)."""
    return out


def _count_backward(_, do):
    from dgraph_tpu.obs.metrics import default_registry

    default_registry.counter("attn.bwd_calls")
    return (do,)


_counted_backward.defvjp(lambda out: (out, None), _count_backward)


def _one_kernel_attend(kernel, heads_mask, allowed):
    """``(q4, k3, v3) -> out`` in the kernels' layout: the library's forward
    kernel (``kernel``'s, kept with its log-sum-exp where the call is
    differentiated) under a ``jax.custom_vjp`` whose backward is
    :func:`dgraph_tpu.ops.pallas_attention.backward` over the forward's own
    tile list. The rules are traced after the trace that built ``kernel`` has
    ended (under ``jax.checkpoint``), so they hold the tile list as numpy and
    nothing of ``kernel``'s arrays."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask_info as mi)

    from dgraph_tpu.obs.metrics import default_registry
    from dgraph_tpu.ops import pallas_attention

    tile = kernel.kwargs["block_sizes"].block_q
    # the list `kernel` was built from (the library keeps the last dozen)
    info, _ = mi.process_mask(
        heads_mask, (tile, tile), downcast_smem_data=True, head_shards=1,
        q_seq_shards=1)
    tile_visits = pallas_attention.visits(info.data_next, info.block_mask)
    forward_kernel = lambda **kw: jax.vmap(sk.SplashAttentionKernel(
        info, None, None, **{**kernel.kwargs, **kw}))

    @jax.custom_vjp
    def attend(q4, k3, v3):
        return forward_kernel()(q4, k3, v3)

    def forward(q4, k3, v3):
        o, (lse,) = forward_kernel(save_residuals=True)(q4, k3, v3)
        return o, (q4, k3, v3, o, lse)

    def backward(kept, do):
        q4, k3, v3, o, lse = kept
        default_registry.counter("attn.bwd_calls")
        default_registry.counter("attn.bwd_one_kernel")
        di = jnp.einsum("hgtd,hgtd->hgt", o.astype(jnp.float32),
                        do.astype(jnp.float32))
        return tuple(pallas_attention.backward(
            q4, k3, v3, do, lse, di, tile_visits, allowed=allowed, tile=tile,
            interpret=kernel.kwargs["interpret"]))

    attend.defvjp(forward, backward)
    return attend


def _one_kernel_backward(T: int, D: int, Dv: int, dtype, *,
                         interpret: bool = False) -> bool:
    """Does a splash call at ``T`` rows and heads of ``D | Dv`` take the
    one-kernel backward? On a TPU (or interpreted), where ``T`` is whole
    tiles and a KV head's resident blocks and float32 accumulators, ``T x (D
    + Dv)`` at whole lane tiles, fit ``pallas_attention.VMEM_BUDGET`` with
    the rest of the kernel's blocks (``vmem_bytes``). Shapes decide: no flag,
    no model's name."""
    from dgraph_tpu.ops import pallas_attention

    return (interpret or jax.default_backend() == "tpu") \
        and pallas_attention.applies(
            T, D, Dv, jnp.dtype(dtype).itemsize, flash_tile(T))


def _splash_block_sizes(sk, T: int, D: int = 128, *, backward: bool = True):
    """Square tiles of ``flash_tile(T)`` for the splash kernels (the forward
    alone without ``backward``: the one-kernel backward takes its tile from
    the forward's), the softmax taken over SPLASH_KV_COMPUTE columns of a kv
    tile at a time (SPLASH_KV_COMPUTE_NARROW for a q.k head ``D`` narrower
    than the lanes)."""
    b = flash_tile(T)
    c = min(b, SPLASH_KV_COMPUTE_NARROW if D < 128 else SPLASH_KV_COMPUTE)
    if not backward:
        return sk.BlockSizes(block_q=b, block_kv=b, block_kv_compute=c)
    return sk.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=c,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=c,
        block_q_dq=b, block_kv_dq=b)


def _flash_block_sizes(fa, T: int):
    """One tile edge for every block of the flash kernels."""
    b = flash_tile(T)
    return fa.BlockSizes(
        block_q=b, block_k_major=b, block_k=b, block_b=1,
        block_q_major_dkv=b, block_k_major_dkv=b, block_k_dkv=b,
        block_q_dkv=b, block_k_major_dq=b, block_k_dq=b, block_q_dq=b)


# Query heads the splash self-check's dense oracle takes at a time: its eager
# programs hold several [rows, heads, rows] float32 arrays, 0.54 GB each at 8
# heads and the check's 4096 rows, and the allocator's peaks are the process's:
# at 16 heads at once (32 on 2) the check read 4.2 GB over the training step's
# own peak (PERF.md section 6, PR 52).
ORACLE_HEADS = 8
# The library's flash kernels engage only after _flash_selfcheck() passed in
# this process (what flash_attention_selfcheck() runs for a caller that reaches
# them); a pinned config True does not stand in for it.
_flash_verified = False
# (mask kind, query heads a kv head, head_dim) whose splash self-check passed
_splash_verified: set = set()


def flash_attention_selfcheck(mask=None, group: int = 1,
                              head_dim: int = 128, v_head_dim=None,
                              rows: Optional[int] = None,
                              dtype=jnp.float32) -> bool:
    """Chip-gated equivalence check vs :func:`dense_attention` (the same
    Mosaic-divergence rationale as bench.py's scatter self-checks: the
    kernel class is invisible to CPU CI) of the kernels the caller's calls
    will run, and of no others; returns False off-TPU.

    As called with nothing, the library's flash kernels, forward and backward
    (what a call with a ``kv_mask``, or not causal, runs); passing latches
    ``_flash_verified``. With a structured ``mask`` (and ``group`` query heads
    a KV head) the splash kernels, forward and backward, under a mask of the
    same kind over four tiles a side (whole, partial and skipped tiles all
    occur) and two KV heads, at heads of ``head_dim`` (and values of
    ``v_head_dim``, where that differs: latched as ``(head_dim,
    v_head_dim)``); passing latches (kind, group, head_dim) in
    ``_splash_verified``. Without a mask the caller's calls are plain causal
    ones of ``rows`` rows and ``dtype`` streams, and where their shape is the
    splash kernels' (:func:`_causal_splash_shape`: a ``head_dim`` that is no
    multiple of 128, or one the one-kernel backward takes) those are checked,
    under the causal mask at THAT head size and grouping, and the flash
    kernels, which such a caller never reaches, are not.

    The call is the stage ``setup.attention_selfcheck`` (always on, one a
    latch asked for; off a TPU it ends at once with ``passed`` false).
    """
    from dgraph_tpu.obs import spans

    with spans.stage("setup.attention_selfcheck",
                     mask=CausalMask.name if mask is None else mask.name,
                     group=group, head_dim=head_dim) as st:
        if jax.default_backend() != "tpu":
            passed = False
        elif mask is not None or _causal_splash_shape(
                rows, head_dim, v_head_dim or head_dim, dtype):
            passed = _splash_selfcheck(
                mask or CausalMask(0), group, head_dim=head_dim,
                v_head_dim=v_head_dim)
        else:
            passed = _flash_selfcheck()
        st.annotate(passed=passed)
    return passed


def _flash_selfcheck() -> bool:
    """The Mosaic flash kernels against the dense oracle, forward and
    backward; passing latches ``_flash_verified``."""
    global _flash_verified

    rng = np.random.default_rng(3)
    T, H, D = 256, 2, 128
    q, k, v = (
        jnp.asarray(rng.standard_normal((T, H, D)), jnp.bfloat16)
        for _ in range(3)
    )
    mask = jnp.asarray((np.arange(T) < T - 32).astype(np.float32))
    close = lambda a, b: np.allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=5e-2, atol=5e-2)
    try:
        for causal in (False, True):
            got = _flash_kernels(q, k, v, causal=causal, scale=None,
                                 kv_mask=mask)
            want = dense_attention(q, k, v, causal=causal, kv_mask=mask)
            real = np.asarray(mask) > 0
            if not close(np.asarray(got, np.float32)[real],
                         np.asarray(want, np.float32)[real]):
                return False
        # causal, no mask (a shape past the one-kernel backward's budget),
        # T past one tile of FLASH_BLOCK, and the backward kernels (dq, dk,
        # dv) as well
        T = 2 * FLASH_BLOCK
        q, k, v, w = (
            jnp.asarray(rng.standard_normal((T, H, D)), jnp.bfloat16)
            for _ in range(4)
        )

        def grads(attend):
            return jax.grad(
                lambda q_, k_, v_: (attend(q_, k_, v_).astype(jnp.float32)
                                    * w.astype(jnp.float32)).sum(),
                argnums=(0, 1, 2))(q, k, v)

        flash = lambda q_, k_, v_: _flash_kernels(
            q_, k_, v_, causal=True, scale=None, kv_mask=None)
        dense = lambda q_, k_, v_: dense_attention(q_, k_, v_, causal=True)
        if not close(flash(q, k, v), dense(q, k, v)):
            return False
        if not all(close(a, b) for a, b in zip(grads(flash), grads(dense))):
            return False
    except Exception:
        return False
    _flash_verified = True
    return True


def _splash_selfcheck(mask, group: int, *, interpret: bool = False,
                      head_dim: int = 128, v_head_dim=None) -> bool:
    """Splash forward and backward against the dense oracle under a mask of
    ``mask``'s kind at heads of ``head_dim``; see
    :func:`flash_attention_selfcheck`. The oracle runs one KV head at a time,
    ``ORACLE_HEADS`` of its query heads at a time (its ``[T, heads, T]``
    float32 logits), the head's ``dk`` and ``dv`` summed over them."""
    rows = 4 * (128 if interpret else FLASH_BLOCK)  # four tiles a side
    small = mask.over(rows)
    rng = np.random.default_rng(5)
    Hkv, D, Dv = 2, head_dim, v_head_dim or head_dim
    q, w = (jnp.asarray(rng.standard_normal((rows, Hkv * group, d)),
                        jnp.bfloat16) for d in (D, Dv))
    k, v = (jnp.asarray(rng.standard_normal((rows, Hkv, d)), jnp.bfloat16)
            for d in (D, Dv))
    close = lambda a, b: np.allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=5e-2, atol=5e-2)

    def both(attend, q_, k_, v_, w_):
        f = lambda *a: (attend(*a).astype(jnp.float32)
                        * w_.astype(jnp.float32)).sum()
        return (attend(q_, k_, v_),) + jax.grad(f, argnums=(0, 1, 2))(
            q_, k_, v_)

    try:
        got = both(lambda *a: _splash_dense(
            *a, mask=small, scale=None, interpret=interpret), q, k, v, w)
        for j in range(Hkv):
            dkv = [0.0, 0.0]
            for h in range(j * group, (j + 1) * group, ORACLE_HEADS):
                heads = slice(h, min(h + ORACLE_HEADS, (j + 1) * group))
                want = both(lambda *a: dense_attention(*a, mask=small),
                            q[:, heads], k[:, j:j + 1], v[:, j:j + 1],
                            w[:, heads])
                if not (close(got[0][:, heads], want[0])
                        and close(got[1][:, heads], want[1])):
                    return False
                dkv = [a + b.astype(jnp.float32)
                       for a, b in zip(dkv, want[2:])]
            if not all(close(got[2 + i][:, j:j + 1], dkv[i])
                       for i in range(2)):
                return False
    except Exception:
        return False
    _splash_verified.add((mask.name, group, _head_key(D, v_head_dim)))
    return True


def ring_attention_sharded(
    q: jax.Array,  # [T, H, D] FULL sequence (host/global view)
    k: jax.Array,
    v: jax.Array,
    mesh: jax.sharding.Mesh,
    axis_name: str = "seq",
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,  # [T] 1.0 = real position
) -> jax.Array:
    """Convenience wrapper: shard the sequence dim over ``mesh[axis_name]``
    and run :func:`ring_attention` under ``shard_map``. T must divide by
    the axis size; ragged sequences pad T upstream and mark real positions
    in ``kv_mask`` (static shapes are the contract everywhere in this
    framework)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    W = mesh.shape[axis_name]
    if q.shape[0] % W:
        raise ValueError(
            f"sequence length {q.shape[0]} not divisible by {axis_name}={W}"
        )
    if kv_mask is None:
        kv_mask = jnp.ones((q.shape[0],), jnp.float32)
    from dgraph_tpu.comm.collectives import shard_map_checks

    fn = shard_map(
        lambda q, k, v, m: ring_attention(
            q, k, v, axis_name, causal=causal, scale=scale, kv_mask=m
        ),
        mesh=mesh,
        in_specs=(P(axis_name),) * 4,
        out_specs=P(axis_name),
        # audited (ISSUE 12): the blanket RELAXED_CHECKS splat is the
        # routed escape now — out is fully sharded, so the rep checker
        # protects nothing here, and 0.4.x's raises a false cond-branch
        # mismatch when AD re-traces the causal lax.cond
        **shard_map_checks(relax="ring-attention causal cond false "
                                 "positive under AD; out fully sharded"),
    )
    return fn(q, k, v, kv_mask)
