"""Pallas rasterization kernel — the LuminCore NRU, re-expressed for TPU.

One grid program = one 16x16-pixel tile.  The tile's depth-sorted Gaussian
features live in VMEM (streamed there by the Pallas pipeline); the kernel
walks them in chunks of ``chunk`` Gaussians:

  frontend (NRU PE array analogue)
      alpha for the whole (chunk x 256 pixels) block is evaluated *densely*
      on the VPU — conic quadratic form + exp — exactly the cheap uniform
      work the paper's PE frontend does for every Gaussian;
  backend (NRU shared backend analogue) — two flavors via ``body``:
      ``'dense'``: the order-sensitive color integration collapses to closed
      form with an exclusive prefix-product of (1 - alpha) along the chunk
      axis (a log-step scan built from sublane rolls) and one weighted
      reduction per color channel — the shape for TPU vector units;
      ``'seq'``: a sequential per-Gaussian update over the chunk (the
      faithful analogue of the FIFO feeding the paper's shared backend),
      with a branch that skips Gaussians contributing to no pixel.  It
      indexes chunk rows dynamically, which only interpret mode can do; on
      CPU it wins big, since the scans cost ~log(C) dense passes that a
      scalar core pays for real and most shared-list entries are invisible
      at the render pose.  ops.py picks ``'seq'`` whenever it interprets
      and ``'dense'`` when compiling natively.
  early exit (sparsity harvesting)
      a `while`-loop over chunks stops as soon as every pixel in the tile is
      terminated / its alpha-record is full / it is not live / past the
      tile's last valid Gaussian (``ncap``) — the TPU analogue of
      warp-divergence elimination: whole chunks of work are skipped at the
      granularity the hardware actually schedules.

Layouts (what the TPU compiler accepts):
  * features travel as one lane-dense *plane* per tile, ``[NF, K]`` f32
    (``pack_plane``): Gaussians on lanes, one row per scalar feature, each
    id split over two rows of exact small integers.  Per chunk the kernel transposes an aligned
    128-lane window into ``[W, NF]`` so each feature is a ``[C, 1]`` column
    that broadcasts against a ``[1, P]`` row of pixels;
  * per-pixel state is lane-dense too: ``[1, P]`` rows, ``[3, P]`` color,
    ``[k, P]`` record.  The wrappers keep the ``[T, P, ...]`` contract of
    ``RasterState`` and transpose at the boundary;
  * scalars (per-tile chunk caps, chunk counts, source-tile lists) sit in
    SMEM blocks.

The same kernel serves three modes (see ops.py):
  * full      — baseline rasterization (S^2 path);
  * prefix    — stop each pixel once its k-record fills (RC phase A:
                "identify the first k significant Gaussians");
  * resume    — continue cache-MISS pixels from their saved state
                (RC phase B), with per-pixel ``start_iter`` gating.

``_kernel_compact`` is the fourth mode: miss-compacted resume, where the P
lanes of a program come from *different* source tiles (LuminCore PE
remapping in software) — see ``ops.rasterize_resume_compacted``.

Exact-match contract with ``repro.kernels.ref.rasterize_ref`` (same
floating-point semantics, including the Gamma<eps freeze rule) — verified by
shape/dtype sweep tests over both body flavors.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.gaussians import ALPHA_MAX, ALPHA_SIGNIFICANT, TRANSMITTANCE_EPS

P = 256            # pixels per tile (16 x 16)
TILE = 16
LANES = 128        # TPU vector lane width: plane windows are this aligned
NF = 16            # plane rows: mx my ca cb cc r g b op id_hi id_lo, zeros
_ID_ROW = 9        # ids ride two rows: id = id_hi * 2**_ID_SPLIT + id_lo
_ID_SPLIT = 12


def pack_plane(mean2d, conic, color, opacity, ids):
    """[..., K, ...] feature arrays -> lane-dense plane [..., NF, K'] f32.

    K' is K padded up to a multiple of ``LANES`` (padding ids are -1, so
    padded entries never contribute).  Each int32 id is split into two
    small integers that f32 holds exactly (``id >> 12`` and ``id & 4095``)
    rather than bit-cast: a bit-cast id is a denormal or NaN float, which
    any float operation on the TPU may flush or rewrite.
    """
    ids = ids.astype(jnp.int32)
    rows = [mean2d[..., 0], mean2d[..., 1], conic[..., 0], conic[..., 1],
            conic[..., 2], color[..., 0], color[..., 1], color[..., 2],
            opacity, ids >> _ID_SPLIT, ids & ((1 << _ID_SPLIT) - 1)]
    plane = jnp.stack([r.astype(jnp.float32) for r in rows], axis=-2)
    pad_k = -ids.shape[-1] % LANES
    lead = [(0, 0)] * (plane.ndim - 2)
    plane = jnp.pad(plane, lead + [(0, NF - len(rows)), (0, 0)])
    if pad_k:
        tail = jnp.zeros(plane.shape[:-1] + (pad_k,), jnp.float32)
        tail = tail.at[..., _ID_ROW:_ID_ROW + 2, :].set(
            jnp.asarray([[-1.0], [(1 << _ID_SPLIT) - 1]], jnp.float32))
        plane = jnp.concatenate([plane, tail], axis=-1)
    return plane


def _window(chunk: int) -> int:
    """Plane lanes loaded per chunk: an aligned 128-lane window holding the
    chunk (chunk divides 128), or the chunk itself (a multiple of 128)."""
    if chunk % LANES == 0:
        return chunk
    assert LANES % chunk == 0, f'chunk {chunk} must divide or be a multiple of {LANES}'
    return LANES


def _chunk_columns(window, tbuf_ref, c, chunk: int):
    """[NF, W] plane window -> ([C, 1] feature columns, [C, 1] int32 ids)
    for chunk ``c``.  The transpose lands in VMEM scratch so the chunk's
    rows can be picked at a dynamic (sublane-aligned) offset."""
    w = window.shape[-1]
    tbuf_ref[...] = window.T                                  # [W, NF]
    off = c * chunk - (c * chunk // w) * w
    cols = tbuf_ref[pl.ds(pl.multiple_of(off, min(chunk, 8)), chunk), :]
    feats = [cols[:, i:i + 1] for i in range(_ID_ROW)]
    hi = cols[:, _ID_ROW:_ID_ROW + 1].astype(jnp.int32)
    lo = cols[:, _ID_ROW + 1:_ID_ROW + 2].astype(jnp.int32)
    return feats, hi * (1 << _ID_SPLIT) + lo


def _window_start(c, chunk: int):
    w = _window(chunk)
    return pl.multiple_of((c * chunk // w) * w, LANES)


def _scan_rows(x, op, identity):
    """Inclusive scan along axis 0 (the chunk axis): a log-step
    (Hillis-Steele) scan from sublane rolls, which Mosaic lowers and
    integer adds keep exact."""
    n = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    s = 1
    while s < n:
        x = op(x, jnp.where(row >= s, pltpu.roll(x, s, 0), identity))
        s *= 2
    return x


def _exclusive_cumprod(x):
    inc = _scan_rows(x, jnp.multiply, 1.0)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    exc = jnp.where(row >= 1, pltpu.roll(inc, 1, 0), 1.0)
    return inc, exc


def _exclusive_cumsum_i32(x):
    x = x.astype(jnp.int32)
    return _scan_rows(x, jnp.add, 0) - x


def _dense_chunk(alpha, sig, gid, abs_pos, allowed, k_record, stop_at_k,
                 col, carry):
    """'dense' backend for one chunk: scan-closed-form integration.
    ``alpha``/``sig``/``allowed`` are [C, N]; ``gid`` is [C, 1] or [C, N];
    ``col`` is three per-channel arrays of the same shape as ``gid``.
    State rows are [1, N] (``rec`` [k, N], ``acc`` three [1, N] rows).
    Returns the updated (acc, trans, rec, cnt, nsig, niter, itk).
    """
    acc, trans, rec, cnt, nsig, niter, itk = carry
    if stop_at_k:
        pos_sig = cnt + _exclusive_cumsum_i32(sig)
        sig = sig & (pos_sig < k_record)

    beta = jnp.where(sig, 1.0 - alpha, 1.0)
    p_inc, p_exc = _exclusive_cumprod(beta)
    p_exc = p_exc * trans
    p_inc = p_inc * trans
    contrib = sig & (p_exc > TRANSMITTANCE_EPS)

    w = jnp.where(contrib, p_exc * alpha, 0.0)               # [C, N]
    acc = tuple(a + jnp.sum(w * ch, axis=0, keepdims=True)
                for a, ch in zip(acc, col))
    trans = jnp.minimum(trans, jnp.min(
        jnp.where(contrib, p_inc, trans), axis=0, keepdims=True))

    pos = cnt + _exclusive_cumsum_i32(contrib)               # [C, N]
    krow = jax.lax.broadcasted_iota(jnp.int32, rec.shape, 0)
    for kk in range(k_record):
        m = contrib & (pos == kk)
        sel = jnp.max(jnp.where(m, gid, -1), axis=0, keepdims=True)
        rec = jnp.where((krow == kk) & (sel >= 0), sel, rec)
    iters = abs_pos + 1                                      # [C, 1]
    m_k = contrib & (pos == (k_record - 1))
    sel_it = jnp.max(jnp.where(m_k, iters, -1), axis=0, keepdims=True)
    itk = jnp.where(sel_it >= 0, sel_it, itk)

    n_contrib = jnp.sum(contrib.astype(jnp.int32), axis=0, keepdims=True)
    cnt = cnt + n_contrib
    nsig = nsig + n_contrib
    active = (p_exc > TRANSMITTANCE_EPS) & (gid >= 0) & allowed
    if stop_at_k:
        # a pixel pauses right after its record fills: iterations past the
        # fill point are not examined (hardware would hand off to lookup)
        active = active & (pos < k_record)
    niter = niter + jnp.sum(active.astype(jnp.int32), axis=0, keepdims=True)
    return acc, trans, rec, cnt, nsig, niter, itk


def _seq_chunk(alpha, sig_pre, gid, abs0, allowed, k_record, stop_at_k,
               col, carry):
    """'seq' backend for one chunk (interpret mode only): per-Gaussian FIFO
    update (bit-identical to the reference oracle's scan body), with a real
    branch skipping Gaussians that are significant for no pixel — under S^2
    sharing a large fraction of a tile's list is invisible at the render
    pose, and a scalar core should not integrate invisibility.

    Shapes as in ``_dense_chunk``; ``sig_pre`` has no record-count gating —
    that is per-pixel state and is applied inside the loop.
    """
    chunk = alpha.shape[0]

    def row(x, i):
        return jax.lax.dynamic_slice_in_dim(x, i, 1, axis=0)

    def gbody(i, carry):
        acc, trans, rec, cnt, nsig, niter, itk = carry
        a_i = row(alpha, i)                                 # [1, N]
        allowed_i = row(allowed, i)
        s_i = row(sig_pre, i) & allowed_i
        gid_i = row(gid, i)                                 # [1, 1] / [1, N]
        active = trans > TRANSMITTANCE_EPS
        # examined uses this Gaussian's *pre-update* record count, exactly
        # like the oracle (the filling Gaussian itself is still examined)
        examined = active & (gid_i >= 0) & allowed_i
        if stop_at_k:
            examined = examined & (cnt < k_record)

        def integrate(carry):
            acc, trans, rec, cnt, nsig, itk = carry
            sig = s_i
            if stop_at_k:
                sig = sig & (cnt < k_record)
            contrib = sig & active
            w = jnp.where(contrib, trans * a_i, 0.0)
            acc = tuple(a + w * row(ch, i) for a, ch in zip(acc, col))
            trans = jnp.where(contrib, trans * (1.0 - a_i), trans)
            can = contrib & (cnt < k_record)
            slot = (jax.lax.broadcasted_iota(jnp.int32, rec.shape, 0)
                    == cnt) & can                           # [k, N]
            rec = jnp.where(slot, gid_i, rec)
            new_cnt = cnt + contrib.astype(jnp.int32)
            just = (new_cnt >= k_record) & (cnt < k_record) & contrib
            itk = jnp.where(just, abs0 + i + 1, itk)
            nsig = nsig + contrib.astype(jnp.int32)
            return acc, trans, rec, new_cnt, nsig, itk

        # skip Gaussians that can contribute to no pixel: only the examined
        # counter can change for them, and it is updated unconditionally.
        # In stop-at-k mode a pixel with a full record can't take
        # contributions either — without that gate phase A would keep
        # integrating the tail of every tile after all records filled.
        may_contrib = s_i & active
        if stop_at_k:
            may_contrib = may_contrib & (cnt < k_record)
        acc, trans, rec, cnt, nsig, itk = jax.lax.cond(
            jnp.any(may_contrib), integrate, lambda c: c,
            (acc, trans, rec, cnt, nsig, itk))
        niter = niter + examined.astype(jnp.int32)
        return acc, trans, rec, cnt, nsig, niter, itk

    return jax.lax.fori_loop(0, chunk, gbody, carry)


def _chunk_update(feats, gid, px, py, c, start, live, k_record, chunk,
                  stop_at_k, body, carry):
    """Frontend + backend for one chunk.  ``feats`` are the nine feature
    columns ([C, 1], or [C, N] when lanes gather from different tiles),
    ``px``/``py``/``start``/``live`` [1, N] rows."""
    gmx, gmy, ca, cb, cc, cr, cg, cbl, op = feats
    dx = px - gmx                                            # [C, N]
    dy = py - gmy
    power = -0.5 * (ca * dx * dx + cc * dy * dy) - cb * dx * dy
    alpha = jnp.minimum(ALPHA_MAX, op * jnp.exp(power))
    valid = (power <= 0.0) & (gid >= 0)

    abs_pos = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    allowed = (abs_pos >= start) & live
    sig = (alpha > ALPHA_SIGNIFICANT) & valid & allowed
    col = (cr, cg, cbl)
    if body == 'dense':
        return _dense_chunk(alpha, sig, gid, abs_pos, allowed, k_record,
                            stop_at_k, col, carry)
    return _seq_chunk(alpha, sig, gid, c * chunk, allowed, k_record,
                      stop_at_k, col, carry)


def _init_carry(acc0, trans0, rec0, cnt0, k_total: int):
    n = trans0.shape[-1]
    return ((acc0[0:1], acc0[1:2], acc0[2:3]), trans0, rec0, cnt0,
            jnp.zeros((1, n), jnp.int32), jnp.zeros((1, n), jnp.int32),
            jnp.full((1, n), k_total, jnp.int32))


def _tile_pixels(t, tiles_x: int, n: int):
    """Pixel-center rows [1, n] of tile ``t`` (repeating every P lanes)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) % P
    ox = (t % tiles_x) * TILE
    oy = (t // tiles_x) * TILE
    px = (lane % TILE + ox).astype(jnp.float32) + 0.5
    py = (lane // TILE + oy).astype(jnp.float32) + 0.5
    return px, py


def _kernel(ncap_ref, plane_ref, acc0_ref, trans0_ref, rec0_ref, cnt0_ref,
            start_ref, live_ref,
            acc_ref, trans_ref, rec_ref, cnt_ref, nsig_ref, niter_ref,
            itk_ref, chunks_ref, tbuf_ref,
            *, tiles_x: int, k_total: int, k_record: int, chunk: int,
            stop_at_k: bool, body: str):
    t = pl.program_id(0)
    w = _window(chunk)
    # per-tile chunk cap: chunks past the tile's last valid Gaussian hold only
    # -1 padding and can never contribute — the while loop must not pay for
    # them (they are what kept empty/short tiles from ever early-exiting)
    nc = jnp.minimum(jnp.int32(k_total // chunk), ncap_ref[0, 0])
    px, py = _tile_pixels(t, tiles_x, P)

    live = live_ref[...] != 0                  # [1, P]
    start = start_ref[...]                     # [1, P] int32
    # first chunk that any live pixel needs
    c0 = jnp.minimum(jnp.min(jnp.where(live, start, k_total)) // chunk, nc)

    def loop_body(carry):
        c, nchunks, inner = carry
        window = plane_ref[:, pl.ds(_window_start(c, chunk), w)]
        feats, gid = _chunk_columns(window, tbuf_ref, c, chunk)
        inner = _chunk_update(feats, gid, px, py, c, start, live, k_record,
                              chunk, stop_at_k, body, inner)
        return c + 1, nchunks + 1, inner

    def cond(carry):
        c, _, (acc, trans, rec, cnt, *_) = carry
        pix_done = ~live | (trans <= TRANSMITTANCE_EPS)
        if stop_at_k:
            pix_done = pix_done | (cnt >= k_record)
        return (c < nc) & ~jnp.all(pix_done)

    init = _init_carry(acc0_ref[...], trans0_ref[...], rec0_ref[...],
                       cnt0_ref[...], k_total)
    _, nchunks, (acc, trans, rec, cnt, nsig, niter, itk) = jax.lax.while_loop(
        cond, loop_body, (c0, jnp.int32(0), init))
    _store_state((acc_ref, trans_ref, rec_ref, cnt_ref, nsig_ref, niter_ref,
                  itk_ref), (acc, trans, rec, cnt, nsig, niter, itk))
    chunks_ref[0, 0] = nchunks


def _store_state(out_refs, state, slot=None):
    """Write the final carry into the seven state output refs; ``slot``
    picks that slot's P lanes and block (slot-batched kernel)."""
    acc, *rest = state
    for ref, v in zip(out_refs, (jnp.concatenate(acc, axis=0), *rest)):
        if slot is None:
            ref[...] = v
        else:
            ref[slot] = v[:, slot * P:(slot + 1) * P]


class RasterState(NamedTuple):
    """Per-pixel kernel state: inputs (phase init) and outputs alike."""

    acc: jax.Array        # [T, P, 3]
    trans: jax.Array      # [T, P]
    record: jax.Array     # [T, P, k]
    rec_cnt: jax.Array    # [T, P]
    n_sig: jax.Array      # [T, P]
    n_iter: jax.Array     # [T, P]
    iter_at_k: jax.Array  # [T, P]
    chunks: jax.Array     # [T, 1] chunks actually processed (early-exit stat)


def _state_in(acc0, trans0, rec0, cnt0, start_iter, live):
    """[.., P, ...] state -> lane-dense kernel layout [.., rows, P]."""
    row = lambda x: jnp.expand_dims(x.astype(jnp.int32), -2)
    return (jnp.swapaxes(acc0.astype(jnp.float32), -1, -2),
            jnp.expand_dims(trans0.astype(jnp.float32), -2),
            jnp.swapaxes(rec0, -1, -2), row(cnt0), row(start_iter), row(live))


def _state_shapes(lead: tuple, k_record: int):
    f, i = jnp.float32, jnp.int32
    return (jax.ShapeDtypeStruct((*lead, 3, P), f),
            jax.ShapeDtypeStruct((*lead, 1, P), f),
            jax.ShapeDtypeStruct((*lead, k_record, P), i),
            *[jax.ShapeDtypeStruct((*lead, 1, P), i)] * 4)


def _state_out(outs, chunks) -> RasterState:
    acc, trans, rec, cnt, nsig, niter, itk = outs
    return RasterState(jnp.swapaxes(acc, -1, -2), trans[..., 0, :],
                       jnp.swapaxes(rec, -1, -2), cnt[..., 0, :],
                       nsig[..., 0, :], niter[..., 0, :], itk[..., 0, :],
                       chunks)


def _smem(block, index_map):
    return pl.BlockSpec(block, index_map, memory_space=pltpu.SMEM)


def rasterize_pallas(mean2d, conic, color, opacity, ids,
                     acc0, trans0, rec0, cnt0, start_iter, live,
                     *, tiles_x: int, interpret: bool, k_record: int = 5,
                     chunk: int = 64, stop_at_k: bool = False,
                     bg: float = 0.0, ncap=None,
                     body: str = 'dense') -> RasterState:
    """Invoke the kernel. Feature arrays are [T, K, ...]; K must be a
    multiple of ``chunk`` (ops.py pads).  State arrays are [T, P(=256), ...].

    ``ncap`` [T] int32 optionally caps the chunks each tile may walk (the
    chunk index of its last valid Gaussian); ``None`` means the full padded
    list.  Chunks past the cap hold only padding and cannot change any
    output, so the cap is a pure compute saving.  ``body`` picks the chunk
    backend flavor ('dense' scan vs 'seq' per-Gaussian FIFO, interpret
    mode only) — both implement the same contract; ops.py defaults by
    platform.  Background compositing happens once, in ops.py, after the
    final phase, so ``bg`` is unused here.
    """
    del bg
    t, k_total = ids.shape
    assert k_total % chunk == 0, (k_total, chunk)
    assert rec0.shape[-1] == k_record
    if ncap is None:
        ncap = jnp.full((t,), k_total // chunk, jnp.int32)
    plane = pack_plane(mean2d, conic, color, opacity, ids)
    kp = plane.shape[-1]

    tile = lambda *dims: pl.BlockSpec((None, *dims),
                                      lambda i: (i,) + (0,) * len(dims))
    state_specs = (tile(3, P), tile(1, P), tile(k_record, P), tile(1, P),
                   tile(1, P), tile(1, P))
    out_specs = (tile(3, P), tile(1, P), tile(k_record, P), tile(1, P),
                 tile(1, P), tile(1, P), tile(1, P),
                 _smem((None, 1, 1), lambda i: (i, 0, 0)))
    kern = functools.partial(_kernel, tiles_x=tiles_x, k_total=k_total,
                             k_record=k_record, chunk=chunk,
                             stop_at_k=stop_at_k, body=body)
    *outs, chunks = pl.pallas_call(
        kern, grid=(t,),
        in_specs=(_smem((None, 1, 1), lambda i: (i, 0, 0)), tile(NF, kp),
                  *state_specs),
        out_specs=out_specs,
        out_shape=(*_state_shapes((t,), k_record),
                   jax.ShapeDtypeStruct((t, 1, 1), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((_window(chunk), NF), jnp.float32)],
        interpret=interpret, name='_kernel_tiles',
    )(ncap.reshape(t, 1, 1).astype(jnp.int32), plane,
      *_state_in(acc0, trans0, rec0, cnt0, start_iter, live))
    return _state_out(outs, chunks.reshape(t, 1))


# ---------------------------------------------------------------------------
# Miss-compacted resume — the software analogue of LuminCore's PE remapping
# ---------------------------------------------------------------------------

def _kernel_compact(srcs_ref, nsrc_ref, plane_hbm, px_ref, py_ref, src_ref,
                    ncap_ref, acc0_ref, trans0_ref, rec0_ref, cnt0_ref,
                    start_ref, live_ref,
                    acc_ref, trans_ref, rec_ref, cnt_ref, nsig_ref,
                    niter_ref, itk_ref, chunks_ref,
                    wbuf_ref, tbuf_ref, sem,
                    *, k_total: int, k_record: int, chunk: int, body: str):
    """Resume integration for one *compacted* tile of P cache-miss pixels.

    Unlike ``_kernel``, the P pixels of a program do not share a source tile:
    each lane carries its own pixel center (``px``/``py``), its source tile
    id (``src``) and its per-pixel chunk cap.  The feature planes stay in
    HBM; for each chunk the kernel copies that chunk's window of every
    distinct source tile of the program (``srcs``, ``nsrc`` of them, listed
    by the wrapper) into VMEM and merges it into the lanes it feeds.  VMEM
    use is one window, whatever the frame size.  This is LuminCore's PE
    remapping in software: scattered miss pixels are regrouped into dense
    tiles so the chunk loop pays per *miss*, not per source tile.

    Per-pixel math is identical to ``_kernel``'s resume mode (no stop-at-k),
    so gather -> resume -> scatter reproduces the full-tile resume exactly.
    """
    nc_total = k_total // chunk
    w = _window(chunk)
    px = px_ref[...]                           # [1, P] f32 pixel centers
    py = py_ref[...]
    src = src_ref[...]                         # [1, P] int32 source tiles
    ncap = ncap_ref[...]                       # [1, P] per-pixel chunk cap
    live = live_ref[...] != 0
    start = start_ref[...]
    c0 = jnp.minimum(
        jnp.min(jnp.where(live, start, k_total)) // chunk, nc_total)

    def gather(c):
        def one_source(j, acc):
            s = srcs_ref[0, j]
            copy = pltpu.make_async_copy(
                plane_hbm.at[s, :, pl.ds(_window_start(c, chunk), w)],
                wbuf_ref, sem)
            copy.start()
            copy.wait()
            feats, gid = _chunk_columns(wbuf_ref[...], tbuf_ref, c, chunk)
            mine = src == s
            return (tuple(jnp.where(mine, f, a) for f, a in zip(feats, acc[0])),
                    jnp.where(mine, gid, acc[1]))

        zero = jnp.zeros((chunk, P), jnp.float32)
        return jax.lax.fori_loop(
            0, nsrc_ref[0, 0], one_source,
            ((zero,) * _ID_ROW, jnp.full((chunk, P), -1, jnp.int32)))

    def loop_body(carry):
        c, nchunks, inner = carry
        feats, gid = gather(c)
        inner = _chunk_update(feats, gid, px, py, c, start, live, k_record,
                              chunk, False, body, inner)
        return c + 1, nchunks + 1, inner

    def cond(carry):
        c, _, (acc, trans, *_) = carry
        # per-chunk early termination: a lane is done once dead, past its
        # transmittance floor, or past its source tile's last valid chunk
        remaining = live & (trans > TRANSMITTANCE_EPS) & (c < ncap)
        return (c < nc_total) & jnp.any(remaining)

    init = _init_carry(acc0_ref[...], trans0_ref[...], rec0_ref[...],
                       cnt0_ref[...], k_total)
    _, nchunks, state = jax.lax.while_loop(cond, loop_body,
                                           (c0, jnp.int32(0), init))
    _store_state((acc_ref, trans_ref, rec_ref, cnt_ref, nsig_ref, niter_ref,
                  itk_ref), state)
    chunks_ref[0, 0] = nchunks


def distinct_sources(src, live):
    """Per compacted tile, the distinct source tiles of its live lanes:
    ([CT, P] ids front-packed, [CT] counts).  Live lanes come first and
    source-major within a compacted tile (``ops.rasterize_resume_compacted``
    packs them so), so a new source starts wherever the id changes."""
    ct, p = src.shape
    prev = jnp.concatenate([jnp.full((ct, 1), -1, src.dtype), src[:, :-1]],
                           axis=1)
    first = live & (src != prev)
    rank = jnp.where(first, jnp.cumsum(first.astype(jnp.int32), axis=1) - 1, p)
    rows = jnp.broadcast_to(jnp.arange(ct)[:, None], (ct, p))
    srcs = jnp.zeros((ct, p), jnp.int32).at[rows, rank].set(src, mode='drop')
    return srcs, jnp.sum(first.astype(jnp.int32), axis=1)


def rasterize_compact_pallas(mean2d, conic, color, opacity, ids,
                             px, py, src, ncap,
                             acc0, trans0, rec0, cnt0, start_iter, live,
                             *, interpret: bool, k_record: int = 5,
                             chunk: int = 64,
                             body: str = 'dense') -> RasterState:
    """Invoke the miss-compacted resume kernel.

    Features are the *full* [T, K, ...] arrays (every program may read any
    source tile); ``px``/``py``/``src``/``ncap`` and the state arrays are
    compacted [CT, P(=256), ...] — CT compacted tiles whose lanes were packed
    miss-first, source-tile-major by ``ops.rasterize_resume_compacted``.
    """
    t, k_total = ids.shape
    assert k_total % chunk == 0, (k_total, chunk)
    ct = src.shape[0]
    assert rec0.shape[-1] == k_record
    src = src.astype(jnp.int32)
    srcs, nsrc = distinct_sources(src, live.astype(bool))
    plane = pack_plane(mean2d, conic, color, opacity, ids)

    lane = lambda *dims: pl.BlockSpec((None, *dims),
                                      lambda i: (i,) + (0,) * len(dims))
    row = lambda x: jnp.expand_dims(x, -2)
    kern = functools.partial(_kernel_compact, k_total=k_total,
                             k_record=k_record, chunk=chunk, body=body)
    *outs, chunks = pl.pallas_call(
        kern, grid=(ct,),
        in_specs=(_smem((None, 1, P), lambda i: (i, 0, 0)),
                  _smem((None, 1, 1), lambda i: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  lane(1, P), lane(1, P), lane(1, P), lane(1, P),
                  lane(3, P), lane(1, P), lane(k_record, P), lane(1, P),
                  lane(1, P), lane(1, P)),
        out_specs=(lane(3, P), lane(1, P), lane(k_record, P), lane(1, P),
                   lane(1, P), lane(1, P), lane(1, P),
                   _smem((None, 1, 1), lambda i: (i, 0, 0))),
        out_shape=(*_state_shapes((ct,), k_record),
                   jax.ShapeDtypeStruct((ct, 1, 1), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((NF, _window(chunk)), jnp.float32),
                        pltpu.VMEM((_window(chunk), NF), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret, name='_kernel_compact',
    )(row(srcs), nsrc.reshape(ct, 1, 1), plane,
      row(px.astype(jnp.float32)), row(py.astype(jnp.float32)), row(src),
      row(ncap.astype(jnp.int32)),
      *_state_in(acc0, trans0, rec0, cnt0, start_iter, live))
    return _state_out(outs, chunks.reshape(ct, 1))


# ---------------------------------------------------------------------------
# Slot-batched kernel — all serving slots' lanes of one tile per program
# ---------------------------------------------------------------------------

def _kernel_slots(ncap_ref, plane_ref, acc0_ref, trans0_ref, rec0_ref,
                  cnt0_ref, start_ref, live_ref,
                  acc_ref, trans_ref, rec_ref, cnt_ref, nsig_ref, niter_ref,
                  itk_ref, chunks_ref, tbuf_ref,
                  *, tiles_x: int, k_total: int, k_record: int, chunk: int,
                  stop_at_k: bool, body: str):
    """One grid program = one tile position ACROSS ALL S serving slots.

    Under ``vmap`` a pallas_call batches by growing the grid — S x T
    programs that interpret mode executes serially, so multi-viewer serving
    gained no vector width from batching while the pure-JAX reference
    amortized its whole batch per op.  Here the slot axis rides *inside*
    the block instead: refs are [S, ...], slot ``s`` owns lanes
    ``[s*P, (s+1)*P)`` of the [C, S*P] chunk bodies, and one program does
    the whole fleet's work for its tile.  The while-loop trip count couples
    slots (a tile iterates until every slot's lanes are done) — pure extra
    *skipped* work for finished slots, bit-identical outputs per lane.
    """
    t = pl.program_id(0)
    s = plane_ref.shape[0]
    n = s * P
    nc_total = k_total // chunk
    w = _window(chunk)
    px, py = _tile_pixels(t, tiles_x, n)

    def lanes(ref):          # [S, r, P] block -> [r, S*P]
        return jnp.concatenate([ref[i] for i in range(s)], axis=1)

    def spread(cols):        # S x [C, 1] columns -> [C, S*P]
        return jnp.concatenate(
            [jnp.broadcast_to(x, (x.shape[0], P)) for x in cols], axis=1)

    live = lanes(live_ref) != 0                # [1, N]
    start = lanes(start_ref)
    ncap = jnp.concatenate(
        [jnp.full((1, P), jnp.minimum(ncap_ref[0, i], nc_total), jnp.int32)
         for i in range(s)], axis=1)
    c0 = jnp.minimum(
        jnp.min(jnp.where(live, start, k_total)) // chunk, nc_total)

    def loop_body(carry):
        c, nchunks, inner = carry
        per_slot = [_chunk_columns(
            plane_ref[i, :, pl.ds(_window_start(c, chunk), w)],
            tbuf_ref, c, chunk) for i in range(s)]
        feats = tuple(spread([f[j] for f, _ in per_slot])
                      for j in range(_ID_ROW))
        gid = spread([g for _, g in per_slot])
        inner = _chunk_update(feats, gid, px, py, c, start, live, k_record,
                              chunk, stop_at_k, body, inner)
        return c + 1, nchunks + 1, inner

    def cond(carry):
        c, _, (acc, trans, rec, cnt, *_) = carry
        remaining = live & (trans > TRANSMITTANCE_EPS) & (c < ncap)
        if stop_at_k:
            remaining = remaining & (cnt < k_record)
        return (c < nc_total) & jnp.any(remaining)

    init = _init_carry(lanes(acc0_ref), lanes(trans0_ref), lanes(rec0_ref),
                       lanes(cnt0_ref), k_total)
    _, nchunks, state = jax.lax.while_loop(cond, loop_body,
                                           (c0, jnp.int32(0), init))
    for i in range(s):
        _store_state((acc_ref, trans_ref, rec_ref, cnt_ref, nsig_ref,
                      niter_ref, itk_ref), state, slot=i)
    chunks_ref[0, 0] = nchunks


def rasterize_slots_pallas(mean2d, conic, color, opacity, ids,
                           acc0, trans0, rec0, cnt0, start_iter, live,
                           *, tiles_x: int, interpret: bool,
                           k_record: int = 5, chunk: int = 64,
                           stop_at_k: bool = False, ncap=None,
                           body: str = 'dense'):
    """Slot-batched kernel invocation: features [S, T, K, ...], state
    [S, T, P, ...], ``ncap`` [S, T].  Grid is (T,) — each program handles
    one tile for every slot.  Returns a RasterState with [S, T, ...] leaves
    whose ``chunks`` is [T, 1] — the per-tile trip count, shared by all
    slots.
    """
    s, t, k_total = ids.shape
    assert k_total % chunk == 0, (k_total, chunk)
    assert rec0.shape[-1] == k_record
    if ncap is None:
        ncap = jnp.full((s, t), k_total // chunk, jnp.int32)
    plane = pack_plane(mean2d, conic, color, opacity, ids)
    kp = plane.shape[-1]

    sb = lambda *dims: pl.BlockSpec((s, None, *dims),
                                    lambda i: (0, i) + (0,) * len(dims))
    state_specs = (sb(3, P), sb(1, P), sb(k_record, P), sb(1, P), sb(1, P),
                   sb(1, P))
    kern = functools.partial(_kernel_slots, tiles_x=tiles_x, k_total=k_total,
                             k_record=k_record, chunk=chunk,
                             stop_at_k=stop_at_k, body=body)
    *outs, chunks = pl.pallas_call(
        kern, grid=(t,),
        in_specs=(_smem((None, 1, s), lambda i: (i, 0, 0)), sb(NF, kp),
                  *state_specs),
        out_specs=(*state_specs[:3], sb(1, P), sb(1, P), sb(1, P), sb(1, P),
                   _smem((None, 1, 1), lambda i: (i, 0, 0))),
        out_shape=(*_state_shapes((s, t), k_record),
                   jax.ShapeDtypeStruct((t, 1, 1), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((_window(chunk), NF), jnp.float32)],
        interpret=interpret, name='_kernel_slots',
    )(ncap.astype(jnp.int32).T.reshape(t, 1, s), plane,
      *_state_in(acc0, trans0, rec0, cnt0, start_iter, live))
    return _state_out(outs, chunks.reshape(t, 1))
