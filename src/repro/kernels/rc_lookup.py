"""Pallas TPU radiance-cache lookup kernel — LuminCache, re-expressed for TPU.

The paper's LuminCache is an SRAM set-associative cache probed with a
concatenated-Gaussian-ID index (Fig. 16).  TPUs expose no hardware cache and
vector gathers from VMEM are weak, but they have an MXU — so the tag probe
becomes a **one-hot matmul**:

    onehot[s, b] = (set_index(query b) == s)          # [S, Bc] f32
    probed       = payload^T @ onehot                  # [W*k | W*3, Bc]

two GEMMs gather every way's tags and values for the whole query chunk
(exact for int payloads < 2^24 in f32).  Tag compare + way select are then
dense VPU ops.  The grid is (groups, query-chunks); each group's full cache
payload (tags+values, ~128 KB at paper sizes) is VMEM-resident for all its
query chunks — the analogue of LuminCache's per-tile-group double buffering.

Updates (insert/pseudo-LRU) stay in `repro.core.radiance_cache`: they run
once per frame on miss pixels only and are scatter-bound, not lookup-bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import radiance_cache as rc


_MIX_CONSTS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1)


def _mix_index(rows, n_sets: int, k: int):
    """Same multiplicative hash as radiance_cache.set_index (mode='hash'),
    over the k id rows of a query block.

    Computed on int32 bit patterns: wrapping multiplies and xors are the
    same bits as the reference's uint32 ones, the fold is a logical shift,
    and a power-of-two set count reduces with a mask.  Constants are
    inlined as scalars: Pallas kernels may not close over array-valued
    constants.
    """
    def mul(x, c):   # uint32 constant as the int32 with the same bits
        return x * jnp.int32(c - (1 << 32) if c >= 1 << 31 else c)

    h = mul(rows[0] + 3, _MIX_CONSTS[0])
    for i in range(1, k):
        m = mul(rows[i] + 3, _MIX_CONSTS[i % len(_MIX_CONSTS)])
        h = mul(h ^ m, 0x9E3779B1)
    h = h ^ jax.lax.shift_right_logical(h, jnp.int32(15))
    if n_sets & (n_sets - 1) == 0:
        return h & (n_sets - 1)
    return (h.astype(jnp.uint32) % jnp.uint32(n_sets)).astype(jnp.int32)


def _kernel(tags_ref, values_ref, ids_ref,
            hit_ref, val_ref, sidx_ref, way_ref,
            *, n_sets: int, n_ways: int, k: int, index_mode: str,
            index_bits_shift: int):
    """Lane-dense probe: queries ride the lanes, so every per-query value
    is a [1, Bc] row and the record is [k, Bc]."""
    ids = ids_ref[...]                       # [k, Bc] int32
    bc = ids.shape[1]
    rows = [ids[i:i + 1] for i in range(k)]

    if index_mode == 'hash':
        sidx = _mix_index(rows, n_sets, k)   # [1, Bc]
    else:  # 'bitconcat' — LuminCache Fig. 16 indexing
        bits_total = n_sets.bit_length() - 1
        per_id = max(1, bits_total // k)
        mask = (1 << per_id) - 1
        acc = jnp.zeros((1, bc), jnp.int32)
        for i in range(k):
            acc = acc + ((rows[i] >> index_bits_shift) & mask) * (
                1 << (per_id * i))
        sidx = jnp.abs(acc) % n_sets

    # one-hot probe: [S, Bc] f32.  HIGHEST precision keeps the gather exact
    # (tags are ints < 2^24; each column selects one row)
    sets = jax.lax.broadcasted_iota(jnp.int32, (n_sets, bc), 0)
    onehot = (sets == sidx).astype(jnp.float32)

    def probe(payload):                       # [S, n] -> [n, Bc]
        return jax.lax.dot_general(
            payload, onehot, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    ptags = probe(tags_ref[...].astype(jnp.float32))      # [W*k, Bc]
    pvals = probe(values_ref[...])                        # [W*3, Bc]

    # first matching way (the oracle's argmax over the way axis)
    hit = jnp.zeros((1, bc), bool)
    way = jnp.zeros((1, bc), jnp.int32)
    for w in range(n_ways):
        match = ptags[w * k:w * k + 1] == rows[0].astype(jnp.float32)
        for i in range(1, k):
            match = match & (ptags[w * k + i:w * k + i + 1]
                             == rows[i].astype(jnp.float32))
        way = jnp.where(match & ~hit, w, way)
        hit = hit | match
    value = []
    for ch in range(3):
        v = jnp.zeros((1, bc), jnp.float32)
        for w in range(n_ways):
            v = jnp.where(way == w, pvals[w * 3 + ch:w * 3 + ch + 1], v)
        value.append(v)

    hit_ref[...] = hit.astype(jnp.int32)
    val_ref[...] = jnp.concatenate(value, axis=0)
    sidx_ref[...] = sidx
    way_ref[...] = way


def rc_lookup_pallas(tags: jax.Array, values: jax.Array, ids: jax.Array,
                     cfg: rc.CacheConfig, *, interpret: bool,
                     query_chunk: int = 512):
    """tags [G,S,W,k] i32, values [G,S,W,3] f32, ids [G,B,k] i32 ->
    (hit [G,B] bool, value [G,B,3] f32, set_idx [G,B] i32, way [G,B] i32).

    On the chip ``query_chunk`` must be a multiple of 128 or all of B."""
    g, s, w, k = tags.shape
    b = ids.shape[1]
    assert b % query_chunk == 0, (b, query_chunk)
    nq = b // query_chunk

    kern = functools.partial(
        _kernel, n_sets=s, n_ways=w, k=k, index_mode=cfg.index_mode,
        index_bits_shift=cfg.index_bits_shift)
    group = lambda *dims: pl.BlockSpec((None, *dims),
                                       lambda gi, qi: (gi,) + (0,) * len(dims))
    query = lambda rows: pl.BlockSpec((None, rows, query_chunk),
                                      lambda gi, qi: (gi, 0, qi))
    hit, val, sidx, way = pl.pallas_call(
        kern, grid=(g, nq),
        in_specs=(group(s, w * k), group(s, w * 3), query(k)),
        out_specs=(query(1), query(3), query(1), query(1)),
        out_shape=(
            jax.ShapeDtypeStruct((g, 1, b), jnp.int32),
            jax.ShapeDtypeStruct((g, 3, b), jnp.float32),
            jax.ShapeDtypeStruct((g, 1, b), jnp.int32),
            jax.ShapeDtypeStruct((g, 1, b), jnp.int32),
        ),
        interpret=interpret, name='rc_lookup',
    )(tags.reshape(g, s, w * k), values.reshape(g, s, w * 3),
      jnp.swapaxes(ids, 1, 2))
    return (hit[:, 0] != 0, jnp.swapaxes(val, 1, 2), sidx[:, 0], way[:, 0])
