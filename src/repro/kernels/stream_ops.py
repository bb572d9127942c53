"""Changelog-stream routing kernels (JAX / Pallas path).

The cluster's routing hot spot is a splitmix64 mix over three decoded
FID header columns (``cluster.fid_slots``).  NumPy computes it with
native wrapping uint64 arithmetic; this module provides the *identical*
mix as a jitted JAX kernel for deployments that keep the routing
columns on an accelerator (the coordinator co-located with the
training job's host program).

JAX disables 64-bit integers unless ``jax_enable_x64`` is set — which
the training side must not flip globally — so the mix runs on
``(hi, lo)`` uint32 *pairs*: 64-bit multiplies are composed from
16x16->32 partial products, shifts and xors act lane-wise on the pair.
Only the low 64 bits of each product are needed, which keeps the limb
algebra to one full 32x32 product plus two wrapping cross terms.

``fid_slots`` is the host-callable wrapper (numpy in, numpy out).
``fid_slots_pallas`` routes the same mix through a tiled ``pallas_call``
elementwise kernel — the fusion-friendly form for TPU deployments;
``interpret=True`` runs its body in Python off the TPU.  The device
twin is used by ``cluster.batch_slots`` under ``REPRO_JAX_ROUTING=1``;
the numpy path stays the default on CPU hosts.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

_C1 = 0xBF58476D1CE4E5B9
_C2 = 0x94D049BB133111EB
_MIX = 0x9E3779B97F4A7C15          # splitmix64 increment (golden ratio)

_LO16 = np.uint32(0xFFFF)  # numpy scalar: weak constant inside pallas kernels
_MAX_SLOTS = 1 << 16               # keeps the modulus inside uint32


def _split(c):
    return np.uint32(c >> 32), np.uint32(c & 0xFFFFFFFF)


def _mul32(a, b):
    """Full 32x32->64 product of two uint32 lanes, as a (hi, lo) pair."""
    a0, a1 = a & _LO16, a >> 16
    b0, b1 = b & _LO16, b >> 16
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    mid = (p00 >> 16) + (p01 & _LO16) + (p10 & _LO16)
    lo = (p00 & _LO16) | (mid << 16)
    hi = a1 * b1 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    return hi, lo


def _mul64(zh, zl, ch, cl):
    """Low 64 bits of ``(zh:zl) * (ch:cl)``, as a (hi, lo) pair: the
    cross terms only touch the high lane, wrapping in uint32."""
    hi, lo = _mul32(zl, cl)
    return hi + zl * ch + zh * cl, lo


def _shr64(zh, zl, k):
    """``(zh:zl) >> k`` for 0 < k < 32."""
    return zh >> k, (zl >> k) | (zh << (32 - k))


def _mix64(zh, zl, n_slots):
    """The splitmix64 finalizer + slot modulus on uint32 pairs."""
    for k, c in ((30, _C1), (27, _C2)):
        sh, sl = _shr64(zh, zl, k)
        zh, zl = zh ^ sh, zl ^ sl
        zh, zl = _mul64(zh, zl, *_split(c))
    sh, sl = _shr64(zh, zl, 31)
    zh, zl = zh ^ sh, zl ^ sl
    n = np.uint32(n_slots)
    # (hi:lo) mod n == (hi mod n) * (2^32 mod n) + (lo mod n), all of
    # which stay below 2^32 while n_slots < 2^16
    return ((zh % n) * np.uint32((1 << 32) % n_slots) + zl % n) % n


def _seed64(seq_hi, seq_lo, oid, ver):
    """seq*C1 ^ oid*C2 ^ ver*MIX on uint32 pairs."""
    zero = jnp.zeros_like(oid)
    zh, zl = _mul64(seq_hi, seq_lo, *_split(_C1))
    th, tl = _mul64(zero, oid, *_split(_C2))
    zh, zl = zh ^ th, zl ^ tl
    th, tl = _mul64(zero, ver, *_split(_MIX))
    return zh ^ th, zl ^ tl


@functools.partial(jax.jit, static_argnames=("n_slots",))
def _fid_slots_jit(seq_hi, seq_lo, oid, ver, n_slots):
    with jax.named_scope("fid_slots"):
        zh, zl = _seed64(seq_hi, seq_lo, oid, ver)
        return _mix64(zh, zl, n_slots)


#: smallest length ``fid_slots`` pads its columns to; above it a call
#: pads to the next power of two, so every length up to 2^k shares one
#: of k - 6 compiled programs
_MIN_BUCKET = 128


def _bucket(n: int) -> int:
    """The padded length of an ``n``-record call."""
    return max(_MIN_BUCKET, 1 << max(n - 1, 0).bit_length())


def _as_pairs(seq, oid, ver, size: int = 0) -> np.ndarray:
    """The four uint32 columns (seq hi, seq lo, oid, ver) as the rows of
    one array, zero-padded to ``size`` rows where that is longer."""
    seq = np.asarray(seq, dtype=np.uint64)
    n = len(seq)
    cols = np.zeros((4, max(n, size)), np.uint32)
    cols[0, :n] = seq >> np.uint64(32)
    cols[1, :n] = seq
    cols[2, :n] = oid
    cols[3, :n] = ver
    return cols


def fid_slots(seq, oid, ver, n_slots: int = 64) -> np.ndarray:
    """JAX twin of ``cluster.fid_slots``: same columns in, same slots
    out (int64 numpy array).  The columns go to the device zero-padded
    to a bucket length (``_bucket``) and the pad rows' slots are
    dropped on the host, so lengths 1..1024 share four programs."""
    if not 0 < n_slots < _MAX_SLOTS:
        raise ValueError(f"n_slots must be in (0, {_MAX_SLOTS})")
    n = len(seq)
    out = _fid_slots_jit(*_as_pairs(seq, oid, ver, _bucket(n)),
                         n_slots=int(n_slots))
    return np.asarray(out)[:n].astype(np.int64)


# -- Pallas form -----------------------------------------------------------
#: the kernel sees the columns as (rows, 128) lane-dense tiles; one grid
#: step covers _TILE_ROWS rows (32768 records, 640 KiB of VMEM for the
#: four inputs and the output), so any record count compiles
_LANES = 128
_TILE_ROWS = 256


def _slots_kernel(seq_hi_ref, seq_lo_ref, oid_ref, ver_ref, out_ref,
                  *, n_slots):
    zh, zl = _seed64(seq_hi_ref[...], seq_lo_ref[...], oid_ref[...],
                     ver_ref[...])
    out_ref[...] = _mix64(zh, zl, n_slots)


@functools.partial(jax.jit, static_argnames=("n_slots", "interpret"))
def _fid_slots_tiled(seq_hi, seq_lo, oid, ver, n_slots, interpret=False):
    """The mix over 1-D uint32 columns as a 1-D grid of row tiles; the
    columns are zero-padded to whole tiles and the pad rows dropped."""
    from jax.experimental import pallas as pl

    n = seq_lo.shape[0]
    rows = -(-max(n, 1) // _LANES)
    rows = -(-rows // 8) * 8                     # whole (8, 128) vreg tiles
    tile = min(_TILE_ROWS, rows)
    rows = -(-rows // tile) * tile
    cols = [jnp.pad(c, (0, rows * _LANES - n)).reshape(rows, _LANES)
            for c in (seq_hi, seq_lo, oid, ver)]
    spec = pl.BlockSpec((tile, _LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_slots_kernel, n_slots=n_slots),
        grid=(rows // tile,),
        in_specs=[spec] * 4,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.uint32),
        interpret=interpret,
    )(*cols)
    return out.reshape(-1)[:n]


def fid_slots_pallas(seq, oid, ver, n_slots: int = 64,
                     interpret: bool = False) -> np.ndarray:
    """The same mix as a tiled elementwise ``pallas_call``: one VPU
    pass over the routing columns on the TPU.  ``interpret=True`` runs
    the kernel body in Python (the equivalence tests off the TPU)."""
    if not 0 < n_slots < _MAX_SLOTS:
        raise ValueError(f"n_slots must be in (0, {_MAX_SLOTS})")
    out = _fid_slots_tiled(*_as_pairs(seq, oid, ver), n_slots=int(n_slots),
                           interpret=interpret)
    return np.asarray(out).astype(np.int64)
