"""Quantized collectives — block-wise int8/int4 allreduce on the method
plane (EQuARX, PAPERS.md 2506.17615): ~4x fewer bytes on the wire.

Every collective session so far shipped full-width float32 rows across
the party axis: a width-W pmean moves W bytes per party per step.  On a
bandwidth-bound mesh that is the whole cost, and EQuARX's observation is
that gradients and activations tolerate block-wise quantization: split
the row into blocks of B float32s, keep one scale per block, ship int8
(or int4) values + scales, dequantize and reduce on arrival.  The wire
footprint drops to ``nfloats + nblocks`` bytes (int8) or
``nfloats/2 + nblocks`` (int4 packs two values per byte) — ~0.26x /
~0.13x of the exact row.

Design decisions, in the order they matter:

- **Scales are powers of two** (one int8 EXPONENT per block, not a
  float32 scale).  Three wins: (1) the scale itself costs 1 byte, not 4;
  (2) quantize and dequantize are EXACT arithmetic — multiplying by 2^e
  only moves the float exponent, so ``dequantize(quantize(v))`` round-
  trips to precisely the value the wire carried on every party, with no
  FP-order luck; (3) the round trip is IDEMPOTENT
  (``quantize(dequantize(q, e))`` dequantizes back to the identical
  bytes), which is what lets quantized CHECKPOINT rings resume
  byte-identically (parallel/mc_dispatch.py): the first replayed step
  re-quantizes the restored state to exactly what the undisturbed chain
  quantized.  The cost vs an optimal float scale is at most one extra
  bit of quantization error — bounded below.
- **Deterministic rounding** (round-half-to-even), never stochastic:
  every party must compute the identical program or the lockstep chain
  diverges — the collective plane's fingerprint contract extends into
  the arithmetic.
- **Block-aligned chunking**: the kernels are ``chunkable=True`` (an
  overlap session may split the row into sub-collectives) but a chunk
  boundary must fall on a block boundary, or the chunk would recompute
  scales from partial blocks and diverge from the full-width bytes.
  ``DeviceMethod.chunk_align = 4 * block`` enforces it at admission,
  pre-lockstep, like every other chunk-safety rule.

Error bound (documented in docs/DEVICE_PLANE.md and gated in
dryrun_multichip): per element, one quantized pmean step differs from
the exact mean by at most ``max_p amax_block(p) / qmax`` — each party's
per-block error is ≤ scale/2, and the power-of-two scale is < 2x the
optimal ``amax/qmax``.  int8 (qmax 127): ≤ ~0.8% of the block's peak
magnitude; int4 (qmax 7): ≤ ~14%.  A K-step chain compounds at most
K times the single-step bound (conservative: post-mean magnitudes only
shrink).  NaN/Inf rows are the caller's bug — the kernels assume finite
float32 data, exactly like the exact pmean.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

QUANT_MODES = ("none", "int8", "int4")
DEFAULT_BLOCK = 32  # float32 values per scale block
_QMAX = {"int8": 127, "int4": 7}

# exponent clamp: int8 storage, and 2^e / 2^-e both normal float32s (the
# scales are assembled from exponent bits, _np_pow2/_jq_pow2); a block of
# subnormal data clamps to _E_MIN and quantizes to zeros
_E_MIN, _E_MAX = -126, 126


def qmax_for(mode: str) -> int:
    return _QMAX[mode]


def supports(width: int, mode: str, block: int = DEFAULT_BLOCK) -> bool:
    """Whether a width-``width``-byte row quantizes in ``mode``: float32
    rows only, whole blocks only (a trailing partial block would need its
    own scale arithmetic and break chunk alignment), and int4 packs two
    values per byte so blocks must hold an even count."""
    if mode not in _QMAX or block <= 0:
        return False
    if width % 4 != 0:
        return False
    nfloats = width // 4
    if nfloats % block != 0:
        return False
    if mode == "int4" and block % 2 != 0:
        return False
    return True


def wire_bytes(width: int, mode: str, block: int = DEFAULT_BLOCK) -> int:
    """Bytes one party ships per step for a width-byte row: the quantized
    values plus one int8 exponent per block (the exact path ships
    ``width``).  Derived from the storage dtypes, not hand math."""
    if mode == "none":
        return int(width)
    if not supports(width, mode, block):
        raise ValueError(f"width {width} does not quantize as {mode}/{block}")
    nfloats = width // 4
    nblocks = nfloats // block
    vals = nfloats * np.dtype(np.int8).itemsize
    if mode == "int4":
        vals //= 2  # two 4-bit values packed per byte
    return vals + nblocks * np.dtype(np.int8).itemsize


# -- the numpy twin ------------------------------------------------------------
#
# Host-side mirror of the jax arithmetic below, used by checkpoint
# restore/reshard (parallel/mc_dispatch._restore_state dequantizes ring
# shards on the host) and by tests as the oracle.  The two twins agree
# BITWISE — the property the restore path depends on — so every
# operation in them is one every backend computes exactly: comparisons,
# frexp, integer arithmetic on the exponent, and multiplication by a
# power of two assembled from its exponent bits.  No division and no
# exp2(): XLA may lower x/c to x*(1/c) and approximates exp2 (measured on
# this jaxlib's CPU backend: exp2 of an integer is off for 221 of the
# 254 normal exponents, |x|/127 for ~4% of values).


def _np_pow2(e: np.ndarray) -> np.ndarray:
    """Exactly 2^e as float32, for integer e in [-126, 127]."""
    return ((e.astype(np.int32) + 127) << 23).astype(np.uint32).view(
        np.float32
    )


def np_block_exponents(xf: np.ndarray, mode: str, block: int) -> np.ndarray:
    """Per-block power-of-two scale exponents: the smallest e with
    ``amax <= qmax * 2^e``.  With amax = m·2^ex and qmax = mq·2^exq,
    both mantissas in [0.5, 1), that is ``ex - exq``, plus one when
    ``m > mq`` (an all-zero block has m = ex = 0 and gets ``-exq``)."""
    mq, exq = math.frexp(_QMAX[mode])
    m, ex = np.frexp(np.abs(xf.reshape(-1, block)).max(axis=1))
    e = ex - exq + (m > np.float32(mq))
    return np.clip(e, _E_MIN, _E_MAX).astype(np.int8)


def np_quantize(
    xf: np.ndarray, mode: str, block: int = DEFAULT_BLOCK
) -> Tuple[np.ndarray, np.ndarray]:
    """float32[nfloats] -> (wire values, int8 exponents).  int8 mode
    returns int8[nfloats]; int4 packs value pairs into uint8[nfloats/2]
    (low nibble first, offset-8 so [-7, 7] maps to [1, 15])."""
    xf = np.asarray(xf, dtype=np.float32).reshape(-1)
    qmax = _QMAX[mode]
    e = np_block_exponents(xf, mode, block)
    q = np.clip(
        np.round(xf.reshape(-1, block) * _np_pow2(-e)[:, None]), -qmax, qmax
    ).astype(np.int8)
    q = q.reshape(-1)
    if mode == "int4":
        u = (q.astype(np.int16) + 8).astype(np.uint8)
        q = (u[0::2] | (u[1::2] << 4)).astype(np.uint8)
    return q, e


def np_dequantize(
    q: np.ndarray, e: np.ndarray, mode: str, block: int = DEFAULT_BLOCK
) -> np.ndarray:
    """Inverse of :func:`np_quantize` — exact (power-of-two scaling)."""
    if mode == "int4":
        u = np.asarray(q, dtype=np.uint8)
        lo = (u & 0xF).astype(np.int16) - 8
        hi = (u >> 4).astype(np.int16) - 8
        q = np.stack([lo, hi], axis=1).reshape(-1).astype(np.int8)
    q = np.asarray(q, dtype=np.int8)
    scale = _np_pow2(np.asarray(e, dtype=np.int8))
    return (
        q.reshape(-1, block).astype(np.float32) * scale[:, None]
    ).reshape(-1)


def np_quantized_pmean(
    rows: List[np.ndarray], steps: int, mode: str, block: int = DEFAULT_BLOCK
) -> np.ndarray:
    """Host model of the K-step quantized pmean chain: each step every
    party quantizes its row, the dequantized contributions average, and
    every party continues from the mean.  Float summation order may
    differ from XLA's by an ulp — compare with a tolerance, not bytes
    (the byte-exactness claims live in the round-trip, not the sum)."""
    cur = [np.asarray(r, dtype=np.float32).reshape(-1) for r in rows]
    for _ in range(int(steps)):
        deq = [np_dequantize(*np_quantize(r, mode, block), mode, block)
               for r in cur]
        m = (np.sum(np.stack(deq), axis=0, dtype=np.float32)
             / np.float32(len(cur)))
        cur = [m.copy() for _ in cur]
    return cur[0]


def pmean_error_bound(
    rows: List[np.ndarray], steps: int, mode: str, block: int = DEFAULT_BLOCK
) -> float:
    """The documented worst-case |quantized - exact| for a K-step pmean
    chain of these operands: per step each party contributes ≤ scale/2 ≤
    amax_block/qmax of error to the mean, so one step is bounded by the
    max over parties of the per-block amax / qmax, and K steps compound
    ≤ K times that (magnitudes only shrink under pmean)."""
    qmax = _QMAX[mode]
    worst = 0.0
    for r in rows:
        xb = np.abs(np.asarray(r, dtype=np.float32).reshape(-1, block))
        worst = max(worst, float(xb.max()))
    return steps * worst / qmax


# -- the jax kernels -----------------------------------------------------------


def _jq_pow2(e):
    """jax twin of _np_pow2."""
    import jax
    import jax.numpy as jnp

    return jax.lax.bitcast_convert_type(
        (e.astype(jnp.int32) + 127) << 23, jnp.float32
    )


def _jq_quantize(xf, mode: str, block: int):
    """jax twin of np_quantize over a [rows, nfloats] float32 array:
    returns (wire values [rows, ...], exponents int8 [rows, nblocks])."""
    import jax.numpy as jnp

    qmax = _QMAX[mode]
    rows = xf.shape[0]
    xb = xf.reshape(rows, -1, block)
    mq, exq = math.frexp(qmax)
    m, ex = jnp.frexp(jnp.max(jnp.abs(xb), axis=-1))
    e = jnp.clip(
        ex - exq + (m > jnp.float32(mq)).astype(ex.dtype), _E_MIN, _E_MAX
    ).astype(jnp.int8)
    q = jnp.clip(
        jnp.round(xb * _jq_pow2(-e)[..., None]), -qmax, qmax
    ).astype(jnp.int8).reshape(rows, -1)
    if mode == "int4":
        u = (q.astype(jnp.int16) + 8).astype(jnp.uint8)
        q = (u[:, 0::2] | (u[:, 1::2] << 4)).astype(jnp.uint8)
    return q, e


def _jq_dequantize(q, e, mode: str, block: int):
    """jax twin of np_dequantize over [rows, ...] wire arrays."""
    import jax.numpy as jnp

    rows = q.shape[0]
    if mode == "int4":
        lo = (q & 0xF).astype(jnp.int16) - 8
        hi = (q >> 4).astype(jnp.int16) - 8
        q = jnp.stack([lo, hi], axis=-1).reshape(rows, -1).astype(jnp.int8)
    return (
        q.reshape(rows, -1, block).astype(jnp.float32)
        * _jq_pow2(e)[..., None]
    ).reshape(rows, -1)


def _make_quantized_pmean_kernel(mode: str, block: int):
    """Mint the quantized pmean kernel for one (mode, block): quantize
    the own row, all_gather the QUANTIZED representation over the party
    axis (this is where the wire bytes shrink — the gathered arrays are
    the int8/int4 values + int8 exponents, never the float32 row),
    dequantize every party's contribution and average.  The closure
    cells (mode, block) enter the DeviceMethod fingerprint, so two
    parametrizations can never silently alias."""

    def kernel(data, n, _mode=mode, _block=block):
        import jax
        import jax.numpy as jnp

        f = jax.lax.bitcast_convert_type(
            data.reshape(-1, 4), jnp.float32
        )[None, :]
        q, e = _jq_quantize(f, _mode, _block)
        # the wire crossing: per party, len(q[0]) + len(e[0]) bytes
        # instead of the width-byte float row
        gq = jax.lax.all_gather(q[0], "par")
        ge = jax.lax.all_gather(e[0], "par")
        v = _jq_dequantize(gq, ge, _mode, _block)
        nparties = jax.lax.psum(1, "par")
        m = jnp.sum(v, axis=0) / jnp.float32(nparties)
        return jax.lax.bitcast_convert_type(m, jnp.uint8).reshape(-1), n

    return kernel


_variant_cache: Dict[tuple, "object"] = {}
_variant_lock = threading.Lock()


def quantized_pmean_dm(
    width: int, mode: str, block: int = DEFAULT_BLOCK
):
    """The quantized pmean DeviceMethod for one (width, mode, block) —
    cached so every resolution in this process hands back the same
    object (and therefore the same fingerprint the peers computed from
    the identical factory)."""
    from incubator_brpc_tpu.rpc.device_method import DeviceMethod

    if not supports(width, mode, block):
        return None
    key = (int(width), mode, int(block))
    with _variant_lock:
        dm = _variant_cache.get(key)
        if dm is None:
            dm = DeviceMethod(
                _make_quantized_pmean_kernel(mode, block),
                width=width,
                chunkable=True,
            )
            dm.quant_mode = mode
            dm.quant_block = int(block)
            dm.chunk_align = 4 * int(block)
            dm.collective_bytes = wire_bytes(width, mode, block)
            _variant_cache[key] = dm
        return dm


def attach_pmean_variants(dm, width: int, block: int = DEFAULT_BLOCK):
    """Hang the int8/int4 pmean variants off an exact pmean DeviceMethod
    (parallel/mc_collective mints one per width): the session plane's
    ``quantize=`` knob resolves through ``DeviceMethod.quantized``, and a
    width that doesn't block-align simply gets no variant — the knob
    then rejects cleanly pre-lockstep."""
    for mode in ("int8", "int4"):
        if supports(width, mode, block):
            var = quantized_pmean_dm(width, mode, block)
            if var is not None:
                dm.quant_variants[mode] = var
    return dm
