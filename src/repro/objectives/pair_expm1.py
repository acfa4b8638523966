"""float64 ``expm1`` computed from pairs of float32 words.

A TPU v5e has no float64 unit: XLA:TPU emulates every float64 op as a
pair of float32s, and its generic float64 ``expm1`` spends about 1,100
ops an element on that, special-value checks included. Griewank's probe
takes ``expm1`` of every candidate's float64 log-product, so in the
float64-aggregate mode that one function was half of a solve. This
routine computes the same value from ``(hi, lo)`` float32 words
("double-float" arithmetic), written for that input:

1. ``x`` is split exactly into ``hi + lo`` and clamped below at -64,
   where ``expm1`` is -1 to far below float64's rounding.
2. ``x = k·ln2/8 + r`` with ``|r| <= ln2/16``: ``k·ln2/8`` is subtracted
   in three float32 parts, the first two short enough that ``k`` times
   them is exact.
3. ``expm1(r)`` is a degree-8 Taylor polynomial in Horner form: the
   coefficients from 1/5! on in float32, the steps from 1/4! down in
   pairs.
4. With ``k = 8·kk + j`` and ``-4 <= j <= 3``, ``expm1(x) =
   2^kk·(U_j + (1 + U_j)·expm1(r)) + (2^kk − 1)``, where ``U_j = 2^(j/8)
   − 1`` comes from an eight-entry pair table picked by selects, and
   ``2^kk`` is built from its exponent bits, so the scaling is exact. A
   signed ``j`` keeps ``kk = 0`` for ``|x| < 0.3``, where a ``2^kk − 1``
   near -1 would cancel against a ``2^kk·U_j`` near 1.

Against numpy's float64 ``expm1`` the worst relative error is below
2^-45 on ``[-1e6, 1]`` (``tests/test_pair_expm1.py``). ``|x| < 2^-60``
returns ``x``, which is float64's own ``expm1`` there, and NaN stays
NaN. Above ~8 the error grows as ``x·2^-49``, the 48 bits the pair keeps
of ``x``, and above log(float32 max) ~ 88.7 the result is not finite;
Griewank's log-product is never positive.

Two things would silently drop the result to float32 precision:

- Constants that the compiler can see. XLA's algebraic simplifier folds
  them through error-free sums (``(c + a) - c`` becomes ``a``), so every
  constant enters through ``optimization_barrier``.
- Contraction. XLA:CPU fuses a product into an add that uses it, even
  one use among several, and may compute a value once per fusion that
  reads it, contracted in one and not in another: ``s = c + a·b`` and
  the ``a·b`` of its error term then round apart. Every rounded product
  therefore gets ``z`` added, a zero computed from the input (NaN for a
  NaN input, which the result is anyway), which no compiler can fold:
  a fused ``a·b + z`` rounds as ``a·b`` does, and nothing contracts an
  add. Exact products (a power-of-two scale, ``k·c1``, Dekker's partial
  products) need no such guard. The bits are then those of the IEEE
  float32 operations as written, in every program that calls this.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_LOWER = -64.0        # expm1(-64) = -1 + 1.6e-28: -1 in float64
_UPPER = 89.0         # past log(float32 max): 2^kk overflows to inf
_TINY = 2.0 ** -60    # expm1(x) == x in float64 below this


def _cut(v: float, bits: int) -> float:
    """``v`` rounded to ``bits`` significant bits."""
    e = np.floor(np.log2(abs(v))) - (bits - 1)
    return float(np.round(v / 2.0 ** e) * 2.0 ** e)


def _f32_pair(v: float) -> tuple[float, float]:
    hi = float(np.float32(v))
    return hi, float(np.float32(v - hi))


def _constants() -> tuple[float, ...]:
    step = np.log(2.0) / 8
    c1 = _cut(step, 13)                   # |k| < 2^11, so k·c1 is exact
    c2 = _cut(step - c1, 13)
    c3 = float(np.float32(step - c1 - c2))
    inv_fact = [_f32_pair(1 / np.prod(np.arange(1.0, i + 1)))
                for i in range(3, 9)]     # 1/3! .. 1/8!
    u = [_f32_pair(2.0 ** (j / 8) - 1) for j in range(-4, 4)]
    return (1.0, 4096.0, 8 / np.log(2.0), c1, c2, c3,
            *(w for p in inv_fact for w in p), *(w for p in u for w in p))


_CONSTS = _constants()


def _two_sum(a, b):
    """Exact ``a + b`` as a pair."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _fast_two_sum(a, b):
    """Exact ``a + b`` as a pair when ``|a| >= |b|``."""
    s = a + b
    return s, b - (s - a)


def _split(a, c4096):
    """``a`` as two float32s of at most 12 significant bits each
    (Veltkamp's split); ``4097·a`` is formed as ``a + 4096·a``, whose
    product is exact."""
    t = a + a * c4096
    hi = t - (t - a)
    return hi, a - hi


def _mul(a, b, c4096, z, a_split=None):
    """Pair times pair (Dekker), to a pair whose low word is not
    renormalized."""
    (ah, al), (bh, bl) = a, b
    p = ah * bh + z
    a1, a2 = a_split or _split(ah, c4096)
    b1, b2 = _split(bh, c4096)
    e = ((a1 * b1 - p) + a1 * b2 + a2 * b1) + a2 * b2
    return _fast_two_sum(p, e + ((ah * bl + z) + (al * bh + z)))


def _add(a, b):
    """Pair plus pair, the low words summed error-free too."""
    s, e = _two_sum(a[0], b[0])
    t, f = _two_sum(a[1], b[1])
    s, e = _fast_two_sum(s, e + t)
    return _fast_two_sum(s, e + f)


def expm1_f64(x: jnp.ndarray) -> jnp.ndarray:
    """``expm1`` of a float64 array, computed in float32 pairs."""
    f32 = jnp.float32
    one, c4096, inv_step, c1, c2, c3, *rest = jax.lax.optimization_barrier(
        tuple(jnp.asarray(c, f32) for c in _CONSTS))
    inv_fact = [tuple(rest[i:i + 2]) for i in range(0, 12, 2)]
    u_table = [tuple(rest[i:i + 2]) for i in range(12, 28, 2)]

    hi = x.astype(f32)
    lo = (x - hi.astype(x.dtype)).astype(f32)
    lo = jnp.where(hi < _LOWER, jnp.zeros_like(lo), lo)
    hi = jnp.clip(hi, _LOWER, _UPPER)
    z = hi - hi

    kf = jnp.round(hi * inv_step)
    k = kf.astype(jnp.int32)
    # r = x - k·ln2/8: k·c1 and k·c2 are exact, and so is hi - k·c1
    b = _two_sum(hi - kf * c1, lo)
    c = _two_sum(b[0], -(kf * c2))
    r = _fast_two_sum(c[0], (c[1] + b[1]) - (kf * c3 + z))

    # expm1(r) = r + r·r·(1/2 + r·(1/3! + r·(1/4! + r·tail))), where
    # tail = 1/5! + r/6! + r²/7! + r³/8! in float32
    r_split = _split(r[0], c4096)
    tail = inv_fact[5][0]
    for i in (4, 3, 2):
        tail = inv_fact[i][0] + (r[0] * tail + z)
    a4 = _add(inv_fact[1], _mul(r, (tail, z), c4096, z, r_split))
    a3 = _add(inv_fact[0], _mul(r, a4, c4096, z, r_split))
    p = _mul(r, a3, c4096, z, r_split)
    a2 = _fast_two_sum(0.5 * one, p[0])
    a2 = _fast_two_sum(a2[0], a2[1] + p[1])
    rr = _mul(r, a2, c4096, z, r_split)
    e = _add(r, _mul(r, rr, c4096, z, r_split))

    # k = 8·kk + j: expm1(x) = 2^kk·(U_j + (1 + U_j)·e) + (2^kk - 1)
    row = k + 4                        # j + 4, the table row, is row & 7
    u = u_table
    for bit in (1, 2, 4):
        take = (row & bit) != 0
        u = [(jnp.where(take, odd[0], even[0]),
              jnp.where(take, odd[1], even[1]))
             for even, odd in zip(u[0::2], u[1::2])]
    u = u[0]
    t = _fast_two_sum(one, u[0])
    w = _add(u, _mul(e, (t[0], t[1] + u[1]), c4096, z))
    scale = jax.lax.bitcast_convert_type(((row >> 3) + 127) << 23, f32)
    s_minus_1 = _two_sum(scale, -one)
    y = _two_sum(scale * w[0], s_minus_1[0])
    out = _fast_two_sum(y[0], y[1] + (scale * w[1] + s_minus_1[1]))
    res = out[0].astype(x.dtype) + out[1].astype(x.dtype)
    return jnp.where(jnp.abs(hi) < _TINY, x, res)
