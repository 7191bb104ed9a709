"""Log-likelihood ratio kernels.

The reference implements Dunning's LLR as ``2*(row + col - matrix)`` unnormalized
entropies with 9 ``x*log(x)`` calls and a clamp of round-off negatives to zero
(reference: ``LogLikelihood.java:41-57``). That form is numerically fine in
float64 but catastrophically cancels in float32 once counts reach ~1e9 (the
entropy terms grow like ``N*log(N)`` ~ 1e12 while the LLR itself is O(100)).

For the TPU path we therefore use the algebraically identical
mutual-information form

    LLR = 2 * sum_ij k_ij * log(k_ij * N / (r_i * c_j))

and substitute ``k_ij*N - r_i*c_j = +/-D`` with ``D = k11*k22 - k12*k21``,
giving four ``k * log1p(+/-D / (r*c))`` terms. Each term is O(k * log-ratio)
with no large cancellation, so float32 keeps absolute error ~1e-4 even at
``N ~ 3e10`` (validated in ``tests/test_llr.py`` against the float64 oracle).

Both forms satisfy the reference's golden test vectors from Dunning's paper
(270.72, 263.90, 48.94 — ``LogLikelihoodTest.java:13-16``).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# NumPy float64 oracle (entropy form, mirrors the reference's math exactly)
# ---------------------------------------------------------------------------

def xlogx_np(x: np.ndarray) -> np.ndarray:
    """``x*log(x)`` with ``0*log(0) = 0`` (reference: ``LogLikelihood.java:59-61``)."""
    x = np.asarray(x, dtype=np.float64)
    out = np.zeros_like(x)
    nz = x > 0
    out[nz] = x[nz] * np.log(x[nz])
    return out


def llr_np(k11, k12, k21, k22) -> np.ndarray:
    """Float64 entropy-form LLR with the reference's round-off clamp.

    Vectorized over broadcastable inputs. Reference: ``LogLikelihood.java:41-57``
    (the 9-log variant: ``all`` is computed once and reused).
    """
    k11 = np.asarray(k11, dtype=np.float64)
    k12 = np.asarray(k12, dtype=np.float64)
    k21 = np.asarray(k21, dtype=np.float64)
    k22 = np.asarray(k22, dtype=np.float64)

    row1 = k11 + k12
    row2 = k21 + k22
    all_ = xlogx_np(row1 + row2)
    row = all_ - xlogx_np(row1) - xlogx_np(row2)
    col = all_ - xlogx_np(k11 + k21) - xlogx_np(k12 + k22)
    matrix = all_ - xlogx_np(k11) - xlogx_np(k12) - xlogx_np(k21) - xlogx_np(k22)

    out = 2.0 * (row + col - matrix)
    # Round-off clamp (reference: LogLikelihood.java:51-53).
    return np.where(row + col < matrix, 0.0, out)


# ---------------------------------------------------------------------------
# JAX kernels
# ---------------------------------------------------------------------------

def _xlogx(x):
    return jnp.where(x > 0, x * jnp.log(jnp.where(x > 0, x, 1.0)), 0.0)


def llr_entropy(k11, k12, k21, k22):
    """Entropy-form LLR (reference formula verbatim). Use only in >= float64.

    Kept for CPU-backend parity testing; the device default is
    :func:`llr_stable`.
    """
    row1 = k11 + k12
    row2 = k21 + k22
    all_ = _xlogx(row1 + row2)
    row = all_ - _xlogx(row1) - _xlogx(row2)
    col = all_ - _xlogx(k11 + k21) - _xlogx(k12 + k22)
    matrix = all_ - _xlogx(k11) - _xlogx(k12) - _xlogx(k21) - _xlogx(k22)
    return jnp.where(row + col < matrix, 0.0, 2.0 * (row + col - matrix))


#: ln 2 split so that ``e * _LN2_HI`` is exact for any float32 exponent
#: (fdlibm's ``ln2_hi``/``ln2_lo``).
_LN2_HI = 0.693145751953125
_LN2_LO = 1.428606765330187e-06


def _two_atanh(s):
    """``2 * atanh(s)`` for ``|s| <= 0.18`` by its odd series (terms past
    ``s^15`` are below float32 resolution there)."""
    z = s * s
    p = 1.0 / 15
    for c in (1.0 / 13, 1.0 / 11, 1.0 / 9, 1.0 / 7, 1.0 / 5, 1.0 / 3, 1.0):
        p = c + z * p
    return 2.0 * s * p


def log1p_f32(x):
    """``log1p`` from IEEE-exact float32 arithmetic alone.

    TPU's ``log``/``log1p`` are approximations: measured on v5e (PR 21)
    at up to 2.6e-4 / 3.7e-4 relative error, while its multiply, add and
    divide are correctly rounded. The LLR's four ``k * log1p(...)`` terms
    cancel, which turned that into 1e-4..1e-2 relative score error on
    chip. Here: ``|x| < 0.25`` uses ``2 atanh(x / (2 + x))`` directly on
    ``x`` (no rounding of ``1 + x``); otherwise ``1 + x = m * 2^e`` with
    ``m`` in ``[sqrt(1/2), sqrt(2))`` and ``log = e ln2 + 2 atanh((m-1)/
    (m+1))``. The branch selects the quotient's numerator and denominator,
    so each element runs one division and one series (the LLR calls this
    four times a cell; see PERF.md §5). Same code on every backend and
    inside Pallas kernels.
    """
    from jax import lax

    u = 1.0 + x
    bits = lax.bitcast_convert_type(u, jnp.int32)
    e = (bits >> 23) - 127
    m = lax.bitcast_convert_type((bits & 0x007FFFFF) | 0x3F800000,
                                 jnp.float32)                # [1, 2)
    high = m > 1.4142135
    m = jnp.where(high, m * 0.5, m)
    ef = jnp.where(high, e + 1, e).astype(jnp.float32)
    small = jnp.abs(x) < 0.25
    s = _two_atanh(jnp.where(small, x, m - 1.0)
                   / jnp.where(small, 2.0 + x, m + 1.0))
    log_u = jnp.where(u > 0, ef * _LN2_HI + (s + ef * _LN2_LO), -jnp.inf)
    return jnp.where(small, s, log_u)


def _split(a):
    """Veltkamp split: ``a == hi + lo`` with 12-bit halves, so their
    products are exact in float32."""
    c = a * 4097.0
    hi = c - (c - a)
    return hi, a - hi


def _det_f32(a, d, b, c):
    """``a*d - b*c`` without cancellation (Dekker's exact products):
    the plain form loses up to 1e-3 relative where the two products
    nearly cancel -- exactly the near-independent cells."""
    p1 = a * d
    p2 = b * c
    ah, al = _split(a)
    dh, dl = _split(d)
    bh, bl = _split(b)
    ch, cl = _split(c)
    e1 = ((ah * dh - p1) + ah * dl + al * dh) + al * dl
    e2 = ((bh * ch - p2) + bh * cl + bl * ch) + bl * cl
    return (p1 - p2) + (e1 - e2)


def llr_stable(k11, k12, k21, k22):
    """Float32-stable LLR via the mutual-information / log1p form.

    ``k_ij*N - r_i*c_j`` equals ``+D`` for the (1,1) and (2,2) cells and
    ``-D`` for (1,2) and (2,1), with ``D = k11*k22 - k12*k21``; each term is
    ``k * log1p(+/-D/(r*c))``, which is cancellation-free. Clamped at zero
    like the reference (``LogLikelihood.java:51-53``).
    """
    r1 = k11 + k12
    r2 = k21 + k22
    c1 = k11 + k21
    c2 = k12 + k22

    det = _det_f32(k11, k22, k12, k21)

    def term(k, rc, sign):
        safe_rc = jnp.where(rc > 0, rc, 1.0)
        x = sign * det / safe_rc
        lg = log1p_f32(jnp.maximum(x, -1.0 + 1e-38))
        return jnp.where((k > 0) & (rc > 0), k * lg, 0.0)

    out = 2.0 * (
        term(k11, r1 * c1, 1.0)
        + term(k12, r1 * c2, -1.0)
        + term(k21, r2 * c1, -1.0)
        + term(k22, r2 * c2, 1.0)
    )
    return jnp.maximum(out, 0.0)


@jax.jit
def llr_stable_jit(k11, k12, k21, k22):
    return llr_stable(k11, k12, k21, k22)


def score_contingency(k11, item_row_sum, other_row_sum, observed, llr_fn=llr_stable):
    """Build the 2x2 table from co-occurrence counts and score it.

    Mirrors ``ItemRowRescorerTwoInputStreamOperator.scoreItem`` (:230-241):
      k12 = rowSum(i) - k11, k21 = rowSum(j) - k11,
      k22 = observed + k11 - k12 - k21.
    All inputs are float arrays (cast by the caller from exact ints).
    """
    k12 = item_row_sum - k11
    k21 = other_row_sum - k11
    k22 = observed + k11 - k12 - k21
    return llr_fn(k11, k12, k21, k22)
