"""LLR kernel tests.

Golden values are the Dunning-paper cases used by the reference test
(``LogLikelihoodTest.java:13-16``): 270.72, 263.90, 48.94 at tolerance 0.1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tpu_cooccurrence.oracle.reference import _llr_scalar
from tpu_cooccurrence.ops import llr as llr_ops

GOLDEN = [
    ((110, 2442, 111, 29114), 270.72),
    ((29, 13, 123, 31612), 263.90),
    ((9, 12, 429, 31327), 48.94),
]


@pytest.mark.parametrize("cells,expected", GOLDEN)
def test_golden_scalar_oracle(cells, expected):
    assert _llr_scalar(*cells) == pytest.approx(expected, abs=0.1)


@pytest.mark.parametrize("cells,expected", GOLDEN)
def test_golden_numpy(cells, expected):
    assert llr_ops.llr_np(*cells) == pytest.approx(expected, abs=0.1)


@pytest.mark.parametrize("cells,expected", GOLDEN)
def test_golden_jax_stable_f32(cells, expected):
    vals = [np.float32(c) for c in cells]
    out = float(llr_ops.llr_stable_jit(*vals))
    assert out == pytest.approx(expected, abs=0.1)


def test_zero_cells():
    # Any zero cell must not produce NaN/inf (0*log 0 = 0 convention,
    # LogLikelihood.java:59-61).
    cases = [(0, 1, 2, 3), (1, 0, 2, 3), (1, 2, 0, 3), (1, 2, 3, 0),
             (0, 0, 0, 0), (5, 0, 0, 0), (0, 5, 0, 0)]
    for cells in cases:
        ref = _llr_scalar(*cells)
        assert np.isfinite(ref)
        got = float(llr_ops.llr_stable_jit(*[np.float32(c) for c in cells]))
        assert np.isfinite(got)
        assert got == pytest.approx(ref, abs=1e-3, rel=1e-4)


def test_independence_is_zero():
    # Perfectly independent table: LLR == 0 exactly.
    # rows (a+b, c+d), cols proportional: k11/k12 == k21/k22.
    assert _llr_scalar(10, 20, 100, 200) == pytest.approx(0.0, abs=1e-9)
    got = float(llr_ops.llr_stable_jit(*(np.float32(x) for x in (10, 20, 100, 200))))
    assert got == pytest.approx(0.0, abs=1e-3)


def _scale_tables(observed=30_000_000_000):
    """2,000 contingency tables at ``observed`` total co-occurrences."""
    rng = np.random.default_rng(0xC0FFEE)
    n = 2000
    k11 = rng.integers(1, 500, n)
    r1 = k11 + rng.integers(0, 500_000, n)
    r2 = rng.integers(0, 1_000_000, n)
    k21 = np.minimum(rng.integers(0, 500_000, n), r2)
    k12 = r1 - k11
    k22 = np.int64(observed) + k11 - k12 - k21
    return k11, k12, k21, k22


def test_stable_f32_matches_f64_oracle_at_scale():
    """The reason llr_stable exists: float32 accuracy at ~1e10 counts where
    the entropy form cancels catastrophically."""
    k11, k12, k21, k22 = _scale_tables()
    ref = llr_ops.llr_np(k11, k12, k21, k22)
    got = np.asarray(
        llr_ops.llr_stable_jit(
            k11.astype(np.float32), k12.astype(np.float32),
            k21.astype(np.float32), k22.astype(np.float32)))
    # Absolute tolerance on scores that range up to ~1e4.
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-3)


def test_entropy_f32_would_fail_at_scale():
    """Documents why the entropy form is not used on device: in float32 it is
    garbage at large counts (sanity check that our reformulation is actually
    load-bearing)."""
    cells = (200.0, 300_000.0, 400_000.0, 3e10)
    ref = float(llr_ops.llr_np(*cells))
    ent32 = float(llr_ops.llr_entropy(*(jnp.float32(c) for c in cells)))
    stable32 = float(llr_ops.llr_stable(*(jnp.float32(c) for c in cells)))
    assert abs(stable32 - ref) < 0.01 * max(1.0, abs(ref))
    assert abs(ent32 - ref) > abs(stable32 - ref)


def test_score_contingency_matches_reference_table():
    """k12/k21/k22 construction mirrors
    ItemRowRescorerTwoInputStreamOperator.java:230-241."""
    k11, rs_i, rs_j, obs = 7, 20, 15, 100
    expect = _llr_scalar(k11, rs_i - k11, rs_j - k11, obs + k11 - (rs_i - k11) - (rs_j - k11))
    got = float(llr_ops.score_contingency(
        np.float32(k11), np.float32(rs_i), np.float32(rs_j), np.float32(obs)))
    assert got == pytest.approx(expect, rel=1e-5, abs=1e-4)


def _log1p_two_series(x):
    """``log1p_f32`` as it stood before it selected the quotient: both
    branches' divisions and series on every element. Frozen here as the
    bit-identity reference."""
    u = 1.0 + x
    bits = lax.bitcast_convert_type(u, jnp.int32)
    e = (bits >> 23) - 127
    m = lax.bitcast_convert_type((bits & 0x007FFFFF) | 0x3F800000,
                                 jnp.float32)
    high = m > 1.4142135
    m = jnp.where(high, m * 0.5, m)
    ef = jnp.where(high, e + 1, e).astype(jnp.float32)
    log_u = ef * llr_ops._LN2_HI + (
        llr_ops._two_atanh((m - 1.0) / (m + 1.0)) + ef * llr_ops._LN2_LO)
    log_u = jnp.where(u > 0, log_u, -jnp.inf)
    return jnp.where(jnp.abs(x) < 0.25,
                     llr_ops._two_atanh(x / (2.0 + x)), log_u)


def _ulp_neighbours(centres, n=4):
    """Each centre and its ``n`` nearest float32 neighbours on each side."""
    out = []
    for c in np.asarray(centres, np.float32):
        lo = hi = c
        out.append(c)
        for _ in range(n):
            lo = np.nextafter(lo, np.float32(-np.inf))
            hi = np.nextafter(hi, np.float32(np.inf))
            out += [lo, hi]
    return np.array(out, np.float32)


def _log_uniform(seed, lo_exp, hi_exp, n=4096):
    rng = np.random.default_rng(seed)
    mag = 10.0 ** rng.uniform(lo_exp, hi_exp, n)
    return (mag * rng.choice([-1.0, 1.0], n)).astype(np.float32)


# 1 + x at the mantissa split m = 1.4142135, over several binades.
_SPLIT = [np.float32(1.4142135) * np.float32(2.0) ** k - np.float32(1.0)
          for k in range(-1, 6)]

LOG1P_CASES = {
    "quarter": _ulp_neighbours([0.25, -0.25], n=8),
    "sqrt2-split": _ulp_neighbours(_SPLIT, n=6),
    "near-minus-one": _ulp_neighbours([-1.0, -0.75, -0.5], n=6),
    "near-zero": np.concatenate([
        _ulp_neighbours([0.0, 1e-30, -1e-30], n=4),
        np.float32([-0.0, 1e-38, -1e-38, 1e-45, -1e-45])]),
    "huge-and-special": np.float32([
        1.0, 2.0, 1e10, 1e20, 1e30, 3e38, np.finfo(np.float32).max,
        -2.0, -1e30, np.inf, -np.inf, np.nan]),
    "log-uniform-tiny": _log_uniform(1, -30, -3),
    "log-uniform-unit": _log_uniform(2, -3, 1),
    "log-uniform-large": _log_uniform(3, 0, 30),
    "uniform-above-minus-one": np.random.default_rng(4).uniform(
        -1.0, 3.0, 4096).astype(np.float32),
}


@pytest.mark.parametrize("case", sorted(LOG1P_CASES))
def test_log1p_f32_bit_identical_to_two_series_form(case):
    """One division and one series per element compute, for every
    element, exactly the ops of the branch the two-series form kept:
    the same float32 bits, op by op."""
    # A device array, so that both forms run every op in XLA (a NumPy
    # operand would take the first ops in NumPy, which keeps the
    # denormals that XLA flushes).
    x = jnp.asarray(LOG1P_CASES[case])
    with jax.disable_jit():
        got = np.asarray(llr_ops.log1p_f32(x))
        want = np.asarray(_log1p_two_series(x))
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


@pytest.mark.parametrize("observed", [30_000_000, 3_000_000_000,
                                      30_000_000_000])
def test_llr_stable_bit_identical_to_two_series_form(observed, monkeypatch):
    cells = [jnp.asarray(c, jnp.float32) for c in _scale_tables(observed)]
    with jax.disable_jit():
        got = np.asarray(llr_ops.llr_stable(*cells))
        monkeypatch.setattr(llr_ops, "log1p_f32", _log1p_two_series)
        want = np.asarray(llr_ops.llr_stable(*cells))
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


def test_llr_stable_traces_one_division_per_log1p():
    """Four ``k * log1p`` terms, each one quotient into ``log1p_f32`` and
    one inside it: at most 8 divisions over a kernel block."""
    def divs(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            n += eqn.primitive.name == "div"
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", None)
                if inner is not None:
                    n += divs(getattr(inner, "jaxpr", inner))
        return n

    block = jnp.zeros((64, 2048), jnp.float32)
    closed = jax.make_jaxpr(llr_ops.llr_stable)(block, block, block, block)
    n = divs(closed.jaxpr)
    assert n <= 8, (
        f"llr_stable traces to {n} divisions over a [64, 2048] block, "
        "over 8: log1p_f32 runs a second division and atanh series again "
        "(PERF.md §6: one quotient per log1p is most of the dense "
        "kernel's gain)")
