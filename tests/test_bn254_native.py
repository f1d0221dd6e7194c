"""Native BN254 G1 backend (native/bn254.cc): parity with the pure-
Python affine implementation on random, infinity, and edge inputs."""

import random

import pytest

from fabric_tpu import native
from fabric_tpu.idemix import bn254 as bn

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)

RNG = random.Random(99)


def _rand_points(n):
    return [bn._g1_mul_py(bn.G1_GEN, bn.rand_zr(RNG)) for _ in range(n)]


def test_msm_parity():
    pts = _rand_points(6)
    ks = [bn.rand_zr(RNG) for _ in range(6)]
    ref = None
    for p, k in zip(pts, ks):
        ref = bn.g1_add(ref, bn._g1_mul_py(p, k))
    assert native.bn254_msm(pts, ks) == ref


def test_msm_edge_scalars():
    p = _rand_points(1)[0]
    # k = 0, 1, R-1, R, R+5 (reduction mod R)
    for k in (0, 1, bn.R - 1, bn.R, bn.R + 5):
        ref = bn._g1_mul_py(p, k)
        assert native.bn254_msm([p], [k]) == ref


def test_msm_infinity_paths():
    p = _rand_points(1)[0]
    # cancellation -> infinity
    assert native.bn254_msm([p, bn.g1_neg(p)], [7, 7]) is None
    # infinity input skipped
    assert native.bn254_msm([None, p], [3, 2]) == bn._g1_mul_py(p, 2)
    # empty
    assert native.bn254_msm([], []) is None


def test_mul_many_parity():
    pts = _rand_points(5) + [None]
    ks = [bn.rand_zr(RNG) for _ in range(5)] + [11]
    ref = [bn._g1_mul_py(p, k) if p else None for p, k in zip(pts, ks)]
    assert native.bn254_mul_many(pts, ks) == ref


def test_doubling_chain_parity():
    # repeated doubling exercises g1_dbl + the add h==0 branch
    p = _rand_points(1)[0]
    assert native.bn254_msm([p, p], [3, 3]) == bn._g1_mul_py(p, 6)
    assert native.bn254_msm([p], [2]) == bn.g1_add(p, p)


def test_pairing_check_bilinearity():
    a, b = bn.rand_zr(RNG), bn.rand_zr(RNG)
    p1 = bn._g1_mul_py(bn.G1_GEN, a)
    q1 = bn.g2_mul(bn.G2_GEN, b)
    p2 = bn.g1_neg(bn._g1_mul_py(bn.G1_GEN, a * b % bn.R))
    assert native.bn254_pairing_check([(p1, q1), (p2, bn.G2_GEN)])
    # python oracle agrees
    assert bn.multi_pairing([(p1, q1), (p2, bn.G2_GEN)]) == bn.FP12_ONE
    # tampered pair fails
    assert not native.bn254_pairing_check([(p1, q1), (bn.g1_neg(p1), bn.G2_GEN)])


def test_pairing_check_identity_inputs():
    p = bn._g1_mul_py(bn.G1_GEN, 5)
    # infinity on either side contributes the identity factor
    assert native.bn254_pairing_check([(None, bn.G2_GEN)])
    assert native.bn254_pairing_check([(p, None)])
    assert native.bn254_pairing_check([])
    # a single non-degenerate pairing is NOT one
    assert not native.bn254_pairing_check([(p, bn.G2_GEN)])


def test_pairing_check_three_way_split():
    # e(aG,bQ) e(bG,cQ) e(-G, (ab+bc)Q) == 1
    a, b, c = (bn.rand_zr(RNG) for _ in range(3))
    pairs = [
        (bn._g1_mul_py(bn.G1_GEN, a), bn.g2_mul(bn.G2_GEN, b)),
        (bn._g1_mul_py(bn.G1_GEN, b), bn.g2_mul(bn.G2_GEN, c)),
        (bn.g1_neg(bn.G1_GEN), bn.g2_mul(bn.G2_GEN, (a * b + b * c) % bn.R)),
    ]
    assert native.bn254_pairing_check(pairs)


# --- the bucket method (sums of bn254_msm_bucket_threshold() terms or more) ---

THRESHOLD = native.bn254_msm_bucket_threshold() if native.available() else 0
# scalars whose signed digits carry through every window, or fill the top
# one: every digit at, one over and far over half the base, for 4- and
# 5-bit windows
_CARRYING = [(1 << 254) - 1, 1 << 253, bn.R - 2] + [
    sum(digit << (c * i) for i in range(253 // c))
    for c in (4, 5) for digit in (1 << (c - 1), (1 << (c - 1)) + 1, (1 << c) - 1)
]


def _oracle(pts, ks):
    out = None
    for p, k in zip(pts, ks):
        if p is not None:
            out = bn.g1_add(out, bn._g1_mul_py(p, k))
    return out


@pytest.fixture(scope="module")
def points():
    """254 distinct points: a random point and its successive sums with
    another."""
    p, step = _rand_points(2)
    out = [p]
    while len(out) < 254:
        out.append(bn.g1_add(out[-1], step))
    return out


def _bucket_case(name, points):
    """(points, scalars) of a named case, every one but the first at or
    over the threshold so that the bucket method sums it."""
    rng = random.Random(name)

    def rand(count):
        return [bn.rand_zr(rng) for _ in range(count)]

    n = THRESHOLD + 4
    if name.startswith("n="):
        n = {"n=threshold-1": THRESHOLD - 1, "n=threshold": THRESHOLD,
             "n=threshold+1": THRESHOLD + 1,
             "n=127": 127, "n=254": 254}[name]
        return points[:n], rand(n)
    if name == "scalars 0, 1, R-1 among random":
        return points[:n], [0, 1, bn.R - 1] + rand(n - 3)
    if name == "digits that carry":
        ks = [k % bn.R for k in _CARRYING]
        return points[:len(ks) + n], ks + rand(n)
    if name == "a point repeated under one scalar":
        k = bn.rand_zr(rng)           # P + P in one bucket, window after window
        return [points[0], points[0]] + points[1:n - 1], [k, k] + rand(n - 2)
    if name == "a point and its negative under one scalar":
        k = bn.rand_zr(rng)           # P + (-P): the bucket returns to infinity
        return ([points[0], bn.g1_neg(points[0])] + points[1:n - 1],
                [k, k] + rand(n - 2))
    if name == "every point the same":
        return [points[3]] * n, rand(n)
    if name == "every term the same":
        return [points[3]] * n, [bn.rand_zr(rng)] * n
    if name == "infinity among the inputs":
        pts = list(points[:n])
        pts[0] = pts[n // 2] = pts[-1] = None
        return pts, rand(n)
    if name == "all scalars 0":
        return points[:n], [0] * n
    if name == "every term cancelled":
        k = bn.rand_zr(rng)
        return [points[0], bn.g1_neg(points[0])] * (n // 2), [k] * (n // 2 * 2)
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "n=threshold-1", "n=threshold", "n=threshold+1", "n=127", "n=254",
    "scalars 0, 1, R-1 among random", "digits that carry",
    "a point repeated under one scalar",
    "a point and its negative under one scalar", "every point the same",
    "every term the same", "infinity among the inputs", "all scalars 0",
    "every term cancelled",
])
def test_bucket_msm_is_the_oracles_sum(points, name):
    pts, ks = _bucket_case(name, points)
    assert bn.g1_msm_engine(len(pts)) == (
        "window" if name == "n=threshold-1" else "bucket")
    want = _oracle(pts, ks)
    if name in ("all scalars 0", "every term cancelled"):
        assert want is None
    assert native.bn254_msm(pts, ks) == want
    # several sums under the same scalars: the same points, byte for byte
    other = [bn.g1_neg(p) for p in pts]
    assert native.bn254_msm_sets([pts, other], ks) == [want, bn.g1_neg(want)]


@pytest.mark.parametrize("n", [THRESHOLD - 3, THRESHOLD - 1, THRESHOLD,
                               THRESHOLD + 1])
def test_the_two_methods_agree_around_the_threshold(points, n):
    """The method is chosen from the term count alone, so the same terms
    reach the other method padded with infinity inputs (over the
    threshold) or cut into sums of fewer terms (under it)."""
    ks = [bn.rand_zr(RNG) for _ in range(n)]
    pts = points[10:10 + n]
    pad = [None] * THRESHOLD
    by_buckets = native.bn254_msm(pts + pad, ks + [5] * THRESHOLD)
    assert bn.g1_msm_engine(n + THRESHOLD) == "bucket"
    cut = max(1, n // 2)
    by_windows = bn.g1_add(native.bn254_msm(pts[:cut], ks[:cut]),
                           native.bn254_msm(pts[cut:], ks[cut:]))
    assert bn.g1_msm_engine(max(cut, n - cut)) == "window"
    assert by_buckets == by_windows == native.bn254_msm(pts, ks)


def test_msm_sets_wants_a_point_a_scalar(points):
    with pytest.raises(ValueError):
        native.bn254_msm_sets([points[:3], points[:2]], [1, 2, 3])
    assert native.bn254_msm_sets([], [1, 2]) == []
    assert native.bn254_msm_sets([[], []], []) == [None, None]


def test_without_the_native_library_every_sum_is_by_the_term(monkeypatch, points):
    ks = [bn.rand_zr(RNG) for _ in range(THRESHOLD)]
    want = native.bn254_msm_sets([points[:THRESHOLD], points[5:5 + THRESHOLD]], ks)
    monkeypatch.setattr(bn, "_NATIVE", None)
    assert bn.g1_msm_engine(254) == "window"
    assert bn.g1_msm_sets([points[:THRESHOLD], points[5:5 + THRESHOLD]], ks) == want
