"""The roofline functions on shapes counted by hand."""
from lib import readers

PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_verify_counts_by_hand():
    v = readers.load_roofline("verify")
    # inverse of s 384, u1/u2 2, Shamir 256*7 + 192*11 = 3904,
    # to affine 272: 4562 products of 2*32*32 = 2048 operations
    assert v.PRODUCTS_PER_SIG == 384 + 2 + 1792 + 2112 + 272 == 4562
    assert v.OPS_PER_SIG == 4562 * 2048 == 9_342_976
    ops, nbytes = v.work(1000, 54_000)
    assert ops == 9_342_976_000
    assert nbytes == 1000 * (64 + 33 + 1) + 54_000
    secs, bound = v.least_seconds(1000, 54_000, PEAKS)
    assert bound == "int8_ops_per_s"
    assert abs(secs - 9_342_976_000 / 393e12) < 1e-15


def test_route_counts_by_hand():
    r = readers.load_roofline("route")
    # 50,000 directed edges, 6,000 nodes, 64 queries
    ops, nbytes = r.work(50_000, 6_000, 64)
    assert ops == 50_000 * 64 * 256
    assert nbytes == 50_000 * 37 + 6_000 * 64 * 48 == 20_282_000
    secs, bound = r.least_seconds(50_000, 6_000, 64, PEAKS)
    assert bound == "hbm_bytes_per_s"
    assert abs(secs - 20_282_000 / 819e9) < 1e-15
