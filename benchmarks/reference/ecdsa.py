"""Frozen pure-Python secp256k1 ECDSA verify (exact integers).

A copy of the verify half of `lightning_tpu/crypto/ref_python.py` as of
PR 23, kept here because the program's copy is also its host fallback
and later PRs may change it.  Written from SEC 1; mirrors
libsecp256k1's `secp256k1_ecdsa_verify` as lightning calls it: a high-S
signature is invalid.  About 25 ms a signature: the benchmark checks a
sample with it, never a whole store.
"""
from __future__ import annotations

import hashlib

P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8

# a point is (x, y), or None for the point at infinity


def _add(p1, p2):
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    if p1[0] == p2[0]:
        if (p1[1] + p2[1]) % P == 0:
            return None
        return _double(p1)
    lam = (p2[1] - p1[1]) * pow(p2[0] - p1[0], -1, P) % P
    x3 = (lam * lam - p1[0] - p2[0]) % P
    return x3, (lam * (p1[0] - x3) - p1[1]) % P


def _double(p1):
    if p1 is None or p1[1] == 0:
        return None
    lam = 3 * p1[0] * p1[0] * pow(2 * p1[1], -1, P) % P
    x3 = (lam * lam - 2 * p1[0]) % P
    return x3, (lam * (p1[0] - x3) - p1[1]) % P


def _mul(k: int, pt):
    k %= N
    acc, addend = None, pt
    while k:
        if k & 1:
            acc = _add(acc, addend)
        addend = _double(addend)
        k >>= 1
    return acc


def pubkey_parse(data: bytes):
    """33-byte compressed key -> point; ValueError if it is none."""
    if len(data) != 33 or data[0] not in (2, 3):
        raise ValueError("bad pubkey encoding")
    x = int.from_bytes(data[1:], "big")
    if x >= P:
        raise ValueError("x out of range")
    y2 = (pow(x, 3, P) + 7) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        raise ValueError("not on curve")
    if (y & 1) != (data[0] & 1):
        y = P - y
    return x, y


def sha256d(data: bytes) -> bytes:
    return hashlib.sha256(hashlib.sha256(data).digest()).digest()


def verify(msg_hash: bytes, sig64: bytes, pubkey33: bytes) -> bool:
    """ECDSA over a 32-byte hash, compact r||s, compressed key."""
    r = int.from_bytes(sig64[:32], "big")
    s = int.from_bytes(sig64[32:], "big")
    if not (0 < r < N and 0 < s <= N // 2):
        return False
    try:
        q = pubkey_parse(pubkey33)
    except ValueError:
        return False
    w = pow(s, -1, N)
    z = int.from_bytes(msg_hash, "big")
    pt = _add(_mul(z * w % N, (GX, GY)), _mul(r * w % N, q))
    return pt is not None and pt[0] % N == r
