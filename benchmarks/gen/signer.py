"""secp256k1 key derivation and ECDSA signing for the store generator,
through OpenSSL (the `cryptography` package) in worker processes.

Independent of the program under test: the benchmark's stores are
signed by OpenSSL, verified by the program's kernels, and checked by
the pure-Python reference in benchmarks/reference/ecdsa.py.  Signing is
RFC 6979 deterministic, so one seed gives one store, byte for byte.

The workers are plain child processes running this file: a job goes in
on stdin as a pickle `(function name, arguments)`, the bytes come back
on stdout.  They import nothing of jax or of the program.
"""
from __future__ import annotations

import pickle
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

from cryptography.hazmat.primitives import hashes, serialization
from cryptography.hazmat.primitives.asymmetric import ec, utils

N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
_ALG = ec.ECDSA(utils.Prehashed(hashes.SHA256()), deterministic_signing=True)


def derive_pubkeys(seckeys: list[int]) -> bytes:
    """33-byte compressed public keys, concatenated."""
    out = bytearray()
    for k in seckeys:
        pub = ec.derive_private_key(k, ec.SECP256K1()).public_key()
        out += pub.public_bytes(serialization.Encoding.X962,
                                serialization.PublicFormat.CompressedPoint)
    return bytes(out)


def sign_jobs(seckeys: list[int], key_of_job: list[int],
              hashes32: bytes) -> bytes:
    """One 64-byte compact low-S signature per job, concatenated.
    key_of_job indexes seckeys; hashes32 holds the jobs' 32-byte
    digests back to back."""
    keys = {}
    out = bytearray()
    for j, ki in enumerate(key_of_job):
        key = keys.get(ki)
        if key is None:
            key = keys[ki] = ec.derive_private_key(seckeys[ki],
                                                   ec.SECP256K1())
        r, s = utils.decode_dss_signature(
            key.sign(hashes32[32 * j: 32 * j + 32], _ALG))
        if s > N // 2:
            s = N - s
        out += r.to_bytes(32, "big") + s.to_bytes(32, "big")
    return bytes(out)


def run_jobs(fn: str, jobs: list[tuple]) -> list[bytes]:
    """Run fn(*job) for every job, each in a child process of its own,
    all at once; returns the results in order.  One job runs inline."""
    if len(jobs) <= 1:
        return [globals()[fn](*j) for j in jobs]

    def one(job) -> bytes:
        proc = subprocess.run([sys.executable, __file__],
                              input=pickle.dumps((fn, job)),
                              capture_output=True, check=False)
        if proc.returncode != 0:
            raise RuntimeError(f"signer worker failed: "
                               f"{proc.stderr.decode()[-500:]}")
        return proc.stdout

    with ThreadPoolExecutor(max_workers=len(jobs)) as pool:
        return list(pool.map(one, jobs))


if __name__ == "__main__":
    _fn, _args = pickle.loads(sys.stdin.buffer.read())
    sys.stdout.buffer.write({"derive_pubkeys": derive_pubkeys,
                             "sign_jobs": sign_jobs}[_fn](*_args))
