"""hsmd-equivalent: the key service.

The reference's hsmd (hsmd/hsmd.c:867, dispatch libhsmd.c:2184) is the
sole holder of secrets; every signature crosses a socketpair to it, one
request at a time — channeld's commitment flow does up to 483 serial
round-trips (channeld/channeld.c:1048-1071).

This service keeps the same trust boundary (a single object owning
secrets; callers hold capability-scoped client handles, mirroring
hsmd/permissions.h) but exposes *batched* signing entry points: a whole
commitment's HTLC signatures are one device call
(sign_batch → ecdsa_sign kernels), and bulk verification rides the same
kernels as gossip.
"""
from __future__ import annotations

import hashlib
import hmac
import os
from dataclasses import dataclass

import numpy as np

import logging

from ..btc import keys as K
from ..btc import tx as T
from ..crypto import field as F
from ..crypto import ref_python as ref
from ..crypto import secp256k1 as S
from ..obs import families as _families
from ..obs import flight as _flight
from ..resilience import breaker as _breaker
from ..resilience import faultinject as _fault
from ..resilience import quarantine as _quarantine
from ..utils import trace

log = logging.getLogger("lightning_tpu.daemon.hsmd")

# Observability for the batched-sign paths: until now only a trace span
# covered sign_htlc_batch, so "did this commitment fan-out actually hit
# the device?" was unanswerable from a scrape.  `path` mirrors
# ecdsa_sign_batch's HOST_VERIFY_MAX micro-batch rule: batches at or
# below the threshold sign on the host oracle, larger ones on device —
# unless the "sign" circuit breaker diverts them host-side.
# (Families declared in obs.families so jax-free consumers see them.)
_M_SIGN_SIGS = _families.SIGN_BATCH_SIGS
_M_SIGN_CALLS = _families.SIGN_CALLS


def _note_sign(op: str, n_sigs: int, path: str) -> None:
    _M_SIGN_SIGS.labels(op).observe(n_sigs)
    _M_SIGN_CALLS.labels(op, path).inc()


def _sign_batch_resilient(op: str, msg_hashes: np.ndarray,
                          seckeys: list[int]) -> np.ndarray:
    """Batched sign under the "sign" circuit breaker
    (doc/resilience.md).  Unlike verify — where quarantine must bisect
    on-device because the host oracle is slower by orders of magnitude
    at store scale — the host signer IS the oracle the device kernel is
    tested against, so a failed device dispatch simply re-signs the
    whole batch host-side (metered as quarantined rows) with identical
    output bytes.

    Every call is one flight-recorded "sign" dispatch (obs/flight.py):
    the caller's span (sign_htlc_batch / sign_withdrawal) is the
    enqueue point, the record's outcome says which path actually signed
    — host by design, host_breaker, ok, or host-with-error after a
    failed device dispatch — and listdispatches shows the batch shape
    the counters only aggregate."""
    B = msg_hashes.shape[0]
    brk = _breaker.get("sign")
    # the carrier links the caller's span (the enqueue point) to this
    # dispatch span with a flow arrow in the exported timeline
    corr = trace.new_corr()
    with _flight.dispatch("sign", n_real=B, lanes=B,
                          shape=(B, 32), corr_ids=(corr.corr_id,),
                          breaker_state=brk.state) as rec:
        with trace.span("sign/dispatch", corr=corr, op=op,
                        dispatch_id=rec["dispatch_id"]):
            return _sign_dispatch(op, msg_hashes, seckeys, brk, rec, B)


def _sign_dispatch(op: str, msg_hashes: np.ndarray, seckeys: list[int],
                   brk, rec: dict, B: int) -> np.ndarray:
    if B <= S.HOST_VERIFY_MAX:
        # micro-batches already sign host-side inside ecdsa_sign_batch
        rec["outcome"] = "host"
        _note_sign(op, B, "host")
        return S.ecdsa_sign_batch(msg_hashes, seckeys)
    if not brk.allow():
        rec["outcome"] = "host_breaker"
        _note_sign(op, B, "host")
        return S.host_sign_batch(msg_hashes, seckeys)
    try:
        _fault.fire("sign", "sign")
        # operand-staging accounting (doc/perf.md): B 32-byte message
        # hashes + B 32-byte scalar keys up, B compact signatures back
        rec["h2d_bytes"] = int(msg_hashes.nbytes) + 32 * B
        _families.TRANSFER_BYTES.labels("sign",
                                        "h2d").inc(rec["h2d_bytes"])
        out = S.ecdsa_sign_batch(msg_hashes, seckeys)
    except Exception as e:
        brk.record_failure()
        _quarantine.note("sign", type(e).__name__, B)
        # recovered on the host oracle: outcome "host" + the error name
        # (the "error" outcome is reserved for unrecovered failures)
        rec["outcome"] = "host"
        rec["error"] = type(e).__name__
        log.warning("device sign dispatch failed (%s); re-signing %d "
                    "hashes on the host oracle", e, B)
        _note_sign(op, B, "host")
        return S.host_sign_batch(msg_hashes, seckeys)
    brk.record_success()
    rec["outcome"] = "ok"
    rec["d2h_bytes"] = 64 * B
    _families.TRANSFER_BYTES.labels("sign", "d2h").inc(64 * B)
    _note_sign(op, B, "device")
    return out

def _check_sigs_resilient(msg_hashes: np.ndarray, sigs64: np.ndarray,
                          pubkeys33: np.ndarray) -> np.ndarray:
    """Batched sig-check under the shared "verify" circuit breaker —
    the same EC verify program family as the gossip replay, so a
    flapping device that opened the replay's breaker also diverts
    commitment self-checks to the exact host oracle instead of wedging
    the commitment dance.  This seam was the one hole graftlint's
    supervision-coverage pass found on its first full-tree run: every
    other dispatch family got breakers in PR 4 and flight records in
    PR 5; check_sigs_batch predated both and got neither."""
    B = msg_hashes.shape[0]
    # the BREAKER is the shared "verify" one (same EC program family,
    # same device health signal as the replay); the FLIGHT family is
    # its own "check" lane — folding these records into "verify" would
    # skew the replay pipeline's ring↔counter reconciliation
    # (doc/perf.md), whose stage timings these records don't carry
    brk = _breaker.get("verify")
    corr = trace.new_corr()
    with _flight.dispatch("check", n_real=B, lanes=B, shape=(B, 32),
                          corr_ids=(corr.corr_id,),
                          breaker_state=brk.state) as rec:
        with trace.span("check/dispatch", corr=corr,
                        dispatch_id=rec["dispatch_id"]):
            if B <= S.HOST_VERIFY_MAX:
                # micro-batches verify host-side inside
                # ecdsa_verify_batch already
                rec["outcome"] = "host"
                return S.ecdsa_verify_batch(msg_hashes, sigs64,
                                            pubkeys33)
            if not brk.allow():
                rec["outcome"] = "host_breaker"
                return S.host_verify_batch(msg_hashes, sigs64,
                                           pubkeys33)
            try:
                _fault.fire("dispatch", "verify")
                out = S.ecdsa_verify_batch(msg_hashes, sigs64,
                                           pubkeys33)
            except Exception as e:
                brk.record_failure()
                _quarantine.note("check", type(e).__name__, B)
                rec["outcome"] = "host"
                rec["error"] = type(e).__name__
                log.warning("device sig-check dispatch failed (%s); "
                            "re-checking %d sigs on the host oracle",
                            e, B)
                return S.host_verify_batch(msg_hashes, sigs64,
                                           pubkeys33)
            brk.record_success()
            rec["outcome"] = "ok"
            return out


# Capability bits (shape mirrors hsmd/permissions.h)
CAP_ECDH = 1
CAP_SIGN_GOSSIP = 2
CAP_SIGN_ONCHAIN = 4
CAP_SIGN_COMMITMENT = 8
CAP_MASTER = 0xFF


class HsmError(Exception):
    pass


@dataclass
class HsmClient:
    """A capability-scoped handle (one per subdaemon in the reference,
    hsmd/hsm_control.c:27)."""

    hsm: "Hsm"
    caps: int
    channel_seed: bytes | None = None

    def _need(self, cap: int):
        if not (self.caps & cap):
            raise HsmError("capability denied")


class Hsm:
    """Owner of hsm_secret.  Derivations follow our own scheme (the
    reference's exact derivation tree is an implementation detail of its
    hsm_secret format; what matters for protocol parity is that channel
    basepoints and the shachain are deterministic from one secret)."""

    def __init__(self, secret: bytes):
        assert len(secret) == 32
        self._secret = secret
        self.node_key = self._derive_int(b"nodeid")
        self.node_pubkey = ref.pubkey_create(self.node_key)

    @classmethod
    def generate(cls) -> "Hsm":
        return cls(os.urandom(32))

    def _derive(self, tag: bytes) -> bytes:
        return hmac.new(self._secret, tag, hashlib.sha256).digest()

    def _derive_int(self, tag: bytes) -> int:
        v = int.from_bytes(self._derive(tag), "big") % ref.N
        return v or 1

    def client(self, caps: int, peer_id: bytes = b"", dbid: int = 0) -> HsmClient:
        chseed = None
        if dbid:
            chseed = self._derive(b"chan" + peer_id + dbid.to_bytes(8, "big"))
        return HsmClient(self, caps, chseed)

    # -- node-level ops ---------------------------------------------------

    def ecdh(self, client: HsmClient, point: ref.Point) -> bytes:
        client._need(CAP_ECDH)
        return hashlib.sha256(
            ref.pubkey_serialize(ref.point_mul(self.node_key, point))
        ).digest()

    def sign_node_announcement_hash(self, client: HsmClient, h32: bytes):
        client._need(CAP_SIGN_GOSSIP)
        return ref.ecdsa_sign(h32, self.node_key)

    def sign_channel_announcement(self, client: HsmClient,
                                  h32: bytes) -> tuple[bytes, bytes]:
        """(node_signature, bitcoin_signature) over a channel_
        announcement hash — node identity key + the channel's funding
        key (hsmd_cannouncement_sig_req, hsmd/libhsmd.c)."""
        client._need(CAP_SIGN_GOSSIP)
        secs = self.channel_secrets(client)
        nr, ns = ref.ecdsa_sign(h32, self.node_key)
        br, bs = ref.ecdsa_sign(h32, secs.funding)
        return (nr.to_bytes(32, "big") + ns.to_bytes(32, "big"),
                br.to_bytes(32, "big") + bs.to_bytes(32, "big"))

    # -- channel-level ops ------------------------------------------------

    def channel_secrets(self, client: HsmClient) -> K.BaseSecrets:
        if client.channel_seed is None:
            raise HsmError("client has no channel")
        return K.BaseSecrets.from_seed(client.channel_seed)

    def channel_basepoints(self, client: HsmClient) -> K.Basepoints:
        return self.channel_secrets(client).basepoints()

    def per_commitment_secret(self, client: HsmClient, commitment_number: int) -> bytes:
        secs = self.channel_secrets(client)
        shaseed = hashlib.sha256(
            client.channel_seed + b"shachain"
        ).digest()
        index = K.LARGEST_INDEX - commitment_number
        return K.shachain_derive_secret(shaseed, index)

    def per_commitment_point(self, client: HsmClient, commitment_number: int) -> ref.Point:
        return K.per_commitment_point(
            self.per_commitment_secret(client, commitment_number)
        )

    # -- batched signing (the TPU fan-out path) ---------------------------

    def sign_htlc_batch(
        self,
        client: HsmClient,
        sighashes: list[bytes],
        remote_per_commitment_point: ref.Point,
    ) -> np.ndarray:
        """Sign every HTLC sighash of a remote commitment in ONE device
        call (vs the reference's per-HTLC hsmd_sign_remote_htlc_tx round
        trips).  Returns (N, 64) compact sigs."""
        client._need(CAP_SIGN_COMMITMENT)
        if not sighashes:
            return np.zeros((0, 64), np.uint8)
        with trace.span("hsmd/sign_htlc_batch", n=len(sighashes)):
            secs = self.channel_secrets(client)
            htlc_priv = K.derive_privkey(secs.htlc,
                                         remote_per_commitment_point)
            hashes = np.stack([np.frombuffer(h, np.uint8)
                               for h in sighashes])
            return _sign_batch_resilient("htlc", hashes,
                                         [htlc_priv] * len(sighashes))

    def sign_remote_commitment(
        self, client: HsmClient, sighash: bytes
    ) -> bytes:
        """The single funding-key signature on the remote commitment tx."""
        client._need(CAP_SIGN_COMMITMENT)
        secs = self.channel_secrets(client)
        r, s = ref.ecdsa_sign(sighash, secs.funding)
        return r.to_bytes(32, "big") + s.to_bytes(32, "big")

    # -- onchain resolution signing (hsmd_wire.csv:289-327 equivalents) ----

    def sign_delayed_payment_to_us(self, client: HsmClient, sighash: bytes,
                                   per_commitment_point: ref.Point) -> bytes:
        """hsmd_sign_any_delayed_payment_to_us: our to_local claim after
        the CSV delay on OUR unilateral close."""
        client._need(CAP_SIGN_ONCHAIN)
        secs = self.channel_secrets(client)
        k = K.derive_privkey(secs.delayed_payment, per_commitment_point)
        r, s = ref.ecdsa_sign(sighash, k)
        return r.to_bytes(32, "big") + s.to_bytes(32, "big")

    def sign_penalty_to_us(self, client: HsmClient, sighash: bytes,
                           their_per_commitment_secret: int) -> bytes:
        """hsmd_sign_penalty_to_us: revocation-key spend of a REVOKED
        remote commitment's outputs."""
        client._need(CAP_SIGN_ONCHAIN)
        secs = self.channel_secrets(client)
        k = K.derive_revocation_privkey(secs.revocation,
                                        their_per_commitment_secret)
        r, s = ref.ecdsa_sign(sighash, k)
        return r.to_bytes(32, "big") + s.to_bytes(32, "big")

    def sign_to_remote_to_us(self, client: HsmClient,
                             sighash: bytes) -> bytes:
        """Claim our to_remote output on THEIR commitment (static
        remotekey: the plain payment basepoint)."""
        client._need(CAP_SIGN_ONCHAIN)
        secs = self.channel_secrets(client)
        r, s = ref.ecdsa_sign(sighash, secs.payment)
        return r.to_bytes(32, "big") + s.to_bytes(32, "big")

    def sign_remote_htlc_to_us(self, client: HsmClient, sighash: bytes,
                               per_commitment_point: ref.Point) -> bytes:
        """Claim an HTLC output on THEIR commitment (success w/ preimage
        or timeout), keyed by our htlc basepoint at their point."""
        client._need(CAP_SIGN_ONCHAIN)
        secs = self.channel_secrets(client)
        k = K.derive_privkey(secs.htlc, per_commitment_point)
        r, s = ref.ecdsa_sign(sighash, k)
        return r.to_bytes(32, "big") + s.to_bytes(32, "big")

    def check_sigs_batch(self, msg_hashes: np.ndarray, sigs: np.ndarray,
                         pubkeys: np.ndarray) -> np.ndarray:
        """Batched verify (the self-check the reference does per-HTLC with
        check_tx_sig, channeld/channeld.c:1068 — here one call)."""
        return _check_sigs_resilient(msg_hashes, sigs, pubkeys)

    # -- on-chain wallet (hsmd_sign_withdrawal equivalents) ---------------

    def bip32_base(self):
        """The wallet's extended key base (hsmd hands lightningd the
        public base at init, hsmd/hsmd.c; our single-process runtime
        hands the KeyManager the private base directly — it never
        crosses a trust boundary)."""
        from ..btc.bip32 import ExtKey

        if getattr(self, "_bip32", None) is None:
            self._bip32 = ExtKey.from_seed(self._derive(b"bip32 seed"))
        return self._bip32

    def sign_withdrawal(self, client: HsmClient, tx, utxo_meta) -> None:
        """Fill P2WPKH witnesses for every wallet input of tx.
        utxo_meta: per-input (amount_sat, keyindex) | None (foreign).
        Reference: hsmd's sign_withdrawal loops inputs serially; here
        all sighashes are ground through one batched low-R device sign
        when there is more than one input.  The sighash recipe lives in
        wallet.onchain.wallet_input_digests (shared with the standalone
        signer)."""
        client._need(CAP_SIGN_ONCHAIN)
        from ..btc.tx import sig_to_der
        from ..wallet.onchain import wallet_input_digests

        if getattr(self, "_bip32_chain0", None) is None:
            self._bip32_chain0 = self.bip32_base().ckd(0)
        base = self._bip32_chain0
        cache: dict = getattr(self, "_bip32_keys", None) or {}
        self._bip32_keys = cache

        def key_for_index(idx: int):
            k = cache.get(idx)
            if k is None:
                k = cache[idx] = base.ckd(idx)
            return k

        items = wallet_input_digests(tx, utxo_meta, key_for_index)
        if len(items) > 1:
            hashes = np.stack([np.frombuffer(d, np.uint8)
                               for _, d, _, _ in items])
            sigs = _sign_batch_resilient("withdrawal", hashes,
                                         [k for _, _, k, _ in items])
            for (i, _, _, pub), sig64 in zip(items, np.asarray(sigs)):
                r = int.from_bytes(bytes(sig64[:32]), "big")
                s = int.from_bytes(bytes(sig64[32:]), "big")
                tx.inputs[i].witness = [sig_to_der(r, s), pub]
        else:
            if items:
                _note_sign("withdrawal", len(items), "host")
            for i, digest, k, pub in items:
                r, s = ref.ecdsa_sign(digest, k)
                tx.inputs[i].witness = [sig_to_der(r, s), pub]
