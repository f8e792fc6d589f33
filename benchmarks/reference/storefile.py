"""The reference's own reader of a gossip_store file, and the validity
of a record as BOLT 7 states it.  Nothing of the program is imported.

Format (upstream `common/gossip_store.h`): one version byte, then
records `be16 flags | be16 len | be32 crc | be32 timestamp | msg`;
flag 0x8000 marks a deleted record.
"""
from __future__ import annotations

from . import ecdsa

MSG_CA, MSG_NA, MSG_CU = 256, 257, 258
FLAG_DELETED = 0x8000


def read_alive(path: str) -> dict[str, list[bytes]]:
    """The alive messages of each kind, in file order."""
    with open(path, "rb") as f:
        return parse_alive(f.read())


def parse_alive(data: bytes) -> dict[str, list[bytes]]:
    out = {"ca": [], "cu": [], "na": []}
    kind = {MSG_CA: "ca", MSG_CU: "cu", MSG_NA: "na"}
    off = 1
    while off + 12 <= len(data):
        flags = int.from_bytes(data[off:off + 2], "big")
        ln = int.from_bytes(data[off + 2:off + 4], "big")
        msg = data[off + 12:off + 12 + ln]
        off += 12 + ln
        k = kind.get(int.from_bytes(msg[:2], "big"))
        if k is not None and not flags & FLAG_DELETED:
            out[k].append(msg)
    return out


def ca_fields(msg: bytes) -> tuple[int, list[bytes]]:
    """(short_channel_id, [node_id_1, node_id_2, bitcoin_key_1, _2])."""
    flen = int.from_bytes(msg[258:260], "big")
    o = 260 + flen + 32
    scid = int.from_bytes(msg[o:o + 8], "big")
    o += 8
    return scid, [msg[o + 33 * i:o + 33 * (i + 1)] for i in range(4)]


def ca_valid(msg: bytes, *, skip: tuple = ()) -> bool:
    """All four signatures cover sha256d of what follows them.  `skip`
    is for the control and the planted faults: a checker that trusts
    the signatures at those positions (0..3) without a look."""
    _, keys = ca_fields(msg)
    h = ecdsa.sha256d(msg[258:])
    return all(ecdsa.verify(h, msg[2 + 64 * i:66 + 64 * i], keys[i])
               for i in range(4) if i not in skip)


def na_valid(msg: bytes) -> bool:
    flen = int.from_bytes(msg[66:68], "big")
    node_id = msg[68 + flen + 4:68 + flen + 4 + 33]
    return ecdsa.verify(ecdsa.sha256d(msg[66:]), msg[2:66], node_id)


def cu_valid(msg: bytes, keys_by_scid: dict[int, list[bytes]]) -> bool:
    """Signed by the channel's node on the update's side."""
    scid = int.from_bytes(msg[98:106], "big")
    keys = keys_by_scid.get(scid)
    if keys is None:
        return False
    return ecdsa.verify(ecdsa.sha256d(msg[66:]), msg[2:66],
                        keys[msg[111] & 1])
