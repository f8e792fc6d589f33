"""End-to-end node driving ENTIRELY over the unix-socket JSON-RPC:
connect → dev-faucet → fundchannel (real wallet coins, real funding tx
on the shared regtest chain, depth-gated lockin) → invoice → pay →
close → listpays/listfunds.

The integration shape that matters: the product
surface is the RPC socket, not library calls (lightningd/jsonrpc.c +
tests' pyln-driven flows).  Two full node stacks share one FakeBitcoind
chain, exactly like pyln-testing nodes share one regtest bitcoind.
"""
from __future__ import annotations

import asyncio
import json

from lightning_tpu.chain.backend import FakeBitcoind
from lightning_tpu.chain.topology import ChainTopology
from lightning_tpu.daemon.hsmd import CAP_SIGN_ONCHAIN, Hsm
from lightning_tpu.daemon.jsonrpc import JsonRpcServer, attach_core_commands
from lightning_tpu.daemon.manager import ChannelManager, attach_manager_commands
from lightning_tpu.daemon.node import LightningNode
from lightning_tpu.daemon.relay import Relay
from lightning_tpu.pay.htlc_set import HtlcSets
from lightning_tpu.pay.invoices import InvoiceRegistry
from lightning_tpu.pay.offers import (FetchInvoice, OfferRegistry,
                                      OffersService, OnionMessenger,
                                      attach_offers_commands)
from lightning_tpu.wallet.db import Db
from lightning_tpu.wallet.onchain import KeyManager, OnchainWallet
from lightning_tpu.wallet.wallet import Wallet
from lightning_tpu.wallet.walletrpc import attach_wallet_commands


def run(coro):
    # generous: first run cold-compiles the EC kernels (~minutes on CPU)
    return asyncio.run(asyncio.wait_for(coro, 1500))


class Stack:
    """One daemon's full wiring (mirrors daemon/__main__.py)."""

    def __init__(self, tmp_path, name: str, secret: bytes,
                 bitcoind: FakeBitcoind):
        self.hsm = Hsm(secret)
        self.node = LightningNode(privkey=self.hsm.node_key)
        self.wallet = Wallet(Db(str(tmp_path / f"{name}.sqlite3")))
        self.bitcoind = bitcoind
        self.topology = ChainTopology(bitcoind, poll_interval=0.05)
        self.onchain = OnchainWallet(
            self.wallet.db, KeyManager(self.hsm.bip32_base(),
                                       self.wallet.db))
        self.onchain.attach(self.topology)
        self.invoices = InvoiceRegistry(self.hsm.node_key,
                                        db=self.wallet.db)
        self.relay = Relay()
        self.manager = ChannelManager(
            self.node, self.hsm, wallet=self.wallet, onchain=self.onchain,
            chain_backend=bitcoind, topology=self.topology,
            invoices=self.invoices, relay=self.relay,
            htlc_sets=HtlcSets(self.invoices))
        self.node.on_peer = self.manager.serve_inbound
        self.rpc = JsonRpcServer(str(tmp_path / f"{name}.rpc"))
        gref = {"map": None}
        attach_core_commands(self.rpc, self.node, gref,
                             manager=self.manager, topology=self.topology)
        attach_manager_commands(self.rpc, self.manager)
        attach_wallet_commands(
            self.rpc, self.onchain, hsm=self.hsm,
            hsm_client=self.hsm.client(CAP_SIGN_ONCHAIN),
            backend=bitcoind, topology=self.topology)
        from lightning_tpu.plugins.txprepare import (
            TxPrepare, attach_txprepare_commands)

        attach_txprepare_commands(
            self.rpc, TxPrepare(self.onchain, hsm=self.hsm,
                                hsm_client=self.hsm.client(
                                    CAP_SIGN_ONCHAIN),
                                backend=bitcoind,
                                topology=self.topology),
            hsm=self.hsm)
        messenger = OnionMessenger(self.node, self.hsm.node_key)
        offer_reg = OfferRegistry(self.wallet.db)
        svc = OffersService(messenger, offer_reg, self.invoices,
                            self.hsm.node_key)
        fetcher = FetchInvoice(messenger, self.hsm.node_key)
        attach_offers_commands(self.rpc, svc, fetcher, offer_reg,
                               self.invoices)

    async def start(self):
        await self.topology.start()
        await self.rpc.start()
        return self

    async def close(self):
        await self.rpc.close()
        await self.topology.stop()
        await self.node.close()
        self.wallet.db.close()


import sys


def _stage(msg):
    print(f"STAGE: {msg}", file=sys.stderr, flush=True)


async def rpc_call(path: str, method: str, params=None):
    reader, writer = await asyncio.open_unix_connection(path)
    req = {"jsonrpc": "2.0", "id": 1, "method": method,
           "params": params or {}}
    writer.write(json.dumps(req).encode())
    await writer.drain()
    buf = b""
    while b"\n\n" not in buf:
        chunk = await reader.read(65536)
        if not chunk:
            break
        buf += chunk
    writer.close()
    resp = json.loads(buf.decode().strip())
    assert "error" not in resp, resp.get("error")
    return resp["result"]


def test_connect_fund_invoice_pay_close(tmp_path):
    async def body():
        bitcoind = FakeBitcoind()
        bitcoind.generate(1)
        a = await Stack(tmp_path, "a", b"\x0a" * 32, bitcoind).start()
        b = await Stack(tmp_path, "b", b"\x0b" * 32, bitcoind).start()
        ra, rb = a.rpc.rpc_path, b.rpc.rpc_path
        try:
            port = await b.node.listen()

            # 1. connect over RPC
            info_b = await rpc_call(rb, "getinfo")
            _stage("connect")
            got = await rpc_call(ra, "connect", {
                "id": f"{info_b['id']}@127.0.0.1:{port}"})
            assert got["id"] == info_b["id"]

            # 2. faucet + fundchannel (the funding tx spends REAL coins)
            _stage("faucet")
            await rpc_call(ra, "dev-faucet", {"satoshi": 2_000_000})
            funds = await rpc_call(ra, "listfunds")
            assert funds["outputs"][0]["status"] == "confirmed"

            _stage("fundchannel-start")
            fund_task = asyncio.create_task(rpc_call(ra, "fundchannel", {
                "id": info_b["id"], "amount": 1_000_000}))
            # the funding tx sits in the shared mempool until a block
            # confirms it; lockin is depth-gated on BOTH sides.  The
            # wait is generous: a cold EC-kernel compile inside the
            # open dance takes minutes on CPU.
            for _ in range(6000):
                if bitcoind.mempool or fund_task.done():
                    break
                await asyncio.sleep(0.1)
            if not fund_task.done():
                assert bitcoind.mempool, "funding tx never broadcast"
                bitcoind.generate(1)
            _stage("fundchannel-await")
            opened = await asyncio.wait_for(fund_task, 600)
            assert opened["funding_txid"]

            info_a = await rpc_call(ra, "getinfo")
            assert info_a["num_active_channels"] == 1
            assert info_a["blockheight"] >= 2

            chans = await rpc_call(ra, "listpeerchannels")
            assert chans["channels"][0]["state"] == "NORMAL"
            assert chans["channels"][0]["total_msat"] == 1_000_000_000

            # change from the funding tx came back to the wallet
            funds = await rpc_call(ra, "listfunds")
            assert any(o["amount_msat"] < 1_000_000_000
                       for o in funds["outputs"])

            # 3. invoice on B, pay from A — all over the sockets
            _stage("invoice")
            inv = await rpc_call(rb, "invoice", {
                "amount_msat": 123_000, "label": "rpc-e2e",
                "description": "end to end"})
            _stage("pay")
            paid = await rpc_call(ra, "pay", {"bolt11": inv["bolt11"]})
            assert paid["status"] == "complete"
            assert paid["amount_msat"] == 123_000

            got_inv = await rpc_call(rb, "listinvoices",
                                     {"label": "rpc-e2e"})
            assert got_inv["invoices"][0]["status"] == "paid"
            pays = await rpc_call(ra, "listpays")
            assert pays["pays"][0]["status"] == "complete"

            # 4. cooperative close over RPC
            _stage("close")
            closed = await rpc_call(ra, "close", {
                "id": opened["channel_id"]})
            assert closed["type"] == "mutual"
            # the closing tx reached the shared chain
            assert any(t.hex() == closed["txid"]
                       for t in bitcoind.mempool)
            info_a = await rpc_call(ra, "getinfo")
            assert info_a["num_active_channels"] == 0
        finally:
            await a.close()
            await b.close()

    run(body())


def test_keysend_and_listhtlcs(tmp_path):
    """Spontaneous payment over RPC: the preimage rides the onion and
    the recipient books income with no invoice (plugins/keysend.c)."""
    async def body():
        bitcoind = FakeBitcoind()
        bitcoind.generate(1)
        a = await Stack(tmp_path, "a", b"\x0a" * 32, bitcoind).start()
        b = await Stack(tmp_path, "b", b"\x0b" * 32, bitcoind).start()
        try:
            port = await b.node.listen()
            info_b = await rpc_call(b.rpc.rpc_path, "getinfo")
            await rpc_call(a.rpc.rpc_path, "connect", {
                "id": f"{info_b['id']}@127.0.0.1:{port}"})
            await rpc_call(a.rpc.rpc_path, "dev-faucet",
                           {"satoshi": 2_000_000})
            fund = asyncio.create_task(rpc_call(a.rpc.rpc_path,
                                                "fundchannel", {
                "id": info_b["id"], "amount": 1_000_000}))
            while not bitcoind.mempool and not fund.done():
                await asyncio.sleep(0.05)
            if bitcoind.mempool:
                bitcoind.generate(1)
            await asyncio.wait_for(fund, 600)

            sent = await rpc_call(a.rpc.rpc_path, "keysend", {
                "destination": info_b["id"], "amount_msat": 12_345_000,
                "retry_for": 300})
            assert sent["status"] == "complete"
            # the preimage resolves at fulfill receipt; the balance
            # lands when the removal dance settles moments later
            for _ in range(200):
                chans_b = await rpc_call(b.rpc.rpc_path,
                                         "listpeerchannels")
                if chans_b["channels"][0]["to_us_msat"] == 12_345_000:
                    break
                await asyncio.sleep(0.1)
            assert chans_b["channels"][0]["to_us_msat"] == 12_345_000
            # no HTLCs left in flight once the final revoke lands
            for _ in range(200):
                htlcs = await rpc_call(a.rpc.rpc_path, "listhtlcs")
                if not htlcs["htlcs"]:
                    break
                await asyncio.sleep(0.1)
            assert htlcs["htlcs"] == []
            # and the keysend shows in the payments log
            pays = await rpc_call(a.rpc.rpc_path, "listpays")
            assert any(p["status"] == "complete"
                       and p["payment_hash"] == sent["payment_hash"]
                       for p in pays["pays"])
        finally:
            await a.close()
            await b.close()

    run(body())


def test_sendamount_fixed_total(tmp_path):
    """sendamount spends a FIXED total against an amount-less invoice:
    for a direct peer the fee is zero, so the destination receives
    exactly the amount (sendamount.json semantics)."""
    async def body():
        bitcoind = FakeBitcoind()
        bitcoind.generate(1)
        a = await Stack(tmp_path, "a", b"\x3a" * 32, bitcoind).start()
        b = await Stack(tmp_path, "b", b"\x3b" * 32, bitcoind).start()
        try:
            port = await b.node.listen()
            info_b = await rpc_call(b.rpc.rpc_path, "getinfo")
            await rpc_call(a.rpc.rpc_path, "connect", {
                "id": f"{info_b['id']}@127.0.0.1:{port}"})
            await rpc_call(a.rpc.rpc_path, "dev-faucet",
                           {"satoshi": 2_000_000})
            fund = asyncio.create_task(rpc_call(a.rpc.rpc_path,
                                                "fundchannel", {
                "id": info_b["id"], "amount": 1_000_000}))
            while not bitcoind.mempool and not fund.done():
                await asyncio.sleep(0.05)
            if bitcoind.mempool:
                bitcoind.generate(1)
            await asyncio.wait_for(fund, 600)

            inv = await rpc_call(b.rpc.rpc_path, "invoice", {
                "amount_msat": "any", "label": "open-amt",
                "description": "fixed-total"})
            sent = await rpc_call(a.rpc.rpc_path, "sendamount", {
                "invstring": inv["bolt11"],
                "amount_msat": 7_000_000, "retry_for": 300})
            assert sent["amount_msat"] == 7_000_000
            assert sent["amount_sent_msat"] == 7_000_000  # direct: no fee
            for _ in range(200):
                chans_b = await rpc_call(b.rpc.rpc_path,
                                         "listpeerchannels")
                if chans_b["channels"][0]["to_us_msat"] == 7_000_000:
                    break
                await asyncio.sleep(0.1)
            assert chans_b["channels"][0]["to_us_msat"] == 7_000_000
        finally:
            await a.close()
            await b.close()

    run(body())
