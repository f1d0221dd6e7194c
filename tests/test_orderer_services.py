"""Registrar + broadcast + deliver service tests (reference
orderer/common/multichannel, broadcast, common/deliver test strategy:
in-process fakes, real block stores)."""

import threading
import time

import pytest

from fabric_tpu.common.deliver import DeliverService, make_seek_info_envelope
from fabric_tpu.orderer.broadcast import BroadcastHandler
from fabric_tpu.orderer.multichannel import Registrar
from fabric_tpu.protos.common import common_pb2
from fabric_tpu.protos.orderer import ab_pb2
from fabric_tpu import protoutil

from fabric_tpu.common import configtx_builder as ctx
from fabric_tpu.msp import msp_config_from_ca

from orgfix import make_org


class _OrgSetup:
    def __init__(self):
        self.org1 = make_org("Org1MSP")
        oorg = make_org("OrdererMSP")
        app = ctx.application_group(
            {"Org1": ctx.org_group("Org1MSP", msp_config_from_ca(self.org1.ca, "Org1MSP"))}
        )
        ordg = ctx.orderer_group(
            {
                "OrdererOrg": ctx.org_group(
                    "OrdererMSP", msp_config_from_ca(oorg.ca, "OrdererMSP")
                )
            },
            consensus_type="solo",
            max_message_count=2,
            batch_timeout="250ms",
        )
        self.channel_id = "testchannel"
        self.genesis = ctx.genesis_block(
            self.channel_id, ctx.channel_group(app, ordg)
        )
        self.csp = self.org1.csp
        self.admin = self.org1.signer("admin", role_ou="admin")


@pytest.fixture(scope="module")
def org():
    return _OrgSetup()


@pytest.fixture
def registrar(org, tmp_path):
    reg = Registrar(str(tmp_path), org.csp)
    reg.startup([org.genesis])
    yield reg
    reg.halt_all()


def _tx_env(org, data: bytes) -> common_pb2.Envelope:
    chdr = protoutil.make_channel_header(
        common_pb2.ENDORSER_TRANSACTION, channel_id=org.channel_id
    )
    shdr = protoutil.make_signature_header(
        org.admin.serialize(), protoutil.random_nonce()
    )
    payload = common_pb2.Payload(data=data)
    payload.header.channel_header = chdr.SerializeToString()
    payload.header.signature_header = shdr.SerializeToString()
    raw = payload.SerializeToString()
    return common_pb2.Envelope(payload=raw, signature=org.admin.sign(raw))


def test_broadcast_orders_into_blocks(registrar, org):
    h = BroadcastHandler(registrar)
    cs = registrar.get_chain(org.channel_id)
    notifier_fired = threading.Event()
    registrar.add_block_listener(lambda ch, blk: notifier_fired.set())
    for i in range(3):
        assert h.process_message(_tx_env(org, b"d%d" % i)) == common_pb2.SUCCESS
    deadline = time.monotonic() + 10
    while cs.store.height < 2 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert cs.store.height >= 2
    assert notifier_fired.is_set()


def test_broadcast_unknown_channel(registrar, org):
    h = BroadcastHandler(registrar)
    chdr = protoutil.make_channel_header(
        common_pb2.ENDORSER_TRANSACTION, channel_id="no-such-channel"
    )
    payload = common_pb2.Payload(data=b"x")
    payload.header.channel_header = chdr.SerializeToString()
    env = common_pb2.Envelope(payload=payload.SerializeToString())
    assert h.process_message(env) == common_pb2.NOT_FOUND


def test_broadcast_rejects_unsigned(registrar, org):
    h = BroadcastHandler(registrar)
    chdr = protoutil.make_channel_header(
        common_pb2.ENDORSER_TRANSACTION, channel_id=org.channel_id
    )
    shdr = protoutil.make_signature_header(b"not-an-identity", b"nonce")
    payload = common_pb2.Payload(data=b"x")
    payload.header.channel_header = chdr.SerializeToString()
    payload.header.signature_header = shdr.SerializeToString()
    env = common_pb2.Envelope(payload=payload.SerializeToString())
    assert h.process_message(env) == common_pb2.FORBIDDEN


def test_deliver_streams_existing_and_new_blocks(registrar, org):
    h = BroadcastHandler(registrar)
    svc = DeliverService(registrar.get_chain, org.csp)
    registrar.add_block_listener(lambda ch, blk: svc.notifier.notify())
    for i in range(3):
        h.process_message(_tx_env(org, b"d%d" % i))
    cs = registrar.get_chain(org.channel_id)
    deadline = time.monotonic() + 10
    while cs.store.height < 2 and time.monotonic() < deadline:
        time.sleep(0.02)

    env = make_seek_info_envelope(
        org.channel_id, 0, cs.store.height - 1, signer=org.admin,
        behavior=ab_pb2.SeekInfo.FAIL_IF_NOT_READY,
    )
    events = list(svc.deliver(env))
    kinds = [k for k, _ in events]
    assert kinds[-1] == "status" and events[-1][1] == common_pb2.SUCCESS
    blocks = [b for k, b in events if k == "block"]
    assert [b.header.number for b in blocks] == list(range(cs.store.height))
    assert blocks[0].header.number == 0  # genesis


def test_deliver_block_until_ready_waits(registrar, org):
    svc = DeliverService(registrar.get_chain, org.csp)
    registrar.add_block_listener(lambda ch, blk: svc.notifier.notify())
    h = BroadcastHandler(registrar)
    got: list = []

    def consume():
        env = make_seek_info_envelope(org.channel_id, 1, 1, signer=org.admin)
        for kind, item in svc.deliver(env):
            got.append((kind, item))

    from fabric_tpu.devtools.lockwatch import spawn_thread

    t = spawn_thread(target=consume, name="deliver-consume", kind="worker")
    t.start()
    time.sleep(0.2)
    assert not got  # waiting for block 1
    for i in range(3):
        h.process_message(_tx_env(org, b"w%d" % i))
    t.join(timeout=10)
    assert got and got[0][0] == "block" and got[0][1].header.number == 1


def test_deliver_forbidden_without_signature(registrar, org):
    svc = DeliverService(registrar.get_chain, org.csp)
    env = make_seek_info_envelope(org.channel_id, 0, 0, signer=None)
    events = list(svc.deliver(env))
    assert events == [("status", common_pb2.FORBIDDEN)]


def test_deliver_unknown_channel(registrar, org):
    svc = DeliverService(registrar.get_chain, org.csp)
    env = make_seek_info_envelope("ghost", 0, 0, signer=org.admin)
    assert list(svc.deliver(env)) == [("status", common_pb2.NOT_FOUND)]


# -- maintenance mode + consensus-type migration ---------------------------
# (reference orderer/common/msgprocessor/maintenancefilter.go:31-44)


class _MigrationWorld:
    """A solo channel whose admins can drive config updates end to end."""

    def __init__(self, tmp_path):
        from fabric_tpu.common import configtx_builder as cb

        self.org1 = make_org("Org1MSP")
        self.oorg = make_org("OrdererMSP")
        app = ctx.application_group(
            {"Org1": ctx.org_group(
                "Org1MSP", msp_config_from_ca(self.org1.ca, "Org1MSP"))}
        )
        ordg = ctx.orderer_group(
            {"OrdererOrg": ctx.org_group(
                "OrdererMSP", msp_config_from_ca(self.oorg.ca, "OrdererMSP"))},
            consensus_type="solo",
            max_message_count=1,
            batch_timeout="200ms",
        )
        self.channel_id = "migrch"
        self.genesis = ctx.genesis_block(
            self.channel_id, ctx.channel_group(app, ordg)
        )
        self.csp = self.org1.csp
        self.client = self.org1.signer("client", role_ou="client")
        self.orderer_admin = self.oorg.signer("oadmin", role_ou="admin")
        from fabric_tpu.orderer.kafka import InProcBroker

        self.registrar = Registrar(
            str(tmp_path), self.csp,
            signer=self.oorg.signer("orderer0", role_ou="orderer"),
            consenter_overrides={"broker": InProcBroker()},
        )
        self.registrar.startup([self.genesis])
        self.handler = BroadcastHandler(self.registrar)

    def current_config(self):
        return self.registrar.get_chain(self.channel_id).bundle.config

    def update_env(self, mutate):
        """Signed CONFIG_UPDATE envelope transforming the current config
        with `mutate(updated_config)`."""
        from fabric_tpu.common.configtx import compute_update
        from fabric_tpu.protos.common import configtx_pb2

        cur = self.current_config()
        upd_cfg = configtx_pb2.Config()
        upd_cfg.CopyFrom(cur)
        mutate(upd_cfg)
        update = compute_update(self.channel_id, cur, upd_cfg)
        ue = configtx_pb2.ConfigUpdateEnvelope(
            config_update=update.SerializeToString()
        )
        shdr = protoutil.make_signature_header(
            self.orderer_admin.serialize(), protoutil.random_nonce()
        ).SerializeToString()
        ue.signatures.add(
            signature_header=shdr,
            signature=self.orderer_admin.sign(
                shdr + ue.config_update
            ),
        )
        chdr = protoutil.make_channel_header(
            common_pb2.CONFIG_UPDATE, channel_id=self.channel_id
        )
        payload = protoutil.make_payload_bytes(
            chdr,
            protoutil.make_signature_header(
                self.orderer_admin.serialize(), protoutil.random_nonce()
            ),
            ue.SerializeToString(),
        )
        return protoutil.make_envelope(payload, signer=self.orderer_admin)

    def set_consensus(self, cfg, ctype=None, state=None):
        from fabric_tpu.common import configtx_builder as cb
        from fabric_tpu.protos.orderer import configuration_pb2 as ocp

        og = cfg.channel_group.groups["Orderer"]
        cur = ocp.ConsensusType.FromString(
            og.values[cb.CONSENSUS_TYPE_KEY].value
        )
        if ctype is not None:
            cur.type = ctype
        if state is not None:
            cur.state = state
        og.values[cb.CONSENSUS_TYPE_KEY].value = cur.SerializeToString()

    def normal_tx(self, signer, data=b"tx"):
        chdr = protoutil.make_channel_header(
            common_pb2.ENDORSER_TRANSACTION, channel_id=self.channel_id
        )
        shdr = protoutil.make_signature_header(
            signer.serialize(), protoutil.random_nonce()
        )
        payload = common_pb2.Payload(data=data)
        payload.header.channel_header = chdr.SerializeToString()
        payload.header.signature_header = shdr.SerializeToString()
        raw = payload.SerializeToString()
        return common_pb2.Envelope(payload=raw, signature=signer.sign(raw))

    def wait_height(self, h, timeout=10.0):
        cs = self.registrar.get_chain(self.channel_id)
        deadline = time.time() + timeout
        while cs.store.height < h and time.time() < deadline:
            time.sleep(0.02)
        return cs.store.height


def test_consensus_migration_through_maintenance_mode(tmp_path):
    """Full migration flow: type change rejected in NORMAL; enter
    maintenance; client txs rejected while orderer admins still write;
    type change accepted in maintenance; exit maintenance; the channel
    orders through the NEW consenter."""
    from fabric_tpu.orderer.msgprocessor import (
        STATE_MAINTENANCE,
        STATE_NORMAL,
    )

    w = _MigrationWorld(tmp_path)
    try:
        reg, h = w.registrar, w.handler
        # 0) type change outside maintenance is FORBIDDEN
        env = w.update_env(
            lambda c: w.set_consensus(c, ctype="kafka")
        )
        assert h.process_message(env) == common_pb2.FORBIDDEN

        # 1) enter maintenance (type unchanged) — accepted
        env = w.update_env(
            lambda c: w.set_consensus(c, state=STATE_MAINTENANCE)
        )
        assert h.process_message(env) == common_pb2.SUCCESS
        hh = w.wait_height(2)
        assert hh == 2
        cs = reg.get_chain(w.channel_id)
        assert cs.processor.in_maintenance()

        # 2) while in maintenance, client txs are rejected...
        assert (
            h.process_message(w.normal_tx(w.client))
            == common_pb2.FORBIDDEN
        )
        # ...and entering again with a simultaneous exit+type change fails
        env = w.update_env(
            lambda c: w.set_consensus(c, ctype="kafka", state=STATE_NORMAL)
        )
        assert h.process_message(env) == common_pb2.FORBIDDEN

        # 3) change the consensus type INSIDE maintenance — accepted;
        #    the registrar swaps the consenter (solo -> kafka)
        env = w.update_env(lambda c: w.set_consensus(c, ctype="kafka"))
        assert h.process_message(env) == common_pb2.SUCCESS
        assert w.wait_height(3) == 3
        deadline = time.time() + 5
        from fabric_tpu.orderer.kafka import KafkaChain

        while time.time() < deadline and not isinstance(
            reg.get_chain(w.channel_id).chain, KafkaChain
        ):
            time.sleep(0.05)
        assert isinstance(reg.get_chain(w.channel_id).chain, KafkaChain)

        # 4) exit maintenance (type now stays kafka) — accepted
        env = w.update_env(
            lambda c: w.set_consensus(c, state=STATE_NORMAL)
        )
        assert h.process_message(env) == common_pb2.SUCCESS
        assert w.wait_height(4) == 4
        assert not reg.get_chain(w.channel_id).processor.in_maintenance()

        # 5) normal client traffic orders through the NEW consenter
        assert (
            h.process_message(w.normal_tx(w.client)) == common_pb2.SUCCESS
        )
        assert w.wait_height(5) == 5
    finally:
        w.registrar.halt_all()


def test_maintenance_filter_unit_rules(tmp_path):
    """Filter matrix at the unit level (the e2e migration test covers
    the happy path): every NORMAL-state type change is rejected, both
    maintenance transitions keep the type, removal of the Orderer group
    is rejected."""
    from fabric_tpu.orderer.msgprocessor import (
        MsgProcessorError,
        STATE_MAINTENANCE,
        STATE_NORMAL,
    )

    w = _MigrationWorld(tmp_path)
    try:
        cs = w.registrar.get_chain(w.channel_id)
        proc = cs.processor
        from fabric_tpu.protos.common import configtx_pb2

        def cfg_with(ctype=None, state=None, drop_orderer=False):
            c = configtx_pb2.Config()
            c.CopyFrom(w.current_config())
            c.sequence += 1
            if drop_orderer:
                del c.channel_group.groups["Orderer"]
            else:
                w.set_consensus(c, ctype=ctype, state=state)
            return c

        # NORMAL -> type change: rejected
        with pytest.raises(MsgProcessorError):
            proc._maintenance_filter(cfg_with(ctype="kafka"))
        # NORMAL -> enter maintenance, same type: allowed
        proc._maintenance_filter(cfg_with(state=STATE_MAINTENANCE))
        # Orderer group removal: rejected
        with pytest.raises(MsgProcessorError):
            proc._maintenance_filter(cfg_with(drop_orderer=True))
        # while IN maintenance: type change allowed; exit+change rejected
        import dataclasses

        oc = cs.bundle.orderer_config
        cs.bundle.orderer_config = dataclasses.replace(
            oc, consensus_state=STATE_MAINTENANCE
        )
        proc._maintenance_filter(
            cfg_with(ctype="kafka", state=STATE_MAINTENANCE)
        )
        with pytest.raises(MsgProcessorError):
            proc._maintenance_filter(
                cfg_with(ctype="kafka", state=STATE_NORMAL)
            )
        # while IN maintenance: touching anything OUTSIDE the Orderer
        # group rides along a migration update — rejected
        # (maintenancefilter.go ensures only-Orderer changes)
        tainted = cfg_with(ctype="kafka", state=STATE_MAINTENANCE)
        tainted.channel_group.groups["Application"].version += 1
        with pytest.raises(MsgProcessorError):
            proc._maintenance_filter(tainted)
        cs.bundle.orderer_config = oc
    finally:
        w.registrar.halt_all()


# -- the solo consenter's batch timer (upstream orderer/consensus/solo) ------


class _ListWriter:
    """Stands where the BlockWriter stands: keeps each batch and when
    it was cut."""

    def __init__(self):
        self.cuts: list = []          # (monotonic time, batch)

    def create_next_block(self, batch):
        return list(batch)

    def write_block(self, blk, is_config=False):
        self.cuts.append((time.monotonic(), blk))


def test_solo_batch_timer_runs_from_the_first_message_of_a_batch():
    """The timer is armed by the message that enters an empty batch
    and is NOT restarted by the messages that follow: a channel whose
    messages come closer together than BatchTimeout still gets a block
    every BatchTimeout.  (A timeout counted from the LAST message cut
    nothing here until the messages stopped.)  A count cut that leaves
    nothing pending disarms it, and the next message arms it afresh."""
    from fabric_tpu.orderer.blockcutter import BlockCutter
    from fabric_tpu.orderer.solo import SoloChain

    timeout, gap, n = 0.3, 0.05, 30
    writer = _ListWriter()
    chain = SoloChain(BlockCutter(max_message_count=1000), writer, batch_timeout_s=timeout)
    chain.start()
    try:
        t_first = time.monotonic()
        for i in range(n):
            chain.order(common_pb2.Envelope(payload=b"m%d" % i))
            time.sleep(gap)
        t_last = time.monotonic()
        cuts_while_flowing = [c for c in writer.cuts if c[0] < t_last]
        time.sleep(2 * timeout)
    finally:
        chain.halt()
    # 1.5 s of messages 50 ms apart under a 0.3 s timeout: blocks were
    # cut while they flowed, the first no sooner than a timeout after
    # the first message and holding a part of them only
    assert len(cuts_while_flowing) >= 2
    assert cuts_while_flowing[0][0] - t_first >= timeout * 0.9
    assert 1 <= len(cuts_while_flowing[0][1]) < n
    # nothing lost, nothing twice, order kept; no empty block
    assert [m for _t, b in writer.cuts for m in b] == [
        common_pb2.Envelope(payload=b"m%d" % i).SerializeToString() for i in range(n)]
    assert all(b for _t, b in writer.cuts)


def test_solo_count_cut_disarms_the_timer_and_the_next_message_arms_it_afresh():
    from fabric_tpu.orderer.blockcutter import BlockCutter
    from fabric_tpu.orderer.solo import SoloChain

    timeout = 0.4
    writer = _ListWriter()
    chain = SoloChain(BlockCutter(max_message_count=3), writer, batch_timeout_s=timeout)
    chain.start()
    try:
        for i in range(3):                       # a full batch: cut by count
            chain.order(common_pb2.Envelope(payload=b"a%d" % i))
        time.sleep(0.75 * timeout)               # the old timer, were it still armed,
        t_lone = time.monotonic()                # would fire 0.25 timeouts from here
        chain.order(common_pb2.Envelope(payload=b"lone"))
        deadline = time.monotonic() + 10 * timeout
        while len(writer.cuts) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        chain.halt()
    assert [len(b) for _t, b in writer.cuts] == [3, 1]
    assert writer.cuts[1][0] - t_lone >= timeout * 0.9
