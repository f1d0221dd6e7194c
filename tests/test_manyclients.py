"""A channel of thousands of enrolled clients (one Fabric CA enrolment
certificate a user; `benchmarks/configs/manyclients-10k.json`): a
block's creators outnumber the per-block memo's reuse, the MSP caches'
100 entries and the provider's 256-key table.  Here, on the CPU at a
small size: such a block validates to the serial host validator's
flags on both collect paths; a creator whose only fault is its
certificate is refused and its neighbours are not; a flush past the
key table goes a key a lane and the next small one takes the table
again; and what the tracing and the counters say of a block is what
the block held.  The blocks are the benchmark's own
(`benchmarks/worlds/x509-manyclients.py`)."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from fabric_tpu.common import tracing  # noqa: E402
from fabric_tpu.csp import SWCSP  # noqa: E402
from fabric_tpu.csp.api import VerifyBatchItem  # noqa: E402
from fabric_tpu.csp.tpu import pallas_ec  # noqa: E402
from fabric_tpu.csp.tpu.provider import TPUCSP, _KeyTable  # noqa: E402

SEED = 2**31 + 32
BAD_CREATOR, MVCC_CONFLICT, VALID = 4, 11, 0
NO_FAULTS = {
    "bad_creator_per_block": 0, "bad_endorsement_per_block": 0,
    "conflict_pairs_per_block": 0, "rogue_ca_creators_per_block": 0,
    "expired_creators_per_block": 0, "revoked_creators_per_block": 0,
    "no_role_ou_creators_per_block": 0,
}


def _build(planted: dict, **deployment):
    from benchlib.manifest import Manifest

    man = Manifest(ROOT)
    with open(os.path.join(BENCH, "configs", "manyclients-10k.json")) as f:
        held = json.load(f)
    dep = dict(held["deployment"], orgs=1, endorsers_per_tx=1, **deployment)
    world = man.world(held)(SEED, dep, dict(held["planted"], **planted), 1)
    return man, held, dep, world


@pytest.fixture(scope="module")
def crowd():
    """One 560-tx block of a one-organisation channel whose creators
    outnumber the key table, with every kind of fault planted."""
    man, held, dep, world = _build({}, block_txs=560)
    assert world.creators_per_block[0] > pallas_ec.KEYTAB
    return man, held, dep, world


def _validator(world, csp, python_collect=False, faithful=False):
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.peer.txvalidator import TxValidator

    ledger = LedgerProvider(None).create(world.genesis)
    v = TxValidator(world.channel, ledger, bundle_from_genesis(world.genesis, csp), csp,
                    faithful=faithful)
    if python_collect:
        v._collect_native = lambda *a, **k: False
    return v


def _block(world, b=0):
    from fabric_tpu.protos.common import common_pb2

    return common_pb2.Block.FromString(world.blocks[b])


@pytest.fixture(scope="module")
def serial_flags(crowd):
    """The judge: the reference's cost model (no memo, no interning, a
    verify a signature) over `SWCSP`."""
    world = crowd[3]
    return list(_validator(world, SWCSP(), faithful=True).validate(_block(world)))


@pytest.mark.parametrize("python_collect", [False, True], ids=["native", "python"])
def test_a_block_of_more_creators_than_the_key_table_validates_as_the_serial_validator_does(
        crowd, serial_flags, python_collect):
    from fabric_tpu import native

    if not python_collect and not native.available():
        pytest.skip(f"no native collector: {native.load_error()}")
    world = crowd[3]
    v = _validator(world, SWCSP(), python_collect=python_collect)
    got = list(v.validate(_block(world)))
    assert got == serial_flags
    # the validator does not run MVCC: the planted conflicts are the ledger's
    assert got == [VALID if f == MVCC_CONFLICT else f for f in world.planted[0]]
    assert got.count(BAD_CREATOR) == 3 + 2 + 1 + 1 + 1


def test_the_plain_reference_reads_the_crowded_block_as_planted(crowd):
    man, held, dep, world = crowd
    flags, states = man.reference(held)(world.public, dep, world.blocks)
    assert [list(f) for f in flags] == [list(p) for p in world.planted]
    assert states[-1] == world.expected_state()


@pytest.mark.parametrize("fault", [
    "rogue_ca_creators_per_block", "expired_creators_per_block",
    "revoked_creators_per_block", "no_role_ou_creators_per_block",
])
@pytest.mark.parametrize("python_collect", [False, True], ids=["native", "python"])
def test_a_creator_whose_only_fault_is_its_certificate_is_refused_alone(fault, python_collect):
    man, held, dep, world = _build(dict(NO_FAULTS, **{fault: 1}), block_txs=8)
    want = list(world.planted[0])
    assert sorted(want) == [VALID] * 7 + [BAD_CREATOR]
    got = list(_validator(world, SWCSP(), python_collect=python_collect).validate(_block(world)))
    assert got == want
    flags, _states = man.reference(held)(world.public, dep, world.blocks)
    assert list(flags[0]) == want
    # the same identities pass an MSP that validates nothing: the fault
    # is the certificate's, and the signature is sound
    from fabric_tpu.msp.msp import MSP

    real = MSP.validate
    try:
        MSP.validate = lambda self, identity: None
        lax = list(_validator(world, SWCSP(), python_collect=python_collect)
                   .validate(_block(world)))
    finally:
        MSP.validate = real
    assert lax == [VALID] * 8


@pytest.mark.parametrize("python_collect", [False, True], ids=["native", "python"])
def test_a_creator_whose_issuer_name_does_not_decode_is_refused_alone_in_the_crowded_block(
        crowd, serial_flags, python_collect):
    """A certificate whose issuer raises when first read (it loads:
    cryptography parses a Name on access) costs its own transaction
    flag 4 and the block nothing: the batch ahead of the loop leaves it
    to its `validate`, whose guard refuses it alone."""
    from fabric_tpu import native
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protoutil.common import compute_tx_id
    from orgfix import undecodable_issuer

    if not python_collect and not native.available():
        pytest.skip(f"no native collector: {native.load_error()}")
    world = crowd[3]
    block = _block(world)
    at = next(i for i in range(100, len(serial_flags))
              if serial_flags[i - 1:i + 2] == [VALID] * 3)
    env = common_pb2.Envelope.FromString(block.data.data[at])
    payload = common_pb2.Payload.FromString(env.payload)
    shdr = common_pb2.SignatureHeader.FromString(payload.header.signature_header)
    chdr = common_pb2.ChannelHeader.FromString(payload.header.channel_header)
    shdr.creator = undecodable_issuer(shdr.creator)
    chdr.tx_id = compute_tx_id(shdr.nonce, shdr.creator)
    payload.header.signature_header = shdr.SerializeToString()
    payload.header.channel_header = chdr.SerializeToString()
    env.payload = payload.SerializeToString()
    block.data.data[at] = env.SerializeToString()
    want = list(serial_flags)
    want[at] = BAD_CREATOR
    serial = common_pb2.Block.FromString(block.SerializeToString())
    assert list(_validator(world, SWCSP(), faithful=True).validate(serial)) == want
    v = _validator(world, SWCSP(), python_collect=python_collect)
    with tracing.scope() as rec:
        got = list(v.validate(block))
        events = tracing.export(rec)["traceEvents"]
    assert got == want
    (collect,) = [e for e in events if e.get("name") == "collect"]
    distinct = world.creators_per_block[0] + 1
    assert collect["args"]["creators"] == distinct
    assert collect["args"]["creator_validations"] == distinct
    if not python_collect and native.ecdsa_verify_host([]) is not None:
        # every creator's chain signature but the one that cannot be read
        assert collect["args"]["creator_chain_batch"] == distinct - 1


# -- the key table and the per-lane layout ---------------------------------


def _signed(n_keys: int, n_items: int, csp=None):
    csp = csp or SWCSP()
    keys = [csp.key_gen() for _ in range(n_keys)]
    items = []
    for i in range(n_items):
        key = keys[i % n_keys]
        digest = csp.hash(b"manyclients-%d" % i)
        sig = csp.sign(key, digest)
        if i % 7 == 3:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        items.append(VerifyBatchItem(key.public_key(), digest, sig))
    return items


def _key_words(items) -> np.ndarray:
    """(16, B): the words of every lane's public key, x then y."""
    return np.concatenate([
        np.stack([_KeyTable._words(it.key.x_bytes) for it in items], axis=1),
        np.stack([_KeyTable._words(it.key.y_bytes) for it in items], axis=1),
    ])


def _lane_keys(layout: dict) -> np.ndarray:
    """The keys a layout hands the kernel, a lane at a time."""
    if "kidx" in layout:
        idx = np.asarray(layout["kidx"])
        return np.concatenate([np.asarray(layout["ktabx"])[:, idx],
                               np.asarray(layout["ktaby"])[:, idx]])
    return np.concatenate([np.asarray(layout["qx"]), np.asarray(layout["qy"])])


def test_a_flush_past_the_table_goes_a_key_a_lane_and_the_next_small_one_takes_the_table():
    sw = SWCSP()
    crowded = _signed(pallas_ec.KEYTAB + 44, 330, sw)
    small = _signed(5, 40, sw)
    table = _KeyTable()
    assert table.last_outcome == "resident"

    assert table.assign([it.key for it in small]) is not None
    assert table.last_outcome == "grown"
    assert table.assign([it.key for it in small]) is not None
    assert table.last_outcome == "resident"

    # past the table: no index a lane, and dedup_keys hands the packed
    # batch over as it came, having counted its keys
    assert table.assign([it.key for it in crowded]) is None
    assert table.last_outcome == "per_lane"
    packed = TPUCSP._marshal_native(crowded)
    if packed is None:
        packed = pallas_ec.prepare_packed(
            next(iter(TPUCSP(min_device_batch=1)._tuple_chunks(crowded)))[0])
    seen: dict = {}
    handed = pallas_ec.dedup_keys(packed, seen)
    assert handed is packed and "kidx" not in handed
    assert seen == {"distinct": pallas_ec.KEYTAB + 44}
    assert (_lane_keys(handed)[:, :len(crowded)] == _key_words(crowded)).all()

    # the next small flush takes the table again, which the crowd left empty
    kidx = table.assign([it.key for it in small])
    assert kidx is not None and table.last_outcome == "grown"
    layout = {"kidx": kidx, "ktabx": table._ktabx, "ktaby": table._ktaby}
    assert (_lane_keys(layout) == _key_words(small)).all()
    assert len(set(kidx.tolist())) == 5
    assert table.assign([it.key for it in small]) is not None
    assert table.last_outcome == "resident"
    # a batch that fits the table but not beside what it holds: cleared, refilled
    wide = crowded[:pallas_ec.KEYTAB - 2]
    kidx = table.assign([it.key for it in wide])
    assert kidx is not None and table.last_outcome == "reset"
    layout = {"kidx": kidx, "ktabx": table._ktabx, "ktaby": table._ktaby}
    assert (_lane_keys(layout) == _key_words(wide)).all()

    # the same keys a lane either way, so the same verdicts: the two
    # kernels' parity on one layout each is tests/test_pallas_ec.py's


class _NoKernel:
    """`pallas_ec.verify_packed` for a backend without Mosaic: keeps
    the layout it was handed and seals every lane true."""

    def __init__(self):
        self.layouts = []

    def __call__(self, packed):
        self.layouts.append(packed)
        b = (packed["kidx"] if "kidx" in packed else packed["qx"]).shape[-1]
        return lambda: np.ones(b, bool)


def test_the_dispatch_says_which_kernel_took_a_flush_and_how_its_keys_came_out(monkeypatch):
    """The TPU branch of `_dispatch` up to the kernel call, on this
    backend: `tpu.keytable{outcome, distinct}`, `tpu.enqueue{kernel}`,
    `csp_tpu_keytable_flushes_total{outcome}`."""
    import jax

    from fabric_tpu import native
    from fabric_tpu.common.metrics import CSPMetrics, PrometheusProvider

    if not native.available():
        pytest.skip(f"no native marshaller: {native.load_error()}")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernel = _NoKernel()
    monkeypatch.setattr(pallas_ec, "verify_packed", kernel)
    prov = PrometheusProvider()
    csp = TPUCSP(min_device_batch=1, stall_factor=None, metrics=CSPMetrics(prov))
    small, crowded = _signed(5, 40), _signed(pallas_ec.KEYTAB + 4, 300)
    wide = crowded[:pallas_ec.KEYTAB - 2]
    flushes = (small, small, crowded, small, wide)
    try:
        with tracing.scope() as rec:
            for items in flushes:
                assert csp.verify_batch_async(items)() == [True] * len(items)
            events = tracing.export(rec)["traceEvents"]
    finally:
        csp.close()
    tables = [e["args"] for e in events if e.get("name") == "tpu.keytable"]
    assert [(a["outcome"], a["distinct"]) for a in tables] == [
        ("grown", 5), ("resident", 5), ("per_lane", pallas_ec.KEYTAB + 4),
        ("grown", 5), ("reset", pallas_ec.KEYTAB - 2)]
    table, lane = "pallas_ec_p256_verify_ktab", "pallas_ec_p256_verify"
    enq = [e["args"] for e in events if e.get("name") == "tpu.enqueue"]
    assert [(a["kernel"], a["lanes"]) for a in enq] == [
        (table, 40), (table, 40), (lane, 300), (table, 40), (table, len(wide))]
    assert ["kidx" in layout for layout in kernel.layouts] == [True, True, False, True, True]
    for layout, items in zip(kernel.layouts, flushes):
        assert (_lane_keys(layout)[:, :len(items)] == _key_words(items)).all()
    text = prov.registry.expose()
    for outcome, n in (("grown", 2), ("resident", 1), ("per_lane", 1), ("reset", 1)):
        assert f'csp_tpu_keytable_flushes_total{{outcome="{outcome}"}} {n}' in text


# -- what the tracing and the counters say of a block ----------------------


@pytest.mark.parametrize("python_collect", [False, True], ids=["native", "python"])
def test_the_collect_span_and_the_stage_clock_count_the_blocks_creators(crowd, python_collect):
    from fabric_tpu import native

    if not python_collect and not native.available():
        pytest.skip(f"no native collector: {native.load_error()}")
    world = crowd[3]
    v = _validator(world, SWCSP(), python_collect=python_collect)
    with tracing.scope() as rec:
        v.validate(_block(world))
        events = tracing.export(rec)["traceEvents"]
    (collect,) = [e for e in events if e.get("name") == "collect"]
    distinct = world.creators_per_block[0]
    assert collect["args"]["creators"] == distinct
    # a block's memo: every creator deserialised and validated once
    assert collect["args"]["creator_validations"] == distinct
    stage = v.validate_stage_seconds
    assert collect["args"]["creator_ms"] == pytest.approx(1e3 * stage["creators"])
    assert 0 < stage["creators"] < stage["collect"]
    assert collect["args"]["creator_ms"] <= collect["dur"] / 1e3 + 1.0
    # the MSP caches behind the memo: a miss a creator and a peer (the
    # policy's principal check validates the endorser), and with more
    # creators than entries the tail has pushed the head out
    tally = v._bundle.msp_manager.tally()
    for cache in ("deserialize", "validate"):
        assert tally["requests"][cache]["miss"] >= distinct
        assert tally["requests"][cache]["hit"] <= 2
        assert tally["evictions"][cache] >= distinct - 100 - 5
    assert tally["requests"]["validate"]["expired"] == 0
    # the refused creators never reached validate's cache as successes,
    # and a second block of the same creators finds a hundred at most
    before = tally["requests"]["deserialize"]["hit"]
    v2 = _validator(world, SWCSP(), python_collect=python_collect)
    v2._bundle = v._bundle
    v2.validate(_block(world))
    assert v._bundle.msp_manager.tally()["requests"]["deserialize"]["hit"] - before <= 100


def test_faithful_mode_validates_a_creator_a_transaction(crowd):
    world = crowd[3]
    v = _validator(world, SWCSP(), faithful=True)
    with tracing.scope() as rec:
        v.validate(_block(world))
        events = tracing.export(rec)["traceEvents"]
    (collect,) = [e for e in events if e.get("name") == "collect"]
    assert collect["args"]["creators"] == world.creators_per_block[0]
    assert collect["args"]["creator_validations"] == len(world.planted[0])


def test_the_msp_caches_count_on_the_metrics_page():
    from fabric_tpu.common.metrics import MSPMetrics, PrometheusProvider
    from fabric_tpu.msp import cache as msp_cache

    man, held, dep, world = _build(NO_FAULTS, block_txs=8)
    prov = PrometheusProvider()
    msp_cache.set_metrics(MSPMetrics(prov))
    try:
        v = _validator(world, SWCSP())
        v.validate(_block(world))
        v.validate(_block(world))
    finally:
        msp_cache.set_metrics(None)
    tally = v._bundle.msp_manager.tally()
    text = prov.registry.expose()
    for cache, outcomes in tally["requests"].items():
        for outcome, n in outcomes.items():
            if n:
                line = f'msp_cache_requests_total{{cache="{cache}",outcome="{outcome}"}} {n}'
                assert line in text, (line, text)
    creators = world.creators_per_block[0]
    assert tally["requests"]["deserialize"]["miss"] >= creators
    assert tally["requests"]["deserialize"]["hit"] >= creators    # the second block
    assert tally["evictions"] == {"deserialize": 0, "validate": 0, "principal": 0}
    assert "msp_cache_evictions_total{" not in text


def test_a_validate_entry_past_its_time_counts_as_expired(monkeypatch):
    from fabric_tpu.msp import cache as msp_cache

    class Inner:
        validated = 0

        def validate(self, identity):
            Inner.validated += 1

    class Ident:
        def serialize(self):
            return b"one"

    cached = msp_cache.CachedMSP(Inner())
    cached.validate(Ident())
    cached.validate(Ident())
    monkeypatch.setattr(msp_cache, "_VALIDATE_TTL_S", 0.0)
    cached.validate(Ident())
    assert Inner.validated == 2
    assert cached.tally()["requests"]["validate"] == {"hit": 1, "miss": 1, "expired": 1}


def test_disarmed_the_new_sites_consult_nothing(crowd):
    """Off, a site is a global load and an `is None` test: the armed
    path's counter stays where it was through a whole crowded block on
    either collect path, and the stage clock still runs.  (The
    provider's dispatch: `test_tracing.py`'s pin over the commit path.)"""
    world = crowd[3]
    assert not tracing.enabled()
    before = tracing.lookup_count()
    for python_collect in (False, True):
        v = _validator(world, SWCSP(), python_collect=python_collect)
        v.validate(_block(world))
        assert v.validate_stage_seconds["creators"] > 0
    assert tracing.lookup_count() == before


# -- a block's creators as one batch ----------------------------------------


@pytest.mark.parametrize("python_collect", [False, True], ids=["native", "python"])
def test_the_collect_span_says_how_many_chain_signatures_one_native_call_decided(
        crowd, serial_flags, python_collect, monkeypatch):
    """The native-walker collect validates the block's distinct creators
    ahead as one batch: every X.509 creator of this channel qualifies
    (one P-256 root an organisation), the refused ones too, and the
    Python OpenSSL check is left to the endorsers' handful.  The Python
    collector learns a creator a transaction and checks each in place."""
    from fabric_tpu import native
    from fabric_tpu.msp import msp as msp_mod

    if native.ecdsa_verify_host([]) is None:
        pytest.skip(f"no native verifier: {native.load_error()}")
    world = crowd[3]
    singles, built = [], []
    real_signed_by, real_trusted = msp_mod._signed_by, msp_mod._Trusted.__init__
    monkeypatch.setattr(msp_mod, "_signed_by",
                        lambda ca, cert: singles.append(cert) or real_signed_by(ca, cert))
    monkeypatch.setattr(msp_mod._Trusted, "__init__",
                        lambda self, cert, root: built.append(cert) or real_trusted(self, cert, root))
    v = _validator(world, SWCSP(), python_collect=python_collect)
    trusted = len(built)
    with tracing.scope() as rec:
        got = list(v.validate(_block(world)))
        events = tracing.export(rec)["traceEvents"]
    assert got == serial_flags
    (collect,) = [e for e in events if e.get("name") == "collect"]
    distinct = world.creators_per_block[0]
    assert collect["args"]["creators"] == distinct
    assert collect["args"]["creator_validations"] == distinct
    # the MSPs' trust index: built with the bundle, a certificate a CA,
    # and not touched again by any of the block's identities
    assert 0 < trusted <= 4 and len(built) == trusted
    if python_collect:
        assert collect["args"]["creator_chain_batch"] == 0
        assert len(singles) >= distinct
    else:
        assert collect["args"]["creator_chain_batch"] == distinct
        assert len(singles) <= 4    # the endorsing peer, the orderer


def test_without_the_native_verifier_every_creator_is_checked_in_place(crowd, serial_flags, monkeypatch):
    from fabric_tpu import native

    if not native.available():
        pytest.skip(f"no native collector: {native.load_error()}")
    monkeypatch.setattr(native, "ecdsa_verify_host", lambda items: None)
    world = crowd[3]
    v = _validator(world, SWCSP())
    with tracing.scope() as rec:
        got = list(v.validate(_block(world)))
        events = tracing.export(rec)["traceEvents"]
    assert got == serial_flags
    (collect,) = [e for e in events if e.get("name") == "collect"]
    assert collect["args"]["creator_chain_batch"] == 0
    assert collect["args"]["creator_validations"] == world.creators_per_block[0]


def test_the_chain_signatures_count_on_the_metrics_page_by_path(crowd):
    from fabric_tpu import native
    from fabric_tpu.common.metrics import MSPMetrics, PrometheusProvider
    from fabric_tpu.msp import cache as msp_cache

    if native.ecdsa_verify_host([]) is None:
        pytest.skip(f"no native verifier: {native.load_error()}")
    world = crowd[3]
    prov = PrometheusProvider()
    msp_cache.set_metrics(MSPMetrics(prov))
    try:
        _validator(world, SWCSP()).validate(_block(world))
        text = prov.registry.expose()
        assert (f'msp_chain_signatures_total{{path="batch"}} '
                f'{world.creators_per_block[0]}') in text
        _validator(world, SWCSP(), python_collect=True).validate(_block(world))
        text = prov.registry.expose()
    finally:
        msp_cache.set_metrics(None)
    (single,) = [ln for ln in text.splitlines()
                 if ln.startswith('msp_chain_signatures_total{path="single"}')]
    assert int(float(single.split()[-1])) >= world.creators_per_block[0]


@pytest.mark.parametrize("python_collect", [False, True], ids=["native", "python"])
def test_a_transaction_signed_with_high_s_is_still_refused(python_collect):
    """A certificate's signature is valid with either S; a
    transaction's is not, and the batch of chain signatures does not
    loosen that: the creator's own signature still goes the block's
    way, low-S enforced."""
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature, encode_dss_signature)

    from fabric_tpu import native
    from fabric_tpu.csp.api import P256_N as _P256_N
    from fabric_tpu.protos.common import common_pb2

    man, held, dep, world = _build(NO_FAULTS, block_txs=8)
    block = _block(world)
    env = common_pb2.Envelope.FromString(block.data.data[3])
    r, s = decode_dss_signature(env.signature)
    assert 2 * s <= _P256_N
    env.signature = encode_dss_signature(r, _P256_N - s)
    block.data.data[3] = env.SerializeToString()
    got = list(_validator(world, SWCSP(), python_collect=python_collect).validate(block))
    assert got == [VALID] * 3 + [BAD_CREATOR] + [VALID] * 4
    # and the native verifier itself, which the chain signatures share
    # with the provider's host fallback, holds what it is handed to low S
    sw = SWCSP()
    key, digest = sw.key_gen(), sw.hash(b"manyclients")
    low = sw.sign(key, digest)
    r, s = decode_dss_signature(low)
    high = encode_dss_signature(r, _P256_N - s)
    pub = key.public_key()
    assert not sw.verify(pub, high, digest)
    assert native.ecdsa_verify_host(
        [VerifyBatchItem(pub, digest, low), VerifyBatchItem(pub, digest, high)]
    ) in ([True, False], None)


@pytest.mark.parametrize("lanes", [1, 15, 16, 17, 63, 64, 128, 129, 257, 330])
def test_a_native_batch_of_any_size_gives_each_lane_its_own_verdict(lanes):
    """`ecverify.cc` keeps a context a key for the length of a call,
    and a block's chain signatures come to it as one batch of any size
    from `_NATIVE_BATCH_MIN` up: the verdicts are the lanes' own."""
    from fabric_tpu import native

    sw = SWCSP()
    items = _signed(7, lanes, sw)
    got = native.ecdsa_verify_host(items)
    if got is None:
        pytest.skip(f"no native verifier: {native.load_error()}")
    assert got == [sw.verify(it.key, it.signature, it.digest) for it in items]
    assert got.count(False) == len([i for i in range(lanes) if i % 7 == 3])


# -- a block's strangers read in one native call -----------------------------


def _needs_native_reader():
    from fabric_tpu import native

    if native.x509_read([]) is None or native.ecdsa_verify_host([]) is None:
        pytest.skip(f"no native reader: {native.load_error()}")


@pytest.mark.parametrize("python_collect", [False, True], ids=["native", "python"])
def test_the_collect_span_says_how_many_certificates_the_native_reader_read(
        crowd, serial_flags, python_collect):
    """The native-walker collect hands the block's distinct creators to
    the MSPs as one batch, whose strangers' certificates one native call
    reads: every creator of this world qualifies, the planted ones too
    (their fault is not their shape).  The Python collector learns a
    creator a transaction and parses each in place."""
    _needs_native_reader()
    world = crowd[3]
    v = _validator(world, SWCSP(), python_collect=python_collect)
    with tracing.scope() as rec:
        got = list(v.validate(_block(world)))
        events = tracing.export(rec)["traceEvents"]
    assert got == serial_flags
    (collect,) = [e for e in events if e.get("name") == "collect"]
    distinct = world.creators_per_block[0]
    assert collect["args"]["creator_validations"] == distinct
    parses = v._bundle.msp_manager.tally()["creator_parses"]
    if python_collect:
        assert collect["args"]["creator_native_parse"] == 0
        assert parses == {"native": 0, "python": 0}      # the batch door was not used
    else:
        assert collect["args"]["creator_native_parse"] == distinct
        assert parses == {"native": distinct, "python": 0}
        assert collect["args"]["creator_chain_batch"] == distinct


def test_a_block_of_few_creators_reads_each_certificate_in_place(monkeypatch):
    """Under `_NATIVE_BATCH_MIN` strangers the batch door's path is the
    one it had: the native reader is not called."""
    from fabric_tpu import native
    from fabric_tpu.msp import msp as msp_mod

    if not native.available():
        pytest.skip(f"no native collector: {native.load_error()}")
    calls = []
    monkeypatch.setattr(native, "x509_read", lambda items, **kw: calls.append(len(items)))
    man, held, dep, world = _build(NO_FAULTS, block_txs=8)
    assert world.creators_per_block[0] < msp_mod._NATIVE_BATCH_MIN
    v = _validator(world, SWCSP())
    with tracing.scope() as rec:
        assert list(v.validate(_block(world))) == [VALID] * 8
        events = tracing.export(rec)["traceEvents"]
    (collect,) = [e for e in events if e.get("name") == "collect"]
    assert calls == []
    assert collect["args"]["creator_native_parse"] == 0
    assert collect["args"]["creator_validations"] == world.creators_per_block[0]
    assert v._bundle.msp_manager.tally()["creator_parses"] == {
        "native": 0, "python": world.creators_per_block[0]}


def test_without_the_native_reader_every_certificate_is_parsed_in_place(
        crowd, serial_flags, monkeypatch):
    from fabric_tpu import native

    if not native.available():
        pytest.skip(f"no native collector: {native.load_error()}")
    monkeypatch.setattr(native, "x509_read", lambda items, **kw: None)
    world = crowd[3]
    v = _validator(world, SWCSP())
    with tracing.scope() as rec:
        got = list(v.validate(_block(world)))
        events = tracing.export(rec)["traceEvents"]
    assert got == serial_flags
    (collect,) = [e for e in events if e.get("name") == "collect"]
    distinct = world.creators_per_block[0]
    assert collect["args"]["creator_native_parse"] == 0
    assert collect["args"]["creator_validations"] == distinct
    assert v._bundle.msp_manager.tally()["creator_parses"] == {"native": 0, "python": distinct}


def test_the_creator_parses_count_on_the_metrics_page_by_path(crowd):
    from fabric_tpu.common.metrics import MSPMetrics, PrometheusProvider
    from fabric_tpu.msp import cache as msp_cache

    _needs_native_reader()
    world = crowd[3]
    distinct = world.creators_per_block[0]
    prov = PrometheusProvider()
    msp_cache.set_metrics(MSPMetrics(prov))
    try:
        _validator(world, SWCSP()).validate(_block(world))
        text = prov.registry.expose()
        assert f'msp_creator_parses_total{{path="native"}} {distinct}' in text
        assert 'msp_creator_parses_total{path="python"}' not in text
        # a small block's creators are read one at a time
        man, held, dep, small = _build(NO_FAULTS, block_txs=8)
        _validator(small, SWCSP()).validate(_block(small))
        text = prov.registry.expose()
    finally:
        msp_cache.set_metrics(None)
    assert f'msp_creator_parses_total{{path="native"}} {distinct}' in text
    assert (f'msp_creator_parses_total{{path="python"}} '
            f'{small.creators_per_block[0]}') in text


def test_disarmed_the_native_readers_site_consults_nothing(crowd, serial_flags):
    """Off, the span argument's site costs what its neighbours cost: the
    armed path's counter stays where it was through a crowded block whose
    strangers the native reader read."""
    _needs_native_reader()
    world = crowd[3]
    assert not tracing.enabled()
    before = tracing.lookup_count()
    v = _validator(world, SWCSP())
    assert list(v.validate(_block(world))) == serial_flags
    assert v._bundle.msp_manager.tally()["creator_parses"]["native"] == world.creators_per_block[0]
    assert tracing.lookup_count() == before
