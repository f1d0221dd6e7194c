"""The native certificate reader (`native.x509_read`, native/x509.cc)
beside the path it spares a crowded block's creators
(`MSP.deserialize_identity`: `cryptography`'s parse, an object a field).

The reader qualifies a certificate or hands it back, and never decides
alone.  So, over a corpus of every kind of certificate the MSP's own
tests make and over a few thousand seeded mutations of a sound one:
wherever it qualifies, every field is what `cryptography` reads and
today's path accepts the parse; wherever today's path raises, it does
not qualify.  And `CachedMSP.deserialize_creators` over the corpus,
with the reader there and with it gone, gives identity for identity what
`deserialize_creator` gives one at a time, by the same lookups in the
same caches."""

import base64
import datetime
import hashlib
import random

import pytest
from cryptography import x509
from cryptography.hazmat.primitives import hashes, serialization
from cryptography.hazmat.primitives.asymmetric import ec, rsa
from cryptography.hazmat.primitives.asymmetric.utils import decode_dss_signature
from cryptography.x509.name import _ASN1Type
from cryptography.x509.oid import NameOID

from fabric_tpu import native
from fabric_tpu.common.crypto import CA
from fabric_tpu.csp import SWCSP
from fabric_tpu.csp.api import P256_N, ECDSAP256PublicKey
from fabric_tpu.msp import MSP, MSPManager, msp_config_from_ca
from fabric_tpu.msp import msp as msp_mod
from fabric_tpu.msp.cache import CachedMSP
from fabric_tpu.msp.identity import Identity
from fabric_tpu.protos.msp import identities_pb2, msp_config_pb2
from orgfix import undecodable_issuer
from test_msp_batch import _creator, _issue_with_s, _rogue

PEM, DER = serialization.Encoding.PEM, serialization.Encoding.DER
OU, CN = NameOID.ORGANIZATIONAL_UNIT_NAME, NameOID.COMMON_NAME
NOW = datetime.datetime.now(datetime.timezone.utc).replace(microsecond=0)
MUTATIONS = 3000


def _needs_reader():
    if native.x509_read([]) is None:
        pytest.skip(f"no native reader: {native.load_error()}")


def _pem(der: bytes, width: int = 64, eol: bytes = b"\n") -> bytes:
    b64 = base64.b64encode(der)
    lines = [b64[i:i + width] for i in range(0, len(b64), width)]
    return (b"-----BEGIN CERTIFICATE-----" + eol + eol.join(lines) + eol
            + b"-----END CERTIFICATE-----" + eol)


def _built(issuer_ca, subject, key=None, sign_key=None, not_before=None, not_after=None,
           algorithm=None):
    """A leaf of `issuer_ca` with the extensions `CA.issue` gives."""
    key = key or ec.generate_private_key(ec.SECP256R1())
    return (
        x509.CertificateBuilder()
        .subject_name(subject).issuer_name(issuer_ca.cert.subject)
        .public_key(key.public_key()).serial_number(x509.random_serial_number())
        .not_valid_before(not_before or NOW - datetime.timedelta(minutes=5))
        .not_valid_after(not_after or NOW + datetime.timedelta(days=30))
        .add_extension(x509.BasicConstraints(ca=False, path_length=None), critical=True)
        .add_extension(x509.SubjectKeyIdentifier(b"\x01" * 20), critical=False)
        .sign(sign_key or issuer_ca.key, algorithm or hashes.SHA256())
    )


def _name(*attrs):
    return x509.Name([x509.NameAttribute(oid, value, _type=kind) if kind else
                      x509.NameAttribute(oid, value) for oid, value, kind in attrs])


def _tlv(tag: int, body: bytes) -> bytes:
    n = len(body)
    head = bytes([n]) if n < 0x80 else (
        bytes([0x81, n]) if n < 0x100 else bytes([0x82, n >> 8, n & 0xff]))
    return bytes([tag]) + head + body


def _v1(ca: CA) -> bytes:
    """A version 1 certificate (no version field, no extensions) the CA
    signed: DER by hand, `cryptography` builds v3 alone."""
    like = ca.issue("v1", ous=["client"]).cert
    alg = bytes.fromhex("300a06082a8648ce3d040302")
    utc = lambda t: _tlv(0x17, t.strftime("%y%m%d%H%M%SZ").encode())  # noqa: E731
    serial = like.serial_number.to_bytes(21, "big").lstrip(b"\0")
    serial = (b"\0" if serial[0] & 0x80 else b"") + serial
    tbs = _tlv(0x30, b"".join([
        _tlv(0x02, serial), alg, like.issuer.public_bytes(),
        _tlv(0x30, utc(like.not_valid_before_utc) + utc(like.not_valid_after_utc)),
        like.subject.public_bytes(),
        like.public_key().public_bytes(DER, serialization.PublicFormat.SubjectPublicKeyInfo),
    ]))
    sig = ca.key.sign(tbs, ec.ECDSA(hashes.SHA256()))
    return _tlv(0x30, tbs + alg + _tlv(0x03, b"\0" + sig))


class _Corpus:
    """name -> (PEM as a creator carries it, does the reader qualify
    it?).  One organisation, its intermediate CA, a CRL."""

    def __init__(self):
        ca = self.ca = CA("ca.org1", "Org1MSP")
        ica = self.ica = ca.new_intermediate("ica.org1")
        revoked = ca.issue("revoked", ous=["client"])
        ca.revoke(revoked.cert)
        self.conf = msp_config_from_ca(ca, "Org1MSP", intermediates=[ica], crls=[ca.gen_crl()])
        past = NOW - datetime.timedelta(days=1)
        rsa_key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
        sound = ca.issue("sound", ous=["client"]).cert
        other = ca.issue("other", ous=["client"]).cert
        pem = lambda cert: cert.public_bytes(PEM)  # noqa: E731
        broken = identities_pb2.SerializedIdentity.FromString(
            undecodable_issuer(_creator("Org1MSP", sound))).id_bytes
        self.sound_der = sound.public_bytes(DER)
        self.certs = {
            "sound": (pem(sound), True),
            "rogue CA": (pem(_rogue(ca).issue("r", ous=["client"]).cert), True),
            "expired": (pem(ca.issue("e", ous=["client"], not_after=past).cert), True),
            "revoked": (pem(revoked.cert), True),
            "no role OU": (pem(ca.issue("n", ous=[]).cert), True),
            "an issuer name that does not decode": (broken, False),
            "a CA signature with high S": (pem(_issue_with_s(ca, True)), True),
            "a CA signature with low S": (pem(_issue_with_s(ca, False)), True),
            "issued by an intermediate CA": (pem(ica.issue("i", ous=["client"]).cert), True),
            "the intermediate CA itself": (ica.cert_pem, True),
            "a P-384 key": (pem(_built(
                ca, _name((CN, "p384", None), (OU, "client", None)),
                key=ec.generate_private_key(ec.SECP384R1()))), False),
            "signed with RSA": (pem(_built(
                ca, _name((CN, "rsa", None), (OU, "client", None)), sign_key=rsa_key)), False),
            "ECDSA with SHA-384": (pem(_built(
                ca, _name((CN, "s384", None), (OU, "client", None)),
                algorithm=hashes.SHA384())), False),
            "two certificates in one PEM": (pem(sound) + pem(other), False),
            "PEM with CRLF": (_pem(self.sound_der, eol=b"\r\n"), False),
            "PEM with trailing text": (pem(sound) + b"enrolled by hand\n", False),
            "PEM with leading text": (b"subject=sound\n" + pem(sound), False),
            "PEM in 76 columns": (_pem(self.sound_der, width=76), False),
            "PEM without its last newline": (pem(sound)[:-1], False),
            "version 1": (_pem(_v1(ca)), False),
            "GeneralizedTime validity": (pem(_built(
                ca, _name((CN, "long", None), (OU, "client", None)),
                not_after=datetime.datetime(2061, 2, 28, 23, 59, 59,
                                            tzinfo=datetime.timezone.utc))), True),
            "OUs as UTF8String beyond ASCII": (pem(_built(
                ca, _name((CN, "büro", None), (OU, "client", None),
                          (OU, "Zürich.außen.東京", None)))), True),
            "OUs as PrintableString": (pem(_built(
                ca, _name((CN, "go", _ASN1Type.PrintableString),
                          (OU, "client", _ASN1Type.PrintableString),
                          (OU, "org1.department1", _ASN1Type.PrintableString)))), True),
            "an OU as IA5String": (pem(_built(
                ca, _name((CN, "ia5", None), (OU, "client", _ASN1Type.IA5String)))), False),
            "a multi-valued RDN": (pem(_built(ca, x509.Name([
                x509.RelativeDistinguishedName([
                    x509.NameAttribute(CN, "pair"), x509.NameAttribute(OU, "client")])]))), False),
            "an empty subject": (pem(_built(ca, x509.Name([]))), True),
            "nine OUs": (pem(_built(ca, _name(
                (CN, "many", None), (OU, "client", None),
                *[(OU, f"ou{i}", None) for i in range(8)]))), False),
        }

    def msp(self) -> MSP:
        return MSP.from_config(self.conf, SWCSP())


@pytest.fixture(scope="module")
def corpus():
    return _Corpus()


CERTS = [
    "sound", "rogue CA", "expired", "revoked", "no role OU",
    "an issuer name that does not decode", "a CA signature with high S",
    "a CA signature with low S", "issued by an intermediate CA",
    "the intermediate CA itself", "a P-384 key", "signed with RSA", "ECDSA with SHA-384",
    "two certificates in one PEM", "PEM with CRLF", "PEM with trailing text",
    "PEM with leading text", "PEM in 76 columns", "PEM without its last newline",
    "version 1", "GeneralizedTime validity", "OUs as UTF8String beyond ASCII",
    "OUs as PrintableString", "an OU as IA5String", "a multi-valued RDN",
    "an empty subject", "nine OUs",
]


def _today(pem: bytes, msp: MSP):
    """What today's path reads of the certificate: every field the MSP
    asks of an identity, through `cryptography`; or the exception."""
    try:
        sid = identities_pb2.SerializedIdentity(mspid=msp.mspid, id_bytes=pem).SerializeToString()
        ident = msp.deserialize_identity(sid)
        cert = ident.cert
        r, s = decode_dss_signature(cert.signature)
        return {
            "serialized": ident.serialize(),
            "pem": cert.public_bytes(PEM),
            "der": cert.public_bytes(DER),
            "ous": ident.ous,
            "x": ident.public_key.x_bytes, "y": ident.public_key.y_bytes,
            "ski": ident.public_key.ski(),
            "issuer": cert.issuer.public_bytes(), "subject": cert.subject.public_bytes(),
            "tbs_digest": hashlib.sha256(cert.tbs_certificate_bytes).digest(),
            "r": r, "s": s,
            "not_before": cert.not_valid_before_utc, "not_after": cert.not_valid_after_utc,
            "serial": cert.serial_number,
        }
    except Exception as exc:
        return exc


def _natively(pem: bytes, msp: MSP):
    """The same of the identity the reader's fields make, or the status
    it handed the certificate back with."""
    sid = identities_pb2.SerializedIdentity(mspid=msp.mspid, id_bytes=pem).SerializeToString()
    (wrapped,), (bare,) = native.x509_read([sid], wrapped=True), native.x509_read([pem])
    if isinstance(wrapped, int) or isinstance(bare, int):
        assert wrapped == bare
        return wrapped
    assert wrapped.mspid == msp.mspid.encode() and bare.mspid == b""
    assert wrapped[1:] == bare[1:]
    ident = Identity.from_fields(msp.mspid, wrapped, sid, msp.csp)
    digest, low = ident.chain_signature
    low_r, low_s = decode_dss_signature(low)
    r, s = (int.from_bytes(b, "big") for b in (wrapped.r_bytes, wrapped.s_bytes))
    assert (low_r, low_s) == (r, min(s, P256_N - s))
    return {
        "serialized": ident.serialize(),
        "pem": wrapped.pem, "der": ident.der, "ous": ident.ous,
        "x": ident.public_key.x_bytes, "y": ident.public_key.y_bytes,
        "ski": ident.public_key.ski(),
        "issuer": ident.issuer_bytes, "subject": wrapped.subject,
        "tbs_digest": digest, "r": r, "s": s,
        "not_before": ident.not_before, "not_after": ident.not_after,
        "serial": ident.serial,
    }


def _held_together(pem: bytes, msp: MSP, what: str):
    """Qualified: today's path reads the same.  Refused today: handed
    back.  Returns whether the reader qualified it."""
    today, natively = _today(pem, msp), _natively(pem, msp)
    if isinstance(natively, int):
        assert natively > 0, what
        return False
    assert not isinstance(today, Exception), (what, today)
    assert natively == today, what
    return True


@pytest.mark.parametrize("name", CERTS)
def test_a_certificate_the_reader_qualifies_reads_as_cryptography_reads_it(corpus, name):
    _needs_reader()
    assert set(CERTS) == set(corpus.certs)
    pem, qualifies = corpus.certs[name]
    assert _held_together(pem, corpus.msp(), name) == qualifies


def test_an_identity_made_from_fields_loads_its_certificate_and_key_when_asked(corpus):
    _needs_reader()
    msp = corpus.msp()
    pem = corpus.certs["sound"][0]
    sid = _creator("Org1MSP", x509.load_pem_x509_certificate(pem))
    (fields,) = native.x509_read([sid], wrapped=True)
    made, loaded = Identity.from_fields("Org1MSP", fields, sid, msp.csp), msp.deserialize_identity(sid)
    assert "cert" not in vars(made) and "_key" not in vars(made.public_key)
    assert made.id == loaded.id and made.expires_at() == loaded.expires_at()
    assert made.cert == loaded.cert
    key = made.public_key
    assert (key.der(), key.pem(), key.raw(), key.x, key.y) == (
        loaded.public_key.der(), loaded.public_key.pem(), loaded.public_key.raw(),
        loaded.public_key.x, loaded.public_key.y)
    assert key.public_key() is key and isinstance(key, ECDSAP256PublicKey)
    signer = ec.generate_private_key(ec.SECP256R1())
    point = signer.public_key().public_numbers()
    ours = ECDSAP256PublicKey.from_coordinates(
        point.x.to_bytes(32, "big"), point.y.to_bytes(32, "big"))
    csp = SWCSP()
    digest = csp.hash(b"signed")
    from fabric_tpu.csp.api import marshal_ecdsa_signature, to_low_s

    r, s = decode_dss_signature(signer.sign(b"signed", ec.ECDSA(hashes.SHA256())))
    assert csp.verify(ours, marshal_ecdsa_signature(r, to_low_s(s)), digest)
    # and either kind of identity passes or fails the MSP's checks alike
    for name in ("sound", "rogue CA", "expired", "revoked", "no role OU",
                 "issued by an intermediate CA", "the intermediate CA itself"):
        sid = _creator("Org1MSP", x509.load_pem_x509_certificate(corpus.certs[name][0]))
        (fields,) = native.x509_read([sid], wrapped=True)
        verdicts = []
        for ident in (Identity.from_fields("Org1MSP", fields, sid, msp.csp),
                      msp.deserialize_identity(sid)):
            try:
                msp.validate(ident)
                verdicts.append(None)
            except msp_mod.MSPError as exc:
                verdicts.append(str(exc))
        assert verdicts[0] == verdicts[1], name
        assert (verdicts[0] is None) == (name in ("sound", "issued by an intermediate CA")), name


def _mutated(rng: random.Random, der: bytes) -> tuple[str, bytes]:
    """One seeded fault: a byte of the DER, a cut of it, or a byte of
    the PEM around it."""
    kind = rng.choice(("byte", "byte", "byte", "cut", "pem"))
    if kind == "byte":
        at = rng.randrange(len(der))
        return f"DER byte {at}", _pem(der[:at] + bytes([rng.randrange(256)]) + der[at + 1:])
    if kind == "cut":
        at = rng.randrange(len(der))
        return f"DER cut at {at}", _pem(der[:at])
    pem = _pem(der)
    at = rng.randrange(len(pem))
    return f"PEM byte {at}", pem[:at] + bytes([rng.randrange(256)]) + pem[at + 1:]


def test_seeded_mutations_of_a_sound_certificate_never_part_the_two_readers(corpus):
    """Where the reader still qualifies a mutated certificate (a byte
    of an extension's value, of a name's text, of the serial number or
    the signature), `cryptography` reads the same fields of it; where
    `cryptography` refuses it, so does the reader."""
    _needs_reader()
    msp = corpus.msp()
    qualified = 0
    for seed in range(MUTATIONS):
        what, pem = _mutated(random.Random(seed), corpus.sound_der)
        qualified += _held_together(pem, msp, f"seed {seed}: {what}")
    # the mutations reach both sides of the reader's door
    assert MUTATIONS // 20 < qualified < MUTATIONS // 2, qualified


def test_seeded_mutations_of_the_identity_around_the_certificate(corpus):
    """The SerializedIdentity in any but its canonical wire form is
    handed back: what the reader qualifies re-serializes to the bytes it
    came as, which is what keys the MSP's caches."""
    _needs_reader()
    msp = corpus.msp()
    sound = _creator("Org1MSP", x509.load_der_x509_certificate(corpus.sound_der))
    qualified = 0
    for seed in range(MUTATIONS // 3):
        rng = random.Random(seed)
        at = rng.randrange(24)      # the tags, the lengths, the MSP id, the PEM's head
        raw = sound[:at] + bytes([rng.randrange(256)]) + sound[at + 1:]
        (fields,) = native.x509_read([raw], wrapped=True)
        if isinstance(fields, int):
            continue
        qualified += 1
        sid = identities_pb2.SerializedIdentity.FromString(raw)
        assert sid.SerializeToString() == raw, seed
        assert (fields.mspid.decode(), fields.pem) == (sid.mspid, sid.id_bytes), seed
        assert x509.load_pem_x509_certificate(fields.pem).public_bytes(DER) == fields.der
    assert qualified > 10
    for raw in (b"", b"\x0a\x00", sound + b"\x1a\x00", sound[:-1],
                b"\x12" + sound[9:] + sound[:9],            # id_bytes before mspid
                sound.replace(b"\x0a\x07Org1MSP", b"\x0a\x87\x00Org1MSP")):  # a padded length
        assert isinstance(native.x509_read([raw], wrapped=True)[0], int)


# -- the batch door over the corpus -----------------------------------------

FILLERS = 130


@pytest.fixture(scope="module")
def channel(corpus):
    """The corpus as creators of Org1MSP, and more sound ones than an
    MSP cache holds."""
    creators = {name: identities_pb2.SerializedIdentity(
        mspid="Org1MSP", id_bytes=pem).SerializeToString()
        for name, (pem, _q) in corpus.certs.items()}
    for i in range(FILLERS):
        creators[f"filler {i}"] = _creator("Org1MSP", corpus.ca.issue(f"f{i}", ous=["client"]).cert)
    creators["another organisation's"] = _creator(
        "Org2MSP", corpus.ca.issue("o", ous=["client"]).cert)
    creators["no identity at all"] = b"\x00garbage"
    return creators


def _manager(corpus) -> CachedMSP:
    return CachedMSP(MSPManager([corpus.msp()]))


def _one_at_a_time(mgr, raws):
    out = []
    for raw in raws:
        try:
            out.append(mgr.deserialize_creator(raw))
        except Exception:
            out.append(None)
    return out


ACCEPTED = {"sound", "a CA signature with high S", "a CA signature with low S",
            "issued by an intermediate CA", "GeneralizedTime validity",
            "OUs as UTF8String beyond ASCII", "OUs as PrintableString",
            "PEM with CRLF", "PEM with trailing text", "PEM with leading text",
            "PEM in 76 columns", "PEM without its last newline", "nine OUs",
            "a multi-valued RDN", "an OU as IA5String", "ECDSA with SHA-384", "version 1"}


@pytest.mark.parametrize("missing", [
    (), ("x509_read",), ("ecdsa_verify_host",), ("x509_read", "ecdsa_verify_host"),
], ids=["native", "no native reader", "no native verifier", "no native library"])
def test_the_batch_door_and_the_single_door_agree_over_the_corpus(
        corpus, channel, missing, monkeypatch):
    _needs_reader()
    for name in missing:
        monkeypatch.setattr(native, name, lambda *a, **k: None)
    names, raws = list(channel), list(channel.values())
    batch_mgr, single_mgr = _manager(corpus), _manager(corpus)
    batch, decided = batch_mgr.deserialize_creators(raws)
    single = _one_at_a_time(single_mgr, raws)
    for name, b, s in zip(names, batch, single):
        accepted = name in ACCEPTED or name.startswith("filler")
        assert (b is not None) == (s is not None) == accepted, name
        if accepted:
            assert b.serialize() == s.serialize(), name
            assert (b.ous, b.public_key.x_bytes, b.public_key.y_bytes, b.id, b.expires_at()) == (
                s.ous, s.public_key.x_bytes, s.public_key.y_bytes, s.id, s.expires_at()), name
            assert b.chain_verdict is None
    # today's batch door: the same pass with the reader gone
    with monkeypatch.context() as gone:
        gone.setattr(native, "x509_read", lambda *a, **k: None)
        todays_mgr = _manager(corpus)
        todays_mgr.deserialize_creators(raws)
    tally, todays, want = batch_mgr.tally(), todays_mgr.tally(), single_mgr.tally()
    assert tally["requests"] == todays["requests"]
    assert tally["evictions"] == todays["evictions"] == want["evictions"]
    assert tally["evictions"]["deserialize"] > 30
    # one at a time, the five other spellings of the sound certificate's
    # PEM find the verdict the first left; the batch asks before any is in
    assert tally["requests"]["deserialize"] == want["requests"]["deserialize"]
    assert (want["requests"]["validate"]["hit"], tally["requests"]["validate"]["hit"]) == (5, 0)
    read = tally["creator_parses"]
    # every creator that loads misses once; the garbage and the other
    # organisation's raise before anything is read
    assert read["native"] + read["python"] == len(raws)
    qualifying = FILLERS + sum(q for _pem, q in corpus.certs.values())
    assert read["native"] == (0 if "x509_read" in missing else qualifying)
    assert want["creator_parses"] == {"native": 0, "python": 0}    # the single door's are not counted
    assert (decided > 0) == ("ecdsa_verify_host" not in missing)
    # a second pass finds the last hundred and reads the others again
    again, _decided = batch_mgr.deserialize_creators(raws)
    assert [i is None for i in again] == [i is None for i in batch]


def test_a_block_under_the_size_is_read_one_at_a_time(corpus, channel, monkeypatch):
    _needs_reader()
    calls = []
    real = native.x509_read
    monkeypatch.setattr(native, "x509_read",
                        lambda items, **kw: calls.append(len(items)) or real(items, **kw))
    few = [channel[f"filler {i}"] for i in range(msp_mod._NATIVE_BATCH_MIN - 1)]
    mgr = _manager(corpus)
    idents, _decided = mgr.deserialize_creators(few)
    assert calls == [] and all(i is not None for i in idents)
    assert mgr.tally()["creator_parses"] == {"native": 0, "python": len(few)}
    assert all("cert" in vars(i) for i in idents)
    # the same creators again, with one more: the cache holds all but that one
    idents, _decided = mgr.deserialize_creators(few + [channel["sound"]])
    assert calls == [] and mgr.tally()["creator_parses"]["python"] == len(few) + 1
    # a crowd of strangers is read in one call
    crowd = [channel[f"filler {i}"] for i in range(20, 20 + msp_mod._NATIVE_BATCH_MIN)]
    idents, _decided = mgr.deserialize_creators(crowd)
    assert calls == [len(crowd)] and all("cert" not in vars(i) for i in idents)
    assert mgr.tally()["creator_parses"] == {"native": len(crowd), "python": len(few) + 1}


def test_a_creator_the_cache_drops_before_its_turn_is_read_then(corpus, channel, monkeypatch):
    """The look ahead moves and counts nothing: a block whose tail the
    deserialize cache holds when the block arrives, and has dropped by
    the time the pass reaches it, makes the lookups and the evictions
    today's pass makes (the reader gone), and reads that tail one at a
    time."""
    _needs_reader()
    held = [channel[f"filler {i}"] for i in range(100)]
    strangers = [_creator("Org1MSP", corpus.ca.issue(f"s{i}", ous=["client"]).cert)
                 for i in range(100)]
    batch_mgr, todays_mgr = _manager(corpus), _manager(corpus)
    batch_mgr.deserialize_creators(held)
    before = batch_mgr.tally()["creator_parses"]
    idents, _decided = batch_mgr.deserialize_creators(strangers + held)
    with monkeypatch.context() as gone:
        gone.setattr(native, "x509_read", lambda *a, **k: None)
        todays_mgr.deserialize_creators(held)
        todays, _decided = todays_mgr.deserialize_creators(strangers + held)
    assert [i.serialize() for i in idents] == [i.serialize() for i in todays]
    tally, want = batch_mgr.tally(), todays_mgr.tally()
    assert tally["requests"] == want["requests"] and tally["evictions"] == want["evictions"]
    assert tally["requests"]["deserialize"] == {"hit": 0, "miss": 300, "expired": 0}
    after = tally["creator_parses"]
    assert (after["native"] - before["native"], after["python"] - before["python"]) == (100, 100)
    assert want["creator_parses"] == {"native": 0, "python": 300}


def test_a_reader_that_raises_reads_nothing_and_refuses_no_one(corpus, channel, monkeypatch):
    def broken(items, **kw):
        raise OSError("the library went away")

    monkeypatch.setattr(native, "x509_read", broken)
    raws = [channel[f"filler {i}"] for i in range(40)]
    mgr = _manager(corpus)
    idents, _decided = mgr.deserialize_creators(raws)
    assert all(i is not None for i in idents)
    assert mgr.tally()["creator_parses"] == {"native": 0, "python": 40}


def test_the_point_has_to_lie_on_the_curve(corpus):
    """The key's coordinates are taken as they stand, so the reader
    checks what `cryptography`'s load checks: on the curve, under p."""
    _needs_reader()
    der = corpus.sound_der
    spki = bytes.fromhex("3059301306072a8648ce3d020106082a8648ce3d03010703420004")
    at = der.index(spki) + len(spki)
    p = 2**256 - 2**224 + 2**192 + 2**96 - 1
    x = int.from_bytes(der[at:at + 32], "big")
    y = int.from_bytes(der[at + 32:at + 64], "big")
    point = lambda x, y: _pem(  # noqa: E731
        der[:at] + x.to_bytes(32, "big") + y.to_bytes(32, "big") + der[at + 64:])
    msp = corpus.msp()
    assert _held_together(point(x, y), msp, "the key as issued")
    assert _held_together(point(x, p - y), msp, "its mirror image")    # on the curve too
    for what, bad in (("y + 1", (x, y + 1)), ("x + 1", (x + 1, y)), ("the origin", (0, 0)),
                      ("x = p", (p, y)), ("y + p", (x, (y + p) % 2**256))):
        assert not _held_together(point(*bad), msp, what), what
