// Native reader of a block's creators: the fields the X.509 MSP needs of
// an enrolment certificate, read from many serialized identities in ONE
// call that holds no interpreter lock (msp/msp.py read_identities, for
// the creators of a crowded block that the MSP caches do not hold).
//
// The reader QUALIFIES a certificate or HANDS IT BACK, and never
// decides alone.  It qualifies exactly the shape a Fabric CA issues and
// `cryptography` would have parsed to the same fields:
//
//   - the SerializedIdentity in canonical wire form (mspid then
//     id_bytes, both present, nothing else), the PEM in canonical form
//     (one CERTIFICATE block, 64-column base64, LF, nothing around it):
//     the identity then re-serializes to the bytes it came as;
//   - strict DER throughout (minimal lengths, no trailing bytes);
//   - X.509 v3, no unique identifiers; a positive serial number;
//   - ecdsa-with-SHA256, inside and outside, without parameters;
//   - names of single-valued RDNs whose values are PrintableString,
//     UTF8String or IA5String and decode; up to MAX_OUS OUs in the
//     subject, each PrintableString or UTF8String;
//   - UTCTime / GeneralizedTime validity in whole seconds, "Z";
//   - an id-ecPublicKey / prime256v1 key as an uncompressed point that
//     lies on the curve;
//   - extensions that parse as SEQUENCE OF { OID, [TRUE], OCTET STRING };
//   - a signature of two positive minimal INTEGERs in [1, n-1].
//
// Anything else gets a status other than 0 ("not mine": the code says
// which rule handed it back) and goes through
// `MSP.deserialize_identity` as ever, which accepts or refuses it.  The
// rules are as strict as rust-asn1's or stricter, so a qualified
// certificate is one the Python path loads; tests/test_msp_native_parse.py
// holds the two together over a corpus and a few thousand mutations.
//
// No verdict is formed here: the chain signature goes to
// fabric_ecdsa_verify_host (ecverify.cc) from the digest and the
// low-S signature this call lays out, and `MSP.validate` accepts or
// refuses.

#include <cstdint>
#include <cstring>

typedef unsigned __int128 u128;
typedef uint8_t u8;
typedef uint32_t u32;
typedef uint64_t u64;
typedef int32_t i32;
typedef int64_t i64;

// collect.cc: libcrypto's SHA-256 where it loads, the scalar one else
extern "C" void fabric_sha256(const u8* p, size_t n, u8* out);
// ecverify.cc: can fabric_ecdsa_verify_host verify at all?
extern "C" int fabric_ecdsa_host_ok();

namespace {

enum Status : i32 {
  OK = 0,
  NOT_WIRE = 1,        // the SerializedIdentity is not in canonical form
  NOT_PEM = 2,         // not one canonical CERTIFICATE block
  NOT_DER = 3,         // a length that does not add up, an unknown tag
  NOT_V3 = 4,          // another version, or unique identifiers
  NOT_SERIAL = 5,      // a negative, padded or over-long serial number
  NOT_ALGORITHM = 6,   // not ecdsa-with-SHA256 without parameters
  NOT_NAME = 7,        // a Name this reader does not hand over byte for byte
  NOT_VALIDITY = 8,    // a time it does not read
  NOT_KEY = 9,         // not an uncompressed P-256 point on the curve
  NOT_EXTENSIONS = 10,
  NOT_SIGNATURE = 11,  // (r, s) not strict DER or out of range
  NO_ROOM = 12,        // more OUs than the arrays hold
};

const int MAX_OUS = 8;

// meta columns, one row an identity (keep in step with
// native/__init__.py x509_read)
enum Col {
  C_STATUS, C_MSPID_OFF, C_MSPID_LEN, C_PEM_OFF, C_PEM_LEN, C_DER_OFF,
  C_DER_LEN, C_ISSUER_OFF, C_ISSUER_LEN, C_SUBJECT_OFF, C_SUBJECT_LEN,
  C_SERIAL_OFF, C_SERIAL_LEN, C_NOT_BEFORE, C_NOT_AFTER, C_LOWSIG_LEN,
  C_OU_COUNT, C_OU0,  // then MAX_OUS (offset, length) pairs
  N_COLS = C_OU0 + 2 * MAX_OUS
};

// ---------------------------------------------------------------------------
// P-256: is (x, y) on the curve?  Montgomery arithmetic mod p
// (-p^-1 mod 2^64 is 1, as p = -1 mod 2^64).
// ---------------------------------------------------------------------------

struct U256 { u64 v[4]; };  // little-endian limbs

const U256 P = {{0xFFFFFFFFFFFFFFFFULL, 0x00000000FFFFFFFFULL,
                 0x0000000000000000ULL, 0xFFFFFFFF00000001ULL}};
const U256 RR_P = {{0x0000000000000003ULL, 0xFFFFFFFBFFFFFFFFULL,
                    0xFFFFFFFFFFFFFFFEULL, 0x00000004FFFFFFFDULL}};
const U256 B_MONT = {{0xD89CDF6229C4BDDFULL, 0xACF005CD78843090ULL,
                      0xE5A220ABF7212ED6ULL, 0xDC30061D04874834ULL}};
// the group order n and n/2, big-endian
const u8 N_BE[32] = {
    0xff, 0xff, 0xff, 0xff, 0x00, 0x00, 0x00, 0x00,
    0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff,
    0xbc, 0xe6, 0xfa, 0xad, 0xa7, 0x17, 0x9e, 0x84,
    0xf3, 0xb9, 0xca, 0xc2, 0xfc, 0x63, 0x25, 0x51};
const u8 HALF_N_BE[32] = {
    0x7f, 0xff, 0xff, 0xff, 0x80, 0x00, 0x00, 0x00,
    0x7f, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff,
    0xde, 0x73, 0x7d, 0x56, 0xd3, 0x8b, 0xcf, 0x42,
    0x79, 0xdc, 0xe5, 0x61, 0x7e, 0x31, 0x92, 0xa8};

int cmp(const U256& a, const U256& b) {
  for (int i = 3; i >= 0; --i) {
    if (a.v[i] < b.v[i]) return -1;
    if (a.v[i] > b.v[i]) return 1;
  }
  return 0;
}

u64 sub(const U256& a, const U256& b, U256* out) {
  u64 borrow = 0;
  for (int i = 0; i < 4; ++i) {
    u128 d = (u128)a.v[i] - b.v[i] - borrow;
    out->v[i] = (u64)d;
    borrow = (d >> 64) ? 1 : 0;
  }
  return borrow;
}

U256 mod_add(const U256& a, const U256& b) {
  U256 r;
  u64 carry = 0;
  for (int i = 0; i < 4; ++i) {
    u128 s = (u128)a.v[i] + b.v[i] + carry;
    r.v[i] = (u64)s;
    carry = (u64)(s >> 64);
  }
  if (carry || cmp(r, P) >= 0) sub(r, P, &r);
  return r;
}

U256 mod_sub(const U256& a, const U256& b) {
  U256 r;
  if (sub(a, b, &r)) {
    u64 carry = 0;
    for (int i = 0; i < 4; ++i) {
      u128 s = (u128)r.v[i] + P.v[i] + carry;
      r.v[i] = (u64)s;
      carry = (u64)(s >> 64);
    }
  }
  return r;
}

// a * b * 2^-256 mod p (CIOS)
U256 mont_mul(const U256& a, const U256& b) {
  u64 t[6] = {0, 0, 0, 0, 0, 0};
  for (int i = 0; i < 4; ++i) {
    u64 carry = 0;
    for (int j = 0; j < 4; ++j) {
      u128 s = (u128)a.v[i] * b.v[j] + t[j] + carry;
      t[j] = (u64)s;
      carry = (u64)(s >> 64);
    }
    u128 s = (u128)t[4] + carry;
    t[4] = (u64)s;
    t[5] = (u64)(s >> 64);
    u64 m = t[0];  // * (-p^-1 mod 2^64), which is 1
    carry = 0;
    for (int j = 0; j < 4; ++j) {
      u128 s2 = (u128)m * P.v[j] + t[j] + carry;
      t[j] = (u64)s2;
      carry = (u64)(s2 >> 64);
    }
    s = (u128)t[4] + carry;
    t[4] = (u64)s;
    t[5] += (u64)(s >> 64);
    t[0] = t[1]; t[1] = t[2]; t[2] = t[3]; t[3] = t[4]; t[4] = t[5];
    t[5] = 0;
  }
  U256 r = {{t[0], t[1], t[2], t[3]}};
  if (t[4] || cmp(r, P) >= 0) sub(r, P, &r);
  return r;
}

U256 from_be(const u8* b) {
  U256 r;
  for (int i = 0; i < 4; ++i) {
    u64 w = 0;
    for (int k = 0; k < 8; ++k) w = (w << 8) | b[8 * (3 - i) + k];
    r.v[i] = w;
  }
  return r;
}

// y^2 = x^3 - 3x + b over GF(p), with x, y < p
bool on_p256(const u8* x_be, const u8* y_be) {
  U256 x = from_be(x_be), y = from_be(y_be);
  if (cmp(x, P) >= 0 || cmp(y, P) >= 0) return false;
  U256 xm = mont_mul(x, RR_P), ym = mont_mul(y, RR_P);
  U256 lhs = mont_mul(ym, ym);
  U256 rhs = mont_mul(mont_mul(xm, xm), xm);
  U256 x3 = mod_add(mod_add(xm, xm), xm);
  rhs = mod_add(mod_sub(rhs, x3), B_MONT);
  return cmp(lhs, rhs) == 0;
}

// ---------------------------------------------------------------------------
// DER, strictly.
// ---------------------------------------------------------------------------

struct Span {
  const u8* p;
  size_t n;
};

// One TLV of tag `tag` at the head of `in`: its contents in `body`, its
// whole extent in `whole` (may be null); `in` moves past it.  Lengths
// in the one form DER allows; no high tag numbers.
bool tlv(Span* in, u8 tag, Span* body, Span* whole = nullptr) {
  const u8* p = in->p;
  size_t n = in->n;
  if (n < 2 || p[0] != tag) return false;
  size_t len, head;
  u8 l0 = p[1];
  if (l0 < 0x80) {
    len = l0;
    head = 2;
  } else if (l0 == 0x81) {
    if (n < 3 || p[2] < 0x80) return false;
    len = p[2];
    head = 3;
  } else if (l0 == 0x82) {
    if (n < 4 || p[2] == 0) return false;
    len = (size_t(p[2]) << 8) | p[3];
    head = 4;
  } else if (l0 == 0x83) {
    if (n < 5 || p[2] == 0) return false;
    len = (size_t(p[2]) << 16) | (size_t(p[3]) << 8) | p[4];
    head = 5;
  } else {
    return false;
  }
  if (len > n - head) return false;
  body->p = p + head;
  body->n = len;
  if (whole) {
    whole->p = p;
    whole->n = head + len;
  }
  in->p = p + head + len;
  in->n = n - head - len;
  return true;
}

// An OBJECT IDENTIFIER's contents: arcs in minimal base 128, each of at
// most four bytes, the whole of at most 32 (rust-asn1 refuses past 63
// bytes and past what its integers hold: stricter here).
bool oid_ok(const Span& oid) {
  if (oid.n == 0 || oid.n > 32) return false;
  size_t arc = 0;
  for (size_t i = 0; i < oid.n; ++i) {
    u8 c = oid.p[i];
    if (arc == 0 && c == 0x80) return false;  // a padded arc
    if (++arc > 4) return false;
    if (!(c & 0x80)) arc = 0;
  }
  return arc == 0;
}

bool utf8_ok(const u8* p, size_t n) {
  size_t i = 0;
  while (i < n) {
    u8 c = p[i];
    if (c < 0x80) { ++i; continue; }
    int more;
    u32 cp;
    if (c >= 0xc2 && c <= 0xdf) { more = 1; cp = c & 0x1f; }
    else if (c >= 0xe0 && c <= 0xef) { more = 2; cp = c & 0x0f; }
    else if (c >= 0xf0 && c <= 0xf4) { more = 3; cp = c & 0x07; }
    else return false;
    if (n - i <= size_t(more)) return false;
    for (int k = 1; k <= more; ++k) {
      u8 cc = p[i + k];
      if ((cc & 0xc0) != 0x80) return false;
      cp = (cp << 6) | (cc & 0x3f);
    }
    if (more == 2 && (cp < 0x800 || (cp >= 0xd800 && cp <= 0xdfff)))
      return false;
    if (more == 3 && (cp < 0x10000 || cp > 0x10ffff)) return false;
    i += size_t(more) + 1;
  }
  return true;
}

bool printable_ok(const u8* p, size_t n) {
  for (size_t i = 0; i < n; ++i) {
    u8 c = p[i];
    bool ok = (c >= 'A' && c <= 'Z') || (c >= 'a' && c <= 'z') ||
              (c >= '0' && c <= '9') || c == ' ' || c == '\'' || c == '(' ||
              c == ')' || c == '+' || c == ',' || c == '-' || c == '.' ||
              c == '/' || c == ':' || c == '=' || c == '?';
    if (!ok) return false;
  }
  return true;
}

bool ascii_ok(const u8* p, size_t n) {
  for (size_t i = 0; i < n; ++i)
    if (p[i] >= 0x80) return false;
  return true;
}

const u8 TAG_UTF8 = 0x0c, TAG_PRINTABLE = 0x13, TAG_IA5 = 0x16;
const u8 OID_OU[3] = {0x55, 0x04, 0x0b};

// A Name's contents: single-valued RDNs of strings this reader
// decodes.  `ous` (null for the issuer) takes the subject's OU values.
Status name_ok(Span name, Span* ous, int* n_ous) {
  while (name.n) {
    Span rdn, ava, oid, value;
    if (!tlv(&name, 0x31, &rdn)) return NOT_NAME;
    if (!tlv(&rdn, 0x30, &ava) || rdn.n) return NOT_NAME;  // one AVA an RDN
    if (!tlv(&ava, 0x06, &oid) || !oid_ok(oid) || ava.n < 2) return NOT_NAME;
    u8 tag = ava.p[0];
    if (!tlv(&ava, tag, &value) || ava.n) return NOT_NAME;
    bool decodes = tag == TAG_UTF8        ? utf8_ok(value.p, value.n)
                   : tag == TAG_PRINTABLE ? printable_ok(value.p, value.n)
                   : tag == TAG_IA5       ? ascii_ok(value.p, value.n)
                                          : false;
    if (!decodes) return NOT_NAME;
    if (ous && oid.n == 3 && memcmp(oid.p, OID_OU, 3) == 0) {
      if (tag == TAG_IA5) return NOT_NAME;
      if (*n_ous == MAX_OUS) return NO_ROOM;
      ous[(*n_ous)++] = value;
    }
  }
  return OK;
}

bool digits(const u8* p, int n, int* out) {
  int v = 0;
  for (int i = 0; i < n; ++i) {
    if (p[i] < '0' || p[i] > '9') return false;
    v = v * 10 + (p[i] - '0');
  }
  *out = v;
  return true;
}

// A Time at the head of `in` as seconds since 1970: UTCTime
// YYMMDDHHMMSSZ (1950-2049) or GeneralizedTime YYYYMMDDHHMMSSZ.
bool read_time(Span* in, i64* out) {
  Span t;
  int year;
  if (in->n && in->p[0] == 0x17) {
    if (!tlv(in, 0x17, &t) || t.n != 13 || !digits(t.p, 2, &year)) return false;
    year += year >= 50 ? 1900 : 2000;
    t.p += 2;
  } else {
    if (!tlv(in, 0x18, &t) || t.n != 15 || !digits(t.p, 4, &year)) return false;
    if (year < 1950) return false;
    t.p += 4;
  }
  int mon, day, hh, mm, ss;
  if (!digits(t.p, 2, &mon) || !digits(t.p + 2, 2, &day) ||
      !digits(t.p + 4, 2, &hh) || !digits(t.p + 6, 2, &mm) ||
      !digits(t.p + 8, 2, &ss) || t.p[10] != 'Z')
    return false;
  static const int mdays[12] = {31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31};
  bool leap = (year % 4 == 0 && year % 100 != 0) || year % 400 == 0;
  if (mon < 1 || mon > 12 || day < 1 ||
      day > mdays[mon - 1] + (mon == 2 && leap ? 1 : 0) || hh > 23 ||
      mm > 59 || ss > 59)
    return false;
  // days from the civil date (H. Hinnant's days_from_civil)
  i64 y = year - (mon <= 2 ? 1 : 0);
  i64 era = y / 400;  // y >= 1949: no negative eras
  i64 yoe = y - era * 400;
  i64 doy = (153 * (mon + (mon > 2 ? -3 : 9)) + 2) / 5 + day - 1;
  i64 doe = yoe * 365 + yoe / 4 - yoe / 100 + doy;
  i64 days = era * 146097 + doe - 719468;
  *out = days * 86400 + hh * 3600 + mm * 60 + ss;
  return true;
}

// A positive INTEGER of at most `max` value bytes in minimal form;
// `body` keeps its contents, sign byte included.
bool positive_int(Span* in, size_t max, Span* body) {
  if (!tlv(in, 0x02, body) || body->n == 0) return false;
  const u8* p = body->p;
  if (p[0] & 0x80) return false;
  if (body->n > 1 && p[0] == 0 && !(p[1] & 0x80)) return false;
  return body->n - (p[0] == 0 && body->n > 1 ? 1 : 0) <= max;
}

void int_to_32(const Span& body, u8* out) {
  size_t skip = (body.n == 33) ? 1 : 0;
  memset(out, 0, 32);
  memcpy(out + 32 - (body.n - skip), body.p + skip, body.n - skip);
}

bool zero32(const u8* a) {
  for (int i = 0; i < 32; ++i)
    if (a[i]) return false;
  return true;
}

// DER of one INTEGER from a 32-byte big-endian value; returns its length
int put_int(const u8* v32, u8* out) {
  int skip = 0;
  while (skip < 31 && v32[skip] == 0) ++skip;
  int pad = (v32[skip] & 0x80) ? 1 : 0;
  out[0] = 0x02;
  out[1] = u8(32 - skip + pad);
  if (pad) out[2] = 0;
  memcpy(out + 2 + pad, v32 + skip, size_t(32 - skip));
  return 2 + pad + 32 - skip;
}

const u8 ALG_ECDSA_SHA256[12] = {0x30, 0x0a, 0x06, 0x08, 0x2a, 0x86,
                                 0x48, 0xce, 0x3d, 0x04, 0x03, 0x02};
// SubjectPublicKeyInfo of an uncompressed P-256 point, up to the point
const u8 SPKI_P256[27] = {0x30, 0x59, 0x30, 0x13, 0x06, 0x07, 0x2a, 0x86, 0x48,
                          0xce, 0x3d, 0x02, 0x01, 0x06, 0x08, 0x2a, 0x86, 0x48,
                          0xce, 0x3d, 0x03, 0x01, 0x07, 0x03, 0x42, 0x00, 0x04};

bool take(Span* in, const u8* bytes, size_t n) {
  if (in->n < n || memcmp(in->p, bytes, n) != 0) return false;
  in->p += n;
  in->n -= n;
  return true;
}

// ---------------------------------------------------------------------------
// PEM and the SerializedIdentity around it, in their canonical forms.
// ---------------------------------------------------------------------------

const char PEM_HEAD[] = "-----BEGIN CERTIFICATE-----\n";
const char PEM_FOOT[] = "-----END CERTIFICATE-----\n";

int b64_value(u8 c) {
  if (c >= 'A' && c <= 'Z') return c - 'A';
  if (c >= 'a' && c <= 'z') return c - 'a' + 26;
  if (c >= '0' && c <= '9') return c - '0' + 52;
  if (c == '+') return 62;
  if (c == '/') return 63;
  return -1;
}

// The DER of one canonical PEM block (what `public_bytes(PEM)` writes:
// 64 columns, LF, padded, no stray bits, nothing before or after), into
// `out`; its length, or -1.
i64 pem_to_der(Span pem, u8* out) {
  const size_t head = sizeof(PEM_HEAD) - 1, foot = sizeof(PEM_FOOT) - 1;
  if (pem.n < head + foot + 5 || memcmp(pem.p, PEM_HEAD, head) != 0 ||
      memcmp(pem.p + pem.n - foot, PEM_FOOT, foot) != 0)
    return -1;
  const u8* p = pem.p + head;
  const u8* end = pem.p + pem.n - foot;
  u8* o = out;
  bool last = false;  // a short or padded line was read
  while (p < end) {
    if (last) return -1;
    const u8* eol = static_cast<const u8*>(memchr(p, '\n', size_t(end - p)));
    if (!eol) return -1;
    size_t len = size_t(eol - p);
    if (len == 0 || len > 64 || len % 4) return -1;
    if (len < 64) last = true;
    for (size_t i = 0; i < len; i += 4) {
      int a = b64_value(p[i]), b = b64_value(p[i + 1]);
      if (a < 0 || b < 0) return -1;
      if (p[i + 2] == '=') {  // xx==: one byte, at the very end
        if (p[i + 3] != '=' || i + 4 != len || (b & 0x0f)) return -1;
        *o++ = u8((a << 2) | (b >> 4));
        last = true;
        break;
      }
      int c = b64_value(p[i + 2]);
      if (c < 0) return -1;
      if (p[i + 3] == '=') {  // xxx=: two bytes, at the very end
        if (i + 4 != len || (c & 0x03)) return -1;
        *o++ = u8((a << 2) | (b >> 4));
        *o++ = u8((b << 4) | (c >> 2));
        last = true;
        break;
      }
      int d = b64_value(p[i + 3]);
      if (d < 0) return -1;
      *o++ = u8((a << 2) | (b >> 4));
      *o++ = u8((b << 4) | (c >> 2));
      *o++ = u8((c << 6) | d);
    }
    p = eol + 1;
  }
  return o - out;
}

// A length-delimited field `tag` at the head of `in`, its length a
// minimal varint and not zero (proto3 leaves an empty field out)
bool wire_field(Span* in, u8 tag, Span* body) {
  if (in->n < 2 || in->p[0] != tag) return false;
  size_t len = 0, i = 1;
  int shift = 0;
  for (;; ++i, shift += 7) {
    if (i >= in->n || shift > 21) return false;
    u8 c = in->p[i];
    len |= size_t(c & 0x7f) << shift;
    if (!(c & 0x80)) {
      if (c == 0 && shift) return false;  // a padded varint
      break;
    }
  }
  ++i;
  if (len == 0 || len > in->n - i) return false;
  body->p = in->p + i;
  body->n = len;
  in->p += i + len;
  in->n -= i + len;
  return true;
}

// ---------------------------------------------------------------------------
// One certificate.
// ---------------------------------------------------------------------------

struct Fields {
  Span issuer, subject, serial, tbs;
  i64 not_before, not_after;
  u8 r[32], s[32];
  const u8* xy;
  Span ous[MAX_OUS];
  int n_ous;
};

Status read_cert(Span der, Fields* f) {
  Span cert, tbs, body;
  if (!tlv(&der, 0x30, &cert) || der.n) return NOT_DER;
  if (!tlv(&cert, 0x30, &tbs, &f->tbs)) return NOT_DER;
  // version [0] EXPLICIT INTEGER 2
  static const u8 V3[5] = {0xa0, 0x03, 0x02, 0x01, 0x02};
  if (!take(&tbs, V3, 5)) return NOT_V3;
  if (!positive_int(&tbs, 20, &f->serial)) return NOT_SERIAL;
  if (!take(&tbs, ALG_ECDSA_SHA256, 12)) return NOT_ALGORITHM;
  if (!tlv(&tbs, 0x30, &body, &f->issuer)) return NOT_DER;
  Status st = name_ok(body, nullptr, nullptr);
  if (st != OK) return st;
  if (!tlv(&tbs, 0x30, &body)) return NOT_DER;
  if (!read_time(&body, &f->not_before) || !read_time(&body, &f->not_after) ||
      body.n)
    return NOT_VALIDITY;
  if (!tlv(&tbs, 0x30, &body, &f->subject)) return NOT_DER;
  f->n_ous = 0;
  st = name_ok(body, f->ous, &f->n_ous);
  if (st != OK) return st;
  if (!take(&tbs, SPKI_P256, 27) || tbs.n < 64) return NOT_KEY;
  f->xy = tbs.p;
  if (!on_p256(f->xy, f->xy + 32)) return NOT_KEY;
  tbs.p += 64;
  tbs.n -= 64;
  if (tbs.n) {
    // no unique identifiers ([1], [2]): extensions or nothing
    if (tbs.p[0] != 0xa3) return NOT_V3;
    Span wrap, exts, ext, oid, value;
    if (!tlv(&tbs, 0xa3, &wrap) || tbs.n) return NOT_EXTENSIONS;
    if (!tlv(&wrap, 0x30, &exts) || wrap.n || !exts.n) return NOT_EXTENSIONS;
    while (exts.n) {
      if (!tlv(&exts, 0x30, &ext) || !tlv(&ext, 0x06, &oid) || !oid_ok(oid))
        return NOT_EXTENSIONS;
      static const u8 CRITICAL[3] = {0x01, 0x01, 0xff};
      if (ext.n && ext.p[0] == 0x01 && !take(&ext, CRITICAL, 3))
        return NOT_EXTENSIONS;  // FALSE is the default and is left out
      if (!tlv(&ext, 0x04, &value) || ext.n) return NOT_EXTENSIONS;
    }
  }
  if (!take(&cert, ALG_ECDSA_SHA256, 12)) return NOT_ALGORITHM;
  // BIT STRING, no unused bits, of SEQUENCE { r INTEGER, s INTEGER }
  Span bits, sig, r, s;
  if (!tlv(&cert, 0x03, &bits) || cert.n) return NOT_DER;
  if (bits.n < 1 || bits.p[0] != 0) return NOT_SIGNATURE;
  bits.p += 1;
  bits.n -= 1;
  if (!tlv(&bits, 0x30, &sig) || bits.n) return NOT_SIGNATURE;
  if (!positive_int(&sig, 32, &r) || !positive_int(&sig, 32, &s) || sig.n)
    return NOT_SIGNATURE;
  int_to_32(r, f->r);
  int_to_32(s, f->s);
  if (zero32(f->r) || zero32(f->s) || memcmp(f->r, N_BE, 32) >= 0 ||
      memcmp(f->s, N_BE, 32) >= 0)
    return NOT_SIGNATURE;
  return OK;
}

}  // namespace

extern "C" {

// the width of a `meta` row, for the wrapper to hold its layout to
int fabric_x509_meta_cols() { return N_COLS; }

// Read n identities, items[off[i]:off[i+1]] each: a SerializedIdentity
// (`wrapped` 1) or a bare PEM certificate (0).
//
//   meta    n x N_COLS: the status, then for a status of 0 the offsets
//           and lengths of the MSP id and the PEM in `items`, of the
//           DER, the issuer, the subject, the serial number's contents
//           and the OU values in `der`, notBefore / notAfter as seconds
//           since 1970, and the length of the low-S signature;
//   der     the certificates' DER, one after another (room for the sum
//           of the items' lengths);
//   digest  n x 32: SHA-256 of the TBS bytes;
//   xy      n x 64: the key's X and Y, 32 bytes big-endian each;
//   rs      n x 64: the signature's r and s as they stand;
//   lowsig  n x 72: DER of (r, min(s, n - s)), what
//           fabric_ecdsa_verify_host takes (a certificate's signature
//           is valid with either S).
//
// Returns 0, or -1 where fabric_ecdsa_verify_host cannot verify (no
// libcrypto): the caller then reads every certificate as ever.
int fabric_x509_read(int n, const u8* items, const i64* off, int wrapped,
                     i64* meta, u8* der, u8* digest, u8* xy, u8* rs,
                     u8* lowsig) {
  if (!fabric_ecdsa_host_ok()) return -1;
  u8* der_at = der;
  for (int i = 0; i < n; ++i) {
    i64* row = meta + size_t(i) * N_COLS;
    memset(row, 0, sizeof(i64) * N_COLS);
    Span item = {items + off[i], size_t(off[i + 1] - off[i])};
    Span mspid = {item.p, 0}, pem = item;
    if (wrapped) {
      bool ok = wire_field(&item, 0x0a, &mspid) &&
                wire_field(&item, 0x12, &pem) && item.n == 0;
      for (size_t k = 0; ok && k < mspid.n; ++k)
        ok = mspid.p[k] > 0x20 && mspid.p[k] < 0x7f;
      if (!ok) { row[C_STATUS] = NOT_WIRE; continue; }
    }
    i64 der_len = pem_to_der(pem, der_at);
    if (der_len < 0) { row[C_STATUS] = NOT_PEM; continue; }
    Fields f;
    Status st = read_cert({der_at, size_t(der_len)}, &f);
    row[C_STATUS] = st;
    if (st != OK) continue;
    row[C_MSPID_OFF] = mspid.p - items;
    row[C_MSPID_LEN] = i64(mspid.n);
    row[C_PEM_OFF] = pem.p - items;
    row[C_PEM_LEN] = i64(pem.n);
    row[C_DER_OFF] = der_at - der;
    row[C_DER_LEN] = der_len;
    row[C_ISSUER_OFF] = f.issuer.p - der;
    row[C_ISSUER_LEN] = i64(f.issuer.n);
    row[C_SUBJECT_OFF] = f.subject.p - der;
    row[C_SUBJECT_LEN] = i64(f.subject.n);
    row[C_SERIAL_OFF] = f.serial.p - der;
    row[C_SERIAL_LEN] = i64(f.serial.n);
    row[C_NOT_BEFORE] = f.not_before;
    row[C_NOT_AFTER] = f.not_after;
    row[C_OU_COUNT] = f.n_ous;
    for (int k = 0; k < f.n_ous; ++k) {
      row[C_OU0 + 2 * k] = f.ous[k].p - der;
      row[C_OU0 + 2 * k + 1] = i64(f.ous[k].n);
    }
    fabric_sha256(f.tbs.p, f.tbs.n, digest + 32 * size_t(i));
    memcpy(xy + 64 * size_t(i), f.xy, 64);
    memcpy(rs + 64 * size_t(i), f.r, 32);
    memcpy(rs + 64 * size_t(i) + 32, f.s, 32);
    // (r, min(s, n - s)): n - s by schoolbook subtraction, big-endian
    u8 low[32];
    memcpy(low, f.s, 32);
    if (memcmp(f.s, HALF_N_BE, 32) > 0) {
      int borrow = 0;
      for (int k = 31; k >= 0; --k) {
        int d = int(N_BE[k]) - int(f.s[k]) - borrow;
        borrow = d < 0 ? 1 : 0;
        low[k] = u8(d + (borrow ? 256 : 0));
      }
    }
    u8* sig = lowsig + 72 * size_t(i);
    int at = 2;
    at += put_int(f.r, sig + at);
    at += put_int(low, sig + at);
    sig[0] = 0x30;
    sig[1] = u8(at - 2);
    row[C_LOWSIG_LEN] = at;
    der_at += der_len;
  }
  return 0;
}

}  // extern "C"
