"""Batched SHA-256 on TPU.

One vectorized compression over all messages of a batch (reference:
msp/identities.go:169-196 hashes each message before `bccsp.Verify`;
bccsp/sw hash dispatch in bccsp/sw/impl.go).  Nothing in the product
routes to it: `TPUCSP.hash_batch` is hashlib, which was faster at every
batch size measured.  It stays as the SHA-256 offload capability
BASELINE.json names, compiled by `__graft_entry__.dryrun_multichip`
and held to hashlib by tests/test_csp_tpu.py.

TPU-first shape: every message is padded (standard SHA-256 Merkle–Damgård
padding, done host-side in numpy) to the same static number of 64-byte
blocks for its bucket, and the kernel runs the 64-round compression as a
`lax.fori_loop` over rounds with the whole batch in lockstep — uint32
VPU arithmetic, no data-dependent control flow, one jit per
(batch, n_blocks) bucket.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from fabric_tpu.csp.tpu import named_jit

_K = np.array(
    [
        0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
        0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
        0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
        0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
        0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
        0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
        0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
        0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
        0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
        0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
        0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
    ],
    dtype=np.uint32,
)

_H0 = np.array(
    [0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
     0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19],
    dtype=np.uint32,
)


def _rotr(x, n: int):
    return (x >> jnp.uint32(n)) | (x << jnp.uint32(32 - n))


def _compress_block(h, w_block):
    """One 64-round compression; h (..., 8), w_block (..., 16) uint32."""
    k = jnp.asarray(_K)

    def round_fn(i, state):
        a, b, c, d, e, f, g, hh, w = state
        wi = w[..., 0]
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = hh + s1 + ch + k[i] + wi
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        # message schedule computed in-place on a rolling 16-word window
        w15 = w[..., 1]
        w2 = w[..., 14]
        sig0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> jnp.uint32(3))
        sig1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> jnp.uint32(10))
        w_next = wi + sig0 + w[..., 9] + sig1
        w = jnp.concatenate([w[..., 1:], w_next[..., None]], axis=-1)
        return (t1 + t2, a, b, c, d + t1, e, f, g, w)

    a, b, c, d, e, f, g, hh = [h[..., i] for i in range(8)]
    a, b, c, d, e, f, g, hh, _ = jax.lax.fori_loop(
        0, 64, round_fn, (a, b, c, d, e, f, g, hh, w_block)
    )
    return h + jnp.stack([a, b, c, d, e, f, g, hh], axis=-1)


def sha256_kernel(words, nblk):
    """words: (B, n_blocks, 16) uint32 big-endian padded message words;
    nblk: (B,) int32 — how many blocks each lane actually occupies (its own
    Merkle–Damgård padding sits inside those blocks).  Lanes freeze once
    their block count is reached, so one jitted program serves mixed-length
    batches padded to a common static width.  Returns (B, 8) digest words."""
    n_blocks = words.shape[-2]
    h = jnp.broadcast_to(jnp.asarray(_H0), words.shape[:-2] + (8,))

    def body(i, h):
        blk = jax.lax.dynamic_index_in_dim(words, i, axis=-2, keepdims=False)
        h_new = _compress_block(h, blk)
        live = (i < nblk)[..., None]
        return jnp.where(live, h_new, h)

    return jax.lax.fori_loop(0, n_blocks, body, h)


@functools.lru_cache(maxsize=None)
def _jit_sha():
    return named_jit(sha256_kernel, "sha256_batch")


def pad_messages(msgs, n_blocks: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Standard SHA-256 padding, each message inside its OWN final block.

    Returns (words (B, n_blocks, 16) uint32, nblk (B,) int32): batches mix
    lengths freely; `n_blocks` only sets the static width (bucketing)."""
    blocks = [(len(m) + 9 + 63) // 64 for m in msgs]
    need = max(blocks) if blocks else 1
    if n_blocks is None:
        n_blocks = need
    if need > n_blocks:
        raise ValueError("messages need %d blocks > %d" % (need, n_blocks))
    out = np.zeros((len(msgs), n_blocks * 64), dtype=np.uint8)
    for i, m in enumerate(msgs):
        out[i, : len(m)] = np.frombuffer(m, dtype=np.uint8)
        out[i, len(m)] = 0x80
        bitlen = (8 * len(m)).to_bytes(8, "big")
        out[i, blocks[i] * 64 - 8 : blocks[i] * 64] = np.frombuffer(bitlen, dtype=np.uint8)
    words = out.reshape(len(msgs), n_blocks, 16, 4)
    packed = (
        (words[..., 0].astype(np.uint32) << 24)
        | (words[..., 1].astype(np.uint32) << 16)
        | (words[..., 2].astype(np.uint32) << 8)
        | words[..., 3].astype(np.uint32)
    )
    return packed, np.asarray(blocks, dtype=np.int32)


def digest_to_bytes(dig: np.ndarray) -> list[bytes]:
    """(B, 8) uint32 words -> list of 32-byte digests."""
    dig = np.asarray(dig)
    b = np.zeros((dig.shape[0], 32), dtype=np.uint8)
    for i in range(8):
        b[:, 4 * i] = (dig[:, i] >> 24) & 0xFF
        b[:, 4 * i + 1] = (dig[:, i] >> 16) & 0xFF
        b[:, 4 * i + 2] = (dig[:, i] >> 8) & 0xFF
        b[:, 4 * i + 3] = dig[:, i] & 0xFF
    return [row.tobytes() for row in b]


def sha256_batch(msgs, n_blocks: int | None = None) -> list[bytes]:
    """Hash a batch of messages on device (one jit per block-count bucket)."""
    if not msgs:
        return []
    words, nblk = pad_messages(msgs, n_blocks)
    return digest_to_bytes(np.asarray(_jit_sha()(words, nblk)))


__all__ = ["sha256_kernel", "sha256_batch", "pad_messages", "digest_to_bytes"]
