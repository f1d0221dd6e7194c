"""Idemix BN254 batch-verify benchmark (BASELINE.json config #5).

The reference verifies each idemix signature with ~10 G1/G2 scalar
multiplications re-deriving the ZK commitments plus TWO pairings
(idemix/signature.go:243,290-291, FP256BN.Ate).  The TPU build's
verify_batch collapses all pairing checks for one issuer into TWO
pairings per batch via random linear combination, leaving per-item
Schnorr recomputation as the host cost.

    python scripts/bench_idemix.py [--sigs 64] [--device]

Prints one JSON line: sequential vs batched sigs/s (and, with
--device, the TPU-batched Schnorr path — csp/tpu/bn254_batch.py — at
the same batch size; one warm-up call pays the per-shape compile).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sigs", type=int, default=64)
    ap.add_argument("--device", action="store_true")
    args = ap.parse_args()

    from fabric_tpu.idemix import bn254 as bn
    from fabric_tpu.idemix import signature
    from fabric_tpu.idemix.credential import (
        attribute_to_scalar,
        new_cred_request,
        new_credential,
    )
    from fabric_tpu.idemix.issuer import IssuerKey

    rng = random.Random(42)
    ik = IssuerKey.generate(["OU", "Role"], rng=rng)
    sk = bn.rand_zr(rng)
    req = new_cred_request(sk, b"nonce", ik.ipk, rng=rng)
    attrs = [attribute_to_scalar("org1"), attribute_to_scalar(2)]
    cred = new_credential(ik, req, attrs, rng=rng)

    sigs, msgs = [], []
    for i in range(args.sigs):
        m = b"bench-%d" % i
        sigs.append(signature.new_signature(
            cred, sk, ik.ipk, m, rng=rng
        ))
        msgs.append(m)

    t0 = time.perf_counter()
    ok = [signature.verify(s, ik.ipk, m) for s, m in zip(sigs, msgs)]
    t_seq = time.perf_counter() - t0
    assert all(ok)

    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        ok = signature.verify_batch(sigs, ik.ipk, msgs, rng)
        best = min(best, time.perf_counter() - t0)
    assert all(ok)

    out = {
        "metric": "idemix_bn254_batch_verify",
        "sigs": args.sigs,
        "sequential_sigs_s": round(args.sigs / t_seq, 2),
        "batched_sigs_s": round(args.sigs / best, 2),
        "speedup": round(t_seq / best, 2),
    }
    if args.device:
        ok = signature.verify_batch_device(sigs, ik.ipk, msgs, rng)  # warm
        assert all(ok)
        dbest = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            ok = signature.verify_batch_device(sigs, ik.ipk, msgs, rng)
            dbest = min(dbest, time.perf_counter() - t0)
        assert all(ok)
        out["device_batched_sigs_s"] = round(args.sigs / dbest, 2)
        out["device_speedup_vs_host_batch"] = round(best / dbest, 2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
