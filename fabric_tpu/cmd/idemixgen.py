"""idemixgen: generate idemix issuer keys and signer credentials
(reference cmd/idemixgen + msp idemix config generation).

    idemixgen ca-keygen --output idemix-config
    idemixgen signerconfig --output idemix-config \
        --org-unit org1 --enrollment-id user1 [--admin]
"""

from __future__ import annotations

import argparse
import os
import pickle
import sys

from fabric_tpu.msp.idemixmsp import (
    generate_issuer,
    issue_signer_config,
    write_issuer_public_key,
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="idemixgen")
    sub = ap.add_subparsers(dest="cmd", required=True)
    ca = sub.add_parser("ca-keygen")
    ca.add_argument("--output", default="idemix-config")
    sc = sub.add_parser("signerconfig")
    sc.add_argument("--output", default="idemix-config")
    sc.add_argument("--org-unit", default="")
    sc.add_argument("--enrollment-id", default="user")
    sc.add_argument("--admin", action="store_true")
    args = ap.parse_args(argv)

    ca_dir = os.path.join(args.output, "ca")
    if args.cmd == "ca-keygen":
        os.makedirs(ca_dir, exist_ok=True)
        issuer = generate_issuer()
        with open(os.path.join(ca_dir, "IssuerKey.pkl"), "wb") as f:
            pickle.dump(issuer, f)
        # what a verifier needs: configtxgen reads it for an
        # organisation with `msptype: idemix`
        pub = write_issuer_public_key(issuer, args.output)
        print(f"issuer key material written to {ca_dir}; public key {pub}")
        return 0

    from fabric_tpu.msp.idemixmsp import ROLE_ADMIN, ROLE_MEMBER

    with open(os.path.join(ca_dir, "IssuerKey.pkl"), "rb") as f:
        issuer = pickle.load(f)
    conf = issue_signer_config(
        issuer,
        mspid="IdemixMSP",
        ou=args.org_unit,
        role=ROLE_ADMIN if args.admin else ROLE_MEMBER,
        enrollment_id=args.enrollment_id,
    )
    user_dir = os.path.join(args.output, "user")
    os.makedirs(user_dir, exist_ok=True)
    with open(os.path.join(user_dir, "SignerConfig.pb"), "wb") as f:
        f.write(conf.SerializeToString())
    print(f"signer config written to {user_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
