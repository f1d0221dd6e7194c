"""A signature policy compiled without cauthdsl's `used` marks: a leaf
takes the first valid identity that satisfies its principal, whoever
took it before.  Breaks "an identity satisfies at most one principal of
a policy": the planted transaction endorsed by Org3, ONE peer of Org4
and Org5 then meets both `AND('Org3MSP.peer','Org4MSP.peer')` and
`AND('Org4MSP.peer','Org5MSP.peer')` of `cc6`'s rule with that one
peer, comes out VALID, and its write lands in the state."""


def apply():
    from fabric_tpu.policies import signature_policy

    inner = signature_policy._compile

    def _compile(policy, identities, deserializer):
        # `inner` compiles its sub-rules through the module's name, so
        # every leaf of every tree comes through here
        closure = inner(policy, identities, deserializer)
        if policy.WhichOneof("Type") != "signed_by":
            return closure
        return lambda valid, used: closure(valid, [False] * len(used))

    signature_policy._compile = _compile
