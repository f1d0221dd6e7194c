"""A validator that never asks what a chaincode's definition says:
every namespace is decided under the channel's default Endorsement
policy.  Breaks "the endorsement policy is evaluated for every
transaction and every namespace it writes: the namespace's committed
definition where it has one": a transaction endorsed by the two
organisations its chaincode's `OutOf(2, ...)` wants is refused (the
default wants three), one endorsed by three organisations outside its
chaincode's rule is accepted, and its write lands in the state."""


def apply():
    from fabric_tpu.peer.validation_plugins import PolicyProvider

    PolicyProvider._resolve_chaincode_policy = (
        lambda self, namespace: self.default_policy()
    )
