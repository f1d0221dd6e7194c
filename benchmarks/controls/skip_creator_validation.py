"""An MSP that validates every identity it is shown: no chain
signature, no validity window, no CRL, no role OU.  Breaks "every
creator's certificate chains to its organisation's CA by signature,
stands inside its validity window, is not on the organisation's CRL
and carries exactly one role OU": the planted creators whose only
fault is their certificate sign correctly, so they pass, and their
writes land in the state."""


def apply():
    from fabric_tpu.msp.msp import MSP

    MSP.validate = lambda self, identity: None
