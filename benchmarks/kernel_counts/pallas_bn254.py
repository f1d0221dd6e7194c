"""What one lane of the BN254 kernel (`csp/tpu/pallas_bn254.py`, the
device operation named `pallas_bn254_pairing`) moves and computes,
counted from the kernel's own structure: the bytes that cross HBM for
a lane, and its 16-bit-limb multiplications.  The kernel computes a
lane's three G1 multi-scalar products (T1, T2, T3 of a credential
proof; for a pseudonym signature only T3 has non-zero scalars, the
ladder runs all the same); it computes no pairing, whatever its name.

All of it per lane of the bucket, padding included: a padded lane runs
the same ladder.  `N_ATTRS` is the configuration's (four attributes).
"""

PATTERN = "pallas_bn254"     # in the device trace's operation names

N_ATTRS = 4
N_SHARED = 3 + N_ATTRS       # g1, h_sk, h_rand, h_attrs[*]
N_LANE_BASES = 4             # a', a_bar, b', nym
N_TERMS = 11 + N_ATTRS       # T1: 4, T2: 4 + N_ATTRS, T3: 3
WINDOWS = 64                 # 4-bit windows of a 256-bit scalar
TABLE = 16
LIMBS = 17                   # 16-bit limbs of a field element (272 bits)
WORD = 4                     # bytes of a uint32


def bytes_in_per_lane() -> int:
    """Lane coordinates (2 x 4 bases x 8 words), the bases' infinity
    flags, and the packed window digits (8 words a term)."""
    return WORD * (2 * N_LANE_BASES * 8 + N_LANE_BASES + N_TERMS * 8)


def bytes_out_per_lane() -> int:
    """Nine Jacobian coordinates and a row of infinity flags, 17 limbs
    each, a uint32 a limb."""
    return WORD * 10 * LIMBS


def table_bytes_per_launch() -> int:
    """The issuer key's window tables (x, y, z limbs and a flag an
    entry) and the five field constants, read once a launch."""
    return WORD * (N_SHARED * TABLE * (3 * LIMBS + 1) + 4 * LIMBS + (LIMBS - 1))


def hbm_bytes_per_lane(bucket: int) -> float:
    return bytes_in_per_lane() + bytes_out_per_lane() + table_bytes_per_launch() / bucket


# -- limb multiplications ----------------------------------------------------

SCHOOLBOOK = LIMBS * LIMBS           # one 17 x 17 product array
FIELD_MUL = 3 * SCHOOLBOOK           # Montgomery: a*b, T*m' and u*m
IS_ZERO = 2 * SCHOOLBOOK             # one REDC: T*m' and u*m
DOUBLE = 7 * FIELD_MUL               # dbl-2009-l for a = 0, as written
# add-2007-bl as written: 16 products, two zero tests, and the doubling
# it always computes for the equal-points case
ADD_FULL = 16 * FIELD_MUL + 2 * IS_ZERO + DOUBLE
# madd-2007-bl as written (table build): 11 products, two zero tests,
# the doubling for the equal-points case
ADD_MIXED = 11 * FIELD_MUL + 2 * IS_ZERO + DOUBLE


def limb_multiplies_per_lane() -> int:
    """16-bit x 16-bit multiplications of one lane: the per-lane window
    tables (14 mixed additions for each of the four bases), 64 windows
    of four doublings of three accumulators and one full addition a
    term, and the final reduction of nine coordinates."""
    tables = N_LANE_BASES * (TABLE - 2) * ADD_MIXED
    ladder = WINDOWS * (4 * 3 * DOUBLE + N_TERMS * ADD_FULL)
    return tables + ladder + 9 * FIELD_MUL
