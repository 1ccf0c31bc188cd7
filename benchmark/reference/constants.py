"""THE BENCHMARK'S COPY of distributed_groth16_tpu/ops/constants.py as of PR 21 (commit
c67d115), kept here so that no later change to the program can move the
yardstick. Do not import the program's module in its place, and do not
edit this file in a PR that claims a gain. Original docstring follows.

BN254 curve and field constants.

Reference parity: the reference framework (zkHubHQ/distributed-groth16) uses
arkworks' ark-bn254 (and ark-bls12-377 in some examples). We standardise on
BN254 (alt_bn128), the curve of the Groth16 service path and of all circom
fixtures (ark-circom/src/circom/r1cs_reader.rs:163-189 hardcodes the 32-byte
BN254 prime).

Domain/FFT conventions match ark-poly's Radix2EvaluationDomain: the size-N
root of unity is GENERATOR^((r-1)/N) with GENERATOR the smallest multiplicative
generator of Fr (5 for BN254), and cosets use offset = GENERATOR
(secret-sharing/src/pss.rs:39-47).
"""

# ---------------------------------------------------------------------------
# BN254 (alt_bn128) parameters
# ---------------------------------------------------------------------------

# Base field modulus q and scalar field modulus r.
Q = 21888242871839275222246405745257275088696311157297823662689037894645226208583
R = 21888242871839275222246405745257275088548364400416034343698204186575808495617

# BN parameter x: q(x), r(x), t(x) are the standard BN polynomials.
BN_X = 4965661367192848881

# Multiplicative generators (smallest) — match arkworks' Fr::GENERATOR /
# Fq::GENERATOR used for coset offsets.
FR_GENERATOR = 5
FQ_GENERATOR = 3

# Two-adicity of r - 1 (28 for BN254 Fr).
FR_TWO_ADICITY = 28
# 2^28-th primitive root of unity in Fr, arkworks convention.
FR_TWO_ADIC_ROOT = pow(FR_GENERATOR, (R - 1) >> FR_TWO_ADICITY, R)

# G1: y^2 = x^3 + 3 over Fq
G1_B = 3
G1_GENERATOR = (1, 2)

# G2: y^2 = x^3 + b/xi over Fq2 = Fq[u]/(u^2+1), xi = 9 + u (D-type twist).
FQ2_NON_RESIDUE = (9, 1)  # xi
# b' = 3 / (9 + u)
G2_B = (
    19485874751759354771024239261021720505790618469301721065564631296452457478373,
    266929791119991161246907387137283842545076965332900288569378510910307636690,
)
G2_GENERATOR = (
    (
        10857046999023057135944570762232829481370756359578518086990519993285655852781,
        11559732032986387107991004021392285783925812861821192530917403151452391805634,
    ),
    (
        8495653923123431417604973247489272438418190587263600148770280649306958101930,
        4082367875863433681332203403145435568316851327593401208105741076214120093531,
    ),
)

# ate pairing loop count: 6x + 2
ATE_LOOP_COUNT = 6 * BN_X + 2

# ---------------------------------------------------------------------------
# Limb configuration for on-device (JAX) representation.
#
# Field elements live on device as uint32 tensors of shape (..., N_LIMBS),
# each limb holding LIMB_BITS bits (radix 2^16).  16x16-bit limbs cover 256
# bits; products of two limbs fit in uint32, which makes schoolbook/Montgomery
# products expressible in pure uint32 vector ops (TPU VPU native width).
# ---------------------------------------------------------------------------

LIMB_BITS = 16
N_LIMBS = 16
LIMB_MASK = (1 << LIMB_BITS) - 1

# Montgomery radix R_mont = 2^(LIMB_BITS * N_LIMBS) = 2^256.
MONT_BITS = LIMB_BITS * N_LIMBS


def to_limbs(x: int, n_limbs: int = N_LIMBS, bits: int = LIMB_BITS):
    """Little-endian limb decomposition of a Python int."""
    mask = (1 << bits) - 1
    return [(x >> (bits * i)) & mask for i in range(n_limbs)]


def from_limbs(limbs, bits: int = LIMB_BITS) -> int:
    acc = 0
    for i, limb in enumerate(limbs):
        acc |= int(limb) << (bits * i)
    return acc
