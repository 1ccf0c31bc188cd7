"""What the mesh round's all_gathers bring each chip, per proof, in MB
(10^6 bytes): the movement of the program's counter
`mesh_gather_bytes_total` over the window's completed proofs
(`_counters.movement_per_proof`). The program counts the bytes from the
gathers' shapes when it is traced and adds them once a launch: the
numerator of a share of the chips' interconnect. None where the program
has no such counter (one without the mesh round) or the window completed no
proof; 0 where the rounds took the star's shape on one chip."""

from ._counters import movement_per_proof

LAYER, UNIT, MOVES = "collectives", "MB", "proof_p50_s"
FAMILY = "mesh_gather_bytes_total"


def read(run):
    per_proof = movement_per_proof(run, FAMILY)
    return None if per_proof is None else per_proof / 1e6
