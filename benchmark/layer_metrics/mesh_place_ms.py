"""Status DTO phases["packing.place"], median: inside
`packing`, an `mpc_prove` job's QAP and witness shares moved party by
party from the chip that made them to the chip that holds the party (one
`prove.party` span a party), and put together as the mesh program's
sharded inputs. The host's dispatch of those copies. None where no job of
the window has the key: a round on one chip (the star's) places nothing,
and a program without the mesh round has no such phase."""

from ._phases import phase_ms

LAYER, UNIT, MOVES = "collectives", "ms", "proof_p50_s"


def read(run):
    return phase_ms(run, "packing.place")
