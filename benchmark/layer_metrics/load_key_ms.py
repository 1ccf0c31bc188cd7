"""Status DTO phases["load.key"], median: `np.load` of `proving_key.npz` and
the upload of its seven arrays, once per job (`models/groth16/keys.py`).
Host work, so the span time is sound although nothing fences the device."""

from ._phases import phase_ms

LAYER, UNIT, MOVES = "host preparation", "ms", "proof_p50_s"


def read(run):
    return phase_ms(run, "load.key")
