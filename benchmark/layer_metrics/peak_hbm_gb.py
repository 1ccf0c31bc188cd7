"""Peak device memory of the fullest chip (`memory_stats()`), in GB. It
moves `setup_s` only in the sense that set-up is what fills the memory
(keys, packed CRS); it is the one end-to-end metric every cell reports."""

LAYER, UNIT, MOVES = "device", "GB", "setup_s"


def read(run):
    return run["memory_peak_bytes"] / 1e9 if run["memory_peak_bytes"] else None
