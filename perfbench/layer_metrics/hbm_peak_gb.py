"""Device: peak bytes held after the window on the fullest chip
(``memory_stats()``: live buffers plus the region reserved for the
programs' temporaries), in GB (1e9 bytes). It decides no PR: it says whether the cell
still fills the chip."""
from perfbench import harness


def read(run):
    peak = harness.memory_peak_bytes(run.cell.chips)
    return peak / 1e9 if peak else None
