"""Share of the traced window in which no op ran on a chip (1 - union of
op intervals / window), the largest over the chips used."""
from harness import readers


def read(rec):
    return readers.idle_share_pct(rec)
