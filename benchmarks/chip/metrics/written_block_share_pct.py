"""Percent of a file's blocks that an edit wrote: 100 x the sum of the
program's ``written`` over the sum of its ``blocks``, over the edits
completed in the window. ``written`` counts the genesis block too where the
block index changed, so an edit that writes one block of twenty reads 5%
and one that also rewrites the index 10%."""
from chipbench.traffic import EDIT


def read(r):
    edits = [d.result for d in r.window.in_window()
             if d.kind == "write" and d.index[0] == EDIT and d.result]
    blocks = sum(e["blocks"] for e in edits)
    return 100.0 * sum(e["written"] for e in edits) / blocks if blocks else None
