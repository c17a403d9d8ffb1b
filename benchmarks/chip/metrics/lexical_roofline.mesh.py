"""The lexical kernel's share of its roofline in the traced stretch of a
serve window over a corpus sharded on the cell's chips: each chip's call
scans that chip's share, ``n_docs / chips`` documents, for one block."""

from chipbench import readers

KERNEL = r"lexical_scan"  # matched against the device op's name and HLO detail


def read(run):
    cfg = run.config
    shape = {"docs": cfg["n_docs"] // run.cell.chips, "pad": cfg["doc_len"][1],
             "vocab": cfg["vocab"]}
    return readers.roofline(run, KERNEL, "lexical_scan", shape)
