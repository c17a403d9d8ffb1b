"""The lexical kernel's share of its roofline in the traced experiment: each
call scans one checkpoint segment (``chunk_size * segment_chunks`` docs)."""

from chipbench import readers

KERNEL = r"lexical_scan"  # matched against the device op's name and HLO detail


def read(run):
    cfg = run.config
    shape = {
        "docs": cfg["chunk_size"] * cfg["segment_chunks"],
        "pad": cfg["doc_len"][1],
        "vocab": cfg["vocab"],
    }
    return readers.roofline(run, KERNEL, "lexical_scan", shape)
