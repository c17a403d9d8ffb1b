"""Mean, over the blocks of the traced stretch, of the last chip's end of the
lexical kernel less the first chip's: the chip that ends last sets the
block's time, since the reduce waits for every chip."""

from chipbench import mesh

KERNEL = r"lexical_scan"


def read(run):
    if run.device_trace is None:
        return None
    blocks = mesh.blocks(run.device_trace, KERNEL)
    if not blocks or len(blocks[0]) < 2:
        return None
    skews = [max(e for _, e in b) - min(e for _, e in b) for b in blocks]
    return sum(skews) / len(skews) * 1e-6
