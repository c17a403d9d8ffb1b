"""Device time of the cross-chip reduce a block, averaged over blocks and
chips: on each chip, after each call of the lexical kernel in the traced
stretch, from the start of the first op named ``mesh_reduce`` (the name
`topk.merge_across_lex` gives its all-gathers and merges, which a TPU trace
prints in each op's name) to the end of the last one before that chip's
next call. A program without that name reads nothing."""

from chipbench import mesh

KERNEL = r"lexical_scan"
REDUCE = r"mesh_reduce"


def read(run):
    if run.device_trace is None:
        return None
    times = mesh.after_each_call(run.device_trace, KERNEL, REDUCE)
    if not times:
        return None
    return sum(times) / len(times) * 1e-6
