"""Least work of one lexical-scan call over ``docs`` padded documents.

Bytes: each of ``docs * pad`` token slots in the least width any exact store
of the tokens needs, ``ceil(log2(vocab + 1))`` bits (the ids and a padding
value), plus a 4-byte length per document. Packing the tokens can therefore
not push a share past 100%. Operations: the term matching is integer
compares on the vector units, whose peak rate the v5e's documentation does
not publish, so no operation bound is given.
"""

import math


def per_call(shape: dict) -> dict:
    bits = math.ceil(math.log2(shape["vocab"] + 1))
    return {"bytes": shape["docs"] * (shape["pad"] * bits / 8 + 4), "flops": None}
