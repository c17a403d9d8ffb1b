"""Least work of one dense score + top-k call: ``queries`` vectors against
``docs`` stored float32 vectors of width ``dim``.

Bytes: the stored vectors and the queries, read once. Operations: the inner
products, ``2 * queries * docs * dim``, counted against the bf16 peak.
"""


def per_call(shape: dict) -> dict:
    q, d, dim = shape["queries"], shape["docs"], shape["dim"]
    return {"bytes": 4.0 * dim * (d + q), "flops": 2.0 * q * d * dim}
