"""Packed corpus segments — width-aware token storage, exact by construction.

The corpus everywhere else in this repro is a PAD-padded ``int32`` token
matrix: 4 bytes per position for vocabularies that fit in 8–21 bits. Every
hop that moves tokens — checkpoint I/O, host→device staging in
`pipeline.prefetch_segments`, HBM→VMEM tiles in the lexical-scan kernel —
pays those 4 bytes, and `BENCH_sharded.json` shows the scan is bandwidth
bound. This module shrinks bytes *moved* without touching bytes *written*:

    **pack on the producer, decode on the consumer, exact round-trip.**

Pack widths (chosen from the vocab size, ``mode="auto"``):

    ========  ======================  ==========================  =========
    mode      representable           storage                     bytes/tok
    ========  ======================  ==========================  =========
    ``u8``    vocab <= 255            ``uint8  [n, L]``           1
    ``u16``   vocab <= 65535          ``uint16 [n, L]``           2
    bitpack   bits(vocab) <= 31       ``int32  [n, G * bits]``    bits / 8
    ========  ======================  ==========================  =========

where ``bits = (vocab).bit_length()`` (the sentinel below must fit too) and
``G = ceil(L / 32)``. Bitpack is *bit-plane* layout: positions are grouped
32 at a time along ``L``; group ``g`` stores ``bits`` int32 words, and bit
``t`` of word ``p`` is bit ``p`` of the token at position ``32 g + t``.
Decode is ``token = sum_p ((word_p >> t) & 1) << p`` — an unrolled loop of
``bits`` shift/mask/add VPU ops per 32 positions, exact in integer
arithmetic, identical under numpy, XLA and Pallas (arithmetic right shift
plus ``& 1`` reads the correct bit even from a negative int32 word).

PAD handling: real tokens are ``0 .. vocab-1`` and `scoring.PAD_TOKEN` is
``-1``, which no unsigned width can hold — so pack maps PAD to the sentinel
value ``vocab`` (always representable by construction: widths are chosen
for ``vocab``, not ``vocab - 1``) and unpack maps it back. The round-trip
``unpack(pack(x)) == x`` is exact for every width, so scores downstream are
byte-identical to the unpacked path *by construction* — packing changes
bytes moved, never bytes written.

:class:`PackedCorpus` is a registered pytree (leaves: packed tokens and
lengths; the :class:`PackSpec` rides in the static treedef), so all
leading-dim plumbing — shard ``take``, segment slicing, ``fold_chunks``
reshape, ``NamedSharding`` placement, jit caching — works unchanged, and
two different pack specs can never alias one trace.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scoring import PAD_TOKEN

# knob values accepted by resolve_mode / TuningConfig.token_pack
PACK_MODES = ("none", "auto", "8", "16", "bitpack")
# storage layouts a PackSpec can carry ("none" never reaches a PackSpec)
_RESOLVED = ("u8", "u16", "bitpack")

_GROUP = 32  # positions per bit-plane group (one int32 word per plane)


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Static description of one packed token matrix.

    Frozen and hashable so it can live in jit static arguments and pytree
    treedefs. ``length`` is the *unpacked* L (the packed trailing dim is
    derived from it); ``bits`` is only meaningful for ``mode="bitpack"``.
    """

    mode: str  # u8 | u16 | bitpack
    vocab: int  # tokens are 0..vocab-1; `vocab` itself is the PAD sentinel
    length: int  # unpacked trailing dim L
    bits: int = 0  # bit-plane count (bitpack only)

    def __post_init__(self):
        if self.mode not in _RESOLVED:
            raise ValueError(f"unknown pack mode {self.mode!r}; expected {_RESOLVED}")
        if self.vocab < 1:
            raise ValueError(f"vocab must be >= 1, got {self.vocab}")
        if self.length < 0:
            raise ValueError(f"length must be >= 0, got {self.length}")
        if self.mode == "u8" and self.vocab > 0xFF:
            raise ValueError(f"u8 cannot hold sentinel {self.vocab}")
        if self.mode == "u16" and self.vocab > 0xFFFF:
            raise ValueError(f"u16 cannot hold sentinel {self.vocab}")
        if self.mode == "bitpack":
            need = int(self.vocab).bit_length()
            if not 1 <= need <= 31:
                raise ValueError(f"bitpack needs 1..31 bits, vocab {self.vocab}")
            if self.bits != need:
                raise ValueError(f"bits {self.bits} != bit_length(vocab) {need}")

    @property
    def packed_width(self) -> int:
        """Trailing dim of the packed matrix."""
        if self.mode == "bitpack":
            return -(-self.length // _GROUP) * self.bits
        return self.length

    def packed_dtype(self) -> np.dtype:
        return np.dtype(
            {"u8": np.uint8, "u16": np.uint16, "bitpack": np.int32}[self.mode]
        )

    def nbytes(self, n_docs: int) -> int:
        """Token bytes for ``n_docs`` packed rows (lengths excluded)."""
        return n_docs * self.packed_width * self.packed_dtype().itemsize

    def describe(self) -> dict:
        return dataclasses.asdict(self)


def resolve_mode(vocab: int, mode: str) -> str:
    """Map a ``token_pack`` knob value to a storage layout for ``vocab``.

    ``"auto"`` picks the narrowest width that holds the sentinel ``vocab``:
    ``u8``, then ``u16``, then ``bitpack`` (bitpack only engages above 16
    bits — below that the cast decode of a native width is cheaper), then
    ``"none"`` for >=32-bit vocabs. A *forced* width the vocab cannot fit
    degrades to the auto choice rather than failing — the tuning contract:
    knobs degrade, never fail.
    """
    if mode not in PACK_MODES:
        raise ValueError(f"unknown token_pack {mode!r}; expected one of {PACK_MODES}")
    if mode == "none":
        return "none"
    bits = int(vocab).bit_length()
    if mode == "8" and vocab <= 0xFF:
        return "u8"
    if mode == "16" and vocab <= 0xFFFF:
        return "u16"
    if mode == "bitpack" and bits <= 31:
        return "bitpack"
    # auto, or a forced width that can't represent the sentinel
    if vocab <= 0xFF:
        return "u8"
    if vocab <= 0xFFFF:
        return "u16"
    if bits <= 31:
        return "bitpack"
    return "none"


def make_spec(vocab: int, length: int, mode: str) -> PackSpec | None:
    """Resolve ``mode`` for ``vocab`` into a spec; ``None`` means unpacked."""
    resolved = resolve_mode(vocab, mode)
    if resolved == "none":
        return None
    bits = int(vocab).bit_length() if resolved == "bitpack" else 0
    return PackSpec(mode=resolved, vocab=int(vocab), length=int(length), bits=bits)


def pack_tokens(tokens: Any, spec: PackSpec) -> np.ndarray:
    """Pack a PAD-padded int32 token matrix ``[n, L]`` under ``spec``.

    Host-side (numpy) — packing happens on the producer, before staging.
    Validates the token range: values outside ``{PAD_TOKEN} | [0, vocab)``
    cannot round-trip and raise instead of corrupting silently.
    """
    t = np.asarray(tokens)
    if t.ndim != 2 or t.shape[1] != spec.length:
        raise ValueError(f"tokens shape {t.shape} != [n, {spec.length}]")
    t = t.astype(np.int64, copy=False)
    bad = (t != PAD_TOKEN) & ((t < 0) | (t >= spec.vocab))
    if bad.any():
        raise ValueError(
            f"tokens outside [0, {spec.vocab}) ∪ {{PAD_TOKEN}} cannot be packed"
        )
    mapped = np.where(t == PAD_TOKEN, spec.vocab, t).astype(np.uint32)
    if spec.mode == "u8":
        return mapped.astype(np.uint8)
    if spec.mode == "u16":
        return mapped.astype(np.uint16)
    n, l = mapped.shape
    groups = -(-l // _GROUP)
    padded = np.zeros((n, groups * _GROUP), np.uint32)
    padded[:, :l] = mapped
    padded = padded.reshape(n, groups, _GROUP)
    # bit-plane transpose: word p of group g collects bit p of 32 tokens
    words = np.zeros((n, groups, spec.bits), np.uint32)
    shifts = np.arange(_GROUP, dtype=np.uint32)
    for p in range(spec.bits):
        plane = (padded >> np.uint32(p)) & np.uint32(1)  # [n, g, 32]
        words[:, :, p] = np.bitwise_or.reduce(plane << shifts, axis=-1)
    return words.reshape(n, groups * spec.bits).view(np.int32)


@functools.partial(jax.jit, static_argnames=("spec",))
def _pack_tokens_jnp(tokens, spec: PackSpec):
    t = tokens.astype(jnp.int32)
    n_bad = jnp.sum((t != PAD_TOKEN) & ((t < 0) | (t >= spec.vocab)))
    mapped = jnp.where(t == PAD_TOKEN, spec.vocab, t).astype(jnp.uint32)
    if spec.mode == "u8":
        return mapped.astype(jnp.uint8), n_bad
    if spec.mode == "u16":
        return mapped.astype(jnp.uint16), n_bad
    n, l = mapped.shape
    groups = -(-l // _GROUP)
    padded = jnp.pad(mapped, ((0, 0), (0, groups * _GROUP - l))).reshape(n, groups, _GROUP)
    shifts = jnp.arange(_GROUP, dtype=jnp.uint32)
    # bit t of word p is bit p of token t: the bits are disjoint, so a sum is an or
    words = jnp.stack(
        [
            jnp.sum(((padded >> jnp.uint32(p)) & jnp.uint32(1)) << shifts, axis=-1,
                    dtype=jnp.uint32)
            for p in range(spec.bits)
        ],
        axis=-1,
    )
    return jax.lax.bitcast_convert_type(words.reshape(n, groups * spec.bits), jnp.int32), n_bad


def pack_tokens_device(tokens: jax.Array, spec: PackSpec) -> jax.Array:
    """:func:`pack_tokens` where the tokens already live: on the device(s)
    holding ``tokens``, keeping their sharding (rows pack independently), with
    the same bits and the same refusal of values outside the vocabulary."""
    packed, n_bad = _pack_tokens_jnp(tokens, spec)
    if int(n_bad):
        raise ValueError(
            f"tokens outside [0, {spec.vocab}) ∪ {{PAD_TOKEN}} cannot be packed"
        )
    return packed


def unpack_tokens(packed: Any, spec: PackSpec, *, pad_to: int | None = None):
    """Decode packed tokens back to PAD-padded int32 ``[n, pad_to or L]``.

    Pure ``jnp`` and traceable — this is the mirrored decode that runs on
    the consumer: inside the Pallas kernel tile (right before the tf
    sub-tile loop) and in the host fold. ``pad_to`` > L appends PAD_TOKEN
    columns (the kernel's ``tile_d`` alignment). Exact: ``unpack_tokens(
    pack_tokens(x, spec), spec) == x`` bit-for-bit.
    """
    l = spec.length
    if pad_to is None:
        pad_to = l
    if pad_to < l:
        raise ValueError(f"pad_to {pad_to} < unpacked length {l}")
    if spec.mode in ("u8", "u16"):
        vals = packed.astype(jnp.int32)
    else:
        n = packed.shape[0]
        groups = -(-l // _GROUP) if l else 0
        words = packed.reshape(n, groups, spec.bits)
        # token t of group g: sum_p ((word[g, p] >> t) & 1) << p — arithmetic
        # shift + mask reads bit t exactly even from negative int32 words
        shifts = jnp.arange(_GROUP, dtype=jnp.int32)  # [32]
        vals = jnp.zeros((n, groups, _GROUP), jnp.int32)
        for p in range(spec.bits):  # static unroll: bits is spec metadata
            plane = (words[:, :, p : p + 1] >> shifts[None, None, :]) & 1
            vals = vals + (plane << p)
        vals = vals.reshape(n, groups * _GROUP)[:, :l]
    toks = jnp.where(vals == spec.vocab, jnp.int32(PAD_TOKEN), vals)
    if pad_to > l:
        toks = jnp.pad(toks, ((0, 0), (0, pad_to - l)), constant_values=PAD_TOKEN)
    return toks


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedCorpus:
    """A packed token matrix + doc lengths + the spec that decodes it.

    Drop-in replacement for the ``(tokens, lengths)`` corpus tuple on the
    lexical scan paths: a pytree whose leaves share the corpus leading dim
    (shard ``take``, segment slicing, ``fold_chunks``, sharding specs all
    work unchanged) and whose treedef carries the hashable spec (jit and
    the fold caches key on it for free).
    """

    tokens: Any  # packed [n, W], dtype per spec
    lengths: Any  # [n] int32
    spec: PackSpec

    def tree_flatten(self):
        return (self.tokens, self.lengths), self.spec

    @classmethod
    def tree_unflatten(cls, spec, leaves):
        return cls(leaves[0], leaves[1], spec)

    @property
    def n_docs(self) -> int:
        return self.tokens.shape[0]

    def unpack(self, *, pad_to: int | None = None):
        """Back to the plain ``(tokens, lengths)`` representation."""
        return unpack_tokens(self.tokens, self.spec, pad_to=pad_to), self.lengths


def pack_corpus(tokens: Any, lengths: Any, *, vocab: int, mode: str = "auto"):
    """Pack a corpus under a ``token_pack`` knob value.

    Returns a :class:`PackedCorpus`, or the plain ``(tokens, lengths)``
    tuple when the resolved mode is ``"none"`` (so callers can pass the
    result straight to the scan either way).
    """
    t = np.asarray(tokens)
    spec = make_spec(vocab, t.shape[1] if t.ndim == 2 else 0, mode)
    if spec is None:
        return tokens, lengths
    return PackedCorpus(pack_tokens(t, spec), np.asarray(lengths, np.int32), spec)


def tree_nbytes(tree: Any) -> int:
    """Total array bytes across a pytree's leaves (obs byte counters)."""
    return sum(
        leaf.nbytes for leaf in jax.tree.leaves(tree) if hasattr(leaf, "nbytes")
    )
