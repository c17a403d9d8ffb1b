"""Jitted public wrappers for the Pallas kernels.

Backend selection: by default kernels run ``interpret=True`` off-TPU (this
container) — the kernel body executes in Python on CPU for correctness —
and compile to Mosaic on a TPU backend. Override either way with the
``REPRO_KERNEL_BACKEND`` env var (``auto`` | ``interpret`` | ``compiled``)
or programmatically with :func:`set_kernel_backend`.

Block/tile geometry: every wrapper's block argument defaults to ``None`` =
"the active :class:`repro.tune.TuningConfig`'s value" — resolved *before*
the jit boundary, so the block size is an ordinary static argument of the
compiled program and two different tunings can never alias one trace. The
default config reproduces the historical hand-picked constants (q/k blocks
128, decode block 512, lexical block 512 / tile 16, dense block 1024)
bit-for-bit; block geometry only regroups value-deterministic merges, so
tuning it changes speed, never output bytes.
"""

from __future__ import annotations

import functools
import os

import jax

from repro.kernels.flash_attn import flash_attention_pallas
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.lexical_scan import lexical_scan_topk_pallas
from repro.kernels.score_topk import score_topk_pallas
from repro.tune import config as tune_config

_BACKENDS = ("auto", "interpret", "compiled")
_backend_override: str | None = None


def set_kernel_backend(mode: str | None) -> None:
    """Force the Pallas execution mode for all kernel wrappers.

    ``"interpret"`` runs kernel bodies in Python (portable, slow),
    ``"compiled"`` always lowers to the real backend (Mosaic on TPU),
    ``"auto"``/``None`` restores the default backend sniffing. Clears all
    jit caches (``jax.clear_caches``) so already-traced callers — including
    outer jitted closures like the serve sessions — retrace with the new
    mode on their next call.
    """
    global _backend_override
    if mode is not None and mode not in _BACKENDS:
        raise ValueError(f"unknown kernel backend {mode!r}; expected one of {_BACKENDS}")
    _backend_override = None if mode in (None, "auto") else mode
    jax.clear_caches()


def kernel_backend() -> str:
    """Resolved mode: explicit override > env var > backend sniffing."""
    mode = _backend_override or os.environ.get("REPRO_KERNEL_BACKEND", "auto")
    if mode not in _BACKENDS:
        raise ValueError(
            f"REPRO_KERNEL_BACKEND={mode!r} invalid; expected one of {_BACKENDS}"
        )
    if mode == "auto":
        return "compiled" if jax.default_backend() == "tpu" else "interpret"
    return mode


def _interpret_default() -> bool:
    return kernel_backend() == "interpret"


@functools.partial(jax.jit, static_argnames=("k", "block_d"))
def _score_topk_jit(q, d, *, k: int, block_d: int):
    return score_topk_pallas(q, d, k=k, block_d=block_d, interpret=_interpret_default())


def score_topk(q, d, *, k: int, block_d: int | None = None):
    """Fused streaming score+top-k (MIREX map+combine). -> (scores, ids).

    The combiner is the k-bounded bitonic fold (`score_topk.fold_block`).
    ``block_d=None`` takes the active tuning's ``dense_block_d`` (1024 when
    untuned — the historical default).
    """
    if block_d is None:
        block_d = tune_config.active().config.dense_block_d or 1024
    return _score_topk_jit(q, d, k=k, block_d=block_d)


@functools.partial(
    jax.jit, static_argnames=("modes", "k", "block_d", "tile_d", "pack_spec")
)
def _lexical_scan_topk_jit(
    q_tokens, weights, ab, d_tokens, d_len, *, modes, k: int,
    block_d: int, tile_d: int, pack_spec,
):
    return lexical_scan_topk_pallas(
        q_tokens, weights, ab, d_tokens, d_len,
        modes=modes, k=k, block_d=block_d, tile_d=tile_d,
        interpret=_interpret_default(), pack_spec=pack_spec,
    )


def lexical_scan_topk(
    q_tokens, weights, ab, d_tokens, d_len, *, modes, k: int,
    block_d: int | None = None, tile_d: int | None = None, pack_spec=None,
):
    """Fused multi-model lexical scan (shared on-chip tf + per-model scorer
    epilogues + resident top-k). -> ``(scores, ids) [n_models, n_q, k]``.

    ``modes`` is the static tuple of `scoring.EpilogueMode`; build all three
    arguments from a scorer grid with `scoring.lexical_epilogues`.
    ``block_d``/``tile_d`` default to the active tuning's ``lex_block_d`` /
    ``lex_tile_d`` (512 / 16 when untuned). ``pack_spec`` (a frozen
    `packing.PackSpec`, static like the block geometry) marks ``d_tokens``
    as packed and turns on the in-VMEM tile decode — bit-identical results,
    fewer bytes streamed.
    """
    if block_d is None or tile_d is None:
        cfg = tune_config.active().config
        if block_d is None:
            block_d = cfg.lex_block_d or 512
        if tile_d is None:
            tile_d = cfg.lex_tile_d
    return _lexical_scan_topk_jit(
        q_tokens, weights, ab, d_tokens, d_len,
        modes=modes, k=k, block_d=block_d, tile_d=tile_d, pack_spec=pack_spec,
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "cap", "block_q", "block_k")
)
def _flash_attention_jit(q, k, v, *, causal, window, cap, block_q, block_k):
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, cap=cap,
        block_q=block_q, block_k=block_k, interpret=_interpret_default(),
    )


def flash_attention(q, k, v, *, causal=True, window=None, cap=None,
                    block_q: int | None = None, block_k: int | None = None):
    """Blockwise attention (causal/window/softcap/GQA). q [B,S,H,hd].

    ``block_q``/``block_k`` default to the active tuning's
    ``flash_block_q``/``flash_block_k`` (128/128 when untuned).
    """
    if block_q is None or block_k is None:
        cfg = tune_config.active().config
        block_q = cfg.flash_block_q if block_q is None else block_q
        block_k = cfg.flash_block_k if block_k is None else block_k
    return _flash_attention_jit(
        q, k, v, causal=causal, window=window, cap=cap,
        block_q=block_q, block_k=block_k,
    )


@functools.partial(jax.jit, static_argnames=("window", "cap", "block_s"))
def _flash_decode_jit(q, k_cache, v_cache, t, *, window, cap, block_s):
    return flash_decode_pallas(
        q, k_cache, v_cache, t, window=window, cap=cap,
        block_s=block_s, interpret=_interpret_default(),
    )


def flash_decode(
    q, k_cache, v_cache, t, *, window=None, cap=None, block_s: int | None = None
):
    """Split-KV single-token decode. q [B,H,hd], caches [B,S,KV,hd].

    ``block_s=None`` takes the active tuning's ``decode_block_s`` (512
    when untuned).
    """
    if block_s is None:
        block_s = tune_config.active().config.decode_block_s
    return _flash_decode_jit(
        q, k_cache, v_cache, t, window=window, cap=cap, block_s=block_s
    )
