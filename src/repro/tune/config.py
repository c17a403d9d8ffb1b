"""The one frozen config every performance knob in this repro lives in.

Before this module, every knob was a hand-picked constant scattered across
layers: the lexical kernel's ``block_d``/``tile_d``, flash attention's
``block_q``/``block_k``, the decode kernel's ``block_s``, the fold's
``chunk_size``, the pipelined executor's prefetch ``depth`` and worker
count, the scheduler's retry backoff, the serve layer's microbatch
triggers. :class:`TuningConfig` centralizes them with **defaults that
reproduce today's hand-picked values bit-for-bit** — a default-constructed
config changes nothing, anywhere, which is the property the whole
autotuning contract rests on:

    **tuning changes speed, never bytes.**

Every knob here is execution geometry: block/tile sizes only regroup the
value-deterministic top-k merges, the tf reduction accumulates in int32,
prefetch/worker/writer knobs reorder work that commutes. Run files produced
under *any* legal ``TuningConfig`` are byte-identical to the default-config
oracle (property-tested in ``tests/test_tune.py``, CI-enforced on the
smoke grid).

Threading model: code paths accept an explicit ``tuning=`` argument and
fall back to the process-wide active config (:func:`active` /
:func:`set_active` / the :func:`use` context manager). The active config is
a module global, not thread-local, so worker threads of a sharded job see
the config their driver installed. Knobs that shape *compiled programs*
(the kernel block sizes) are part of the jit-cache keys in
`cluster.mapreduce` via :meth:`TuningConfig.fold_key` — two configs that
compile different programs can never alias one cache entry.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import threading
from typing import Any, Iterator
import contextlib

# Bump when knobs are added/removed/re-meaning-ed: persisted winner-cache
# entries recorded under another version are stale and fall back to defaults.
SPACE_VERSION = 3  # v3: + token_pack (packed corpus segments, core.packing)

# legal token_pack values (mirrors packing.PACK_MODES; kept literal here so
# config stays importable without jax)
_TOKEN_PACK_MODES = ("none", "auto", "8", "16", "bitpack")


@dataclasses.dataclass(frozen=True)
class TuningConfig:
    """Every performance knob, one frozen record. Defaults == today's
    hand-picked values, so ``TuningConfig()`` is the identity config.

    ``None`` on the geometry knobs means "follow the caller": ``chunk_size``
    defers to the experiment/job's declared chunking, ``lex_block_d`` /
    ``dense_block_d`` follow ``chunk_size`` on the scan paths (today's
    behavior of passing ``block_d=chunk_size`` into the kernels) and the
    kernels' native defaults (512 / 1024) on direct calls, ``max_workers``
    defers to one-worker-per-device. ``serve_max_bucket=None`` means an
    uncapped bucket ladder (its default is a *cap*, 128 — the measured
    serve sweet spot; capping only regroups dispatches, so results stay
    byte-identical and the identity contract is on bytes, not grouping).
    """

    # -- scan fold / pipelined executor (cluster.job / core.pipeline) -------
    chunk_size: int | None = None  # rows per fold chunk; None = caller's
    prefetch_depth: int = 2  # staged segments ahead of the fold
    max_workers: int | None = None  # shard pool cap; None = per device
    cross_shard_prefetch: bool = True  # stage next shard's first segment
    writer_reuse: bool = False  # share the async ckpt writer per worker
    keep_checkpoints: int = 2  # committed segments kept on disk
    # -- scheduler retry pacing (cluster.scheduler) -------------------------
    backoff_base: float = 0.1
    backoff_cap: float = 5.0
    # -- fused lexical-scan kernel (kernels.lexical_scan) -------------------
    lex_block_d: int | None = None  # doc tile; None = chunk_size / 512
    lex_tile_d: int = 16  # L_d sub-tile of the tf reduction
    # -- dense score+top-k kernel (kernels.score_topk) ----------------------
    dense_block_d: int | None = None  # doc tile; None = chunk_size / 1024
    # -- flash kernels (kernels.flash_attn / flash_decode) ------------------
    flash_block_q: int = 128
    flash_block_k: int = 128
    decode_block_s: int = 512
    # -- serve microbatching (serve.microbatch / serve.service) -------------
    serve_max_batch: int = 64
    serve_max_delay_s: float = 5e-3
    serve_min_bucket: int = 8
    # bucket-ladder cap: blocks never pad past this, and oversize takes are
    # split into <= cap dispatches (the @256 amortization-cliff fix — past
    # the MXU/cache sweet spot per-query cost *rises*, so two sweet-spot
    # scans beat one giant one). None = uncapped (the pre-cap ladder).
    serve_max_bucket: int | None = 128
    # -- packed corpus segments (core.packing) ------------------------------
    # Token storage width for corpora the runner/serve layer prepares:
    # "none" keeps int32 (the identity default), "auto" picks the narrowest
    # width the vocab fits (u8/u16/bitpack), "8"/"16"/"bitpack" force one
    # (degrading to auto's choice if the vocab doesn't fit — knobs degrade,
    # never fail). Packed segments decode exactly on the consumer, so this
    # knob changes bytes moved, never bytes written. Not part of fold_key:
    # a packed corpus is a different pytree treedef, which jit and the
    # mesh/fold caches already key on.
    token_pack: str = "none"

    def __post_init__(self):
        for name in (
            "chunk_size", "lex_block_d", "dense_block_d", "max_workers",
            "serve_max_bucket",
        ):
            v = getattr(self, name)
            if v is not None and (not isinstance(v, int) or v < 1):
                raise ValueError(f"{name} must be a positive int or None, got {v!r}")
        for name in (
            "prefetch_depth", "keep_checkpoints", "lex_tile_d",
            "flash_block_q", "flash_block_k", "decode_block_s",
            "serve_max_batch", "serve_min_bucket",
        ):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"{name} must be a positive int, got {v!r}")
        for name in ("backoff_base", "backoff_cap", "serve_max_delay_s"):
            v = getattr(self, name)
            if not isinstance(v, (int, float)) or v < 0:
                raise ValueError(f"{name} must be a non-negative number, got {v!r}")
        if self.token_pack not in _TOKEN_PACK_MODES:
            raise ValueError(
                f"token_pack must be one of {_TOKEN_PACK_MODES}, "
                f"got {self.token_pack!r}"
            )
        if (
            self.serve_max_bucket is not None
            and self.serve_max_bucket < self.serve_min_bucket
        ):
            raise ValueError(
                f"serve_max_bucket {self.serve_max_bucket} below "
                f"serve_min_bucket {self.serve_min_bucket}"
            )

    # -- derivation ---------------------------------------------------------

    def replace(self, **kw: Any) -> "TuningConfig":
        return dataclasses.replace(self, **kw)

    def describe(self) -> dict:
        """JSON-able full knob table (report / cache payloads)."""
        return dataclasses.asdict(self)

    def overrides(self) -> dict:
        """Only the knobs that differ from the defaults — the readable form
        for reports ('{}' literally means 'the hand-picked configuration')."""
        base = DEFAULT.describe()
        return {k: v for k, v in self.describe().items() if v != base[k]}

    @classmethod
    def from_dict(cls, d: dict, *, strict: bool = True) -> "TuningConfig":
        """Build from a (possibly partial) knob dict. ``strict`` rejects
        unknown knob names — the stale-cache guard: an entry recorded under
        a different knob space must not half-apply."""
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - fields
        if unknown and strict:
            raise ValueError(f"unknown tuning knobs {sorted(unknown)}")
        return cls(**{k: v for k, v in d.items() if k in fields})

    def config_hash(self) -> str:
        """Short content hash of (knob space version, full knob table) —
        stamped into report.json and BENCH provenance so perf numbers are
        attributable to the exact configuration that produced them."""
        payload = json.dumps(
            {"space_version": SPACE_VERSION, "config": self.describe()},
            sort_keys=True,
        )
        return hashlib.sha256(payload.encode()).hexdigest()[:12]

    # -- resolution helpers (the scan-path geometry rules) ------------------

    def resolve_chunk_size(self, declared: int) -> int:
        """Effective fold chunk size given the job's declared one."""
        return self.chunk_size if self.chunk_size is not None else declared

    def lex_block(self, chunk_size: int, n_rows: int | None = None) -> int:
        """Lexical-kernel doc tile for a scan over ``chunk_size`` chunks.

        ``None`` follows the chunk, halved while it exceeds 512 rows (the
        largest tile whose tf block and sort fit the kernel's scoped VMEM on
        a v5e); an explicit block that doesn't divide the shard gracefully
        falls back to that default — the scan must never fail on a knob,
        only ignore it (byte-identical either way: block size only regroups
        the combiner fold).
        """
        return _block(self.lex_block_d, chunk_size, n_rows, cap=512)

    def dense_block(self, chunk_size: int, n_rows: int | None = None) -> int:
        """Dense-kernel doc tile; same rules as :meth:`lex_block`, capped at
        1024 rows."""
        return _block(self.dense_block_d, chunk_size, n_rows, cap=1024)

    def fold_key(self, use_kernel: bool) -> tuple:
        """The knobs that shape the *compiled* fold program — the tuning
        component of `cluster.segment_fold`'s (and `search_mesh`'s) cache
        key. Host folds are shaped by chunk_size alone (already in the key);
        kernel folds additionally bake the block/tile geometry into the
        traced Pallas program, so those knobs must key the cache or two
        configs would silently share one program."""
        if not use_kernel:
            return ()
        return (self.lex_block_d, self.lex_tile_d, self.dense_block_d)


def _block(knob: int | None, chunk_size: int, n_rows: int | None, *, cap: int) -> int:
    """Kernel doc tile: the knob where it divides the shard, else the chunk
    halved (while even) down to ``cap`` rows."""
    default = chunk_size
    while default > cap and default % 2 == 0:
        default //= 2
    block = knob if knob is not None else default
    if n_rows is not None and n_rows % block:
        block = default
    return block


DEFAULT = TuningConfig()


@dataclasses.dataclass(frozen=True)
class ActiveTuning:
    """The installed config plus where it came from — provenance for
    report.json / BENCH_*.json stamping."""

    config: TuningConfig = DEFAULT
    source: str = "default"  # default | explicit | file | cache | search
    cache_hit: bool = False

    def provenance(self) -> dict:
        return {
            "config_hash": self.config.config_hash(),
            "source": self.source,
            "cache_hit": self.cache_hit,
        }


_LOCK = threading.Lock()
_active = ActiveTuning()


def active() -> ActiveTuning:
    """The process-wide active tuning (never None; defaults when unset)."""
    return _active


def set_active(
    config: TuningConfig | None,
    *,
    source: str = "explicit",
    cache_hit: bool = False,
) -> ActiveTuning:
    """Install ``config`` as the process-wide active tuning; returns the
    *previous* record so callers can restore it. ``None`` restores defaults."""
    global _active
    with _LOCK:
        prev = _active
        if config is None:
            _active = ActiveTuning()
        else:
            _active = ActiveTuning(config=config, source=source, cache_hit=cache_hit)
        return prev


def _restore(record: ActiveTuning) -> None:
    global _active
    with _LOCK:
        _active = record


@contextlib.contextmanager
def use(
    config: TuningConfig | None,
    *,
    source: str = "explicit",
    cache_hit: bool = False,
) -> Iterator[ActiveTuning]:
    """Scoped :func:`set_active` — the autotune harness measures every
    candidate under ``with use(candidate): ...`` and leaks nothing."""
    prev = set_active(config, source=source, cache_hit=cache_hit)
    try:
        yield active()
    finally:
        _restore(prev)


def resolve(tuning: TuningConfig | None) -> TuningConfig:
    """Explicit argument wins; otherwise the active config. The standard
    first line of every ``tuning=``-threaded code path."""
    return tuning if tuning is not None else _active.config


def provenance() -> dict:
    """The active config's provenance block (benchmarks stamp this)."""
    return _active.provenance()


def save(config: TuningConfig, path: str) -> str:
    """Write a config as JSON (the ``--tuning-config`` file format: a flat
    knob dict; missing knobs mean 'default')."""
    with open(path, "w") as f:
        json.dump(config.describe(), f, indent=2)
        f.write("\n")
    return path


def load(path: str) -> TuningConfig:
    """Read a ``--tuning-config`` JSON file (flat knob dict, strict)."""
    with open(path) as f:
        return TuningConfig.from_dict(json.load(f), strict=True)
