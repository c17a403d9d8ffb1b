"""Sharded, atomic, elastic checkpointing.

Fault-tolerance contract (DESIGN §5):
  * **atomic** — a checkpoint directory is written under ``.tmp-`` and
    renamed into place; a crash mid-write can never corrupt the latest good
    step (Hadoop's rename-commit, kept on purpose).
  * **sharded** — each leaf is saved as one ``.npy``; at multi-host scale
    each host would save only its addressable shards (the single-host
    container saves everything, same layout).
  * **elastic** — ``restore(..., shardings=)`` device_puts every leaf under
    the *current* mesh's NamedSharding, so a job restarted on a different
    topology (16×16 ↔ 2×16×16, or a degraded pod) resumes from the same
    bytes — elastic scaling without conversion jobs.

Leaf paths are flattened with ``jax.tree_util.keystr`` into a manifest, so
structure changes are detected instead of silently mis-zipped.
"""

from __future__ import annotations

import json
import os
import queue as queue_mod
import shutil
import threading
import time

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from repro import obs

# numpy can't serialize ml_dtypes (bfloat16 etc.) natively; store them as
# same-width unsigned ints and record the true dtype in the manifest.
_VIEW_AS = {"bfloat16": np.uint16, "float8_e4m3fn": np.uint8, "float8_e5m2": np.uint8}


# the AsyncCheckpointer whose writer thread is running the current task
_WRITER = threading.local()


def _writer_backlog() -> int:
    """Tasks waiting behind the one running, on a writer thread (else 0)."""
    writer = getattr(_WRITER, "checkpointer", None)
    return 0 if writer is None else writer._queue.qsize()


def _flatten(tree):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in leaves], treedef


def save(ckpt_dir: str, step: int, tree, *, on_commit=None, shard: int = 0) -> str:
    """Write checkpoint for ``step``; returns the final directory.

    ``on_commit(step, tmp_dir)``, if given, runs after the full write but
    *before* the rename-commit — an error raised there aborts the commit and
    leaves only the ``.tmp-`` dir behind (exactly the disk state a real I/O
    failure at that instant would leave). This is the checkpoint-writer
    fault-injection point used by ``cluster.faults``; a later retry of the
    same step removes the stale tmp dir and commits cleanly.

    ``shard`` only labels the spans: ``ckpt.fetch`` (the state's leaves to
    the host: for a scan job's segment the first wait on its fold) and
    ``ckpt.write`` (the ``.npy`` files and the manifest), both inside
    ``ckpt.save``.
    """
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = os.path.join(ckpt_dir, f".tmp-step_{step:08d}")
    tr = obs.tracer()
    t_save = time.monotonic()
    with tr.span("ckpt.save", "ckpt", shard=shard, step=step):
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp, exist_ok=True)
        named, _ = _flatten(tree)
        with tr.span("ckpt.fetch", "ckpt", shard=shard, step=step) as fetch:
            if tr.enabled:
                fetch.set(queued=_writer_backlog())
            arrays = jax.device_get([leaf for _, leaf in named])
        with tr.span("ckpt.write", "ckpt", shard=shard, step=step):
            manifest = []
            for i, ((key, _), arr) in enumerate(zip(named, arrays)):
                arr = np.asarray(arr)
                true_dtype = str(arr.dtype)
                if true_dtype in _VIEW_AS:
                    arr = arr.view(_VIEW_AS[true_dtype])
                fname = f"leaf_{i:05d}.npy"
                np.save(os.path.join(tmp, fname), arr)
                manifest.append(
                    {"key": key, "file": fname, "shape": list(arr.shape), "dtype": true_dtype}
                )
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump({"step": step, "leaves": manifest}, f)
        if on_commit is not None:
            on_commit(step, tmp)
        if os.path.exists(final):
            shutil.rmtree(final)
        with tr.span("ckpt.rename", "ckpt", step=step):
            os.replace(tmp, final)  # atomic commit
        obs.metrics().histogram("ckpt.save_s").observe(time.monotonic() - t_save)
    return final


def replace_dir(src: str, dst: str) -> None:
    """Promote checkpoint dir ``src`` over ``dst`` (speculative-win commit).

    Not a single atomic step when ``dst`` already exists (the rmtree+rename
    pair has a window with no ``dst``), but ``src`` holds a complete,
    committed lineage throughout — a crash in the window loses no data, and
    the scan-job resume path treats a missing shard dir as a fresh start.
    """
    if os.path.exists(dst):
        shutil.rmtree(dst)
    os.replace(src, dst)


def all_steps(ckpt_dir: str) -> list[int]:
    """Committed checkpoint steps (ascending); uncommitted .tmp dirs excluded."""
    if not os.path.isdir(ckpt_dir):
        return []
    return sorted(
        int(d.split("_")[1])
        for d in os.listdir(ckpt_dir)
        if d.startswith("step_") and os.path.exists(os.path.join(ckpt_dir, d, "manifest.json"))
    )


def latest_step(ckpt_dir: str) -> int | None:
    steps = all_steps(ckpt_dir)
    return steps[-1] if steps else None


def prune(ckpt_dir: str, keep: int) -> list[int]:
    """Delete all but the newest ``keep`` checkpoints; returns removed steps.

    Bounds the disk footprint of segment-checkpointed scan jobs (one commit
    per corpus segment) without ever touching the newest good step.
    """
    if keep < 1:
        raise ValueError(f"keep must be >= 1, got {keep}")
    steps = all_steps(ckpt_dir)
    drop = steps[:-keep] if len(steps) > keep else []
    for s in drop:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:08d}"))
    return drop


class AsyncCheckpointer:
    """Ordered background committer: checkpoint I/O off the critical path.

    A pipelined scan job hands each post-segment commit sequence —
    ``save(step)`` → progress manifest → ``prune`` — to one writer thread
    and keeps folding the next segment; the device arrays it enqueues are
    immutable, so the writer's later ``device_get`` reads exactly the
    committed value. The contract that makes this safe to swap for inline
    commits:

    * **same order** — tasks run strictly in submission order on a single
      thread, so the on-disk write sequence is identical to the synchronous
      path's; a hard kill at any instant leaves a disk state the
      synchronous path could also have left (atomicity of each ``save`` is
      unchanged — the rename-commit happens on the writer thread).
    * **fail-stop** — the first task error poisons the queue: later tasks
      are skipped (a progress manifest must never claim a commit whose
      ``save`` failed) and the error re-raises on the next
      :meth:`drain`/:meth:`submit`/:meth:`close`.
    * **drain barrier** — :meth:`drain` blocks until everything submitted
      so far is durably on disk; jobs drain before reporting a step done
      (e.g. ahead of an injected lost-ack kill) and before returning, so
      resume semantics match the synchronous path exactly.
    """

    def __init__(self):
        self._queue: queue_mod.Queue = queue_mod.Queue()
        self._error: BaseException | None = None
        self._thread = threading.Thread(
            target=self._run, name="ckpt-writer", daemon=True
        )
        self._closed = False
        self._thread.start()

    def _run(self):
        _WRITER.checkpointer = self
        while True:
            item = self._queue.get()
            try:
                if item is None:
                    return
                if self._error is None:  # poison: skip everything after a failure
                    fn, args, kwargs = item
                    fn(*args, **kwargs)
            except BaseException as e:  # noqa: BLE001 — re-raised on drain
                self._error = e
            finally:
                self._queue.task_done()
                obs.metrics().gauge("ckpt.writer_queue_depth").set(
                    self._queue.qsize()
                )

    def _check(self):
        # the error stays set: a failed commit poisons the writer for good,
        # so no later task (e.g. a progress manifest claiming the failed
        # step) can ever run, even after the error has been reported once
        if self._error is not None:
            raise self._error

    def submit(self, fn, *args, **kwargs) -> None:
        """Enqueue ``fn(*args, **kwargs)`` after everything already queued."""
        if self._closed:
            raise RuntimeError("AsyncCheckpointer is closed")
        self._check()
        self._queue.put((fn, args, kwargs))
        obs.metrics().gauge("ckpt.writer_queue_depth").set(self._queue.qsize())

    def drain(self) -> None:
        """Block until all submitted work is on disk; re-raise writer errors."""
        self._queue.join()
        self._check()

    def _shutdown(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._queue.join()
        self._queue.put(None)
        self._thread.join()

    def close(self) -> None:
        """Drain, stop the writer thread, and re-raise any pending error."""
        was_closed = self._closed
        self._shutdown()
        if not was_closed:
            self._check()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        # don't mask an in-flight exception (e.g. an injected kill) with a
        # writer error; the writer error still surfaces for clean exits
        if exc_type is not None:
            self._shutdown()
            return False
        self.close()
        return False


def restore(ckpt_dir: str, step: int, tree_like, *, shardings=None):
    """Load ``step`` into the structure of ``tree_like``.

    ``shardings``: optional matching pytree of NamedShardings (the *current*
    mesh) — this is the elastic-rescale path.
    """
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    named, treedef = _flatten(tree_like)
    by_key = {m["key"]: m for m in manifest["leaves"]}
    if set(by_key) != {k for k, _ in named}:
        missing = {k for k, _ in named} ^ set(by_key)
        raise ValueError(f"checkpoint structure mismatch; differing keys: {sorted(missing)[:5]}")
    shard_named = None
    if shardings is not None:
        shard_named, _ = _flatten(shardings)
        shard_named = dict(shard_named)
    leaves = []
    for key, like in named:
        meta = by_key[key]
        arr = np.load(os.path.join(d, meta["file"]))
        if meta["dtype"] in _VIEW_AS:
            arr = arr.view(getattr(ml_dtypes, meta["dtype"]))
        if shard_named is not None:
            leaves.append(jax.device_put(arr, shard_named[key]))
        else:
            leaves.append(jax.device_put(arr))
    return jax.tree_util.tree_unflatten(treedef, leaves)
