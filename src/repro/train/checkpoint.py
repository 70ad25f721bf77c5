"""Distributed model-state checkpointing to the object store.

This module owns the *model* half of the recovery story: uploading a pytree
of arrays as immutable leaf objects plus a ``MANIFEST.ckpt`` index
(manifest-last ordering gives atomic visibility, exactly like the data
plane's TGBs), and reading it back into a template pytree.

The *binding* half — coupling a model checkpoint to the data-plane cursor so
a crash between the two saves cannot break exactly-once — lives in the
RunManifest (``repro.run``): ``TrainSession.checkpoint`` calls
:func:`upload_model_state` and then commits a RunManifest entry naming the
upload. A model upload whose RunManifest commit never landed is invisible to
recovery and is detected by ``batchweave fsck`` as a safe orphan.

``save_checkpoint`` / ``restore_checkpoint`` keep the pre-RunManifest
behaviour (free-floating step dirs + per-rank watermarks) for callers that
manage their own cursor persistence; new code should go through
``TrainSession``.

Layout under ``{ns}/checkpoints/{step:010d}/``:
    MANIFEST.ckpt             msgpack: schema, step, cursor, leaf index
    leaf-{i:05d}.npy          raw little-endian array bytes per pytree leaf

Each leaf is one object PUT and one GET, largest leaf first. From
``POOL_MIN_BYTES`` of state up these requests run ``POOL_WORKERS`` at a time
on the checkpoint's own pool, while the calling thread copies the next leaf
to the host or makes the last leaf fetched a device array; a smaller state
moves one request at a time on the calling thread.

On a real multi-host pod each host writes only its addressable shards and the
manifest records the global shape + shard map; in this single-process
container leaves are written whole.

jax is imported lazily: chaos/ops tooling checkpoints plain numpy pytrees in
environments without jax installed.
"""
from __future__ import annotations

import threading
from concurrent.futures import CancelledError, Future, as_completed, wait
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import msgpack
import numpy as np

try:  # optional: plain numpy pytrees work without jax
    import jax
except Exception:  # pragma: no cover - exercised in jax-free CI jobs
    jax = None

from repro.core.objectstore import IOPool, Namespace, NoSuchKey
from repro.obs.tracer import TRACER, trace_span

#: model-checkpoint MANIFEST schema tag (independent of the RunManifest's)
CKPT_SCHEMA = 2

#: a state of at least this many bytes moves through the checkpoint's pool;
#: a smaller one moves one request at a time on the calling thread
POOL_MIN_BYTES = 64 << 20
#: concurrent object requests of one save or restore above that size
POOL_WORKERS = 8
#: host bytes of leaf copies a save holds for PUTs not yet finished, beyond
#: which the calling thread waits before it hands over another; four of the
#: benchmark's 470 MB leaves fit, enough to fill the store's link
HELD_BYTES = 2 << 30


# ---------------------------------------------------------------------------
# Pytree flattening (jax when present, deterministic pure-python fallback)
# ---------------------------------------------------------------------------

def _flatten_py(tree, prefix: str = "") -> List[Tuple[str, Any]]:
    """Deterministic nested dict/list/tuple flattener (sorted dict keys),
    path-compatible with the jax flattener for those container types."""
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out.extend(_flatten_py(tree[k], f"{prefix}{k}/"))
        return out
    if isinstance(tree, (list, tuple)):
        out = []
        for i, leaf in enumerate(tree):
            out.extend(_flatten_py(leaf, f"{prefix}{i}/"))
        return out
    return [(prefix.rstrip("/"), tree)]


def _leaf_paths(tree) -> List[Tuple[str, Any]]:
    if jax is None:
        return _flatten_py(tree)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out.append((key, leaf))
    return out


def _np_dtype(dtype_str: str) -> np.dtype:
    if jax is not None:
        return np.dtype(jax.numpy.dtype(dtype_str))
    return np.dtype(dtype_str)


def _nbytes(leaf) -> int:
    """A leaf's size from its shape and dtype, without copying it."""
    nbytes = getattr(leaf, "nbytes", None)
    return int(nbytes) if nbytes is not None else np.asarray(leaf).nbytes


def _as_leaf_array(buf: bytes, dtype_str: str, shape: List[int]) -> Any:
    """A leaf from its bytes."""
    arr = np.frombuffer(buf, dtype=_np_dtype(dtype_str)).reshape(shape)
    if jax is not None:
        return jax.numpy.asarray(arr)
    return arr.copy()


def _rebuild(template, leaves: List[Any]):
    if jax is not None:
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(template), leaves)

    it = iter(leaves)

    def walk(node):
        if isinstance(node, dict):
            return {k: walk(node[k]) for k in sorted(node)}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(x) for x in node)
        return next(it)

    return walk(template)


# ---------------------------------------------------------------------------
# Object requests of one save or restore
# ---------------------------------------------------------------------------

_pool: Optional[IOPool] = None
_pool_lock = threading.Lock()


def _checkpoint_pool() -> IOPool:
    """The checkpoint's own pool of ``POOL_WORKERS`` threads (``bw-ckpt``),
    apart from the readers' shared ``IOPool.default()`` so a save's PUTs
    never queue their GETs; started by the first save or restore that needs
    it and kept for the process."""
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = IOPool(POOL_WORKERS, name="bw-ckpt")
        return _pool


def _largest_first(sizes: List[int]) -> List[int]:
    """Leaf indices, largest leaf first (ties in tree order)."""
    return sorted(range(len(sizes)), key=lambda i: -sizes[i])


class _Requests:
    """The object requests of one save or restore of a state of
    ``total_bytes``.

    From ``POOL_MIN_BYTES`` up, requests run on :func:`_checkpoint_pool`
    and their spans name ``parent`` (the span open on the calling thread) as
    theirs. Below it, each request runs on the calling thread as it is
    submitted, and a failure raises from :meth:`submit`. Results come back
    on the calling thread through :meth:`finished`. A request holds the
    ``nbytes`` of host memory it was handed until it ends; :meth:`submit`
    waits (a ``checkpoint.drain`` span) while that would take more than
    ``HELD_BYTES``. After the first failure no request starts; leaving the
    ``with`` block waits for those in flight.
    """

    def __init__(self, total_bytes: int):
        self.parent = TRACER.current()
        self.peak = 0              # most requests in flight at once
        self._pool = (_checkpoint_pool() if total_bytes >= POOL_MIN_BYTES
                      else None)
        self._cond = threading.Condition()
        self._held = 0             # bytes of requests handed over, not ended
        self._inflight = 0
        self._error: Optional[BaseException] = None
        self._pending: List[Future] = []

    def _full(self, nbytes: int) -> bool:
        return (self._error is None and self._held > 0
                and self._held + nbytes > HELD_BYTES)

    def submit(self, nbytes: int, fn: Callable, *args, **kw) -> None:
        """Run ``fn(*args, **kw)``, which holds ``nbytes`` of host memory
        until it ends."""
        with self._cond:
            if self._full(nbytes):
                with trace_span("checkpoint.drain", cat="checkpoint"):
                    while self._full(nbytes):
                        self._cond.wait()
            if self._error is not None:
                raise self._error
            self._held += nbytes
        if self._pool is None:
            fut: Future = Future()
            fut.set_result(self._run(nbytes, fn, args, kw))
        else:
            fut = self._pool.submit(self._run, nbytes, fn, args, kw)
        self._pending.append(fut)

    def _run(self, nbytes: int, fn: Callable, args: tuple, kw: dict):
        with self._cond:
            started = self._error is None
            if started:
                self._inflight += 1
                self.peak = max(self.peak, self._inflight)
        try:
            if not started:
                raise CancelledError("an earlier request failed")
            return fn(*args, **kw)
        except BaseException as e:
            with self._cond:
                if self._error is None:
                    self._error = e
            raise
        finally:
            with self._cond:
                self._inflight -= started
                self._held -= nbytes
                self._cond.notify_all()

    def finished(self, wait: bool = False) -> Iterator[Any]:
        """The results of requests that have completed, as they complete;
        with ``wait``, of every request submitted. Raises the first
        failure."""
        futs = list(self._pending)
        for fut in (as_completed(futs) if wait
                    else [f for f in futs if f.done()]):
            self._pending.remove(fut)
            if fut.exception() is not None:
                raise self._error   # set by the request that failed first
            yield fut.result()

    def __enter__(self) -> "_Requests":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None and self._pending:
            with self._cond:
                if self._error is None:
                    self._error = exc
                self._cond.notify_all()
            for fut in self._pending:
                fut.cancel()
            wait(self._pending)   # the requests in flight
        return False


# ---------------------------------------------------------------------------
# Model-state upload / load (the RunManifest-era primitives)
# ---------------------------------------------------------------------------

def checkpoint_dir_step(dirname: str) -> Optional[int]:
    """The step prefix of a checkpoint directory name (``0000000008`` or
    ``0000000008-r1``), or None for foreign directory names."""
    try:
        return int(dirname.split("-", 1)[0])
    except ValueError:
        return None


def upload_model_state(ns: Namespace, step: int, state: Dict[str, Any],
                       cursor: Optional[Tuple[int, int]] = None,
                       tag: Optional[str] = None, stats=None) -> str:
    """Upload ``state`` (arbitrary pytree of arrays) under the step's
    checkpoint prefix; returns the ``MANIFEST.ckpt`` key once every leaf and
    the MANIFEST are stored.

    The upload alone does **not** make the checkpoint recoverable — only a
    RunManifest entry naming the returned key does. ``cursor`` is recorded
    for the legacy two-file flow and for human inspection. ``tag`` suffixes
    the directory name (``{step:010d}-{tag}``) so distinct upload attempts
    at the same step never overwrite an object an earlier RunManifest entry
    already binds. ``stats`` (a ``TrainStats``), where given, counts the
    bytes and PUTs of the upload in ``checkpoint_bytes`` and
    ``checkpoint_puts`` and sets ``checkpoint_puts_inflight_peak``.

    The calling thread copies each leaf to the host (a ``checkpoint.to_host``
    span) and hands its PUT (a ``checkpoint.put`` span) to
    :class:`_Requests`, largest leaf first, which runs it behind the next
    copies from ``POOL_MIN_BYTES`` of state up; then it waits for the PUTs
    (``checkpoint.drain``, as when ``HELD_BYTES`` are held before a copy is
    handed over) and PUTs the MANIFEST (one more ``checkpoint.put``). If a
    leaf's PUT fails, no MANIFEST is written.
    """
    dirname = f"{step:010d}" + (f"-{tag}" if tag else "")
    leaves = _leaf_paths(state)
    sizes = [_nbytes(leaf) for _, leaf in leaves]
    index: List[Optional[dict]] = [None] * len(leaves)
    stored = [0, 0]   # bytes, PUTs: counted on this thread as PUTs complete

    def put(key: str, data: bytes, parent: Optional[int], **args) -> int:
        with trace_span("checkpoint.put", cat="checkpoint", parent=parent,
                        **args):
            ns.store.put(key, data)
        return len(data)

    def count(nbytes: int) -> None:
        stored[0] += nbytes
        stored[1] += 1

    with _Requests(sum(sizes)) as req:
        for i in _largest_first(sizes):
            with trace_span("checkpoint.to_host", cat="checkpoint", leaf=i):
                arr = np.asarray(leaves[i][1])
                data = arr.tobytes()
            key = ns.key("checkpoints", dirname, f"leaf-{i:05d}.npy")
            # str(dtype) round-trips extended dtypes (bfloat16 via ml_dtypes)
            index[i] = {"path": leaves[i][0], "shape": list(arr.shape),
                        "dtype": str(arr.dtype), "key": key}
            req.submit(len(data), put, key, data, req.parent, leaf=i)
            del arr, data
            for nbytes in req.finished():
                count(nbytes)
        with trace_span("checkpoint.drain", cat="checkpoint"):
            for nbytes in req.finished(wait=True):
                count(nbytes)
    manifest = msgpack.packb({
        "schema": CKPT_SCHEMA,
        "step": step,
        "cursor": (None if cursor is None
                   else {"version": cursor[0], "step": cursor[1]}),
        "leaves": index,
    }, use_bin_type=True)
    mkey = ns.key("checkpoints", dirname, "MANIFEST.ckpt")
    count(put(mkey, manifest, None))  # manifest-last: atomic visibility
    if stats is not None:
        stats.checkpoint_bytes += stored[0]
        stats.checkpoint_puts += stored[1]
        stats.checkpoint_puts_inflight_peak = req.peak
    return mkey


def load_model_state(ns: Namespace, model_key: str, template: Dict[str, Any]
                     ) -> Tuple[Dict[str, Any], dict]:
    """Read a model checkpoint by its ``MANIFEST.ckpt`` key into a pytree
    matching ``template``'s structure. Returns ``(state, manifest_doc)``.

    After the MANIFEST, every leaf is fetched by one GET (a
    ``checkpoint.get`` span) through :class:`_Requests`, largest first, and
    concurrently from ``POOL_MIN_BYTES`` of state up. The calling thread
    makes each leaf a device array as soon as its bytes are in. A failed GET
    raises; no state is returned.
    """
    with trace_span("checkpoint.get", cat="checkpoint"):
        raw = ns.store.get(model_key)
    doc = msgpack.unpackb(raw, raw=False)
    by_path = {e["path"]: e for e in doc["leaves"]}
    entries = [by_path[path] for path, _leaf in _leaf_paths(template)]
    sizes = [int(np.prod(e["shape"], dtype=np.int64))
             * _np_dtype(e["dtype"]).itemsize for e in entries]
    out_leaves: List[Any] = [None] * len(entries)

    def get(i: int, parent: Optional[int]) -> Tuple[int, bytes]:
        with trace_span("checkpoint.get", cat="checkpoint", parent=parent,
                        leaf=i):
            return i, ns.store.get(entries[i]["key"])

    with _Requests(sum(sizes)) as req:
        for i in _largest_first(sizes):
            req.submit(0, get, i, req.parent)
        for i, data in req.finished(wait=True):
            e = entries[i]
            out_leaves[i] = _as_leaf_array(data, e["dtype"], e["shape"])
    return _rebuild(template, out_leaves), doc


# ---------------------------------------------------------------------------
# Legacy two-file flow (pre-RunManifest; kept for direct-namespace callers)
# ---------------------------------------------------------------------------

def save_checkpoint(ns: Namespace, step: int, state: Dict[str, Any],
                    cursor: Tuple[int, int],
                    consumer_ranks: Optional[List[int]] = None) -> str:
    """Persist ``state`` + data cursor the pre-RunManifest way: the cursor
    rides inside ``MANIFEST.ckpt`` and per-rank watermarks are written
    immediately. Not atomic against the data plane — a crash between this
    and a separately-persisted cursor breaks exactly-once, which is exactly
    what ``TrainSession.checkpoint`` (RunManifest) exists to fix."""
    from repro.core.lifecycle import Watermark, write_watermark

    mkey = upload_model_state(ns, step, state, cursor=cursor)
    wm = Watermark(version=cursor[0], step=cursor[1])
    for rank in (consumer_ranks or [0]):
        write_watermark(ns, rank, wm)
    return mkey


def list_checkpoints(ns: Namespace) -> List[int]:
    steps = set()
    for key in ns.store.list(ns.key("checkpoints")):
        if key.endswith("MANIFEST.ckpt"):
            step = checkpoint_dir_step(key.split("/")[-2])
            if step is not None:
                steps.add(step)
    return sorted(steps)


def _manifest_key_for_step(ns: Namespace, step: int) -> str:
    """The MANIFEST key of a step's most recent upload attempt (tagged
    retry dirs supersede the untagged original; tags count upward)."""
    best: Tuple[int, Optional[str]] = (-1, None)
    for key in ns.store.list(ns.key("checkpoints")):
        if not key.endswith("MANIFEST.ckpt"):
            continue
        dirname = key.split("/")[-2]
        if checkpoint_dir_step(dirname) != step:
            continue
        parts = dirname.split("-", 1)
        attempt = 0
        if len(parts) == 2:
            try:
                attempt = int(parts[1].lstrip("r")) or 0
            except ValueError:
                continue
        if attempt > best[0]:
            best = (attempt, key)
    if best[1] is None:
        raise NoSuchKey(f"no checkpoint at step {step}")
    return best[1]


def restore_checkpoint(ns: Namespace, template: Dict[str, Any],
                       step: Optional[int] = None
                       ) -> Tuple[Dict[str, Any], Tuple[int, int], int]:
    """Restore the pytree (matching ``template``'s structure) + cursor.

    Returns (state, (cursor_version, cursor_step), ckpt_step). Note this is
    the *legacy* recovery path — it picks a step's newest upload attempt;
    only ``TrainSession.restore_model`` knows which upload a RunManifest
    entry actually bound.
    """
    steps = list_checkpoints(ns)
    if not steps:
        raise NoSuchKey("no checkpoints")
    if step is None:
        step = steps[-1]
    state, doc = load_model_state(ns, _manifest_key_for_step(ns, step),
                                  template)
    cur = doc.get("cursor") or {"version": -1, "step": 0}
    return state, (cur["version"], cur["step"]), doc["step"]
