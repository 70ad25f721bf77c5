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

On a real multi-host pod each host writes only its addressable shards and the
manifest records the global shape + shard map; in this single-process
container leaves are written whole.

jax is imported lazily: chaos/ops tooling checkpoints plain numpy pytrees in
environments without jax installed.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import msgpack
import numpy as np

try:  # optional: plain numpy pytrees work without jax
    import jax
except Exception:  # pragma: no cover - exercised in jax-free CI jobs
    jax = None

from repro.core.objectstore import Namespace, NoSuchKey
from repro.obs.tracer import trace_span

#: model-checkpoint MANIFEST schema tag (independent of the RunManifest's)
CKPT_SCHEMA = 2


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


def _as_leaf_array(buf: bytes, dtype_str: str, shape: List[int]) -> Any:
    if jax is not None:
        dt = np.dtype(jax.numpy.dtype(dtype_str))
        arr = np.frombuffer(buf, dtype=dt).reshape(shape)
        return jax.numpy.asarray(arr)
    return np.frombuffer(buf, dtype=np.dtype(dtype_str)).reshape(shape).copy()


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
    checkpoint prefix; returns the ``MANIFEST.ckpt`` key.

    The upload alone does **not** make the checkpoint recoverable — only a
    RunManifest entry naming the returned key does. ``cursor`` is recorded
    for the legacy two-file flow and for human inspection. ``tag`` suffixes
    the directory name (``{step:010d}-{tag}``) so distinct upload attempts
    at the same step never overwrite an object an earlier RunManifest entry
    already binds. ``stats`` (a ``TrainStats``), where given, counts the
    bytes and PUTs of the upload in ``checkpoint_bytes`` and
    ``checkpoint_puts``.

    Each leaf is a ``checkpoint.to_host`` span (the device-to-host copy and
    its bytes) then a ``checkpoint.put`` span; the MANIFEST's PUT is one
    more ``checkpoint.put``.
    """
    dirname = f"{step:010d}" + (f"-{tag}" if tag else "")
    leaves = _leaf_paths(state)
    index = []

    def put(key: str, data: bytes, **args) -> None:
        with trace_span("checkpoint.put", cat="checkpoint", **args):
            ns.store.put(key, data)
        if stats is not None:
            stats.checkpoint_bytes += len(data)
            stats.checkpoint_puts += 1

    for i, (path, leaf) in enumerate(leaves):
        with trace_span("checkpoint.to_host", cat="checkpoint", leaf=i):
            arr = np.asarray(leaf)
            data = arr.tobytes()
        key = ns.key("checkpoints", dirname, f"leaf-{i:05d}.npy")
        put(key, data, leaf=i)
        # str(dtype) round-trips extended dtypes (bfloat16 via ml_dtypes)
        index.append({"path": path, "shape": list(arr.shape),
                      "dtype": str(arr.dtype), "key": key})
    manifest = msgpack.packb({
        "schema": CKPT_SCHEMA,
        "step": step,
        "cursor": (None if cursor is None
                   else {"version": cursor[0], "step": cursor[1]}),
        "leaves": index,
    }, use_bin_type=True)
    mkey = ns.key("checkpoints", dirname, "MANIFEST.ckpt")
    put(mkey, manifest)  # manifest-last: atomic visibility
    return mkey


def load_model_state(ns: Namespace, model_key: str, template: Dict[str, Any]
                     ) -> Tuple[Dict[str, Any], dict]:
    """Read a model checkpoint by its ``MANIFEST.ckpt`` key into a pytree
    matching ``template``'s structure. Returns ``(state, manifest_doc)``."""
    raw = ns.store.get(model_key)
    doc = msgpack.unpackb(raw, raw=False)
    by_path = {e["path"]: e for e in doc["leaves"]}
    out_leaves = []
    for path, _leaf in _leaf_paths(template):
        e = by_path[path]
        buf = ns.store.get(e["key"])
        out_leaves.append(_as_leaf_array(buf, e["dtype"], e["shape"]))
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
