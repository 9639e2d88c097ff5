"""CEAZ-compressed, fault-tolerant, mesh-elastic checkpoints.

This is the paper's MPI_File_write scenario made first-class: checkpoint
tensors are compressed with the full adaptive CEAZ pipeline (offline
codewords -> chi-policy updates, error-bounded mode) before hitting
storage, cutting write volume by the measured CR (see
benchmarks/parallel_io.py).

Leaves are streamed through the async compression-I/O engine
(`repro.io.engine`): compression of leaf i+1 overlaps the ordered
commit of leaf i into ONE indexed `leaves.ceazs` stream per step.

Fault-tolerance contract:
  * ATOMIC: a checkpoint becomes visible only via os.replace() of a
    completed step directory and of the LATEST pointer file — a crash
    mid-write never corrupts the restore path.
  * VERIFIED: the stream footer carries per-leaf crc32s (plus a footer
    checksum); restore refuses silently corrupted files and falls back
    to the previous step.
  * ELASTIC: tensors are stored in LOGICAL (unsharded) space with the tree
    structure in the manifest, so a checkpoint written on a (2,16,16) mesh
    restores onto (16,16), (4,4), or a single CPU device — node-failure
    recovery with a different device count is a restore, not a migration.
  * ASYNC: `save_checkpoint(..., background=True)` snapshots to host then
    writes off the training thread (straggler/jitter isolation).

Float leaves >= `min_compress` elements go through CEAZ (mode='rel',
eb=1e-5 by default for params — measured loss-impact in EXPERIMENTS.md);
small/int leaves are stored raw. `mode='raw'` disables lossy compression
entirely (bit-exact restore, still atomic+verified).
"""
from __future__ import annotations

import concurrent.futures as futures
import dataclasses
import hashlib
import io
import json
import os
import pickle
import shutil
import tempfile
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from ..core import CEAZ, CEAZConfig
from ..io import engine as E
from ..runtime.sharding import ShardingPlan, leaf_sharding

LATEST = "LATEST"
LEAVES_STREAM = "leaves.ceazs"
_EXEC: Optional[futures.ThreadPoolExecutor] = None
_PENDING = []


@dataclasses.dataclass
class CheckpointConfig:
    mode: str = "ceaz"             # 'ceaz' | 'raw'
    eb: float = 5e-4               # value-range-relative bound for params
    predictor: str = "auto"        # weights are noise-like => value-direct
    min_compress: int = 4096       # leaves smaller than this stored raw
    chunk_bytes: int = 1 << 22
    # device-resident fused pipeline for float32 Lorenzo leaves (smooth
    # fields such as embedding tables / activations snapshots); the
    # value-direct leaves the auto predictor selects stay on the staged
    # host path (float64 semantics).
    use_fused: bool = True
    # async engine: compress leaf i+1 while committing leaf i; False
    # runs the same stages inline (byte-identical stream)
    overlap: bool = True
    writers: int = 2
    # restore side: leaf records decode in groups of `restore_group` as
    # one batched fused device pass each, prefetch of the next group
    # overlapping the decode of the current one
    restore_group: int = 8


def _flatten(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = jax.tree_util.keystr(path, simple=True, separator="/")
        flat[key] = np.asarray(leaf)
    return flat


def _treedef_of(tree):
    return jax.tree_util.tree_structure(tree)


def _compressor(cfg: CheckpointConfig) -> CEAZ:
    return CEAZ(CEAZConfig(mode="rel", eb=cfg.eb,
                           chunk_bytes=cfg.chunk_bytes,
                           predictor=cfg.predictor,
                           use_fused=cfg.use_fused))


def _leaf_lossy(arr: np.ndarray, cfg: CheckpointConfig) -> bool:
    return (cfg.mode == "ceaz"
            and arr.dtype in (np.float32, np.float64)
            and arr.size >= cfg.min_compress
            and bool(np.all(np.isfinite(arr))))


def _decode_leaf(payload: bytes, meta: Dict, comp: CEAZ) -> np.ndarray:
    """Legacy format-1 (per-leaf files, sha256 meta) decoder."""
    if hashlib.sha256(payload).hexdigest() != meta["sha256"]:
        raise IOError("checkpoint payload hash mismatch (corruption)")
    if meta["codec"] == "ceaz":
        c = pickle.loads(payload)
        out = comp.decompress(c)
        return out.astype(_np_dtype(meta["dtype"])).reshape(meta["shape"])
    if meta["codec"] == "bytes":
        return np.frombuffer(payload, dtype=_np_dtype(meta["dtype"])) \
            .reshape(meta["shape"]).copy()
    arr = np.load(io.BytesIO(payload), allow_pickle=False)
    if arr.dtype.kind == "V":        # npy stored an ml_dtypes array as void
        arr = arr.view(_np_dtype(meta["dtype"]))
    return arr


_np_dtype = E._np_dtype            # ml_dtypes-aware dtype resolver


def read_manifest(step_dir: str) -> Dict:
    """The step directory's manifest. An unreadable or malformed one is
    checkpoint corruption and raises OSError, the one error class the
    restore paths treat as "try an older step" (StreamCorruptionError is
    an OSError too)."""
    path = os.path.join(step_dir, "manifest.json")
    try:
        with open(path) as f:
            manifest = json.load(f)
    except ValueError as e:                 # not JSON
        raise OSError(f"bad checkpoint manifest {path}: {e}") from e
    if not (isinstance(manifest, dict)
            and {"step", "leaves"} <= set(manifest)):
        raise OSError(f"bad checkpoint manifest {path}: no step/leaves")
    return manifest


def save_checkpoint(directory: str, state: Any, step: int,
                    extra: Optional[Dict] = None,
                    cfg: Optional[CheckpointConfig] = None,
                    background: bool = False) -> str:
    """Write state atomically as <directory>/step_<step>/ and update LATEST.

    Returns the (future) checkpoint path. With background=True the device->
    host snapshot happens NOW, the file writes happen on a worker thread
    (wait_for_pending() to join, e.g. before process exit)."""
    cfg = cfg or CheckpointConfig()
    flat = _flatten(state)                      # host snapshot (sync)
    treedef = jax.tree_util.tree_structure(state)

    def _write():
        comp = _compressor(cfg)
        os.makedirs(directory, exist_ok=True)
        tmp = tempfile.mkdtemp(dir=directory, prefix=f".tmp_step_{step}_")
        manifest = {"step": step, "extra": extra or {},
                    "treedef": str(treedef), "format": 2,
                    "file": LEAVES_STREAM,
                    "mode": cfg.mode, "leaves": {}}

        def encode(keys, items):
            # lossy float leaves ride the fused facade; everything else
            # passes through as raw arrays for the npy/bytes codecs
            return [comp.compress(arr.astype(np.float32))
                    if _leaf_lossy(arr, cfg) else arr for arr in items]

        try:
            eng = E.AsyncCompressWriteEngine(
                os.path.join(tmp, LEAVES_STREAM), encode,
                writers=cfg.writers, sync=not cfg.overlap,
                meta={"kind": "checkpoint", "step": step},
                block_size=comp.cfg.block_size)
            with eng:
                for key, arr in sorted(flat.items()):
                    eng.submit(key, arr, meta={
                        "shape": list(arr.shape), "dtype": str(arr.dtype),
                        "raw_nbytes": int(arr.nbytes),
                        **({"eb_rel": cfg.eb}
                           if _leaf_lossy(arr, cfg) else {})})
            for rec in eng.stats.records:
                manifest["leaves"][rec["key"]] = {
                    k: v for k, v in rec.items() if k != "key"}
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f, indent=1)
                f.flush()
                os.fsync(f.fileno())
            final = os.path.join(directory, f"step_{step:08d}")
            if os.path.exists(final):
                shutil.rmtree(final)
            os.replace(tmp, final)
            # atomic LATEST pointer
            ptr_tmp = os.path.join(directory, ".LATEST.tmp")
            with open(ptr_tmp, "w") as f:
                f.write(f"step_{step:08d}")
                f.flush()
                os.fsync(f.fileno())
            os.replace(ptr_tmp, os.path.join(directory, LATEST))
            return final
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise

    if background:
        global _EXEC
        if _EXEC is None:
            _EXEC = futures.ThreadPoolExecutor(max_workers=1)
        fut = _EXEC.submit(_write)
        _PENDING.append(fut)
        return os.path.join(directory, f"step_{step:08d}")
    return _write()


def wait_for_pending():
    for f in list(_PENDING):
        f.result()
    _PENDING.clear()


def available_steps(directory: str):
    if not os.path.isdir(directory):
        return []
    steps = []
    for d in os.listdir(directory):
        if d.startswith("step_") and os.path.isfile(
                os.path.join(directory, d, "manifest.json")):
            steps.append(int(d.split("_")[1]))
    return sorted(steps)


def restore_checkpoint(directory: str, step: Optional[int] = None,
                       plan: Optional[ShardingPlan] = None,
                       cfg: Optional[CheckpointConfig] = None,
                       template: Any = None,
                       leaf_transform=None
                       ) -> Optional[Tuple[Any, Dict]]:
    """Restore (state, meta). Falls back to earlier steps on corruption
    (an OSError: a failed stream check, a bad manifest, a read error);
    any other error, such as a device or compile failure, propagates.

    Format-2 leaf streams restore through the engine-fed decode
    pipeline: the prefetch thread reads+deserializes leaf records while
    groups of `cfg.restore_group` leaves decode as one batched fused
    device pass each — no per-leaf host-numpy decode bounce. With
    `plan`, every leaf is device_put with the sharding derived from
    PARAM_RULES as soon as it decodes — the restore mesh may differ
    arbitrarily from the save mesh (elastic restart).

    `leaf_transform(key, arr) -> arr` runs on each decoded host leaf
    BEFORE placement, so a serving-dtype cast happens while only that
    one leaf exists in both precisions — never the whole tree (peak
    restore memory stays at the target-dtype footprint)."""
    cfg = cfg or CheckpointConfig()
    steps = available_steps(directory)
    if step is not None:
        steps = [s for s in steps if s == step]
    if not steps:
        return None
    comp = _compressor(cfg)
    sharded = plan is not None and plan.mesh is not None

    def place(key: str, arr):
        """Per-leaf transform, then placement on the restore mesh."""
        if leaf_transform is not None:
            arr = leaf_transform(key, arr)
        if not sharded:
            return arr
        return jax.device_put(arr, leaf_sharding(key, np.shape(arr), plan))

    for s in reversed(steps):
        d = os.path.join(directory, f"step_{s:08d}")
        try:
            manifest = read_manifest(d)
            flat = {}
            if manifest.get("format", 1) >= 2:
                stream = os.path.join(d, manifest.get("file",
                                                      LEAVES_STREAM))
                with E.AsyncDecodeReadEngine(
                        stream, comp, group=cfg.restore_group) as eng:
                    for rec, obj in eng:
                        if rec.get("codec") == "ceaz":
                            obj = obj.astype(_np_dtype(rec["dtype"])) \
                                .reshape(rec["shape"])
                        flat[rec["key"]] = place(rec["key"], obj)
            else:                                  # legacy per-leaf files
                for key, meta in manifest["leaves"].items():
                    with open(os.path.join(d, meta["file"]), "rb") as f:
                        flat[key] = place(key, _decode_leaf(f.read(),
                                                            meta, comp))
            state = _unflatten_like(flat, template)
            return state, {"step": manifest["step"],
                           **manifest.get("extra", {})}
        except OSError as e:         # corrupted or unreadable -> try older
            print(f"checkpoint {d} unusable ({e}); trying previous")
            continue
    return None


def _unflatten_like(flat: Dict[str, np.ndarray], template: Any):
    """Rebuild the nested dict/list structure from 'a/b/0/c' paths."""
    root: Dict = {}
    for key, arr in flat.items():
        parts = key.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr

    def fix(node):
        if isinstance(node, dict):
            keys = list(node.keys())
            if keys and all(k.lstrip("-").isdigit() for k in keys):
                return [fix(node[k]) for k in sorted(keys, key=int)]
            return {k: fix(v) for k, v in node.items()}
        return node

    return fix(root)
