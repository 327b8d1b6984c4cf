"""Fragment persistence: one compressed npz per (field, view, shard).

Layout under the holder path (mirrors the reference's
``indexes/<idx>/backends/rbf/shard.NNNN`` per-shard DB files,
reference: dbshard.go:123):

    indexes/<index>/fields/<field>/views/<view>/frag.<shard>.npz
    indexes/<index>/fields/<field>/bsi/frag.<shard>.npz

Dense planes compress well (zlib of zero runs), and load is a single
mmap-friendly read + device_put — no B-tree walk on the query path.
"""

from __future__ import annotations

import glob
import os
import re
import time
from typing import TYPE_CHECKING

import numpy as np

from pilosa_tpu.core.fragment import BSIFragment, SetFragment
from pilosa_tpu.obs import metrics as M
from pilosa_tpu.obs.tracing import annotate
from pilosa_tpu.ops import bsi as bsiops

if TYPE_CHECKING:
    from pilosa_tpu.core.holder import Holder

_FRAG_RE = re.compile(r"frag\.(\d+)\.npz$")


def _views_dir(idx_path: str, field: str) -> str:
    return os.path.join(idx_path, "fields", field, "views")


def _bsi_dir(idx_path: str, field: str) -> str:
    return os.path.join(idx_path, "fields", field, "bsi")


def save_holder_data(holder: "Holder") -> None:
    """Persist the schema and every fragment whose file the disk does not
    already hold. Atomic per file via tmp+rename (the coarse analog of
    the reference's RBF checkpoint, rbf/db.go:149, which copies only the
    pages the WAL touched).

    ``holder.saved_versions`` (path -> (fragment, version)) says what the
    disk holds. A file is ``skipped`` when its entry is this very
    fragment (never a successor under the same name: a field dropped and
    made again is another object) at its present version (every write
    route bumps it, as the device stacks already require) and the file
    still exists; anything else is ``changed`` and written. A path enters
    the new map only with its file in place at that version: carried over
    when skipped, recorded after the rename when written. The map is
    replaced only by a save that ran to its end; one that died part-way
    leaves the old map, whose entries for the files it did rewrite name
    versions their fragments have left, so those are written again."""
    if not holder.path:
        raise ValueError("holder has no data dir")
    holder.save_schema()
    before, saved = holder.saved_versions, {}

    def save(path: str, frag, **arrays) -> None:
        was, version = before.get(path, (None, None))
        held = (frag, frag.version)
        if was is frag and version == frag.version and os.path.exists(path):
            state = "skipped"
            saved[path] = held
        else:
            state = "changed"
            if _atomic_savez(path, **arrays):
                saved[path] = held
        M.REGISTRY.count(M.METRIC_RECOVERY_CHECKPOINT_FRAGMENTS, state=state)

    for idx in holder.indexes.values():
        idx_path = holder._index_path(idx.name)
        for field in idx.fields.values():
            for view, frags in field.views.items():
                for shard, frag in frags.items():
                    n = len(frag.row_ids)
                    save(os.path.join(_views_dir(idx_path, field.name), view,
                                      f"frag.{shard}.npz"), frag,
                         planes=frag.planes[:n],
                         row_ids=np.asarray(frag.row_ids, dtype=np.uint64))
            for shard, bfrag in field.bsi.items():
                save(os.path.join(_bsi_dir(idx_path, field.name),
                                  f"frag.{shard}.npz"), bfrag,
                     planes=bfrag.planes)
        idx.dataframe.save()
    holder.saved_versions = saved


def load_holder_data(holder: "Holder") -> None:
    """Discover and load fragment files for all schema-known fields
    (reference: dbshard.go:241 LoadExistingDBs + view.openWithShardSet).
    What is loaded is what the disk holds, so it seeds
    ``holder.saved_versions`` (see ``save_holder_data``)."""
    if not holder.path:
        return
    saved = holder.saved_versions
    for idx in holder.indexes.values():
        idx_path = holder._index_path(idx.name)
        for field in idx.fields.values():
            vdir = _views_dir(idx_path, field.name)
            if os.path.isdir(vdir):
                for view in sorted(os.listdir(vdir)):
                    for path in glob.glob(os.path.join(vdir, view, "frag.*.npz")):
                        m = _FRAG_RE.search(path)
                        if not m:
                            continue
                        shard = int(m.group(1))
                        with np.load(path) as z:
                            planes, row_ids = z["planes"], z["row_ids"]
                        frag = field.fragment(shard, view, create=True)
                        for slot, row in enumerate(row_ids.tolist()):
                            frag.import_row_plane(int(row), planes[slot], clear=True)
                        saved[path] = (frag, frag.version)
            for path in glob.glob(os.path.join(_bsi_dir(idx_path, field.name),
                                               "frag.*.npz")):
                m = _FRAG_RE.search(path)
                if not m:
                    continue
                shard = int(m.group(1))
                with np.load(path) as z:
                    planes = z["planes"]
                bfrag = field.bsi_fragment(shard, create=True)
                bfrag.depth = planes.shape[0] - bsiops.OFFSET
                bfrag.planes = planes.copy()
                bfrag.version += 1
                saved[path] = (bfrag, bfrag.version)
        idx.dataframe.load()


def export_holder(holder: "Holder", root: str) -> None:
    """Write a complete, self-contained snapshot tree under ``root`` —
    schema + fragments + BSI + dataframe + translate journals — the
    payload of `backup` (reference: ctl/backup.go streaming schema,
    shard snapshots, translate partitions). Works for path-less holders
    too (translate stores are dumped from memory)."""
    import json as _json

    os.makedirs(root, exist_ok=True)
    schema = {
        "indexes": [
            {
                "name": idx.name,
                "options": idx.options.to_json(),
                "fields": [
                    {"name": f.name, "options": f.options.to_json()}
                    for f in idx.public_fields()
                ],
            }
            for idx in sorted(holder.indexes.values(), key=lambda i: i.name)
        ]
    }
    with open(os.path.join(root, "schema.json"), "w") as f:
        _json.dump(schema, f, indent=1)
    for idx in holder.indexes.values():
        idx_path = os.path.join(root, "indexes", idx.name)
        for field in idx.fields.values():
            for view, frags in field.views.items():
                for shard, frag in frags.items():
                    n = len(frag.row_ids)
                    _atomic_savez(
                        os.path.join(_views_dir(idx_path, field.name), view,
                                     f"frag.{shard}.npz"),
                        planes=frag.planes[:n],
                        row_ids=np.asarray(frag.row_ids, dtype=np.uint64),
                    )
            for shard, bfrag in field.bsi.items():
                _atomic_savez(
                    os.path.join(_bsi_dir(idx_path, field.name),
                                 f"frag.{shard}.npz"),
                    planes=bfrag.planes,
                )
            if field.translate is not None:
                _dump_translate(
                    field.translate.key_to_id,
                    os.path.join(idx_path, "fields", field.name, "keys.jsonl"))
        if idx.translate is not None:
            _dump_translate(idx.translate.key_to_id,
                            os.path.join(idx_path, "keys.jsonl"))
        df = idx.dataframe
        for shard, frame in df.frames.items():
            arrays = {}
            for name, col in frame.columns.items():
                arrays[f"c:{name}"] = col
                arrays[f"v:{name}"] = frame.valid[name]
            _atomic_savez(
                os.path.join(idx_path, "dataframe", f"shard.{shard}.npz"),
                **arrays)


def _dump_translate(key_to_id, path: str) -> None:
    import json as _json

    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for key, id_ in sorted(key_to_id.items(), key=lambda kv: kv[1]):
            f.write(_json.dumps([key, id_]) + "\n")


def _atomic_savez(path: str, **arrays) -> bool:
    """tmp + fsync + rename + dir-fsync: the snapshot survives power
    loss, not just process death (rename alone only orders metadata on
    some filesystems). Kill sites bracket the rename — the atomicity
    claim under test is exactly "crash on either side leaves a complete
    old or complete new file" (storage/recovery.py CrashPlan; the plan
    arrives thread-locally because array names own the kwargs). Returns
    whether the new file is in place: False when the crash plan is dead,
    and the 'process' therefore wrote nothing or did not rename."""
    from pilosa_tpu.storage.recovery import scoped_plan
    from pilosa_tpu.storage.wal import fsync_dir

    plan = scoped_plan()
    if plan is not None and plan.dead:
        return False
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    count = M.REGISTRY.count
    t0 = time.perf_counter()
    with open(tmp, "wb") as f:
        with annotate("checkpoint.serialize"):
            np.savez_compressed(f, **arrays)
        t1 = time.perf_counter()
        count(M.METRIC_RECOVERY_CHECKPOINT_PHASE_SECONDS, t1 - t0,
              phase="serialize")
        count(M.METRIC_RECOVERY_CHECKPOINT_BYTES,
              sum(np.asarray(a).nbytes for a in arrays.values()), kind="raw")
        count(M.METRIC_RECOVERY_CHECKPOINT_BYTES, f.tell(), kind="stored")
        with annotate("checkpoint.fsync"):
            f.flush()
            os.fsync(f.fileno())
    try:
        if plan is not None and not plan.fire("savez.pre_replace"):
            return False
        with annotate("checkpoint.fsync"):
            os.replace(tmp, path)
            if plan is None or plan.fire("savez.post_replace"):
                fsync_dir(os.path.dirname(path))
        return True
    finally:
        # file fsync + rename + directory fsync, as they accrue
        count(M.METRIC_RECOVERY_CHECKPOINT_PHASE_SECONDS,
              time.perf_counter() - t1, phase="fsync")


def export_shard_arrays(idx, shard: int) -> dict:
    """One shard's planes as named arrays (the shard-snapshot payload;
    reference: api.go:1265 IndexShardSnapshot streams the RBF pages —
    here the dense planes). Keys: set|field|view + rows|field|view for
    bitmap fragments, bsi|field for BSI stacks."""
    out = {}
    for fname, field in idx.fields.items():
        for view, frags in field.views.items():
            frag = frags.get(shard)
            if frag is not None and frag.row_ids:
                n = len(frag.row_ids)
                out[f"set|{fname}|{view}"] = frag.planes[:n]
                out[f"rows|{fname}|{view}"] = np.asarray(
                    frag.row_ids, dtype=np.int64)
        bfrag = field.bsi.get(shard)
        if bfrag is not None:
            out[f"bsi|{fname}"] = bfrag.planes
    return out


def install_shard_arrays(idx, shard: int, arrays: dict) -> None:
    """Inverse of export_shard_arrays: plane-level install (restore /
    DAX snapshot resume)."""
    from pilosa_tpu.core.fragment import _grow_rows

    for key, arr in arrays.items():
        parts = key.split("|")
        if parts[0] == "set":
            _, fname, view = parts
            frag = idx.field(fname).fragment(shard, view, create=True)
            rows = arrays[f"rows|{fname}|{view}"]
            frag.row_ids = [int(r) for r in rows]
            frag.row_index = {int(r): i for i, r in enumerate(rows)}
            frag.planes = _grow_rows(
                np.ascontiguousarray(arr, dtype=np.uint32), len(rows))
            frag.version += 1
            frag.deltas.reset(frag.version)
        elif parts[0] == "bsi":
            _, fname = parts
            bfrag = idx.field(fname).bsi_fragment(shard, create=True)
            bfrag.planes = np.ascontiguousarray(arr, dtype=np.uint32)
            bfrag.depth = bfrag.planes.shape[0] - 2
            bfrag.version += 1
            bfrag.deltas.reset(bfrag.version)
