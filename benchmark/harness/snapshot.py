"""Write a data directory the server recovers from, straight from numpy.

Every run makes its table anew from ``--seed``, so loading is set-up, and
the JSON import endpoints (about 1 M field values a second) would spend
minutes on it. This module writes the product's checkpoint layout
instead (``pilosa_tpu/storage/store.py``: ``schema.json``, one
``frag.<shard>.npz`` per field, view and shard holding ``planes`` and
``row_ids``, BSI stacks as ``[exists, sign, magnitude...]`` planes, row
keys in ``keys.jsonl``) and the server then opens it through its own
``Holder.recover``. The layout is the one coupling of the benchmark to a
file format of the program; PERF.md lists a bulk-load endpoint as the way
to remove it.
"""

import json
import os

import numpy as np

SHARD_WIDTH = 1 << 20
WORDS = SHARD_WIDTH // 32
_BSI_OFFSET = 2  # planes 0/1 are exists/sign


def pack_rows(slots, n_rows):
    """uint32[n_rows, WORDS]: bit ``c`` of row ``slots[c]`` set, for the
    columns ``c`` of one shard (LSB-first within a word)."""
    n = slots.size
    order = np.argsort(slots, kind="stable")
    key = slots[order].astype(np.int64) * WORDS + (order >> 5)
    bit = np.uint32(1) << (order & 31).astype(np.uint32)
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]]) if n else []
    planes = np.zeros(n_rows * WORDS, dtype=np.uint32)
    if n:
        planes[key[first]] = np.bitwise_or.reduceat(bit, first)
    return planes.reshape(n_rows, WORDS)


def _bits(mask):
    out = np.zeros(WORDS, dtype=np.uint32)
    packed = np.packbits(mask, bitorder="little")
    out.view(np.uint8)[:packed.size] = packed
    return out


def pack_bsi(values, depth):
    """uint32[2 + depth, WORDS] for non-negative ``values`` of the first
    ``values.size`` columns of one shard."""
    planes = np.zeros((_BSI_OFFSET + depth, WORDS), dtype=np.uint32)
    planes[0] = _bits(np.ones(values.size, dtype=bool))
    for k in range(depth):
        planes[_BSI_OFFSET + k] = _bits((values >> k) & 1 == 1)
    return planes


def row_ids(field):
    """Wire row id of every slot: given ids, or 1.. for keyed rows (the
    server's translate stores allocate from 1)."""
    if field["keys"] is not None:
        return list(range(1, field["rows"] + 1))
    return field["ids"]


def write_schema(data_dir, index, fields):
    doc = {"indexes": [{
        "name": index,
        "options": {"keys": False, "track_existence": True},
        "fields": [{"name": f["name"], "options": {
            "type": f["type"], "keys": f.get("keys") is not None,
            "min": f.get("min"), "max": f.get("max")}} for f in fields],
    }]}
    os.makedirs(data_dir, exist_ok=True)
    with open(os.path.join(data_dir, "schema.json"), "w") as fh:
        json.dump(doc, fh)
    for f in fields:
        if f.get("keys") is not None:
            fdir = os.path.join(data_dir, "indexes", index, "fields",
                                f["name"])
            os.makedirs(fdir, exist_ok=True)
            with open(os.path.join(fdir, "keys.jsonl"), "w") as fh:
                for i, key in enumerate(f["keys"]):
                    fh.write(json.dumps([key, i + 1]) + "\n")


def write_shard(data_dir, index, fields, shard, columns):
    """The fragments of one shard from its columns (slot or value per
    record, records filling the shard from column 0)."""
    root = os.path.join(data_dir, "indexes", index, "fields")

    def save(field, kind, **arrays):
        d = os.path.join(root, field, kind)
        os.makedirs(d, exist_ok=True)
        np.savez(os.path.join(d, f"frag.{shard}.npz"), **arrays)

    count = None
    for f in fields:
        col = columns[f["name"]]
        count = col.size
        if f["type"] == "int":
            if int(col.min()) < 0:
                raise ValueError(f"{f['name']}: negative values")
            save(f["name"], "bsi",
                 planes=pack_bsi(col, int(f["max"]).bit_length()))
        else:
            save(f["name"], os.path.join("views", "standard"),
                 planes=pack_rows(col, f["rows"]),
                 row_ids=np.asarray(row_ids(f), dtype=np.uint64))
    save("_exists", os.path.join("views", "standard"),
         planes=_bits(np.ones(count, dtype=bool))[None, :],
         row_ids=np.zeros(1, dtype=np.uint64))
