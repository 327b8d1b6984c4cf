"""The checkpoint writer's bit packing against a bit-by-bit reference."""

import json
import os

import numpy as np

from harness import snapshot


def bit(planes, row, col):
    return int(planes[row, col >> 5] >> np.uint32(col & 31)) & 1


def test_pack_rows_sets_exactly_the_bit_of_each_column():
    rng = np.random.default_rng(3)
    slots = rng.integers(0, 11, 5000)
    planes = snapshot.pack_rows(slots, 11)
    assert planes.shape == (11, snapshot.WORDS) and planes.dtype == np.uint32
    for col in list(range(70)) + [4999]:
        assert [bit(planes, r, col) for r in range(11)] == \
            [int(r == slots[col]) for r in range(11)]
    popcount = sum(bin(int(w)).count("1") for w in planes.ravel() if w)
    assert popcount == 5000  # nothing beyond the records, nothing twice


def test_pack_rows_of_nothing_is_all_zero():
    assert not snapshot.pack_rows(np.zeros(0, dtype=np.int64), 3).any()


def test_pack_bsi_holds_exists_and_every_magnitude_bit():
    values = np.array([0, 1, 2, 3, 1000, 1023], dtype=np.int64)
    planes = snapshot.pack_bsi(values, 10)
    assert planes.shape == (12, snapshot.WORDS)
    assert planes[0, 0] == 0b111111 and not planes[0, 1:].any()
    assert not planes[1].any()  # no negative value
    for col, v in enumerate(values):
        got = sum(bit(planes, 2 + k, col) << k for k in range(10))
        assert got == v


def test_schema_and_keys_are_written_where_the_server_looks(tmp_path):
    fields = [
        {"name": "k", "type": "mutex", "rows": 2, "ids": None,
         "keys": ["a", "b"]},
        {"name": "v", "type": "int", "min": 0, "max": 7}]
    snapshot.write_schema(str(tmp_path), "idx", fields)
    snapshot.write_shard(str(tmp_path), "idx", fields, 3, {
        "k": np.array([1, 0, 1]), "v": np.array([7, 0, 5])})
    doc = json.loads((tmp_path / "schema.json").read_text())
    opts = {f["name"]: f["options"] for f in doc["indexes"][0]["fields"]}
    assert opts["k"]["keys"] is True and opts["v"]["type"] == "int"
    root = tmp_path / "indexes" / "idx" / "fields"
    assert (root / "k" / "keys.jsonl").read_text().splitlines() == \
        ['["a", 1]', '["b", 2]']
    with np.load(root / "k" / "views" / "standard" / "frag.3.npz") as z:
        assert z["row_ids"].tolist() == [1, 2]
        assert z["planes"][:, 0].tolist() == [0b010, 0b101]
    with np.load(root / "v" / "bsi" / "frag.3.npz") as z:
        assert z["planes"].shape[0] == 2 + 3
    with np.load(root / "_exists" / "views" / "standard"
                 / "frag.3.npz") as z:
        assert z["planes"][0, 0] == 0b111 and z["row_ids"].tolist() == [0]
    assert os.path.isdir(tmp_path / "indexes" / "idx")
