"""Data-model tests: fragments, fields, index, holder, persistence.

Mirrors the reference's fragment/field/index internal tests
(fragment_internal_test.go, field_test.go, index_test.go) at the
behaviors that matter for query semantics.
"""

import datetime as dt

import numpy as np
import pytest

from pilosa_tpu.core import (
    EXISTENCE_FIELD,
    Field,
    FieldOptions,
    FieldType,
    Holder,
    Index,
    IndexOptions,
)
from pilosa_tpu.core.fragment import BSIFragment, SetFragment
from pilosa_tpu.ops.bitmap import plane_to_bits
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import load_holder_data, save_holder_data

W = 1 << 9  # small planes for fragment-level tests


def bits(plane):
    return set(int(x) for x in plane_to_bits(np.asarray(plane)))


class TestSetFragment:
    def test_set_clear(self):
        f = SetFragment(0, W)
        assert f.set_bit(3, 100)
        assert not f.set_bit(3, 100)  # already set
        assert f.set_bit(3, 101)
        assert f.set_bit(9, 100)
        assert bits(f.row_plane(3)) == {100, 101}
        assert bits(f.row_plane(9)) == {100}
        assert f.clear_bit(3, 100)
        assert not f.clear_bit(3, 100)
        assert bits(f.row_plane(3)) == {101}
        assert bits(f.row_plane(777)) == set()

    def test_set_many(self):
        f = SetFragment(0, W)
        n = f.set_many([1, 1, 2, 2, 2], [10, 11, 10, 11, 11])
        assert n == 4  # duplicate (2,11) counted once
        assert bits(f.row_plane(1)) == {10, 11}
        assert bits(f.row_plane(2)) == {10, 11}

    def test_clear_column_mutex(self):
        f = SetFragment(0, W)
        for r in range(5):
            f.set_bit(r, 42)
        f.set_bit(2, 43)
        assert f.clear_column(42, except_row=2)
        assert bits(f.row_plane(2)) == {42, 43}
        for r in (0, 1, 3, 4):
            assert bits(f.row_plane(r)) == set()
        assert not f.clear_column(42, except_row=2)  # nothing left to clear

    def test_device_cache_invalidation(self):
        f = SetFragment(0, W)
        f.set_bit(0, 1)
        d1 = f.device_planes()
        assert f.device_planes() is d1  # cached
        f.set_bit(0, 2)
        d2 = f.device_planes()
        assert d2 is not d1
        assert bits(np.asarray(d2)[0]) == {1, 2}

    def test_capacity_growth_pow2(self):
        f = SetFragment(0, W)
        for r in range(20):
            f.set_bit(r * 7, 1)
        assert f.planes.shape[0] == 32  # next pow2 >= 20
        assert f.existing_rows() == [r * 7 for r in range(20)]


class TestBSIFragment:
    def test_set_get_clear(self):
        f = BSIFragment(0, W)
        f.set_value(10, 1234)
        f.set_value(11, -77)
        f.set_value(12, 0)
        assert f.value(10) == 1234
        assert f.value(11) == -77
        assert f.value(12) == 0
        assert f.value(13) is None
        f.set_value(10, -5)  # overwrite shrinks magnitude, must fully clear
        assert f.value(10) == -5
        assert f.clear_value(11)
        assert f.value(11) is None
        assert not f.clear_value(11)

    def test_depth_growth(self):
        f = BSIFragment(0, W)
        f.set_value(1, 3)
        assert f.depth == 2
        f.set_value(2, 1 << 40)
        assert f.depth == 41
        assert f.value(1) == 3
        assert f.value(2) == 1 << 40

    def test_set_values_last_wins(self):
        f = BSIFragment(0, W)
        f.set_values([5, 6, 5], [100, 200, 300])
        assert f.value(5) == 300
        assert f.value(6) == 200


class TestField:
    def test_mutex_semantics(self):
        fld = Field("i", "m", FieldOptions(type=FieldType.MUTEX))
        fld.set_bit(1, 100)
        fld.set_bit(2, 100)  # must clear row 1 for col 100
        frag = fld.fragment(0)
        assert bits(frag.row_plane(1)) == set()
        assert bits(frag.row_plane(2)) == {100}

    def test_bool_semantics(self):
        fld = Field("i", "b", FieldOptions(type=FieldType.BOOL))
        fld.set_bool(7, True)
        fld.set_bool(7, False)
        frag = fld.fragment(0)
        assert bits(frag.row_plane(1)) == set()
        assert bits(frag.row_plane(0)) == {7}

    def test_time_views(self):
        fld = Field("i", "t", FieldOptions(type=FieldType.TIME, time_quantum="YMD"))
        ts = dt.datetime(2010, 1, 2, 3)
        fld.set_bit(1, 5, timestamp=ts)
        assert set(fld.view_names()) == {
            "standard", "standard_2010", "standard_201001", "standard_20100102",
        }
        for v in fld.view_names():
            assert bits(fld.fragment(0, v).row_plane(1)) == {5}

    def test_shard_routing(self):
        fld = Field("i", "s", FieldOptions())
        col = 3 * SHARD_WIDTH + 17
        fld.set_bit(9, col)
        assert fld.shards() == {3}
        assert bits(fld.fragment(3).row_plane(9)) == {17}

    def test_decimal_scale(self):
        fld = Field("i", "d", FieldOptions(type=FieldType.DECIMAL, scale=2))
        fld.set_value(1, 12.34)
        assert fld.value(1) == pytest.approx(12.34)

    def test_timestamp_roundtrip(self):
        fld = Field("i", "ts", FieldOptions(type=FieldType.TIMESTAMP))
        fld.set_value(1, "2020-05-06T07:08:09Z")
        v = fld.value(1)
        assert v == dt.datetime(2020, 5, 6, 7, 8, 9,
                                tzinfo=dt.timezone.utc).timestamp()

    def test_int_min_max_enforced(self):
        fld = Field("i", "n", FieldOptions(type=FieldType.INT, min=0, max=100))
        fld.set_value(1, 50)
        with pytest.raises(ValueError):
            fld.set_value(1, 101)
        with pytest.raises(ValueError):
            fld.set_value(1, -1)


class TestIndexHolder:
    def test_existence_tracking(self):
        idx = Index("i")
        assert EXISTENCE_FIELD in idx.fields
        idx.add_exists(10)
        idx.add_exists(SHARD_WIDTH + 5)
        assert bits(idx.existence_plane(0)) == {10}
        assert bits(idx.existence_plane(1)) == {5}
        assert idx.existence_plane(7) is None

    def test_field_crud(self):
        idx = Index("i")
        idx.create_field("f")
        with pytest.raises(ValueError):
            idx.create_field("f")
        with pytest.raises(ValueError):
            idx.create_field("BadCase")
        assert [f.name for f in idx.public_fields()] == ["f"]
        idx.delete_field("f")
        assert idx.public_fields() == []
        with pytest.raises(ValueError):
            idx.delete_field(EXISTENCE_FIELD)

    def test_invalid_index_name(self):
        for bad in ("", "9lives", "UPPER"):
            with pytest.raises(ValueError):
                Index(bad)

    def test_holder_schema_persistence(self, tmp_path):
        h = Holder(str(tmp_path))
        idx = h.create_index("trips", IndexOptions(keys=False))
        idx.create_field("dist", FieldOptions(type=FieldType.INT))
        idx.create_field("tags", FieldOptions(type=FieldType.SET, keys=True))
        h.save_schema()

        h2 = Holder(str(tmp_path))
        assert set(h2.indexes) == {"trips"}
        assert h2.index("trips").field("dist").options.type == FieldType.INT
        assert h2.index("trips").field("tags").options.keys

    def test_holder_data_roundtrip(self, tmp_path):
        h = Holder(str(tmp_path))
        idx = h.create_index("i")
        f = idx.create_field("f")
        f.set_bit(3, 100)
        f.set_bit(5, SHARD_WIDTH + 1)
        n = idx.create_field("n", FieldOptions(type=FieldType.INT))
        n.set_value(100, -42)
        idx.add_exists(100)
        save_holder_data(h)

        h2 = Holder(str(tmp_path))
        load_holder_data(h2)
        f2 = h2.index("i").field("f")
        assert bits(f2.fragment(0).row_plane(3)) == {100}
        assert bits(f2.fragment(1).row_plane(5)) == {1}
        assert h2.index("i").field("n").value(100) == -42
        assert bits(h2.index("i").existence_plane(0)) == {100}

    def test_translation(self, tmp_path):
        from pilosa_tpu.hashing import key_to_partition, shard_to_partition
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        h = Holder(str(tmp_path))
        idx = h.create_index("i", IndexOptions(keys=True))
        ids = idx.translate.create_keys(["alice", "bob"])
        assert set(ids) == {"alice", "bob"}
        # Record-key IDs land in a shard whose partition matches the
        # key's partition (reference: translate.go:103), and stay stable.
        for k, id_ in ids.items():
            assert id_ >= 1  # 0 stays invalid
            assert (shard_to_partition("i", id_ // SHARD_WIDTH)
                    == key_to_partition("i", k))
        again = idx.translate.create_keys(["bob", "carol"])
        assert again["bob"] == ids["bob"]
        assert len({*ids.values(), again["carol"]}) == 3  # all distinct
        # Row keys start at 1 (0 reserved).
        f = idx.create_field("f", FieldOptions(keys=True))
        rows = f.translate.create_keys(["x"])
        assert rows == {"x": 1}
        # Journal replay.
        h2 = Holder(str(tmp_path))
        idx2 = h2.index("i")
        assert idx2.translate.find_keys(["alice", "carol"]) == {
            "alice": ids["alice"], "carol": again["carol"]}
        assert idx2.translate.translate_ids([ids["bob"]]) == {ids["bob"]: "bob"}
        # Replayed stores keep allocating fresh IDs.
        dave = idx2.translate.create_keys(["dave"])["dave"]
        assert dave not in {ids["alice"], ids["bob"], again["carol"]}


class TestParanoia:
    """Opt-in invariant re-validation (reference: roaringparanoia /
    roaringsentinel build tags, SURVEY §5.2)."""

    def test_paranoia_catches_corruption(self, monkeypatch):
        from pilosa_tpu.core import fragment as fragmod

        monkeypatch.setattr(fragmod, "PARANOIA", True)
        frag = fragmod.SetFragment(0)
        frag.set_bit(1, 5)  # healthy mutation passes
        frag.row_index[99] = 7  # corrupt the slot map
        with pytest.raises(AssertionError):
            frag.set_bit(1, 6)

    def test_paranoia_bsi_exists_invariant(self, monkeypatch):
        import numpy as np

        from pilosa_tpu.core import fragment as fragmod
        from pilosa_tpu.ops import bsi as bsiops

        monkeypatch.setattr(fragmod, "PARANOIA", True)
        frag = fragmod.BSIFragment(0)
        frag.set_values([1, 2], [3, 4])
        # magnitude bit without existence = corruption
        frag.planes[bsiops.OFFSET, 100] = np.uint32(1)
        with pytest.raises(AssertionError):
            frag.set_values([3], [5])

    def test_budget_audit_detects_drift(self):
        from pilosa_tpu.core.stacked import DeviceBudget

        b = DeviceBudget(1 << 20)
        b.charge(("x", 0), 100, lambda: None)
        b.audit()
        b._used[next(iter(b._used))] += 7  # simulated leak
        with pytest.raises(AssertionError):
            b.audit()
