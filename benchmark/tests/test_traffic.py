"""The schedule is the seed's: same seed, same requests at the same times."""

import numpy as np
import pytest

from harness import manifest, traffic

MAN = manifest.Manifest()
FIELDS = {f["name"]: f for f in manifest.load_dataset("ssb_flat").fields()}


def texts(reqs):
    return [(r.at, r.text) for r in reqs]


def test_open_schedule_repeats_for_a_seed_and_differs_across_seeds():
    mix = MAN.mixes["filter-open"]
    a = traffic.open_schedule(mix, MAN.families, FIELDS, "ssb", 4, 20.0)
    b = traffic.open_schedule(mix, MAN.families, FIELDS, "ssb", 4, 20.0)
    c = traffic.open_schedule(mix, MAN.families, FIELDS, "ssb", 5, 20.0)
    assert texts(a) == texts(b) and texts(a) != texts(c)
    assert all(0 <= r.at < 20.0 for r in a)
    assert [r.at for r in a] == sorted(r.at for r in a)
    assert len(a) == pytest.approx(mix["rate"] * 20.0, rel=0.15)
    share = sum(r.family == "count-intersect" for r in a) / len(a)
    assert share == pytest.approx(0.30, abs=0.05)


def test_even_arrivals_are_evenly_spaced():
    mix = dict(MAN.mixes["filter-open"], arrivals="uniform", rate=10)
    reqs = traffic.open_schedule(mix, MAN.families, FIELDS, "ssb", 1, 3.0)
    assert np.allclose(np.diff([r.at for r in reqs]), 0.1)
    assert len(reqs) == 30


def test_side_reads_walk_a_fixed_cycle_whatever_the_seed():
    spec = MAN.mixes["ingest-sustained"]["side_reads"]
    a = traffic.open_schedule(spec, MAN.families, FIELDS, "ssb", 4, 50.0)
    b = traffic.open_schedule(spec, MAN.families, FIELDS, "ssb", 5, 50.0)
    assert len(a) == len(b) == 250
    assert np.allclose(np.diff([r.at for r in a]), 0.2)
    # the seed draws the parameters, never which families a window holds
    assert [r.family for r in a] == [r.family for r in b]
    assert [r.text for r in a] != [r.text for r in b]
    cycle = [r.family for r in a[:20]]
    assert [r.family for r in a[20:40]] == cycle
    # the shares of filter-open, exactly, in every 20 reads (4 s)
    assert {k: cycle.count(k) for k in set(cycle)} == {
        "count-intersect": 6, "bsi-range-count": 4, "sum-filter": 4,
        "ssb-q1.1": 2, "ssb-q1.2": 1, "ssb-q1.3": 1, "sql-count": 2}
    assert [k for k in cycle if k.startswith("ssb-q1")] == [
        "ssb-q1.1", "ssb-q1.2", "ssb-q1.3", "ssb-q1.1"]


def test_a_round_is_fixed_and_each_client_starts_elsewhere():
    mix = MAN.mixes["groupby-closed"]
    a = traffic.closed_sequences(mix, MAN.families, FIELDS, "ssb", 9)
    b = traffic.closed_sequences(mix, MAN.families, FIELDS, "ssb", 9)
    assert [[r.text for r in s] for s in a] == \
        [[r.text for r in s] for s in b]
    assert len(a) == mix["clients"]
    n = len(mix["round"]) * mix["round_draws"]
    assert all(len(s) == n for s in a)
    assert sorted(r.text for r in a[0]) == sorted(r.text for r in a[1])
    assert a[0][0].text != a[1][0].text


def test_weighted_clients_hold_every_family_in_proportion_everywhere():
    mix = MAN.mixes["mixed-closed"]
    seqs = traffic.closed_sequences(mix, MAN.families, FIELDS, "ssb", 2,
                                    length=400)
    again = traffic.closed_sequences(mix, MAN.families, FIELDS, "ssb", 3,
                                     length=400)
    assert len(seqs) == mix["clients"]
    # another seed draws other parameters for the same order of families
    assert [[r.family for r in s] for s in seqs] == \
        [[r.family for r in s] for s in again]
    assert [r.text for r in seqs[0]] != [r.text for r in again[0]]
    heavy = [[i for i, r in enumerate(s) if r.family == "groupby2-count"]
             for s in seqs]
    # 5%: one in about every twenty, in any stretch, for every client,
    # and the clients do not all send theirs at the same step
    assert all(len(h) == 20 for h in heavy)
    assert all(17 <= b - a <= 23 for h in heavy for a, b in zip(h, h[1:]))
    assert len({h[0] for h in heavy}) == len(heavy)
    cycle = traffic._cycle({"a": 3, "b": 1}, 8)
    assert cycle == ["a", "a", "b", "a", "a", "a", "b", "a"]
    uneven = traffic._cycle({"a": 1, "b": 1, "c": 1}, 100)
    assert len(uneven) == 100
    assert sorted(uneven.count(k) for k in "abc") == [33, 33, 34]


def test_zipf_prefers_low_ranks_and_fixed_is_fixed():
    rng = np.random.default_rng(0)
    field = FIELDS["p_brand1"]
    draws = [traffic._draw(rng, {"dist": "zipf:1.1"}, field)
             for _ in range(2000)]
    assert min(draws) == 0 and max(draws) <= 999
    assert sum(d < 10 for d in draws) > sum(d >= 500 for d in draws)
    assert traffic._draw(rng, {"value": 4}, field) == 4
    with pytest.raises(ValueError):
        traffic._draw(rng, {"dist": "normal"}, field)
