"""Star Schema Benchmark at the ``tiny`` scale: all 13 queries against
the independent numpy oracle, on one node (where each is also held to
the ``PILOSA_TPU_SEMIJOIN=0`` hash-join reference) and on a 3-node
cluster that drops one request to a non-coordinator node in every
query."""

import os

import pytest

from pilosa_tpu.api import API
from pilosa_tpu.cluster.harness import LocalCluster
from pilosa_tpu.cluster.resilience import FaultPlan
from pilosa_tpu.loadgen import ssb
from pilosa_tpu.sql import SQLEngine

QIDS = list(ssb.QUERIES)


@pytest.fixture(scope="module")
def data():
    return ssb.generate("tiny", seed=7)


@pytest.fixture(scope="module")
def loaded(data):
    eng = SQLEngine(API())
    ssb.load(lambda q: eng.query(q), data)
    return data, eng


@pytest.fixture(scope="module")
def cluster(data, tmp_path_factory):
    """(cluster, plan, victim): three nodes, two replicas, the tables
    loaded through the coordinator; ``plan`` carries no rule yet."""
    plan = FaultPlan()  # seeded from PILOSA_TPU_FAULT_SEED
    with LocalCluster(3, replica_n=2,
                      base_path=str(tmp_path_factory.mktemp("ssb3")),
                      fault_plan=plan) as c:
        ssb.load(c.coordinator.sql, data)
        yield c, plan, c.nodes[1].node.id


class TestSSBSmoke:
    @pytest.mark.parametrize("qid", QIDS)
    def test_flight_vs_oracle(self, loaded, qid):
        data, eng = loaded
        got = eng.query(ssb.QUERIES[qid]).data
        assert ssb.verify(data, qid, got) is None
        os.environ["PILOSA_TPU_SEMIJOIN"] = "0"
        try:
            hashed = eng.query(ssb.QUERIES[qid]).data
        finally:
            del os.environ["PILOSA_TPU_SEMIJOIN"]
        assert got == hashed

    @pytest.mark.parametrize("qid", QIDS)
    def test_cluster_flight_vs_oracle(self, data, cluster, qid):
        c, plan, victim = cluster
        first = plan.seen(victim)
        dropped = len(plan.events)
        # the next request the coordinator sends the victim (a leg of
        # this query) is refused with an InjectedFault, an OSError to
        # the client, which sends it again: the answer must not change
        plan.drop(victim, first=first, count=1)
        got = c.coordinator.sql(ssb.QUERIES[qid]).data
        assert ssb.verify(data, qid, got) is None
        assert plan.events[dropped:] == [(victim, first, "drop")]

    def test_all_queries_parse_and_plan(self, loaded):
        _, eng = loaded
        for qid, q in ssb.QUERIES.items():
            eng.query(q)  # no SQLError on any of the 13

    def test_datagen_deterministic(self):
        a = ssb.generate("tiny", seed=7)
        b = ssb.generate("tiny", seed=7)
        assert (a.lineorder["lo_revenue"] == b.lineorder["lo_revenue"]).all()
        assert a.part["p_brand1"] == b.part["p_brand1"]
