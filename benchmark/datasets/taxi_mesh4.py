"""The taxi table as one four-chip host of a v5e-16 holds it.

The rides, the schema and every distribution are ``datasets/taxi.py``'s
(loaded here by path, as ``harness/manifest.load_dataset`` loads a
dataset, into a module of this file's own: that file is not edited and
the copy ``taxi-1b-c16`` runs is not touched). What differs is the
deployment (``configs/taxi-1b-mesh4.json``): four nodes hold the 1,049
shards, so this node's share is 264 of them over a mesh of four chips,
and the timeline is cut into 264 equal slices in place of 66. Stream
``s`` is shard ``s`` and holds the ``s``-th slice, so a shard spans about
ten days where a sixteenth's spans six weeks.
"""

import importlib.util
import os

#: this node's shards: a quarter of the table's 1,049, 66 a chip
NODE_SHARDS = 264
CLUSTER_NODES = 4
MESH_CHIPS = 4


def _taxi():
    spec = importlib.util.spec_from_file_location(
        "benchmark_dataset_taxi_for_mesh4",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "taxi.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the one thing that module reads at call time to place a stream on
    # the timeline: how many slices the loaded table is cut into
    mod.CHIP_SHARDS = NODE_SHARDS
    mod.CLUSTER_NODES = CLUSTER_NODES
    return mod


_TAXI = _taxi()

INDEX = _TAXI.INDEX
INGEST_STREAM = _TAXI.INGEST_STREAM
SHARD_WIDTH = _TAXI.SHARD_WIDTH
TABLE_SHARDS = _TAXI.TABLE_SHARDS
JITTER_S = _TAXI.JITTER_S
fields = _TAXI.fields
pickup_seconds = _TAXI.pickup_seconds
make = _TAXI.make
