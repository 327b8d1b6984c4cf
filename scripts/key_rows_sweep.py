"""The key-rows derivation kernel timed on the chip at the shape SF-10's
brand stack sends (``ops/keyrows._key_rows_pallas``: 32 rows from 11 key
bits stored as 16 planes over 58 shards), by word block and chunk, beside
its XLA twin: the table behind ``keyrows.BLOCK_WORDS`` / ``CHUNK_WORDS``
(PERF.md §5). Run it through the chip tool, from the repo root:

    python scripts/key_rows_sweep.py [out.json]

Every variant is a jitted program of its own name; its time is the mean
device time of its ``XLA Modules`` events in one profiler trace (the
method of ``scripts/pair_counts_sweep.py``), and its rows are checked
against the plain numpy derivation (``keyrows.reference``) on a slice of
the words. ``floor_ms``: the bytes a call must move (key planes read once,
rows written once) over 819 GB/s.
"""

import json
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from pilosa_tpu.ops import keyrows as K

WORDS = 58 * 32768
K_PAD, BITS, ROWS = 16, 11, 32
FIRST = 480           # a block inside the stack: slots 480..511
BLOCKS = (4096, 8192, 16384, 32768)
CHUNKS = (256, 512, 1024)
CALLS = 20
CHECK_WORDS = 4096    # numpy checks the first words of every variant
#: the kernel's Python function under ``guarded_call`` and ``jax.jit``
PALLAS = K._key_rows_pallas.__wrapped__.__wrapped__


def variants():
    """``(name, program, word block, chunk)``: the kernel at each word
    block and chunk (the module constants stand at the variant's values
    while it is traced), then the XLA twin."""
    for bw in BLOCKS:
        for chunk in CHUNKS:
            if chunk > bw:
                continue

            def fn(keys, first):
                # the plain function: a nested jit would keep its first
                # trace, and with it the first variant's constants
                return PALLAS(keys, first, ROWS, BITS, False)

            fn.__name__ = f"kr_b{bw}_c{chunk}"
            yield fn.__name__, jax.jit(fn), bw, chunk

    def twin(keys, first):
        return K._key_rows_xla.__wrapped__.__wrapped__(keys, first, ROWS)

    twin.__name__ = "kr_xla"
    yield "kr_xla", jax.jit(twin), K.BLOCK_WORDS, K.CHUNK_WORDS


def main(out_path):
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    rng = np.random.default_rng(38)
    host = np.zeros((K_PAD, WORDS), dtype=np.uint32)
    host[:BITS] = rng.integers(0, 1 << 32, (BITS, WORDS), dtype=np.uint32)
    keys = jax.device_put(host)
    first = jax.device_put(np.array([FIRST], dtype=np.int32))
    want = K.reference(host[:, :CHECK_WORDS], range(FIRST, FIRST + ROWS))
    rows, runs = [], []
    keep = K.BLOCK_WORDS, K.CHUNK_WORDS
    try:
        for name, prog, bw, chunk in variants():
            K.BLOCK_WORDS, K.CHUNK_WORDS = bw, chunk
            try:
                got = np.asarray(prog(keys, first))
            except Exception as e:  # a variant the compiler refuses
                print(name, "refused:", str(e)[:200], flush=True)
                continue
            rows.append({"name": name, "equal": bool(
                (got[:, :CHECK_WORDS] == want).all())})
            runs.append(prog)
    finally:
        K.BLOCK_WORDS, K.CHUNK_WORDS = keep
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for prog in runs:
            for _ in range(CALLS):
                out = prog(keys, first)
            out.block_until_ready()
        jax.profiler.stop_trace()
        times = module_ms(tmp)
    floor = 4.0 * (K_PAD + ROWS) * WORDS / 819e9 * 1e3
    for row in rows:
        row["ms"], row["calls"] = times.get(row["name"], (None, 0))
        row["floor_ms"] = round(floor, 4)
        print(json.dumps(row), flush=True)
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as fh:
            json.dump(rows, fh, indent=1)


def module_ms(trace_dir):
    """{program name: (mean ms, calls)} from the device's module line."""
    from jax.profiler import ProfileData

    pb = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
          for f in fs if f.endswith(".xplane.pb")][0]
    sums = {}
    for plane in ProfileData.from_file(pb).planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                m = re.match(r"jit_(kr_\w+)\(", e.name)
                if m:
                    s = sums.setdefault(m.group(1), [0.0, 0])
                    s[0] += e.duration_ns / 1e6
                    s[1] += 1
    return {k: (round(t / n, 4), n) for k, (t, n) in sums.items()}


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else None)
