"""Both bodies of the pair-count kernel, timed on the chip at the shapes
the benchmark's cells send: the table behind ``ops/groupby.pallas_body``
(PERF.md §5). Run it through the chip tool, from the repo root:

    python scripts/pair_counts_sweep.py [out.json]

Every variant is a jitted program of its own name; its time is the mean
device time of its ``XLA Modules`` events in one profiler trace, and its
counts are checked against the XLA scan's (``wall_ms``: the host's clock
over the same calls sent back to back, dispatch included; ``lower_s``:
the host's seconds to trace and lower the variant, what a process pays
once a shape before the compile cache can be asked).
"""

import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu.ops import groupby as G

TAXI, SSB, MESH = 2_162_688, 196_608, 65_536
#: (rows a, rows b, words): what the cells send ...
SHAPES = [
    (24, 16, TAXI), (8, 16, TAXI), (16, 16, TAXI), (16, 8, TAXI),
    (1, 80, TAXI), (1, 2, TAXI),
    (16, 256, SSB), (8, 256, SSB), (8, 1000, SSB), (2, 14, SSB),
    (2, 25, SSB), (8, 512, MESH), (128, 256, SSB),
    (25, 256, SSB), (25, 232, SSB), (25, 32, SSB), (25, 8, SSB),
    (100, 17, SSB),
]
#: ... then the rule's border, by r1 * r2 / (r1 + r2) from 16 to 64
SHAPES += [
    (32, 32, TAXI), (24, 64, TAXI), (20, 256, SSB), (40, 40, SSB),
    (24, 128, SSB), (128, 24, SSB), (32, 64, SSB), (24, 256, SSB),
    (48, 48, SSB), (28, 256, SSB), (32, 128, SSB), (128, 32, SSB),
    (48, 64, SSB), (56, 56, SSB), (32, 256, SSB), (40, 128, SSB),
    (64, 64, SSB), (128, 64, SSB), (64, 256, SSB), (128, 128, SSB),
]
VPU_BLOCKS = (2048, 4096, 8192, 16384)
CALLS = 20


def variants(r1, tr2, words):
    """``(tag, body)``; a VPU variant is traced while the module's cap on
    its word block stands at that variant's: trace it before the next."""
    yield "mxu", G._pair_counts_mxu
    cap, seen = G._VPU_MAX_BW, set()
    try:
        for G._VPU_MAX_BW in VPU_BLOCKS:
            bw = G._vpu_block_words(r1, tr2, words)
            if bw not in seen:
                seen.add(bw)
                yield f"vpu{bw}", G._pair_counts_vpu
    finally:
        G._VPU_MAX_BW = cap


def main(out_path):
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    runs, rows = [], []
    for n, (r1, r2, words) in enumerate(SHAPES):
        ka, kb = jax.random.split(jax.random.PRNGKey(n))
        a = jax.random.bits(ka, (r1, words), jnp.uint32)
        b = jax.random.bits(kb, (r2, words), jnp.uint32)
        want = np.asarray(G._pair_counts_xla(a, b))
        for tag, body in variants(r1, G._row_tile(r2)[0], words):
            name = f"pc_{r1}x{r2}_{words}_{tag}"

            def fn(a, b, body=body):
                return body(a, b, G.PU.use_interpret())

            fn.__name__ = name
            prog = jax.jit(fn)
            try:
                t0 = time.perf_counter()
                prog.lower(a, b)
                lower_s = time.perf_counter() - t0
                got = np.asarray(prog(a, b))
            except Exception as e:  # a variant the compiler refuses
                print(name, "refused:", str(e)[:200], flush=True)
                continue
            rows.append({"r1": r1, "r2": r2, "words": words, "body": tag,
                         "name": name, "equal": bool((got == want).all()),
                         "lower_s": round(lower_s, 3)})
            runs.append((prog, a, b))
        del a, b
        for (prog, x, y), row in zip(runs, rows[-len(runs):]):
            t0 = time.perf_counter()
            for _ in range(CALLS):
                out = prog(x, y)
            out.block_until_ready()
            row["wall_ms"] = round((time.perf_counter() - t0) / CALLS * 1e3, 4)
        # one trace a shape keeps the operands' memory bounded
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            for prog, x, y in runs:
                for _ in range(CALLS):
                    out = prog(x, y)
                out.block_until_ready()
            jax.profiler.stop_trace()
            times = module_ms(tmp)
        runs.clear()
        for row in rows:
            if row["name"] in times and "ms" not in row:
                row["ms"], row["calls"] = times[row["name"]]
                floor = 4.0 * (r1 + r2) * words / 819e9 * 1e3
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
                m = re.match(r"jit_(pc_\w+)\(", e.name)
                if m:
                    s = sums.setdefault(m.group(1), [0.0, 0])
                    s[0] += e.duration_ns / 1e6
                    s[1] += 1
    return {k: (round(t / n, 4), n) for k, (t, n) in sums.items()}


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else None)
