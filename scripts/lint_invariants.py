#!/usr/bin/env python3
"""Project-invariant linter CLI (static half of the analysis plane).

Runs :mod:`pilosa_tpu.analysis.lint` over the tree and gates on the
checked-in baseline: pre-existing violations listed (with a reason) in
``pilosa_tpu/analysis/baseline.json`` are suppressed; anything NEW
exits 1. Stale baseline entries (matched nothing — the site was fixed)
are reported so the ratchet only ever goes down.

Usage:
    scripts/lint_invariants.py                        # lint pilosa_tpu/
    scripts/lint_invariants.py --baseline pilosa_tpu/analysis/baseline.json
    scripts/lint_invariants.py --json                 # machine-readable
    scripts/lint_invariants.py --write-baseline       # (re)seed baseline
    scripts/lint_invariants.py --list-rules
    scripts/lint_invariants.py --selftest             # exercises every rule

``--selftest`` seeds one positive and one negative fixture per rule
plus a baseline round-trip, so the gate logic itself is testable
without the tree.

Wired into tier1.sh as the analysis lane's first step.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from pilosa_tpu.analysis import lint  # noqa: E402

DEFAULT_BASELINE = os.path.join("pilosa_tpu", "analysis", "baseline.json")


def _fmt(v: lint.Violation) -> str:
    return f"{v.path}:{v.line}: [{v.rule}] {v.message}\n    {v.match}"


# ---------------------------------------------------------------------------
# selftest fixtures: (rule name, violating source, clean source, path)
# ---------------------------------------------------------------------------

_FIXTURES = [
    ("no-raw-time",
     "import time\ndef tick():\n    return time.time()\n",
     "import time\nclass WallClock:\n    def now(self):\n"
     "        return time.time()\n"
     "def tick(clock):\n    return clock.now()\n",
     "pilosa_tpu/obs/sample.py"),
    ("no-bare-lock",
     "import threading\nLOCK = threading.Lock()\n",
     "from pilosa_tpu.analysis import locktrace\n"
     "LOCK = locktrace.tracked_lock('sample.lock')\n",
     "pilosa_tpu/cache/sample.py"),
    ("no-callback-under-lock",
     "def fire(self):\n    with self._lock:\n"
     "        for listener in self._listeners:\n            listener(1)\n",
     "def fire(self):\n    with self._lock:\n"
     "        pending = list(self._listeners)\n"
     "    for fn in pending:\n        fn(1)\n",
     "pilosa_tpu/cluster/sample.py"),
    ("no-device-call-outside-platform",
     "import jax.numpy as jnp\ndef up(x):\n    return jnp.sum(x)\n",
     "from pilosa_tpu import platform\n"
     "def up(x):\n    return platform.guarded_call(lambda: x)\n",
     "pilosa_tpu/stream/sample.py"),
    ("contextvar-set-reset",
     "import contextvars\nCV = contextvars.ContextVar('cv')\n"
     "def enter(v):\n    CV.set(v)\n",
     "import contextvars\nCV = contextvars.ContextVar('cv')\n"
     "def enter(v):\n    token = CV.set(v)\n    return token\n"
     "def leave(token):\n    CV.reset(token)\n",
     "pilosa_tpu/obs/sample2.py"),
    ("metrics-label-hygiene",
     "def rec(registry, shard):\n"
     "    registry.count('reads_total', shard=f'shard-{shard}')\n",
     "def rec(registry, outcome):\n"
     "    registry.count('reads_total', outcome=outcome)\n",
     "pilosa_tpu/server/sample.py"),
]


def selftest() -> int:
    engine = lint.default_engine()
    failures = []
    for rule, bad, good, path in _FIXTURES:
        hits = [v for v in engine.check_source(path, bad) if v.rule == rule]
        if not hits:
            failures.append(f"{rule}: positive fixture not flagged")
        clean = [v for v in engine.check_source(path, good)
                 if v.rule == rule]
        if clean:
            failures.append(f"{rule}: negative fixture flagged: "
                            f"{clean[0].message}")
    # baseline round-trip: suppressing the positive fixtures yields zero
    # new violations and zero stale entries; an extra entry goes stale
    all_bad = [v for rule, bad, _, path in _FIXTURES
               for v in lint.default_engine().check_source(path, bad)
               if v.rule == rule]
    entries = lint.baseline_entries_for(all_bad, reason="selftest")
    new, suppressed, stale = lint.apply_baseline(all_bad, entries)
    if new or stale or len(suppressed) != len(all_bad):
        failures.append(f"baseline round-trip: new={len(new)} "
                        f"stale={len(stale)} "
                        f"suppressed={len(suppressed)}/{len(all_bad)}")
    extra = entries + [{"rule": "no-raw-time", "path": "gone.py",
                        "match": "time.time()", "reason": "fixed"}]
    _, _, stale2 = lint.apply_baseline(all_bad, extra)
    if len(stale2) != 1:
        failures.append(f"stale detection: expected 1, got {len(stale2)}")
    if failures:
        for f in failures:
            print(f"SELFTEST FAIL: {f}", file=sys.stderr)
        return 1
    print(f"selftest OK: {len(_FIXTURES)} rules x (positive+negative) + "
          f"baseline round-trip")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("root", nargs="?", default="pilosa_tpu",
                    help="file or directory to lint (default: pilosa_tpu)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help=f"baseline JSON (default: {DEFAULT_BASELINE}; "
                         f"'-' disables)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit machine-readable JSON report")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write current violations to --baseline "
                         "(entries need reasons filled in) and exit 0")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--selftest", action="store_true",
                    help="run built-in fixtures for every rule and exit")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest()

    engine = lint.default_engine()
    if args.list_rules:
        for r in engine.rules:
            print(f"{r.name:36s} {r.description}")
        return 0

    violations = engine.check_tree(args.root)

    if args.write_baseline:
        entries = lint.baseline_entries_for(violations)
        lint.save_baseline(args.baseline, entries)
        print(f"wrote {len(entries)} entries to {args.baseline} "
              f"(fill in reasons before committing)")
        return 0

    entries = [] if args.baseline == "-" else \
        lint.load_baseline(args.baseline)
    new, suppressed, stale = lint.apply_baseline(violations, entries)

    if args.as_json:
        print(json.dumps({
            "new": [v.to_json() for v in new],
            "suppressed": [v.to_json() for v in suppressed],
            "stale_baseline_entries": stale,
        }, indent=1))
    else:
        for v in new:
            print(_fmt(v))
        for e in stale:
            print(f"STALE baseline entry (site fixed — delete it): "
                  f"[{e['rule']}] {e['path']} :: {e['match']}")
        print(f"lint: {len(new)} new, {len(suppressed)} baselined, "
              f"{len(stale)} stale baseline entries")

    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
