"""The benchmark's launcher: the one process that owns the chip.

It runs the program's ordinary server command (``pilosa_tpu`` ``server``,
default configuration unless the cell's configuration file carries a TOML
fragment) in its main thread. Only the process that holds the chip can
trace it or read its memory, so a control thread answers one-line
commands on stdin with one JSON line each on stdout:

    trace-start <dir>   jax.profiler.start_trace, host Python tracer off
    trace-stop          jax.profiler.stop_trace
    stats               peak device memory and the program counters

and ``jax.monitoring`` listeners count the programs built (compiled or
fetched from the persistent cache) and, of those, the fetched ones.
Before anything else it initialises the backend, prints
``{"event": "ready", "platform", "kind", "count"}``, and waits for ``go``:
the parent writes the data directory meanwhile.

    python server_child.py <repo root> <port> <data dir> [<config.toml>]
"""

import json
import sys
import threading

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def say(obj):
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def main(argv):
    root, port, data_dir = argv[1], argv[2], argv[3]
    config = argv[4] if len(argv) > 4 else None
    sys.path.insert(0, root)
    import jax
    import jax.monitoring

    # COMPILE_EVENT fires for every program built, compiled or fetched
    # from the persistent cache; CACHE_HIT_EVENT for the fetched ones
    counts = {"programs": 0, "cache_loads": 0}

    def on_duration(event, _secs, **_kw):
        if event == COMPILE_EVENT:
            counts["programs"] += 1
        elif event == CACHE_HIT_EVENT:
            counts["cache_loads"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    devices = jax.devices()
    say({"event": "ready", "platform": devices[0].platform,
         "kind": devices[0].device_kind, "count": len(devices)})
    if sys.stdin.readline().strip() != "go":
        return 2

    def stats():
        mem = [d.memory_stats() or {} for d in devices]
        return {"peak_bytes": max((m.get("peak_bytes_in_use", 0)
                                   for m in mem), default=0), **counts}

    def control():
        for line in sys.stdin:
            cmd, _, arg = line.strip().partition(" ")
            try:
                if cmd == "trace-start":
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(arg, profiler_options=opts)
                    say({"ok": True})
                elif cmd == "trace-stop":
                    jax.profiler.stop_trace()
                    say({"ok": True})
                elif cmd == "stats":
                    say({"ok": True, **stats()})
                else:
                    say({"ok": False, "error": f"unknown command {cmd!r}"})
            except Exception as e:  # the parent decides what a failure means
                say({"ok": False, "error": repr(e)})

    threading.Thread(target=control, daemon=True).start()
    from pilosa_tpu.ctl.cli import main as cli

    args = ["server", "--port", port, "--data-dir", data_dir]
    if config:
        args += ["--config", config]
    return cli(args)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
