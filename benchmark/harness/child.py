"""The parent's handle on the launcher (``server_child.py``): spawn it,
exchange one-line commands for one-line JSON answers, stop it."""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class Failed(Exception):
    """The run cannot produce a result."""


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Launcher:
    def __init__(self, data_dir, log_path, toml=None):
        self.port = free_port()
        self.log_path = log_path
        argv = [sys.executable, os.path.join(HERE, "server_child.py"),
                ROOT, str(self.port), data_dir] + ([toml] if toml else [])
        self.log = open(log_path, "wb")
        # the environment passes through: JAX_PLATFORMS and
        # JAX_COMPILATION_CACHE_DIR mean to the server what they mean here
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self.log, text=True)
        self.replies = []
        self.cond = threading.Condition()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self):
        for line in self.proc.stdout:
            try:
                obj = json.loads(line)
            except ValueError:
                continue  # the program's own prints
            with self.cond:
                self.replies.append(obj)
                self.cond.notify_all()

    def _next(self, timeout):
        deadline = time.monotonic() + timeout
        with self.cond:
            while not self.replies:
                left = deadline - time.monotonic()
                if left <= 0 or self.proc.poll() is not None:
                    raise Failed(f"the server child gave no answer "
                                 f"(rc={self.proc.poll()}); see "
                                 f"{self.log_path}")
                self.cond.wait(min(left, 0.5))
            return self.replies.pop(0)

    def send(self, line):
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def command(self, line, timeout=120.0):
        """One command, one answer. Commands are not interleaved: the
        window's tracer is the only caller while it runs."""
        self.send(line)
        reply = self._next(timeout)
        if not reply.get("ok"):
            raise Failed(f"child command {line!r}: {reply.get('error')}")
        return reply

    def ready(self):
        """The child's first line: the devices its JAX found."""
        return self._next(600.0)

    def alive(self):
        return self.proc.poll() is None

    def stop(self):
        if self.alive():
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.log.close()

    def log_tail(self, n=4000):
        with open(self.log_path, errors="replace") as fh:
            return fh.read()[-n:]
