"""The load generator: one thread submits, one queries.

The submitter is a closed loop: it submits the tape's chunks back to back,
so the server's queue stays at its bound and every micro-batch is full. A
submit that finds the queue full blocks (overload "block") or is refused
and retried; either way the tape's chunk reaches the graph. The querier
reads snapshots at Poisson times (``query(..., min_seq=0)``: whatever is
published, never waiting) and records how late each read started.
"""
from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from repro.serve import AdmissionError

from .traffic import poisson_times

_JOIN_S = 120.0


class Load:
    def __init__(self, server, tape, traffic: dict,
                 ss: np.random.SeedSequence, *, span=None):
        if traffic["loop"] != "closed":
            raise ValueError(f"loop must be closed: {traffic['loop']}")
        self.server = server
        self.tape = tape
        self.q_rng = np.random.default_rng(ss)
        self.q_times = poisson_times(traffic["query_rate_per_s"], self.q_rng)
        self.q_size = int(traffic["query_vertices"])
        self.span = span              # context-manager factory or None
        self.chunks: list[tuple] = []                  # (sent, size)
        self.queries: list[tuple] = []                 # (due, start, done)
        self.refused = 0
        self.errors: list[BaseException] = []
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []

    def start(self) -> "Load":
        self.t0 = time.perf_counter()
        for fn in (self._submit_loop, self._query_loop):
            th = threading.Thread(target=self._guard, args=(fn,), daemon=True)
            th.start()
            self._threads.append(th)
        return self

    def stop(self) -> None:
        """Stop offering load and wait for both threads (the caller
        drains the server)."""
        self._stop.set()
        for th in self._threads:
            th.join(_JOIN_S)
        alive = [th for th in self._threads if th.is_alive()]
        if alive:
            raise RuntimeError(f"{len(alive)} load threads did not end")

    def _guard(self, fn) -> None:
        try:
            fn()
        except BaseException as e:      # surfaced by the harness
            self.errors.append(e)
            self._stop.set()

    def _wrap(self, name: str):
        return self.span(name) if self.span else contextlib.nullcontext()

    def _submit_loop(self) -> None:
        while not self._stop.is_set():
            name, ups = self.tape.next_chunk()
            sent = time.perf_counter()
            with self._wrap("bench.submit"):
                self._submit(name, ups)
            self.chunks.append((sent, len(ups)))

    def _submit(self, name: str, ups) -> int:
        """Submit, retrying a refused chunk until it is admitted (the tape
        has drawn it, so it must reach the graph)."""
        while True:
            try:
                return self.server.submit(name, ups)
            except AdmissionError:
                self.refused += 1
                time.sleep(1e-3)

    def _query_loop(self) -> None:
        names, shares = self.tape.names, self.tape.shares
        n = self.tape.n
        for off in self.q_times:
            due = self.t0 + off
            left = due - time.perf_counter()
            if left > 0:
                self._stop.wait(left)
            if self._stop.is_set():
                break
            name = names[int(self.q_rng.choice(len(names), p=shares))]
            verts = self.q_rng.integers(0, n, size=self.q_size)
            start = time.perf_counter()
            with self._wrap("bench.query"):
                self.server.query(name, verts, min_seq=0)
            self.queries.append((due, start, time.perf_counter()))
