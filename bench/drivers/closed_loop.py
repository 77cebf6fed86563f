"""Closed-loop traffic: every client keeps one request outstanding.

A tick is: replace the relations the mix names by their next data
version, every client submits its request, the service ticks, and each
client waits for its outputs.  A request is timed from its submit to the
moment its outputs are ready on the device.

Traffic file keys (``bench/traffic/<mix>.json``, ``"driver":
"closed_loop"``):

* ``versions``: how many data versions the refreshed relations cycle
  through;
* ``refresh``: ``"all"`` or a list of relation names replaced before each
  tick after the first;
* ``warm_ticks``: ticks run in set-up, enough for every combination of
  versions the window reads to appear once.
"""
from __future__ import annotations

import time

import numpy as np

from ticks import serve


class Driver:
    def __init__(self, server, traffic: dict, base_relations):
        self.server = server
        self.n_versions = traffic["versions"]
        self.warm_ticks = traffic["warm_ticks"]
        refresh = traffic["refresh"]
        self.base = sorted(base_relations)
        self.refresh = self.base if refresh == "all" else list(refresh)
        self.clients = list(range(len(server.clients)))
        self.t = 0

    def versions_at(self, t: int) -> dict:
        k = t % self.n_versions
        return {r: (k if r in self.refresh else 0) for r in self.base}

    def tick(self):
        t = self.t
        self.t += 1
        versions = self.versions_at(t)
        if t > 0:
            self.server.refresh(self.refresh, versions[self.refresh[0]])
        return serve(self.server, t, versions, self.clients, time.perf_counter)

    def warm(self) -> None:
        for _ in range(self.warm_ticks):
            self.tick()

    def window(self, seconds: float, check_ticks: int, rng: np.random.Generator):
        """Tick until ``seconds`` have passed, finishing the tick in flight.
        Returns ``(start, ticks, kept)``: ``kept`` is a seeded reservoir
        sample of at most ``check_ticks`` ticks whose requests keep their
        outputs; every other tick drops them."""
        ticks, kept = [], []
        start = time.perf_counter()
        while time.perf_counter() - start < seconds:
            tk = self.tick()
            i = len(ticks)
            ticks.append(tk)
            if i < check_ticks:
                kept.append(tk)
            else:
                j = int(rng.integers(0, i + 1))
                if j < check_ticks:
                    kept[j].requests = []
                    kept[j] = tk
                else:
                    tk.requests = []
        return start, ticks, kept
