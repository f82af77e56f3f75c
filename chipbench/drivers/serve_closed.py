"""Closed-loop serving: `clients` clients, each sending its next request as
soon as its last one completes, through the program's PagedServeLoop.

Set-up ends with every client's first request submitted and one tick
run (it admits what the pool holds and prefills it), so the window opens
on a loaded server rather than on eight prefills at once.  Traffic-file
keys: clients, max_batch, the size keys of chipbench/traffic.py (every
seed sends the same sizes in the same order), limits.
"""
from __future__ import annotations

import time

from chipbench import common, serving, traffic


def run(cfgfile, t, *, seed, seconds, traced, clock, t_start, devs):
    server = serving.Server(cfgfile, t, seed, common.Spans(traced))
    return serving.measure(server, seconds, traced, clock, t_start, devs,
                           seed, t, *drive_for(server, t, seed, seconds))


def drive_for(server, t, seed, seconds):
    """(prime, drive): prime(window) is the end of set-up; drive(window)
    runs the window."""
    specs = traffic.stream(t, seed, server.vocab, open_loop=False)

    def prime(w: serving.Window):
        now = time.perf_counter()
        for _ in range(t["clients"]):
            w.submit(next(specs), now)
        for _ in w.tick():
            w.submit(next(specs), time.perf_counter())

    def drive(w: serving.Window):
        while time.perf_counter() < w.t_end:
            for _ in w.tick():
                w.submit(next(specs), time.perf_counter())

    return prime, drive
