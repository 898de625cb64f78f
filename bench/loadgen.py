"""The one traffic generator.  A mix is a data file under ``traffic/``
whose ``path`` names how its events reach the program, and whose other
keys are that path's parameters:

* ``launch`` — a closed loop of blocking ``Function.launch`` calls on one
  ``HetSession``, each with a new query against the next resident
  sequence (round robin), and its output copied to the host;
* ``serve`` — an open loop through ``ServingFrontEnd``: requests arrive
  on a fixed trace of Gamma-distributed gaps (rate, coefficient of
  variation), each from a tenant drawn by popularity, and each is one
  launch against one of its tenant's resident sequences.  Latency runs
  from the request's *due* time to its output on the host;
* ``migrate`` — a closed loop of launches that start on one backend,
  rest at a key-tile barrier, are moved with ``migrate()`` to a session
  on another backend and finish there.  Downtime runs from the pause to
  the target's first segment done.

Every path warms up exactly the calls its window makes, records one dict
per event of the window, and keeps each event's output for the
comparison with the reference.  Spans named ``bench.<step>`` mark the
host's steps in a profiler trace.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

#: the backend the launch and serve paths run on: the Mosaic-lowered one
BACKEND = "pallas"


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


def device_bytes_in_use() -> int:
    """Bytes of device memory in use now, on the fullest chip."""
    import jax

    return max((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in jax.local_devices())


class HostPauses:
    """The garbage collector's pauses while the context is open: one
    ``[seconds since open, generation, ms]`` per collection of 1 ms or
    more, and the count and total of all of them."""

    def __init__(self):
        self.pauses: List[List[float]] = []
        self.count, self.total_ms = 0, 0.0

    def _hook(self, phase: str, info: Dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._began = now
            return
        ms = (now - self._began) * 1e3
        self.count += 1
        self.total_ms += ms
        if ms >= 1.0:
            self.pauses.append([self._began - self.t0, info["generation"],
                                ms])

    def __enter__(self):
        self.t0 = time.perf_counter()
        gc.callbacks.append(self._hook)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._hook)
        return False

    def summary(self) -> str:
        worst = max((ms for _, _, ms in self.pauses), default=0.0)
        return (f"gc_collections={self.count} gc_ms_total="
                f"{self.total_ms:.3f} gc_ms_max={worst:.3f} "
                f"gc_pauses_over_1ms={len(self.pauses)}")


def block_on(engine) -> None:
    """Wait until every device array of a launch's state is computed."""
    import jax

    st = engine.state
    leaves = list(st.regs.values()) + list(st.globals_.values())
    if st.shared is not None:
        leaves.append(st.shared)
    jax.block_until_ready([a for a in leaves if isinstance(a, jax.Array)])


class Path:
    """One way for events to reach the program.  ``plan`` fixes how many
    distinct queries the run draws, ``prepare`` puts the inputs into the
    program's buffers, ``warm`` makes one event of each kind the window
    makes, and ``window`` runs for the given seconds."""

    def __init__(self, config: Dict, traffic: Dict, kernel, seed: int):
        self.traffic, self.kernel = traffic, kernel
        self.attn = config["attn"]
        self.n_seq = int(config["resident_sequences"])
        self.grid, self.block = kernel.geometry(self.attn)
        self.rng = np.random.default_rng(seed)
        self.answers: List[Tuple[Dict, np.ndarray]] = []
        self.sessions: Dict[str, object] = {}
        #: device bytes in use as each event of the window ends
        self.bytes_in_use: List[int] = []

    def plan(self, seconds: float) -> int:
        return int(self.traffic["queries"])

    def session(self, backend: str):
        from repro.core import HetSession

        if backend not in self.sessions:
            self.sessions[backend] = HetSession(backend)
        return self.sessions[backend]

    def resident(self, sess, k: np.ndarray, v: np.ndarray) -> None:
        """Copy every resident sequence's keys and values into buffers of
        ``sess`` (``self.kv``)."""
        self.kv = []
        for s in range(self.n_seq):
            kb = sess.alloc(k[s].size).copy_from_host(k[s])
            vb = sess.alloc(v[s].size).copy_from_host(v[s])
            self.kv.append((kb, vb))

    def shape_o(self, o: np.ndarray) -> np.ndarray:
        return np.asarray(o, np.float32).reshape(self.grid, -1)

    def release(self) -> None:
        for sess in self.sessions.values():
            sess.synchronize()
        self.sessions.clear()
        self.kv = []

    def segments(self) -> int:
        """Segment kernels executed so far by the pallas sessions (the
        ``block_stats()`` tiled and scalar counts)."""
        return sum(int(bs.get("tiled", 0)) + int(bs.get("scalar", 0))
                   for bs in (sess.block_stats()
                              for sess in self.sessions.values()))


class LaunchPath(Path):
    """Closed loop, one stream: launch, wait, output to the host."""

    def prepare(self, k, v, q) -> None:
        self.q = q
        sess = self.session(BACKEND)
        self.fn = sess.load(self.kernel.program(self.attn)).function()
        self.resident(sess, k, v)
        self.qb = sess.alloc(q[0].size)
        self.ob = sess.alloc(self.grid * int(self.attn["head_dim"]))

    def _one(self, i: int, s: int) -> np.ndarray:
        kb, vb = self.kv[s]
        with span("launch"):
            self.qb.copy_from_host(self.q[i % len(self.q)])
            self.fn.launch(self.grid, self.block, self.kernel.args(
                self.attn, self.qb, kb, vb, self.ob))
        with span("copy_to_host"):
            return self.ob.copy_to_host()

    def warm(self) -> None:
        self._one(0, 0)

    def window(self, seconds: float):
        recs = []
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            s = i % self.n_seq
            start = time.perf_counter()
            o = self._one(i + 1, s)
            end = time.perf_counter()
            self.bytes_in_use.append(device_bytes_in_use())
            rec = {"kind": "launch", "seq": s, "q": (i + 1) % len(self.q),
                   "start": start - t0, "end": end - t0, "ok": True}
            recs.append(rec)
            self.answers.append((rec, self.shape_o(o)))
            i += 1
        return recs, {"t0": 0.0, "t1": time.perf_counter() - t0}


class ServePath(Path):
    """Open loop through the serving front end."""

    def plan(self, seconds: float) -> int:
        t = self.traffic
        rate, cv = float(t["rate_per_s"]), float(t["gap_cv"])
        tenants = t["tenants"]
        # the arrival trace is part of the mix: drawn from the mix's own
        # schedule seed, the same for every run seed, so every seed offers
        # the same work; the run seed draws the numbers and which of its
        # tenant's sequences each request reads
        sched = np.random.default_rng(int(t["schedule_seed"]))
        shape = 1.0 / (cv * cv)
        due, clock = [], 0.0
        while True:
            clock += sched.gamma(shape, 1.0 / (rate * shape))
            if clock >= seconds:
                break
            due.append(clock)
        pop = np.array([float(x["popularity"]) for x in tenants])
        who = sched.choice(len(tenants), size=len(due), p=pop / pop.sum())
        per = int(t["sequences_per_tenant"])
        if per * len(tenants) != self.n_seq:
            raise ValueError(f"{len(tenants)} tenants x {per} sequences "
                             f"!= {self.n_seq} resident sequences")
        pick = self.rng.integers(0, per, size=len(due))
        pool = int(t["queries"])
        self.events = [{"due": d, "tenant": int(w), "seq": int(w) * per
                        + int(p), "q": (i + 1) % pool}
                       for i, (d, w, p) in enumerate(zip(due, who, pick))]
        return pool

    def prepare(self, k, v, q) -> None:
        from repro.core import ServingFrontEnd

        t = self.traffic
        sess = self.session(BACKEND)
        self.fn = sess.load(self.kernel.program(self.attn)).function()
        self.resident(sess, k, v)
        self.front = ServingFrontEnd(sess, max_inflight=int(t["max_inflight"]))
        self.names = []
        for x in t["tenants"]:
            self.front.tenant(x["name"], weight=float(x["weight"]),
                              max_inflight=int(t["quota_per_tenant"]))
            self.names.append(x["name"])
        d_out = self.grid * int(self.attn["head_dim"])
        self.qbufs = [sess.alloc(q[e["q"]].size).copy_from_host(q[e["q"]])
                      for e in self.events]
        self.obufs = [sess.alloc(d_out) for _ in self.events]
        self.warm_bufs = (sess.alloc(q[0].size).copy_from_host(q[0]),
                          sess.alloc(d_out))
        self.inflight: List[int] = []

    def _submit(self, tenant: str, qb, ob, s: int):
        kb, vb = self.kv[s]
        with span("submit"):
            return self.front.submit(tenant, self.fn, self.grid, self.block,
                                     self.kernel.args(self.attn, qb, kb, vb,
                                                      ob))

    def warm(self) -> None:
        self._submit(self.names[0], *self.warm_bufs, 0)
        with span("pump"):
            self.front.drain()
        self.warm_bufs[1].copy_to_host()

    def window(self, seconds: float):
        from repro.core.serving import QuotaExceeded

        grace = float(self.traffic["grace_s"])
        #: [seconds into the window, ms] of every pump of 50 ms or more
        self.long_pumps: List[List[float]] = []
        recs = [{"kind": "request", "seq": e["seq"], "q": e["q"],
                 "tenant": self.names[e["tenant"]], "due": e["due"],
                 "ok": False} for e in self.events]
        open_: List[Tuple[object, int]] = []
        t0 = time.perf_counter()
        i, n = 0, len(self.events)
        while i < n or open_:
            now = time.perf_counter() - t0
            while i < n and self.events[i]["due"] <= now:
                e, rec = self.events[i], recs[i]
                self.inflight.append(self.front.total_inflight)
                try:
                    ticket = self._submit(rec["tenant"], self.qbufs[i],
                                          self.obufs[i], e["seq"])
                    open_.append((ticket, i))
                except QuotaExceeded:
                    rec["refused"] = True
                rec["submitted"] = time.perf_counter() - t0
                i += 1
            if not open_:
                if i < n:
                    with span("wait_arrival"):
                        time.sleep(max(0.0, min(self.events[i]["due"] - now,
                                                0.01)))
                continue
            if now > seconds + grace:
                break   # what is still open never came: failed
            with span("pump"):
                began = time.perf_counter()
                self.front.pump(1)
                took = time.perf_counter() - began
            if took >= 0.05:
                self.long_pumps.append([began - t0, took * 1e3])
            still = []
            for ticket, j in open_:
                if ticket.record.cancelled:
                    recs[j]["cancelled"] = True      # it will never come
                elif ticket.record.finished:
                    with span("copy_to_host"):
                        o = self.obufs[j].copy_to_host()
                    end = time.perf_counter() - t0
                    rec = recs[j]
                    rec.update(end=end, ok=True,
                               latency_ms=(end - rec["due"]) * 1e3)
                    self.bytes_in_use.append(device_bytes_in_use())
                    self.answers.append((rec, self.shape_o(o)))
                else:
                    still.append((ticket, j))
            open_ = still
        return recs, {"t0": 0.0, "t1": time.perf_counter() - t0}


class MigratePath(Path):
    """Closed loop: start on the source backend, pause at a key-tile
    barrier, migrate, finish on the target backend."""

    def prepare(self, k, v, q) -> None:
        t = self.traffic
        self.q = q
        prog = self.kernel.program(self.attn)
        self.src = self.session(t["source_backend"])
        self.dst = self.session(t["target_backend"])
        self.fn_src = self.src.load(prog).function()
        self.fn_dst = self.dst.load(prog).function()
        self.resident(self.src, k, v)
        self.qb = self.src.alloc(q[0].size)
        self.ob = self.src.alloc(self.grid * int(self.attn["head_dim"]))
        # the pause barriers: every inner tile barrier once, in an order
        # drawn from the seed, so every seed pauses at the same set
        nt = self.kernel.ntiles(self.attn)
        self.tiles = self.rng.permutation(np.arange(1, nt))
        self.migrations: List[Dict[str, float]] = []

    def _one(self, i: int, s: int, tile: int):
        from repro.core import migrate

        kb, vb = self.kv[s]
        with span("launch"):
            self.qb.copy_from_host(self.q[i % len(self.q)])
            rec = self.fn_src.launch_async(self.grid, self.block,
                                           self.kernel.args(self.attn,
                                                            self.qb, kb, vb,
                                                            self.ob))
            if rec.advance(max_segments=self.kernel.after_tile(tile)):
                raise RuntimeError("launch finished before its pause")
            block_on(rec.engine)
        paused = time.perf_counter()
        with span("migrate"):
            new = migrate(rec, self.src, self.dst, self.fn_dst)
        with span("first_segment"):
            new.advance(max_segments=1)
            block_on(new.engine)
        first = time.perf_counter()
        with span("finish"):
            new.wait()
        with span("copy_to_host"):
            o = new.buffer("O").copy_to_host()
        # the target's buffers were made by the restore: release them, so
        # every migration lands on fresh buffers as on a fresh device
        for db in new.bindings.values():
            db.free()
        lm = dict(self.dst.stats["last_migration"])
        lm["downtime_ms"] = (first - paused) * 1e3
        return o, lm, paused, first

    def warm(self) -> None:
        self._one(0, 0, int(self.tiles[0]))

    def window(self, seconds: float):
        recs = []
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            s, tile = i % self.n_seq, int(self.tiles[i % len(self.tiles)])
            start = time.perf_counter()
            o, lm, paused, first = self._one(i + 1, s, tile)
            end = time.perf_counter()
            self.bytes_in_use.append(device_bytes_in_use())
            rec = {"kind": "migration", "seq": s, "q": (i + 1) % len(self.q),
                   "tile": tile, "start": start - t0, "end": end - t0,
                   "paused": paused - t0, "first": first - t0, "ok": True,
                   **lm}
            recs.append(rec)
            self.answers.append((rec, self.shape_o(o)))
            i += 1
        return recs, {"t0": 0.0, "t1": time.perf_counter() - t0}


PATHS = {"launch": LaunchPath, "serve": ServePath, "migrate": MigratePath}


def path_for(config: Dict, traffic: Dict, kernel, seed: int) -> Path:
    kind = traffic.get("path")
    if kind not in PATHS:
        raise ValueError(f"traffic path {kind!r} is not one of "
                         f"{sorted(PATHS)}")
    return PATHS[kind](config, traffic, kernel, seed)


def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it."""
    if not values:
        return None
    xs = sorted(values)
    rank = max(1, int(np.ceil(q / 100.0 * len(xs))))
    return float(xs[rank - 1])
