"""The one traffic generator.  A mix is a data file under ``traffic/``
whose ``path`` names how its events reach the program, and whose other
entries are that path's parameters:

* ``launch`` — a closed loop of blocking ``Function.launch`` calls on one
  ``HetSession``, each with a new event's inputs against the next
  resident unit (round robin), and its outputs copied to the host;
* ``serve`` — an open loop through ``ServingFrontEnd``: requests arrive
  on a fixed trace of Gamma-distributed gaps (rate, coefficient of
  variation), each from a tenant drawn by popularity, and each is one
  launch against one of its tenant's resident units.  Latency runs from
  the request's *due* time to its outputs on the host;
* ``migrate`` — a closed loop of launches that start on one backend,
  rest at one of the kernel's rest points, are moved with ``migrate()``
  to a session on another backend and finish there.  Downtime runs from
  the pause to the target's first segment done.

A path not in :data:`PATHS` is the ``PATH`` class of ``paths/<path>.py``
(:func:`path_for`).  Every path warms up exactly the calls its window
makes, records one dict per event of the window, and keeps each event's
answer (``kernel.answer``) for the comparison with the reference.  What a
kernel's buffers are and how its launch is built is the kernel module's
(``bench/harness.py`` lists its interface).  Spans named ``bench.<step>``
mark the host's steps in a profiler trace.
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
    distinct events the run draws, ``prepare`` puts the drawn inputs into
    the program's buffers, ``warm`` makes one event of each kind the
    window makes, and ``window`` runs for the given seconds."""

    def __init__(self, config: Dict, traffic: Dict, kernel, seed: int):
        self.traffic, self.kernel = traffic, kernel
        self.kernel_name = config["kernel"]
        self.shape = kernel.shape(config)
        self.n_units = int(kernel.units(config))
        self.grid, self.block = kernel.geometry(self.shape)
        self.outputs = kernel.outputs(self.shape)
        self.rng = np.random.default_rng(seed)
        self.answers: List[Tuple[Dict, object]] = []
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

    def resident(self, sess, inputs: Dict) -> None:
        """Copy every resident unit's inputs and the shared ones into
        buffers of ``sess``."""
        self.unit_bufs = [
            {name: sess.alloc(a[u].size, a.dtype).copy_from_host(a[u])
             for name, a in inputs["unit"].items()}
            for u in range(self.n_units)]
        self.shared_bufs = {
            name: sess.alloc(a.size, a.dtype).copy_from_host(a)
            for name, a in inputs["shared"].items()}

    def event_buffers(self, sess, event: Dict) -> Dict[str, object]:
        """Empty buffers for one event's inputs, sized by drawn event 0."""
        return {name: sess.alloc(a[0].size, a.dtype)
                for name, a in event.items()}

    def output_buffers(self, sess) -> Dict[str, object]:
        return {name: sess.alloc(size, dtype)
                for name, (size, dtype) in self.outputs.items()}

    def args(self, u: int, ins: Dict, outs: Dict) -> Dict[str, object]:
        """The launch's arguments for an event on resident unit ``u``."""
        return self.kernel.args(self.shape, {**self.shared_bufs,
                                             **self.unit_bufs[u], **ins,
                                             **outs})

    def release(self) -> None:
        for sess in self.sessions.values():
            sess.synchronize()
        self.sessions.clear()
        self.unit_bufs, self.shared_bufs = [], {}

    def segments(self) -> int:
        """Segment kernels executed so far by the pallas sessions (the
        ``block_stats()`` tiled and scalar counts)."""
        return sum(int(bs.get("tiled", 0)) + int(bs.get("scalar", 0))
                   for bs in (sess.block_stats()
                              for sess in self.sessions.values()))


def fill(bufs: Dict[str, object], event: Dict, j: int) -> Dict[str, object]:
    """Copy drawn event ``j``'s inputs into ``bufs``."""
    for name, b in bufs.items():
        b.copy_from_host(event[name][j])
    return bufs


def to_host(bufs: Dict[str, object]) -> Dict[str, np.ndarray]:
    return {name: b.copy_to_host() for name, b in bufs.items()}


class LaunchPath(Path):
    """Closed loop, one stream: launch, wait, outputs to the host."""

    def prepare(self, inputs: Dict) -> None:
        sess = self.session(BACKEND)
        self.fn = sess.load(self.kernel.program(self.shape)).function()
        self.bind(sess, inputs)

    def bind(self, sess, inputs: Dict) -> None:
        """The resident inputs into ``sess``, and one set of event buffers
        that every launch fills anew."""
        self.event_in = inputs["event"]
        self.n_drawn = len(next(iter(self.event_in.values())))
        self.resident(sess, inputs)
        self.ins = self.event_buffers(sess, self.event_in)
        self.outs = self.output_buffers(sess)

    def _one(self, i: int, u: int) -> Dict[str, np.ndarray]:
        with span("launch"):
            fill(self.ins, self.event_in, i % self.n_drawn)
            self.fn.launch(self.grid, self.block,
                           self.args(u, self.ins, self.outs))
        with span("copy_to_host"):
            return to_host(self.outs)

    def warm(self) -> None:
        self._one(0, 0)

    def window(self, seconds: float):
        recs = []
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            u = i % self.n_units
            start = time.perf_counter()
            outs = self._one(i + 1, u)
            end = time.perf_counter()
            self.bytes_in_use.append(device_bytes_in_use())
            rec = {"kind": "launch", "seq": u, "q": (i + 1) % self.n_drawn,
                   "start": start - t0, "end": end - t0, "ok": True}
            recs.append(rec)
            self.answers.append((rec, self.kernel.answer(self.shape, outs)))
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
        # the same work; the run seed draws the inputs and which of its
        # tenant's units each request reads
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
        if per * len(tenants) != self.n_units:
            raise ValueError(f"{len(tenants)} tenants x {per} units "
                             f"!= {self.n_units} resident units")
        pick = self.rng.integers(0, per, size=len(due))
        pool = int(t["queries"])
        self.events = [{"due": d, "tenant": int(w), "seq": int(w) * per
                        + int(p), "q": (i + 1) % pool}
                       for i, (d, w, p) in enumerate(zip(due, who, pick))]
        return pool

    def prepare(self, inputs: Dict) -> None:
        from repro.core import ServingFrontEnd

        t = self.traffic
        sess = self.session(BACKEND)
        self.fn = sess.load(self.kernel.program(self.shape)).function()
        self.resident(sess, inputs)
        self.front = ServingFrontEnd(sess, max_inflight=int(t["max_inflight"]))
        self.names = []
        for x in t["tenants"]:
            self.front.tenant(x["name"], weight=float(x["weight"]),
                              max_inflight=int(t["quota_per_tenant"]))
            self.names.append(x["name"])
        event = inputs["event"]
        self.in_bufs = [fill(self.event_buffers(sess, event), event, e["q"])
                        for e in self.events]
        self.out_bufs = [self.output_buffers(sess) for _ in self.events]
        self.warm_bufs = (fill(self.event_buffers(sess, event), event, 0),
                          self.output_buffers(sess))
        self.inflight: List[int] = []

    def _submit(self, tenant: str, ins: Dict, outs: Dict, u: int):
        with span("submit"):
            return self.front.submit(tenant, self.fn, self.grid, self.block,
                                     self.args(u, ins, outs))

    def warm(self) -> None:
        self._submit(self.names[0], *self.warm_bufs, 0)
        with span("pump"):
            self.front.drain()
        to_host(self.warm_bufs[1])

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
                    ticket = self._submit(rec["tenant"], self.in_bufs[i],
                                          self.out_bufs[i], e["seq"])
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
                        outs = to_host(self.out_bufs[j])
                    end = time.perf_counter() - t0
                    rec = recs[j]
                    rec.update(end=end, ok=True,
                               latency_ms=(end - rec["due"]) * 1e3)
                    self.bytes_in_use.append(device_bytes_in_use())
                    self.answers.append(
                        (rec, self.kernel.answer(self.shape, outs)))
                else:
                    still.append((ticket, j))
            open_ = still
        return recs, {"t0": 0.0, "t1": time.perf_counter() - t0}


class MigratePath(LaunchPath):
    """Closed loop: start on the source backend, pause at one of the
    kernel's rest points, migrate, finish on the target backend."""

    def plan(self, seconds: float) -> int:
        rests = getattr(self.kernel, "rests", None)
        rests = rests(self.shape) if rests is not None else []
        if not rests:
            raise ValueError(
                f"kernel {self.kernel_name!r} has no rest points: the "
                f"migrate path pauses a launch at a barrier the kernel "
                f"names in rests(shape)")
        # every rest point once, in an order drawn from the seed, so every
        # seed pauses at the same set
        self.rests = self.rng.permutation(np.asarray(rests))
        return super().plan(seconds)

    def prepare(self, inputs: Dict) -> None:
        t = self.traffic
        prog = self.kernel.program(self.shape)
        self.src = self.session(t["source_backend"])
        self.dst = self.session(t["target_backend"])
        self.fn_src = self.src.load(prog).function()
        self.fn_dst = self.dst.load(prog).function()
        self.bind(self.src, inputs)

    def _one(self, i: int, u: int, rest: int):
        from repro.core import migrate

        with span("launch"):
            fill(self.ins, self.event_in, i % self.n_drawn)
            rec = self.fn_src.launch_async(self.grid, self.block,
                                           self.args(u, self.ins, self.outs))
            if rec.advance(max_segments=rest):
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
            outs = {name: new.buffer(name).copy_to_host()
                    for name in self.outputs}
        # the target's buffers were made by the restore: release them, so
        # every migration lands on fresh buffers as on a fresh device
        for db in new.bindings.values():
            db.free()
        lm = dict(self.dst.stats["last_migration"])
        lm["downtime_ms"] = (first - paused) * 1e3
        return outs, lm, paused, first

    def warm(self) -> None:
        self._one(0, 0, int(self.rests[0]))

    def window(self, seconds: float):
        recs = []
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            u, rest = i % self.n_units, int(self.rests[i % len(self.rests)])
            start = time.perf_counter()
            outs, lm, paused, first = self._one(i + 1, u, rest)
            end = time.perf_counter()
            self.bytes_in_use.append(device_bytes_in_use())
            rec = {"kind": "migration", "seq": u, "q": (i + 1) % self.n_drawn,
                   "rest": rest, "start": start - t0, "end": end - t0,
                   "paused": paused - t0, "first": first - t0, "ok": True,
                   **lm}
            recs.append(rec)
            self.answers.append((rec, self.kernel.answer(self.shape, outs)))
            i += 1
        return recs, {"t0": 0.0, "t1": time.perf_counter() - t0}


PATHS = {"launch": LaunchPath, "serve": ServePath, "migrate": MigratePath}


def path_for(config: Dict, traffic: Dict, kernel, seed: int) -> Path:
    """The mix's path: one of :data:`PATHS`, or the ``PATH`` class of
    ``paths/<path>.py`` beside this module."""
    kind = traffic.get("path")
    if kind in PATHS:
        cls = PATHS[kind]
    else:
        from harness import BENCH, load_module

        try:
            cls = load_module(BENCH / "paths" / f"{kind}.py",
                              f"bench_path_{kind}").PATH
        except FileNotFoundError:
            raise ValueError(f"traffic path {kind!r} is neither one of "
                             f"{sorted(PATHS)} nor a file paths/{kind}.py"
                             ) from None
    return cls(config, traffic, kernel, seed)


def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it."""
    if not values:
        return None
    xs = sorted(values)
    rank = max(1, int(np.ceil(q / 100.0 * len(xs))))
    return float(xs[rank - 1])
