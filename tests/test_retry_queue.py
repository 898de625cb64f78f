"""Property tests for the fleet's two-tier retry queue.

The queue is the self-healing invariant's source of truth, so its
contract is tested as a *property* over arbitrary event interleavings —
enqueue, dispatch, worker crash (requeue), graceful reassign, coordinator
crash + restart (reload from disk, demote stale inflight), ack — rather
than as a handful of happy paths:

* **nothing is ever lost**: every enqueued launch remains reachable
  (pending / inflight / acked) through any interleaving, including a
  coordinator restart over the same directory;
* **nothing is delivered twice**: ``ack`` returns True exactly once per
  launch, no matter how many replays raced it;
* **durability round-trips bit-exactly**: ndarray args come back from the
  JSON tier with identical bytes, dtype, and shape.

Runs in two modes, like the fuzz harness (``fuzz_corpus.py``): a fixed-seed
random corpus (no third-party dependency, deterministic) always runs;
when hypothesis is installed the same simulation becomes a shrinking
property test (``derandomize=True`` keeps CI reproducible).
"""
import numpy as np
import pytest

from repro.core.fleet import RetryQueue, _decode_value, _encode_value

try:
    import hypothesis
    from hypothesis import strategies as st
except ImportError:  # container without hypothesis: corpus still runs
    hypothesis = None


# ---------------------------------------------------------------------------
# model-based simulation: drive a real RetryQueue (durable tier on disk)
# against a trivial in-memory reference model, then check the invariants
# ---------------------------------------------------------------------------

#: event alphabet: (op, target-index) — target resolved modulo the
#: relevant launch population at replay time
OPS = ("enqueue", "dispatch", "crash_worker", "reassign", "ack",
       "restart", "duplicate_ack")


def run_simulation(events, qdir):
    """Apply ``events`` to a durable RetryQueue; return the queue plus
    the reference model ``{launch_id: acked?}`` and the ack log."""
    q = RetryQueue(qdir)
    model = {}          # launch_id -> acked (reference truth)
    deliveries = []     # launch_ids whose ack() returned True
    n = 0
    for op, tgt in events:
        if op == "enqueue":
            lid = f"L{n:04d}"
            n += 1
            q.enqueue(lid, "dyn_matmul", 4, 16,
                      {"A": np.arange(4, dtype=np.float32), "K": 32},
                      ("A",))
            model[lid] = False
        elif op == "dispatch":
            pending = q.pending()
            if pending:
                q.mark_inflight(pending[tgt % len(pending)],
                                worker=tgt % 3)
        elif op == "crash_worker":
            # a worker died: everything it held goes back to pending
            for lid in q.inflight(worker=tgt % 3):
                q.requeue(lid)
        elif op == "reassign":
            inflight = q.inflight()
            if inflight:
                q.reassign(inflight[tgt % len(inflight)],
                           worker=(tgt + 1) % 3)
        elif op == "ack":
            inflight = q.inflight()
            if inflight:
                lid = inflight[tgt % len(inflight)]
                if q.ack(lid):
                    deliveries.append(lid)
                model[lid] = True
        elif op == "duplicate_ack":
            # a raced result for an already-acked launch: must be refused
            acked = [lid for lid, done in model.items() if done]
            if acked:
                lid = acked[tgt % len(acked)]
                assert q.ack(lid) is False
                assert q.is_acked(lid)
        elif op == "restart":
            # the coordinator dies: a fresh queue over the same dir
            # must reload every record; stale inflight demotes to pending
            q = RetryQueue(qdir)
            q.recover()
    return q, model, deliveries


def check_invariants(q, model, deliveries, qdir):
    # 1. nothing lost: every enqueued launch is still in the queue,
    #    and unacked ones are reachable for (re)dispatch
    assert len(q) == len(model)
    for lid, acked in model.items():
        rec = q.get(lid)
        assert rec["state"] == ("acked" if acked else rec["state"])
        if not acked:
            assert rec["state"] in ("pending", "inflight")
    assert sorted(q.unacked()) == sorted(
        lid for lid, acked in model.items() if not acked)
    # 2. exactly-once delivery: one True ack per acked launch, ever
    assert sorted(deliveries) == sorted(
        lid for lid, acked in model.items() if acked)
    assert len(set(deliveries)) == len(deliveries)
    # 3. a final restart loses nothing and changes no ack state
    q2 = RetryQueue(qdir)
    demoted = q2.recover()
    assert len(q2) == len(model)
    for lid, acked in model.items():
        assert q2.is_acked(lid) == acked
        assert q2.get(lid)["state"] == \
            ("acked" if acked else "pending")
    assert all(not model[lid] for lid in demoted)
    # 4. durable args round-trip bit-exactly
    for lid in model:
        args = q2.decode_args(lid)
        ref = np.arange(4, dtype=np.float32)
        assert args["A"].dtype == ref.dtype
        assert args["A"].tobytes() == ref.tobytes()
        assert args["K"] == 32
    # 5. enqueue order survives restarts
    order = [q2.get(lid)["seq"] for lid in sorted(model)]
    assert order == sorted(order)


def _random_events(rng, length):
    # enqueue-weighted so interleavings act on a real population
    weights = {"enqueue": 4, "dispatch": 4, "crash_worker": 2,
               "reassign": 1, "ack": 3, "restart": 1, "duplicate_ack": 1}
    ops = [op for op, w in weights.items() for _ in range(w)]
    return [(ops[rng.integers(len(ops))], int(rng.integers(64)))
            for _ in range(length)]


# -- fixed-seed corpus (always runs; deterministic) -------------------------

@pytest.mark.parametrize("seed", range(20))
def test_retry_queue_interleavings_corpus(tmp_path, seed):
    rng = np.random.default_rng(1000 + seed)
    events = _random_events(rng, int(rng.integers(10, 60)))
    qdir = tmp_path / "q"
    q, model, deliveries = run_simulation(events, qdir)
    check_invariants(q, model, deliveries, qdir)


# -- hypothesis mode (shrinking; CI installs it) ----------------------------

if hypothesis is not None:
    @hypothesis.settings(max_examples=60, deadline=None,
                         derandomize=True)
    @hypothesis.given(events=st.lists(
        st.tuples(st.sampled_from(OPS), st.integers(0, 63)),
        min_size=1, max_size=60))
    def test_retry_queue_interleavings_hypothesis(tmp_path_factory, events):
        qdir = tmp_path_factory.mktemp("rq") / "q"
        q, model, deliveries = run_simulation(events, qdir)
        check_invariants(q, model, deliveries, qdir)


# ---------------------------------------------------------------------------
# directed unit cases for the sharp edges
# ---------------------------------------------------------------------------

def test_ack_consumes_exactly_once(tmp_path):
    q = RetryQueue(tmp_path / "q")
    q.enqueue("L1", "k", 1, 1, {}, ())
    q.mark_inflight("L1", worker=0)
    assert q.ack("L1") is True
    assert q.ack("L1") is False          # the double-ack guard
    assert q.requeue("L1") is False      # a late death cannot resurrect
    assert q.is_acked("L1")


def test_mark_inflight_counts_attempts(tmp_path):
    q = RetryQueue(tmp_path / "q")
    q.enqueue("L1", "k", 1, 1, {}, ())
    assert q.mark_inflight("L1", worker=0) == 1
    assert q.requeue("L1") is True
    assert q.mark_inflight("L1", worker=1) == 2
    with pytest.raises(ValueError):
        q.enqueue("L1", "k", 1, 1, {}, ())   # duplicate accept refused
    q.ack("L1")
    with pytest.raises(ValueError):
        q.mark_inflight("L1", worker=0)      # acked is terminal


def test_restart_demotes_stale_inflight(tmp_path):
    q = RetryQueue(tmp_path / "q")
    q.enqueue("L1", "k", 1, 1, {}, ())
    q.enqueue("L2", "k", 1, 1, {}, ())
    q.mark_inflight("L1", worker=0)
    q.ack("L1")
    q.mark_inflight("L2", worker=1)
    q2 = RetryQueue(tmp_path / "q")          # coordinator restart
    assert q2.recover() == ["L2"]            # only the stale inflight
    assert q2.get("L2")["state"] == "pending"
    assert q2.is_acked("L1")


def test_torn_record_is_skipped_not_fatal(tmp_path):
    qdir = tmp_path / "q"
    q = RetryQueue(qdir)
    q.enqueue("L1", "k", 1, 1, {}, ())
    (qdir / "garbage.json").write_text("{not json")
    (qdir / "foreign.json").write_text('{"launch_id": "X"}')  # bad state
    q2 = RetryQueue(qdir)
    assert sorted(r for r in q2.unacked()) == ["L1"]


def test_memory_only_mode_keeps_semantics(tmp_path):
    q = RetryQueue(None)
    q.enqueue("L1", "k", 1, 1, {"x": np.float32(2.5)}, ())
    assert q.mark_inflight("L1", 0) == 1
    assert q.requeue("L1") and q.pending() == ["L1"]
    assert q.stats()["durable"] is False


def test_ndarray_codec_bit_exact():
    for arr in (np.arange(7, dtype=np.float32),
                np.linspace(-1, 1, 12, dtype=np.float64).reshape(3, 4),
                np.array([], dtype=np.int32),
                np.array([[1, 2], [3, 4]], dtype=np.uint8)):
        back = _decode_value(_encode_value(arr))
        assert back.dtype == arr.dtype and back.shape == arr.shape
        assert back.tobytes() == arr.tobytes()
    assert _decode_value(_encode_value(np.float32(1.5))) == np.float32(1.5)
    assert _decode_value(_encode_value(None)) is None
    with pytest.raises(TypeError):
        _encode_value(object())
