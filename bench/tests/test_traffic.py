"""Each traffic mix drives the program end to end at the tiny size on the
CPU, and its outputs pass the comparison with the reference."""
import json

import numpy as np
import pytest

from conftest import BENCH, TINY, run_tiny

CELLS = [("glm4-9b.decode-8k", "decode_ms", {}),
         ("granite-3b-a800m.serve-2k", "served_p90_ms", {"rate_per_s": 25.0}),
         ("glm4-9b.migrate-8k", "migrate_ms", {})]


@pytest.mark.parametrize("workload,metric,traffic", CELLS)
def test_mix_runs_and_is_correct(monkeypatch, bench_spec, workload, metric,
                                 traffic):
    res = run_tiny(monkeypatch, bench_spec, workload, traffic=traffic)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {metric, "setup_s"}
    assert res["metrics"][metric]["value"] > 0
    assert list(res)[-1] == "compared"
    assert res["compared"]["o_err_max"]["value"] <= \
        res["compared"]["o_err_max"]["limit"]


def _serve_path(seed):
    import harness
    import loadgen

    traffic = json.loads((BENCH / "traffic" / "serve-open.json").read_text())
    kernel = harness.load_module(BENCH / "kernels" / "attn_decode.py", "k")
    config = dict(TINY, resident_sequences=3 * traffic["sequences_per_tenant"])
    path = loadgen.path_for(config, traffic, kernel, seed)
    path.plan(400.0)
    return path.events, traffic["rate_per_s"]


def test_serve_trace_is_the_same_for_every_seed():
    """The arrival times and tenants are the mix's; the seed draws only
    which of its tenant's sequences a request reads."""
    (a, rate), (b, _) = _serve_path(1), _serve_path(2 ** 40 + 7)
    assert [(e["due"], e["tenant"]) for e in a] == \
        [(e["due"], e["tenant"]) for e in b]
    assert [e["seq"] for e in a] != [e["seq"] for e in b]
    gaps = np.diff([0.0] + [e["due"] for e in a])
    assert abs(len(a) / (rate * 400.0) - 1) < 0.3
    assert 1.4 < gaps.std() / gaps.mean() < 2.6   # bursty: CV about 2


def test_inputs_of_one_sequence_match_the_batch():
    """The reference redraws a sequence alone; it must be the numbers the
    run's inputs hold."""
    import harness

    ref = harness.load_module(BENCH / "reference" / "attn_decode.py", "r")
    k, v, q = ref.inputs(2 ** 33 + 5, 2, 64, 16, 3, 4)
    k1, v1 = ref.sequence(2 ** 33 + 5, 2, 2, 64, 16)
    np.testing.assert_array_equal(k[2], k1)
    np.testing.assert_array_equal(v[2], v1)
    np.testing.assert_array_equal(
        q[3], ref.queries(2 ** 33 + 5, [3], 2, 16)[0])
