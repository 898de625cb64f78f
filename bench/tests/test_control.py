"""The control: the plain reference put in the program's place and
computed one precision step below the configuration's float32 (both
contractions at ``high``, three bfloat16 passes).  Its answers, passed
through the harness's own comparison and verdict, make ``correct`` false,
while the same reference in full float32 makes it true.  At each
configuration's full width and context (every head, every key), on three
seeds."""
import json

import numpy as np
import pytest

from conftest import BENCH

QUERIES = [1, 2]


def _float32(q, k, v, scale):
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    s = np.matmul(k, q[:, :, None])[:, :, 0] * np.float32(scale)
    p = np.exp(s - s.max(axis=1, keepdims=True))
    return np.matmul(p[:, None, :], v)[:, 0, :] / p.sum(axis=1,
                                                        keepdims=True)


@pytest.mark.parametrize("name", ["glm4-9b-attn", "granite-3b-a800m-attn"])
@pytest.mark.parametrize("seed", [11, 2 ** 35 + 3, 4_000_000_001])
def test_control_fails_and_float32_passes(name, seed):
    import harness

    ref = harness.load_module(BENCH / "reference" / "attn_decode.py", "rc")
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    attn = config["attn"]
    H, S, D = attn["heads"], attn["keys"], attn["head_dim"]
    k, v = ref.sequence(seed, 0, H, S, D)
    recs = [{"seq": 0, "q": i} for i in QUERIES]
    qs = ref.queries(seed, QUERIES, H, D)
    kc, vc = ref.split_high(k), ref.split_high(v)
    control = [(r, ref.decode_control(q, kc, vc, attn["scale"]))
               for r, q in zip(recs, qs)]
    exact = [(r, _float32(q, k, v, attn["scale"])) for r, q in zip(recs, qs)]
    del kc, vc

    readings = {}
    for label, answers in (("control", control), ("float32", exact)):
        check = harness.compare(answers, attn, ref, seed)
        correct, compared = harness.verdict(check, 0, len(recs),
                                            config["limits"])
        readings[label] = (correct, compared["o_err_max"])
    assert readings["control"][0] is False, readings
    assert readings["float32"][0] is True, readings
