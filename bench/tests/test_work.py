"""The algorithm's work and the roofline bound, on hand-computed shapes."""
import json

import pytest

import work
from conftest import BENCH


def test_attn_decode_work_by_hand():
    # H=1, S=128, D=2: q.k and p.v are 2*128*2 FLOPs each, the softmax
    # 5*128, the division 2; K and V 2*128*2 floats, Q and O 2 each
    assert work.attn_decode(1, 128, 2) == {"flops": 1024 + 640 + 2,
                                          "bytes": 4 * (512 + 4)}


@pytest.mark.parametrize("name,bytes_,bound_us", [
    ("glm4-9b-attn", 268_468_224, 327.80),
    ("granite-3b-a800m-attn", 25_178_112, 30.74),
])
def test_cell_bounds_are_memory_bound(name, bytes_, bound_us):
    attn = json.loads((BENCH / "configs" / f"{name}.json").read_text())["attn"]
    w = work.attn_decode(attn["heads"], attn["keys"], attn["head_dim"])
    assert w["bytes"] == bytes_
    peak = work.peaks("TPU v5 lite")
    assert w["flops"] / peak["flops_per_s"] < w["bytes"] / peak["hbm_bytes_per_s"]
    assert work.bound_s(w, peak) * 1e6 == pytest.approx(bound_us, abs=0.01)


@pytest.mark.parametrize("name,bytes_", [
    ("glm4-9b-attn", 268_468_224),
    ("granite-3b-a800m-attn", 25_178_112),
])
def test_harness_finds_the_count_through_the_kernels_reference(name,
                                                               bytes_):
    """The count ``run_cell`` bounds a launch by: the configuration's
    reference module's ``work`` of the kernel module's shape block."""
    import harness

    config = harness.load_json("configs", name)
    kernel, ref = harness.kernel_modules(config)
    attn = kernel.shape(config)
    w = ref.work(attn)
    assert w == work.attn_decode(attn["heads"], attn["keys"],
                                 attn["head_dim"])
    assert w["bytes"] == bytes_


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")
