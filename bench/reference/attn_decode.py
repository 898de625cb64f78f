"""Plain reference for one decode step of one attention layer, the inputs
it is fed, and the control that the comparison must reject.

Nothing here imports the program under test.  The inputs are drawn from
the run's seed with ``jax.random`` on the device, so the harness and the
reference draw the same numbers independently: the reference regenerates
a sequence's keys and values from the seed and never reads a buffer the
program holds.

Shapes: ``q`` is ``(H, D)``, ``k`` and ``v`` are ``(H, S, D)``; every query
head reads its own key/value rows (no grouped KV heads).

:func:`draw`, :func:`check` and :func:`work` are the reference interface
that ``bench/harness.py`` describes; ``attn`` is the configuration's
shape block.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Dict, List

import numpy as np

import work as work_counts

# sub-streams of the run's key: keys, values, queries
_K, _V, _Q = 1, 2, 3


def root_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (a run's seed need not fit
    32)."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, stream: int, index, shape):
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(jax.random.fold_in(key, stream), index)
    return jax.random.normal(k, shape, jnp.float32)


@lru_cache(maxsize=None)
def _tensor_generator(H: int, S: int, D: int):
    """One jitted program that draws one ``(H, S, D)`` tensor: the keys
    or the values (``stream``) of one resident sequence."""
    import jax

    return jax.jit(lambda key, stream, s: _normal(key, stream, s, (H, S, D)))


def inputs(seed: int, H: int, S: int, D: int, n_seq: int, n_q: int):
    """Every input of a run, drawn on the device and returned on the
    host: keys and values ``(n_seq, H, S, D)`` and queries ``(n_q, H, D)``,
    float32.  One compiled program draws the tensors one at a time, so
    the draw never holds more of the device than one sequence's keys:
    less than a launch of the program holds, so the run's
    ``memory_peak_bytes`` is the program's."""
    draw = _tensor_generator(H, S, D)
    key = root_key(seed)
    k = np.empty((n_seq, H, S, D), np.float32)
    v = np.empty((n_seq, H, S, D), np.float32)
    for s in range(n_seq):
        k[s] = draw(key, _K, s)
        v[s] = draw(key, _V, s)
    return k, v, np.stack(queries(seed, range(n_q), H, D))


def draw(seed: int, attn: Dict, n_units: int, n_events: int) -> Dict:
    """The run's inputs by role: each resident sequence's ``K`` and ``V``
    and each event's ``Q``, as :func:`inputs` draws them."""
    k, v, q = inputs(seed, int(attn["heads"]), int(attn["keys"]),
                     int(attn["head_dim"]), n_units, n_events)
    return {"unit": {"K": k, "V": v}, "shared": {}, "event": {"Q": q}}


def sequence(seed: int, s: int, H: int, S: int, D: int):
    """Keys and values of resident sequence ``s`` alone, the same numbers
    :func:`inputs` draws for it, on the host."""
    draw = _tensor_generator(H, S, D)
    key = root_key(seed)
    return np.asarray(draw(key, _K, s)), np.asarray(draw(key, _V, s))


def queries(seed: int, idx, H: int, D: int):
    """Queries ``idx`` of a run, the same numbers :func:`inputs` draws
    for them, on the host."""
    import jax
    import jax.numpy as jnp

    q = _query_generator(H, D)(root_key(seed), jnp.asarray(list(idx)))
    return list(np.asarray(jax.device_get(q)))


@lru_cache(maxsize=None)
def _query_generator(H: int, D: int):
    import jax

    return jax.jit(jax.vmap(lambda key, i: _normal(key, _Q, i, (H, D)),
                            in_axes=(None, 0)))


def decode(q, k, v, scale: float) -> np.ndarray:
    """softmax(q k^T * scale) v per head, in float64 on the host."""
    q = np.asarray(q, np.float64)
    k, v = np.asarray(k, np.float64), np.asarray(v, np.float64)
    s = np.matmul(k, q[:, :, None])[:, :, 0] * scale
    p = np.exp(s - s.max(axis=1, keepdims=True))
    o = np.matmul(p[:, None, :], v)[:, 0, :]
    return o / p.sum(axis=1, keepdims=True)


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def split_high(x) -> tuple:
    """An operand of a ``high``-precision contraction: its bfloat16 high
    part and the bfloat16 rounding of the rest, as float64.  Splitting a
    sequence's keys and values once serves every query against it."""
    if isinstance(x, tuple):
        return x
    hi = _bf16(x)
    return hi, _bf16(np.asarray(x, np.float64) - hi)


def _matmul_high(a, b) -> np.ndarray:
    """A float32 contraction at JAX's ``high`` precision, as the TPU's
    matrix unit computes it: each operand split into a bfloat16 high and
    low part, the low-by-low product dropped, sums in float32 (here in
    float64 and then rounded, which only makes the control more
    accurate)."""
    (a_hi, a_lo), (b_hi, b_lo) = split_high(a), split_high(b)
    return (np.matmul(a_hi, b_hi) + np.matmul(a_hi, b_lo)
            + np.matmul(a_lo, b_hi)).astype(np.float32)


def decode_control(q, k, v, scale: float) -> np.ndarray:
    """The reference computed one precision step below what the
    configuration states: float32 with both contractions at ``high``
    (three bfloat16 passes) instead of full float32.  ``k`` and ``v`` may
    come split by :func:`split_high`."""
    k_hi, k_lo = split_high(k)
    v_hi, v_lo = split_high(v)
    s = _matmul_high((k_hi, k_lo), np.asarray(q, np.float32)[:, :, None])
    s = s[:, :, 0] * np.float32(scale)
    p = np.exp(s - s.max(axis=1, keepdims=True)).astype(np.float32)
    o = _matmul_high(p[:, None, :], (v_hi, v_lo))[:, 0, :]
    return o / p.sum(axis=1, keepdims=True, dtype=np.float32)


def rel_err(o, ref) -> float:
    """Largest elementwise gap between an output and the reference, as a
    share of the reference's largest magnitude."""
    ref = np.asarray(ref, np.float64)
    gap = np.abs(np.asarray(o, np.float64).reshape(ref.shape) - ref)
    if not np.all(np.isfinite(gap)):
        return float("inf")
    return float(gap.max() / max(np.abs(ref).max(), 1e-30))


def check(answers, attn: Dict, seed: int, control: bool = False
          ) -> Dict[str, object]:
    """Each answer's O against the reference's, grouped by resident
    sequence (``seq``) so each sequence's keys and values are drawn once;
    ``q`` names the answer's query.  With ``control`` the reference's
    lower-precision twin is compared in the program's place, over the
    same queries."""
    H, D = int(attn["heads"]), int(attn["head_dim"])
    S, scale = int(attn["keys"]), float(attn["scale"])
    by_seq: Dict[int, List] = {}
    for rec, o in answers:
        by_seq.setdefault(rec["seq"], []).append((rec, o))
    worst, n = 0.0, 0
    for s, items in sorted(by_seq.items()):
        k, v = (np.asarray(x, np.float64) for x in sequence(seed, s, H, S, D))
        qs = queries(seed, [rec["q"] for rec, _ in items], H, D)
        if control:
            kc, vc = split_high(k), split_high(v)
        for (rec, o), q in zip(items, qs):
            want = decode(q, k, v, scale)
            got = decode_control(q, kc, vc, scale) if control else o
            worst = max(worst, rel_err(got, want))
            n += 1
    return {"o_err_max": worst, "compared": n}


def work(attn: Dict) -> Dict[str, int]:
    """The algorithm's work for one launch: ``work.attn_decode``."""
    return work_counts.attn_decode(int(attn["heads"]), int(attn["keys"]),
                                   int(attn["head_dim"]))
