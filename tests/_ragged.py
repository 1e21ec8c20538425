"""The dense per-token oracle of the ragged paged-attention tests and the
small mixed batch they share (tests/test_ragged.py,
tests/test_ragged_blocked.py). Not a test file.
"""

import jax
import jax.numpy as jnp
import numpy as np


def dense_oracle(q, kp, vp, pt, q_start, q_len, kv_len,
                  k_scale=None, v_scale=None):
    """Per-token dense attention: gather row pages, causal-mask by the
    token's absolute position, fp32 softmax. Padding tokens -> 0."""
    q, kp, vp = map(lambda a: np.asarray(a, np.float64), (q, kp, vp))
    if k_scale is not None:
        kp = kp * np.asarray(k_scale, np.float64)[..., None]
        vp = vp * np.asarray(v_scale, np.float64)[..., None]
    T, Hq, D = q.shape
    Hkv, ps = kp.shape[1], kp.shape[2]
    g = Hq // Hkv
    out = np.zeros((T, Hq, D))
    for r in range(len(q_start)):
        for j in range(int(q_len[r])):
            t = int(q_start[r]) + j
            vis = int(kv_len[r]) - int(q_len[r]) + j + 1
            pages = np.asarray(pt[r])[: -(-vis // ps)]
            k = kp[pages].transpose(1, 0, 2, 3).reshape(Hkv, -1, D)[:, :vis]
            v = vp[pages].transpose(1, 0, 2, 3).reshape(Hkv, -1, D)[:, :vis]
            qg = q[t].reshape(Hkv, g, D)
            s = np.einsum("hgd,htd->hgt", qg, k) * D ** -0.5
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[t] = np.einsum("hgt,htd->hgd", p, v).reshape(Hq, D)
    return out


def mixed_batch(key, Hq, Hkv, D, ps=8, pages=12, max_pages=4):
    """2 decode rows + 1 inactive row + 2 prefill chunks, one chunk
    straddling a page boundary (ends mid-page after crossing one)."""
    ks = jax.random.split(key, 3)
    T = 16
    q = jax.random.normal(ks[0], (T, Hq, D), jnp.float32)
    kp = jax.random.normal(ks[1], (pages, Hkv, ps, D), jnp.float32)
    vp = jax.random.normal(ks[2], (pages, Hkv, ps, D), jnp.float32)
    pt = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8], [0, 0, 0, 0],
                    [9, 10, 11, 1], [2, 3, 4, 5]], jnp.int32)
    # rows: decode len 11, decode len 24, inactive, 6-tok chunk ending
    # at kv position 21 (straddles the page-2 -> page-3 boundary), 4-tok
    # chunk fully inside page 0 of its table
    q_start = jnp.array([0, 1, 0, 3, 9], jnp.int32)
    q_len = jnp.array([1, 1, 0, 6, 4], jnp.int32)
    kv_len = jnp.array([11, 24, 0, 21, 4], jnp.int32)
    return q, kp, vp, pt, q_start, q_len, kv_len


def unowned(args):
    """Mask of the padding tokens of a ragged batch (owned by no row)."""
    owned = np.zeros(args[0].shape[0], bool)
    for s, l in zip(args[4], args[5]):
        owned[int(s):int(s) + int(l)] = True
    return ~owned
