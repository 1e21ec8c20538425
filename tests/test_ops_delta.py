"""ops/delta.py: the gated delta rule's one-token update and its chunk form
against the token-by-token recurrence, float32 on the CPU.

TOL: all three compute in float32 at the highest matmul precision, so they
differ by summation order and by the chunk form's triangular solve: ~5e-7
on outputs of order 1. 1e-5 leaves room and still fails a state rounded to
bf16 (test_a_state_rounded_to_bf16_is_told_apart: ~1e-3), a decay applied
after the read, a row that starts from another row's state or from zeros
mid-sequence, a block boundary that drops a token.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.ops import delta  # noqa: E402

TOL = 1e-5
L, S, HK, HV, DK, DV = 2, 5, 2, 4, 8, 16


def _operands(T, seed=0):
    """q, k (normalised, q scaled), v, g (a head forgets over 1 to 1000
    tokens), beta for T tokens."""
    rng = np.random.default_rng(seed)
    q, k = rng.normal(size=(2, T, HK, DK))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(T, HV, DV))
    g = -np.exp(rng.uniform(np.log(1e-3), 0.0, size=(T, HV)))
    beta = 1 / (1 + np.exp(-rng.normal(size=(T, HV))))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)]


def _state(seed=1):
    """A leaf that holds garbage in every slot, the scratch slot too."""
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(L, S + 1, HV, DK, DV)), jnp.float32)


def _by_hand(s, q, k, v, g, beta):
    """One row, token after token, in numpy float64: the three lines of
    the module's docstring as written. s [HV, DK, DV]."""
    s, out = np.asarray(s, np.float64), []
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g,
                                                           beta))
    for t in range(q.shape[0]):
        for h in range(HV):
            j = h // (HV // HK)
            s[h] = np.exp(g[t, h]) * s[h]
            s[h] = s[h] + np.outer(k[t, j],
                                   beta[t, h] * (v[t, h] - s[h].T @ k[t, j]))
        out.append(np.stack([s[h].T @ q[t, h // (HV // HK)]
                             for h in range(HV)]))
    return np.stack(out), s


#: (name, q_start, q_len, first position of each row, slot of each row):
#: rows 0 and 1 hold tokens, row 2 none (the scratch slot)
RAGGED = {
    # row 0 continues a sequence at position 7 from slot 2; row 1 is fresh
    "continue+fresh": ([0, 21, 31], [21, 10, 0], [7, 0, 0], [2, 0, S]),
    # both rows continue, the first of one token only
    "one+many": ([0, 1, 38], [1, 37, 0], [3, 90, 0], [4, 1, S]),
    # a row that starts exactly on a block boundary of 8 and ends on one
    "on-edges": ([0, 8, 32], [8, 24, 0], [0, 16, 0], [3, 0, S]),
}
T = 40


@pytest.mark.parametrize("chunk", [4, 8, 16, 64])
@pytest.mark.parametrize("case", sorted(RAGGED))
def test_chunk_form_equals_the_recurrence(case, chunk):
    """Boundaries of the chunk form inside rows and across them, a row that
    starts from its slot's state, a fresh row, the scratch slot."""
    q_start, q_len, first, slots = (jnp.asarray(a, jnp.int32)
                                    for a in RAGGED[case])
    pos = np.zeros(T, np.int32)
    for a, n, p in zip(*RAGGED[case][:3]):
        pos[a:a + n] = p + np.arange(n)
    ops, state = _operands(T), _state()
    want_o, want_s = delta.delta_scan_reference(
        state, *ops, jnp.asarray(pos), q_start, q_len, slots, 1)
    got_o, got_s = delta.delta_chunk_scan(
        state, *ops, jnp.asarray(pos), q_start, q_len, slots, layer=1,
        chunk=chunk, impl="kernel")
    assert got_s.dtype == jnp.float32
    n_owned = int(sum(RAGGED[case][1]))
    assert float(jnp.abs(got_o - want_o).max()) < TOL
    assert float(jnp.abs(got_o[n_owned:]).max()) == 0.0   # tokens no row owns
    assert float(jnp.abs(got_s - want_s)[:, :S].max()) < TOL
    # the other layer and the slots no row names are as they were
    untouched = [s for s in range(S) if s not in RAGGED[case][3][:2]]
    assert np.array_equal(np.asarray(got_s[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(got_s[1, untouched]),
                          np.asarray(state[1, untouched]))
    # ... and both are the recurrence as written, row by row
    for a, n, p, slot in zip(*RAGGED[case]):
        if n:
            s0 = np.zeros((HV, DK, DV)) if p == 0 else state[1, slot]
            o, s = _by_hand(s0, *(x[a:a + n] for x in ops))
            assert np.abs(np.asarray(got_o[a:a + n]) - o).max() < TOL
            assert np.abs(np.asarray(got_s[1, slot]) - s).max() < TOL


@pytest.mark.parametrize("interpret", [None, True])
def test_one_token_update(interpret):
    """The vectorised reference and the Pallas kernel (interpret mode): a
    row from its slot's state, a fresh row, two rows on the scratch slot."""
    R = 4
    ops, state = _operands(R, seed=3), _state()
    slots = jnp.asarray([3, 1, S, S], jnp.int32)
    fresh = jnp.asarray([False, True, False, True])
    o, s = delta.delta_decode_update(
        state, *ops, slots, fresh, layer=0,
        **(dict(interpret=True) if interpret else dict(impl="reference")))
    assert s.dtype == jnp.float32
    for r, (slot, new) in enumerate([(3, False), (1, True)]):
        s0 = np.zeros((HV, DK, DV)) if new else state[0, slot]
        want_o, want_s = _by_hand(s0, *(x[r:r + 1] for x in ops))
        assert np.abs(np.asarray(o[r]) - want_o[0]).max() < TOL
        assert np.abs(np.asarray(s[0, slot]) - want_s).max() < TOL
    assert np.array_equal(np.asarray(s[1]), np.asarray(state[1]))
    for slot in (0, 2, 4):
        assert np.array_equal(np.asarray(s[0, slot]),
                              np.asarray(state[0, slot]))


def test_update_then_chunk_is_one_sequence():
    """A sequence whose first 13 tokens go in as a chunk row, the next 5 as
    one-token updates and the rest as a chunk row again reads what the
    recurrence gives over all of them: the state in the slot is the whole
    hand-over."""
    n = 30
    ops, state = _operands(n, seed=7), _state()
    want_o, _ = _by_hand(np.zeros((HV, DK, DV)), *ops)
    slot = jnp.asarray([2], jnp.int32)
    got = []

    def chunk_row(lo, hi, state):
        o, state = delta.delta_chunk_scan(
            state, *(x[lo:hi] for x in ops),
            jnp.arange(lo, hi, dtype=jnp.int32), jnp.asarray([0], jnp.int32),
            jnp.asarray([hi - lo], jnp.int32), slot, layer=0, chunk=8,
            impl="kernel")
        got.append(o)
        return state

    state = chunk_row(0, 13, state)
    for t in range(13, 18):
        o, state = delta.delta_decode_update(
            state, *(x[t:t + 1] for x in ops), slot,
            jnp.asarray([False]), layer=0, impl="reference")
        got.append(o)
    state = chunk_row(18, n, state)
    assert np.abs(np.asarray(jnp.concatenate(got)) - want_o).max() < TOL


def test_a_state_rounded_to_bf16_is_told_apart():
    """What TOL is for: the same update over a leaf held in bf16 (the
    reference path takes any dtype; the kernel refuses it) misses by a
    hundred times the tolerance."""
    R = 2
    ops, state = _operands(R, seed=5), _state()
    slots, fresh = jnp.asarray([0, 1], jnp.int32), jnp.zeros(2, bool)
    o32, _ = delta.delta_decode_update(state, *ops, slots, fresh, layer=0,
                                       impl="reference")
    o16, s16 = delta.delta_decode_update(
        state.astype(jnp.bfloat16), *ops, slots, fresh, layer=0,
        impl="reference")
    assert s16.dtype == jnp.bfloat16
    assert float(jnp.abs(o32 - o16).max()) > 100 * TOL
    with pytest.raises(ValueError, match="float32"):
        delta.delta_decode_update(state.astype(jnp.bfloat16), *ops, slots,
                                  fresh, layer=0, interpret=True)


@pytest.mark.parametrize("Q", [1, 2, 8, 64])
def test_unit_lower_inverse(Q):
    rng = np.random.default_rng(Q)
    a = np.tril(rng.normal(size=(3, Q, Q)), -1)
    got = delta._unit_lower_inverse(jnp.asarray(a, jnp.float32))
    want = np.linalg.inv(np.eye(Q) + a)
    assert np.abs(np.asarray(got) - want).max() < 1e-3 * max(
        1.0, np.abs(want).max())
    with pytest.raises(ValueError, match="power of two"):
        delta._unit_lower_inverse(jnp.zeros((6, 6)))
