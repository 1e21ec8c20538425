"""The Phi-4-mini-flash block's walk is cut at its tail: in a mixed step
every token of a row but its last leaves the walk before the cross-decoder
(llm/model.py: tail_start), on the row `phi4flash` of tests/_blocks.py,
against the benchmark's plain reference. Tiny widths on the CPU, float32
compute; the rest of the block's own tests: tests/test_llm_phi4flash.py.
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

from _blocks import _step, built, config  # noqa: E402
from benchmark import reference_phi4flash as ref  # noqa: E402
from ray_tpu.llm import InferenceEngine  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402

TOL = 1e-4


# ------------------------------------------- the walk is cut at its tail

#: decode rows beside chunk rows: A and B decode while C, D and E prefill
#: in chunks of 16, so one mixed step (three rows dealt: the shape of four)
#: holds decode rows, idle decode rows, a chunk row that ends its prompt
#: (C's 4 tokens, E's 14), one that does not (D's second 16) and an empty
#: one
_MIXED = dict(page_size=8, total_pages=64, max_batch=5, max_seq_len=64,
              prefill_chunk=16, prefill_rows=4, decode_chunk=4)


@pytest.fixture(scope="module")
def mixed_steps():
    """(cfg, params, [every mixed step the engine packed: its
    descriptor's fields, the pool before it, each row's sequence so far])
    of an engine held to one program at a time (every token of a
    descriptor is then a value)."""
    cfg, params = built("phi4flash")
    eng = InferenceEngine(cfg, params, **_MIXED)
    eng._run_ahead = False
    steps, pack = [], eng._pack_mixed

    def packing(active, rows, n_rows):
        buf = pack(active, rows, n_rows)
        B = eng.max_batch
        seen = {slot: seq.prompt + seq.generated for slot, seq in active}
        seen.update({B + j: seq.prompt[:start + C]
                     for j, (seq, start, C) in enumerate(rows)})
        fields = M.cut(buf.copy(), eng._fns.step_layouts[n_rows])
        fields.pop("newest_slot")
        steps.append((fields, jax.tree.map(jnp.copy, eng.kv), seen))
        return buf
    eng._pack_mixed = packing
    rng = np.random.default_rng(4)
    prompt = lambda n: rng.integers(1, cfg.vocab_size, n).tolist()  # noqa
    for n in (5, 9):
        eng.add_request(prompt(n), 12)
    eng.step()
    for n in (20, 40, 30):
        eng.add_request(prompt(n), 3)
    for _ in range(60):
        eng.step()
        if not eng.has_work():
            break
    assert not eng.has_work()
    return cfg, params, steps


def _run_step(cfg, params, fields, kv):
    return _step(params, **{k: jnp.asarray(a) for k, a in fields.items()},
                 kv=kv, cfg=cfg, paged_impl="reference",
                 max_q_len=_MIXED["prefill_chunk"],
                 decode_rows=_MIXED["max_batch"])


def _the_mix(steps):
    """The steps that hold every kind of row: decode rows, an idle one, a
    chunk row that ends its prompt, one that does not, an empty one."""
    B, C = _MIXED["max_batch"], _MIXED["prefill_chunk"]
    found = []
    for fields, kv, seen in steps:
        q_len = fields["q_len"]
        n_decode = int(q_len[:B].sum())
        ends = [r for r in seen if r >= B and q_len[r] < C]
        if 0 < n_decode < B and ends and (q_len[B:] == C).any() \
                and (q_len[B:] == 0).any():
            found.append((fields, kv, seen))
    return found


def test_every_rows_last_token_has_the_references_logits(mixed_steps):
    """Through the cut, in every mixed step the engine packed: each row
    that holds a token gets, at its last one, the logits the plain
    reference gives its sequence there."""
    cfg, params, steps = mixed_steps
    assert _the_mix(steps)
    for fields, kv, seen in steps:
        logits, _, _ = _run_step(cfg, params, fields, kv)
        for row, tokens in seen.items():
            assert fields["kv_len"][row] == len(tokens)
            # the reference over the sequence padded to ONE length (it is
            # causal: what stands behind a token does not reach it), so it
            # is traced once and not once for every length a row has had
            padded = tokens + [0] * (_MIXED["max_seq_len"] - len(tokens))
            with jax.default_matmul_precision("highest"):
                want = ref.forward_logits(
                    params, jnp.asarray(padded, jnp.int32),
                    ref.dims_of(cfg))[len(tokens) - 1]
            assert float(jnp.abs(logits[row] - want).max()) < TOL, row


def test_the_pool_is_what_the_self_decoder_alone_writes(mixed_steps):
    """Every page leaf and every SLOT_STATE leaf after a mixed step, as
    the first six layers alone (no tail: nothing is cut) leave them: the
    tail writes nothing, and leaving it early changes nothing kept."""
    cfg, params, steps = mixed_steps
    at = M.tail_start(cfg)
    alone = config("phi4flash", dtype=jnp.float32, n_layers=at,
                   layer_types=list(cfg.layer_types[:at]))
    layers = params["layers"]
    head = {**params, "layers": {
        **{k: layers[k] for k in ("attn", "attn_window", "mamba1")},
        "dense": jax.tree.map(lambda w: w[:at], layers["dense"])}}
    assert M.tail_start(alone) == alone.n_layers == 6
    for fields, kv, _ in _the_mix(steps):
        _, got, _ = _run_step(cfg, params, fields, kv)
        _, want, _ = _run_step(alone, head, fields, kv)
        assert set(got) == set(want) == {"k", "v", "k_win", "v_win",
                                         "ssm1", "ssm1_conv"}
        for leaf in want:
            np.testing.assert_array_equal(got[leaf], want[leaf], leaf)


def _dot_shapes(jaxpr):
    """The result shape of every dot_general of ``jaxpr`` at any depth, a
    kernel's body not entered."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.outvars[0].aval.shape)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                out += _dot_shapes(getattr(sub, "jaxpr", sub))
    return out


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_the_tails_products_have_a_row_a_row_not_a_row_a_token(
        mixed_steps, impl):
    """The mechanism, in the program's text: in the tail's scan (the last
    of the walk's two) every product has R rows, the feed-forward's [1, R,
    ffn] among them; in the scan before it, T."""
    fields = mixed_steps[2][-1][0]
    cfg = config("phi4flash")
    T, R = fields["tokens"].shape[0], fields["q_len"].shape[0]
    assert T != R and cfg.ffn_dim not in (T, R)
    params, kv = jax.eval_shape(lambda: (
        M.init_params(cfg, jax.random.PRNGKey(0)),
        M.make_kv_cache(cfg, 16, 8, max_batch=_MIXED["max_batch"],
                        lane_pad=impl == "kernel", window_pages=9)))
    jaxpr = jax.make_jaxpr(lambda p, f, kv: M._ragged_logits(
        p, **f, kv=kv, cfg=cfg, paged_impl=impl,
        max_q_len=_MIXED["prefill_chunk"],
        decode_rows=_MIXED["max_batch"]))(
        params, {k: jax.ShapeDtypeStruct(a.shape, jnp.int32)
                 for k, a in fields.items()}, kv).jaxpr
    before, tail = (_dot_shapes(eqn.params["jaxpr"].jaxpr)
                    for eqn in jaxpr.eqns if eqn.primitive.name == "scan")
    assert (1, R, cfg.ffn_dim) in tail and (1, T, cfg.ffn_dim) in before
    assert not [s for s in tail if T in s]
    assert not [s for s in before if R in s and len(s) == 3]
    # the head: R rows, as ever
    assert [eqn.outvars[0].aval.shape for eqn in jaxpr.eqns
            if eqn.primitive.name == "dot_general"][-1] \
        == (R, cfg.vocab_size)


def test_running_ahead_serves_the_synchronous_engines_tokens_and_counts():
    """tests/test_llm_ahead.py's method on this block, chunk rows that do
    and do not end their prompts beside decode rows: the same tokens from
    the engine that runs one program ahead, and in both engines every
    token of a chunk row but its last counted as having left the walk."""
    from test_llm_ahead import _both, _requests
    cfg = config("phi4flash")
    requests = _requests(11, 9, cfg.vocab_size, longest=60)
    got, ahead, sync = _both("phi4flash", requests,
                             at=[0, 0, 0, 1, 3, 3, 8, 8, 20])
    assert ahead.stats["ahead_dispatches"] > 2
    for eng in (ahead, sync):
        st = eng.stats
        assert st["walk_tokens"] == st["ragged_real_tokens"]
        assert st["walk_tokens_left"] \
            == st["prefill_tokens"] - st["chunk_rows"] > 0
