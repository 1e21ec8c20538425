"""The Brumby block in the serving engine: power-retention layers of degree
2 (a gated matrix state over the expanded key and its normaliser, per batch
slot and key/value head, NO attention layer and so no paged layer at all),
through the one ragged step and the decode loop, against the benchmark's
plain QUADRATIC reference (benchmark/reference_brumby.py: explicit weights,
no expansion, no state) on seeded weights. Tiny widths on the CPU, float32
compute: 4 query heads on 2 key/value heads of 16 (two 8-value blocks: one
diagonal pair and one off the diagonal in every expansion), blocks of 8
tokens.

TOL: everything runs in float32 here (cfg.dtype, the state as held and the
reference), so the two sides differ by summation order only; but a weight is
a SQUARE of a 16-term product times a decay, summed over up to 60 tokens on
both sides of a ratio, and the served side sums it through 192 expanded
terms: still ~1e-6 on logits of unit spread (measured: 2.5e-6 at worst over
the chunk sizes below). 1e-4 leaves room and still fails a bf16
computation (~1e-2), a row that starts from another sequence's
state or from zeros mid-sequence, a gate without its bias, a missing
sqrt(2), a scale left out, a normaliser without its eps.
"""

import dataclasses
import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from _blocks import (chunked_logits, reference_logits, run,  # noqa: E402
                     seeded)
from _chunk_rows import (check_descriptor,  # noqa: E402
                         check_state_keeps_one_row, SHAPE_CASES,
                         check_shapes, pin_full_shape)
from ray_tpu.llm import InferenceEngine  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402
from ray_tpu.llm.cache import (RET_LEAF, RET_NORM_LEAF,  # noqa: E402
                               prefix_cache_supported)
from ray_tpu.models.llama import (LlamaConfig,  # noqa: E402
                                  mechanisms_beyond)
from ray_tpu.ops import retention  # noqa: E402

TOL = 1e-4
HD, G, MQ = 16, 2, 2
BRUMBY = dict(dim=64, n_layers=3, n_heads=G * MQ, n_kv_heads=G, ffn_dim=96,
              layer_types=["retention"] * 3, qk_norm_per_head=True,
              tie_embeddings=False, retention_chunk=8, norm_eps=1e-6,
              dtype=jnp.float32)
ENGINE = dict(page_size=8, total_pages=64, max_batch=4, max_seq_len=128,
              prefill_chunk=16, prefill_rows=2, decode_chunk=4, seed=3)


@pytest.fixture(scope="module")
def brumby():
    jax.clear_caches()
    cfg = LlamaConfig.tiny(**BRUMBY)
    return cfg, InferenceEngine(cfg, seeded(cfg), **ENGINE)


# ------------------------------------------------------ ops/retention.py

@pytest.mark.parametrize("d", [8, 16, 128])
def test_the_expansion_squares_the_inner_product(d):
    """phi(a) . phi(b) = (a . b)^2 for the layout the program holds: one
    block, a diagonal and an off-diagonal pair, the published head."""
    a, b = jax.random.normal(jax.random.PRNGKey(d), (2, 7, d))
    fa, fb = retention.expand(a), retention.expand(b)
    assert fa.shape == (7, retention.expanded_dim(d))
    want = jnp.sum(a * b, axis=-1) ** 2
    assert float(jnp.abs(jnp.sum(fa * fb, axis=-1) - want).max()) \
        < 1e-5 * float(want.max())
    assert retention.expanded_dim(128) == 8704
    with pytest.raises(ValueError, match="whole blocks of 8"):
        retention.expanded_dim(12)


def _ragged_case(dtype=jnp.float32):
    """Three one-token rows (one of them empty) and two chunk rows, one
    continuing a sequence at position 5 and one starting at 0, padding
    after; over state leaves that hold garbage."""
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    L, S, T = 2, 5, 3 + 13 + 11 + 4
    D = retention.expanded_dim(HD)
    state = jax.random.normal(ks[0], (L, S + 1, G, D, HD)).astype(dtype)
    z = jax.random.normal(ks[1], (L, S + 1, G, HD, HD))
    norm = jnp.einsum("...ij,...kj->...ik", z, z)
    q = jax.random.normal(ks[2], (T, G * MQ, HD)) * HD ** -0.25
    k = jax.random.normal(ks[3], (T, G, HD)) * HD ** -0.25
    v = jax.random.normal(ks[4], (T, G, HD))
    a = -jax.random.uniform(ks[5], (T, G), minval=0.01, maxval=0.7)
    pos = np.zeros(T, np.int32)
    pos[0], pos[1] = 9, 0                  # a running row, a fresh one
    pos[3:16] = 5 + np.arange(13)          # continues at 5 (slot 2)
    pos[16:27] = np.arange(11)             # starts at 0 (slot 3)
    return dict(state=state, norm=norm, q=q, k=k, v=v, a=a,
                pos=jnp.asarray(pos), T=T, S=S)


def _by_ops(case, layer, **how):
    """The case through the two entry points as ``_retention`` calls them:
    (o [T, H, d], state, norm)."""
    S, chunk = case["S"], how.pop("chunk", 8)
    # the update's kernel needs the interpreter here; the chunk form's
    # "kernel" is plain jnp
    o1, state, norm = retention.retention_decode_update(
        case["state"], case["norm"], case["q"][:3], case["k"][:3],
        case["v"][:3], case["a"][:3], jnp.asarray([0, 1, S]),
        case["pos"][:3] == 0, layer=layer,
        **(dict(interpret=True) if how.get("impl") == "kernel" else how))
    o2, state, norm = retention.retention_chunk_scan(
        state, norm, case["q"][3:], case["k"][3:], case["v"][3:],
        case["a"][3:], case["pos"][3:], jnp.asarray([0, 13, 0]),
        jnp.asarray([13, 11, 0]), jnp.asarray([2, 3, S]), layer=layer,
        chunk=chunk, **how)
    return jnp.concatenate([o1, o2]), state, norm


_HAND = {}


def _by_hand(case, layer):
    """Every row of the case as the plain sum over its own tokens: the
    slot's state and normaliser as one more term, decayed; no expansion
    beyond reading the held state through phi(q)."""
    if layer in _HAND:                     # the case is the same every time
        return _HAND[layer]
    f32 = jnp.float32
    rows = [(0, [0], 0, False), (1, [1], 1, True),
            (2, list(range(3, 16)), 2, False),
            (3, list(range(16, 27)), 3, True)]
    out = np.zeros((case["T"], G * MQ, HD), np.float32)
    for _, toks, slot, fresh in rows:
        S0 = case["state"][layer, slot].astype(f32)
        Z0 = case["norm"][layer, slot]
        for i, t in enumerate(toks):
            for j in range(G * MQ):
                g = j // MQ
                q = case["q"][t, j]
                since = jnp.exp(case["a"][toks[0]:t + 1, g].sum())
                num = 0.0 if fresh else since * (
                    retention.expand(q) @ S0[g])
                den = 0.0 if fresh else since * (q @ Z0[g] @ q)
                for s in toks[:i + 1]:
                    w = jnp.exp(case["a"][s + 1:t + 1, g].sum()) \
                        * (q @ case["k"][s, g]) ** 2
                    num, den = num + w * case["v"][s, g], den + w
                out[t, j] = num / (den + retention.EPS)
    _HAND[layer] = out
    return out


@pytest.mark.parametrize("how", [
    dict(impl="reference"), dict(impl="kernel", chunk=256),
    dict(impl="kernel", chunk=8), dict(impl="kernel", chunk=5),
    dict(interpret=True, chunk=8)])
def test_update_and_chunk_form_against_the_plain_sum(how):
    """The sequential reference of each op, the chunk form at three block
    sizes (one block; blocks that cut both rows; blocks that are no divisor
    of anything) and the Pallas kernel in interpret mode: each token's
    output against the plain weighted sum over its row's own tokens, with
    the slot's garbage as the entering state where the row continues and
    unread where it starts."""
    case = _ragged_case()
    how = dict(how)
    if how.get("interpret"):
        how["impl"] = None
    want = _by_hand(case, 1)
    got, state, norm = _by_ops(case, 1, **how)
    owned = [0, 1] + list(range(3, 27))
    assert float(np.abs(np.asarray(got)[owned] - want[owned]).max()) < 1e-4
    assert float(jnp.abs(got[27:]).max()) == 0.0          # padding
    # the other layer and the slots no row named are untouched
    assert jnp.array_equal(state[0], case["state"][0])
    assert jnp.array_equal(state[1, 4], case["state"][1, 4])
    assert jnp.array_equal(norm[1, 4], case["norm"][1, 4])
    # every way leaves the same state behind
    _, s_ref, z_ref = _by_ops(case, 1, impl="reference")
    for r in range(4):
        assert float(jnp.abs(state[1, r] - s_ref[1, r]).max()) < 1e-4
        assert float(jnp.abs(norm[1, r] - z_ref[1, r]).max()) < 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_update_kernel_in_interpret_mode(dtype):
    """The kernel against the vectorised reference, the state held in
    float32 and in bfloat16 (rounded once, on the way out, by both)."""
    case = _ragged_case(dtype)
    args = (case["q"][:3], case["k"][:3], case["v"][:3], case["a"][:3],
            jnp.asarray([0, 1, case["S"]]), case["pos"][:3] == 0)
    o1, s1, z1 = retention.retention_decode_reference(
        case["state"], case["norm"], *args, 1)
    o2, s2, z2 = retention.retention_decode_update(
        case["state"], case["norm"], *args, layer=1, interpret=True)
    assert s2.dtype == dtype
    assert float(jnp.abs(o1[:2] - o2[:2]).max()) < 1e-4
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -7
    for slot in (0, 1):
        assert float(jnp.abs(s1[1, slot].astype(jnp.float32)
                             - s2[1, slot].astype(jnp.float32)).max()) < tol
        assert float(jnp.abs(z1[1, slot] - z2[1, slot]).max()) < 1e-4


# ------------------------------------------------------------- the engine

def test_param_tree_pool_and_pattern(brumby):
    cfg, eng = brumby
    stack = eng.params["layers"]["retention"]
    assert set(eng.params["layers"]) == {"retention", "dense"}
    assert stack["w_g"].shape == (3, 64, G) and stack["b_g"].shape == (3, G)
    assert stack["b_g"].dtype == jnp.float32
    # sigmoid(b_g) in 1 - [1e-3, 1e-1]: a head forgets over 10 to 1000 tokens
    forget = 1.0 - np.asarray(jax.nn.sigmoid(stack["b_g"]))
    assert 1e-3 * 0.99 <= forget.min() and forget.max() <= 1e-1 * 1.01
    assert stack["q_norm"].shape == (3, HD)
    D = retention.expanded_dim(HD)
    kv = eng.kv
    assert kv["k"].shape[0] == kv["v"].shape[0] == 0      # no paged layer
    assert kv[RET_LEAF].shape == (3, 5, G, D, HD)
    assert kv[RET_NORM_LEAF].shape == (3, 5, G, HD, HD)
    assert kv[RET_NORM_LEAF].dtype == jnp.float32
    per_slot = 3 * G * (D * HD * 4 + HD * HD * 4)
    assert eng.stats["state_bytes_per_slot"] == per_slot
    assert eng.stats["state_bytes"] == 5 * per_slot
    report = eng.device_report()
    assert report["kv_token_layer_bytes"] == 0
    assert report["state_bytes_per_slot"] == per_slot
    assert report["kv_bytes"] == 5 * per_slot
    assert M._pattern(cfg) == ([], [("retention", "dense")], 3)
    assert "retention" in mechanisms_beyond(cfg) and cfg.hybrid


def test_a_descriptor_holds_the_arrays_the_engine_packed_before():
    """Retention layers and no page in use: token_state is a field of the
    descriptor, the page fields ride as ever; every field the old
    packing's."""
    cfg = LlamaConfig.tiny(**BRUMBY)
    params = seeded(cfg)
    check_descriptor(lambda **kw: InferenceEngine(
        cfg, params, **{**ENGINE, **kw}))


def test_a_sequence_that_prefills_alone_keeps_one_row_a_step(brumby):
    check_state_keeps_one_row(brumby[1])


@pytest.fixture(scope="module")
def shaped_and_full():
    """The same weights behind the set of mixed-step shapes and behind
    the full shape alone (what every step ran in before the set)."""
    cfg = LlamaConfig.tiny(**BRUMBY)
    params = seeded(cfg)
    return [InferenceEngine(cfg, params, **ENGINE),
            pin_full_shape(InferenceEngine(cfg, params, **ENGINE))]


@pytest.mark.parametrize("case", SHAPE_CASES)
def test_a_mixed_step_runs_the_smallest_shape_that_holds_its_rows(
        shaped_and_full, case):
    """One-row and two-row steps in turn through the RETENTION layers: a
    row's slot and the scratch slot are addressed through token_state in
    either shape, and the tokens are the full shape's."""
    check_shapes(case, *shaped_and_full)


def test_a_pool_with_no_paged_layer_admits_by_slots(brumby, caplog):
    """No layer owns a page: the page leaves are empty, a token costs no
    page bytes, the prefix cache is off by the engine's own rule, no page
    is ever copied, and with pages that never bind what holds a fifth
    request back is the fourth slot."""
    cfg, eng = brumby
    assert not prefix_cache_supported(cfg)
    copies = M.copy_page._cache_size()
    with caplog.at_level(logging.WARNING, logger="ray_tpu.llm.engine"):
        on = InferenceEngine(cfg, eng.params, **ENGINE, prefix_cache=True)
    said = [r.message for r in caplog.records
            if "prefix cache off" in r.message]
    assert len(said) == 1 and str(eng.stats["state_bytes_per_slot"]) in said[0]
    assert on.prefix is None
    assert sum(x.nbytes for k, x in on.kv.items()
               if k not in (RET_LEAF, RET_NORM_LEAF)) == 0
    prompt = list(range(7, 7 + 32))              # four full pages
    want = eng.generate(prompt, 7)
    assert on.generate(prompt, 7) == want
    assert on.generate(prompt, 7) == want
    assert on.stats["cached_tokens"] == 0 and on.stats["cow_copies"] == 0
    rids = [on.add_request(list(range(i, i + 20)), 8) for i in range(5)]
    on.step()
    assert len(on.waiting) == 1 and all(s is not None for s in on._slots)
    assert on.allocator.num_free > on.max_pages_per_seq    # pages do not bind
    done = run(on)
    assert set(rids) <= set(done)
    assert M.copy_page._cache_size() == copies   # never compiled, never run
    assert on.stats["preemptions"] == 0


@pytest.mark.parametrize("leaf", ["b_g", "w_g", "q_norm", "k_norm", "wv"])
def test_no_part_of_the_operator_is_left_out(brumby, leaf):
    """Each leaf of the operator moves the served logits as it moves the
    reference's: changed on both sides they still agree, changed on one
    they do not."""
    cfg, eng = brumby
    prompt = list(range(9, 40))
    stack = dict(eng.params["layers"]["retention"])
    stack[leaf] = stack[leaf] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), stack[leaf].shape).astype(stack[leaf].dtype)
    other = {**eng.params, "layers": {**eng.params["layers"],
                                      "retention": stack}}
    want = reference_logits("brumby", other, cfg, prompt)[-1]
    got, _ = chunked_logits(cfg, other, prompt, 16)
    assert float(jnp.abs(got - want).max()) < TOL
    stale, _ = chunked_logits(cfg, eng.params, prompt, 16)
    assert float(jnp.abs(stale - want).max()) > 100 * TOL


def test_the_scale_and_the_rotary_embedding_are_applied(brumby):
    """The reference without its scale inside the power, or with another
    rope_theta, is told apart from the served logits (the scale cancels
    only up to eps, so it is told apart through eps alone: compared at a
    tolerance far under TOL's room it still is)."""
    cfg, eng = brumby
    prompt = list(range(9, 40))
    got, _ = chunked_logits(cfg, eng.params, prompt, 16)
    other = dataclasses.replace(cfg, rope_theta=cfg.rope_theta * 4)
    want = reference_logits("brumby", eng.params, other, prompt)[-1]
    assert float(jnp.abs(got - want).max()) > 100 * TOL
    no_rope, _ = chunked_logits(dataclasses.replace(cfg, rope=False),
                                 eng.params, prompt, 16)
    assert float(jnp.abs(no_rope - got).max()) > 100 * TOL


def test_config_refuses_what_is_not_built():
    with pytest.raises(ValueError, match="retention.*beside experts"):
        LlamaConfig.tiny(**{**BRUMBY, "n_experts": 4,
                            "experts_per_token": 2})
    with pytest.raises(ValueError, match="beside latent attention"):
        LlamaConfig.tiny(**{**BRUMBY, "kv_lora_rank": 32,
                            "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
                            "v_head_dim": 8, "qk_norm_per_head": False})
    with pytest.raises(ValueError, match="whole blocks of 8"):
        LlamaConfig.tiny(**{**BRUMBY, "dim": 48})           # head_dim 12
    with pytest.raises(ValueError, match="n_kv_heads to divide"):
        LlamaConfig.tiny(**{**BRUMBY, "n_heads": 8, "n_kv_heads": 3})

