"""models/llama.py's ONE table of mechanisms (MECHANISMS): every field of
LlamaConfig is the Llama/Mistral block's (or a run option) or belongs to
exactly one mechanism, and the configuration's cross-checks, the training
side's refusal and llm/tp.py's are read off the table: a field, set alone,
is refused by all of them by name, and a mechanism that is added to the
table is refused by all of them with no edit to any.
"""

import dataclasses

import jax.numpy as jnp
import pytest

from ray_tpu.llm import tp
from ray_tpu.models import llama
from ray_tpu.models.llama import (BUILT_BESIDE, IDLE_WHERE_OFF, LAYER_KINDS,
                                  LLAMA_BLOCK, MAMBA, MECHANISMS,
                                  LlamaConfig, mechanisms_beyond)

#: a value off its default for every field of a mechanism
WITNESS = dict(
    n_experts=4, experts_per_token=2, norm_topk_prob=True,
    router_score="sigmoid", router_bias=True, router_eps=1e-6,
    router_scale=2.0, n_dense_layers=1, dense_ffn_dim=32, shared_ffn_dim=32,
    experts_held=(0, 2), qk_norm=True, qk_norm_per_head=True,
    tie_embeddings=False, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, q_lora_rank=8,
    rope_yarn=(8, 16, 32, 1, 1, 1), conv_kernel=4, ssm_state=8, ssm_heads=4,
    ssm_head_dim=8, ssm_conv=3, ssm_chunk=8, retention_chunk=8,
    sliding_window=8, window_kv_heads=2, window_rope_theta=1e4,
    attn_sink=True, delta_key_heads=2, delta_value_heads=4, delta_key_dim=8,
    delta_value_dim=8, delta_conv=3, delta_chunk=8, delta_norm_eps=1e-5,
    delta_gate_scale=1.0, score_head_dim=16, value_head_dim=16, rotary_dim=4,
    value_scale=0.5, embed_scale=2.0, residual_scale=0.5, logits_divisor=2.0,
    attn_scale=0.1, rope=False, attn_gate=True, post_norms=True,
    full_rope=False, norm_gate=2.0, ffn_clamp=5.0, ssm1_state=8,
    ssm1_expand=4, ssm1_conv=3, ssm1_dt_rank=4, diff_attention=True,
    layer_norm=True, attn_bias=True, ut_steps=3)


def _consumers(cfg):
    """what -> (the call that refuses or serves ``cfg``, what it serves
    beyond the Llama/Mistral block)."""
    return {
        "models.llama.forward": (lambda: llama.forward(
            None, jnp.zeros((1, 4), jnp.int32), cfg), ()),
        "param_specs": (lambda: llama.param_specs(cfg), ()),
        "num_params": (lambda: llama.num_params(cfg), ()),
        "tp=2": (lambda: tp.validate_tp(cfg, 2), ("untied head",))}


def test_every_field_is_declared_in_exactly_one_place():
    """The partition: a field added to LlamaConfig and not declared (the
    Llama/Mistral block's, or ONE mechanism's) fails here, and so does one
    declared twice or one that is no field."""
    declared = list(LLAMA_BLOCK) + [
        f for fields in MECHANISMS.values() for f in fields]
    assert sorted(declared) == sorted(
        f.name for f in dataclasses.fields(LlamaConfig))
    assert set(WITNESS) == {f for fields in MECHANISMS.values()
                            for f in fields}
    # every layer kind is a mechanism; the idle constants and the rows of
    # what is built beside what name the table's own words
    assert set(LAYER_KINDS) <= set(MECHANISMS)
    assert IDLE_WHERE_OFF <= set(WITNESS)
    assert set(BUILT_BESIDE) | {m for row in BUILT_BESIDE.values()
                                for m in row} <= set(MECHANISMS)


@pytest.mark.parametrize("field", sorted(WITNESS))
def test_a_field_set_alone_is_refused_by_name_or_needs_its_switch(field):
    """Each field of each mechanism, alone on the Llama/Mistral block:
    the configuration refuses it (it describes a mechanism whose switch is
    off, or needs another field) or builds, and then forward, param_specs,
    num_params and validate_tp(cfg, 2) each refuse it and name the field
    and its mechanism; but for an untied head, which llm/tp.py shards, and
    the idle constants, which turn nothing on (the configuration was
    accepted with them before the table: ROADMAP.md D22)."""
    mechanism = next(m for m, fields in MECHANISMS.items() if field in fields)
    try:
        cfg = LlamaConfig.tiny(**{field: WITNESS[field]})
    except ValueError as e:
        assert field in str(e)
        return
    for what, (call, served) in _consumers(cfg).items():
        found = mechanisms_beyond(cfg, served)
        if field in IDLE_WHERE_OFF or mechanism in served:
            assert not found
            if what != "models.llama.forward":     # it has no weights here
                call()
            continue
        assert found == {mechanism: (field,)}
        with pytest.raises(NotImplementedError) as e:
            call()
        assert what in str(e.value) and field in str(e.value)
        assert llama.named(found) in str(e.value)


def test_a_new_mechanism_is_refused_everywhere_with_no_edit(monkeypatch):
    """Allow-lists: a row added to the table (here over a field the Llama
    block has, so that a configuration can set it) is refused by the
    training side and by llm/tp.py by name, and is built beside no
    mechanism that says what it is built beside, with no edit to
    _require_llama_block, validate_tp or __post_init__."""
    monkeypatch.setitem(MECHANISMS, "a new operator", ("norm_eps",))
    cfg = LlamaConfig.tiny(norm_eps=1e-6)
    for what, (call, served) in _consumers(cfg).items():
        with pytest.raises(NotImplementedError, match="a new operator"):
            call()
    with pytest.raises(ValueError, match="not built beside a new operator"):
        LlamaConfig.tiny(norm_eps=1e-6, n_layers=2, layer_types=[MAMBA] * 2,
                         ssm_state=8, ssm_heads=4, ssm_head_dim=8)
    assert not mechanisms_beyond(LlamaConfig.tiny())


def test_the_tables_rows_say_what_the_blocks_are():
    """Every served block (tests/_blocks.py) is a set of mechanisms that
    are built beside each other, and none of them is the Llama block's."""
    from _blocks import BLOCKS, config
    for block in BLOCKS:
        found = mechanisms_beyond(config(block))
        assert bool(found) == (block != "mistral"), block
        for name in found.keys() & BUILT_BESIDE.keys():
            # ... or beside it WHERE another mechanism of the block is on
            where = {m for on, rows in llama.BUILT_BESIDE_WHERE.items()
                     if on in found for m in rows.get(name, ())}
            assert set(found) - {name} <= set(BUILT_BESIDE[name]) | where, \
                block
