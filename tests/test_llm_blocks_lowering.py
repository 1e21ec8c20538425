"""A block lowers to the code of its own fields and to no other block's.

`LlamaConfig` is the configuration of several blocks (the rows of
tests/_blocks.py:BLOCKS), and one decoder body in llm/model.py follows its
fields. A configuration that sets none of a block's fields must take none
of that block's code: the tests here read the jaxprs of both step programs and of
the page copy, on the kernel path and on the reference path, and the
parameter and pool trees, and look for what only another block brings.

The pool and the walk are declared ONCE each (llm/cache.py: SLOT_STATE,
llm/model.py: OPERATORS and _pattern): the tests here hold every block's
pool, descriptor and engine to those declarations.

As a script, `python3 tests/test_llm_blocks_lowering.py [checkout]` prints
a digest of each of those texts for the checkout given (default: this
one), and beside each step program's the `scan` / `while` equations of
its jaxpr, the kernels' bodies not counted: one scan is the walk over the
layers (and one more the decode loop's steps), whatever else is there an
operator's or a reference's own. Run it on two commits and compare the
lines: a PR that must leave a block's programs alone shows it so (text
for text the same jaxpr, the kernels' bodies included), and its PERF.md
entry keeps the finding; no digest is kept here, where every later change
to model.py or a kernel would have to overwrite it.
"""

import glob
import hashlib
import json
import os
import re
import sys

ROOT = os.path.abspath(sys.argv[1]) if __name__ == "__main__" \
    and len(sys.argv) > 1 else \
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from _blocks import BLOCKS, config  # noqa: E402
from ray_tpu.llm import model as M  # noqa: E402
from ray_tpu.llm import cache as C  # noqa: E402
from ray_tpu.llm.cache import make_kv_cache  # noqa: E402
from ray_tpu.models.llama import LAYER_KINDS, init_params  # noqa: E402

#: what only a block's own fields may bring into a program's text or
#: trees: named scopes, parameter leaves, and the shape of the pool
ONLY_LATENT = ("mla_proj", "w_kva", "w_uk", "w_uv", "kv_norm")
ONLY_SHARED = ("moe_shared", "w_shared_gate", "w_shared_up", "w_shared_down")
ONLY_CONV = ("short_conv", "conv_norm")
ONLY_SSM = ("ssm_proj", "ssm_update", "ssm_scan", "w_xbc", "ssm_conv")
#: ... the two recurrences with a decay a head
ONLY_DECAY = ("A_log", "dt_bias")
ONLY_DELTA = ("delta_proj", "delta_update", "delta_chunk", "delta_norm",
              "w_qkv", "w_ba", "delta_conv", "wq_a", "q_a_norm")
ONLY_RETENTION = ("retention_proj", "retention_update", "retention_chunk",
                  "retention_norm", "'b_g'")
ONLY_WINDOW = ("attn_window", "attn_full_proj", "k_win", "v_win", "'sink'",
               "ragged_window_kernel")
#: ... a gated block's: the gate's and the head's scopes, the gate's
#: projection, the norms after a branch
ONLY_GATED = ("attn_gate", "w_og", "attn_post_norm", "mlp_post_norm")
#: ... a decoder-hybrid-decoder's: the Mamba-1 operator's scopes and
#: leaves, the gated memory unit's and the cross operator's scopes, the
#: differential combine and its leaves, a LayerNorm's bias, the
#: projections' biases
ONLY_SAMBAY = ("ssm1", "w_xproj", "gmu_norm", "attn_cross", "shared_kv",
               "attn_diff", "lambda_q1", "subln", "norm_b", "'bq'")
#: ... a looped stack's: the scope between two passes, the gate's leaves
ONLY_LOOPED = ("ut_exit", "exit_w", "exit_b")
#: ... and its head's scope, in a program's text (in a parameter tree it
#: is every untied head's leaf)
SCOPE_HEAD = "lm_head"
#: entries of a window row's compact table at the sizes traced below
_WINDOW_PAGES = 4


def _text(t) -> str:
    """A jaxpr with each equation's named scopes beside it (a tree's text
    as it is)."""
    return t if isinstance(t, str) else t.pretty_print(name_stack=True)


def loops(jaxpr) -> dict:
    """How many `scan` and `while` equations ``jaxpr`` holds at any depth,
    a kernel's body (`pallas_call`) not entered."""
    n = dict(scan=0, **{"while": 0})
    todo = [jaxpr.jaxpr]
    while todo:
        for eqn in todo.pop().eqns:
            name = eqn.primitive.name
            if name in n:
                n[name] += 1
            if name != "pallas_call":
                todo += jax.core.jaxprs_in_params(eqn.params)
    return n


def traced(block: str, **over) -> dict:
    """name -> a tree's text or a jaxpr: the parameter tree, and for
    the reference and the kernel path the pool's tree and the jaxprs of
    the mixed step, the decode loop and the page copy, of ``block`` at
    tiny widths (``over``: fields on top of the row's)."""
    cfg = config(block, **over)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    out = {f"{block}.params": str(jax.tree.map(
        lambda a: (a.shape, str(a.dtype)), params))}
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)        # noqa: E731
    T, R, mp = 12, 5, 4
    windowed = "sliding_attention" in cfg.layer_types
    for impl in ("reference", "kernel"):
        kv = jax.eval_shape(lambda: make_kv_cache(
            cfg, 16, 8, max_batch=3, lane_pad=impl == "kernel",
            **(dict(window_pages=9) if windowed else {})))
        extra = (i32(T),) if cfg.layer_types and not windowed else ()
        out[f"{block}.{impl}.pool"] = str(jax.tree.map(
            lambda a: (a.shape, str(a.dtype)), kv))
        if windowed:
            # the second page group's fields ride the descriptor (and each
            # token's slot, where a layer keeps state beside the two groups)
            state = (i32(T),) if C.keeps_slot_state(cfg) else ()
            out[f"{block}.{impl}.step"] = jax.make_jaxpr(
                lambda *a: M._ragged_step_body(
                    *a[:10], cfg=cfg, paged_impl=impl, max_q_len=8,
                    decode_rows=3, token_page_win=a[10],
                    page_table_win=a[11], page_base_win=a[12],
                    token_state=a[13] if len(a) > 13 else None))(
                params, i32(T), i32(T), i32(T), i32(T), i32(R, mp), i32(R),
                i32(R), i32(R), kv, i32(T), i32(R, _WINDOW_PAGES), i32(R),
                *state)
            out[f"{block}.{impl}.loop"] = jax.make_jaxpr(
                lambda p, t, pos, kv, pt, sl, wt, wb: M._ragged_decode_loop(
                    p, t, pos, kv, pt, sl, 4, cfg, None, impl, wt, wb))(
                params, i32(3), i32(3), kv, i32(3, mp), i32(3),
                i32(3, _WINDOW_PAGES), i32(3))
            out[f"{block}.{impl}.copy"] = jax.make_jaxpr(
                M._copy_page_body)(kv, i32(), i32())
            continue
        out[f"{block}.{impl}.step"] = jax.make_jaxpr(
            lambda *a: M._ragged_step_body(
                *a[:10], cfg=cfg, paged_impl=impl, max_q_len=8,
                decode_rows=3,
                token_state=a[10] if len(a) > 10 else None))(
            params, i32(T), i32(T), i32(T), i32(T), i32(R, mp), i32(R),
            i32(R), i32(R), kv, *extra)
        out[f"{block}.{impl}.loop"] = jax.make_jaxpr(
            lambda p, t, pos, kv, pt, sl: M._ragged_decode_loop(
                p, t, pos, kv, pt, sl, 4, cfg, None, impl))(
            params, i32(3), i32(3), kv, i32(3, mp), i32(3))
        out[f"{block}.{impl}.copy"] = jax.make_jaxpr(
            M._copy_page_body)(kv, i32(), i32())
    return out


def lowered(block: str) -> dict:
    """``traced`` with every jaxpr as its text."""
    return {name: _text(t) for name, t in traced(block).items()}


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_a_block_takes_no_other_blocks_code(block):
    """None of the latent block's or the shared expert's scopes, leaves or
    pool layout in a configuration that sets none of their fields (and no
    conv operator where no layer is one): its pool keeps a `v` leaf beside
    `k`, both per KV head."""
    cfg = config(block)
    texts = lowered(block)
    everything = "\n".join(texts.values())
    if "linear_attention" in cfg.layer_types:
        # the control for the delta block's own words, beside the latent
        # operator's, the shared expert's and the gated block's, which it
        # has too; and nothing of the other recurrences or of a window
        missing = [w for w in ONLY_DELTA + ONLY_DECAY + ONLY_LATENT
                   + ONLY_SHARED + ONLY_GATED if w not in everything]
        assert not missing, f"the delta block's texts lack {missing}"
        assert not [w for w in ONLY_CONV + ONLY_SSM + ONLY_RETENTION
                    + ONLY_WINDOW if w in everything]
        assert all("'v'" not in texts[f"{block}.{impl}.pool"]
                   for impl in ("reference", "kernel"))
        return
    if cfg.kv_lora_rank:
        # the control: the block that HAS the fields shows every word, so
        # the search below finds what it looks for
        missing = [w for w in ONLY_LATENT + ONLY_SHARED
                   if w not in everything]
        assert not missing, f"the latent block's texts lack {missing}"
        assert not [w for w in ONLY_CONV + ONLY_SSM + ONLY_DECAY
                    + ONLY_RETENTION + ONLY_DELTA if w in everything]
        assert all("'v'" not in texts[f"{block}.{impl}.pool"]
                   for impl in ("reference", "kernel"))
        return
    sambay = "mamba1" in cfg.layer_types
    absent = ONLY_LATENT + ONLY_DELTA \
        + (() if cfg.shared_ffn_dim else ONLY_SHARED) \
        + (() if cfg.gated_block else ONLY_GATED) \
        + (() if "conv" in cfg.layer_types else ONLY_CONV) \
        + (() if "mamba" in cfg.layer_types else ONLY_SSM) \
        + (() if sambay else ONLY_SAMBAY) \
        + (() if cfg.ut_steps > 1 else ONLY_LOOPED) \
        + (() if sambay or "mamba" in cfg.layer_types else ONLY_DECAY) \
        + (() if "retention" in cfg.layer_types else ONLY_RETENTION) \
        + (() if "sliding_attention" in cfg.layer_types else ONLY_WINDOW)
    for kind, words in (("mamba", ONLY_SSM + ONLY_DECAY),
                        ("retention", ONLY_RETENTION),
                        ("sliding_attention", ONLY_WINDOW)):
        if kind in cfg.layer_types:
            # the control for the block's own words (a sink is MiMo's)
            missing = [w for w in words if w not in everything
                       and (cfg.attn_sink or w != "'sink'")]
            assert not missing, f"the {kind} block's texts lack {missing}"
    if cfg.ut_steps > 1:
        # the looped stack's own words, and of a gated block's the norms
        # after a branch alone (no output gate, no shared expert); one scan
        # more than the walk's in each program: the passes
        missing = [w for w in ONLY_LOOPED + ONLY_GATED[2:]
                   if w not in everything]
        assert not missing, f"the looped block's texts lack {missing}"
        assert not [w for w in ONLY_GATED[:2] + ONLY_SHARED
                    if w in everything]
        found = traced(block)
        assert [loops(found[f"{block}.{impl}.{program}"])["scan"]
                for impl in ("reference", "kernel")
                for program in ("step", "loop")] == [2, 3, 2, 3]
    elif cfg.gated_block:
        missing = [w for w in ONLY_GATED + ONLY_SHARED
                   if w not in everything]
        assert not missing, f"the gated block's texts lack {missing}"
    if sambay:
        missing = [w for w in ONLY_SAMBAY if w not in everything]
        assert not missing, f"the hybrid decoder's texts lack {missing}"
    for name, text in texts.items():
        found = [word for word in absent if word in text]
        assert not found, f"{name} holds {found}"
        if name.endswith((".step", ".loop")):
            assert (SCOPE_HEAD in text) == cfg.gated_block, name
    for impl in ("reference", "kernel"):
        if "sliding_attention" in cfg.layer_types:
            # two widths and two groups: held to their shapes in
            # tests/test_llm_mimo.py
            continue
        kv = jax.eval_shape(lambda: make_kv_cache(
            cfg, 16, 8, max_batch=3, lane_pad=impl == "kernel"))
        assert kv["k"].shape == kv["v"].shape
        assert kv["k"].shape[2] == cfg.n_kv_heads
        # a layer for each attention layer (a plane a pass and layer where
        # they run several times): none where every layer is a retention
        # layer
        assert kv["k"].shape[0] == cfg.ut_steps * len(
            cfg.layers_of("full_attention")) == C.page_planes(cfg)


#: (leading layers, one period, periods) of each block: what the one walk
#: runs; the Llama tree's two blocks are patterns of period 1
PATTERNS = {
    "mistral": ([], [("full_attention", "dense")], 2),
    "olmoe": ([], [("full_attention", "moe")], 2),
    "lfm2": ([("conv", "dense")] * 2,
             [("full_attention", "moe")] + [("conv", "moe")] * 3, 2),
    "kanana": ([("full_attention", "dense")], [("full_attention", "moe")],
               2),
    "granite": ([], [("mamba", "dense"), ("mamba", "dense"),
                     ("full_attention", "dense"), ("mamba", "dense")], 2),
    "brumby": ([], [("retention", "dense")], 2),
    "mimo": ([("full_attention", "dense")],
             [("sliding_attention", "moe"), ("full_attention", "moe")], 2),
    "trinity": ([("sliding_attention", "dense")],
                [("sliding_attention", "moe")] * 3
                + [("full_attention", "moe")], 1),
    "gigachat": ([("linear_attention", "dense")],
                 [("linear_attention", "moe")] * 3
                 + [("full_attention", "moe")], 1),
    # a segment starts at the tail (model.py: tail_start), and the six
    # layers before it are cheaper as one period run once than as segments
    # (_SEGMENT_COST); the published depth is three segments, a scan each
    # (tests/test_llm_phi4flash.py)
    "phi4flash": ([], [("mamba1", "dense"), ("sliding_attention", "dense")]
                  * 2 + [("mamba1", "dense"), ("full_attention", "dense")], 1,
                  [("gmu", "dense"), ("cross_attention", "dense")], 1),
    # the pattern of ONE pass: the passes are a scan around the walk
    "ouro": ([], [("full_attention", "dense")], 2)}


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_every_block_is_a_pattern_of_the_one_walk(block):
    cfg = config(block)
    assert M._pattern(cfg) == PATTERNS[block]
    # the walk finds a stack for every kind the pattern names, the Llama
    # tree's by the split of its flat leaves: the same arrays
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    stacks = M._stacks(params["layers"], cfg)
    lead, *segments = PATTERNS[block]
    walked = lead + sum(segments[::2], [])
    assert set(stacks) == {M.OPERATORS[op][0] for op, _ in walked} \
        | {ffn for _, ffn in walked}
    assert sorted(map(id, jax.tree.leaves(stacks))) \
        == sorted(map(id, jax.tree.leaves(params["layers"])))


#: the published decoder-hybrid-decoder's depth, on the row's tiny widths
PUBLISHED_DEPTH = dict(n_layers=32, layer_types=(
    ["mamba1", "sliding_attention"] * 8 + ["mamba1", "full_attention"]
    + ["gmu", "cross_attention"] * 7))
#: the layers of each block's TAIL (model.py: tail_start), which keep
#: nothing: every token of a row but its last leaves the walk before them
TAILS = {**dict.fromkeys(BLOCKS, ()),
         "phi4flash": ("gmu", "cross_attention"),
         "phi4flash at the published depth": ("gmu", "cross_attention") * 7}


@pytest.mark.parametrize("case", sorted(TAILS))
def test_the_tail_is_the_layers_that_keep_nothing(case):
    """Empty for every other block (their walk is cut nowhere), the
    cross-decoder for this one (layers 18-31 of the published 32), read
    from the two tables: no page group's kind, no leaf of SLOT_STATE, a dense
    feed-forward. The pattern starts a segment there."""
    block, _, deep = case.partition(" at ")
    cfg = config(block, **(PUBLISHED_DEPTH if deep else {}))
    kinds = cfg.layer_types or ("full_attention",) * cfg.n_layers
    at = M.tail_start(cfg)
    assert tuple(kinds[at:]) == TAILS[case]
    assert at == (18 if deep else cfg.n_layers - len(TAILS[case]))
    assert all(C.keeps_nothing(k) for k in kinds[at:])
    assert at == 0 or not C.keeps_nothing(kinds[at - 1]) or cfg.n_experts
    lead, *segments = M._pattern(cfg)
    starts = [len(lead)]
    for period, n in zip(segments[::2], segments[1::2]):
        starts.append(starts[-1] + len(period) * n)
    assert at in starts
    # a one-token row is its own last token: the decode loop is cut nowhere
    assert not M.leaves_early(cfg, 1)
    assert M.leaves_early(cfg, 8) == bool(TAILS[case])


def test_every_kind_of_layer_is_declared_once_in_each_table():
    """A kind of layer is a body and a stack in llm/model.py's table and
    its state a slot in llm/cache.py's: nothing else lists the kinds."""
    assert set(M.OPERATORS) == set(C.SLOT_STATE) == set(LAYER_KINDS)
    for kind, (stack, body) in M.OPERATORS.items():
        assert isinstance(stack, str) and callable(body), kind
    assert C.STATE_LEAVES == ("conv", "ssm", "ssm_conv", "retention",
                              "retention_norm", "delta", "delta_conv",
                              "ssm1", "ssm1_conv")


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_pool_descriptor_and_engine_follow_the_declared_state(block):
    """The pool is the page leaves and exactly the leaves SLOT_STATE
    declares for the kinds the block has, in their shapes and dtypes; one
    predicate says whether there are any, and the prefix cache, the mixed
    step's descriptor and the engine's stats all follow it."""
    from ray_tpu.llm import InferenceEngine
    cfg = config(block)
    kinds = set(cfg.layer_types) or {"full_attention"}
    declared = {
        leaf: ((len(cfg.layers_of(kind)), 3 + 1) + of(cfg)[0],
               jnp.dtype(of(cfg)[1]))
        for kind in kinds for leaf, of in C.SLOT_STATE[kind].items()}
    assert C.slot_state_kinds(cfg) == tuple(
        k for k in C.SLOT_STATE if k in kinds and C.SLOT_STATE[k])
    has_state = C.keeps_slot_state(cfg)
    windowed = "sliding_attention" in kinds
    assert has_state == bool(declared)
    # pages are the only state, and none of them frees behind a window
    assert C.prefix_cache_supported(cfg) == (not has_state and not windowed)
    kv = jax.eval_shape(lambda: make_kv_cache(
        cfg, 16, 8, max_batch=3, **(dict(window_pages=9) if windowed
                                    else {})))
    pages = {"k"} if cfg.kv_lora_rank else {"k", "v"} \
        | (set(C.WINDOW_LEAVES) if windowed else set())
    assert set(kv) == pages | set(declared)
    assert {leaf: (kv[leaf].shape, kv[leaf].dtype) for leaf in declared} \
        == declared
    if has_state:
        with pytest.raises(ValueError, match="needs max_batch"):
            make_kv_cache(cfg, 16, 8)
    layout = dict(M.step_layout(3, 2, 8, 4, has_state))
    fns = M.StepPrograms(cfg, decode_chunk=4, max_q_len=8, decode_rows=3,
                         max_pages=4, kv_quantized=False, prefill_rows=2,
                         page_size=8)
    assert fns.step_layouts[2] == M.step_layout(
        3, 2, 8, 4, has_state, fns.window_pages["step"])
    assert bool(fns.window_pages["step"]) == windowed \
        == ("page_table_win" in dict(fns.decode_layout))
    assert ("token_state" in layout) == has_state
    eng = InferenceEngine(cfg, page_size=8, total_pages=16, max_batch=3,
                          max_seq_len=32, prefill_chunk=8, prefill_rows=2,
                          decode_chunk=4, prefix_cache=True)
    assert ("state_bytes" in eng.stats) == has_state
    assert ("page_steps_window" in eng.stats) == windowed \
        == (eng.window_allocator is not None)
    assert (eng.prefix is None) == (has_state or windowed)
    assert eng.device_report()["state_bytes"] == sum(
        a.nbytes for k, a in eng.kv.items() if k in declared)


def test_every_block_has_the_file_that_collects_its_engine_cases():
    """A new block is a row of BLOCKS, and a row's engine-level cases
    (tests/_block_cases.py) are collected under a file of their own, the
    unit a worker of the suite is handed: every row has its file, and no
    file names a row that is gone."""
    here = os.path.dirname(os.path.abspath(__file__))
    named = {}
    for path in glob.glob(os.path.join(here, "test_llm_block_*.py")):
        with open(path) as f:
            named[os.path.basename(path)] = re.findall(
                r'^BLOCK = "(\w+)"$', f.read(), re.M)
    want = {f"test_llm_block_{block}.py": [block] for block in BLOCKS}
    missing = sorted(b for b in BLOCKS
                     if f"test_llm_block_{b}.py" not in named)
    stray = sorted(f for f, rows in named.items() if rows != want.get(f))
    assert not missing and not stray, (
        f"rows of tests/_blocks.py:BLOCKS without their file: {missing}; "
        f"files that name no row, or not the row of their name: {stray}. "
        "A row's file is tests/test_llm_block_<row>.py and three lines (as "
        "tests/test_llm_block_mistral.py): a docstring, `from _block_cases "
        'import *  # noqa: F401,F403` and `BLOCK = "<row>"`; a row that '
        "is gone takes its file with it. A new row also brings its entry "
        "of PATTERNS above (what the one walk runs for it), the words "
        "only it may bring into a text (an ONLY_* tuple, as ONLY_LOOPED "
        "is the looped stack's) and its plain reference under benchmark/ "
        "(reference_<block>.py: dims_of, forward_logits, score_greedy).")


if __name__ == "__main__":
    print(json.dumps({"jax": jax.__version__, "root": ROOT}))
    for block in sorted(BLOCKS):
        try:
            found = traced(block)
        except ValueError as e:      # a checkout from before the block
            print(block, "not built here:", str(e)[:60])
            continue
        if block == "phi4flash":
            # the depth the cell serves: its segments are the published
            # ones, where the row's eight layers are cut to fewer
            found.update({name.replace(block, block + "@32", 1): t
                          for name, t in traced(
                              block, **PUBLISHED_DEPTH).items()})
        for name, t in found.items():
            print(name, hashlib.sha256(_text(t).encode()).hexdigest()[:16],
                  *(() if isinstance(t, str) or name.endswith(".copy")
                    else (f"{k}={n}" for k, n in loops(t).items())))
