"""The probed replica for the GigaChat3.5 block: replica.py's probes
unchanged, with the reference check bound to that block's plain reference
(reference_gigachat.py: whole sequences, token after token through the
recurrence, no page, no state leaf) instead of the Llama/Mistral one; and,
beside the served tokens' gaps, the program's recurrence against the
reference's ON THE SAME INPUTS (``served_state``): the one reading of a run
that tells a recurrent state held in float32 from one held in bf16."""

from __future__ import annotations

import functools
from typing import Any, Dict

from benchmark.replica import ProbedLLMServer


@functools.lru_cache(maxsize=None)
def _recurrence_programs(cfg, row: int, impl):
    """(a chunk row of ``row`` tokens from ``lo``, of which ``n`` count;
    the one token ``t``), both on slot 0 of the first delta layer, the
    state donated: ops/delta.py's two entry points, as
    llm/model.py:_delta binds them."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.ops import delta
    zero = jnp.zeros((1,), jnp.int32)

    @functools.partial(jax.jit, donate_argnums=0)
    def chunk_row(state, inputs, lo, n):
        return delta.delta_chunk_scan(
            state, *(lax.dynamic_slice_in_dim(a, lo, row) for a in inputs),
            lo + jnp.arange(row, dtype=jnp.int32), zero, n[None], zero,
            layer=0, chunk=cfg.delta_chunk, impl=impl)[1]

    @functools.partial(jax.jit, donate_argnums=0)
    def one_token(state, inputs, t):
        return delta.delta_decode_update(
            state, *(lax.dynamic_slice_in_dim(a, t, 1) for a in inputs),
            zero, (t == 0)[None], layer=0, impl=impl)[1]

    return chunk_row, one_token


def served_state(cfg, inputs, n_prompt: int, n_total: int, row: int, impl):
    """The first delta layer's state after ``n_total`` tokens as the PROGRAM
    computes it: a state leaf made as the pool makes it (make_kv_cache, so
    in the dtype llm/cache.py:SLOT_STATE says), the first ``n_prompt``
    tokens through the chunk form in chunk rows of ``row`` tokens, each from
    its slot's state as a prompt over several steps is served, the rest
    through the update kernel a token a step (``impl`` is the engine's).
    ``inputs`` are reference_gigachat.first_layer_state's, the same arrays
    the reference's recurrence took. Returns [Hv, dk, dv]."""
    import jax.numpy as jnp

    from ray_tpu.llm.cache import DELTA_LEAF, make_kv_cache
    if -(-n_prompt // row) * row > inputs[0].shape[0]:
        raise ValueError(f"{n_prompt} tokens in rows of {row} pass the "
                         f"{inputs[0].shape[0]} the inputs hold")
    chunk_row, one_token = _recurrence_programs(cfg, row, impl)
    state = make_kv_cache(cfg, 2, 8, max_batch=1)[DELTA_LEAF]
    for lo in range(0, n_prompt, row):
        state = chunk_row(state, inputs, jnp.int32(lo),
                          jnp.int32(min(row, n_prompt - lo)))
    for t in range(n_prompt, n_total):
        state = one_token(state, inputs, jnp.int32(t))
    return state[0, 0]


class ProbedGigaChatServer(ProbedLLMServer):

    def bench_reference_check(self, request: Dict[str, Any]
                              ) -> Dict[str, Any]:
        import numpy as np

        from benchmark import checks_gigachat, reference_gigachat
        eng = self.engine
        dims = reference_gigachat.dims_of(eng.cfg)
        prompt, served = list(request["prompt_ids"]), list(
            request["token_ids"])
        out = reference_gigachat.score_greedy(
            eng.params, dims, prompt, served, int(request["pad_to"]))
        # the recurrence alone, over the head of the prompt and of what was
        # served after it: one padded length, so one program
        n_p, n_g = checks_gigachat.STATE_TOKENS
        toks = np.zeros((n_p + n_g,), np.int32)
        head = prompt[:n_p] + served[:n_g]
        toks[:len(head)] = head
        inputs, want = reference_gigachat.first_layer_state(
            eng.params, toks, len(head), dims)
        got = served_state(eng.cfg, inputs, min(len(prompt), n_p),
                           len(head), eng.prefill_chunk,
                           eng._fns.paged_impl)
        out["state_error"] = reference_gigachat.state_error(got, want)
        return out
