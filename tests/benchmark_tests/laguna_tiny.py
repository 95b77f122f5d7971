"""A tiny Laguna-shaped configuration for the CPU tests and the rehearsal of
``laguna.mixed_batch``, and a harness that runs the PROGRAM's typed decode
path (prefill through pool and rings, then one-token steps beside idle rows)
for its logits.  Never a measurement."""

import copy

import numpy as np

ROPE = {
    "full_attention": {"rope_theta": 500000, "rope_type": "yarn",
                       "factor": 64, "original_max_position_embeddings": 32,
                       "beta_slow": 1, "beta_fast": 4,
                       "attention_factor": 1.4158883083359672,
                       "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1},
}
#: hidden 64, heads of 16 (half of a full layer's rotated, under YaRN over an
#: "original" context of 32 so that the ramp falls inside the 4 pairs), 6
#: against 8 query heads over 2 K/V heads, a window of 8, a dense layer 96
#: wide, 16 experts top-4 of width 32 and a shared one: every width small,
#: every mechanism there
TINY = {
    "model_type": "laguna", "hidden_size": 64, "intermediate_size": 96,
    "num_key_value_heads": 2, "head_dim": 16, "num_attention_heads": 6,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "attention_bias": False,
    "num_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "tie_word_embeddings": False,
    "gating": True, "sliding_window": 8, "rope_parameters": ROPE,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "moe_routed_scaling_factor": 2.5, "torch_dtype": "float32",
    "driver": "serve", "model": "laguna",
    "correct": {"sample_requests": 32, "limits": {"max_gap": 2e-5}},
}
HEADS = {"F": 6, "S": 8}


def tiny(kinds="FSSSF", dense=1, **deployment):
    """The tiny configuration with ``kinds`` as its layers (``F`` full,
    ``S`` sliding-window attention), the first ``dense`` of them with a
    dense feed-forward."""
    model = copy.deepcopy(TINY)
    model.update(
        num_hidden_layers=len(kinds),
        layer_types=[{"F": "full_attention", "S": "sliding_attention"}[c]
                     for c in kinds],
        num_attention_heads_per_layer=[HEADS[c] for c in kinds],
        mlp_layer_types=["dense"] * dense + ["sparse"] * (len(kinds) - dense),
        deployment={"chips": 1, "rows": 4, "max_len": 128, "page_size": 8,
                    "n_pages": 72, **deployment})
    return model


def program_logits(model, weights, prompt, new, *, row=2, rows=4, bucket=8,
                   page=8, n_pages=72, dirty=False, store=None):
    """Prefill ``prompt`` (padded to ``bucket``) into row slot ``row`` and
    decode ``new - 1`` greedy tokens beside idle rows, through the
    program's ``decode_step`` with a paged pool and the rings.  Returns
    (logits [new, V] at the prompt's last position and after, the tokens,
    the store ``(pool, state)`` as left).  ``dirty`` fills pool and rings
    with ones first: what a slot's last row may have left there; ``store``
    continues from an earlier call's."""
    import jax
    import jax.numpy as jnp
    from benchmark.models import laguna
    from tfmesos_tpu.models.transformer import (PageAllocator, decode_step,
                                                init_paged_cache,
                                                init_row_state)
    cfg = laguna.program_config(model, 128)
    pool, state = store or (init_paged_cache(cfg, n_pages, page),
                            init_row_state(cfg, rows))
    if dirty:
        pool, state = jax.tree_util.tree_map(jnp.ones_like, (pool, state))
    alloc = PageAllocator(n_pages, page)
    sink = alloc.reserve_page()
    width = -(-len(prompt) // bucket) * bucket
    np_max = 128 // page
    alloc.ensure(row, width)
    padded = np.zeros((1, width), np.int32)
    padded[0, :len(prompt)] = prompt
    cache = dict(pool, state=state, slots=jnp.asarray([row], jnp.int32),
                 pages=alloc.table([row], width=np_max, fill=sink),
                 valid=jnp.asarray([len(prompt)], jnp.int32))
    logits, cache = jax.jit(
        lambda c, t: decode_step(cfg, weights, c, t, 0))(
            cache, jnp.asarray(padded))
    assert logits.shape[1] == 1         # the head ran at one position
    out, toks, pos = [np.asarray(logits[0, 0])], [], len(prompt)
    toks.append(int(np.argmax(out[-1])))
    step = jax.jit(lambda c, t, p: decode_step(cfg, weights, c, t, p))
    for _ in range(new - 1):
        alloc.ensure(row, pos + 1)
        tok = np.zeros((rows, 1), np.int32)
        at = np.zeros((rows,), np.int32)
        tok[row, 0], at[row] = toks[-1], pos
        cache = {"k": cache["k"], "v": cache["v"], "state": cache["state"],
                 "pages": alloc.table(range(rows), width=np_max, fill=sink)}
        logits, cache = step(cache, jnp.asarray(tok), jnp.asarray(at))
        out.append(np.asarray(logits[row, 0]))
        toks.append(int(np.argmax(out[-1])))
        pos += 1
    return np.stack(out), toks, ({"k": cache["k"], "v": cache["v"]},
                                 cache["state"])


def reference_logits(model, weights, prompt, toks):
    """The plain reference's logits at the same positions: the prompt and
    the served tokens but the last in one full forward."""
    from benchmark.models import laguna_reference as ref
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(toks[:-1], np.int32)])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
    return np.asarray(ref.logits_at(weights, model, seq, at))
