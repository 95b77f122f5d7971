"""A tiny MiMo-shaped configuration for the CPU tests and the rehearsal of
``mimo.agent_batch``, and a harness that runs the PROGRAM's typed decode
path (prefill through pool and rings, then one-token steps beside idle rows)
for its logits.  Never a measurement."""

import copy

import numpy as np

#: hidden 64, 8 query heads, keys of 24 and values of 16 channels (the first
#: int(24 x 0.334) = 8 of a key's rotate), 2 K/V heads in a full layer and 4
#: in a window layer, a window of 8 with a sink a head, the values scaled, a
#: dense layer 96 wide, 8 experts top-2 of width 32 of which 2 are held, no
#: shared expert: every width small, every mechanism there
TINY = {
    "model_type": "mimo_v2_flash", "hidden_size": 64,
    "intermediate_size": 96, "num_attention_heads": 8,
    "swa_num_attention_heads": 8, "num_key_value_heads": 2,
    "swa_num_key_value_heads": 4, "head_dim": 24, "swa_head_dim": 24,
    "v_head_dim": 16, "swa_v_head_dim": 16, "vocab_size": 256,
    "layernorm_epsilon": 1e-5, "attention_bias": False,
    "attention_value_scale": 0.707, "partial_rotary_factor": 0.334,
    "rope_theta": 5000000, "swa_rope_theta": 10000, "sliding_window": 8,
    "sliding_window_size": 8, "attention_chunk_size": 8,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "moe_intermediate_size": 32,
    "n_routed_experts": 2, "n_shared_experts": None,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "routed_scaling_factor": None,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "published": {"n_routed_experts": 8},
    "driver": "serve", "model": "mimo",
    "correct": {"sample_requests": 32, "limits": {"max_gap": 2e-5}},
}


def tiny(kinds="FSSSSFS", dense=1, held=2, shard=0, **deployment):
    """The tiny configuration with ``kinds`` as its layers (``F`` full,
    ``S`` sliding-window attention), the first ``dense`` of them with a
    dense feed-forward, ``held`` of the 8 experts held (share ``shard``)."""
    model = copy.deepcopy(TINY)
    model.update(
        num_hidden_layers=len(kinds), n_routed_experts=held,
        hybrid_layer_pattern=[{"F": 0, "S": 1}[c] for c in kinds],
        moe_layer_freq=[0] * dense + [1] * (len(kinds) - dense),
        deployment={"chips": 1, "rows": 4, "max_len": 128, "page_size": 8,
                    "n_pages": 72, "expert_shard": shard, **deployment})
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
    from benchmark.models import mimo
    from tfmesos_tpu.models.transformer import (PageAllocator, decode_step,
                                                init_paged_cache,
                                                init_row_state)
    cfg = mimo.program_config(model, 128)
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
    from benchmark.models import mimo_reference as ref
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(toks[:-1], np.int32)])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
    return np.asarray(ref.logits_at(weights, model, seq, at))
