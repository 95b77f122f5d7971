"""A tiny Solar-Open2-shaped configuration for the CPU tests and the rehearsal
of ``solar2.reason_batch``, and a harness that runs the PROGRAM's typed decode
path (prefill through pool and row state, then one-token steps beside idle
rows) for its logits.  Never a measurement."""

import copy

import numpy as np

#: 16 experts top-3, an expert 32 wide, 4 KDA heads of 16, chunks of 64 (the
#: adapter's), 4 query / 2 K/V heads of 16 over a hidden size of 48 (so the
#: head size is NOT hidden / heads): every width small, every mechanism there
TINY = {
    "model_type": "solar_open2", "hidden_size": 48,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "partial_rotary_factor": 1, "use_rope": False, "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "first_k_dense_replace": 0, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 3, "tie_word_embeddings": False,
    "torch_dtype": "float32", "driver": "serve", "model": "solar_kda",
    "correct": {"sample_requests": 8, "limits": {"max_gap": 1e-5}},
}
EXPERTS = 16
VOCAB = 512         # the "published" vocabulary the tiny one is a slice of


def tiny(kinds="akkk", held=4, shard=0, vocab=256, **deployment):
    """The tiny configuration with ``kinds`` as its layers (``k`` KDA,
    ``a`` attention), holding ``held`` of the 16 experts (share ``shard``)
    and rows ``0 .. vocab - 1`` of a vocabulary of 512."""
    model = copy.deepcopy(TINY)
    model.update(
        num_hidden_layers=len(kinds), vocab_size=vocab,
        gqa_layers=[i for i, c in enumerate(kinds) if c == "a"],
        n_routed_experts=held,
        published={"n_routed_experts": EXPERTS, "vocab_size": VOCAB},
        deployment={"chips": 1, "expert_parallel": EXPERTS // held,
                    "expert_shard": shard, "rows": 4, "max_len": 256,
                    "page_size": 16, "n_pages": 40, **deployment})
    return model


def program_logits(model, weights, prompt, new, *, row=2, rows=4, bucket=16,
                   page=16, n_pages=40, dirty=False, store=None):
    """Prefill ``prompt`` (padded to ``bucket``) into row slot ``row`` and
    decode ``new - 1`` greedy tokens beside idle rows, through the
    program's ``decode_step`` with a paged pool and a row-state store.
    Returns (logits [new, V] at the prompt's last position and after, the
    tokens, the store ``(pool, state)`` as left).  ``dirty`` fills pool and
    state with ones first: what a slot's last row may have left there;
    ``store`` continues from an earlier call's."""
    import jax
    import jax.numpy as jnp
    from benchmark.models import solar_kda as sk
    from tfmesos_tpu.models.transformer import (PageAllocator, decode_step,
                                                init_paged_cache,
                                                init_row_state)
    cfg = sk.program_config(model, 256)
    pool, state = store or (init_paged_cache(cfg, n_pages, page),
                            init_row_state(cfg, rows))
    if dirty:
        pool, state = jax.tree_util.tree_map(jnp.ones_like, (pool, state))
    alloc = PageAllocator(n_pages, page)
    sink = alloc.reserve_page()
    width = -(-len(prompt) // bucket) * bucket
    np_max = 256 // page
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
    from benchmark.models import solar_kda_reference as ref
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(toks[:-1], np.int32)])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
    return np.asarray(ref.logits_at(weights, model, seq, at))
