"""Test env: force JAX onto a virtual 8-device CPU mesh BEFORE backends init.

Multi-chip sharding is validated on virtual CPU devices; nothing in tests/
touches a real TPU (``tests/test_tpu_compile.py`` compiles for a described
one, with no chip attached).
"""

import os

os.environ.setdefault("TPUMESOS_LOGLEVEL", "WARNING")

from tfmesos_tpu.utils.platform import (enable_compile_cache,  # noqa: E402
                                        force_platform)

force_platform("cpu", min_host_devices=8)
# The CPU is only the vehicle for correctness at tiny shapes, and most of a
# cold run is XLA's CPU compile time: compile at the backend's lowest
# optimisation level (measured here: 287 s -> 214 s for test_models +
# test_fleet on a cold cache).  Tasks and replicas the tests launch inherit
# the flag, so a stream is compared with one compiled the same way.
os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"

# The suite compiles thousands of tiny XLA programs in ONE pytest
# process, and every loaded executable costs ~3-4 kernel memory maps that
# jax's in-memory caches keep alive forever.  At vm.max_map_count's
# default 65530 the process hits the ceiling a few thousand executables
# in, and the next native mmap fails as a SIGSEGV in whatever
# compile/deserialize happens to run — observed as rc=139 at a
# DETERMINISTIC test deep in the full run (while any subset passes).
# Two-part fix:
#   1. the program's persistent compilation cache, so recompiles are cheap
#      deserializes (and reruns skip native compilation entirely);
#   2. jax.clear_caches() after every test module, releasing each
#      module's executables (and their maps) — the disk cache makes the
#      cross-module recompiles it causes nearly free.
import gc  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

enable_compile_cache()


@pytest.fixture(autouse=True, scope="module")
def _bound_executable_maps():
    yield
    jax.clear_caches()
    gc.collect()


# Heavyweight multi-chip tests pushed out of the tier-1 budget: they
# compile real multi-device executables, minutes of XLA time on a cold
# cache.  They run only outside `-m 'not slow'`; representative mesh
# coverage stays in tier-1 (mesh serving/batcher tests, sharded decode
# kernels, checkpoint mesh restore, fused-ce dp/tp variants, moe ep
# shards).
_HEAVY_MULTICHIP = {
    "test_transformer_train_step_1f1b_moe_matches_gpipe",
    "test_transformer_train_step_1f1b_matches_loss_fn",
    "test_transformer_train_step_1f1b_interleaved",
    "test_pipeline_sp_stages_match_reference",
    "test_ring_attention_window_flash_inner",
    "test_ring_attention_flash_impl_matches_reference",
    "test_ring_attention_gradients_match",
    "test_ring_attention_window_gradients_match",
    "test_ulysses_gradients_match",
    "test_attend_window_sp_composition",
    "test_dryrun_multichip_in_process",
    "test_dryrun_multichip_reexecs_when_backend_pinned",
    "test_tfrun_runs_transformer_trainer_on_mesh",
    "test_vocab_parallel_ce_through_trainer_machinery",
    "test_mode_a_distributed_worker_only_dp_mesh",
    "test_mode_a_distributed_jax_sharded_sum",
    "test_cross_process_multiaxis_meshes",
    "test_cross_process_continuous_batching",
    "test_end_to_end_kill_restart_resume",
    "test_transformer_moe_pp_trains_with_aux_loss",
    "test_transformer_moe_pp_tp_ep_trains",
    "test_transformer_switch_moe_on_ep_mesh",
    "test_shared_experts_switch_and_pp",
    "test_load_balance_loss_trains_router_to_balance",
    # Parametrized duplicates: one representative of each family stays
    # in tier-1, the sibling axes/sizes run with the slow suite.
    "test_pipeline_1f1b_matches_sequential[4-2-8]",
    "test_pipeline_1f1b_matches_sequential[8-1-4]",
    "test_pipeline_circular_matches_sequential[2-8]",
    "test_pipeline_circular_matches_sequential[4-4]",
    "test_pipeline_1f1b_interleaved_matches_sequential[2-4-8-1]",
    "test_pipeline_1f1b_interleaved_matches_sequential[4-2-8-1]",
    "test_pipeline_with_aux_matches_sequential",
    "test_ring_attention_sliding_window_matches_reference[1]",
    "test_ring_attention_sliding_window_matches_reference[7]",
    "test_ring_attention_sliding_window_matches_reference[40]",
    "test_ulysses_gqa_matches_reference[4]",
    "test_transformer_gqa_ulysses_sp_mesh_matches_single_device",
    "test_transformer_moe_switch_pp_tp",
    "test_transformer_moe_switch_pp_ep",
    "test_transformer_moe_shared_experts_pp_tp",
    "test_transformer_moe_pp_tp_matches_sequential",
    "test_transformer_moe_pp_ep_matches_pp",
    "test_transformer_pp_tp_dp_matches_sequential",
    "test_transformer_pp_circular_schedule",
    "test_vocab_parallel_ce_matches_reference[axes1]",
    "test_vocab_parallel_ce_matches_reference[axes2]",
    "test_vocab_parallel_ce_inbody_matches_reference[0.001]",
    "test_sharded_matches_reference_pure_ep[4]",
    "test_sharded_matches_reference_pure_ep[8]",
    "test_topk_sharded_matches_reference[4]",
    # The two heaviest single-device tests (20s+ each on this host) —
    # full-suite only, pure tier-1 budget headroom.
    "test_inception_tiny_forward_and_train",
    "test_window_validation",
    # More mesh-compile budget headroom (all were trace-time failures
    # before the shim; siblings of each stay in tier-1).
    "test_restore_onto_resized_mesh",
    "test_sharded_flash_decode_matches_einsum[True]",
    "test_sharded_prefill_kernel_matches_einsum",
    "test_gqa_trains_on_sp_mesh",
    "test_transformer_sp_mesh_matches_single_device",
    "test_dp_fused_ce_matches_reference[axes1]",
    "test_loss_fn_tp_mesh_matches_single_device",
    "test_sharded_dp_ep_matches_per_shard_reference",
    # Budget headroom for the fleet-autoscaler e2e pair (PR 6): the
    # heaviest sibling-covered variants move to the full suite — one
    # representative of each family ([False] serve example, the other
    # mesh/pipelined/multistep batcher axes, the remaining moe
    # shared-expert/aux tests) stays
    # in tier-1.
    "test_serve_example_end_to_end[True]",
    "test_shared_experts_add_dense_ffn",
    "test_mesh_batcher_token_identical[axes2-spec_chunk_prefix]",
    "test_switch_moe_topk_aux_metrics_in_loss",
    "test_multistep_batcher_token_identical[2-pipelined_mesh]",
    "test_staggered_stream_matches_offline",
    "test_speculative_batcher_sampled_invariance_and_prefix_equality",
    "test_shared_prefix_matches_generate[21]",
    "test_accept_rejection_budget_exhausts_into_fatal",
    "test_speculative_int8_cache_exactness",
    # Budget headroom for the preempt/resume matrix + migration tests
    # (PR 7): sibling-covered parametrized duplicates move to the full
    # suite — the k=2 multistep variants (plus [4-base]) keep every
    # axis in tier-1, pipelined/mesh/spec families each keep
    # representatives of the moved variants' axes.
    "test_multistep_batcher_token_identical[4-staggered]",
    "test_multistep_batcher_token_identical[4-stop]",
    "test_multistep_batcher_token_identical[4-sampled]",
    "test_multistep_batcher_token_identical[4-prefix]",
    "test_multistep_batcher_token_identical[4-mesh]",
    "test_multistep_batcher_token_identical[4-pipelined_stop]",
    "test_multistep_batcher_token_identical[4-pipelined_mesh]",
    "test_mesh_batcher_token_identical[axes1-base]",
    "test_speculative_batcher_with_shared_prefix[13]",
    "test_speculative_batcher_with_shared_prefix[21]",
    "test_speculative_with_chunked_prefill[True]",
    "test_warmup_outputs_bit_identical[pcache]",
    "test_shared_prefix_matches_generate[11]",
    "test_prefix_cache_composes_with_global_prefix[11]",
    "test_mesh_batcher_token_identical[axes3-sampled]",
    # Budget headroom offsetting PR 8's new containment/deadline tests
    # (all tier-1): sibling-covered preempt-matrix variants move to the
    # full suite — greedy + sampled keep the resume-stream contract in
    # tier-1, and the int8/chunked/pcache axes stay covered by the
    # warmup/prefix/multistep families above; the second mesh prefix-
    # cache variant rides along.
    "test_preempt_resume_token_identical[int8]",
    "test_preempt_resume_token_identical[chunked]",
    "test_preempt_resume_token_identical[pcache]",
    "test_prefix_cache_with_mesh[axes1]",
    # Budget headroom for the paged-kernel restructure matrix (PR 17):
    # the heaviest interpret-mode cells (big page / int8 fused) move to
    # the full suite; every axis — head-blocked kv, q_per_kv, int8,
    # fused multi-row K — keeps a tier-1 representative.
    "test_flash_decode_paged_equivalence_matrix[128-2-2-False-8-None]",
    "test_flash_decode_paged_equivalence_matrix[16-2-2-True-8-None]",
    "test_flash_decode_paged_equivalence_matrix[32-4-2-True-4-None]",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.originalname in _HEAVY_MULTICHIP or \
                item.name in _HEAVY_MULTICHIP:
            item.add_marker(pytest.mark.slow)
