"""Test bootstrap: force host-only JAX with an 8-device virtual CPU mesh so
multi-chip sharding (TP/DP) is exercised without TPU hardware — the same
"multi-node behavior without the hardware" strategy the reference uses with
fake OpenAI backends + envtest (SURVEY §4)."""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Also via jax.config, so unit tests always run on the 8-device virtual CPU
# mesh whatever the caller's environment says (TPU matmuls default to bf16
# precision, which would sink f32 parity tests).
import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# NOTE: do NOT enable jax's persistent compilation cache here. This image's
# XLA:CPU AOT executable reload is broken (machine-feature mismatch in the
# loader — "prefer-no-scatter is not supported on the host machine" →
# intermittent segfaults on cache READS, reproduced even with a fresh
# per-interpreter cache dir). So every run compiles cold, and a test's time IS
# its compiles: tests/engine_kit.py (the `kit` fixture below) keeps a module's
# engines by configuration so that a test pays for an engine only once a file.


# ---------------------------------------------------------------------------
# The tier the driver runs (/root/TESTS_LAST_RUN.json `commands`): `timeout
# 1470 ... python -m pytest tests/ -q -m 'not slow' -p xdist -n 6 --dist
# loadfile --junitxml=...`, passes counted from the junit file. Measured by the
# driver: 1,316 passed in 1,026 s at PR 43; after PR 44 (the kit): the
# builder's run of that command in its sandbox, 1,320 passed in 946 s (the
# ledger's `tests` has the driver's). The dominant cost is per-test XLA CPU
# compiles (the persistent cache is unusable here — see the note above), and
# `loadfile` keeps a file on one worker, so the longest file is the run's
# floor. The engine-compile-heavy and multi-process e2e tests listed here carry
# a `slow` marker and the default `-m "not slow"` (pytest.ini) skips them;
# NOTHING runs them today (`-m ""` would): ROADMAP D17. Node ids listed here
# (not decorated in-file) so the tier has ONE source of truth; the list was
# ranked from a --durations table of a tree thirteen PRs ago.
SLOW_TESTS = {
    "test_ring_attention.py::test_engine_e2e_on_sp_mesh",
    "test_engine.py::test_coarse_warmup_precompiles_dominating_lattice",
    "test_distributed.py::test_multiprocess_pd_dryrun_ships_kv_across_processes",
    "test_distributed.py::test_multiprocess_pd_dryrun_tp2_roles",
    "test_distributed.py::test_multiprocess_device_peer_dryrun_pulls_over_collectives",
    "test_spec_decode.py::test_spec_engine_matches_plain_greedy",
    "test_sharding.py::test_engine_e2e_on_pp_mesh",
    "test_sharding.py::test_qwen3_qk_norm_engine_tp2_matches_tp1",
    "test_disagg_prefill.py::test_streamed_pull_8k_prompt_overlaps_decode",
    "test_engine.py::test_compile_fallback_pads_up_to_warm_program",
    "test_pallas_attention.py::test_engine_chunked_prefill_pallas_backend_matches_xla",
    "test_moe.py::test_engine_e2e_mixtral_on_ep_mesh",
    "test_engine.py::test_warmup_compiles_bucket_set",
    "test_engine.py::test_long_context_prefill_through_flash_path",
    "test_kv_device_transfer.py::test_device_ship_bit_identical_continuation",
    "test_sharding.py::test_engine_e2e_on_dp_tp_mesh",
    "test_pallas_attention.py::test_pallas_fp8_pool_numerics",
    "test_quantization.py::test_quantized_with_lora_and_sleep_wake",
    "test_lora.py::test_adapter_generation_matches_merged_hf",
    "test_spec_decode.py::test_spec_mixed_sampling_batch",
    "test_spec_decode.py::test_spec_sole_request_near_pool_exhaustion_finishes",
    "test_disagg_prefill.py::test_export_import_makes_prompt_resident",
    "test_kv_remote.py::test_cross_engine_prefill_warms_from_remote",
    "test_kv_device_transfer.py::test_device_ship_under_tp2",
    "test_engine.py::test_midblock_chunked_prefill_matches_unchunked",
    "test_pallas_attention.py::test_engine_serves_pallas_under_tp2",
    "test_distributed.py::test_multiprocess_dryrun_two_processes",
    "test_disagg_prefill.py::test_pd_e2e_through_router",
    "test_quantization.py::test_engine_serves_quantized_and_rejects_unknown",
    "test_engine.py::test_prefix_cache_hits_across_requests",
    "test_kv_device_transfer.py::test_device_ship_guards",
    "test_rerank_score.py::test_score_one_vs_many_and_self_similarity",
    "test_engine_server.py::test_lora_endpoints_full_cycle",
    "test_stress.py::test_concurrent_streams_aborts_and_control_plane",
    "test_gemma.py::test_gemma_engine_generates",
    "test_engine.py::test_width_floor_blocks_config",
    "test_subprocess_e2e.py::test_session_stickiness_across_processes",
    "test_subprocess_e2e.py::test_roundrobin_distribution_across_processes",
    "test_subprocess_e2e.py::test_graceful_sigterm_shutdown",
    "test_fp8_kv.py::test_fp8_engine_end_to_end",
    "test_kv_offload.py::test_kv_controller_picks_longest_match_and_kvaware_routes_there",
    "test_kv_offload.py::test_offload_reload_roundtrip_preserves_outputs",
    "test_engine.py::test_request_outgrowing_pool_aborts_with_output",
    "test_logprobs.py::test_logprobs_with_sampling_and_no_logprobs_default",
    "test_kv_offload.py::test_host_tier_disabled_by_default",
    "test_benchmarks.py::test_sharegpt_mode_and_plot",
    "test_kv_offload.py::test_lookup_spans_tiers",
    "test_kv_offload.py::test_lora_requests_never_match_base_kv",
    "test_fp8_kv.py::test_fp8_pool_forward_close_to_exact",
    "test_spec_decode.py::test_spec_respects_max_tokens_and_stops",
    "test_rerank_score.py::test_rerank_validation",
    "test_rerank_score.py::test_score_elementwise_and_length_mismatch",
    "test_rerank_score.py::test_rerank_orders_by_relevance",
    "test_rerank_score.py::test_score_and_rerank_through_router",
    "test_engine_server.py::test_step_loop_recovers_from_transient_fault",
    "test_benchmarks.py::test_multi_round_qa_against_router",
    "test_model_numerics.py::test_chunked_prefill_matches_full_prefill",
    "test_checkpoint_loading.py::test_engine_serves_checkpoint_greedy_matches_hf",
    "test_checkpoint_loading.py::test_llama31_rope_scaling_checkpoint_end_to_end",
    "test_checkpoint_loading.py::test_qwen3_engine_greedy_matches_hf",
    "test_checkpoint_loading.py::test_mistral_sliding_window_checkpoint",
    "test_checkpoint_loading.py::test_gemma2_checkpoint_full_conventions",
    "test_checkpoint_loading.py::test_phi3_checkpoint_fused_weights_and_window",
    "test_checkpoint_loading.py::test_olmo2_checkpoint_post_norms_and_flat_qk",
    "test_moe.py::test_qwen3moe_checkpoint_parity",
    "test_engine_server.py::test_n_choices_stream_disconnect_aborts_all",
    "test_engine.py::test_greedy_batch_matches_solo",
    "test_engine.py::test_byte_tokenizer_text_roundtrip",
    "test_lora.py::test_unload_restores_base",
    "test_quantization.py::test_param_bytes_accounting",
    "test_logprobs.py::test_completions_logprobs_greedy",
}


@pytest.fixture(scope="module")
def kit():
    """tests/engine_kit.py: the module's engines by configuration."""
    from engine_kit import EngineKit

    built = EngineKit()
    yield built
    built.close()


def pytest_collection_modifyitems(config, items):
    import pytest as _pytest

    matched: set[str] = set()
    collected_files: set[str] = set()
    for item in items:
        rel = item.nodeid.split("tests/")[-1]
        collected_files.add(rel.split("::", 1)[0])
        # parametrized ids match their base test
        base = rel.split("[", 1)[0]
        if base in SLOW_TESTS:
            matched.add(base)
            item.add_marker(_pytest.mark.slow)
    # rot guard: an entry whose FILE was fully collected but whose test
    # wasn't means a rename/typo silently moved a compile-heavy test back
    # into the fast tier — fail loudly instead. Node-id-scoped or -k runs
    # legitimately collect partial files, so the guard only arms on plain
    # file/dir invocations.
    partial_selection = config.getoption("keyword", "") or any(
        "::" in a for a in config.invocation_params.args
    )
    if partial_selection:
        return
    stale = {
        t for t in SLOW_TESTS - matched
        if t.split("::", 1)[0] in collected_files
    }
    if stale:
        raise _pytest.UsageError(
            f"SLOW_TESTS entries match no collected test (renamed?): "
            f"{sorted(stale)}"
        )
