"""The hybrid cell's stand-in for the rehearsal checkout, as data: a tiny
configuration with the published keys, a tiny closed mix of the
``serve_closed_decoded`` kind and the cell that joins them.
``tests/conftest.py`` registers them in ``rehearsal.py``'s tables (it is
loaded first, for any subset of the tests), so that no file that was there
is edited; the tests of ``test_perfbench_falcon_h1.py`` import the same
names."""

TINY_FALCON_H1 = {
    "name": "tiny-falcon-h1", "source": "test",
    "builders": {"serve": "falcon_h1_serve"},
    "attention_bias": False, "attention_in_multiplier": 1.0,
    "attention_out_multiplier": 0.5, "embedding_multiplier": 5.6,
    "head_dim": 8, "hidden_act": "silu", "hidden_size": 48,
    "intermediate_size": 80, "key_multiplier": 0.3,
    "lm_head_multiplier": 0.1, "mamba_chunk_size": 8,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 8,
    "mamba_d_ssm": 32, "mamba_d_state": 16, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 4,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "max_position_embeddings": 64,
    "mlp_bias": False, "mlp_multipliers": [0.4, 0.2],
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "projectors_bias": False, "rms_norm_eps": 1e-5,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.35, 0.25, 0.18, 0.5, 0.35],
    "ssm_out_multiplier": 0.5, "tie_word_embeddings": False,
    "vocab_size": 128,
    "model": {"family": "falcon_h1", "vocab_size": 128},
    "serve": {"dtype": "fp32", "param_dtype": "float32",
              "compute_dtype": "float32", "state_dtype": "float32",
              "use_flash_attention": False, "cache_positions": 64,
              "serving": {"slots": 4, "prompt_bucket": 16},
              "first_token_tolerance": 0.01,
              "decode_check": {"mean_margin_max": 0.001,
                               "share_within_tolerance_min": 0.99,
                               "largest_margin_max": 0.01,
                               "mean_state_error_max": 1e-4,
                               "first_layer_head_state_error_max": 1e-4,
                               "mean_tail_error_max": 1e-4,
                               "live_lanes": 2}},
    "reduced": []}
TINY_CLOSED_DECODED = {
    "kind": "serve_closed_decoded", "clients": 4,
    "prompt_lengths": [5, 9, 20, 30], "output_lengths": [3, 4, 5, 6],
    "prompt_bucket": 16, "max_positions": 64, "ramp_output_step": 1,
    "pregenerate_requests": 40, "trace_seconds": 1, "reference_samples": 2}
TINY_CELL = {"name": "tiny-falcon-h1-serve", "config": "tiny-falcon-h1",
             "traffic": "tiny-closed-decoded", "chips": 1,
             "why": "rehearsal"}
STAND_IN = {"falcon-h1-34b-serve-closed": "tiny-falcon-h1-serve"}
# the case of the contract test that holds every configuration to
# ``reduced == []`` (PERF.md, section 7), expected to fail for one that
# lists its cut; ``test_reduced_is_exactly_what_differs_from_the_catalog``
# replaces it
PREDATES_REDUCED = "test_configuration_entry_and_file[falcon-h1-34b-6layer]"

def register(rehearsal):
    rehearsal.CONFIGS.setdefault(TINY_FALCON_H1["name"], TINY_FALCON_H1)
    rehearsal.TRAFFIC.setdefault(TINY_CELL["traffic"], TINY_CLOSED_DECODED)
    if TINY_CELL not in rehearsal.CELLS:
        rehearsal.CELLS.append(TINY_CELL)
    rehearsal.STAND_IN.update(STAND_IN)
