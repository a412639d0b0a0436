"""The mixed-kinds cell's stand-in for the rehearsal checkout, as data: a
tiny configuration with the published keys that keeps the shape of the
thing (two leading dense layers, the pattern ``c c A c c c A c``, 8 experts
of which 4 a token, a bias that changes choices), the tiny closed mix of
the ``serve_closed_decoded`` kind that the retention cell's stand-in
brought, and the cell that joins them. ``tests/conftest.py`` registers
them in ``rehearsal.py``'s tables, so that no file that was there is
edited; the tests of ``test_perfbench_lfm2.py`` and
``tests/unit/test_lfm2.py`` import the same names."""
from brumby_tiny import TINY_CELL as _CLOSED_DECODED_CELL

TINY_LFM2 = {
    "name": "tiny-lfm2", "source": "test",
    "builders": {"serve": "lfm2_serve"},
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 32,
    "intermediate_size": 48,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv", "full_attention", "conv"],
    "max_position_embeddings": 64, "model_type": "lfm2_moe",
    "moe_intermediate_size": 16, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 4, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 128,
    "tie_word_embeddings": True,
    "model": {"family": "lfm2_moe", "vocab_size": 128},
    "moe": {"expert_bias_std": 0.1},
    "serve": {"dtype": "fp32", "param_dtype": "float32",
              "compute_dtype": "float32", "cache_positions": 64,
              "serving": {"slots": 4, "prompt_bucket": 16},
              "load_batch": [2, 16],
              "first_token_tolerance": 0.01,
              "decode_check": {"mean_margin_max": 0.001,
                               "share_within_tolerance_min": 0.99,
                               "largest_margin_max": 0.01,
                               "mean_state_error_max": 1e-4,
                               "first_layer_head_state_error_max": 1e-4,
                               "mean_tail_error_max": 1e-4,
                               "live_lanes": 2}},
    "reduced": []}
TINY_CELL = {"name": "tiny-lfm2-serve", "config": "tiny-lfm2",
             "traffic": _CLOSED_DECODED_CELL["traffic"], "chips": 1,
             "why": "rehearsal"}
STAND_IN = {"lfm2-8b-a1b-serve-closed-256": "tiny-lfm2-serve"}
# the case of the contract test that holds every configuration to
# ``reduced == []``, expected to fail for one that lists its cut;
# ``test_reduced_is_exactly_what_differs_from_the_catalog`` of
# ``test_perfbench_lfm2.py`` replaces it
PREDATES_REDUCED = "test_configuration_entry_and_file[lfm2-8b-a1b-12layer]"


def register(rehearsal):
    rehearsal.CONFIGS.setdefault(TINY_LFM2["name"], TINY_LFM2)
    if TINY_CELL not in rehearsal.CELLS:
        rehearsal.CELLS.append(TINY_CELL)
    rehearsal.STAND_IN.update(STAND_IN)
