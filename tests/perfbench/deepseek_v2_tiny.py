"""The latent-attention cell's stand-in for the rehearsal checkout, as
data: a tiny configuration with the published keys (8 groups of 2 experts,
one group held), the tiny closed mix of the ``serve_closed_decoded`` kind
that the retention cell's stand-in brought, and the cell that joins them.
``tests/conftest.py`` registers them in ``rehearsal.py``'s tables, so that
no file that was there is edited; the tests of
``test_perfbench_deepseek_v2.py`` and ``tests/unit/test_deepseek_v2.py``
import the same names."""
from brumby_tiny import TINY_CELL as _CLOSED_DECODED_CELL

TINY_DEEPSEEK = {
    "name": "tiny-deepseek-v2", "source": "test",
    "builders": {"serve": "deepseek_v2_serve"},
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 48,
    "kv_lora_rank": 16, "max_position_embeddings": 64,
    "model_type": "deepseek_v2", "moe_intermediate_size": 16,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 2,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts_per_tok": 6,
    "num_hidden_layers": 3, "num_key_value_heads": 4, "q_lora_rank": 24,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 3,
    "topk_method": "group_limited_greedy", "v_head_dim": 8,
    "vocab_size": 128,
    "model": {"family": "deepseek_v2", "vocab_size": 128},
    "moe": {"routed_over": 16, "experts_held": [2, 2]},
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
TINY_CELL = {"name": "tiny-deepseek-v2-serve", "config": "tiny-deepseek-v2",
             "traffic": _CLOSED_DECODED_CELL["traffic"], "chips": 1,
             "why": "rehearsal"}
STAND_IN = {"deepseek-v2-serve-closed-256": "tiny-deepseek-v2-serve"}
# the case of the contract test that holds every configuration to
# ``reduced == []``, expected to fail for one that lists its cut;
# ``test_reduced_is_exactly_what_differs_from_the_catalog`` of
# ``test_perfbench_deepseek_v2.py`` replaces it
PREDATES_REDUCED = "test_configuration_entry_and_file[deepseek-v2-ep8-5layer]"


def register(rehearsal):
    rehearsal.CONFIGS.setdefault(TINY_DEEPSEEK["name"], TINY_DEEPSEEK)
    if TINY_CELL not in rehearsal.CELLS:
        rehearsal.CELLS.append(TINY_CELL)
    rehearsal.STAND_IN.update(STAND_IN)
