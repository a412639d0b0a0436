"""The two-latent-kinds cell's stand-in for the rehearsal checkout, as data:
a tiny configuration with the published keys that keeps the shape of the
thing (a leading dense full layer, a full expert layer, then three sliding
ones; 4 heads over a 32-wide latent read through an indexer that chooses 8
rows, 2 heads of another width over a 48-wide latent that sees a window of
5 positions in a ring of 8, so a lane's ring wraps several times inside a
request and the selection bites from the ninth row on; 16 experts scored, 4
held, 2 a token, a shared expert, a bias that changes choices), a tiny mix
of the ``serve_resident_latent`` kind, and the cell that joins them.
``tests/conftest.py`` registers them in ``rehearsal.py``'s tables, so that
no file that was there is edited; ``test_perfbench_dots3.py`` and
``tests/unit/test_dots3.py`` import the same names."""

TINY_DOTS3 = {
    "name": "tiny-dots3", "source": "test",
    "builders": {"serve": "dots3_serve"},
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 64, "index_head_dim": 16,
    "index_n_heads": 2, "index_topk": 8, "intermediate_size": 96,
    "kv_lora_rank": 32,
    "layer_types": ["full_attention", "full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention"],
    "max_position_embeddings": 64, "model_type": "dots3_note",
    "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_routed_experts": 4,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 5,
    "num_key_value_heads": 4, "q_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 80000000, "routed_scaling_factor": 1,
    "scoring_func": "sigmoid", "sliding_window_size": 5,
    "swa_attention_gate_type": "headwise", "swa_kv_lora_rank": 48,
    "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2,
    "swa_q_lora_rank": 32, "swa_qk_nope_head_dim": 24,
    "swa_qk_rope_head_dim": 8, "swa_rope_theta": 50000, "swa_v_head_dim": 16,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 16, "vocab_size": 128,
    "model": {"family": "dots3", "vocab_size": 128},
    "moe": {"routed_over": 16, "experts_held": [4, 4],
            "expert_bias_std": 0.1},
    "serve": {"dtype": "fp32", "param_dtype": "float32",
              "compute_dtype": "float32", "cache_positions": 64,
              "window_slack": 3, "index_q_chunk": 4, "index_kv_chunk": 16,
              "serving": {"slots": 4, "prompt_bucket": 8},
              "load_batch": [2, 16],
              "first_token_tolerance": 0.01,
              "decode_check": {"mean_margin_max": 0.001,
                               "share_within_tolerance_min": 0.99,
                               "largest_margin_max": 0.01,
                               "mean_state_error_max": 1e-4,
                               "first_layer_head_state_error_max": 1e-4,
                               "mean_tail_error_max": 1e-4,
                               "mean_index_key_error_max": 1e-4,
                               "mean_selection_miss_max": 0.0,
                               "mean_choice_miss_max": 0.0,
                               "mean_step_row_error_max": 1e-4}},
    "reduced": []}
TINY_TRAFFIC = {"kind": "serve_resident_latent", "clients": 4,
                "prompt_lengths": [9, 14, 19, 23], "output_tokens": 36,
                "ramp_tokens": 2, "prompt_bucket": 8, "max_positions": 64,
                "trace_seconds": 1, "reference_samples": 2}
TINY_CELL = {"name": "tiny-dots3-resident", "config": "tiny-dots3",
             "traffic": "tiny-resident-latent", "chips": 1,
             "why": "rehearsal"}
STAND_IN = {"dots3-note-serve-resident-16k": "tiny-dots3-resident"}
# the case of the contract test that holds every configuration to
# ``reduced == []``, expected to fail for one that lists its cut;
# ``test_reduced_is_exactly_what_differs_from_the_catalog`` of
# ``test_perfbench_dots3.py`` replaces it
PREDATES_REDUCED = \
    "test_configuration_entry_and_file[dots3-note-ep8-5layer]"


def register(rehearsal):
    rehearsal.CONFIGS.setdefault(TINY_DOTS3["name"], TINY_DOTS3)
    rehearsal.TRAFFIC.setdefault(TINY_CELL["traffic"], TINY_TRAFFIC)
    if TINY_CELL not in rehearsal.CELLS:
        rehearsal.CELLS.append(TINY_CELL)
    rehearsal.STAND_IN.update(STAND_IN)
