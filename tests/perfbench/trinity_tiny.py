"""The window-and-full cell's stand-in for the rehearsal checkout, as data:
a tiny configuration with the published keys that keeps the shape of the
thing (a leading dense sliding layer, then sliding, sliding, full, sliding;
a window of 8 positions with a slack of 4, so a lane's ring wraps several
times inside a request; 16 experts scored, 4 held, 2 a token, a shared
expert, a bias that changes choices), a tiny mix of the
``serve_resident_decoded`` kind, and the cell that joins them.
``tests/conftest.py`` registers them in ``rehearsal.py``'s tables, so that
no file that was there is edited; ``test_perfbench_trinity.py`` and
``tests/unit/test_afmoe.py`` import the same names."""

TINY_TRINITY = {
    "name": "tiny-trinity", "source": "test",
    "builders": {"serve": "afmoe_serve"},
    "global_attn_every_n_layers": 4, "head_dim": 8, "hidden_act": "silu",
    "hidden_size": 32, "intermediate_size": 48,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention",
                    "sliding_attention"],
    "load_balance_coeff": 5e-05, "max_position_embeddings": 64,
    "model_type": "afmoe", "moe_intermediate_size": 16,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 4,
    "num_dense_layers": 1, "num_expert_groups": 1, "num_experts": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "sliding_window": 8,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 128,
    "model": {"family": "afmoe", "vocab_size": 128},
    "moe": {"routed_over": 16, "experts_held": [4, 4],
            "expert_bias_std": 0.1},
    "serve": {"dtype": "fp32", "param_dtype": "float32",
              "compute_dtype": "float32", "cache_positions": 64,
              "window_slack": 4,
              "serving": {"slots": 4, "prompt_bucket": 8},
              "load_batch": [2, 16],
              "first_token_tolerance": 0.01,
              "decode_check": {"mean_margin_max": 0.001,
                               "share_within_tolerance_min": 0.99,
                               "largest_margin_max": 0.01,
                               "mean_state_error_max": 1e-4,
                               "first_layer_head_state_error_max": 1e-4,
                               "mean_tail_error_max": 1e-4}},
    "reduced": []}
TINY_TRAFFIC = {"kind": "serve_resident_decoded", "clients": 4,
                "prompt_lengths": [9, 14, 19, 23], "output_tokens": 36,
                "ramp_tokens": 2, "prompt_bucket": 8, "max_positions": 64,
                "trace_seconds": 1, "reference_samples": 2}
TINY_CELL = {"name": "tiny-trinity-resident", "config": "tiny-trinity",
             "traffic": "tiny-resident-decoded", "chips": 1,
             "why": "rehearsal"}
# (the open cell above capacity runs the accepted open cell's stand-in)
STAND_IN = {"trinity-large-serve-resident-16k": "tiny-trinity-resident",
            "gpt-1.3b-serve-open-over": "tiny-serve-open"}
# the case of the contract test that holds every configuration to
# ``reduced == []``, expected to fail for one that lists its cut;
# ``test_reduced_is_exactly_what_differs_from_the_catalog`` of
# ``test_perfbench_trinity.py`` replaces it
PREDATES_REDUCED = \
    "test_configuration_entry_and_file[trinity-large-ep8-5layer]"


def register(rehearsal):
    rehearsal.CONFIGS.setdefault(TINY_TRINITY["name"], TINY_TRINITY)
    rehearsal.TRAFFIC.setdefault(TINY_CELL["traffic"], TINY_TRAFFIC)
    if TINY_CELL not in rehearsal.CELLS:
        rehearsal.CELLS.append(TINY_CELL)
    rehearsal.STAND_IN.update(STAND_IN)
