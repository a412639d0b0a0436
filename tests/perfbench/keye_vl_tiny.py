"""The selected-attention cell's stand-in for the rehearsal checkout, as
data: a tiny configuration with the published keys (16 experts routed
over, 4 held; an indexer that chooses 8 positions), a tiny mix of the
``serve_resident`` kind whose contexts run several times over ``topk``,
and the cell that joins them. ``tests/conftest.py`` registers them in
``rehearsal.py``'s tables, so that no file that was there is edited; the
tests of ``test_perfbench_keye_vl.py`` and ``tests/unit/test_keye_vl.py``
import the same names."""

TINY_KEYE = {
    "name": "tiny-keye-vl", "source": "test",
    "builders": {"serve": "keye_vl_serve"},
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 48,
    "max_position_embeddings": 64, "max_window_layers": 2,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 16, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 2, "num_key_value_heads": 2,
    "num_local_experts": 16, "rms_norm_eps": 1e-6,
    "rope_scaling": {"mrope_section": [4, 2, 2], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 3,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                  "q_chunk_size": 8, "topk": 8},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 128,
    "model": {"family": "keye_vl", "vocab_size": 128},
    "moe": {"routed_over": 16, "experts_held": [4, 4]},
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
                               "mean_selection_miss_max": 0.01,
                               "mean_choice_miss_max": 0.01}},
    "reduced": []}
TINY_RESIDENT = {"kind": "serve_resident", "clients": 4,
                 "prompt_lengths": [9, 14, 20, 30], "output_tokens": 32,
                 "ramp_tokens": 2, "prompt_bucket": 16, "max_positions": 64,
                 "trace_seconds": 1, "reference_samples": 2}
TINY_CELL = {"name": "tiny-keye-vl-serve", "config": "tiny-keye-vl",
             "traffic": "tiny-resident", "chips": 1, "why": "rehearsal"}
STAND_IN = {"keye-vl-2.0-serve-resident-16k": "tiny-keye-vl-serve"}
# the case of the contract test that holds every configuration to
# ``reduced == []``, expected to fail for one that lists its cut;
# ``test_reduced_is_exactly_what_differs_from_the_catalog`` of
# ``test_perfbench_keye_vl.py`` replaces it
PREDATES_REDUCED = "test_configuration_entry_and_file[keye-vl-2.0-ep8-6layer]"


def register(rehearsal):
    rehearsal.CONFIGS.setdefault(TINY_KEYE["name"], TINY_KEYE)
    rehearsal.TRAFFIC.setdefault("tiny-resident", TINY_RESIDENT)
    if TINY_CELL not in rehearsal.CELLS:
        rehearsal.CELLS.append(TINY_CELL)
    rehearsal.STAND_IN.update(STAND_IN)
