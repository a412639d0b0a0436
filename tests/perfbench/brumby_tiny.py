"""The attention-free cell's stand-in for the rehearsal checkout, as data:
a tiny configuration with the published keys, a tiny closed mix of the
``serve_closed_decoded`` kind and the cell that joins them.
``tests/conftest.py`` registers them in ``rehearsal.py``'s tables (it is
loaded first, for any subset of the tests), so that no file that was there
is edited; the tests of ``test_perfbench_brumby.py`` and
``tests/unit/test_brumby.py`` import the same names."""

TINY_BRUMBY = {
    "name": "tiny-brumby", "source": "test",
    "builders": {"serve": "brumby_serve"},
    "attention_bias": False, "head_dim": 8, "hidden_act": "silu",
    "hidden_size": 48, "intermediate_size": 80,
    "max_position_embeddings": 64, "max_window_layers": 2,
    "model_type": "brumby", "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 128,
    "model": {"family": "brumby", "vocab_size": 128},
    "retention": {"degree": 2, "eps": 1e-6},
    "serve": {"dtype": "fp32", "param_dtype": "float32",
              "compute_dtype": "float32", "state_dtype": "float32",
              "cache_positions": 64, "prefill_chunk": 8,
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
TINY_CELL = {"name": "tiny-brumby-serve", "config": "tiny-brumby",
             "traffic": "tiny-closed-decoded-4", "chips": 1,
             "why": "rehearsal"}
STAND_IN = {"brumby-14b-serve-closed": "tiny-brumby-serve"}
# the case of the contract test that holds every configuration to
# ``reduced == []`` (PERF.md, section 7), expected to fail for one that
# lists its cut; ``test_reduced_is_exactly_what_differs_from_the_catalog``
# of ``test_perfbench_brumby.py`` replaces it
PREDATES_REDUCED = "test_configuration_entry_and_file[brumby-14b-5layer]"


def register(rehearsal):
    rehearsal.CONFIGS.setdefault(TINY_BRUMBY["name"], TINY_BRUMBY)
    rehearsal.TRAFFIC.setdefault(TINY_CELL["traffic"], TINY_CLOSED_DECODED)
    if TINY_CELL not in rehearsal.CELLS:
        rehearsal.CELLS.append(TINY_CELL)
    rehearsal.STAND_IN.update(STAND_IN)
